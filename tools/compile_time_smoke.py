"""CI ``compile-time`` job: the ISSUE 9 compile/memory levers, gated.

Three checks:

1. **Bind-time regression gate (scan-over-layers)** — a deep (32-layer)
   transformer must bind + compile its first fused step inside a hard
   budget with scan ON, the plan must actually apply
   (``scan_applied``/``scan_layers``), and two scan-off comparisons
   hold: the deterministic one (the unrolled forward jaxpr carries >= 2x
   the equations of the scanned one at this depth — eqn count cannot be
   gamed by a fast box) and the wall-clock one (bind+first-step speedup
   >= 1.8x here; the >= 5x acceptance number is the deep regime, L=96+,
   measured out-of-band because a CI box should not burn 80s on the
   control arm's unrolled XLA compile... which is exactly the point).
2. **AOT warm-start smoke (MXNET_TPU_COMPILE_CACHE)** — process A
   trains 2 steps and must serialize the fused-step executable
   (``aot_store``); process B repeats the identical program and must
   deserialize it (``aot_hit``), record ZERO backend-compile phases for
   the ``fused_step`` scope in the obs compile accounting, and land
   bit-identical parameters.
3. **Zero-cost gate** — with all three knobs off
   (``MXNET_TPU_SCAN_LAYERS=off``, ``MXNET_TPU_REMAT=off``,
   ``MXNET_TPU_COMPILE_CACHE=``) a bind + fused step must import NONE of
   the new modules (scan / remat / aot / analysis) and bump none of
   their counters.

Exit code 0 = all gates passed.
"""
import json
import os
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BIND_BUDGET_SECS = float(os.environ.get("COMPILE_TIME_BIND_BUDGET", "90"))


def _env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def _run_child(code, **env):
    proc = subprocess.run([sys.executable, "-c", code], text=True,
                          capture_output=True, env=_env(**env),
                          timeout=600)
    if proc.returncode != 0:
        raise SystemExit("child failed (rc %d):\n%s\n%s"
                         % (proc.returncode, proc.stdout[-2000:],
                            proc.stderr[-4000:]))
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise SystemExit("child produced no JSON:\n%s" % proc.stdout[-2000:])


# ------------------------------------------------------------- 1. scan

def check_scan_bind_time():
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.models import transformer

    L, D, H, T, V, B = 32, 128, 4, 64, 256, 4
    sym = transformer.get_symbol(vocab_size=V, num_layers=L, d_model=D,
                                 n_heads=H, seq_len=T)
    jax.jit(lambda x: x * 2)(np.ones(4))   # warm jax itself

    def arm(mode):
        mx.config.set("MXNET_TPU_SCAN_LAYERS", mode)
        t0 = time.perf_counter()
        mod = mx.mod.Module(sym, context=mx.cpu(0))
        mod.bind(data_shapes=[("data", (B, T))],
                 label_shapes=[("softmax_label", (B, T))])
        mod.init_params(mx.init.Xavier())
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.01})
        x = np.random.RandomState(0).randint(0, V, (B, T)).astype(
            np.float32)
        y = np.random.RandomState(1).randint(0, V, (B, T)).astype(
            np.float32)
        db = mx.io.DataBatch(data=[mx.nd.array(x)],
                             label=[mx.nd.array(y)])
        mod._fit_step(db)
        float(np.asarray(mod._exec.arg_dict["lm_head_weight"].data[0, 0]))
        return mod, time.perf_counter() - t0

    mod_on, secs_on = arm("auto")
    assert mod_on._exec._scan_plan is not None, "scan plan did not apply"
    assert mx.profiler.gauges().get("scan_layers") == L
    assert secs_on <= BIND_BUDGET_SECS, \
        "deep transformer bind+first-step %.1fs exceeds %.0fs budget " \
        "with scan on" % (secs_on, BIND_BUDGET_SECS)

    mod_off, secs_off = arm("off")
    assert mod_off._exec._scan_plan is None

    # deterministic program-size gate: trace both forwards
    ex = mod_off._exec
    args = {n: a.data for n, a in ex.arg_dict.items()}
    aux = {n: a.data for n, a in ex.aux_dict.items()}
    key = jax.random.PRNGKey(0)
    n_off = len(jax.make_jaxpr(
        lambda a: mod_off._exec._fn(a, aux, key, True))(args).jaxpr.eqns)
    n_on = len(jax.make_jaxpr(
        lambda a: mod_on._exec._fn(a, aux, key, True))(args).jaxpr.eqns)
    assert n_off >= 2.0 * n_on, \
        "unrolled/scan eqn ratio %.2f < 2 (off %d, on %d)" \
        % (n_off / n_on, n_off, n_on)
    speedup = secs_off / secs_on
    assert speedup >= 1.8, \
        "scan bind+first-step speedup %.2fx < 1.8x (on %.1fs off %.1fs)" \
        % (speedup, secs_on, secs_off)
    mx.config.reset("MXNET_TPU_SCAN_LAYERS")
    print("scan gate: L=%d on %.1fs off %.1fs speedup %.1fx "
          "eqns %d->%d (%.1fx)"
          % (L, secs_on, secs_off, speedup, n_off, n_on, n_off / n_on))


# -------------------------------------------------------------- 2. AOT

_AOT_CHILD = """
import json, os, sys
sys.path.insert(0, %(root)r)
import numpy as np
import mxnet_tpu as mx
mx.config.set("MXNET_TPU_COMPILE_CACHE", %(cache)r)
np.random.seed(0)
X = np.random.uniform(-1, 1, (64, 16)).astype(np.float32)
Y = (X.sum(axis=1) > 0).astype(np.float32)
it = mx.io.NDArrayIter(X, Y, batch_size=16, label_name="softmax_label")
net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                            name="fc1")
net = mx.sym.Activation(net, act_type="relu")
net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
net = mx.sym.SoftmaxOutput(net, mx.sym.Variable("softmax_label"),
                           name="softmax")
mod = mx.mod.Module(net, context=mx.cpu(0))
init = {"fc1_weight": mx.nd.array(np.full((8, 16), 0.01, np.float32)),
        "fc1_bias": mx.nd.zeros((8,)),
        "fc2_weight": mx.nd.array(np.full((2, 8), 0.01, np.float32)),
        "fc2_bias": mx.nd.zeros((2,))}
mod.fit(it, num_epoch=1, arg_params=init,
        optimizer_params={"learning_rate": 0.1})
c = mx.profiler.counters()
fused_compiles = [r for r in mx.obs.compiles.snapshot()
                  if r.get("scope") == "fused_step"]
print(json.dumps({
    "aot_hit": c.get("aot_hit", 0), "aot_store": c.get("aot_store", 0),
    "aot_error": c.get("aot_error", 0),
    "fused_backend_compiles": len(fused_compiles),
    "w00": repr(mod.get_params()[0]["fc1_weight"].asnumpy()[0, 0])}))
"""


def check_aot_warm_start():
    cache = tempfile.mkdtemp(prefix="aot_smoke_")
    child = _AOT_CHILD % {"root": ROOT, "cache": cache}
    cold = _run_child(child)
    assert cold["aot_store"] >= 1, "first process stored nothing: %r" % cold
    assert cold["aot_error"] == 0, cold
    warm = _run_child(child)
    assert warm["aot_hit"] >= 1, "second process missed the cache: %r" % warm
    assert warm["aot_error"] == 0, warm
    assert warm["fused_backend_compiles"] == 0, \
        "warm process backend-compiled the fused step: %r" % warm
    assert warm["w00"] == cold["w00"], \
        "warm-start params diverged: %r vs %r" % (cold["w00"], warm["w00"])
    print("aot gate: cold store=%d warm hit=%d fused compiles warm=%d"
          % (cold["aot_store"], warm["aot_hit"],
             warm["fused_backend_compiles"]))


# -------------------------------------------------------- 3. zero cost

_ZERO_CHILD = """
import json, sys
sys.path.insert(0, %(root)r)
import numpy as np
import mxnet_tpu as mx
net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
    mx.sym.Variable("data"), num_hidden=4, name="fc1"), name="softmax")
mod = mx.mod.Module(net, context=mx.cpu(0))
mod.bind(data_shapes=[("data", (4, 8))],
         label_shapes=[("softmax_label", (4,))])
mod.init_params(mx.init.Xavier())
mod.init_optimizer(optimizer="sgd")
db = mx.io.DataBatch(data=[mx.nd.array(np.zeros((4, 8), np.float32))],
                     label=[mx.nd.array(np.zeros((4,), np.float32))])
mod._fit_step(db)
bad_modules = [m for m in sys.modules
               if m in ("mxnet_tpu.symbol.scan", "mxnet_tpu.remat",
                        "mxnet_tpu.aot")
               or m.startswith("mxnet_tpu.analysis")]
c = mx.profiler.counters()
bad_counters = {k: v for k, v in c.items()
                if k.startswith(("scan_", "remat_", "aot_", "accum_"))
                and v}
print(json.dumps({"bad_modules": bad_modules,
                  "bad_counters": bad_counters}))
"""


def check_zero_cost():
    rec = _run_child(_ZERO_CHILD % {"root": ROOT},
                     MXNET_TPU_SCAN_LAYERS="off", MXNET_TPU_REMAT="off",
                     MXNET_TPU_COMPILE_CACHE="", MXNET_TPU_ANALYZE="off")
    assert not rec["bad_modules"], \
        "knobs off but modules imported: %r" % rec["bad_modules"]
    assert not rec["bad_counters"], \
        "knobs off but counters bumped: %r" % rec["bad_counters"]
    print("zero-cost gate: no scan/remat/aot/analysis import, "
          "no counters")


def main():
    check_zero_cost()
    check_aot_warm_start()
    check_scan_bind_time()
    print("compile-time smoke: all gates passed")


if __name__ == "__main__":
    main()
