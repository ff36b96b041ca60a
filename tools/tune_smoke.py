"""CI ``tune`` job: the ISSUE 19 autotuner, gated.

Four checks:

1. **Zero-cost gate** — with ``MXNET_TPU_TUNE`` unset, a full fit must
   import NO ``mxnet_tpu.tune`` module and bump no ``tune_*`` counter.
2. **Bounded search (tiny MLP)** — ``search()`` with probe subprocesses
   must return inside a hard wall-clock budget, probe the default, and
   pick a winner whose probe score is >= the default's (the default is
   always in the probe set, so this holds by construction — the gate
   asserts the construction).
3. **Bounded search (tiny transformer)** — same gates on the seq-model
   path (int32 embedding inputs, seq labels, Loss metric).
4. **Warm restart** — process A runs ``fit(tune="auto")`` with a config
   store: searches, persists, trains. Process B repeats the identical
   program: it must LOAD the stored config (``tune_store_hit``, zero
   probes, zero search) and finish with the tuned knobs applied
   (``tune_applied``) and no steady-state recompile.

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

SEARCH_BUDGET_SECS = float(os.environ.get("TUNE_SEARCH_BUDGET", "300"))
# CPU probes need an explicit MFU denominator
os.environ.setdefault("MXNET_TPU_OBS_PEAK_FLOPS", "1e12")


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


# -------------------------------------------------------- 1. zero cost

_ZERO_CHILD = """
import json, sys
sys.path.insert(0, %(root)r)
import numpy as np
import mxnet_tpu as mx
net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
    mx.sym.Variable("data"), num_hidden=4, name="fc1"), name="softmax")
X = np.zeros((16, 8), np.float32)
Y = np.zeros((16,), np.float32)
it = mx.io.NDArrayIter(X, Y, batch_size=8, label_name="softmax_label")
mod = mx.mod.Module(net, context=mx.cpu(0))
mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.01})
bad_modules = [m for m in sys.modules if m.startswith("mxnet_tpu.tune")]
bad_counters = {k: v for k, v in mx.profiler.counters().items()
                if k.startswith("tune") and v}
print(json.dumps({"bad_modules": bad_modules,
                  "bad_counters": bad_counters}))
"""


def check_zero_cost():
    env = {k: "" for k in os.environ if k.startswith("MXNET_TPU_TUNE")}
    rec = _run_child(_ZERO_CHILD % {"root": ROOT}, **env)
    assert not rec["bad_modules"], \
        "tuner off but modules imported: %r" % rec["bad_modules"]
    assert not rec["bad_counters"], \
        "tuner off but counters bumped: %r" % rec["bad_counters"]
    print("zero-cost gate: no tune import, no tune counters")


# -------------------------------------------- 2+3. bounded search gates

def check_bounded_search(net_name):
    from mxnet_tpu.tune import search
    from mxnet_tpu.tune.__main__ import _zoo
    batch = 8 if net_name == "transformer" else 32
    sym, data_shapes, label_shapes, dtypes = _zoo(net_name, batch)
    t0 = time.perf_counter()
    cfg = search(sym, data_shapes, label_shapes, optimizer="sgd",
                 mode="auto", probe_steps=4, max_probes=2,
                 probe_deadline_s=120, data_dtypes=dtypes,
                 use_store=False)
    wall = time.perf_counter() - t0
    assert wall <= SEARCH_BUDGET_SECS, \
        "%s search took %.0fs > %.0fs budget" \
        % (net_name, wall, SEARCH_BUDGET_SECS)
    assert cfg.n_probed >= 1, "no probe completed for %s" % net_name
    assert cfg.source in ("probe", "static"), cfg.source
    if cfg.source == "probe":
        assert cfg.baseline is not None, \
            "winner scored without a default baseline"
        win = cfg.score.get("steps_per_sec") or 0
        base = cfg.baseline.get("steps_per_sec") or 0
        assert win >= base, \
            "winner %.2f steps/s < default %.2f" % (win, base)
        assert int(cfg.score.get("loop_recompile") or 0) == 0
    print("bounded search gate (%s): %.1fs, %d probed, winner %s (%s)"
          % (net_name, wall, cfg.n_probed, cfg.candidate.to_dict(),
             cfg.source))


# ----------------------------------------------------- 4. warm restart

_TUNE_CHILD = """
import json, sys, time
sys.path.insert(0, %(root)r)
import numpy as np
import mxnet_tpu as mx
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
t0 = time.perf_counter()
mod.fit(it, num_epoch=1, tune="auto",
        optimizer_params={"learning_rate": 0.1})
wall = time.perf_counter() - t0
c = mx.profiler.counters()
print(json.dumps({
    "wall_s": round(wall, 2),
    "tune_applied": c.get("tune_applied", 0),
    "tune_probe": c.get("tune_probe", 0),
    "tune_store_write": c.get("tune_store_write", 0),
    "tune_store_hit": c.get("tune_store_hit", 0),
    "loop_recompile": c.get("loop_recompile", 0)}))
"""


def check_warm_restart():
    cache = tempfile.mkdtemp(prefix="tune_smoke_")
    child = _TUNE_CHILD % {"root": ROOT}
    env = dict(MXNET_TPU_TUNE_STORE=cache,
               MXNET_TPU_TUNE_PROBE_STEPS="4",
               MXNET_TPU_TUNE_MAX_PROBES="2")
    cold = _run_child(child, **env)
    assert cold["tune_applied"] == 1, cold
    assert cold["tune_probe"] >= 1, "cold start probed nothing: %r" % cold
    assert cold["tune_store_write"] == 1, cold
    warm = _run_child(child, **env)
    assert warm["tune_store_hit"] == 1, \
        "restart did not read the stored config: %r" % warm
    assert warm["tune_probe"] == 0, \
        "restart re-searched (%d probes): %r" % (warm["tune_probe"], warm)
    assert warm["tune_applied"] == 1, warm
    assert warm["loop_recompile"] == 0, warm
    print("warm-restart gate: cold %.1fs (%d probes, stored) -> "
          "warm %.1fs (store hit, 0 probes)"
          % (cold["wall_s"], cold["tune_probe"], warm["wall_s"]))


def main():
    check_zero_cost()
    check_bounded_search("mlp")
    check_bounded_search("transformer")
    check_warm_restart()
    print("tune smoke: all gates passed")


if __name__ == "__main__":
    main()
