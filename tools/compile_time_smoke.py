"""CI ``compile-time`` job: the compile/memory levers cost nothing off.

**Zero-cost gate** — with ``MXNET_TPU_REMAT=off`` and
``MXNET_TPU_ANALYZE=off`` and no ``grad_accum`` a bind + fused step must
import neither ``mxnet_tpu.remat`` nor ``mxnet_tpu.analysis`` and bump
none of the ``remat_`` / ``accum_`` counters. What the levers do when
they are on is the business of ``tests/test_compile_time.py``
(``TestRemat``, ``TestGradAccum``).

Exit code 0 = the gate passed.
"""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


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
               if m == "mxnet_tpu.remat"
               or m.startswith("mxnet_tpu.analysis")]
c = mx.profiler.counters()
bad_counters = {k: v for k, v in c.items()
                if k.startswith(("remat_", "accum_"))
                and v}
print(json.dumps({"bad_modules": bad_modules,
                  "bad_counters": bad_counters}))
"""


def check_zero_cost():
    rec = _run_child(_ZERO_CHILD % {"root": ROOT},
                     MXNET_TPU_REMAT="off", MXNET_TPU_ANALYZE="off")
    assert not rec["bad_modules"], \
        "knobs off but modules imported: %r" % rec["bad_modules"]
    assert not rec["bad_counters"], \
        "knobs off but counters bumped: %r" % rec["bad_counters"]
    print("zero-cost gate: no remat/analysis import, no counters")


def main():
    check_zero_cost()
    print("compile-time smoke: all gates passed")


if __name__ == "__main__":
    main()
