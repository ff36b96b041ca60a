"""Drive one rehearsal of the harness with a part of the fourth family's
mathematics changed in the program: ``python faulty_mimo.py <fault>
<run.py's arguments>``.

The fault is planted in ``mxnet_tpu.models.window_moe``, which the served
prefill and decode programs are built from, never in the benchmark: the
window layers' sink left out of the softmax, the window one position
short (the ring one row shorter with it), the full layers read through the
window's mask, or the rotary turned on every lane of a head. The harness
must come out with ``correct`` false.
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

FAULTS = ("no_sink", "window_off_by_one", "full_windowed", "rotary_all_lanes")


def plant(fault):
    from mxnet_tpu.models import window_moe as layer
    if fault == "no_sink":
        attend = layer.attend
        layer.attend = lambda kind, q, k, v, keep, sink=None, shared=False: \
            attend(kind, q, k, v, keep, None, shared)
    elif fault == "window_off_by_one":
        init = layer.Arch.__init__

        def short(self, doc):
            init(self, doc)
            self.window -= 1
        layer.Arch.__init__ = short
    elif fault == "full_windowed":
        init = layer.Arch.__init__
        seen = {}

        def remember(self, doc):
            init(self, doc)
            seen["window"] = self.window
        layer.Arch.__init__ = remember
        causal = layer.full_keep
        layer.full_keep = lambda q, k: causal(q, k) \
            & (k[None, :] > q[:, None] - seen["window"])
    elif fault == "rotary_all_lanes":
        init = layer.Kind.__init__

        def everywhere(self, heads, kv_heads, d_k, d_v, theta, rot_factor,
                       sink):
            init(self, heads, kv_heads, d_k, d_v, theta, 1.0, sink)
        layer.Kind.__init__ = everywhere
    elif fault != "none":
        raise SystemExit("no fault %r" % fault)


def main():
    fault, argv = sys.argv[1], sys.argv[2:]
    from benchmarks import run
    # the harness sets the platform before JAX is imported; the fault
    # needs the program, so set it here the same way first
    run.prepare_environment(argparse.Namespace(rehearse=True))
    plant(fault)
    return run.main(["--rehearse"] + argv)


if __name__ == "__main__":
    sys.exit(main())
