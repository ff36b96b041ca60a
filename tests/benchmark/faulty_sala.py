"""Drive one rehearsal of the harness with a part of the third family's
mathematics left out of the program: ``python faulty_sala.py <fault>
<run.py's arguments>``.

The fault is planted in ``mxnet_tpu.models.sparse_linear``, which the
served prefill and decode programs are built from, never in the benchmark:
the selection (every block scores alike, so the forced blocks and the
lowest-numbered ones are read), the lightning layers' decay (``lambda`` 1),
their output norm, the output gates, or the read of the selected blocks
(other blocks than those named are read; nothing is read and the heads'
outputs are zeros: what a faulty kernel does). The harness must come out
with ``correct`` false.
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

FAULTS = ("no_selection", "no_decay", "no_output_norm", "no_gate",
          "other_blocks_read", "nothing_read")


def plant(fault):
    import numpy as np
    from mxnet_tpu.models import sparse_linear as layer
    if fault == "no_selection":
        select = layer.select_blocks
        layer.select_blocks = lambda a, s, t, n: select(a, 0.0 * s, t, n)
    elif fault == "no_decay":
        init = layer.Arch.__init__

        def undecayed(self, doc):
            init(self, doc)
            self.decay_rate = np.zeros_like(self.decay_rate)
        layer.Arch.__init__ = undecayed
    elif fault == "no_output_norm":
        layer.lightning_out = lambda a, p, o, u: layer.gated_out(p, o, u)
    elif fault == "no_gate":
        layer.gated_out = lambda p, o, u: layer.dense(o, p["att_o_weight"])
    elif fault == "other_blocks_read":
        # the scored blocks, which lie behind the forced ones in the list,
        # read one block lower than named
        attend = layer.attend_selected
        forced = 3

        def shifted(a, q, k, v, idx, n_valid, t):
            import jax.numpy as jnp
            low = jnp.maximum(idx - 1, 1)
            behind = jnp.arange(idx.shape[-1]) >= forced
            return attend(a, q, k, v, jnp.where(behind, low, idx), n_valid,
                          t)
        layer.attend_selected = shifted
    elif fault == "nothing_read":
        attend = layer.attend_selected
        layer.attend_selected = lambda *args: 0.0 * attend(*args)
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
