"""Drive one rehearsal of the harness with the timed path broken
underneath: ``python faulty_run.py <fault> <run.py's arguments>``.

The fault is planted in the program, not in the benchmark: the compiled
step that ``Module.fit`` drives, or the sampler that ``GenerativeServer``
calls for every token. The harness must come out with ``correct`` false.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _break_step(how):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.module.module import Module
    build = Module._build_fused_step

    def first_half_twice(v):
        n = v.shape[0] // 2
        return jnp.concatenate([v[:n]] * 2, axis=0)

    def broken_build(self):
        build(self)
        step = getattr(self, "_fused_jit", None)
        if step is None:
            return

        def faulty(params, states, aux, inputs, *rest):
            if how == "state_unchanged":
                # the step runs and its state is thrown away
                copies = jax.tree_util.tree_map(jnp.copy,
                                                (params, states, aux))
                outs = step(*copies, inputs, *rest)[0]
                return outs, params, states, aux
            # half of the batch left out, the mean taken over the rest:
            # the rows kept stand in for the rows dropped
            inputs = {k: first_half_twice(v) for k, v in inputs.items()}
            return step(params, states, aux, inputs, *rest)
        self._fused_jit = faulty
        self._fused_call = None
        self._fused_aot_key = None
    Module._build_fused_step = broken_build


def _break_sampler():
    from mxnet_tpu.serve import decode
    sample = decode.sample_token
    calls = [0]

    def altered(logits, *args, **kwargs):
        tok = sample(logits, *args, **kwargs)
        calls[0] += 1
        return (tok + 1) % len(logits) if calls[0] % 5 == 0 else tok
    decode.sample_token = altered


def main():
    fault, argv = sys.argv[1], sys.argv[2:]
    from benchmarks import run
    args = ["--rehearse"] + argv
    # the harness sets the platform before JAX is imported; the fault
    # needs the program, so set it here the same way first
    import argparse
    run.prepare_environment(argparse.Namespace(rehearse=True))
    if fault == "token_altered":
        _break_sampler()
    elif fault in ("state_unchanged", "half_batch"):
        _break_step(fault)
    elif fault != "none":
        raise SystemExit("no fault %r" % fault)
    return run.main(args)


if __name__ == "__main__":
    sys.exit(main())
