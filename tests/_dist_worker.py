"""Worker body for the fake-cluster test (reference pattern:
tests/nightly/dist_sync_kvstore.py run via `tools/launch.py -n N`).

Run by tests/test_dist.py through tools/launch.py; NOT collected by pytest.
Asserts push/pull allreduce semantics, then trains a tiny MLP with
rank-dependent data for a few steps and dumps the weights; the parent
asserts replicas are bit-identical across ranks (sync data-parallel SGD).
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# the suite runs on the CPU whatever the environment says
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def main():
    outdir = sys.argv[1]
    import mxnet_tpu as mx

    kv = mx.kv.create("dist_sync")
    rank, n = kv.rank, kv.num_workers
    assert n == int(os.environ["DMLC_NUM_WORKER"]), (n, os.environ)
    assert rank == int(os.environ["DMLC_WORKER_ID"]), rank

    # --- push/pull semantics: store = init + sum_r (rank+1) applied once
    kv.init(300, mx.nd.ones((4, 2)))
    kv.push(300, mx.nd.array(np.full((4, 2), rank + 1, np.float32)))
    out = mx.nd.zeros((4, 2))
    kv.pull(300, out=out)
    expect = 1.0 + n * (n + 1) / 2.0
    np.testing.assert_allclose(out.asnumpy(), expect)

    # --- big-array path: with a tiny MXNET_KVSTORE_BIGARRAY_BOUND the
    # fused flush must chunk the flattened buffer (reference: big-array
    # server sharding, tests/nightly/dist_sync_kvstore.py:30-40) and the
    # sum must still be exact; several keys staged before one pull also
    # exercises the single-fused-allreduce path
    from mxnet_tpu import config as _config
    _config.set("MXNET_KVSTORE_BIGARRAY_BOUND", 1000)
    big = np.arange(4096, dtype=np.float32).reshape(64, 64)
    kv.init("big", mx.nd.zeros((64, 64)))
    kv.init("small", mx.nd.zeros((3,)))
    kv.push("big", mx.nd.array(big * (rank + 1)))
    kv.push("small", mx.nd.array(np.full((3,), rank + 1, np.float32)))
    bout = mx.nd.zeros((64, 64))
    sout = mx.nd.zeros((3,))
    kv.pull("big", out=bout)
    kv.pull("small", out=sout)
    scale = n * (n + 1) / 2.0
    np.testing.assert_allclose(bout.asnumpy(), big * scale, rtol=1e-6)
    np.testing.assert_allclose(sout.asnumpy(), scale)
    _config.set("MXNET_KVSTORE_BIGARRAY_BOUND", 1000000)

    # --- rank-dependent init must be overridden by rank 0's broadcast
    kv.init("w0", mx.nd.array(np.full((3,), float(rank), np.float32)))
    got = mx.nd.zeros((3,))
    kv.pull("w0", out=got)
    np.testing.assert_allclose(got.asnumpy(), 0.0)

    # --- sync data-parallel training: different data per rank, identical
    # weights after every update (the dist_sync contract)
    rng = np.random.RandomState(100 + rank)
    x = rng.uniform(-1, 1, (64, 2)).astype(np.float32)
    y = ((x[:, 0] > 0) ^ (x[:, 1] > 0)).astype(np.float32)

    d = mx.sym.Variable("data")
    f1 = mx.sym.FullyConnected(d, num_hidden=8, name="fc1")
    a1 = mx.sym.Activation(f1, act_type="tanh")
    f2 = mx.sym.FullyConnected(a1, num_hidden=2, name="fc2")
    sym = mx.sym.SoftmaxOutput(f2, name="softmax")

    it = mx.io.NDArrayIter(x, y, batch_size=16)
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.fit(it, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Xavier(),
            kvstore=kv, num_epoch=2)

    params = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    np.savez(os.path.join(outdir, "params_rank%d.npz" % rank), **params)

    # --- failure detection (§5.3): heartbeats published via the
    # coordinator KV store; everyone alive -> zero dead nodes
    dead = kv.get_num_dead_node(0, timeout=2)
    assert dead == 0, "expected no dead nodes, got %d" % dead

    kv.barrier()
    print("dist worker rank %d/%d OK" % (rank, n), flush=True)


if __name__ == "__main__":
    main()
