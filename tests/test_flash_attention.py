"""Numerics of the flash-attention walk (ISSUE 31), on the CPU in
interpreter mode: output, dq, dk and dv against a dense float32
reference, over the shapes that select each branch of the one path — both
head widths, a sequence of one block, one the block does not divide (the
padding path) and the training cell's, with and without ``causal``, keys
of another length than the queries, both operand widths, the caller's
blocks under the default, and a head whose K/V does not stay resident
(several chunks fetched by the grid)."""
import importlib

import numpy as np
import pytest


def _dense(q, k, v, causal):
    import jax
    import jax.numpy as jnp
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") \
        / np.sqrt(q.shape[-1])
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v,
                      precision="highest")


def _gaps(S, Sk, d_head, causal, dtype, block_q, block_k):
    """Worst gap of (out, dq, dk, dv) to the dense reference, each as a
    share of the reference's largest value."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas import flash_attention
    rng = np.random.RandomState(S + Sk + d_head)
    cpu = jax.local_devices(backend="cpu")[0]
    q, k, v, w = (jax.device_put(jnp.asarray(rng.randn(1, 2, n, d_head), t),
                                 cpu)
                  for n, t in ((S, dtype), (Sk, dtype), (Sk, dtype),
                               (S, "float32")))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=True)

    def weighed(f):
        return lambda *a: (f(*a).astype(jnp.float32) * w).sum()

    got = (flash(q, k, v),) + jax.grad(weighed(flash), (0, 1, 2))(q, k, v)
    want = (_dense(q, k, v, causal),) + jax.grad(
        weighed(lambda *a: _dense(*a, causal)), (0, 1, 2))(q, k, v)
    assert got[0].dtype == q.dtype and got[1].dtype == q.dtype
    return [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32)))
                  / jnp.max(jnp.abs(b.astype(jnp.float32))))
            for a, b in zip(got, want)]


_LIMIT = {"float32": 5e-6, "bfloat16": 2e-2}

_CASES = (
    # the whole cross at float32
    [(S, S, d, causal, "float32", 512, 512)
     for d in (64, 128) for S in (128, 640, 2048) for causal in (True, False)]
    # bf16 operands: P and dS rounded for the products, statistics float32
    + [(640, 640, d, causal, "bfloat16", 512, 512)
       for d in (64, 128) for causal in (True, False)]
    # keys of another length than the queries
    + [(128, 640, 64, False, "float32", 512, 128),
       (640, 128, 128, False, "bfloat16", 512, 512)]
    # the caller's blocks under the default, unequal both ways
    + [(640, 640, 64, True, "float32", 128, 128),
       (512, 512, 64, True, "float32", 64, 256),
       (512, 512, 128, True, "float32", 256, 64)])


@pytest.mark.parametrize("S,Sk,d_head,causal,dtype,block_q,block_k", _CASES)
def test_flash_walk_matches_dense(S, Sk, d_head, causal, dtype, block_q,
                                  block_k):
    gaps = _gaps(S, Sk, d_head, causal, dtype, block_q, block_k)
    assert max(gaps) < _LIMIT[dtype], gaps


@pytest.fixture
def fa():
    """The kernels' module, with nothing built: a kernel is built (and
    its tiles counted) once for all calls of the same shapes."""
    module = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")

    def forget():
        module._fa_forward.clear_cache()
        module._fa_backward.clear_cache()
    forget()
    yield module
    forget()


@pytest.mark.parametrize("S,Sk,causal,block_q,block_k",
                         [(512, 512, True, 128, 128),
                          (512, 512, True, 64, 128),
                          (512, 512, True, 128, 64),
                          (256, 512, False, 128, 128)])
def test_flash_walk_over_chunks_fetched_by_the_grid(monkeypatch, fa, S, Sk,
                                                    causal, block_q,
                                                    block_k):
    """A head whose K/V does not fit stays on the one path: the grid
    fetches chunks, (m, l, acc) pass between them through scratch, each
    K/V chunk writes its own slab of dq, and a chunk above the diagonal
    is stepped over (the counters then differ, and say by how much)."""
    import mxnet_tpu as mx
    monkeypatch.setattr(fa, "_RESIDENT_BYTES", 128 * 128 * 4)
    with mx.profiler.counter_delta() as tiles:
        gaps = _gaps(S, Sk, 64, causal, "float32", block_q, block_k)
    assert max(gaps) < _LIMIT["float32"], gaps
    visited = tiles.get("flash_attn_tiles_visited")
    stepped = tiles.get("flash_attn_tiles_grid")
    assert (stepped > visited) if causal else (stepped == visited)


@pytest.mark.parametrize("causal,visited", [(True, 20), (False, 32)])
def test_flash_walk_counts_its_tiles(fa, causal, visited):
    """One call, forward and backward, at the training cell's sequence
    and the old blocks: 10 of 16 tiles a kernel under ``causal``, and the
    grid steps over no other (the three old kernels read 30 of 48)."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    head = jax.ShapeDtypeStruct((2, 2048, 64), jnp.float32)
    with mx.profiler.counter_delta() as tiles:
        jax.eval_shape(jax.grad(
            lambda *a: fa._fa(*a, 0.125, causal, 512, 512, True).sum(),
            argnums=(0, 1, 2)), head, head, head)
    assert tiles.get("flash_attn_tiles_visited") == visited
    assert tiles.get("flash_attn_tiles_grid") == visited
