"""Decode attention over the serving KV cache, read where it lies.

One new token per resident sequence attends to that sequence's cached
keys and values. The cache (``serve/kv_cache.py``) is
``(layers, slots, max_seq, n_heads * d_head)`` — one lane-dense row a
position, heads side by side — and this kernel takes the WHOLE array and
a layer index, so no layer, bucket or head is sliced out or laid out
again on the way (the XLA read of the same bucket makes a ``slice`` and
a transposed ``copy`` of it per layer and tensor).

Grid ``(slot, key block)``. The layer, each slot's write position and a
small fetch plan are scalar-prefetched, so the K and V ``BlockSpec``
index maps can use them: a key block past a sequence's last needed one
maps to that last block again, and a free slot maps to the block the
previous grid step already holds — an unchanged block index is not
fetched, so neither costs HBM traffic, and ``pl.when`` skips their
arithmetic. Blocks that are live fold into ``(m, l, acc)`` VMEM scratch
by online softmax; the last key step writes the slot's output row.

Heads stay in the row. The query row is spread to a block-diagonal
``(n_heads, n_heads * d_head)`` matrix (row ``h`` keeps head ``h``'s
lanes), so scores for all heads are ONE ``(H, HD) x (block_k, HD)^T``
product and the context ONE ``(H, block_k) x (block_k, HD)`` product
whose diagonal blocks are the heads' outputs: ``H`` times the needed
multiplies, on an MXU that a matrix-vector product leaves idle anyway,
in exchange for no per-head lane slicing and no transposes.

Off-TPU the kernel runs interpreted (``rtc.resolve_interpret``), as
``flash_attention`` does. A Mosaic call is opaque to the SPMD
partitioner: under a multi-device jit it needs ``shard_map``, which the
serving engine does not give it — a sharded cache keeps the XLA read.
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["decode_attention", "fetch_plan", "block_for"]

_NEG_INF = -1e30
_BLOCK_K = 256


def block_for(bucket: int, block_k: int = _BLOCK_K) -> int:
    """Key block for a bucket: the largest common divisor with the
    preferred block, so every bucket of the ladder tiles exactly."""
    return math.gcd(int(bucket), int(block_k))


def fetch_plan(pos, active, block_k: int):
    """Per-slot scalars the index maps read: ``(slot_of, first, last,
    pos)``, all ``(slots,)`` int32. An active slot walks its own blocks
    ``0 .. pos // block_k``. A free slot is pinned to the one block the
    grid step before it leaves resident (the nearest active slot's last
    block; before the first active slot, that slot's block 0, which is
    also what the step after it wants), and its ``pos`` is -1: no key
    is live. The same plan serves every layer of a step."""
    slots = pos.shape[0]
    pos = pos.astype(jnp.int32)
    idx = jnp.arange(slots, dtype=jnp.int32)
    own_last = pos // block_k
    nearest = lax.cummax(jnp.where(active, idx, -1))
    leading = nearest < 0
    slot_of = jnp.where(leading, jnp.argmax(active).astype(jnp.int32),
                        nearest)
    last = jnp.where(leading, 0, own_last[slot_of])
    first = jnp.where(active, 0, last)
    return slot_of, first, last, jnp.where(active, pos, -1)


def _decode_attn_kernel(layer_ref, slot_of_ref, first_ref, last_ref,
                        pos_ref, q_ref, k_ref, v_ref, o_ref,
                        m_scr, l_scr, acc_scr, *, scale, block_k, d_head):
    import jax.experimental.pallas as pl
    del layer_ref, slot_of_ref, first_ref, last_ref  # the index maps' own

    # program ids are read at the top level only: a pl.when body is a
    # cond branch, where the interpreter cannot resolve program_id
    slot = pl.program_id(0)
    kv_step = pl.program_id(1)
    n_kv = pl.num_programs(1)
    pos = pos_ref[slot]
    n_heads, row = acc_scr.shape
    # own[h, c]: lane c of the row belongs to head h
    own = (lax.broadcasted_iota(jnp.int32, (n_heads, row), 1) // d_head
           == lax.broadcasted_iota(jnp.int32, (n_heads, row), 0))

    @pl.when(kv_step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # keys 0..pos inclusive: the token just written attends to itself
    @pl.when(kv_step * block_k <= pos)
    def _update():
        q = jnp.where(own, q_ref[0], 0.0) * scale       # (H, HD)
        k = k_ref[0, 0]                                 # (block_k, HD)
        v = v_ref[0, 0]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        k_pos = kv_step * block_k + \
            lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= pos, s, _NEG_INF)        # (H, block_k)
        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        acc_scr[:] = acc_scr[:] * alpha[:, None] + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # (H, HD)
        m_scr[:, 0] = m_new
        l_scr[:, 0] = l_prev * alpha + jnp.sum(p, axis=-1)

    @pl.when(kv_step == n_kv - 1)
    def _finish():
        # a free slot folded nothing: acc 0 over the floor is a 0 row
        denom = jnp.maximum(l_scr[:, 0], 1e-37)
        ctx = jnp.where(own, acc_scr[:] / denom[:, None], 0.0)
        o_ref[0] = jnp.sum(ctx, axis=0, keepdims=True).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, layer, plan, *, n_heads: int,
                     bucket: int, scale=None, block_k: int = _BLOCK_K):
    """Attention of one query row per slot over rows ``[0, bucket)`` of
    layer ``layer`` of the row-layout cache.

    ``q`` ``(slots, n_heads * d_head)``; ``k_cache`` / ``v_cache``
    ``(layers, slots, max_seq, n_heads * d_head)``; ``plan`` from
    :func:`fetch_plan` with this call's block (:func:`block_for`).
    Slot ``s`` attends to keys ``0 .. pos[s]``; a free slot's row is 0.
    Returns ``(slots, n_heads * d_head)``.
    """
    from ...rtc import resolve_interpret
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[1] // n_heads)
    return _attend(q, k_cache, v_cache, jnp.asarray(layer, jnp.int32), plan,
                   n_heads=int(n_heads), bucket=int(bucket),
                   scale=float(scale), bk=block_for(bucket, block_k),
                   interpret=resolve_interpret((q, k_cache, v_cache)))


# jitted with the layer a traced scalar: a program that attends once per
# layer traces and lowers the kernel once, not once a layer (the Mosaic
# lowering was two thirds of a decode program's trace time)
@functools.partial(jax.jit, static_argnames=("n_heads", "bucket", "scale",
                                             "bk", "interpret"))
def _attend(q, k_cache, v_cache, layer, plan, *, n_heads, bucket, scale, bk,
            interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, row = q.shape

    def q_map(s, j, *_):
        return (s, 0, 0)

    def kv_map(s, j, layer, slot_of, first, last, pos):
        return (layer[0], slot_of[s], jnp.clip(j, first[s], last[s]), 0)

    kernel = functools.partial(_decode_attn_kernel, scale=scale,
                               block_k=bk, d_head=row // n_heads)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((slots, 1, row), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(slots, bucket // bk),
            in_specs=[pl.BlockSpec((1, 1, row), q_map),
                      pl.BlockSpec((1, 1, bk, row), kv_map),
                      pl.BlockSpec((1, 1, bk, row), kv_map)],
            out_specs=pl.BlockSpec((1, 1, row), q_map),
            scratch_shapes=[pltpu.VMEM((n_heads, 1), jnp.float32),
                            pltpu.VMEM((n_heads, 1), jnp.float32),
                            pltpu.VMEM((n_heads, row), jnp.float32)]),
        name="decode_attention",
        interpret=interpret,
    )(layer.reshape(1), *plan, q.reshape(slots, 1, row), k_cache, v_cache)
    return out.reshape(slots, row)
