"""Decode attention over the key blocks a selection names, read where the
cache holds them.

One new token per resident sequence attends, for each key/value head, to
at most ``K`` blocks of ``block`` positions of that sequence's cached
keys and values, named by an index list (``models/sparse_linear.py::
select_blocks``): the bytes moved for K and V are ``K`` blocks a slot,
key/value head and layer whatever the sequence bucket. The cache
(``serve/kv_cache.py``) is ``(layers, slots, max_seq, kv_heads * d_head)``
and the kernel takes the WHOLE array and a layer index; a key/value head
is the lane block ``g`` of the row, the ``group`` query heads that read it
are the rows of one ``(group, d_head)`` query tile.

Grid ``(slot, key/value head, K / per_step)``. The layer, the index list,
each slot's count of valid entries and its position are scalar-prefetched.
The cache is handed to the kernel ``per_step`` times, each time with its
own ``BlockSpec`` whose index map reads another entry of the list, so one
grid step fetches ``per_step`` blocks of K and of V and Pallas overlaps the
next step's fetches with this step's arithmetic. An entry past the slot's
count maps to its last valid block again (an unchanged block index is not
fetched) and ``pl.when`` skips a step with no valid entry; a free slot
(count 0) costs one block's fetch. Valid blocks fold into ``(m, l, acc)``
VMEM scratch by online softmax; the last step writes the output tile.

Off-TPU the kernel runs interpreted (``rtc.resolve_interpret``), its
operands widened to float32 there (XLA's CPU backend has no bfloat16
product).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["sparse_decode_attention", "tiles"]

_NEG_INF = -1e30
_PER_STEP = 8


def tiles(block: int, d_head: int, dtype) -> bool:
    """Whether a ``(block, d_head)`` tile of the cache in ``dtype`` is one
    the TPU can fetch as a block (whole sublane tiles, whole lanes)."""
    sub = 8 * (4 // jnp.dtype(dtype).itemsize)
    return block % sub == 0 and d_head % 128 == 0


def _sparse_decode_kernel(layer_ref, idx_ref, count_ref, pos_ref, q_ref,
                          *refs, scale, block, per_step, n_sel, kv_heads,
                          widen):
    import jax.experimental.pallas as pl
    del layer_ref
    k_refs, v_refs = refs[:per_step], refs[per_step:2 * per_step]
    o_ref, m_scr, l_scr, acc_scr = refs[2 * per_step:]

    slot = pl.program_id(0)
    g = pl.program_id(1)
    step = pl.program_id(2)
    n_steps = pl.num_programs(2)
    count = count_ref[slot]
    pos = pos_ref[slot]
    base = (slot * kv_heads + g) * n_sel
    first = step * per_step
    # the blocks this step holds, as the index maps chose them
    ids = [idx_ref[base + jnp.minimum(first + i, n_sel - 1)]
           for i in range(per_step)]

    @pl.when(step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(first < count)
    def _update():
        q = q_ref[0, 0]                                     # (group, d)
        k = jnp.concatenate([r[0, 0] for r in k_refs], axis=0)
        v = jnp.concatenate([r[0, 0] for r in v_refs], axis=0)
        if widen:
            q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        entry = col // block                                # 0..per_step-1
        start = jnp.zeros_like(col)
        for i in range(per_step):
            start = jnp.where(entry == i, ids[i] * block, start)
        key_pos = start + col % block
        ok = (key_pos <= pos) & (first + entry < count)
        s = jnp.where(ok, s, _NEG_INF)                      # (group, P*B)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new[:, None]), 0.0)
        acc_scr[:] = acc_scr[:] * alpha[:, None] + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:, 0] = m_new
        l_scr[:, 0] = l_scr[:, 0] * alpha + jnp.sum(p, axis=-1)

    @pl.when(step == n_steps - 1)
    def _finish():
        # a free slot folded nothing: acc 0 over the floor is a 0 tile
        denom = jnp.maximum(l_scr[:, 0], 1e-37)
        o_ref[0, 0] = (acc_scr[:] / denom[:, None]).astype(o_ref.dtype)


def sparse_decode_attention(q, k_cache, v_cache, layer, idx, count, pos, *,
                            block: int, scale: float):
    """``q (slots, kv_heads, group, d_head)``; ``k_cache`` / ``v_cache``
    ``(layers, slots, max_seq, kv_heads * d_head)``; ``idx (slots,
    kv_heads, K)`` int32 block numbers of which slot ``s`` attends the
    first ``count[s]`` (``K`` a multiple of ``_PER_STEP`` or smaller than
    it), keys at positions ``<= pos[s]`` only. Returns float32 ``(slots,
    kv_heads * group * d_head)``; a slot with ``count`` 0 a row of 0."""
    from ...rtc import resolve_interpret
    slots = q.shape[0]
    n_sel = int(idx.shape[-1])
    per_step = min(_PER_STEP, n_sel)
    assert n_sel % per_step == 0, (n_sel, per_step)
    interpret = resolve_interpret((q, k_cache, v_cache))
    out = _attend(q.astype(k_cache.dtype), k_cache, v_cache,
                  jnp.asarray(layer, jnp.int32),
                  idx.reshape(-1).astype(jnp.int32),
                  count.astype(jnp.int32), pos.astype(jnp.int32),
                  block=int(block), scale=float(scale), per_step=per_step,
                  n_sel=n_sel, interpret=interpret)
    return out.reshape(slots, -1)


# jitted with the layer a traced scalar: every sparse layer of a program
# shares one trace and one lowering of the kernel
@functools.partial(jax.jit, static_argnames=("block", "scale", "per_step",
                                             "n_sel", "interpret"))
def _attend(q, k_cache, v_cache, layer, idx, count, pos, *, block, scale,
            per_step, n_sel, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, kv_heads, group, d_head = q.shape

    def q_map(s, g, j, *_):
        return (s, g, 0, 0)

    def kv_map(i):
        def index(s, g, j, layer, idx, count, pos):
            e = jnp.minimum(j * per_step + i, jnp.maximum(count[s] - 1, 0))
            return (layer[0], s, idx[(s * kv_heads + g) * n_sel + e], g)
        return index

    kernel = functools.partial(
        _sparse_decode_kernel, scale=scale, block=block, per_step=per_step,
        n_sel=n_sel, kv_heads=kv_heads,
        widen=interpret and k_cache.dtype != jnp.float32)
    kv_specs = [pl.BlockSpec((1, 1, block, d_head), kv_map(i))
                for i in range(per_step)]
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((slots, kv_heads, group, d_head),
                                       jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(slots, kv_heads, n_sel // per_step),
            in_specs=[pl.BlockSpec((1, 1, group, d_head), q_map)]
            + kv_specs + kv_specs,
            out_specs=pl.BlockSpec((1, 1, group, d_head), q_map),
            scratch_shapes=[pltpu.VMEM((group, 1), jnp.float32),
                            pltpu.VMEM((group, 1), jnp.float32),
                            pltpu.VMEM((group, d_head), jnp.float32)]),
        name="sparse_decode_attention",
        interpret=interpret,
    )(layer.reshape(1), idx, count, pos, q,
      *([k_cache] * per_step), *([v_cache] * per_step))
