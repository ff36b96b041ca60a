"""Grouped-query decode attention over the serving cache, read where it
lies.

One new token per resident sequence attends, for each of ``H_kv``
key/value heads, with the ``group`` query heads that read that head, to
its sequence's cached keys and values. The cache (``serve/kv_cache.py``)
holds K as ``(layers, slots, max_seq, H_kv * d_k)`` and V as ``(layers,
slots, max_seq, H_kv * d_v)``, one row a position with the heads side by
side as the projections made them; the kernel takes the WHOLE arrays and a
layer index, so nothing is sliced out or laid out again on the way.

**Heads a product.** A key/value head of ``d_k`` lanes need not be whole
128-lane tiles (192 is one and a half), and the TPU fetches and slices
whole tiles. So the kernel takes the fewest heads whose K and V lanes are
whole tiles together (``heads_per_product``: two heads of 192 are three
tiles, of 128 two), and their ``per * group`` query heads are the rows of
ONE ``(per * group, per * d_k) x (block_k, per * d_k)^T`` product, each
query row placed over its own head's lanes with zeros over the others'
(``place_queries``): ``per`` times the needed multiplies of the scores,
none of the bytes. The context is a product a head, ``(group, block_k) x
(block_k, d_v)``, over whole tiles of V.

Grid ``(head set, slot, key block)``. The layer, each slot's position and
the fetch plan of ``decode_attention.fetch_plan`` are scalar-prefetched
and read by the index maps: a key block past a slot's last needed one maps
to that last block again, and a free slot to the block the grid step
before it holds (the head set is the outermost axis, so that block is the
nearest active slot's own); an unchanged block index is not fetched, so
neither costs HBM traffic, and ``pl.when`` skips their arithmetic. Live
blocks fold into ``(m, l, acc)`` VMEM scratch by online softmax; the last
key step writes the output tile.

Off-TPU the kernel runs interpreted (``rtc.resolve_interpret``), its
operands widened to float32 there (XLA's CPU backend has no bfloat16
product).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .decode_attention import fetch_plan  # noqa: F401  (the plan it reads)

__all__ = ["gqa_decode_attention", "heads_per_product", "place_queries",
           "block_for", "tiles", "fetch_plan"]

_NEG_INF = -1e30
_BLOCK_K = 2048


def block_for(bucket: int, block_k: int = _BLOCK_K) -> int:
    """Key block for a bucket: the largest common divisor with the
    preferred block, so every bucket tiles exactly."""
    return math.gcd(int(bucket), int(block_k))


def heads_per_product(kv_heads: int, d_k: int, d_v: int) -> int:
    """The fewest key/value heads whose K lanes and V lanes are whole
    128-lane tiles together; all of them where no fewer are."""
    for per in range(1, kv_heads + 1):
        if kv_heads % per == 0 and (per * d_k) % 128 == 0 \
                and (per * d_v) % 128 == 0:
            return per
    return kv_heads


def tiles(kv_heads: int, d_k: int, d_v: int, bucket: int, dtype) -> bool:
    """Whether the TPU can fetch the kernel's blocks: whole lane tiles a
    head set and whole sublane tiles a key block."""
    per = heads_per_product(kv_heads, d_k, d_v)
    sub = 8 * (4 // jnp.dtype(dtype).itemsize)
    return (per * d_k) % 128 == 0 and (per * d_v) % 128 == 0 \
        and block_for(bucket) % sub == 0


def place_queries(q, per: int):
    """``q (slots, H_kv, group, d_k)`` -> ``(slots, H_kv / per, per *
    group, per * d_k)``: the queries of a head set as the rows of one
    tile, row ``i * group + j`` (head ``i`` of the set, query ``j``) over
    lanes ``i * d_k .. (i + 1) * d_k`` and zeros elsewhere."""
    slots, kv_heads, group, d_k = q.shape
    sets = kv_heads // per
    q = q.reshape(slots, sets, per, group, 1, d_k)
    eye = jnp.eye(per, dtype=q.dtype).reshape(1, 1, per, 1, per, 1)
    return (q * eye).reshape(slots, sets, per * group, per * d_k)


def _gqa_decode_kernel(layer_ref, slot_of_ref, first_ref, last_ref, pos_ref,
                       q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                       scale, block_k, per, group, d_v, widen):
    import jax.experimental.pallas as pl
    del layer_ref, slot_of_ref, first_ref, last_ref  # the index maps' own

    # program ids are read at the top level only: a pl.when body is a
    # cond branch, where the interpreter cannot resolve program_id
    slot = pl.program_id(1)
    kv_step = pl.program_id(2)
    n_kv = pl.num_programs(2)
    pos = pos_ref[slot]

    @pl.when(kv_step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # keys 0..pos inclusive: the token just written attends to itself
    @pl.when(kv_step * block_k <= pos)
    def _update():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        if widen:
            q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        k_pos = kv_step * block_k + \
            lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= pos, s, _NEG_INF)        # (per*group, bk)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        pv = p.astype(v.dtype)
        ctx = jnp.concatenate([lax.dot_general(
            pv[i * group:(i + 1) * group], v[:, i * d_v:(i + 1) * d_v],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            for i in range(per)], axis=0)              # (per*group, d_v)
        acc_scr[:] = acc_scr[:] * alpha[:, None] + ctx
        m_scr[:, 0] = m_new
        l_scr[:, 0] = l_scr[:, 0] * alpha + jnp.sum(p, axis=-1)

    @pl.when(kv_step == n_kv - 1)
    def _finish():
        # a free slot folded nothing: acc 0 over the floor is a 0 tile
        denom = jnp.maximum(l_scr[:, 0], 1e-37)
        o_ref[0, 0] = (acc_scr[:] / denom[:, None]).astype(o_ref.dtype)


def gqa_decode_attention(q, k_cache, v_cache, layer, plan, *, bucket: int,
                         scale: float, block_k: int = _BLOCK_K):
    """``q (slots, H_kv, group, d_k)``; ``k_cache`` ``(layers, slots,
    max_seq, H_kv * d_k)``, ``v_cache`` ``(layers, slots, max_seq, H_kv *
    d_v)``; ``plan`` from :func:`fetch_plan` with this call's block
    (:func:`block_for`). Slot ``s`` attends to keys ``0 .. pos[s]`` of rows
    ``[0, bucket)``; a free slot's output is 0. Returns float32 ``(slots,
    H_kv * group * d_v)``, the query heads in order."""
    from ...rtc import resolve_interpret
    slots, kv_heads, group, d_k = q.shape
    d_v = v_cache.shape[-1] // kv_heads
    per = heads_per_product(kv_heads, d_k, d_v)
    interpret = resolve_interpret((q, k_cache, v_cache))
    out = _attend(place_queries(q.astype(k_cache.dtype), per), k_cache,
                  v_cache, jnp.asarray(layer, jnp.int32), plan,
                  bucket=int(bucket), scale=float(scale),
                  bk=block_for(bucket, block_k), per=per, group=group,
                  interpret=interpret)
    return out.reshape(slots, -1)


# jitted with the layer a traced scalar: every full layer of a program
# shares one trace and one lowering of the kernel
@functools.partial(jax.jit, static_argnames=("bucket", "scale", "bk", "per",
                                             "group", "interpret"))
def _attend(q, k_cache, v_cache, layer, plan, *, bucket, scale, bk, per,
            group, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, sets, rows, width = q.shape
    d_v = v_cache.shape[-1] // (sets * per)

    def q_map(t, s, j, *_):
        return (s, t, 0, 0)

    def kv_map(t, s, j, layer, slot_of, first, last, pos):
        return (layer[0], slot_of[s], jnp.clip(j, first[s], last[s]), t)

    kernel = functools.partial(
        _gqa_decode_kernel, scale=scale, block_k=bk, per=per, group=group,
        d_v=d_v, widen=interpret and k_cache.dtype != jnp.float32)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((slots, sets, rows, d_v),
                                       jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(sets, slots, bucket // bk),
            in_specs=[pl.BlockSpec((1, 1, rows, width), q_map),
                      pl.BlockSpec((1, 1, bk, width), kv_map),
                      pl.BlockSpec((1, 1, bk, per * d_v), kv_map)],
            out_specs=pl.BlockSpec((1, 1, rows, d_v), q_map),
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, d_v), jnp.float32)]),
        name="gqa_decode_attention",
        interpret=interpret,
    )(layer.reshape(1), *plan, q, k_cache, v_cache)
    return out
