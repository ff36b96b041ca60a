"""Ring attention — sequence/context parallelism over the device mesh.

The reference predates long-context training (SURVEY.md §5.7: its sequence
story is BucketingModule + fused RNN); the task spec requires the modern TPU
capability: shard the sequence axis across devices and compute exact
attention by rotating key/value blocks around the ring with ``ppermute``
while accumulating an online softmax (blockwise attention), so no device
ever materializes the full S×S score matrix. Collectives ride ICI
neighbor-to-neighbor, overlapping with the per-block matmuls (the pattern
from the ring-attention literature; see PAPERS.md).
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

__all__ = ["ring_attention", "ring_self_attention",
           "local_attention_block", "chunked_causal_attention",
           "sharding_island"]


def sharding_island():
    """Canonical layout claims of the sequence-parallel island (audited
    by ``analysis.sharding_passes.check_islands``): drawn from the
    unified SpecLayout — the sequence dim rides the canonical ``tp``
    model axis and the batch layout matches every other island, so the
    audit reports zero cross-island disagreements."""
    from .layout import island_specs
    return "ring_attention", island_specs("ring_attention")


def local_attention_block(q, k, v, mask=None, scale=None):
    """One (q-block, kv-block) attention contribution with running-softmax
    statistics. Returns (o_unnormalized, row_sum l, row_max m)."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isneginf(m)[..., None], 0.0, p)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(p.dtype))
    return o, l, m


def chunked_causal_attention(q, k, v, scale=None, chunk: int = 512):
    """Single-device blockwise causal attention — the serving prefill's
    long-context path. Same online-softmax accumulation the ring kernel
    rotates across devices, applied to local sequence chunks so no
    (S, S) score matrix ever materializes: for prefill buckets past the
    chunk size the score working set drops from O(S^2) to
    O(S * chunk). Strictly-future (q-chunk, kv-chunk) pairs are skipped
    at trace time (the causal half of the schedule), so the chunk grid
    is lower-triangular like the ring's causal mask.

    q, k, v: (B, H, S, D); returns (B, H, S, D) in q's dtype.
    """
    b, h, s, d = q.shape
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    if s <= chunk:
        mask = (jnp.arange(s)[:, None] >= jnp.arange(s)[None, :])[None, None]
        o, l, m = local_attention_block(q, k, v, mask, scale)
        return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    if s % chunk:
        raise ValueError("sequence %d is not a multiple of chunk %d "
                         "(prefill buckets are pow2 — pick a pow2 chunk)"
                         % (s, chunk))
    n = s // chunk
    outs = []
    for qi in range(n):
        q_blk = lax.slice_in_dim(q, qi * chunk, (qi + 1) * chunk, axis=2)
        q_pos = qi * chunk + jnp.arange(chunk)
        o_acc = jnp.zeros((b, h, chunk, d), jnp.float32)
        l_acc = jnp.zeros((b, h, chunk), jnp.float32)
        m_acc = jnp.full((b, h, chunk), -jnp.inf, jnp.float32)
        for ki in range(qi + 1):          # causal: only past/diag chunks
            k_blk = lax.slice_in_dim(k, ki * chunk, (ki + 1) * chunk,
                                     axis=2)
            v_blk = lax.slice_in_dim(v, ki * chunk, (ki + 1) * chunk,
                                     axis=2)
            if ki == qi:                  # diagonal chunk needs the mask
                k_pos = ki * chunk + jnp.arange(chunk)
                mask = (q_pos[:, None] >= k_pos[None, :])[None, None]
            else:
                mask = None
            o_blk, l_blk, m_blk = local_attention_block(
                q_blk, k_blk, v_blk, mask, scale)
            m_new = jnp.maximum(m_acc, m_blk)
            m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            alpha = jnp.exp(jnp.where(jnp.isneginf(m_acc), -jnp.inf,
                                      m_acc - m_safe))
            beta = jnp.exp(jnp.where(jnp.isneginf(m_blk), -jnp.inf,
                                     m_blk - m_safe))
            o_acc = o_acc * alpha[..., None] + o_blk * beta[..., None]
            l_acc = l_acc * alpha + l_blk * beta
            m_acc = m_new
        outs.append(o_acc / jnp.maximum(l_acc, 1e-30)[..., None])
    return jnp.concatenate(outs, axis=2).astype(q.dtype)


def _ring_attention_shard(q, k, v, axis_name: str, causal: bool,
                          scale: Optional[float]):
    """Per-shard body: q/k/v are the local sequence blocks
    (B, H, S_local, D); rotate k/v around the ring, accumulate online
    softmax."""
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    if scale is None:
        scale = 1.0 / np.sqrt(d)

    o_acc = jnp.zeros((b, h, s_q, d), jnp.float32)
    l_acc = jnp.zeros((b, h, s_q), jnp.float32)
    m_acc = jnp.full((b, h, s_q), -jnp.inf, jnp.float32)
    if hasattr(lax, "pvary"):
        # mark initial carries as varying over the ring axis so the scan
        # carry types match (shard_map vma typing in recent jax)
        o_acc, l_acc, m_acc = lax.pvary((o_acc, l_acc, m_acc), (axis_name,))

    q_pos = my_idx * s_q + jnp.arange(s_q)

    def body(i, carry):
        o_acc, l_acc, m_acc, k_cur, v_cur = carry
        kv_idx = (my_idx - i) % axis_size  # owner of the block we now hold
        if causal:
            k_pos = kv_idx * s_k + jnp.arange(s_k)
            mask = q_pos[:, None] >= k_pos[None, :]
            mask = mask[None, None]
        else:
            mask = None
        o_blk, l_blk, m_blk = local_attention_block(q, k_cur, v_cur, mask,
                                                    scale)
        # online softmax merge
        m_new = jnp.maximum(m_acc, m_blk)
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        alpha = jnp.exp(jnp.where(jnp.isneginf(m_acc), -jnp.inf,
                                  m_acc - m_safe))
        beta = jnp.exp(jnp.where(jnp.isneginf(m_blk), -jnp.inf,
                                 m_blk - m_safe))
        o_new = o_acc * alpha[..., None] + o_blk * beta[..., None]
        l_new = l_acc * alpha + l_blk * beta
        # rotate kv to the next device (neighbor exchange on ICI)
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return o_new, l_new, m_new, k_nxt, v_nxt

    o_acc, l_acc, m_acc, _, _ = lax.fori_loop(
        0, axis_size, body, (o_acc, l_acc, m_acc, k, v))
    out = o_acc / jnp.maximum(l_acc, 1e-30)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, axis_name: Optional[str] = None,
                   causal: bool = False, scale: Optional[float] = None):
    """Exact attention with the sequence axis sharded over ``axis_name``.

    q, k, v: (B, H, S, D) arrays (global view); S is sharded over the mesh
    axis. Returns (B, H, S, D) with the same sharding. ``axis_name=None``
    resolves to the legacy ``sp`` axis when the mesh carries it, else
    the unified SpecLayout's model axis (``tp``).
    """
    if axis_name is None:
        from .layout import resolve_model_axis
        axis_name = resolve_model_axis(mesh, "sp")
    elif axis_name not in mesh.axis_names:
        raise ValueError("mesh has no axis %r (axes: %s)"
                         % (axis_name, tuple(mesh.axis_names)))
    spec = P(None, None, axis_name, None)
    fn = shard_map(
        functools.partial(_ring_attention_shard, axis_name=axis_name,
                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def ring_self_attention(x, w_qkv, w_out, mesh: Mesh, num_heads: int,
                        axis_name: Optional[str] = None,
                        causal: bool = False):
    """Full self-attention layer with sequence-parallel ring attention:
    x (B, S, E) sharded on S; projections are local (no collective), only
    the kv ring moves data."""
    b, s, e = x.shape
    d = e // num_heads
    qkv = jnp.einsum("bse,ecf->bscf", x,
                     w_qkv.reshape(e, 3, e)).reshape(b, s, 3, num_heads, d)
    q = qkv[:, :, 0].transpose(0, 2, 1, 3)
    k = qkv[:, :, 1].transpose(0, 2, 1, 3)
    v = qkv[:, :, 2].transpose(0, 2, 1, 3)
    o = ring_attention(q, k, v, mesh, axis_name=axis_name, causal=causal)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, e)
    return jnp.einsum("bse,ef->bsf", o, w_out)
