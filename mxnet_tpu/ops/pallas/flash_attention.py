"""Blocked online-softmax (flash) attention as a Pallas TPU kernel.

The showcase custom kernel (SURVEY.md §2.22 calls Pallas ports "the only
real kernel engineering in the project"): attention with O(S) memory —
the S×S score matrix never leaves VMEM, materialized one
(block_q, block_k) tile at a time while running max/sum statistics fold
each tile into the output accumulator (Dao et al., FlashAttention;
Rabe & Staats, self-attention does not need O(n²) memory).

**The walk.** A tile is too little work to pay for a grid step of its
own (at d_head 64 a 512×512 tile is a third of a microsecond of the MXU),
so the grid hands a kernel a *chunk* — ``n_qb`` Q blocks by ``n_kv`` K/V
blocks — and the kernel walks the chunk's tiles in loops of its own
(``_Walk``). A chunk holds all of a head's rows when they fit
``_RESIDENT_BYTES`` of VMEM (``S * d_head * itemsize``, the lanes padded
to 128; 2048 rows of 64 bf16 values are 512 KB), else the largest run of
blocks that does; the grid is (batch*heads, chunks, chunks), one step a
head when the head is resident. Under ``causal`` a Q block's walk stops
at the diagonal, the mask is built on the tiles the diagonal crosses and
on no other, and a chunk wholly above the diagonal is named by no index
map (its step names its live neighbour again, so nothing is fetched).
``scale`` multiplies the Q block once, not every score.

Both kernels work a tile transposed, keys down the sublanes and queries
along the lanes, so every row statistic is a lane-dense (1, block_q) row
that broadcasts down the sublanes, and what is carried for a Q block —
the forward's accumulator, the backward's dQ — is (d_head, block_q) with
no lane left empty at d_head 64; it is transposed back once a Q block.

- Forward, ``_fa_kernel``: grid (batch*heads, Q chunks, K/V chunks).
  (m, l, acc) are float32 values carried along a Q block's row of tiles
  (and through VMEM scratch from one K/V chunk to the next when a head
  has several).
- Backward, ``_fa_bwd_kernel``, one pass (five products a tile, where a
  dQ kernel beside a dK/dV kernel make seven and two exponentials): grid
  (batch*heads, K/V chunks, Q chunks). Each tile rebuilds
  Pᵀ = exp(K Qᵀ - lse) once and gives dV += Pᵀ dO,
  dSᵀ = Pᵀ ∘ (V dOᵀ - Δ), dK += dSᵀ Q and dQᵀ += Kᵀ dSᵀ from it; dK and
  dV of the chunk accumulate in float32 VMEM scratch. ``exp(s - lse)``
  needs no running rescale: lse is the final statistic. With several K/V
  chunks each writes its own float32 slab of dQ and XLA adds them.

**The statistics.** lse and Δ = rowsum(dO ∘ O) (one fused reduce of
XLA's) cross HBM as ``(batch*heads, S/block_q, block_q)`` float32, one
lane-dense row a Q block, the size they have; a head's rows are one
resident block.

**Block sizes** are chosen in ``_blocks`` from (S, Sk) and the chunk
from (d_head, dtype) beside them; the caller's ``block_q`` / ``block_k``
are upper bounds. ``_fa_forward`` and ``_fa_backward`` are jitted, so a
model's layers share one trace and one lowering of each kernel, and the
calls are named ``_fa_kernel`` and ``_fa_bwd_kernel`` in the device's
trace. Building a kernel adds to the profiler counters
``flash_attn_tiles_visited`` (tiles of one head the walk does arithmetic
on) and ``flash_attn_tiles_grid`` (those, and tiles a grid step passes
over doing none): equal whenever a head is resident.

A Mosaic custom call is opaque to the SPMD partitioner (jax refuses to
lower one under a multi-device jit: "cannot be automatically
partitioned. Please wrap the call in a shard_map"). So a graph bound to
a mesh hands the op ``batch_rows`` (``executor.graph_function``) and the
whole differentiable attention runs inside one ``shard_map`` over the
batch axes: each batch row is independent, so every chip runs the forward
and the backward kernel on its own rows.

Off-TPU the same kernels run in interpreter mode (exact, slow) so the
CPU tests can check numerics; ``interpret=False`` off-TPU is an error.
The mode is ``rtc.resolve_interpret``'s: the inputs' devices, or under
tracing ``jax.default_backend()``.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ... import profiler as _profiler

__all__ = ["flash_attention"]

_NEG_INF = -1e30
_LANES = 128
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_TN = (((0,), (0,)), ((), ()))          # a.T @ b
# what one of a head's operands may hold of VMEM and stay resident
_RESIDENT_BYTES = 1 << 20
# every buffer of the largest resident case, twice over: v5e has 128 MiB
_VMEM_LIMIT_BYTES = 64 << 20


def _resident_blocks(n_blocks, block, d_head, itemsize):
    """Blocks of one operand a grid step holds: all of them if the head's
    rows fit ``_RESIDENT_BYTES``, else the largest divisor that does."""
    row_bytes = max(d_head, _LANES) * itemsize
    fit = max(1, _RESIDENT_BYTES // (block * row_bytes))
    return max(n for n in range(1, n_blocks + 1)
               if n_blocks % n == 0 and n <= fit)


class _Walk(object):
    """How the kernels of one call walk a head's tiles: the blocks, the
    chunks a grid step holds (``n_qb`` Q blocks by ``n_kv`` K/V blocks)
    and the loops over them. Indices count blocks. Building one counts
    its tiles in ``mx.profiler``."""

    def __init__(self, q, k, causal, block_q, block_k):
        D = q.shape[-1]
        self.causal, self.block_q, self.block_k = causal, block_q, block_k
        self.n_q = q.shape[1] // block_q
        self.n_k = k.shape[1] // block_k
        self.n_qb = _resident_blocks(self.n_q, block_q, D, q.dtype.itemsize)
        self.n_kv = _resident_blocks(self.n_k, block_k, D, k.dtype.itemsize)
        self.q_chunks = self.n_q // self.n_qb
        self.kv_chunks = self.n_k // self.n_kv
        self._count_tiles()

    def _live(self, qb, kb):
        return not self.causal or \
            kb * self.block_k <= (qb + 1) * self.block_q - 1

    def _count_tiles(self):
        """Tiles of one head the walk does arithmetic on, and those plus
        the tiles of grid steps that lie wholly above the diagonal."""
        visited = sum(self._live(qb, kb) for qb in range(self.n_q)
                      for kb in range(self.n_k))
        dead_steps = sum(not self._live(q0 + self.n_qb - 1, k0)
                         for q0 in range(0, self.n_q, self.n_qb)
                         for k0 in range(0, self.n_k, self.n_kv))
        _profiler.incr_counter("flash_attn_tiles_visited", visited)
        _profiler.incr_counter(
            "flash_attn_tiles_grid",
            visited + dead_steps * self.n_qb * self.n_kv)

    def kv_blocks(self, q_block, kv_chunk):
        """``(full, stop)``: of the chunk's K/V blocks ``[0, n_kv)``, Q
        block ``q_block`` walks ``[0, stop)``; ``[0, full)`` lie wholly
        under the diagonal and need no mask."""
        if not self.causal:
            return self.n_kv, self.n_kv
        first = kv_chunk * self.n_kv
        stop = jnp.clip(((q_block + 1) * self.block_q + self.block_k - 1)
                        // self.block_k - first, 0, self.n_kv)
        full = jnp.clip((q_block * self.block_q + 1) // self.block_k
                        - first, 0, stop)
        return full, stop

    def tiles(self, q_block, kv_chunk, tile, carry):
        """``carry`` through ``tile(kb, carry, masked)`` over the tiles of
        Q block ``q_block`` in this chunk, the masked ones last."""
        full, stop = self.kv_blocks(q_block, kv_chunk)
        carry = lax.fori_loop(
            0, full, functools.partial(tile, masked=False), carry)
        if self.causal:
            carry = lax.fori_loop(
                full, stop, functools.partial(tile, masked=True), carry)
        return carry

    def masked(self, s, q_block, kv_block):
        """A transposed tile's scores (keys down, queries along) with
        every key after its query sent to -1e30."""
        k_pos = kv_block * self.block_k + \
            lax.broadcasted_iota(jnp.int32, s.shape, 0)
        q_pos = q_block * self.block_q + \
            lax.broadcasted_iota(jnp.int32, s.shape, 1)
        return jnp.where(q_pos >= k_pos, s, _NEG_INF)


def _rows(i, size):
    """Rows ``[i * size, (i + 1) * size)`` of a ref."""
    import jax.experimental.pallas as pl
    return pl.ds(pl.multiple_of(i * size, size), size)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
               *, scale, walk):
    """Tiles are worked transposed, keys down the sublanes and queries
    along the lanes: m, l and lse are (1, block_q) rows, the accumulator
    is (d, block_q), and the output block is transposed back once."""
    import jax.experimental.pallas as pl

    block_q, block_k = walk.block_q, walk.block_k
    # program ids are read at the top level only: a pl.when body is a cond
    # branch, where the interpreter cannot resolve program_id
    q_chunk, kv_chunk = pl.program_id(1), pl.program_id(2)
    carried = walk.kv_chunks > 1    # (m, l, acc) pass from chunk to chunk
    D = q_ref.shape[-1]

    if carried:
        @pl.when(kv_chunk == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

    def q_rows(qb, _):
        rows = _rows(qb, block_q)
        q_block = q_chunk * walk.n_qb + qb
        q = (q_ref[0, rows, :].astype(jnp.float32) * scale) \
            .astype(q_ref.dtype)

        def tile(kb, carry, masked):
            m_prev, l_prev, acc = carry
            k = k_ref[0, _rows(kb, block_k), :]        # (block_k, d)
            v = v_ref[0, _rows(kb, block_k), :]
            s = lax.dot_general(k, q, _NT,
                                preferred_element_type=jnp.float32)
            if masked:
                s = walk.masked(s, q_block, kv_chunk * walk.n_kv + kb)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                     # (block_k, block_q)
            l_new = l_prev * alpha + jnp.sum(p, axis=0, keepdims=True)
            acc = acc * alpha + lax.dot_general(
                v, p.astype(v.dtype), _TN,
                preferred_element_type=jnp.float32)    # (d, block_q)
            return m_new, l_new, acc

        if carried:
            carry = (m_scr[_rows(qb, 1), :], l_scr[_rows(qb, 1), :],
                     acc_scr[qb])
        else:
            carry = (jnp.full((1, block_q), _NEG_INF, jnp.float32),
                     jnp.zeros((1, block_q), jnp.float32),
                     jnp.zeros((D, block_q), jnp.float32))
        m, l, acc = walk.tiles(q_block, kv_chunk, tile, carry)
        if carried:
            m_scr[_rows(qb, 1), :], l_scr[_rows(qb, 1), :] = m, l
            acc_scr[qb] = acc
        # after the last chunk these are the results; before it they are
        # overwritten where they lie
        denom = jnp.maximum(l, 1e-37)
        o_ref[0, rows, :] = (acc / denom).T.astype(o_ref.dtype)
        lse_ref[0, _rows(q_block, 1), :] = m + jnp.log(denom)

    lax.fori_loop(0, walk.n_qb, q_rows, None)


# jitted, so that a model's layers share one trace and one lowering of
# each kernel: traced anew for every layer the two kernels were 4 to 9 s
# of a warm start of the 8-layer training cell
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _fa_forward(q, k, v, scale, causal, block_q, block_k, interpret):
    """(out, lse): lse is ``(BH, S/block_q, block_q)``, a row a Q block."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = q.shape
    walk = _Walk(q, k, causal, block_q, block_k)
    q_rows, kv_rows = walk.n_qb * block_q, walk.n_kv * block_k

    def kv_map(b, i, j):
        if causal:  # a chunk above the diagonal names the last live one
            j = jnp.minimum(j, ((i + 1) * q_rows - 1) // kv_rows)
        return b, j, 0

    q_spec = pl.BlockSpec((1, q_rows, D), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, kv_rows, D), kv_map)
    return pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale, walk=walk),
        out_shape=(jax.ShapeDtypeStruct((BH, S, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, walk.n_q, block_q),
                                        jnp.float32)),
        grid=(BH, walk.q_chunks, walk.kv_chunks),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=(q_spec, pl.BlockSpec((1, walk.n_q, block_q),
                                        lambda b, i, j: (b, 0, 0))),
        scratch_shapes=[
            pltpu.VMEM((walk.n_qb, block_q), jnp.float32),
            pltpu.VMEM((walk.n_qb, block_q), jnp.float32),
            pltpu.VMEM((walk.n_qb, D, block_q), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="_fa_kernel",      # the device trace's name for it
    )(q, k, v)


def _fa_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale, walk):
    """One pass over the tiles of a (K/V chunk, Q chunk) pair, worked
    transposed as the forward's: lse and Δ are the (1, block_q) rows they
    are stored as, and dq of a Q block is carried as (d, block_q)."""
    import jax.experimental.pallas as pl

    block_q, block_k = walk.block_q, walk.block_k
    kv_chunk, q_chunk = pl.program_id(1), pl.program_id(2)
    D = q_ref.shape[-1]

    @pl.when(q_chunk == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def q_rows(qb, _):
        rows = _rows(qb, block_q)
        q_block = q_chunk * walk.n_qb + qb
        do = do_ref[0, rows, :]                        # (block_q, d)
        q = (q_ref[0, rows, :].astype(jnp.float32) * scale).astype(do.dtype)
        lse = lse_ref[0, _rows(q_block, 1), :]         # (1, block_q)
        delta = delta_ref[0, _rows(q_block, 1), :]

        def tile(kb, dq, masked):
            cols = _rows(kb, block_k)
            k = k_ref[0, cols, :]                      # (block_k, d)
            v = v_ref[0, cols, :]
            s = lax.dot_general(k, q, _NT,
                                preferred_element_type=jnp.float32)
            if masked:
                s = walk.masked(s, q_block, kv_chunk * walk.n_kv + kb)
            p = jnp.exp(s - lse)                       # (block_k, block_q)
            dv_scr[cols, :] += jnp.dot(
                p.astype(do.dtype), do, preferred_element_type=jnp.float32)
            dp = lax.dot_general(v, do, _NT,
                                 preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(q.dtype)
            # q carries the scale, so dk has it; dq takes it at the end
            dk_scr[cols, :] += jnp.dot(
                ds, q, preferred_element_type=jnp.float32)
            return dq + lax.dot_general(
                k, ds, _TN, preferred_element_type=jnp.float32)

        dq = walk.tiles(q_block, kv_chunk, tile,
                        jnp.zeros((D, block_q), jnp.float32))
        dq_ref[0, 0, rows, :] = (dq.T * scale).astype(dq_ref.dtype)

    lax.fori_loop(0, walk.n_qb, q_rows, None)

    @pl.when(q_chunk == walk.q_chunks - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _fa_backward(q, k, v, out, lse, do, scale, causal, block_q, block_k,
                 interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = q.shape
    Sk = k.shape[1]
    walk = _Walk(q, k, causal, block_q, block_k)
    q_rows, kv_rows = walk.n_qb * block_q, walk.n_kv * block_k
    # Δ_i = rowsum(dO ⊙ O): one fused elementwise+reduce of XLA's
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(lse.shape)

    def q_map(b, j, i):
        if causal:  # a chunk above the diagonal names the first live one
            i = jnp.maximum(i, (j * kv_rows) // q_rows)
        return b, i, 0

    q_spec = pl.BlockSpec((1, q_rows, D), q_map)
    kv_spec = pl.BlockSpec((1, kv_rows, D), lambda b, j, i: (b, j, 0))
    stat_spec = pl.BlockSpec((1, walk.n_q, block_q),
                             lambda b, j, i: (b, 0, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_kernel, scale=scale, walk=walk),
        # each K/V chunk's share of dq is a slab of its own
        out_shape=(jax.ShapeDtypeStruct(
            (walk.kv_chunks, BH, S, D),
            q.dtype if walk.kv_chunks == 1 else jnp.float32),
            jax.ShapeDtypeStruct((BH, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), v.dtype)),
        grid=(BH, walk.kv_chunks, walk.q_chunks),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
        out_specs=(
            pl.BlockSpec((1, 1, q_rows, D), lambda b, j, i: (j, b, i, 0)),
            kv_spec, kv_spec),
        scratch_shapes=[pltpu.VMEM((kv_rows, D), jnp.float32),
                        pltpu.VMEM((kv_rows, D), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="_fa_bwd_kernel",
    )(q, k, v, do, lse, delta)
    dq = dq[0] if walk.kv_chunks == 1 else dq.sum(0).astype(q.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fa(q, k, v, scale, causal, block_q, block_k, interpret):
    return _fa_forward(q, k, v, scale, causal, block_q, block_k,
                       interpret)[0]


def _fa_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    out, lse = _fa_forward(q, k, v, scale, causal, block_q, block_k,
                           interpret)
    return out, (q, k, v, out, lse)


def _fa_bwd(scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _fa_backward(q, k, v, out, lse, g.astype(q.dtype), scale,
                        causal, block_q, block_k, interpret)


_fa.defvjp(_fa_fwd, _fa_bwd)


def _blocks(S, Sk, block_q, block_k):
    """(block_q, block_k) for these lengths, the caller's as upper bounds:
    as large as they allow (on the chip 512 x 512 beat every smaller tile
    at d_head 64, bf16; head width and operand width size the chunk, not
    the tile). Queries are padded to their block; keys cannot be, so
    their block is the largest lane-aligned one that divides ``Sk``, if
    there is one."""
    block_q, block_k = min(block_q, S), min(block_k, Sk)
    dividing = [b for b in range(_LANES, block_k + 1, _LANES) if Sk % b == 0]
    return block_q, max(dividing, default=block_k)


def flash_attention(q, k, v, causal=False, scale=None, block_q=512,
                    block_k=512, interpret=None, batch_rows=None):
    """Flash attention over (B, H, S, D) inputs.

    The query length is padded to the Q block (padded rows are computed
    then sliced off — they influence nothing). The key length must divide
    ``block_k`` — padded keys would need in-kernel masking to stay out of
    the softmax, so an unaligned key length raises instead of silently
    attending to padding. ``causal`` assumes S == Sk (self-attention).
    ``block_q`` / ``block_k`` bound the tile; the tile itself is chosen
    from the shapes (module docstring). Gradients flow through one fused
    Pallas backward kernel (the forward saves the per-row logsumexp); the
    S×S matrix never reaches HBM in either direction.

    ``batch_rows``: ``(mesh, axes)`` when the inputs live on a mesh with
    the batch dimension sharded over ``axes`` (module docstring); heads
    sharded over another axis are gathered first.
    """
    S, D = q.shape[2], q.shape[3]
    Sk = k.shape[2]
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    from ...rtc import resolve_interpret
    if interpret is None:
        interpret = resolve_interpret((q, k, v))
    elif not interpret and resolve_interpret((q, k, v)):
        raise ValueError(
            "flash_attention(interpret=False): the compiled Mosaic kernel "
            "needs a TPU, but the inputs are off-TPU (default backend %r)"
            % jax.default_backend())

    bq, bk = _blocks(S, Sk, block_q, block_k)
    if Sk % bk:
        raise ValueError(
            "flash_attention: key length %d must be a multiple of block_k "
            "%d (padded keys would join the softmax)" % (Sk, bk))
    pad_q = (-S) % bq

    def on_rows(q, k, v):
        B, H = q.shape[:2]
        qf = q.reshape(B * H, S, D)
        kf = k.reshape(B * H, Sk, D)
        vf = v.reshape(B * H, Sk, D)
        if pad_q:
            qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
        out = _fa(qf, kf, vf, float(scale), bool(causal), bq, bk,
                  bool(interpret))
        if pad_q:
            out = out[:, :S]
        return out.reshape(B, H, S, D)

    if batch_rows is None:
        return on_rows(q, k, v)
    from jax.sharding import PartitionSpec as P
    mesh, axes = batch_rows
    spec = P(axes or None)
    return jax.shard_map(on_rows, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)(q, k, v)


# registered as an ordinary framework op so Symbol/Gluon graphs can use it
from ..registry import register as _register  # noqa: E402


@_register("FlashAttention", num_inputs=3,
           aliases=("_contrib_FlashAttention",))
def _flash_attention_op(q, k, v, causal=False, scale=None, block_q=512,
                        block_k=512, interpret=None, _batch_rows=None):
    """Pallas flash attention over (B, H, S, D) q/k/v (see module
    docstring; the mx.rtc escape-hatch showcase kernel). Inside a bound
    symbol graph the inputs are tracers, so ``interpret=None`` follows
    ``jax.default_backend()``, and a graph bound to a mesh supplies
    ``_batch_rows``."""
    return flash_attention(q, k, v, causal=causal, scale=scale,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret, batch_rows=_batch_rows)
