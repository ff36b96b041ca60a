"""Blocked online-softmax (flash) attention as a Pallas TPU kernel.

The showcase custom kernel (SURVEY.md §2.22 calls Pallas ports "the only
real kernel engineering in the project"): attention with O(S) memory —
the S×S score matrix never leaves VMEM, materialized one
(BLOCK_Q, BLOCK_K) tile at a time while running max/sum statistics fold
each tile into the output accumulator (Dao et al., FlashAttention;
Rabe & Staats, self-attention does not need O(n²) memory).

Kernel layout: grid (batch*heads, S/BLOCK_Q, S/BLOCK_K); the innermost
grid axis walks KV tiles, carrying (m, l, acc) in VMEM scratch that lives
across grid steps; the normalized output tile is written on the last KV
step. QKᵀ and PV both hit the MXU with fp32 accumulation.

Backward is fused too (FlashAttention-2 style): the forward additionally
writes the per-row logsumexp, and two Pallas kernels — one accumulating
dQ over KV tiles, one accumulating dK/dV over Q tiles — rebuild each
P tile as ``exp(s - lse)`` so the S×S probability matrix never hits HBM
in either direction. ``exp(s - lse)`` needs no running rescale: lse is
the final statistic, making the backward tiles embarrassingly
order-independent (unlike the forward's online softmax).

A Mosaic custom call is opaque to the SPMD partitioner (jax refuses to
lower one under a multi-device jit: "cannot be automatically
partitioned. Please wrap the call in a shard_map"). So a graph bound to
a mesh hands the op ``batch_rows`` (``executor.graph_function``) and the
whole differentiable attention runs inside one ``shard_map`` over the
batch axes: each batch row is independent, so every chip runs the forward
and both backward kernels on its own rows.

Off-TPU the same kernel runs in interpreter mode (exact, slow) so the
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

__all__ = ["flash_attention"]

_NEG_INF = -1e30
# Inside the kernels lse/delta ride as (BH, S, _LANES) with the row value
# replicated across lanes: Mosaic wants >=2D tiles whose last block dim
# divides 128 OR equals the array dim. In HBM the tiled layout pads that
# minor dim to 128 lanes whatever _LANES is (16x for 8), so the lane form
# is only ever a transient around a kernel call: the residual the forward
# saves for the backward is the (BH, S) column.
_LANES = 8


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
               *, scale, causal, block_q, block_k):
    import jax.experimental.pallas as pl

    # program ids are read at the top level only: a pl.when body is a cond
    # branch, where the interpreter cannot resolve program_id
    q_step = pl.program_id(1)
    kv_step = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(kv_step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: a KV tile strictly above the diagonal band contributes
    # nothing — skip its matmuls entirely (~2x for long sequences)
    live = (kv_step * block_k <= (q_step + 1) * block_q - 1) \
        if causal else True

    @pl.when(live)
    def _update():
        q = q_ref[0]                               # (block_q, d)
        k = k_ref[0]                               # (block_k, d)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (block_q, block_k)

        if causal:
            q_pos = q_step * block_q + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = kv_step * block_k + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)

        m_prev = m_scr[:, 0]                       # (block_q,)
        l_prev = l_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_scr[:] = acc_scr[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:, 0] = m_new
        l_scr[:, 0] = l_new

    @pl.when(kv_step == n_kv - 1)
    def _finish():
        denom = jnp.maximum(l_scr[:, 0], 1e-37)
        o_ref[0] = (acc_scr[:] / denom[:, None]).astype(o_ref.dtype)
        # lane-replicated across the _LANES trailing dim (see _LANES note)
        lse_ref[0] = jnp.broadcast_to(
            (m_scr[:, 0] + jnp.log(denom))[:, None], lse_ref[0].shape)


def _fa_forward(q, k, v, scale, causal, block_q, block_k, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = q.shape
    Sk = k.shape[1]
    nq = S // block_q
    nk = Sk // block_k
    kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k)
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((BH, S, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, S, _LANES), jnp.float32)),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dq_scr, *, scale, causal, block_q, block_k):
    """dQ accumulator: grid (BH, nq, nk), KV tiles innermost.

    Rebuilds P = exp(s - lse) from the saved logsumexp (exact — lse is the
    final softmax statistic, so no online rescaling is needed), then
    dS = P * (dO·Vᵀ - Δ) and dQ += dS·K, all tiles resident in VMEM.
    """
    import jax.experimental.pallas as pl

    i = pl.program_id(1)
    j = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    live = (j * block_k <= (i + 1) * block_q - 1) if causal else True

    @pl.when(live)
    def _update():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = i * block_q + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = j * block_k + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, 0:1])            # (bq, bk)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, bk)
        ds = p * (dp - delta_ref[0][:, 0:1]) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == n_kv - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                       block_q, block_k):
    """dK/dV accumulator: grid (BH, nk, nq), Q tiles innermost.

    dV += Pᵀ·dO and dK += dSᵀ·Q per Q tile; writing per-KV-tile outputs
    from a KV-major grid means no cross-tile races and no atomics.
    """
    import jax.experimental.pallas as pl

    j = pl.program_id(1)
    i = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # causal: a Q tile entirely above (before) this KV tile sees none of it
    live = ((i + 1) * block_q - 1 >= j * block_k) if causal else True

    @pl.when(live)
    def _update():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if causal:
            q_pos = i * block_q + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = j * block_k + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, 0:1])              # (bq, bk)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bk, d)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, bk)
        ds = p * (dp - delta_ref[0][:, 0:1]) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bk, d)

    @pl.when(i == n_q - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _fa_backward(q, k, v, out, lse, do, scale, causal, block_q, block_k,
                 interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = q.shape
    Sk = k.shape[1]
    nq = S // block_q
    nk = Sk // block_k
    # Δ_i = rowsum(dO ⊙ O): tiny elementwise+reduce, XLA fuses it
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                              # (BH, S)
    delta = jnp.broadcast_to(delta[:, :, None], (BH, S, _LANES))
    lse = jnp.broadcast_to(lse[:, :, None], (BH, S, _LANES))

    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        out_shape=(jax.ShapeDtypeStruct((BH, Sk, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, Sk, D), v.dtype)),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fa(q, k, v, scale, causal, block_q, block_k, interpret):
    return _fa_forward(q, k, v, scale, causal, block_q, block_k,
                       interpret)[0]


def _fa_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    out, lse = _fa_forward(q, k, v, scale, causal, block_q, block_k,
                           interpret)
    return out, (q, k, v, out, lse[:, :, 0])


def _fa_bwd(scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _fa_backward(q, k, v, out, lse, g.astype(q.dtype), scale,
                        causal, block_q, block_k, interpret)


_fa.defvjp(_fa_fwd, _fa_bwd)


def flash_attention(q, k, v, causal=False, scale=None, block_q=512,
                    block_k=512, interpret=None, batch_rows=None):
    """Flash attention over (B, H, S, D) inputs.

    The query length is padded to ``block_q`` (padded rows are computed
    then sliced off — they influence nothing). The key length must divide
    ``block_k`` — padded keys would need in-kernel masking to stay out of
    the softmax, so an unaligned key length raises instead of silently
    attending to padding. ``causal`` assumes S == Sk (self-attention).
    Gradients flow through fused Pallas dQ and dK/dV kernels (the forward
    saves the per-row logsumexp); the S×S matrix never reaches HBM in
    either direction.

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

    bq = min(block_q, S)
    bk = min(block_k, Sk)
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
