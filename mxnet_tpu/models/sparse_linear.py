"""A decoder stack whose layers are of two kinds, as pure JAX functions:
block-sparse attention that selects a fixed number of key blocks a query,
and lightning (linear) attention whose memory is a recurrent state.

This is the layer mathematics that ``serve/sparse_linear.py`` builds its
prefill and decode programs from (``model_type`` ``minicpm_sala``; the
equations are written out in ``docs/architecture/serving_families.md``
and, independently, in the benchmark's plain reference).

* The stack: ``x0 = scale_emb * E[token]``; each layer ``h = x + s *
  Mixer(RMS(x))``, ``y = h + s * MLP(RMS(h))`` with ``s = scale_depth /
  sqrt(published depth)``; the head reads ``RMS(x) / (hidden_size /
  dim_model_base)``.
* A ``minicpm4`` layer: grouped-query attention (``group`` query heads a
  key/value head) without rotary, RMS norm over every q and k head,
  and a selection a query and key/value head: compressed keys ``Kc_j =
  mean(k[stride j : stride j + kernel])``, the group's summed softmax over
  them, max-pooled to blocks; the first block and the window's blocks are
  forced, and the ``topk`` best blocks are attended
  (:func:`select_blocks`, :func:`attend_selected`, :func:`attend_blocks`).
  An output gate ``sigmoid(W_g u)`` before ``W_o``.
* A ``lightning-attn`` layer: per head ``S_t = lambda_h S_(t-1) + k_t
  v_t^T``, ``o_t = S_t^T q_t / sqrt(d)`` with a fixed decay a head, rotary
  on q and k, RMS norm over the joined heads, an output gate
  (:func:`lightning_step` a token, :func:`lightning_chunk` a chunk).

Weights keep the dtype they are given (bfloat16 as served, float32 in
tests); every product takes its operands in that dtype and accumulates in
float32; norms, rotary angles, softmax, selection scores, decay and the
recurrent state are float32.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from .layers import (dense, gated_mlp, layer_params, product,  # noqa: F401
                     rms_norm, rope, softmax_where, yarn_frequencies)

__all__ = ["Arch", "param_shapes", "SPARSE", "LIGHTNING"]

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
_BIG = 1e30
_SPARSE_DEFAULTS = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                    "init_blocks": 1, "window_size": 2048, "topk": 64}


class Arch:
    """The architecture's description, as ``GenerativeServer`` is told it:
    the published configuration's keys, ``mixer_types`` the kinds of the
    layers held, ``depth_scale_layers`` the published depth the residual
    scale is taken from (the held depth where absent),
    ``max_position_embeddings`` a slot's length, ``sparse_config`` the
    selection's sizes."""

    def __init__(self, doc: Dict[str, Any]):
        need = ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "intermediate_size", "mixer_types",
                "lightning_nh", "lightning_head_dim", "rms_norm_eps",
                "scale_emb", "scale_depth", "dim_model_base",
                "max_position_embeddings", "vocab_size")
        missing = [k for k in need if k not in doc]
        if missing:
            raise ValueError("architecture description lacks %s" % missing)
        self.d = int(doc["hidden_size"])
        self.heads = int(doc["num_attention_heads"])
        self.kv_heads = int(doc["num_key_value_heads"])
        self.d_head = int(doc["head_dim"])
        if self.heads % self.kv_heads:
            raise ValueError("%d query heads for %d key/value heads"
                             % (self.heads, self.kv_heads))
        self.group = self.heads // self.kv_heads
        self.kv_row = self.kv_heads * self.d_head
        self.l_heads = int(doc["lightning_nh"])
        self.l_d = int(doc["lightning_head_dim"])
        if int(doc.get("lightning_nkv", self.l_heads)) != self.l_heads:
            raise ValueError("lightning layers with fewer key/value heads "
                             "than heads are not served")
        self.d_ff = int(doc["intermediate_size"])
        self.eps = float(doc["rms_norm_eps"])
        self.mixer_types: List[str] = list(doc["mixer_types"])
        bad = sorted(set(self.mixer_types) - {SPARSE, LIGHTNING})
        if bad:
            raise ValueError("mixer_types %s are not served" % bad)
        self.num_layers = len(self.mixer_types)
        if int(doc.get("num_hidden_layers", self.num_layers)) \
                != self.num_layers:
            raise ValueError("num_hidden_layers %s, mixer_types name %d"
                             % (doc["num_hidden_layers"], self.num_layers))
        self.sparse_layers = [i for i, k in enumerate(self.mixer_types)
                              if k == SPARSE]
        self.lightning_layers = [i for i, k in enumerate(self.mixer_types)
                                 if k == LIGHTNING]
        depth = int(doc.get("depth_scale_layers", self.num_layers))
        self.residual_scale = float(doc["scale_depth"]) / float(np.sqrt(depth))
        self.scale_emb = float(doc["scale_emb"])
        self.head_divisor = self.d / float(doc["dim_model_base"])
        self.max_seq = int(doc["max_position_embeddings"])
        self.vocab_size = int(doc["vocab_size"])
        self.dtype = str(doc.get("dtype", "bfloat16"))
        if doc.get("attn_use_rope", False):
            raise ValueError("sparse layers with a rotary are not served")
        if not doc.get("lightning_use_rope", True):
            raise ValueError("lightning layers without a rotary are not "
                             "served")
        self.rope_freq, self.rope_mscale, _ = yarn_frequencies(
            self.l_d, float(doc.get("rope_theta", 10000.0)),
            doc.get("rope_scaling"))
        self.score_scale = self.d_head ** -0.5
        self.lightning_scale = self.l_d ** -0.5
        # decay rate a head: lambda_h = exp(-rate_h), the ALiBi slopes
        self.decay_rate = np.asarray(
            [2.0 ** (-8.0 * (h + 1) / self.l_heads)
             for h in range(self.l_heads)], np.float32)
        sc = dict(_SPARSE_DEFAULTS, **(doc.get("sparse_config") or {}))
        self.kernel = int(sc["kernel_size"])
        self.stride = int(sc["kernel_stride"])
        self.block = int(sc["block_size"])
        self.init_blocks = int(sc["init_blocks"])
        self.window_blocks = int(sc["window_size"]) // self.block
        self.topk = int(sc["topk"])
        if self.kernel != 2 * self.stride or self.block != 4 * self.stride:
            raise ValueError("the selection is served for a kernel of two "
                             "strides and a block of four")
        if self.max_seq % self.block:
            raise ValueError("max_position_embeddings %d is not whole "
                             "blocks of %d" % (self.max_seq, self.block))


def layer_shapes(arch: Arch, kind: str) -> Dict[str, Tuple[int, ...]]:
    d = arch.d
    out = {"ln1_gamma": (d,), "ln2_gamma": (d,),
           "ffn_gate_weight": (arch.d_ff, d), "ffn_up_weight": (arch.d_ff, d),
           "ffn_down_weight": (d, arch.d_ff)}
    if kind == SPARSE:
        hd = arch.heads * arch.d_head
        out.update({"att_q_weight": (hd, d), "att_k_weight": (arch.kv_row, d),
                    "att_v_weight": (arch.kv_row, d),
                    "att_q_norm_gamma": (arch.d_head,),
                    "att_k_norm_gamma": (arch.d_head,),
                    "att_gate_weight": (hd, d), "att_o_weight": (d, hd)})
    else:
        hd = arch.l_heads * arch.l_d
        out.update({"att_q_weight": (hd, d), "att_k_weight": (hd, d),
                    "att_v_weight": (hd, d),
                    "att_q_norm_gamma": (arch.l_d,),
                    "att_k_norm_gamma": (arch.l_d,),
                    "att_out_norm_gamma": (hd,),
                    "att_gate_weight": (hd, d), "att_o_weight": (d, hd)})
    return out


def param_shapes(arch: Arch) -> Dict[str, Tuple[int, ...]]:
    """name -> shape of every leaf the stack is served from; a weight
    lies ``(out, in)``."""
    out = {"tok_embed_weight": (arch.vocab_size, arch.d),
           "final_ln_gamma": (arch.d,),
           "lm_head_weight": (arch.vocab_size, arch.d)}
    for i, kind in enumerate(arch.mixer_types):
        out.update({"layer%d_%s" % (i, n): s
                    for n, s in layer_shapes(arch, kind).items()})
    return out


def check_params(arch: Arch, params) -> None:
    want = param_shapes(arch)
    missing = sorted(set(want) - set(params))
    if missing:
        raise ValueError("sparse_linear: parameters missing: %s" % missing[:6])
    for name, shape in want.items():
        if tuple(params[name].shape) != tuple(shape):
            raise ValueError("sparse_linear: %s has shape %s, the "
                             "description gives %s"
                             % (name, tuple(params[name].shape), tuple(shape)))


# ------------------------------------------------------------ the stack


def embed(arch: Arch, params, tokens):
    import jax.numpy as jnp
    return arch.scale_emb * params["tok_embed_weight"][tokens].astype(
        jnp.float32)


def head(arch: Arch, params, x):
    """Logits of rows ``x (N, D)``."""
    h = rms_norm(x, params["final_ln_gamma"], arch.eps) / arch.head_divisor
    return dense(h, params["lm_head_weight"])


def mlp_half(arch: Arch, p, x):
    return x + arch.residual_scale * gated_mlp(
        rms_norm(x, p["ln2_gamma"], arch.eps), p["ffn_gate_weight"],
        p["ffn_up_weight"], p["ffn_down_weight"])


def gated_out(p, o, u):
    """``W_o (o * sigmoid(W_g u))``: ``o (N, H*d)`` the heads side by
    side, ``u`` the layer's normed input."""
    import jax
    return dense(o * jax.nn.sigmoid(dense(u, p["att_gate_weight"])),
                 p["att_o_weight"])


# --------------------------------------------------- block-sparse attention


def sparse_project(arch: Arch, p, u):
    """Of the normed input ``u (N, D)``: the normed queries ``(N, KV,
    group, d)`` (query head ``group * g + i`` reads key/value head ``g``)
    and the rows the cache keeps, ``k`` (normed) and ``v``, ``(N, KV *
    d)`` each."""
    n = u.shape[0]
    q = rms_norm(dense(u, p["att_q_weight"]).reshape(
        n, arch.kv_heads, arch.group, arch.d_head),
        p["att_q_norm_gamma"], arch.eps)
    k = rms_norm(dense(u, p["att_k_weight"]).reshape(
        n, arch.kv_heads, arch.d_head), p["att_k_norm_gamma"], arch.eps)
    return q, k.reshape(n, arch.kv_row), dense(u, p["att_v_weight"])


def compress_groups(arch: Arch, rows):
    """Means of ``stride`` rows each of ``rows (R, W)``, float32: ``(R /
    stride, W)``. A compressed key is the mean of two neighbours."""
    import jax.numpy as jnp
    r, w = rows.shape
    return jnp.mean(rows.astype(jnp.float32).reshape(
        r // arch.stride, arch.stride, w), axis=1)


def select_blocks(arch: Arch, s, t, n_blocks: int):
    """The blocks a query attends. ``s (N, KV, group, J)``: the scaled
    scores of the group's heads against the ``J = 4 n_blocks`` compressed
    keys of the query's context; ``t (N,)`` the query's position.
    Returns ``(idx (N, KV, K) int32, n_valid (N,) int32)``, ``K =
    min(topk, n_blocks)``: the first ``n_valid`` entries of ``idx`` are
    the blocks selected (forced ones first, then by falling score, ties to
    the lower index), the rest name blocks past the query's own."""
    import jax.numpy as jnp
    from jax import lax
    j = jnp.arange(s.shape[-1], dtype=jnp.int32)
    done = (arch.stride * j + arch.kernel)[None, :] <= (t + 1)[:, None]
    done = done[:, None, None, :]
    p = jnp.where(done, softmax_where(s, done), 0.0)
    sg = jnp.sum(p, axis=2)                                 # (N, KV, J)
    # B(b) = max of sg over kernels 4b-1 .. 4b+3
    padded = jnp.pad(sg, ((0, 0), (0, 0), (1, 0)))
    four = jnp.max(padded[..., :4 * n_blocks].reshape(
        sg.shape[:2] + (n_blocks, 4)), axis=-1)
    score = jnp.maximum(four, padded[..., 4::4])            # (N, KV, nb)
    b = jnp.arange(n_blocks, dtype=jnp.int32)[None, :]
    own = (t // arch.block)[:, None]
    forced = (b < arch.init_blocks) | (b > own - arch.window_blocks)
    score = jnp.where(forced[:, None, :], _BIG, score)
    score = jnp.where((b <= own)[:, None, :], score, -_BIG)
    k = min(arch.topk, n_blocks)
    idx = lax.top_k(score, k)[1].astype(jnp.int32)
    return idx, jnp.minimum(own[:, 0] + 1, k).astype(jnp.int32)


def attend_selected(arch: Arch, q, k_rows, v_rows, idx, n_valid, t):
    """Attention of one query a row over the blocks ``idx`` names, read by
    a gather (the XLA path of a decode step; the Pallas kernel of
    ``ops/pallas/sparse_decode_attention.py`` computes the same). ``q (N,
    KV, group, d)``; ``k_rows``/``v_rows (N, S, KV * d)`` each query's own
    slot; ``idx``/``n_valid`` from :func:`select_blocks`; ``t (N,)``.
    Returns the heads' outputs side by side, ``(N, H * d)``."""
    import jax.numpy as jnp
    n, s_len, _ = k_rows.shape
    kv, blk, dh = arch.kv_heads, arch.block, arch.d_head
    at = (jnp.arange(n)[:, None, None], idx, slice(None),
          jnp.arange(kv)[None, :, None])

    def blocks(rows):
        return rows.reshape(n, s_len // blk, blk, kv, dh)[at]  # (N,KV,K,B,d)
    kb, vb = blocks(k_rows), blocks(v_rows)
    s = product("ngid,ngkbd->ngikb", q, kb) * arch.score_scale
    key_pos = idx[..., None] * blk + jnp.arange(blk, dtype=jnp.int32)
    keep = (key_pos <= t[:, None, None, None]) & (
        jnp.arange(idx.shape[-1])[None, None, :, None]
        < n_valid[:, None, None, None])
    shape = s.shape
    a = softmax_where(s.reshape(shape[:3] + (-1,)),
                      keep.reshape(n, kv, 1, -1)).reshape(shape)
    o = product("ngikb,ngkbd->ngid", a, vb)
    return o.reshape(n, -1)


def attend_blocks(arch: Arch, q, k_ctx, v_ctx, t, idx=None, n_valid=None):
    """Attention of a block of queries over one context under the causal
    mask and, where ``idx`` is given, the selection as a block-level mask
    (a prefill chunk). ``q (N, KV, group, d)``; ``k_ctx``/``v_ctx (S, KV *
    d)``; ``t (N,)``. Returns ``(N, H * d)``."""
    import jax.numpy as jnp
    n = q.shape[0]
    kv, blk, dh = arch.kv_heads, arch.block, arch.d_head
    s_len = k_ctx.shape[0]
    keep = (jnp.arange(s_len, dtype=jnp.int32)[None, :]
            <= t[:, None])[:, None, :]                       # (N, 1, S)
    if idx is not None:
        chosen = jnp.arange(idx.shape[-1])[None, None, :] \
            < n_valid[:, None, None]
        picked = jnp.zeros((n, kv, s_len // blk), bool).at[
            jnp.arange(n)[:, None, None], jnp.arange(kv)[None, :, None],
            idx].set(chosen)
        keep = keep & jnp.repeat(picked, blk, axis=-1)       # (N, KV, S)
    s = product("ngid,sgd->ngis", q, k_ctx.reshape(s_len, kv, dh)) \
        * arch.score_scale
    a = softmax_where(s, keep[:, :, None, :])
    o = product("ngis,sgd->ngid", a, v_ctx.reshape(s_len, kv, dh))
    return o.reshape(n, -1)


# ------------------------------------------------------ lightning attention


def lightning_project(arch: Arch, p, u, pos):
    """Of the normed input ``u (N, D)`` at positions ``pos (N,)``: ``q``,
    ``k`` (normed, rotated) and ``v``, ``(N, H, d)`` float32 each."""
    n = u.shape[0]
    shape = (n, arch.l_heads, arch.l_d)
    q = rms_norm(dense(u, p["att_q_weight"]).reshape(shape),
                 p["att_q_norm_gamma"], arch.eps)
    k = rms_norm(dense(u, p["att_k_weight"]).reshape(shape),
                 p["att_k_norm_gamma"], arch.eps)
    q = rope(q, pos, arch.rope_freq, arch.rope_mscale)
    k = rope(k, pos, arch.rope_freq, arch.rope_mscale)
    return q, k, dense(u, p["att_v_weight"]).reshape(shape)


def lightning_out(arch: Arch, p, o, u):
    """The joined heads ``o (N, H * d)`` through the output norm, the gate
    and ``W_o``."""
    return gated_out(p, rms_norm(o, p["att_out_norm_gamma"], arch.eps), u)


def lightning_step(arch: Arch, state, q, k, v):
    """One token a row: ``state (N, H, d, d)`` float32 -> ``(S_t, o_t (N,
    H * d))`` with ``S_t = lambda S + k v^T`` and ``o_t = S_t^T q /
    sqrt(d)``, all in float32."""
    import jax.numpy as jnp
    lam = jnp.exp(-jnp.asarray(arch.decay_rate))[None, :, None, None]
    new = lam * state + k[..., :, None] * v[..., None, :]
    o = jnp.sum(new * q[..., :, None], axis=-2) * arch.lightning_scale
    return new, o.reshape(o.shape[0], -1)


def lightning_chunk(arch: Arch, state, q, k, v, n_real):
    """A chunk of ``C`` tokens of one sequence, of which the first
    ``n_real`` are real: ``state (H, d, d)`` float32 before the chunk ->
    ``(state after its last real token, O (C, H * d))``. ``O = ((Q K^T) *
    D) V + (Q * lambda^(i+1)) S``, ``D_ij = lambda^(i-j)`` for ``i >= j``;
    ``S' = lambda^n S + (K * lambda^(n-1-j))^T V`` over the real rows."""
    import jax.numpy as jnp
    from jax import lax
    c = q.shape[0]
    dt = jnp.dtype(arch.dtype)
    rate = jnp.asarray(arch.decay_rate)                      # (H,)
    i = jnp.arange(c, dtype=jnp.float32)
    real = (jnp.arange(c) < n_real)
    gap = i[:, None] - i[None, :]                            # i - j
    decay = jnp.where((gap >= 0)[None], jnp.exp(
        -rate[:, None, None] * jnp.maximum(gap, 0.0)[None]), 0.0)
    a = product("ihd,jhd->hij", q, k.astype(dt)) * decay
    intra = product("hij,jhd->ihd", a, v.astype(dt))
    q_in = q * jnp.exp(-rate[None, :] * (i + 1.0)[:, None])[..., None]
    # the state is read in float32, whole: no operand of it is rounded
    inter = jnp.einsum("ihk,hkv->ihv", q_in, state,
                       precision=lax.Precision.HIGHEST)
    o = (intra + inter) * arch.lightning_scale
    left = jnp.maximum(n_real.astype(jnp.float32) - 1.0 - i, 0.0)
    k_out = jnp.where(real[:, None, None], k * jnp.exp(
        -rate[None, :] * left[:, None])[..., None], 0.0)
    new = jnp.exp(-rate * n_real.astype(jnp.float32))[:, None, None] * state \
        + product("jhk,jhv->hkv", k_out, v.astype(dt))
    return new, o.reshape(c, -1)
