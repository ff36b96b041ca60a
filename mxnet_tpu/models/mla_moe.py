"""A decoder block of latent attention and routed experts beside a shared
one, as pure JAX functions.

This is the layer mathematics that ``serve/mla_moe.py`` builds its prefill
and decode programs from (``model_type`` ``sarvam_mla``; the equations are
written out in ``docs/architecture/serving_families.md``):

* RMS normalisation, rotary positions with YaRN's frequencies (lane ``i``
  paired with lane ``i + d/2``), a SiLU-gated MLP;
* multi-head latent attention (MLA) without a query latent, the heads'
  queries normalised before the rotary, in its two forms: per-head keys
  and values expanded from the latent (:func:`attend_per_head`, a prefill
  chunk over its context) and the absorbed form that works on the cached
  latent rows themselves (:func:`attend`, a decode step);
* the expert layer is ``parallel/moe.py``'s ``moe_share_apply``.

Weights keep the dtype they are given (bfloat16 as served, float32 in
tests); every product accumulates in float32, and norms, rotary angles,
router and softmax are float32.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

from .layers import (dense, gated_mlp, layer_params, product,  # noqa: F401
                     rms_norm, rope as _rope, softmax_where as _softmax,
                     yarn_frequencies)

__all__ = ["Arch", "param_shapes", "yarn_frequencies"]


class Arch:
    """The architecture's description, as ``GenerativeServer`` is told it
    (the published configuration's keys; ``num_experts`` is the router's
    width and ``experts_held`` ``(first, count)`` this chip's share;
    ``vocab_size`` the rows of embedding and head held;
    ``max_position_embeddings`` a slot's length)."""

    def __init__(self, doc: Dict[str, Any]):
        need = ("hidden_size", "num_attention_heads", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "intermediate_size", "moe_intermediate_size", "num_experts",
                "num_experts_per_tok", "routed_scaling_factor",
                "rms_norm_eps", "num_hidden_layers", "first_k_dense_replace",
                "max_position_embeddings", "vocab_size")
        missing = [k for k in need if k not in doc]
        if missing:
            raise ValueError("architecture description lacks %s" % missing)
        self.d = int(doc["hidden_size"])
        self.heads = int(doc["num_attention_heads"])
        self.kv_rank = int(doc["kv_lora_rank"])
        self.d_nope = int(doc["qk_nope_head_dim"])
        self.d_rope = int(doc["qk_rope_head_dim"])
        self.d_q = self.d_nope + self.d_rope
        self.d_v = int(doc["v_head_dim"])
        self.d_ff = int(doc["intermediate_size"])
        self.d_expert = int(doc["moe_intermediate_size"])
        self.n_shared = int(doc.get("num_shared_experts", 1))
        self.n_routed = int(doc["num_experts"])
        first, count = doc.get("experts_held", (0, self.n_routed))
        self.expert_first, self.experts_held = int(first), int(count)
        self.per_tok = int(doc["num_experts_per_tok"])
        self.scaling = float(doc["routed_scaling_factor"])
        self.eps = float(doc["rms_norm_eps"])
        self.num_layers = int(doc["num_hidden_layers"])
        n_dense = int(doc["first_k_dense_replace"])
        self.mlp_types: List[str] = ["dense"] * n_dense \
            + ["sparse"] * (self.num_layers - n_dense)
        self.max_seq = int(doc["max_position_embeddings"])
        self.vocab_size = int(doc["vocab_size"])
        self.dtype = str(doc.get("dtype", "bfloat16"))
        self.rope_freq, self.rope_mscale, on_scores = yarn_frequencies(
            self.d_rope, float(doc.get("rope_theta", 10000.0)),
            doc.get("rope_scaling"))
        self.score_scale = self.d_q ** -0.5 * on_scores
        self.row = self.kv_rank + self.d_rope       # one cached latent row
        # as the cache stores it: whole 128-lane tiles, zeros behind the
        # row. A row of 576 is not, and the TPU compiler then keeps the
        # plane positions-minor and copies all of it around every append
        self.row_stored = -(-self.row // 128) * 128


def param_shapes(arch: Arch) -> Dict[str, Tuple[int, ...]]:
    """name -> shape of every leaf the block is served from. A weight
    lies ``(out, in)``; an expert's matrices lie ``(in, out)``, stacked
    over the experts held."""
    d, h = arch.d, arch.heads
    out = {"tok_embed_weight": (arch.vocab_size, d), "final_ln_gamma": (d,),
           "lm_head_weight": (arch.vocab_size, d)}
    for i in range(arch.num_layers):
        p = "layer%d_" % i
        out.update({
            p + "ln1_gamma": (d,), p + "ln2_gamma": (d,),
            p + "att_q_weight": (h * arch.d_q, d),
            p + "att_q_norm_gamma": (arch.d_q,),
            p + "att_kva_weight": (arch.row, d),
            p + "att_kva_norm_gamma": (arch.kv_rank,),
            p + "att_kvb_weight": (h * (arch.d_nope + arch.d_v),
                                   arch.kv_rank),
            p + "att_o_weight": (d, h * arch.d_v)})
        if arch.mlp_types[i] == "dense":
            out.update({p + "ffn_gate_weight": (arch.d_ff, d),
                        p + "ffn_up_weight": (arch.d_ff, d),
                        p + "ffn_down_weight": (d, arch.d_ff)})
        else:
            f, fs = arch.d_expert, arch.d_expert * arch.n_shared
            e = arch.experts_held
            out.update({p + "router_weight": (arch.n_routed, d),
                        p + "router_bias": (arch.n_routed,),
                        p + "experts_gate_weight": (e, d, f),
                        p + "experts_up_weight": (e, d, f),
                        p + "experts_down_weight": (e, f, d),
                        p + "shared_gate_weight": (fs, d),
                        p + "shared_up_weight": (fs, d),
                        p + "shared_down_weight": (d, fs)})
    return out


# ------------------------------------------------------------- small parts
# (``product``, ``dense``, ``rms_norm``, ``gated_mlp``, the rotary and its
# frequencies live in ``models/layers.py``, shared with the other families)


def rope(arch: Arch, x, pos):
    """The architecture's rotary on the last axis of ``x (N, ..., d_rope)``
    (``layers.rope`` at its YaRN frequencies and factor)."""
    return _rope(x, pos, arch.rope_freq, arch.rope_mscale)


# --------------------------------------------------------- latent attention


def mla_project(arch: Arch, p, h, pos):
    """What one layer makes of ``h (N, D)`` at positions ``pos (N,)``: the
    heads' ``q_nope (N, H, d_nope)`` and rotated ``q_rope (N, H, d_rope)``
    (each head's whole query normalised first), and the row the cache
    keeps, ``[RMS(c_kv) | rotated k_rope] (N, kv_rank + d_rope)``."""
    import jax.numpy as jnp
    n = h.shape[0]
    q = rms_norm(dense(h, p["att_q_weight"]).reshape(n, arch.heads, arch.d_q),
                 p["att_q_norm_gamma"], arch.eps)
    q_rope = rope(arch, q[..., arch.d_nope:], pos)
    kva = dense(h, p["att_kva_weight"])
    c_kv = rms_norm(kva[:, :arch.kv_rank], p["att_kva_norm_gamma"], arch.eps)
    k_rope = rope(arch, kva[:, arch.kv_rank:], pos)
    return q[..., :arch.d_nope], q_rope, \
        jnp.concatenate([c_kv, k_rope], axis=-1)


def _kvb(arch: Arch, p):
    w = p["att_kvb_weight"].reshape(arch.heads, arch.d_nope + arch.d_v,
                                    arch.kv_rank)
    return w[:, :arch.d_nope], w[:, arch.d_nope:]       # keys', values'


def stored_row(arch: Arch, row, dtype):
    """A latent row as the cache stores it: cast, zeros behind it."""
    import jax.numpy as jnp
    return jnp.pad(row.astype(dtype),
                   ((0, 0), (0, arch.row_stored - arch.row)))


def absorb_query(arch: Arch, p, q_nope, q_rope):
    """The absorbed query ``[W_kvb,h^K^T q_nope_h | q_rope_h | 0] (N, H,
    row_stored)``: its product with a stored row is the head's score."""
    import jax.numpy as jnp
    w_k, _ = _kvb(arch, p)
    q_abs = product("nhd,hdr->nhr", q_nope, w_k)
    q = jnp.concatenate([q_abs, q_rope], axis=-1)
    return jnp.pad(q, ((0, 0), (0, 0), (0, arch.row_stored - arch.row)))


def attend(arch: Arch, q, rows, keep):
    """Absorbed attention of a decode step: ``q (N, H, row_stored)`` from
    :func:`absorb_query`, ``rows (N, K, row_stored)`` each query's own
    slot as the cache holds it, ``keep (N, K)``. Returns the mixed latent
    ``(N, H, kv_rank)``. The stored rows are read where they lie, whole:
    the mix runs over the rotary lanes and the padding too, and what they
    give is cut off the result (a slice of the rows would be a copy of
    the bucket)."""
    s = product("nhd,nkd->nhk", q, rows) * arch.score_scale
    a = _softmax(s, keep[:, None, :])
    return product("nhk,nkr->nhr", a, rows)[..., :arch.kv_rank]


def expand_values(arch: Arch, p, mixed):
    """``o_h = W_kvb,h^V (mixed latent)``, heads side by side, through the
    output projection: ``(N, D)``."""
    _, w_v = _kvb(arch, p)
    o = product("nhr,hvr->nhv", mixed, w_v)
    return dense(o.reshape(o.shape[0], -1), p["att_o_weight"])


def expand_keys_values(arch: Arch, p, rows):
    """A context's per-head keys' nope part ``(S, H, d_nope)`` and values
    ``(S, H, d_v)``, expanded from its latent rows ``(S, row)``."""
    w_k, w_v = _kvb(arch, p)
    c_kv = rows[:, :arch.kv_rank]
    k_nope = product("sr,hdr->shd", c_kv, w_k)
    v = product("sr,hvr->shv", c_kv, w_v)
    return k_nope.astype(rows.dtype), v.astype(rows.dtype)


def attend_per_head(arch: Arch, q_nope, q_rope, k_nope, v, k_rope, keep):
    """MLA as published, per head, of a block of queries over a context:
    ``k_nope``/``v`` from :func:`expand_keys_values`, ``k_rope (S,
    d_rope)`` the rows' rotary part, ``keep (N, S)``. Returns the heads'
    outputs side by side, ``(N, H * d_v)``."""
    s = product("nhd,shd->nhs", q_nope, k_nope)
    s = s + product("nhd,sd->nhs", q_rope, k_rope)
    a = _softmax(s * arch.score_scale, keep[:, None, :])
    o = product("nhs,shv->nhv", a, v)
    return o.reshape(o.shape[0], -1)


# ---------------------------------------------------------------------- FFN


def ffn(arch: Arch, p, h, mlp_type, active):
    """The block's FFN on ``h (N, D)``. ``active (N,) bool``: rows that
    are padding or an empty slot are routed nowhere. Returns ``(y (N, D),
    counts)``; ``counts (experts held,) int32`` are the assignments each
    expert held received, None for a dense layer."""
    import jax.numpy as jnp
    from ..parallel.moe import moe_share_apply, route_sigmoid
    if mlp_type == "dense":
        return gated_mlp(h, p["ffn_gate_weight"], p["ffn_up_weight"],
                         p["ffn_down_weight"]), None
    experts, gates = route_sigmoid(h, p["router_weight"], p["router_bias"],
                                   top_k=arch.per_tok, scaling=arch.scaling)
    experts = jnp.where(active[:, None], experts, -1)
    y, counts = moe_share_apply(
        h, experts, gates, p["experts_gate_weight"],
        p["experts_up_weight"], p["experts_down_weight"],
        first=arch.expert_first)
    return y + gated_mlp(h, p["shared_gate_weight"], p["shared_up_weight"],
                         p["shared_down_weight"]), counts


def check_params(arch: Arch, params) -> None:
    """The leaves against the description: a wrong share of experts or
    vocabulary is named here, not in a traced shape error."""
    want = param_shapes(arch)
    missing = sorted(set(want) - set(params))
    if missing:
        raise ValueError("mla_moe: parameters missing: %s" % missing[:6])
    for name, shape in want.items():
        if tuple(params[name].shape) != tuple(shape):
            raise ValueError("mla_moe: %s has shape %s, the description "
                             "gives %s" % (name, tuple(params[name].shape),
                                           tuple(shape)))
