"""A decoder stack whose attention layers are of two kinds, full and
windowed, over grouped key/value heads, with routed experts behind them,
as pure JAX functions.

This is the layer mathematics that ``serve/window_moe.py`` builds its
prefill and decode programs from (``model_type`` ``mimo_v2_flash``; the
benchmark's plain reference writes the same equations independently).
Each layer is two residual steps, RMS norms at ``layernorm_epsilon``:
``x += Attn(RMS(x))``, ``x += FFN(RMS(x))``; then a final RMS and the head
over the rows held.

* Attention. ``H_kv`` key/value heads for ``H`` query heads, by the
  layer's kind (full or window). ``q = W_q h`` (``H`` heads of ``d_k``),
  ``k = W_k h`` (``H_kv`` heads of ``d_k``), ``v = value_scale * W_v h``
  (``H_kv`` heads of ``d_v``). Rotary on the first ``rot =
  partial_rotary_factor * d_k`` lanes of every q and k head, lane ``i``
  turned with ``i + rot/2``, theta by kind. Scores ``q . k / sqrt(d_k)``;
  query head ``h`` reads key/value head ``h // (H / H_kv)``. A full layer
  is causal; a window layer sees key ``j`` from query ``t`` iff ``t - W <
  j <= t`` and its softmax takes a learned logit ``s_h`` a head into the
  denominator only: ``p_j = e^{z_j} / (e^{s_h} + sum_j e^{z_j})``
  (:func:`attend`). ``W_o`` over the joined heads.
* FFN. A SiLU-gated MLP on a dense layer; on an expert layer ``s =
  sigmoid(W_r x)`` over all the layer's experts in float32, the top
  ``k`` chosen by ``s + b``, gates ``s / sum_chosen s`` times
  ``routed_scaling_factor`` (1 where null), SiLU-gated experts of which
  this chip computes those it holds (``parallel/moe.py``), no shared one.

Weights keep the dtype they are given (bfloat16 as served, float32 in
tests); every product takes its operands in that dtype and accumulates in
float32; norms, rotary angles, router and softmax are float32.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

from .layers import (NEG, dense, gated_mlp, layer_params,  # noqa: F401
                     product, rms_norm, rope, yarn_frequencies)

__all__ = ["Arch", "Kind", "param_shapes", "check_params", "FULL", "WINDOW",
           "DENSE", "MOE"]

FULL, WINDOW = "full", "window"
DENSE, MOE = "dense", "moe"


class Kind:
    """One kind of attention layer: its heads, widths, rotary and sink."""

    def __init__(self, heads: int, kv_heads: int, d_k: int, d_v: int,
                 theta: float, rot_factor: float, sink: bool):
        if heads % kv_heads:
            raise ValueError("%d query heads for %d key/value heads"
                             % (heads, kv_heads))
        self.heads, self.kv_heads = int(heads), int(kv_heads)
        self.group = self.heads // self.kv_heads
        self.d_k, self.d_v = int(d_k), int(d_v)
        self.k_row = self.kv_heads * self.d_k
        self.v_row = self.kv_heads * self.d_v
        self.rot = int(rot_factor * self.d_k) // 2 * 2
        self.freq, _, _ = yarn_frequencies(self.rot, float(theta), None)
        self.sink = bool(sink)
        self.score_scale = self.d_k ** -0.5


class Arch:
    """The architecture's description, as ``GenerativeServer`` is told it:
    the published configuration's keys; ``hybrid_layer_pattern`` and
    ``moe_layer_freq`` as published, of which the first
    ``num_hidden_layers`` are the layers held; ``n_routed_experts`` the
    router's width and ``experts_held`` ``(first, count)`` this chip's
    share; ``vocab_size`` the rows of embedding and head held;
    ``max_position_embeddings`` a slot's length."""

    def __init__(self, doc: Dict[str, Any]):
        need = ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "v_head_dim", "swa_num_attention_heads",
                "swa_num_key_value_heads", "swa_head_dim", "swa_v_head_dim",
                "intermediate_size", "moe_intermediate_size",
                "n_routed_experts", "num_experts_per_tok",
                "layernorm_epsilon", "num_hidden_layers",
                "hybrid_layer_pattern", "moe_layer_freq", "sliding_window",
                "rope_theta", "swa_rope_theta", "partial_rotary_factor",
                "attention_value_scale", "max_position_embeddings",
                "vocab_size")
        missing = [k for k in need if k not in doc]
        if missing:
            raise ValueError("architecture description lacks %s" % missing)
        if doc.get("scoring_func", "sigmoid") != "sigmoid" \
                or doc.get("topk_method", "noaux_tc") != "noaux_tc" \
                or not doc.get("norm_topk_prob", True):
            raise ValueError("only sigmoid noaux_tc routing with normalised "
                             "gates is served")
        if doc.get("n_shared_experts"):
            raise ValueError("shared experts are not served by this family")
        if int(doc.get("n_group", 1)) != 1:
            raise ValueError("grouped routing is not served")
        self.d = int(doc["hidden_size"])
        rot = float(doc["partial_rotary_factor"])
        self.kinds = {
            FULL: Kind(doc["num_attention_heads"], doc["num_key_value_heads"],
                       doc["head_dim"], doc["v_head_dim"], doc["rope_theta"],
                       rot, doc.get("add_full_attention_sink_bias", False)),
            WINDOW: Kind(doc["swa_num_attention_heads"],
                         doc["swa_num_key_value_heads"], doc["swa_head_dim"],
                         doc["swa_v_head_dim"], doc["swa_rope_theta"], rot,
                         doc.get("add_swa_attention_sink_bias", False))}
        if self.kinds[FULL].sink:
            raise ValueError("a sink on full layers is not served")
        self.num_layers = int(doc["num_hidden_layers"])
        pattern = list(doc["hybrid_layer_pattern"])[:self.num_layers]
        freq = list(doc["moe_layer_freq"])[:self.num_layers]
        if len(pattern) != self.num_layers or len(freq) != self.num_layers:
            raise ValueError("the layer pattern is shorter than the %d "
                             "layers held" % self.num_layers)
        # 0 in the pattern is a full layer, 1 a window layer
        self.attn_types: List[str] = [WINDOW if k else FULL for k in pattern]
        self.mlp_types: List[str] = [MOE if f else DENSE for f in freq]
        self.full_layers = [i for i, k in enumerate(self.attn_types)
                            if k == FULL]
        self.window_layers = [i for i, k in enumerate(self.attn_types)
                              if k == WINDOW]
        self.window = int(doc["sliding_window"])
        self.value_scale = float(doc["attention_value_scale"])
        self.d_ff = int(doc["intermediate_size"])
        self.d_expert = int(doc["moe_intermediate_size"])
        self.n_routed = int(doc["n_routed_experts"])
        first, count = doc.get("experts_held", (0, self.n_routed))
        self.expert_first, self.experts_held = int(first), int(count)
        self.per_tok = int(doc["num_experts_per_tok"])
        scaling = doc.get("routed_scaling_factor")
        self.scaling = 1.0 if scaling is None else float(scaling)
        self.eps = float(doc["layernorm_epsilon"])
        self.max_seq = int(doc["max_position_embeddings"])
        self.vocab_size = int(doc["vocab_size"])
        self.dtype = str(doc.get("dtype", "bfloat16"))


def layer_shapes(arch: Arch, i: int) -> Dict[str, Tuple[int, ...]]:
    """name -> shape of layer ``i``'s leaves (without the prefix). A
    weight lies ``(out, in)``; an expert's matrices lie ``(in, out)``,
    stacked over the experts held."""
    d, kind = arch.d, arch.kinds[arch.attn_types[i]]
    out = {"ln1_gamma": (d,), "ln2_gamma": (d,),
           "att_q_weight": (kind.heads * kind.d_k, d),
           "att_k_weight": (kind.k_row, d), "att_v_weight": (kind.v_row, d),
           "att_o_weight": (d, kind.heads * kind.d_v)}
    if kind.sink:
        out["att_sink"] = (kind.heads,)
    if arch.mlp_types[i] == DENSE:
        out.update({"ffn_gate_weight": (arch.d_ff, d),
                    "ffn_up_weight": (arch.d_ff, d),
                    "ffn_down_weight": (d, arch.d_ff)})
    else:
        e, f = arch.experts_held, arch.d_expert
        out.update({"router_weight": (arch.n_routed, d),
                    "router_bias": (arch.n_routed,),
                    "experts_gate_weight": (e, d, f),
                    "experts_up_weight": (e, d, f),
                    "experts_down_weight": (e, f, d)})
    return out


def param_shapes(arch: Arch) -> Dict[str, Tuple[int, ...]]:
    """name -> shape of every leaf the stack is served from."""
    out = {"tok_embed_weight": (arch.vocab_size, arch.d),
           "final_ln_gamma": (arch.d,),
           "lm_head_weight": (arch.vocab_size, arch.d)}
    for i in range(arch.num_layers):
        out.update({"layer%d_%s" % (i, n): s
                    for n, s in layer_shapes(arch, i).items()})
    return out


def check_params(arch: Arch, params) -> None:
    """The leaves against the description: a wrong share of experts or
    vocabulary is named here, not in a traced shape error."""
    want = param_shapes(arch)
    missing = sorted(set(want) - set(params))
    if missing:
        raise ValueError("window_moe: parameters missing: %s" % missing[:6])
    for name, shape in want.items():
        if tuple(params[name].shape) != tuple(shape):
            raise ValueError("window_moe: %s has shape %s, the description "
                             "gives %s" % (name, tuple(params[name].shape),
                                           tuple(shape)))


# ---------------------------------------------------------------- attention


def rope_part(kind: Kind, x, pos):
    """The rotary on the first ``kind.rot`` lanes of every head of ``x
    (N, heads, d)``, lane ``i`` with ``i + rot/2``; the rest as it is."""
    import jax.numpy as jnp
    return jnp.concatenate([rope(x[..., :kind.rot], pos, kind.freq),
                            x[..., kind.rot:]], axis=-1)


def project(arch: Arch, kind: Kind, p, h, pos):
    """``q (N, H_kv, group, d_k)``, ``k (N, H_kv, d_k)`` rotated at
    ``pos (N,)``, and ``v (N, H_kv, d_v)`` times the value scale, of the
    normed rows ``h (N, D)``."""
    n = h.shape[0]
    q = dense(h, p["att_q_weight"]).reshape(n, kind.heads, kind.d_k)
    k = dense(h, p["att_k_weight"]).reshape(n, kind.kv_heads, kind.d_k)
    v = dense(h, p["att_v_weight"]).reshape(n, kind.kv_heads, kind.d_v)
    q = rope_part(kind, q, pos)
    return (q.reshape(n, kind.kv_heads, kind.group, kind.d_k),
            rope_part(kind, k, pos), arch.value_scale * v)


def attend(kind: Kind, q, k_rows, v_rows, keep, sink=None, shared=False):
    """Grouped-query attention: ``q (N, H_kv, group, d_k)`` over the rows
    as the cache holds them, ``k_rows (.., S, H_kv * d_k)`` and ``v_rows
    (.., S, H_kv * d_v)``, each query's own (``(N, S, ..)``) or, with
    ``shared``, one context for every query (``(S, ..)``); ``keep (N,
    S)``. ``sink (H,)`` logits enter each head's denominator only. Returns
    ``(N, H * d_v)`` float32.

    The rows are read as the decode kernel reads them, in lane blocks
    that are whole tiles: a set of key/value heads for the scores
    (``gqa_decode_attention.heads_per_product``; a head of 192 lanes is a
    tile and a half), each query placed over its own head's lanes of the
    set (``place_queries``), and a head's values for the context. A split
    of the rows into heads, or a product batched over the sets, would
    have the compiler lay out the whole cache plane again."""
    import jax.numpy as jnp
    from ..ops.pallas.gqa_decode_attention import (heads_per_product,
                                                   place_queries)
    n = q.shape[0]
    per = heads_per_product(kind.kv_heads, kind.d_k, kind.d_v)
    width = per * kind.d_k
    qp = place_queries(q, per)                  # (N, sets, per * group, .)
    eq = "nrd,sd->nrs" if shared else "nrd,nsd->nrs"
    s = jnp.stack([product(eq, qp[:, t], k_rows[..., t * width:
                                                (t + 1) * width])
                   for t in range(kind.kv_heads // per)], axis=1)
    s = s.reshape(n, kind.kv_heads, kind.group, -1)
    s = jnp.where(keep[:, None, None, :], s * kind.score_scale, NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(1, kind.kv_heads,
                                                kind.group, 1)
        m = jnp.maximum(m, sink)
    e = jnp.where(keep[:, None, None, :], jnp.exp(s - m), 0.0)
    denom = jnp.sum(e, axis=-1, keepdims=True)
    if sink is not None:
        denom = denom + jnp.exp(sink - m)
    a = e / jnp.maximum(denom, 1e-37)
    eq = "nis,sd->nid" if shared else "nis,nsd->nid"
    d_v = kind.d_v
    o = jnp.stack([product(eq, a[:, g], v_rows[..., g * d_v:(g + 1) * d_v])
                   for g in range(kind.kv_heads)], axis=1)
    return o.reshape(n, -1)


def full_keep(q_pos, k_pos):
    """``(N, S)``: a full layer sees key ``k_pos`` from a query at
    ``q_pos`` iff ``k <= q``."""
    return k_pos[None, :] <= q_pos[:, None]


def window_keep(window: int, q_pos, k_pos):
    """``(N, S)``: a window layer sees key ``k_pos`` from a query at
    ``q_pos`` iff ``q - W < k <= q`` and ``k >= 0``."""
    return full_keep(q_pos, k_pos) \
        & (k_pos[None, :] > q_pos[:, None] - window) \
        & (k_pos[None, :] >= 0)


def ring_order(start, window: int):
    """Row of a ring of ``window`` rows that holds each of the positions
    ``start - window .. start - 1``, in that order."""
    import jax.numpy as jnp
    return (start + jnp.arange(window, dtype=jnp.int32)) % window


def ring_rows(last, start, window: int):
    """Where each row of the ring comes from once positions up to ``last``
    are written: row ``r`` holds the last position ``<= last`` that is
    ``r`` mod ``window``, taken from a context whose first row stands at
    position ``start - window``."""
    import jax.numpy as jnp
    r = jnp.arange(window, dtype=jnp.int32)
    return last - (last - r) % window - (start - window)


# ---------------------------------------------------------------------- FFN


def ffn(arch: Arch, p, h, mlp_type: str, active):
    """The layer's FFN on ``h (N, D)``; ``active (N,) bool``: padding and
    empty slots are routed nowhere. Returns ``(y (N, D), counts)``,
    ``counts (experts held,) int32`` the assignments each expert held
    received, None on a dense layer."""
    import jax.numpy as jnp
    from ..parallel.moe import moe_share_apply, route_sigmoid
    if mlp_type == DENSE:
        return gated_mlp(h, p["ffn_gate_weight"], p["ffn_up_weight"],
                         p["ffn_down_weight"]), None
    experts, gates = route_sigmoid(h, p["router_weight"], p["router_bias"],
                                   top_k=arch.per_tok, scaling=arch.scaling)
    experts = jnp.where(active[:, None], experts, -1)
    return moe_share_apply(h, experts, gates, p["experts_gate_weight"],
                           p["experts_up_weight"], p["experts_down_weight"],
                           first=arch.expert_first)


def embed(params, tokens):
    import jax.numpy as jnp
    return params["tok_embed_weight"][tokens].astype(jnp.float32)


def head(arch: Arch, params, x):
    """Logits of rows ``x (N, D)`` over the vocabulary held."""
    return dense(rms_norm(x, params["final_ln_gamma"], arch.eps),
                 params["lm_head_weight"])
