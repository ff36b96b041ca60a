"""Operations and bytes a decode step of the ``mimo_v2_flash`` stack needs,
counted from shapes, as ``lib/flops.py`` counts the dense decoder's: only
work the mathematics requires, at the configuration's ``compute_dtype``
width, so that no share can pass 100 %.

A step that produces one token for each of the resident sequences needs:
every weight outside the routed experts once (attention, the dense
layer's FFN, the routers, the head's rows held), each routed expert held
that was hit once, and for a sequence of ``n`` keys (the new token's
included) its ``n`` K and V rows on every full layer and ``min(n,
sliding_window)`` on every window layer. The embedding is a lookup and
costs no product; the bucket's rows past a sequence's length, a ring's
rows past the window and the slots that are empty are not needed work.
"""
from benchmarks.builders.mimo_window_moe import layer_kinds as _kinds
from benchmarks.lib.flops import DTYPE_BYTES


def _heads(cfg, attn):
    """(query heads, key/value heads, d_k, d_v) of a kind of layer."""
    pre = "swa_" if attn == "window" else ""
    return (cfg[pre + "num_attention_heads"],
            cfg[pre + "num_key_value_heads"], cfg[pre + "head_dim"],
            cfg[pre + "v_head_dim"])


def attention_params(cfg, attn):
    """Weights of one layer's attention (no norms, no sink)."""
    h, kv, dk, dv = _heads(cfg, attn)
    return cfg["hidden_size"] * (h * dk + kv * dk + kv * dv + h * dv)


def expert_params(cfg):
    """One routed expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def outside_experts_params(cfg):
    """Every weight a token is multiplied by whatever it is routed to."""
    d = cfg["hidden_size"]
    n = cfg["vocab_held"] * d
    for attn, ffn in _kinds(cfg):
        n += attention_params(cfg, attn)
        n += 3 * d * cfg["intermediate_size"] if ffn == "dense" \
            else cfg["published"]["n_routed_experts"] * d
    return n


def rows_read(cfg, attn, keys):
    """Key positions a layer reads for a token that attends from ``keys``
    (itself included)."""
    return keys if attn == "full" else min(keys, cfg["sliding_window"])


def token_attention(cfg, keys, only=None):
    """(operations, cache values) of one token's attention over all its
    layers (of kind ``only``, where given): scores and mixing of every
    query head, and the K and V values its rows hold."""
    flops = values = 0
    for attn, _ffn in _kinds(cfg):
        if only is not None and attn != only:
            continue
        h, kv, dk, dv = _heads(cfg, attn)
        n = rows_read(cfg, attn, keys)
        flops += 2 * h * (dk + dv) * n
        values += kv * (dk + dv) * n
    return flops, values


def decode_steps_cost(cfg, lengths, steps, experts_hit, assignments, dtype):
    """(flops, bytes) that ``steps`` decode steps need to produce one
    token for each entry of ``lengths`` (keys the new token attends from,
    itself included), when in all ``experts_hit`` (expert, layer, step)
    triples received a token and ``assignments`` choices went to experts
    held here."""
    width = DTYPE_BYTES[dtype]
    attention = [token_attention(cfg, n) for n in lengths]
    flops = sum(2 * outside_experts_params(cfg) + f for f, _v in attention) \
        + 2 * assignments * expert_params(cfg)
    weights = steps * outside_experts_params(cfg) \
        + experts_hit * expert_params(cfg)
    cache = sum(v for _f, v in attention)
    return flops, (weights + cache) * width


def gqa_kernel_cost(cfg, lengths):
    """(flops, bytes) of the full layers' attention alone, over all full
    layers: what ``gqa_decode_attention`` has to do for one token for each
    entry of ``lengths``: the rows of K and V it needs, the queries in and
    the float32 outputs out."""
    width = DTYPE_BYTES[cfg["assumed"]["kv_dtype"]]
    h, _kv, dk, dv = _heads(cfg, "full")
    full = sum(1 for attn, _f in _kinds(cfg) if attn == "full")
    flops = values = 0
    for n in lengths:
        f, v = token_attention(cfg, n, only="full")
        flops += f
        values += v
    return flops, values * width + full * len(lengths) * h * (
        dk * width + dv * 4)
