"""Operations and bytes a decode step of the ``sarvam_mla`` block needs,
counted from shapes, as ``lib/flops.py`` counts the dense decoder's: only
work the mathematics requires, at the configuration's ``compute_dtype``
width, so that no share can pass 100 %.

A step that produces one token for each of the resident sequences needs:
every weight outside the routed experts once (attention, router, shared
expert, the dense layer's FFN, the head's rows held), each routed expert
held that was hit once, and for a sequence of ``n`` keys (the new token's
included) ``n * (kv_lora_rank + qk_rope_head_dim)`` latent values on every
layer. The embedding is a lookup and costs no product; padding behind a
stored row, the bucket's rows past a sequence's length and the slots that
are empty are not needed work.
"""
from benchmarks.builders.sarvam_mla_moe import layer_kinds as _kinds
from benchmarks.lib.flops import DTYPE_BYTES


def latent_row(cfg):
    """Values the cache has to keep a position a layer."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def attention_params(cfg):
    """Weights of one layer's latent attention (no norms)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (h * (dn + dr) * d + latent_row(cfg) * d
            + h * (dn + dv) * cfg["kv_lora_rank"] + d * h * dv)


def expert_params(cfg):
    """One routed expert, or the shared expert's one share."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_ffn_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg):
    return cfg["published"]["num_experts"] * cfg["hidden_size"]


def head_params(cfg):
    return cfg["vocab_held"] * cfg["hidden_size"]


def outside_experts_params(cfg):
    """Every weight a token is multiplied by whatever it is routed to."""
    n = head_params(cfg)
    for mlp_type in _kinds(cfg):
        n += attention_params(cfg)
        if mlp_type == "dense":
            n += dense_ffn_params(cfg)
        else:
            n += router_params(cfg) \
                + cfg["num_shared_experts"] * expert_params(cfg)
    return n


def token_flops(cfg, keys, assignments_here):
    """Forward operations of one token that attends from a context of
    ``keys`` (itself included) and sends ``assignments_here`` of its
    choices to experts held here, summed over the sparse layers."""
    h = cfg["num_attention_heads"]
    # absorbed attention: scores over the row, mixing over the latent
    attend = len(_kinds(cfg)) * 2 * h * keys \
        * (latent_row(cfg) + cfg["kv_lora_rank"])
    return 2 * outside_experts_params(cfg) + attend \
        + 2 * assignments_here * expert_params(cfg)


def decode_steps_cost(cfg, lengths, steps, experts_hit, assignments, dtype):
    """(flops, bytes) that ``steps`` decode steps need to produce one
    token for each entry of ``lengths`` (keys the new token attends from,
    itself included), when in all ``experts_hit`` (expert, layer, step)
    triples received a token and ``assignments`` choices went to experts
    held here."""
    width = DTYPE_BYTES[dtype]
    flops = sum(token_flops(cfg, n, 0) for n in lengths) \
        + 2 * assignments * expert_params(cfg)
    cache = len(_kinds(cfg)) * latent_row(cfg) * sum(lengths)
    weights = steps * outside_experts_params(cfg) \
        + experts_hit * expert_params(cfg)
    return flops, (weights + cache) * width


def experts_hit_bytes(cfg, experts_hit, dtype):
    """Bytes of the routed experts' weights that ``experts_hit`` (expert,
    layer, step) triples have to read: what the grouped products stream."""
    return experts_hit * expert_params(cfg) * DTYPE_BYTES[dtype]
