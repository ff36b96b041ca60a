"""The ``mimo_v2_flash`` decoder as this program names and is told it.

Adapts a configuration file to ``GenerativeServer``: the leaves of the
layers held (one chip's share: the experts held, the vocabulary slice) and
the architecture's description. Weights are made from the seed leaf by
leaf, in the configuration's dtype, by the driver; the program never draws
them.
"""


def layer_kinds(cfg):
    """``(attention, ffn)`` layer by layer, of the layers held: the first
    ``num_hidden_layers`` entries of the published pattern (0 full, 1
    window) and of ``moe_layer_freq`` (0 dense, 1 experts)."""
    n = cfg["num_hidden_layers"]
    return [("window" if a else "full", "moe" if f else "dense")
            for a, f in zip(cfg["hybrid_layer_pattern"][:n],
                            cfg["moe_layer_freq"][:n])]


def layer_shapes(cfg, kind):
    attn, ffn = kind
    d = cfg["hidden_size"]
    pre = "swa_" if attn == "window" else ""
    h, kv = cfg[pre + "num_attention_heads"], cfg[pre + "num_key_value_heads"]
    dk, dv = cfg[pre + "head_dim"], cfg[pre + "v_head_dim"]
    out = {"ln1_gamma": (d,), "ln2_gamma": (d,),
           "att_q_weight": (h * dk, d), "att_k_weight": (kv * dk, d),
           "att_v_weight": (kv * dv, d), "att_o_weight": (d, h * dv)}
    if attn == "window" and cfg["add_swa_attention_sink_bias"]:
        out["att_sink"] = (h,)
    if ffn == "dense":
        f = cfg["intermediate_size"]
        out.update({"ffn_gate_weight": (f, d), "ffn_up_weight": (f, d),
                    "ffn_down_weight": (d, f)})
    else:
        f, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
        routed = cfg["published"]["n_routed_experts"]
        out.update({"router_weight": (routed, d), "router_bias": (routed,),
                    # an expert's matrices lie (in, out), as the
                    # program's grouped products read them
                    "experts_gate_weight": (held, d, f),
                    "experts_up_weight": (held, d, f),
                    "experts_down_weight": (held, f, d)})
    return out


def leaf_specs(cfg):
    """name -> (shape, mean, std): every leaf N(0, init_std), norm scales
    1 + N(0, init_std), sinks N(sink_init_mean, init_std)."""
    d, v = cfg["hidden_size"], cfg["vocab_held"]
    shapes = {"tok_embed_weight": (v, d), "final_ln_gamma": (d,),
              "lm_head_weight": (v, d)}
    for i, kind in enumerate(layer_kinds(cfg)):
        shapes.update({"layer%d_%s" % (i, n): s for n, s in
                       layer_shapes(cfg, kind).items()})
    std = float(cfg["init_std"])

    def mean(name):
        if name.endswith("_gamma"):
            return 1.0
        return float(cfg["sink_init_mean"]) if name.endswith("_sink") \
            else 0.0
    return {n: (s, mean(n), std) for n, s in shapes.items()}


def architecture(cfg):
    """What ``GenerativeServer`` is told: the published keys the layers
    need, the router's published width, and this chip's share."""
    keys = ("model_type", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "v_head_dim",
            "swa_num_attention_heads", "swa_num_key_value_heads",
            "swa_head_dim", "swa_v_head_dim", "intermediate_size",
            "moe_intermediate_size", "num_experts_per_tok",
            "routed_scaling_factor", "scoring_func", "topk_method",
            "norm_topk_prob", "n_group", "n_shared_experts",
            "layernorm_epsilon", "num_hidden_layers", "hybrid_layer_pattern",
            "moe_layer_freq", "sliding_window", "rope_theta",
            "swa_rope_theta", "partial_rotary_factor",
            "attention_value_scale", "add_swa_attention_sink_bias",
            "add_full_attention_sink_bias", "max_position_embeddings")
    arch = {k: cfg[k] for k in keys}
    arch["n_routed_experts"] = cfg["published"]["n_routed_experts"]
    arch["experts_held"] = [cfg["deployment"]["expert_first"],
                            cfg["n_routed_experts"]]
    arch["vocab_size"] = cfg["vocab_held"]
    arch["dtype"] = cfg["assumed"]["param_dtype"]
    return arch


def param_count(cfg):
    n = 0
    for shape, _m, _s in leaf_specs(cfg).values():
        k = 1
        for s in shape:
            k *= s
        n += k
    return n
