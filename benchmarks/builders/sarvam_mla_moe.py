"""The ``sarvam_mla`` decoder as this program names and is told it.

Adapts a configuration file to ``GenerativeServer``: the leaves (one
chip's share: the experts held, the vocabulary slice) and the
architecture's description. Weights are made from the seed leaf by leaf,
in the configuration's dtype, by the driver; the program never draws them.
"""


def layer_kinds(cfg):
    """The FFN's kind, layer by layer: leading dense layers, then sparse."""
    dense = cfg["first_k_dense_replace"]
    return ["dense"] * dense + ["sparse"] * (cfg["num_hidden_layers"] - dense)


def layer_shapes(cfg, mlp_type):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rkv = cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    out = {"ln1_gamma": (d,), "ln2_gamma": (d,),
           "att_q_weight": (h * (dn + dr), d),
           "att_q_norm_gamma": (dn + dr,),
           "att_kva_weight": (rkv + dr, d), "att_kva_norm_gamma": (rkv,),
           "att_kvb_weight": (h * (dn + dv), rkv),
           "att_o_weight": (d, h * dv)}
    if mlp_type == "dense":
        f = cfg["intermediate_size"]
        out.update({"ffn_gate_weight": (f, d), "ffn_up_weight": (f, d),
                    "ffn_down_weight": (d, f)})
    else:
        f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
        routed = cfg["published"]["num_experts"]
        fs = f * cfg["num_shared_experts"]
        out.update({"router_weight": (routed, d), "router_bias": (routed,),
                    # an expert's matrices lie (in, out), as the
                    # program's grouped products read them
                    "experts_gate_weight": (held, d, f),
                    "experts_up_weight": (held, d, f),
                    "experts_down_weight": (held, f, d),
                    "shared_gate_weight": (fs, d),
                    "shared_up_weight": (fs, d),
                    "shared_down_weight": (d, fs)})
    return out


def leaf_specs(cfg):
    """name -> (shape, mean, std): every leaf N(0, init_std), norm scales
    1 + N(0, init_std)."""
    d, v = cfg["hidden_size"], cfg["vocab_held"]
    shapes = {"tok_embed_weight": (v, d), "final_ln_gamma": (d,),
              "lm_head_weight": (v, d)}
    for i, mlp_type in enumerate(layer_kinds(cfg)):
        shapes.update({"layer%d_%s" % (i, n): s for n, s in
                       layer_shapes(cfg, mlp_type).items()})
    std = float(cfg["init_std"])
    return {n: (s, 1.0 if n.endswith("_gamma") else 0.0, std)
            for n, s in shapes.items()}


def architecture(cfg):
    """What ``GenerativeServer`` is told: the published keys the layer
    needs, the router's published width, and this chip's share."""
    keys = ("model_type", "hidden_size", "num_attention_heads",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "moe_intermediate_size",
            "num_shared_experts", "num_experts_per_tok",
            "routed_scaling_factor", "rms_norm_eps", "num_hidden_layers",
            "first_k_dense_replace", "max_position_embeddings",
            "rope_theta", "rope_scaling")
    arch = {k: cfg[k] for k in keys}
    arch["num_experts"] = cfg["published"]["num_experts"]
    arch["experts_held"] = [cfg["deployment"]["expert_first"],
                            cfg["num_experts"]]
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
