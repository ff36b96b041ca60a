"""The ``minicpm_sala`` decoder stack as this program names and is told it.

Adapts a configuration file to ``GenerativeServer``: the leaves of the
layers held (every head, the whole vocabulary) and the architecture's
description. Weights are made from the seed leaf by leaf, in the
configuration's dtype, by the driver; the program never draws them.
"""

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def layer_kinds(cfg):
    """The mixer's kind, layer by layer, of the layers held."""
    return list(cfg["mixer_types"])


def layer_shapes(cfg, kind):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    out = {"ln1_gamma": (d,), "ln2_gamma": (d,),
           "ffn_gate_weight": (f, d), "ffn_up_weight": (f, d),
           "ffn_down_weight": (d, f)}
    if kind == SPARSE:
        dh = cfg["head_dim"]
        hd, kvd = cfg["num_attention_heads"] * dh, \
            cfg["num_key_value_heads"] * dh
        out.update({"att_q_weight": (hd, d), "att_k_weight": (kvd, d),
                    "att_v_weight": (kvd, d)})
    else:
        dh = cfg["lightning_head_dim"]
        hd = cfg["lightning_nh"] * dh
        out.update({"att_q_weight": (hd, d), "att_k_weight": (hd, d),
                    "att_v_weight": (hd, d), "att_out_norm_gamma": (hd,)})
    out.update({"att_q_norm_gamma": (dh,), "att_k_norm_gamma": (dh,),
                "att_gate_weight": (hd, d), "att_o_weight": (d, hd)})
    return out


def leaf_specs(cfg):
    """name -> (shape, mean, std): every leaf N(0, init_std), norm scales
    1 + N(0, init_std)."""
    d, v = cfg["hidden_size"], cfg["vocab_held"]
    shapes = {"tok_embed_weight": (v, d), "final_ln_gamma": (d,),
              "lm_head_weight": (v, d)}
    for i, kind in enumerate(layer_kinds(cfg)):
        shapes.update({"layer%d_%s" % (i, n): s for n, s in
                       layer_shapes(cfg, kind).items()})
    std = float(cfg["init_std"])
    return {n: (s, 1.0 if n.endswith("_gamma") else 0.0, std)
            for n, s in shapes.items()}


def architecture(cfg):
    """What ``GenerativeServer`` is told: the published keys the layers
    need, the kinds of the layers held, the published depth the residual
    scale is taken from, and the selection's assumed sizes."""
    keys = ("model_type", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "lightning_nh", "lightning_nkv", "lightning_head_dim",
            "lightning_use_rope", "attn_use_rope", "rms_norm_eps",
            "rope_theta", "scale_emb", "scale_depth", "dim_model_base",
            "num_hidden_layers", "mixer_types", "max_position_embeddings")
    arch = {k: cfg[k] for k in keys}
    arch["depth_scale_layers"] = cfg["published"]["num_hidden_layers"]
    arch["sparse_config"] = dict(cfg["assumed"]["sparse_config"])
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
