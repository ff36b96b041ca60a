"""The OPT decoder as this program builds and names it.

Adapts a configuration file to the program's entry points: the training
symbol of ``mxnet_tpu.models.transformer`` and the parameter names that
``Module`` and ``GenerativeServer`` share. Weights are made by
``benchmarks.lib.leaves`` from the seed; the program never draws them.
"""
from benchmarks.lib import flops as _flops


def leaf_specs(cfg):
    """name -> (shape, mean, std): every leaf N(0, init_std), LayerNorm
    scales 1 + N(0, init_std)."""
    d, f, v = cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"]
    shapes = {"tok_embed_weight": (v, d),
              "pos_embed_weight": (cfg["max_position_embeddings"], d),
              "final_ln_gamma": (d,), "final_ln_beta": (d,),
              "lm_head_weight": (v, d), "lm_head_bias": (v,)}
    for i in range(cfg["num_hidden_layers"]):
        p = "layer%d_" % i
        shapes.update({
            p + "ln1_gamma": (d,), p + "ln1_beta": (d,),
            p + "att_qkv_weight": (3 * d, d), p + "att_qkv_bias": (3 * d,),
            p + "att_proj_weight": (d, d), p + "att_proj_bias": (d,),
            p + "ln2_gamma": (d,), p + "ln2_beta": (d,),
            p + "ff1_weight": (f, d), p + "ff1_bias": (f,),
            p + "ff2_weight": (d, f), p + "ff2_bias": (d,)})
    std = float(cfg["init_std"])
    return {n: (s, 1.0 if n.endswith("_gamma") else 0.0, std)
            for n, s in shapes.items()}


def aux_specs(cfg):
    """The model has no auxiliary state."""
    return {}


def parts(cfg, name):
    """Where one stored leaf holds several of the model's: the fused
    query, key and value projection is compared as its three thirds (the
    key's bias has no gradient under softmax, the other two have)."""
    if "_att_qkv_" not in name:
        return [("", None)]
    d = cfg["hidden_size"]
    return [("." + part, slice(i * d, (i + 1) * d))
            for i, part in enumerate("qkv")]


def symbol(cfg, traffic):
    from mxnet_tpu.models import transformer
    return transformer.get_symbol(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        d_ff=cfg["ffn_dim"], seq_len=traffic["seq_len"],
        attention=cfg["assumed"]["attention"])


def unit():
    return "tokens"


def train_flops_per_unit(cfg, traffic):
    return _flops.lm_train_flops_per_token(cfg, traffic["seq_len"])
