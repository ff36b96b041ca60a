"""Operations and bytes the algorithm needs, counted from shapes.

Only work the mathematics requires is counted (no recomputation, no
padding, no copies), at the configuration's ``compute_dtype`` width, so a
faster implementation reads a higher share of the peak and a wasteful one
a lower share: no share can pass 100 %.

A matrix product of (m, k) by (k, n) is 2*m*k*n operations. The backward
pass of a product costs two products of the same size.
"""

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def lm_param_count(cfg):
    """Parameters of the decoder as the program builds it (untied head)."""
    d, f, v = cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"]
    per_layer = (3 * d * d + 3 * d) + (d * d + d) + (d * f + f) \
        + (f * d + d) + 4 * d
    return (v * d + cfg["max_position_embeddings"] * d
            + cfg["num_hidden_layers"] * per_layer + 2 * d + (d + 1) * v)


def lm_matmul_params(cfg):
    """Weights that every token is multiplied by (embedding lookups and
    biases cost no product)."""
    d, f, v = cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"]
    return cfg["num_hidden_layers"] * (4 * d * d + 2 * d * f) + d * v


def lm_forward_flops_per_token(cfg, context):
    """Forward operations for one token that attends to ``context`` keys
    (itself included): the weight products plus scores and mixing."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    return 2 * lm_matmul_params(cfg) + layers * 4 * d * context


def lm_train_flops_per_token(cfg, seq_len):
    """Forward and backward for one token of a packed causal row of
    ``seq_len``: a token attends on average to (seq_len + 1) / 2 keys;
    backward is twice the forward."""
    return 3 * lm_forward_flops_per_token(cfg, (seq_len + 1) / 2.0)


def flash_attention_cost(cfg, rows, seq_len, dtype):
    """(flops, bytes) of causal attention, forward and backward, for
    ``rows`` packed rows in all layers: what the flash forward and its two
    backward kernels have to do whatever implements them.

    Forward: scores and mixing over the causal half, 4*T*(T+1)/2*d_head
    per head. Backward: dv, dp, dq and dk, four products, twice the
    forward; the scores a flash kernel recomputes are not counted.
    Bytes: q, k, v, o read or written once forward; q, k, v, o,
    do read and dq, dk, dv written backward."""
    heads = cfg["num_attention_heads"]
    d_head = cfg["hidden_size"] // heads
    layers = cfg["num_hidden_layers"]
    per_head_fwd = 4 * d_head * seq_len * (seq_len + 1) / 2.0
    flops = rows * heads * layers * per_head_fwd * 3.0
    tensor = rows * heads * seq_len * d_head * DTYPE_BYTES[dtype]
    return flops, layers * tensor * (4 + 8)


def decode_steps_cost(cfg, lengths, steps, dtype):
    """(flops, bytes) that ``steps`` decode steps need to produce one
    token for each entry of ``lengths`` (keys the new token attends to,
    itself included): every weight read once a step, each sequence's keys
    and values read once for each of its tokens."""
    flops = sum(lm_forward_flops_per_token(cfg, n) for n in lengths)
    width = DTYPE_BYTES[dtype]
    kv = 2 * cfg["num_hidden_layers"] * cfg["hidden_size"] * sum(lengths)
    return flops, (steps * lm_matmul_params(cfg) + kv) * width


def roofline_seconds(flops, nbytes, peak):
    """Least time the chip could take, and which bound holds."""
    t_compute = flops / peak["bf16_flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    return max(t_compute, t_memory), \
        ("compute" if t_compute >= t_memory else "memory")


# ResNet (He et al. 2015): multiply-adds of the convolutions and the
# classifier, forward, per image, layer by layer from the configuration's
# shapes. The stride of a stage's first unit sits on its first 1x1
# convolution, as in the paper. For the 50-layer model at 224x224 this
# gives 3.86e9, the paper's "3.8 x 10^9 FLOPs" (multiply-adds).
def _conv_macs(cin, cout, k, out_hw):
    return cin * cout * k * k * out_hw * out_hw


def resnet_forward_macs(cfg):
    hw = cfg["image_size"] // 2
    macs = _conv_macs(3, cfg["stem_width"], 7, hw)
    cin, hw = cfg["stem_width"], hw // 2
    for stage, (blocks, cout) in enumerate(zip(cfg["stage_blocks"],
                                               cfg["stage_widths"])):
        mid = cout // cfg["bottleneck_ratio"]
        for b in range(blocks):
            out_hw = hw // 2 if (b == 0 and stage > 0) else hw
            macs += _conv_macs(cin, mid, 1, out_hw)
            macs += _conv_macs(mid, mid, 3, out_hw)
            macs += _conv_macs(mid, cout, 1, out_hw)
            if b == 0:
                macs += _conv_macs(cin, cout, 1, out_hw)
            cin, hw = cout, out_hw
    return macs + cin * cfg["num_classes"]


def resnet_train_flops_per_image(cfg):
    """Forward and backward: 3 x 2 x multiply-adds."""
    return 6 * resnet_forward_macs(cfg)
