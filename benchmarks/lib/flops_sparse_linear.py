"""Operations and bytes a decode step of the ``minicpm_sala`` stack needs,
counted from shapes, as ``lib/flops.py`` counts the dense decoder's: only
work the mathematics requires, at the widths the state is held in, so that
no share can pass 100 %.

A step that produces one token for each of the resident sequences needs
every weight once (both kinds of mixer, the MLPs, the head; the embedding
is a lookup) and, for a sequence of ``n`` keys (the new token's included):
on every sparse layer the compressed keys that are complete (``(n -
kernel) // stride + 1`` rows of ``kv_heads * head_dim``) and the K and V
rows of the blocks selected (``min(blocks held, topk)`` blocks, the own
block as far as it is filled), in the cache's dtype; on every lightning
layer the state of ``heads * head_dim * head_dim`` float32 read and
written. The bucket's rows past the selection, the slots that are empty
and the compressed keys past the sequence are not needed work.
"""
from benchmarks.lib.flops import DTYPE_BYTES

SPARSE = "minicpm4"


def _selection(cfg):
    return cfg["assumed"]["sparse_config"]


def mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def mixer_params(cfg, kind):
    """Weights of one layer's mixer (no norms): q, k, v, the output gate
    and the output projection."""
    d = cfg["hidden_size"]
    if kind == SPARSE:
        hd = cfg["num_attention_heads"] * cfg["head_dim"]
        kvd = cfg["num_key_value_heads"] * cfg["head_dim"]
        return d * (hd + 2 * kvd + hd + hd)
    hd = cfg["lightning_nh"] * cfg["lightning_head_dim"]
    return d * 5 * hd


def head_params(cfg):
    return cfg["vocab_held"] * cfg["hidden_size"]


def weights_params(cfg):
    """Every weight a token is multiplied by."""
    return head_params(cfg) + sum(
        mixer_params(cfg, kind) + mlp_params(cfg)
        for kind in cfg["mixer_types"])


def layers_of(cfg):
    """(sparse layers, lightning layers) held."""
    sparse = sum(1 for kind in cfg["mixer_types"] if kind == SPARSE)
    return sparse, len(cfg["mixer_types"]) - sparse


def compressed_keys(cfg, keys):
    """Compressed keys complete for a token that attends from ``keys``."""
    sel = _selection(cfg)
    if keys < sel["kernel_size"]:
        return 0
    return (keys - sel["kernel_size"]) // sel["kernel_stride"] + 1


def keys_attended(cfg, keys):
    """Positions whose K and V rows the token's selection reads."""
    sel = _selection(cfg)
    return min(keys, sel["topk"] * sel["block_size"])


def blocks_read(cfg, keys):
    sel = _selection(cfg)
    return min(-(-keys // sel["block_size"]), sel["topk"])


def state_values(cfg):
    """Values of one lightning layer's state a sequence."""
    return cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2


def token_flops(cfg, keys):
    """Forward operations of one token that attends from ``keys``."""
    n_sparse, n_lin = layers_of(cfg)
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    score = 2 * h * dh * compressed_keys(cfg, keys)
    attend = 2 * 2 * h * dh * keys_attended(cfg, keys)
    # decay, outer product and sum, then the read: 5 a state value
    state = 5 * state_values(cfg)
    return 2 * weights_params(cfg) + n_sparse * (score + attend) \
        + n_lin * state


def token_cache_bytes(cfg, keys, dtype):
    n_sparse, n_lin = layers_of(cfg)
    row = cfg["num_key_value_heads"] * cfg["head_dim"]
    held = row * (compressed_keys(cfg, keys) + 2 * keys_attended(cfg, keys))
    return n_sparse * held * DTYPE_BYTES[dtype] \
        + n_lin * 2 * state_values(cfg) * 4


def decode_steps_cost(cfg, lengths, steps, dtype):
    """(flops, bytes) that ``steps`` decode steps need to produce one
    token for each entry of ``lengths`` (keys the new token attends from,
    itself included)."""
    flops = sum(token_flops(cfg, n) for n in lengths)
    cache = sum(token_cache_bytes(cfg, n, cfg["assumed"]["kv_dtype"])
                for n in lengths)
    return flops, steps * weights_params(cfg) * DTYPE_BYTES[dtype] + cache


def sparse_kernel_cost(cfg, lengths):
    """(flops, bytes) of the selected blocks' attention alone, over all
    sparse layers: what ``sparse_decode_attention`` has to do for one
    token for each entry of ``lengths``. A block is read whole (the own
    block's rows past the token are fetched with it: a tile is the least
    the kernel can fetch), queries in and rows out beside it."""
    n_sparse, _ = layers_of(cfg)
    sel = _selection(cfg)
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    row = cfg["num_key_value_heads"] * dh
    width = DTYPE_BYTES[cfg["assumed"]["kv_dtype"]]
    flops = bytes_ = 0
    for n in lengths:
        rows = blocks_read(cfg, n) * sel["block_size"]
        flops += 2 * 2 * h * dh * rows
        bytes_ += 2 * rows * row * width + h * dh * (width + 4)
    return n_sparse * flops, n_sparse * bytes_
