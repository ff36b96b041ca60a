"""``lib/flops_sparse_linear.py`` against a hand count at tiny sizes, and
against ISSUE 33's arithmetic at the configuration's own."""
import json
import os

import pytest

from benchmarks.builders import minicpm_sala as builder
from benchmarks.lib import flops_sparse_linear as fl

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# three layers: a sparse one (4 query heads for 2 key/value heads of 3)
# and two lightning ones (2 heads of 5)
TINY = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 3, "lightning_nh": 2, "lightning_head_dim": 5,
        "intermediate_size": 16, "vocab_held": 32,
        "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn"],
        "assumed": {"kv_dtype": "bfloat16", "sparse_config": {
            "kernel_size": 4, "kernel_stride": 2, "block_size": 8,
            "init_blocks": 1, "window_size": 16, "topk": 3}}}


def test_weights_by_hand():
    # sparse: q 12x8, k and v 6x8 each, gate 12x8, o 8x12
    assert fl.mixer_params(TINY, "minicpm4") == 96 + 48 + 48 + 96 + 96
    # lightning: q, k, v, gate 10x8 each, o 8x10
    assert fl.mixer_params(TINY, "lightning-attn") == 5 * 80
    assert fl.mlp_params(TINY) == 3 * 8 * 16
    assert fl.head_params(TINY) == 256
    assert fl.weights_params(TINY) == 256 + 384 + 2 * 400 + 3 * 384
    assert fl.layers_of(TINY) == (1, 2)


@pytest.mark.parametrize("keys,kc,attended,blocks", [
    (3, 0, 3, 1), (4, 1, 4, 1), (9, 3, 9, 2), (24, 11, 24, 3),
    (25, 11, 24, 3), (100, 49, 24, 3)])
def test_what_a_token_reads_by_hand(keys, kc, attended, blocks):
    """Compressed keys that are complete, positions attended (every one
    under ``topk`` blocks, ``topk x block`` beyond), blocks fetched."""
    assert fl.compressed_keys(TINY, keys) == kc
    assert fl.keys_attended(TINY, keys) == attended
    assert fl.blocks_read(TINY, keys) == blocks
    weights = fl.weights_params(TINY)
    state = 2 * 5 * 5
    assert fl.token_flops(TINY, keys) == 2 * weights \
        + 1 * (2 * 4 * 3 * kc + 2 * 2 * 4 * 3 * attended) + 2 * 5 * state
    assert fl.token_cache_bytes(TINY, keys, "bfloat16") \
        == 1 * 6 * (kc + 2 * attended) * 2 + 2 * 2 * state * 4


def test_a_step_and_the_kernel_by_hand():
    flops, nbytes = fl.decode_steps_cost(TINY, [9, 100], steps=1,
                                         dtype="bfloat16")
    assert flops == fl.token_flops(TINY, 9) + fl.token_flops(TINY, 100)
    assert nbytes == 2 * fl.weights_params(TINY) \
        + fl.token_cache_bytes(TINY, 9, "bfloat16") \
        + fl.token_cache_bytes(TINY, 100, "bfloat16")
    # the weights once a step, whoever is resident
    _f, two = fl.decode_steps_cost(TINY, [9, 10], 2, "bfloat16")
    assert two == 2 * 2 * fl.weights_params(TINY) \
        + fl.token_cache_bytes(TINY, 9, "bfloat16") \
        + fl.token_cache_bytes(TINY, 10, "bfloat16")
    # the kernel: whole blocks of K and V rows of 6 bf16, the queries in
    # (bf16) and the rows out (float32) of 4 heads of 3
    k_flops, k_bytes = fl.sparse_kernel_cost(TINY, [9, 100])
    assert k_flops == 2 * 2 * 4 * 3 * (16 + 24)
    assert k_bytes == 2 * (16 + 24) * 6 * 2 + 2 * 4 * 3 * (2 + 4)


def test_the_configuration_counts_what_the_issue_counted():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "minicpm-sala-1chip.json")) as f:
        cfg = json.load(f)
    # ISSUE 33: gated MLP 201.3 M; a sparse layer's mixer 52.4 M, a
    # lightning layer's 83.9 M; 16 layers 4.44 B; the vocabulary 2 x 300.8 M
    assert fl.mlp_params(cfg) == 3 * 4096 * 16384
    assert round(fl.mixer_params(cfg, "minicpm4") / 1e6, 1) == 52.4
    assert round(fl.mixer_params(cfg, "lightning-attn") / 1e6, 1) == 83.9
    assert fl.layers_of(cfg) == (4, 12)
    layers = fl.weights_params(cfg) - fl.head_params(cfg)
    assert round(layers / 1e9, 2) == 4.44
    assert round(fl.head_params(cfg) / 1e6, 1) == 300.8
    # the builder's leaves are those weights, the embedding and the norms
    norms = builder.param_count(cfg) - fl.weights_params(cfg) \
        - fl.head_params(cfg)
    assert 0 < norms < 1e6
    assert round(2 * builder.param_count(cfg) / 1e9, 2) == 10.08
    # a long session's token: 64 blocks of 64 of its 32 768 keys, 2047
    # compressed keys, twelve states of 32 x 128 x 128 read and written
    assert fl.keys_attended(cfg, 32768) == 4096
    assert fl.compressed_keys(cfg, 32768) == 2047
    assert fl.token_cache_bytes(cfg, 32768, "bfloat16") \
        == 4 * 256 * (2047 + 2 * 4096) * 2 + 12 * 2 * 32 * 128 * 128 * 4
