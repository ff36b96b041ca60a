"""``lib/flops_mla_moe.py`` against a hand count at tiny sizes."""
import json
import os

import pytest

from benchmarks.builders import sarvam_mla_moe as builder
from benchmarks.lib import flops_mla_moe as fl

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# three layers: a dense one, then two sparse ones with 2 of 8 experts held
TINY = {"hidden_size": 8, "num_attention_heads": 2, "kv_lora_rank": 4,
        "qk_nope_head_dim": 3, "qk_rope_head_dim": 2, "v_head_dim": 5,
        "intermediate_size": 16, "moe_intermediate_size": 6,
        "num_shared_experts": 1, "num_experts": 2, "num_experts_per_tok": 2,
        "vocab_held": 32, "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "published": {"num_experts": 8}}


def test_weights_by_hand():
    # q 2x(3+2)x8, kv_a (4+2)x8, kv_b 2x(3+5)x4, o 8x(2x5)
    assert fl.attention_params(TINY) == 80 + 48 + 64 + 80
    assert fl.latent_row(TINY) == 6
    assert fl.expert_params(TINY) == 3 * 8 * 6
    assert fl.dense_ffn_params(TINY) == 3 * 8 * 16
    assert fl.router_params(TINY) == 8 * 8
    assert fl.head_params(TINY) == 32 * 8
    outside = 256 + (272 + 384) + 2 * (272 + 64 + 144)
    assert fl.outside_experts_params(TINY) == outside


def test_a_token_and_a_step_by_hand():
    outside = fl.outside_experts_params(TINY)
    # 7 keys on three layers: scores over the 6-wide row, mixing over the
    # 4-wide latent, 2 heads
    assert fl.token_flops(TINY, 7, 0) == 2 * outside \
        + 3 * (2 * 2 * 7 * (6 + 4))
    # 25 keys; one choice went to an expert here
    assert fl.token_flops(TINY, 25, 1) == 2 * outside \
        + 3 * (2 * 2 * 25 * 10) + 2 * 144
    flops, nbytes = fl.decode_steps_cost(TINY, [7, 25], steps=1,
                                         experts_hit=1, assignments=1,
                                         dtype="bfloat16")
    assert flops == fl.token_flops(TINY, 7, 0) + fl.token_flops(TINY, 25, 1)
    assert nbytes == 2 * (outside + 144 + 3 * 6 * (7 + 25))
    # an expert that no token reached costs nothing; one hit twice in two
    # steps is read twice
    _f, none = fl.decode_steps_cost(TINY, [7], 1, 0, 0, "bfloat16")
    _f, twice = fl.decode_steps_cost(TINY, [7, 8], 2, 2, 2, "bfloat16")
    assert none == 2 * (outside + 3 * 6 * 7)
    assert twice == 2 * (2 * outside + 2 * 144 + 3 * 6 * 15)
    assert fl.experts_hit_bytes(TINY, 5, "bfloat16") == 5 * 144 * 2


def test_the_configuration_counts_what_the_issue_counted():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sarvam-105b-l9-ep8.json")) as f:
        cfg = json.load(f)
    assert fl.attention_params(cfg) == pytest.approx(94.6e6, rel=2e-3)
    assert fl.expert_params(cfg) == pytest.approx(25.17e6, rel=2e-3)
    assert fl.dense_ffn_params(cfg) + fl.attention_params(cfg) \
        == pytest.approx(296.0e6, rel=2e-3)
    assert fl.head_params(cfg) == 32768 * 4096
    # everything but the norms, the routers' biases and the embedding
    total = fl.outside_experts_params(cfg) \
        + 8 * 16 * fl.expert_params(cfg) + fl.head_params(cfg)
    assert builder.param_count(cfg) == pytest.approx(total, rel=1e-3)
    assert builder.param_count(cfg) == pytest.approx(4.75e9, rel=2e-3)
    # ISSUE 28's step: 38 sequences of about 1200 keys, 91 % of the
    # experts held hit: 8.7 GB of weights and half a GB of latent
    hit = 0.91 * 8 * 16
    _f, nbytes = fl.decode_steps_cost(cfg, [1200] * 38, 1, hit, 38 * 8,
                                      "bfloat16")
    weights = 2 * (fl.outside_experts_params(cfg)
                   + hit * fl.expert_params(cfg))
    assert weights == pytest.approx(8.7e9, rel=0.02)
    assert nbytes - weights == 2 * 9 * 576 * 1200 * 38
    assert nbytes / 819e9 == pytest.approx(11.2e-3, rel=0.03)
