"""Every operation and byte count against figures worked by hand, and the
table of peaks."""
import json
import os

import pytest

from benchmarks.lib import device, flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def opt():
    return _config("opt-1.3b-l8.json")


def test_opt_parameter_counts(opt):
    d, f, v = 2048, 8192, 50272
    per_layer = 3 * d * d + d * d + 2 * d * f          # 50 331 648
    assert per_layer == 50331648
    assert flops.lm_matmul_params(opt) == 8 * per_layer + d * v
    assert flops.lm_matmul_params(opt) == 505610240
    # untied head, biases, LayerNorms, 2048 learned positions
    assert flops.lm_param_count(opt) == (
        2 * v * d + v + 2048 * d + 2 * d
        + 8 * (per_layer + 3 * d + d + f + d + 4 * d))
    assert flops.lm_param_count(opt) == 613028960
    # and it is what the builder draws, leaf by leaf
    import math
    from benchmarks.builders import opt_lm
    assert sum(math.prod(shape) for shape, _m, _s
               in opt_lm.leaf_specs(opt).values()) == 613028960


def test_opt_flops_per_token(opt):
    # forward: 2 x 505.6 M weights, and 4 x d x context for scores and
    # mixing in each of 8 layers
    assert flops.lm_forward_flops_per_token(opt, 1) \
        == 2 * 505610240 + 8 * 4 * 2048
    # a packed causal row of 2048: a token sees 1024.5 keys on average;
    # backward is twice the forward
    assert flops.lm_train_flops_per_token(opt, 2048) == pytest.approx(
        3 * (1011220480 + 8 * 4 * 2048 * 1024.5))
    assert flops.lm_train_flops_per_token(opt, 2048) == pytest.approx(
        3.235e9, rel=1e-3)


def test_flash_attention_cost(opt):
    # one row, one head, one layer, forward: scores and mixing over the
    # causal half, 2 products of 2 x 64 x 2048 x 2049 / 2
    per_head = 4 * 64 * 2048 * 2049 / 2
    need_f, need_b = flops.flash_attention_cost(opt, 7, 2048, "bfloat16")
    assert need_f == pytest.approx(7 * 32 * 8 * per_head * 3)
    # q, k, v, o once forward (4 tensors) and q, k, v, o, do, dq, dk, dv
    # backward (8), each rows x heads x 2048 x 64 x 2 bytes, in 8 layers
    assert need_b == 8 * 12 * (7 * 32 * 2048 * 64 * 2)
    least, bound = flops.roofline_seconds(need_f, need_b, V5E)
    assert bound == "compute"
    assert least == pytest.approx(need_f / 197e12)


def test_decode_steps_cost(opt):
    # two steps; three tokens that saw 100, 200 and 300 keys
    need_f, need_b = flops.decode_steps_cost(opt, [100, 200, 300], 2,
                                             "bfloat16")
    assert need_f == 3 * 1011220480 + 8 * 4 * 2048 * 600
    # weights once a step, keys and values of 8 layers x 2048 wide once
    # for each token, two bytes each
    assert need_b == (2 * 505610240 + 2 * 8 * 2048 * 600) * 2
    least, bound = flops.roofline_seconds(need_f, need_b, V5E)
    assert bound == "memory"
    assert least == pytest.approx(need_b / 819e9)


def test_resnet50_counts():
    cfg = _config("resnet-50.json")
    # He et al. 2015, table 1: 3.8 x 10^9 multiply-adds at 224 x 224
    macs = flops.resnet_forward_macs(cfg)
    assert macs == 3857973248
    # by hand: the stem, 7 x 7 x 3 x 64 at 112 x 112
    stem = 7 * 7 * 3 * 64 * 112 * 112
    # conv2_x, first unit, at 56 x 56: 1x1 64->64, 3x3 64->64,
    # 1x1 64->256 and the projection 64->256
    unit = (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256) * 56 * 56
    assert stem == 118013952 and unit == 231211008
    tiny = dict(cfg, stage_blocks=[1], stage_widths=[256])
    assert flops.resnet_forward_macs(tiny) == stem + unit + 256 * 1000
    assert flops.resnet_train_flops_per_image(cfg) == 6 * macs


def test_peaks_table_has_the_v5e_and_no_default():
    table = device.peaks_table(ROOT)
    assert set(table) == {"TPU v5 lite"}
    v5e = table["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert v5e["ici_bits_per_s"] == 1600e9
    with open(os.path.join(ROOT, "benchmarks", "peaks.json")) as f:
        assert "Google Cloud" in json.load(f)["source"]


def test_no_chip_or_an_unknown_chip_is_an_error():
    # the tests run on the CPU: a measurement finds no accelerator
    with pytest.raises(device.DeviceError):
        device.describe(1, ROOT, rehearse=False)
    info, peaks = device.describe(1, ROOT, rehearse=True)
    assert info["platform"] == "cpu" and peaks is None
