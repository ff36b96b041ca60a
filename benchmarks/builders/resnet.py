"""ResNet (He et al. 2015, post-activation bottleneck units) as this
program builds and names it: ``mxnet_tpu.models.resnet`` at version 1.
Convolutions and the classifier are drawn N(0, sqrt(2 / fan_in)) from the
seed (He et al.'s own initialisation), BatchNorm scales 1, shifts and the
classifier's bias 0, moving means 0 and moving variances 1.
"""
import math

from benchmarks.lib import flops as _flops


def _convs(cfg):
    """name -> (cout, cin, k) of every convolution, and the BatchNorm
    that follows it (name, channels)."""
    widths = cfg["stage_widths"]
    convs = {"conv0": (cfg["stem_width"], 3, 7)}
    bns = {"bn0": cfg["stem_width"]}
    cin = cfg["stem_width"]
    for s, (blocks, cout) in enumerate(zip(cfg["stage_blocks"], widths)):
        for b in range(blocks):
            p = "stage%d_unit%d_" % (s + 1, b + 1)
            mid = cout // cfg["bottleneck_ratio"]
            convs[p + "conv1"] = (mid, cin, 1)
            convs[p + "conv2"] = (mid, mid, 3)
            convs[p + "conv3"] = (cout, mid, 1)
            bns[p + "bn1"], bns[p + "bn2"], bns[p + "bn3"] = mid, mid, cout
            if b == 0:
                convs[p + "sc"] = (cout, cin, 1)
                bns[p + "sc_bn"] = cout
            cin = cout
    return convs, bns


def leaf_specs(cfg):
    convs, bns = _convs(cfg)
    out = {}
    for n, (cout, cin, k) in convs.items():
        out[n + "_weight"] = ((cout, cin, k, k), 0.0,
                              math.sqrt(2.0 / (cin * k * k)))
    for n, c in bns.items():
        out[n + "_gamma"] = ((c,), 1.0, 0.0)
        out[n + "_beta"] = ((c,), 0.0, 0.0)
    last = cfg["stage_widths"][-1]
    out["fc1_weight"] = ((cfg["num_classes"], last), 0.0,
                         math.sqrt(2.0 / last))
    out["fc1_bias"] = ((cfg["num_classes"],), 0.0, 0.0)
    return out


def aux_specs(cfg):
    _convs_, bns = _convs(cfg)
    out = {}
    for n, c in bns.items():
        out[n + "_moving_mean"] = ((c,), 0.0, 0.0)
        out[n + "_moving_var"] = ((c,), 1.0, 0.0)
    return out


def parts(cfg, name):
    return [("", None)]


def symbol(cfg, traffic):
    from mxnet_tpu.models import resnet
    size = cfg["image_size"]
    return resnet.resnet(
        units=list(cfg["stage_blocks"]), num_stages=len(cfg["stage_blocks"]),
        filter_list=[cfg["stem_width"]] + list(cfg["stage_widths"]),
        num_classes=cfg["num_classes"], image_shape=(3, size, size),
        bottleneck=True, version=1)


def unit():
    return "images"


def train_flops_per_unit(cfg, traffic):
    return _flops.resnet_train_flops_per_image(cfg)
