"""Plain reference of ResNet (He et al. 2015): float32, convolutions and
the classifier at ``precision="highest"``, BatchNorm over the whole batch
in training mode, nothing of the program. Units are post-activation
bottlenecks; the stride of a stage's first unit sits on its first 1x1
convolution, as in the paper.

``precision`` "highest" is the reference. "fp8" is the control: the same
mathematics with both operands of every convolution and of the classifier
rounded to float8_e4m3fn, the step below the bfloat16 the configuration
computes in.
"""
import jax
import jax.numpy as jnp
from jax import lax


def _round(x, precision):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _conv(x, w, stride, pad, precision):
    return lax.conv_general_dilated(
        _round(x, precision), _round(w, precision), (stride, stride),
        [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=lax.Precision.HIGHEST)


def _bn(x, params, aux, name, cfg):
    """Training-mode BatchNorm: the batch's own mean and (biased)
    variance; the moving statistics follow with the stated momentum."""
    mean = jnp.mean(x, axis=(0, 2, 3))
    var = jnp.mean(jnp.square(x - mean[None, :, None, None]), axis=(0, 2, 3))
    m = cfg["batch_norm_momentum"]
    new_aux = {name + "_moving_mean": m * aux[name + "_moving_mean"]
               + (1 - m) * mean,
               name + "_moving_var": m * aux[name + "_moving_var"]
               + (1 - m) * var}
    inv = lax.rsqrt(var + cfg["batch_norm_eps"])
    out = (x - mean[None, :, None, None]) * (
        inv * params[name + "_gamma"])[None, :, None, None] \
        + params[name + "_beta"][None, :, None, None]
    return out, new_aux


def _unit(x, params, aux, name, stride, project, cfg, precision):
    new_aux = {}

    def conv_bn(h, conv, bn, k, s):
        h = _conv(h, params[name + conv + "_weight"], s, k // 2, precision)
        h, upd = _bn(h, params, aux, name + bn, cfg)
        new_aux.update(upd)
        return h
    h = jax.nn.relu(conv_bn(x, "conv1", "bn1", 1, stride))
    h = jax.nn.relu(conv_bn(h, "conv2", "bn2", 3, 1))
    h = conv_bn(h, "conv3", "bn3", 1, 1)
    short = conv_bn(x, "sc", "sc_bn", 1, stride) if project else x
    return jax.nn.relu(h + short), new_aux


def _max_pool_3x3_s2(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                             (1, 1, 2, 2), [(0, 0), (0, 0), (1, 1), (1, 1)])


def loss_sum(params, aux, x, y, cfg, precision="highest"):
    """(summed cross-entropy of images x (N, 3, H, W) against classes y
    (N,), the moving statistics after this batch)."""
    new_aux = {}
    h = _conv(x, params["conv0_weight"], 2, 3, precision)
    h, upd = _bn(h, params, aux, "bn0", cfg)
    new_aux.update(upd)
    h = _max_pool_3x3_s2(jax.nn.relu(h))
    for s, blocks in enumerate(cfg["stage_blocks"]):
        for b in range(blocks):
            name = "stage%d_unit%d_" % (s + 1, b + 1)
            unit = jax.checkpoint(
                lambda h_, p_, name=name, stride=2 if (b == 0 and s > 0)
                else 1, project=(b == 0): _unit(
                    h_, p_, aux, name, stride, project, cfg, precision))
            h, upd = unit(h, {k: v for k, v in params.items()
                              if k.startswith(name)})
            new_aux.update(upd)
    h = jnp.mean(h, axis=(2, 3))
    z = jnp.einsum("nk,ck->nc", _round(h, precision),
                   _round(params["fc1_weight"], precision),
                   precision=lax.Precision.HIGHEST) + params["fc1_bias"]
    logp = jax.nn.log_softmax(z, axis=-1)
    total = -jnp.sum(jnp.take_along_axis(logp, y[:, None], axis=1))
    return total, new_aux


def make_block_grad(cfg, precision="highest"):
    """Jitted (params, aux, acc, x, y) -> (loss_sum, acc + grads, aux).
    BatchNorm couples the rows of a batch, so a block is the whole batch."""
    def fn(params, aux, acc, x, y):
        (val, new_aux), g = jax.value_and_grad(loss_sum, has_aux=True)(
            params, aux, x, y.astype(jnp.int32), cfg, precision)
        return val, jax.tree_util.tree_map(jnp.add, acc, g), new_aux
    return jax.jit(fn, donate_argnums=(2,))


def loss_units(cfg, x):
    """The loss reported is the mean over the images."""
    return x.shape[0]


def grad_units(cfg, x):
    """SoftmaxOutput's default normalisation is none: the gradient is
    the sum over the batch, and the optimizer's rescale divides it."""
    return 1
