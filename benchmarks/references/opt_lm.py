"""Plain reference of the OPT decoder: float32 ``jax.numpy``, products at
``precision="highest"``, dense causal attention, no kernels, no cache,
nothing of the program. Follows Zhang et al. (pre-LayerNorm, ReLU,
learned positions, biases) with the configuration file's departures: the
head is untied and has a bias, positions are not offset.

``precision`` "highest" is the reference. "fp8" is the control: the same
mathematics with both operands of every product rounded to
float8_e4m3fn, the step below the bfloat16 the configuration computes in.
"""
import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _round(x, precision):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _mm(a, b, precision):
    """a @ b.T at full float32 accuracy, operands rounded for a control."""
    return jnp.einsum("...k,nk->...n", _round(a, precision),
                      _round(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def _ln(x, gamma, beta):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * gamma + beta


def _layer(x, p, heads, precision):
    """One block on (T, D)."""
    t, d = x.shape
    dh = d // heads
    h = _ln(x, p["ln1_gamma"], p["ln1_beta"])
    qkv = _mm(h, p["att_qkv_weight"], precision) + p["att_qkv_bias"]
    qkv = qkv.reshape(t, 3, heads, dh)
    q, k, v = (qkv[:, i].transpose(1, 0, 2) for i in range(3))   # (H,T,dh)
    s = jnp.einsum("htd,hkd->htk", _round(q, precision),
                   _round(k, precision),
                   precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(
                       jnp.float32(dh))
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("htk,hkd->htd", _round(a, precision),
                     _round(v, precision),
                     precision=jax.lax.Precision.HIGHEST)
    ctx = ctx.transpose(1, 0, 2).reshape(t, d)
    x = x + _mm(ctx, p["att_proj_weight"], precision) + p["att_proj_bias"]
    h = _ln(x, p["ln2_gamma"], p["ln2_beta"])
    h = jax.nn.relu(_mm(h, p["ff1_weight"], precision) + p["ff1_bias"])
    return x + _mm(h, p["ff2_weight"], precision) + p["ff2_bias"]


def _layer_params(params, i):
    pre = "layer%d_" % i
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def _num_layers(params):
    n = 0
    while "layer%d_ln1_gamma" % n in params:
        n += 1
    return n


def hidden(params, tokens, heads, precision="highest", remat=False):
    """Final hidden states (T, D) of one row of token ids (T,)."""
    t = tokens.shape[0]
    x = params["tok_embed_weight"][tokens] + params["pos_embed_weight"][:t]
    layer = functools.partial(_layer, heads=heads, precision=precision)
    if remat:
        layer = jax.checkpoint(layer)
    for i in range(_num_layers(params)):
        x = layer(x, _layer_params(params, i))
    return _ln(x, params["final_ln_gamma"], params["final_ln_beta"])


def loss_sum(params, x, y, heads, precision="highest"):
    """Summed next-token cross-entropy of rows x (R, T) against y."""
    def row(tokens, labels):
        h = hidden(params, tokens, heads, precision, remat=True)
        z = _mm(h, params["lm_head_weight"], precision) \
            + params["lm_head_bias"]
        logp = jax.nn.log_softmax(z, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=1))
    total = jnp.float32(0)
    for r in range(x.shape[0]):
        total = total + row(x[r], y[r])
    return total


def make_rows_forward(cfg, precision="highest"):
    """Jitted (params, tokens (T,), at (K,)) -> logits (K, V) read off
    the positions ``at`` of one padded row: what lies after a position
    does not reach it, so one padded length serves every request."""
    heads = cfg["num_attention_heads"]

    def fn(params, tokens, at):
        x = hidden(params, tokens, heads, precision)[at]
        return _mm(x, params["lm_head_weight"], precision) \
            + params["lm_head_bias"]
    return jax.jit(fn)


def make_block_grad(cfg, precision="highest"):
    """Jitted (params, aux, acc, x, y) -> (loss_sum, acc + grads, aux):
    one block of rows' summed loss and its gradient added to ``acc`` in
    place. The model has no auxiliary state; ``aux`` passes through."""
    heads = cfg["num_attention_heads"]

    def fn(params, aux, acc, x, y):
        val, g = jax.value_and_grad(loss_sum)(params, x, y, heads,
                                              precision)
        return val, jax.tree_util.tree_map(jnp.add, acc, g), aux
    return jax.jit(fn, donate_argnums=(2,))


def loss_units(cfg, x):
    """What the summed loss is averaged over: every token of the block."""
    return x.shape[0] * x.shape[1]


def grad_units(cfg, x):
    """The symbol's SoftmaxOutput normalises by its rows
    (``normalization="batch"``): every token of the block."""
    return x.shape[0] * x.shape[1]

