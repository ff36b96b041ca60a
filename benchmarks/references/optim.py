"""The optimizers' published update rules in plain float32 ``jax.numpy``,
as ``mx.optimizer`` documents them (reference MXNet semantics): the
gradient the optimizer gets is ``rescale * grad + wd * weight`` for leaves
that decay."""
import functools

import jax
import jax.numpy as jnp


def effective_grad(w, g, rescale, wd):
    return rescale * g + wd * w


@functools.partial(jax.jit, donate_argnums=(0, 2, 3),
                   static_argnames=("beta1", "beta2", "eps"))
def adam_leaf(w, g_eff, m, v, lr, t, beta1, beta2, eps):
    """mx.optimizer.Adam: bias correction folded into the rate."""
    m = beta1 * m + (1 - beta1) * g_eff
    v = beta2 * v + (1 - beta2) * jnp.square(g_eff)
    rate = lr * jnp.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
    return w - rate * m / (jnp.sqrt(v) + eps), m, v


@functools.partial(jax.jit, donate_argnums=(0, 2),
                   static_argnames=("momentum",))
def sgd_mom_leaf(w, g_eff, mom, lr, momentum):
    """mx.optimizer.SGD with momentum."""
    mom = momentum * mom - lr * g_eff
    return w + mom, mom


def mx_params(opt):
    """The configuration's optimizer group as ``fit(optimizer_params=)``."""
    return {k: v for k, v in opt.items() if k != "name"}


def init_state(opt, params):
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    if opt["name"] == "adam":
        return {"m": zeros(params), "v": zeros(params)}
    if opt["name"] == "sgd":
        return {"mom": zeros(params)}
    raise ValueError("no reference for optimizer %r" % opt["name"])


def update_leaf(opt, name, w, g_eff, state, t):
    """One step on one leaf; its state is updated in place, the new
    weight returned."""
    lr = jnp.float32(opt["learning_rate"])
    if opt["name"] == "adam":
        w, state["m"][name], state["v"][name] = adam_leaf(
            w, g_eff, state["m"][name], state["v"][name], lr,
            jnp.float32(t), beta1=opt["beta1"], beta2=opt["beta2"],
            eps=opt["epsilon"])
    else:
        w, state["mom"][name] = sgd_mom_leaf(
            w, g_eff, state["mom"][name], lr, momentum=opt["momentum"])
    return w


def first_grad(opt, states):
    """The first gradient as the optimizer got it, read off the program's
    optimizer state after one step: (tree, factor), the gradient being
    ``factor * tree``. Adam's first moment is (1 - beta1) * g, momentum
    SGD's buffer is -lr * g. The tree is the state's own leaves, so that
    no copy of it is made."""
    tree = {k: s[0] if isinstance(s, (tuple, list)) else s
            for k, s in states.items()}
    if opt["name"] == "adam":
        return tree, 1.0 / (1.0 - opt["beta1"])
    return tree, -1.0 / opt["learning_rate"]
