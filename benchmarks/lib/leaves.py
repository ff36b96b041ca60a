"""Weights from the seed, and the norms the training check compares.

A builder describes its model's leaves as ``name -> (shape, mean, std)``
(``std`` 0 gives a constant); everything here works from that
description: one jitted call draws every leaf on the device, each leaf's
draw depending only on the seed and the leaf's place in the sorted names,
so the initial value can be drawn again, leaf by leaf, when the change is
measured and no second copy of the model is ever held.
"""
import functools

import numpy as np


def seed_key(seed):
    """A JAX key from any whole number (seeds pass 2**31)."""
    import jax
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32))


def _draw(key, index, shape, mean, std):
    import jax
    import jax.numpy as jnp
    if not std:
        return jnp.full(shape, mean, jnp.float32)
    return mean + std * jax.random.normal(jax.random.fold_in(key, index),
                                          shape, jnp.float32)


def make(specs, seed):
    """name -> float32 array on the default device, in one jitted call."""
    import jax
    order = sorted(specs)

    def draw_all(key):
        return {n: _draw(key, i, *specs[n]) for i, n in enumerate(order)}
    return jax.jit(draw_all)(seed_key(seed))


def _norm(x):
    import jax.numpy as jnp
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@functools.lru_cache(maxsize=None)
def _jitted_cut_norms():
    import jax

    @functools.partial(jax.jit, static_argnames=("cuts",))
    def cut_norms(v, cuts):
        return [_norm(v if rows is None else v[slice(*rows)])
                for _suffix, rows in cuts]
    return cut_norms


def _cuts(parts, name):
    return tuple((s, None if r is None else (r.start, r.stop))
                 for s, r in parts(name))


def norms(tree, parts):
    """name[.part] -> norm, on the device. ``parts(name)`` lists
    (suffix, rows) where one stored leaf holds several of the model's."""
    cut_norms = _jitted_cut_norms()
    out = {}
    for name, v in tree.items():
        cuts = _cuts(parts, name)
        for (suffix, _r), val in zip(cuts, cut_norms(v, cuts)):
            out[name + suffix] = val
    return out


def change_norms(specs, seed, params, parts):
    """name[.part] -> norm of (params[name] - its initial value). One
    program per kind of leaf: leaves that share shape, draw and parts
    share it."""
    import jax

    @functools.partial(jax.jit, static_argnames=("spec", "cuts"))
    def one(w, key, index, spec, cuts):
        d = w.astype("float32") - _draw(key, index, *spec)
        return [_norm(d if rows is None else d[slice(*rows)])
                for _suffix, rows in cuts]

    key = seed_key(seed)
    out = {}
    for i, n in enumerate(sorted(specs)):
        cuts = _cuts(parts, n)
        shape, mean, std = specs[n]
        vals = one(params[n], key, np.int32(i),
                   (tuple(shape), float(mean), float(std)), cuts)
        out.update({n + s: v for (s, _r), v in zip(cuts, vals)})
    return out


def make_loss_fn():
    """(softmax output of the step (N, V), labels of any shape with N
    entries) -> mean cross-entropy, as a device scalar."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def loss(probs, labels):
        idx = labels.reshape(-1).astype(jnp.int32)
        p = jnp.take_along_axis(probs, idx[:, None], axis=1)[:, 0]
        return -jnp.mean(jnp.log(p.astype(jnp.float32)))
    return loss


def weight_decayed(name):
    """mx.optimizer's rule: weights and normalisation scales decay,
    biases and shifts do not."""
    return name.endswith("_weight") or name.endswith("_gamma")
