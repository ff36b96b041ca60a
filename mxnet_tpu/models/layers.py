"""The small parts every served decoder block is made of, as pure JAX
functions: the product in the weight's dtype, RMS normalisation, the
rotary and its frequencies, the SiLU-gated MLP, a masked softmax.

``models/mla_moe.py`` and ``models/sparse_linear.py`` both build their
layers from these; neither holds a copy. The plain rotary is
:func:`yarn_frequencies` without ``scaling`` (YaRN's factor-1 case).

Weights keep the dtype they are given (bfloat16 as served, float32 in
tests); every product accumulates in float32, and norms, rotary angles and
softmax are float32.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import numpy as np

__all__ = ["yarn_frequencies", "product", "dense", "rms_norm", "rope",
           "gated_mlp", "softmax_where", "layer_params"]

NEG = -1e30


def _yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def yarn_frequencies(d: int, theta: float,
                     scaling: Optional[Dict[str, Any]]):
    """``(frequencies (d/2,) float32, the factor on cos and sin, the
    factor on the softmax scale)`` of a rotary over ``d`` lanes. Without
    ``scaling`` the plain ``theta**(-2i/d)``; with ``deepseek_yarn`` pair
    ``i`` turns at ``f_i (1 - g_i) + (f_i / factor) g_i``, ``g`` a ramp
    from the pair that makes ``beta_fast`` turns over the original length
    to the one that makes ``beta_slow``."""
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if not scaling:
        return f.astype(np.float32), 1.0, 1.0
    kind = scaling.get("type", scaling.get("rope_type"))
    if kind != "deepseek_yarn":
        raise ValueError("rope_scaling of type %r is not served" % (kind,))
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def pair_of(turns):
        return d * math.log(orig / (2 * math.pi * turns)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair_of(float(scaling.get("beta_fast", 32)))), 0)
    high = min(math.ceil(pair_of(float(scaling.get("beta_slow", 1)))), d - 1)
    g = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    all_dim = float(scaling.get("mscale_all_dim", 0.0))
    on_angles = _yarn_mscale(factor, float(scaling.get("mscale", 1.0))) \
        / _yarn_mscale(factor, all_dim)
    on_scores = _yarn_mscale(factor, all_dim) ** 2 if all_dim else 1.0
    return (f * (1 - g) + f / factor * g).astype(np.float32), \
        on_angles, on_scores


def product(eq, a, b):
    """``einsum(eq, a, b)`` with ``a`` cast to ``b``'s dtype (the weight's,
    the cache's), accumulated in float32 (``rtc.product_operands``: off
    the TPU the rounded operands are multiplied as float32)."""
    import jax.numpy as jnp
    from .. import rtc
    a, b = rtc.product_operands(a, b)
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def dense(x, w):
    """``x @ w.T`` in the weight's dtype, accumulated in float32."""
    return product("...k,nk->...n", x, w)


def rms_norm(x, gamma, eps):
    import jax.numpy as jnp
    from jax import lax
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * gamma.astype(jnp.float32)


def rope(x, pos, freq, mscale: float = 1.0):
    """Rotary on the last axis of ``x (N, ..., d)``: the pair ``(x[i],
    x[i + d/2])`` turns by ``pos[n]`` times ``freq[i]`` (from
    :func:`yarn_frequencies`, which also gives ``mscale``, the factor on
    cos and sin)."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freq)[None]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos = (jnp.cos(ang) * mscale).reshape(shape)
    sin = (jnp.sin(ang) * mscale).reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def gated_mlp(h, gate, up, down):
    import jax
    return dense(jax.nn.silu(dense(h, gate)) * dense(h, up), down)


def softmax_where(s, keep):
    """Softmax over the last axis of ``s`` where ``keep``."""
    import jax
    import jax.numpy as jnp
    return jax.nn.softmax(jnp.where(keep, s, NEG), axis=-1)


def layer_params(params, i):
    """Layer ``i``'s leaves, by their names without the layer's prefix."""
    pre = "layer%d_" % i
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
