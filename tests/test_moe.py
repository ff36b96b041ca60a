"""Expert parallelism (parallel/moe.py): routing, capacity, the sharded
all_to_all lowering, and exactness against a dense oracle.

The reference has no MoE (SURVEY.md §2.21) — this is the TPU build's
modern-capability extension; tests follow the repo's numpy-oracle style.
f64 is used for tight comparisons because this backend's f32 matmuls run
at DEFAULT (bf16-accumulate) precision on CPU.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.moe import moe_init, moe_apply


def _dense_oracle(params, x, k=2):
    """Apply every expert to every token; gather top-k with renormalized
    gates (no capacity drops)."""
    logits = x @ params["router"]
    probs = jax.nn.softmax(logits)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / gate.sum(-1, keepdims=True)
    h = jax.nn.gelu(jnp.einsum("td,edh->teh", x, params["wi"]))
    y = jnp.einsum("teh,ehd->ted", h, params["wo"])
    sel = jnp.take_along_axis(y, idx[:, :, None], axis=1)
    return jnp.einsum("tk,tkd->td", gate, sel)


def test_moe_matches_dense_oracle_f64():
    with jax.enable_x64():
        rng = np.random.RandomState(0)
        T, D, H, E = 64, 16, 32, 8
        params = moe_init(rng, D, H, E, dtype=np.float64)
        x = rng.normal(0, 1, (T, D))
        out, aux = moe_apply(params, x, top_k=2, capacity_factor=8.0)
        ref = _dense_oracle(params, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-10, atol=1e-12)
        assert float(aux) > 0


def test_moe_capacity_drops_tokens():
    with jax.enable_x64():
        rng = np.random.RandomState(1)
        T, D, H, E = 32, 8, 16, 4
        params = moe_init(rng, D, H, E, dtype=np.float64)
        # route everything to one expert: tokens over capacity must differ
        # from the ample-capacity result
        params["router"][:, 0] = 5.0
        x = rng.normal(0, 1, (T, D))
        full, _ = moe_apply(params, x, top_k=1, capacity_factor=E * 1.0)
        tight, _ = moe_apply(params, x, top_k=1, capacity_factor=0.25)
        assert not np.allclose(np.asarray(full), np.asarray(tight))
        # dropped tokens produce zero output rows (gate renorm denom -> 0)
        n_zero = int(np.sum(np.all(np.asarray(tight) == 0, axis=1)))
        assert n_zero > 0


def test_moe_sharded_matches_unsharded():
    mesh = make_mesh({"expert": 8})
    with jax.enable_x64():
        rng = np.random.RandomState(2)
        T, D, H, E = 64, 16, 32, 8
        params = moe_init(rng, D, H, E, dtype=np.float64)
        x = rng.normal(0, 1, (T, D))
        out, _ = moe_apply(params, x, capacity_factor=8.0)
        out_sh, _ = jax.jit(
            lambda p, xx: moe_apply(p, xx, capacity_factor=8.0,
                                    mesh=mesh))(params, x)
        np.testing.assert_allclose(np.asarray(out_sh), np.asarray(out),
                                   rtol=1e-10, atol=1e-12)


def test_moe_sharded_lowering_redistributes_tokens():
    # dp x ep: tokens sharded over "data", experts over "expert" — the
    # dispatch einsum must move tokens across devices (GSPMD picks
    # all-to-all or all-gather depending on shapes)
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh({"data": 2, "expert": 4})
    rng = np.random.RandomState(3)
    params = moe_init(rng, 16, 32, 8)
    x = rng.normal(0, 1, (64, 16)).astype(np.float32)
    x_sh = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    txt = jax.jit(
        lambda p, xx: moe_apply(p, xx, mesh=mesh)[0]
    ).lower(params, x_sh).compile().as_text()
    assert ("all-to-all" in txt) or ("all-gather" in txt)


def test_moe_gradients_flow_and_aux_balances():
    rng = np.random.RandomState(4)
    T, D, H, E = 64, 8, 16, 4
    params = moe_init(rng, D, H, E)
    x = rng.normal(0, 1, (T, D)).astype(np.float32)

    def loss(p):
        out, aux = moe_apply(p, x)
        return jnp.mean(out ** 2) + 0.01 * aux

    g = jax.grad(loss)(params)
    for k in ("router", "wi", "wo"):
        assert float(jnp.linalg.norm(g[k])) > 0, k
    # perfectly uniform routing minimizes the GShard aux loss at 1.0
    _, aux = moe_apply(params, x)
    assert float(aux) >= 1.0 - 1e-3


def test_moe_bf16_routing_exact_beyond_256_assignments():
    # Routing bookkeeping must be exact in int32: with bf16 activations the
    # cumsum position counters saturate at 256 (bf16 has 8 mantissa bits),
    # so tokens past the 256th collide in one capacity slot and their
    # dispatched activations get summed together. Force every token to one
    # expert with ample capacity; each token's output must then equal the
    # dense bf16 FFN of that token alone.
    rng = np.random.RandomState(7)
    T, D, H, E = 1024, 16, 32, 4          # 1024 assignments to expert 0
    params = moe_init(rng, D, H, E)
    params["router"] = np.zeros((D, E), np.float32)
    x = rng.normal(0, 1, (T, D)).astype(np.float32)
    x[:, 0] = 5.0                          # all tokens prefer expert 0
    params["router"][0, 0] = 10.0
    p16 = {k: jnp.asarray(v, jnp.bfloat16) for k, v in params.items()}
    x16 = jnp.asarray(x, jnp.bfloat16)

    out, _ = moe_apply(p16, x16, top_k=1, capacity_factor=float(E))
    dense = jax.nn.gelu(x16 @ p16["wi"][0]) @ p16["wo"][0]
    err = jnp.max(jnp.abs((out - dense).astype(jnp.float32)))
    scale = float(jnp.max(jnp.abs(dense.astype(jnp.float32)))) + 1e-6
    assert float(err) / scale < 0.05, float(err) / scale
