"""Pipeline parallelism (parallel/pipeline.py): GPipe microbatch schedule
over a mesh axis, forward and gradients checked against the sequential
oracle on the 8-device virtual CPU mesh.

Reference parity target: the reference's inter-layer model parallelism
(group2ctx + PlaceDevice, src/executor/graph_executor.cc:279-393) — here
as an explicit SPMD schedule with ppermute stage hops.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.parallel import make_mesh, pipeline_apply, stack_stage_params

N_STAGES = 4


def _setup(dtype=np.float32, n_micro=8, mb=4, dim=16):
    rng = np.random.RandomState(0)
    stages = [{"w": rng.normal(0, 0.3, (dim, dim)).astype(dtype),
               "b": rng.normal(0, 0.1, (dim,)).astype(dtype)}
              for _ in range(N_STAGES)]
    x = rng.normal(0, 1, (n_micro, mb, dim)).astype(dtype)
    return stages, x


def _stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def _seq(stages, x):
    y = x
    for p in stages:
        y = jnp.tanh(y @ p["w"] + p["b"])
    return y


def test_pipeline_forward_matches_sequential():
    mesh = make_mesh({"pipe": N_STAGES})
    stages, x = _setup()
    out = pipeline_apply(_stage_fn, stack_stage_params(stages), x,
                         mesh=mesh, axis="pipe")
    np.testing.assert_allclose(np.asarray(out), np.asarray(_seq(stages, x)),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_gradients_match_sequential_f64():
    # float64 removes scan-order rounding: forward AND backward must be
    # bit-tight vs the sequential program
    mesh = make_mesh({"pipe": N_STAGES})
    with jax.enable_x64():
        stages, x = _setup(dtype=np.float64, n_micro=6, mb=2, dim=8)
        stacked = stack_stage_params(stages)

        def loss_pipe(params, xx):
            return jnp.sum(pipeline_apply(_stage_fn, params, xx, mesh=mesh,
                                          axis="pipe") ** 2)

        def loss_seq(ps, xx):
            return jnp.sum(_seq(ps, xx) ** 2)

        g = jax.grad(loss_pipe)(stacked, x)
        g_ref = jax.grad(loss_seq)(stages, x)
        for i in range(N_STAGES):
            np.testing.assert_allclose(np.asarray(g["w"][i]),
                                       np.asarray(g_ref[i]["w"]),
                                       rtol=1e-12, atol=1e-12)
        gx = jax.grad(lambda xx: loss_pipe(stacked, xx))(x)
        gx_ref = jax.grad(lambda xx: loss_seq(stages, xx))(x)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                                   rtol=1e-12, atol=1e-12)


def test_pipeline_trains_f32():
    # one SGD step through the pipelined loss moves params and tracks the
    # sequential update within f32 schedule-rounding tolerance
    mesh = make_mesh({"pipe": N_STAGES})
    stages, x = _setup(n_micro=4, mb=2, dim=8)
    stacked = stack_stage_params(stages)

    def loss(params, xx):
        return jnp.mean(pipeline_apply(_stage_fn, params, xx, mesh=mesh,
                                       axis="pipe") ** 2)

    g = jax.grad(loss)(stacked, x)
    g_ref = jax.grad(
        lambda ps, xx: jnp.mean(_seq(ps, xx) ** 2))(stages, x)
    for i in range(N_STAGES):
        np.testing.assert_allclose(np.asarray(g["w"][i]),
                                   np.asarray(g_ref[i]["w"]),
                                   rtol=5e-2, atol=5e-4)
    new_w = stacked["w"] - 0.1 * g["w"]
    assert not np.allclose(np.asarray(new_w), np.asarray(stacked["w"]))


def test_pipeline_rejects_empty_microbatches():
    mesh = make_mesh({"pipe": N_STAGES})
    stages, x = _setup()
    with pytest.raises(ValueError):
        pipeline_apply(_stage_fn, stack_stage_params(stages), x[:0],
                       mesh=mesh, axis="pipe")


def test_pipeline_heterogeneous_embed_to_loss():
    # first_fn embeds int ids -> wire, stage_fn maps wire -> wire,
    # last_fn projects wire -> per-token logits; checks the full
    # embed -> blocks -> head shape change against the sequential oracle
    mesh = make_mesh({"pipe": N_STAGES})
    rng = np.random.RandomState(1)
    V, D, O, n_micro, mb, T = 11, 8, 5, 6, 2, 3
    stages = [{"w": rng.normal(0, 0.3, (D, D)).astype(np.float32),
               "b": rng.normal(0, 0.1, (D,)).astype(np.float32)}
              for _ in range(N_STAGES)]
    fparams = {"emb": rng.normal(0, 1, (V, D)).astype(np.float32)}
    lparams = {"head": rng.normal(0, 0.3, (D, O)).astype(np.float32)}
    ids = rng.randint(0, V, (n_micro, mb, T)).astype(np.int32)

    def first(p, raw):
        return p["emb"][raw]                     # (mb, T, D)

    def last(p, h):
        return h @ p["head"]                     # (mb, T, O)

    out = pipeline_apply(_stage_fn, stack_stage_params(stages),
                         jnp.asarray(ids), mesh=mesh, axis="pipe",
                         first_fn=first, first_params=fparams,
                         last_fn=last, last_params=lparams)
    assert out.shape == (n_micro, mb, T, O)
    ref = last(lparams, _seq(stages, first(fparams, jnp.asarray(ids))))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    # gradients flow into the replicated first/last params too
    def loss(fp, sp, lp):
        o = pipeline_apply(_stage_fn, sp, jnp.asarray(ids), mesh=mesh,
                           axis="pipe", first_fn=first, first_params=fp,
                           last_fn=last, last_params=lp)
        return jnp.mean(o ** 2)

    gf, gs, gl = jax.grad(loss, argnums=(0, 1, 2))(fparams,
                                                   stack_stage_params(stages),
                                                   lparams)
    def ref_loss(fp, sp_list, lp):
        return jnp.mean(last(lp, _seq(sp_list, first(fp, jnp.asarray(ids)))) ** 2)
    rf, rs, rl = jax.grad(ref_loss, argnums=(0, 1, 2))(fparams, stages, lparams)
    np.testing.assert_allclose(np.asarray(gf["emb"]), np.asarray(rf["emb"]),
                               rtol=5e-4, atol=5e-6)
    np.testing.assert_allclose(np.asarray(gl["head"]), np.asarray(rl["head"]),
                               rtol=5e-4, atol=5e-6)
    for i in range(N_STAGES):
        np.testing.assert_allclose(np.asarray(gs["w"][i]),
                                   np.asarray(rs[i]["w"]),
                                   rtol=5e-4, atol=5e-6)


def test_pipeline_remat_matches_plain():
    mesh = make_mesh({"pipe": N_STAGES})
    stages, x = _setup(n_micro=4, mb=2, dim=8)
    stacked = stack_stage_params(stages)

    def loss(params, xx, remat):
        return jnp.mean(pipeline_apply(_stage_fn, params, xx, mesh=mesh,
                                       axis="pipe", remat=remat) ** 2)

    g_plain = jax.grad(lambda p: loss(p, x, False))(stacked)
    g_remat = jax.grad(lambda p: loss(p, x, True))(stacked)
    np.testing.assert_allclose(np.asarray(g_remat["w"]),
                               np.asarray(g_plain["w"]),
                               rtol=1e-6, atol=1e-7)


def test_1f1b_stacked_and_tuple_match_sequential():
    """pipeline_1f1b's two parameter layouts (stacked/P(axis)-sharded
    for homogeneous stages, per-stage tuple for heterogeneous) must both
    reproduce the sequential model's gradients exactly."""
    from mxnet_tpu.parallel.pipeline import pipeline_1f1b

    D = 8
    rng = np.random.RandomState(0)
    Ws = [jnp.asarray(rng.randn(D, D).astype(np.float32) * 0.3)
          for _ in range(N_STAGES)]
    We = jnp.asarray(rng.randn(6, D).astype(np.float32) * 0.3)
    Wh = jnp.asarray(rng.randn(D, 4).astype(np.float32) * 0.3)
    X = jnp.asarray(rng.randn(16, 6).astype(np.float32))
    L = jnp.asarray(rng.randn(16, 4).astype(np.float32))
    mesh = make_mesh({"pipe": N_STAGES})
    inputs = {"data": X.reshape(8, 2, 6), "label": L.reshape(8, 2, 4)}
    first = lambda p, raw, k: raw["data"] @ p["we"]
    last = lambda p, y, raw, k: jnp.sum((y @ p["wh"] - raw["label"]) ** 2,
                                        axis=-1)
    fp, lp = {"we": We}, {"wh": Wh}
    sfn = lambda p, x, k: jnp.tanh(x @ p["w"])

    o1, g1 = pipeline_1f1b(sfn, stack_stage_params([{"w": w} for w in Ws]),
                           inputs, mesh=mesh, axis="pipe", first_fn=first,
                           first_params=fp, last_fn=last, last_params=lp)
    o2, g2 = pipeline_1f1b([sfn] * N_STAGES, tuple({"w": w} for w in Ws),
                           inputs, mesh=mesh, axis="pipe", first_fn=first,
                           first_params=fp, last_fn=last, last_params=lp)

    def ref_loss(ps):
        fp_, ws, lp_ = ps
        h = X @ fp_["we"]
        for w in ws:
            h = jnp.tanh(h @ w)
        return jnp.sum(jnp.sum((h @ lp_["wh"] - L) ** 2, axis=-1))

    gr = jax.grad(ref_loss)((fp, tuple(Ws), lp))
    for k in range(N_STAGES):
        np.testing.assert_allclose(np.asarray(g1["stages"]["w"][k]),
                                   np.asarray(gr[1][k]), rtol=5e-3,
                                   atol=5e-4)
        np.testing.assert_allclose(np.asarray(g2["stages"][k]["w"]),
                                   np.asarray(gr[1][k]), rtol=5e-3,
                                   atol=5e-4)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-5, atol=1e-5)
