"""Custom-kernel escape hatch: mx.rtc.PallasKernel + the flash-attention
showcase kernel (reference surface: python/mxnet/rtc.py / mxrtc.h §2.22 —
NVRTC there, Pallas here). Runs in Pallas interpreter mode on the CPU rig;
numerics are identical to the compiled TPU path."""
import numpy as np
import pytest

import mxnet_tpu as mx


def test_pallas_kernel_elementwise():
    def scale_add(x_ref, y_ref, o_ref):
        o_ref[:] = x_ref[:] * 2.0 + y_ref[:]

    kern = mx.rtc.PallasKernel(scale_add, ((8, 128), np.float32),
                               interpret=True)
    rng = np.random.RandomState(0)
    x = rng.rand(8, 128).astype(np.float32)
    y = rng.rand(8, 128).astype(np.float32)
    out = kern(mx.nd.array(x), mx.nd.array(y))
    np.testing.assert_allclose(out.asnumpy(), x * 2 + y, rtol=1e-6)


def test_pallas_kernel_register_as_op():
    def relu_k(x_ref, o_ref):
        import jax.numpy as jnp
        o_ref[:] = jnp.maximum(x_ref[:], 0.0)

    kern = mx.rtc.PallasKernel(relu_k, ((4, 128), np.float32),
                               interpret=True)
    kern.register("my_pallas_relu")
    x = np.random.RandomState(1).randn(4, 128).astype(np.float32)
    out = mx.nd.my_pallas_relu(mx.nd.array(x))
    np.testing.assert_allclose(out.asnumpy(), np.maximum(x, 0), rtol=1e-6)
    # symbol path too
    s = mx.sym.my_pallas_relu(mx.sym.Variable("data"))
    ex = s.simple_bind(ctx=mx.cpu(), data=(4, 128))
    ex.arg_dict["data"][:] = x
    np.testing.assert_allclose(ex.forward()[0].asnumpy(), np.maximum(x, 0),
                               rtol=1e-6)


def test_cuda_module_points_to_pallas():
    with pytest.raises(NotImplementedError, match="Pallas"):
        mx.rtc.CudaModule("__global__ void k(){}")


def _ref_attention(q, k, v, causal=False):
    B, H, S, D = q.shape
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    if causal:
        mask = np.tril(np.ones((S, S), bool))
        s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v)


def test_flash_attention_matches_reference():
    import jax
    rng = np.random.RandomState(2)
    B, H, S, D = 2, 2, 256, 32
    q = rng.randn(B, H, S, D).astype(np.float32)
    k = rng.randn(B, H, S, D).astype(np.float32)
    v = rng.randn(B, H, S, D).astype(np.float32)
    from mxnet_tpu.ops.pallas import flash_attention
    # pin to CPU: the f64 oracle's tolerance assumes CPU f32 matmuls
    cpu = jax.local_devices(backend="cpu")[0]
    qj, kj, vj = (jax.device_put(a, cpu) for a in (q, k, v))
    out = np.asarray(flash_attention(qj, kj, vj, block_q=128, block_k=128,
                                     interpret=True))
    np.testing.assert_allclose(out, _ref_attention(q, k, v),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_causal_and_op():
    rng = np.random.RandomState(3)
    B, H, S, D = 1, 2, 128, 16
    q = rng.randn(B, H, S, D).astype(np.float32)
    k = rng.randn(B, H, S, D).astype(np.float32)
    v = rng.randn(B, H, S, D).astype(np.float32)
    out = mx.nd.FlashAttention(mx.nd.array(q), mx.nd.array(k),
                               mx.nd.array(v), causal=True,
                               block_q=64, block_k=64).asnumpy()
    np.testing.assert_allclose(out, _ref_attention(q, k, v, causal=True),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_grad_matches_xla():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas import flash_attention
    rng = np.random.RandomState(4)
    B, H, S, D = 1, 1, 128, 16
    cpu = jax.local_devices(backend="cpu")[0]
    q = jax.device_put(rng.randn(B, H, S, D).astype(np.float32), cpu)
    k = jax.device_put(rng.randn(B, H, S, D).astype(np.float32), cpu)
    v = jax.device_put(rng.randn(B, H, S, D).astype(np.float32), cpu)

    def f_flash(q, k, v):
        return flash_attention(q, k, v, interpret=True).sum()

    def f_ref(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v).sum()

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_flash_attention_causal_grad_with_padded_q():
    # S not a multiple of block_q: the recompute backward must use the same
    # top-aligned causal mask as the kernel (regression: a bottom-aligned
    # tril offset corrupted every real row's gradient)
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas import flash_attention
    rng = np.random.RandomState(7)
    B, H, S, D = 1, 1, 96, 16
    cpu = jax.local_devices(backend="cpu")[0]
    q = jax.device_put(rng.randn(B, H, S, D).astype(np.float32), cpu)
    k = jax.device_put(rng.randn(B, H, S, D).astype(np.float32), cpu)
    v = jax.device_put(rng.randn(B, H, S, D).astype(np.float32), cpu)

    def f_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_k=32,
                               interpret=True).sum()

    def f_ref(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v).sum()

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_pallas_kernel_multi_output_symbol_visible():
    def split_k(x_ref, a_ref, b_ref):
        a_ref[:] = x_ref[:] * 2.0
        b_ref[:] = x_ref[:] + 1.0

    kern = mx.rtc.PallasKernel(
        split_k, [((4, 128), np.float32), ((4, 128), np.float32)],
        interpret=True)
    kern.register("my_pallas_split")
    x = np.random.RandomState(8).rand(4, 128).astype(np.float32)
    a, b = mx.nd.my_pallas_split(mx.nd.array(x))
    np.testing.assert_allclose(a.asnumpy(), x * 2, rtol=1e-6)
    np.testing.assert_allclose(b.asnumpy(), x + 1, rtol=1e-6)
    s = mx.sym.my_pallas_split(mx.sym.Variable("data"))
    assert len(s.list_outputs()) == 2
    ex = s.simple_bind(ctx=mx.cpu(), data=(4, 128))
    ex.arg_dict["data"][:] = x
    outs = ex.forward()
    assert len(outs) == 2
    np.testing.assert_allclose(outs[1].asnumpy(), x + 1, rtol=1e-6)


def test_flash_attention_compiled_mode_off_tpu_raises():
    from mxnet_tpu.ops.pallas import flash_attention
    q = np.zeros((1, 1, 64, 16), np.float32)
    with pytest.raises(ValueError, match="needs a TPU"):
        flash_attention(q, q, q, interpret=False)


def test_flash_attention_lowers_to_mosaic_for_tpu():
    # cross-platform lowering: the forward and the one-pass backward go
    # through jax's Mosaic lowering at the bench's block shape without a
    # chip (libtpu's own compile of the custom calls is chip_smoke.py's
    # and test_serve_decode_compile.py's to prove)
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.flash_attention import _fa
    q = jax.ShapeDtypeStruct((4, 1024, 128), jnp.bfloat16)

    def loss(q, k, v):
        return _fa(q, k, v, 0.088, True, 512, 512, False) \
            .astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, q, q) \
        .lower(lowering_platforms=("tpu",)).as_text()
    for kernel in ("_fa_kernel", "_fa_bwd_kernel"):
        assert 'kernel_name = "%s"' % kernel in text
    assert text.count("@tpu_custom_call") == 2


def test_flash_attention_runs_on_each_devices_own_rows():
    # with batch_rows the whole differentiable attention is one shard_map
    # over the batch axes: rows stay where they are, values and gradients
    # unchanged
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu.ops.pallas import flash_attention
    rng = np.random.RandomState(9)
    q, k, v = (jnp.asarray(rng.randn(4, 2, 64, 16), jnp.float32)
               for _ in range(3))
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    rows = NamedSharding(mesh, P("data"))

    def f(q, k, v, batch_rows=None):
        return flash_attention(q, k, v, causal=True, block_q=32,
                               block_k=32, interpret=True,
                               batch_rows=batch_rows)

    def g(q, k, v, batch_rows=None):
        return jax.grad(lambda *a: (f(*a, batch_rows) ** 2).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    on_mesh = (mesh, ("data",))
    qs, ks, vs = (jax.device_put(a, rows) for a in (q, k, v))
    out = jax.jit(lambda *a: f(*a, on_mesh))(qs, ks, vs)
    grads = jax.jit(lambda *a: g(*a, on_mesh))(qs, ks, vs)
    assert out.sharding.is_equivalent_to(rows, out.ndim)
    np.testing.assert_allclose(np.asarray(out), np.asarray(f(q, k, v)),
                               rtol=1e-6, atol=1e-6)
    for a, b in zip(grads, g(q, k, v)):
        assert a.sharding.is_equivalent_to(rows, a.ndim)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_flash_attention_rejects_unaligned_keys():
    q = np.zeros((1, 1, 64, 16), np.float32)
    k = np.zeros((1, 1, 100, 16), np.float32)
    with pytest.raises(ValueError, match="multiple of block_k"):
        from mxnet_tpu.ops.pallas import flash_attention
        flash_attention(q, k, k, block_k=64, interpret=True)


def test_flash_attention_fused_bwd_cross_and_bf16():
    # fused Pallas backward: rectangular (Sk != S) grads match XLA, and the
    # bf16 path stays within bf16 tolerance of the f32 oracle
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas import flash_attention
    rng = np.random.RandomState(11)
    B, H, S, Sk, D = 1, 2, 64, 128, 16
    cpu = jax.local_devices(backend="cpu")[0]
    q = jax.device_put(rng.randn(B, H, S, D).astype(np.float32), cpu)
    k = jax.device_put(rng.randn(B, H, Sk, D).astype(np.float32), cpu)
    v = jax.device_put(rng.randn(B, H, Sk, D).astype(np.float32), cpu)

    def f_flash(q, k, v):
        return flash_attention(q, k, v, block_q=32, block_k=32,
                               interpret=True).sum()

    def f_ref(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v).sum()

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)

    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    g16 = jax.grad(lambda *a: f_flash(*a).astype(jnp.float32),
                   argnums=(0, 1, 2))(qb, kb, vb)
    for a, b in zip(g16, g_ref):
        err = np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b)))
        scale = np.max(np.abs(np.asarray(b))) + 1e-6
        assert err / scale < 0.06, err / scale
