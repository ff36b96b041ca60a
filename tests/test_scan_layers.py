"""Scan-over-layers (ISSUE 9): detection soundness, bit-identical
lowering, and the fused-fit integration.

The contract (mxnet_tpu/symbol/scan.py): chains of verified-isomorphic
repeated blocks lower through ONE ``jax.lax.scan``; anything that does
not verify falls back to the unrolled path silently. Forward is
bit-identical to unrolled execution; backward is allowed 2 float32 ulps
(XLA fuses the pointwise backward chains differently across the two
program shapes — the divergence is reassociation, not math).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym_mod
from mxnet_tpu.models import transformer
from mxnet_tpu.symbol.scan import build_scan_plan

sym = mx.sym

V, T, B = 64, 8, 2


def _tf(num_layers=4, d_model=32, seq_len=T):
    return transformer.get_symbol(vocab_size=V, num_layers=num_layers,
                                  d_model=d_model, n_heads=2,
                                  seq_len=seq_len)


def _bind_pair(net, data_shapes, label_shapes=None, seed=3):
    """Two executors over identical params/RNG: scan off and scan on."""
    executors = []
    for mode in ("off", "2"):
        mx.config.set("MXNET_TPU_SCAN_LAYERS", mode)
        try:
            kw = {n: s for n, s in data_shapes.items()}
            if label_shapes:
                kw.update(label_shapes)
            executors.append(net.simple_bind(mx.cpu(), **kw))
        finally:
            mx.config.reset("MXNET_TPU_SCAN_LAYERS")
    ex0, ex1 = executors
    rs = np.random.RandomState(seed)
    for n, a in ex0.arg_dict.items():
        val = rs.uniform(-0.1, 0.1, a.shape).astype(np.float32)
        a[:] = val
        ex1.arg_dict[n][:] = val
    ex1._base_key = ex0._base_key
    return ex0, ex1


# ------------------------------------------------------------- detection

def test_detects_transformer_chain():
    plan = build_scan_plan(_tf(4), min_repeat=2)
    assert plan is not None
    assert plan.n_layers == 4
    assert len(plan.var_lists) == 12          # 12 params per block
    assert all(len(v) == 4 for v in plan.var_lists.values())


def test_min_repeat_threshold():
    net = _tf(3)
    assert build_scan_plan(net, min_repeat=4) is None
    assert build_scan_plan(net, min_repeat=2) is not None


def test_no_chain_in_mlp():
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=16,
                             name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=16, name="fc2")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=16, name="fc3")
    # fc1/fc2/fc3 form a name family but fc3 feeds no next block — the
    # chain check must reject rather than mis-scan
    assert build_scan_plan(net, min_repeat=2) is None


def test_shared_weight_chain_falls_back():
    # RNN-style unroll: ONE weight variable used by every step — no
    # per-layer family, so no plan
    x = sym.Variable("data")
    w = sym.Variable("w")
    for i in range(4):
        x = sym.FullyConnected(x, weight=w, no_bias=True, num_hidden=16,
                               name="step%d" % i)
    assert build_scan_plan(x, min_repeat=2) is None


def test_heterogeneous_blocks_fall_back():
    # same names-by-index but different widths: attrs differ -> reject
    x = sym.Variable("data")
    for i, nh in enumerate((16, 16, 32, 16)):
        x = sym.FullyConnected(x, num_hidden=nh, name="layer%d_fc" % i)
        x = sym.Activation(x, act_type="relu")
    assert build_scan_plan(x, min_repeat=2) is None


def test_internal_output_consumed_outside_falls_back():
    # expose an interior block output as a second head (get_internals
    # use case): scanning would hide the value, so no plan
    net = _tf(4)
    internals = net.get_internals()
    probe = [name for name in internals.list_outputs()
             if name.startswith("layer1_att_proj")][0]
    grouped = sym_mod.Group([net, internals[probe]])
    assert build_scan_plan(grouped, min_repeat=2) is None


def test_executor_knob_off_and_auto_threshold():
    net = _tf(4)
    # off is the default: nothing scans unless asked
    assert mx.config.get("MXNET_TPU_SCAN_LAYERS") == "off"
    ex = net.simple_bind(mx.cpu(), data=(B, T), softmax_label=(B, T))
    assert ex._scan_plan is None
    mx.config.set("MXNET_TPU_SCAN_LAYERS", "auto")
    try:
        # auto: min repeat 4 -> a 4-layer chain scans
        ex = net.simple_bind(mx.cpu(), data=(B, T),
                             softmax_label=(B, T))
        assert ex._scan_plan is not None and ex._scan_plan.n_layers == 4
    finally:
        mx.config.reset("MXNET_TPU_SCAN_LAYERS")


# ----------------------------------------------------------- bit parity

def test_forward_bit_identical():
    ex0, ex1 = _bind_pair(_tf(4), {"data": (B, T)},
                          {"softmax_label": (B, T)})
    assert ex1._scan_plan is not None
    for n in ("data", "softmax_label"):
        v = np.random.RandomState(0).randint(0, V, (B, T)).astype(
            np.float32)
        ex0.arg_dict[n][:] = v
        ex1.arg_dict[n][:] = v
    o0 = ex0.forward(is_train=False)[0].asnumpy()
    o1 = ex1.forward(is_train=False)[0].asnumpy()
    np.testing.assert_array_equal(o0, o1)


def test_backward_parity_within_ulps():
    ex0, ex1 = _bind_pair(_tf(4), {"data": (B, T)},
                          {"softmax_label": (B, T)})
    for n in ("data", "softmax_label"):
        v = np.random.RandomState(1).randint(0, V, (B, T)).astype(
            np.float32)
        ex0.arg_dict[n][:] = v
        ex1.arg_dict[n][:] = v
    for ex in (ex0, ex1):
        ex.forward(is_train=True)
        ex.backward()
    for n in ex0.grad_dict:
        g0 = ex0.grad_dict[n].asnumpy()
        g1 = ex1.grad_dict[n].asnumpy()
        # 2 f32 ulps of the observed grad scale (~1e-2): XLA pointwise
        # fusion reassociates differently across program shapes
        np.testing.assert_allclose(g0, g1, rtol=0, atol=5e-9,
                                   err_msg=n)


def test_rng_ops_fold_identically():
    # dropout inside the repeated block: the per-node topo indices ride
    # the scan xs, so masks must match the unrolled program bit-for-bit
    x = sym.Variable("data")
    for i in range(4):
        x = sym.FullyConnected(x, num_hidden=16, name="blk%d_fc" % i)
        x = sym.Activation(x, act_type="relu")
        x = sym.Dropout(x, p=0.5)
    ex0, ex1 = _bind_pair(x, {"data": (8, 16)})
    assert ex1._scan_plan is not None and ex1._scan_plan.n_layers == 4
    v = np.random.RandomState(2).rand(8, 16).astype(np.float32)
    ex0.arg_dict["data"][:] = v
    ex1.arg_dict["data"][:] = v
    o0 = ex0.forward(is_train=True)[0].asnumpy()
    o1 = ex1.forward(is_train=True)[0].asnumpy()
    np.testing.assert_array_equal(o0, o1)


# ------------------------------------------------------------- fused fit

def _fit(net, scan_mode, X, Y, init, epochs=2, accum=None):
    mx.config.set("MXNET_TPU_SCAN_LAYERS", scan_mode)
    try:
        it = mx.io.NDArrayIter(X, Y, batch_size=B,
                               label_name="softmax_label")
        mod = mx.mod.Module(net, context=mx.cpu(0))
        mod.fit(it, num_epoch=epochs,
                arg_params={k: v.copy() for k, v in init.items()},
                eval_metric=mx.metric.Loss(),
                optimizer_params={"learning_rate": 0.05},
                grad_accum=accum)
        return {n: v.asnumpy() for n, v in mod.get_params()[0].items()}
    finally:
        mx.config.reset("MXNET_TPU_SCAN_LAYERS")


@pytest.fixture(scope="module")
def tf_fixture():
    net = _tf(4)
    m = mx.mod.Module(net, context=mx.cpu(0))
    m.bind(data_shapes=[("data", (B, T))],
           label_shapes=[("softmax_label", (B, T))])
    rs = np.random.RandomState(5)
    init = {n: mx.nd.array(rs.uniform(-0.05, 0.05, a.shape)
                           .astype(np.float32))
            for n, a in m._exec.arg_dict.items()
            if n not in ("data", "softmax_label")}
    X = np.random.RandomState(0).randint(0, V, (8, T)).astype(np.float32)
    Y = np.random.RandomState(1).randint(0, V, (8, T)).astype(np.float32)
    return net, X, Y, init


def test_fused_fit_parity_and_counters(tf_fixture):
    net, X, Y, init = tf_fixture
    from mxnet_tpu import profiler
    p_off = _fit(net, "off", X, Y, init)
    with profiler.counter_delta() as d:
        p_on = _fit(net, "2", X, Y, init)
    assert d.get("scan_applied") >= 1
    assert d.get("loop_recompile") == 0
    for n in p_off:
        np.testing.assert_allclose(p_off[n], p_on[n], rtol=0, atol=5e-8,
                                   err_msg=n)


def test_scan_grads_reach_every_layer(tf_fixture):
    # stacked-param vjp unstacks per layer: after a step, every layer's
    # params must have moved (a silently-dropped gradient path would
    # leave a layer frozen)
    net, X, Y, init = tf_fixture
    p_on = _fit(net, "2", X, Y, init, epochs=1)
    for n, v in init.items():
        assert np.abs(p_on[n] - v.asnumpy()).max() > 0, \
            "%s never updated under scan" % n
