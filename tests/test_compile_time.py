"""Compile-time control (ISSUE 9): applied remat (on the fused step and
on the non-fused forward_backward path) and gradient accumulation.

Companion: tools/compile_time_smoke.py (the CI job's cross-process
zero-cost gate).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.base import MXNetError

sym = mx.sym


def _mlp(normalization="null"):
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=16,
                             name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=4, name="fc2")
    return sym.SoftmaxOutput(net, sym.Variable("softmax_label"),
                             name="softmax", normalization=normalization)


def _data(n=64, d=8, classes=4, seed=0):
    rs = np.random.RandomState(seed)
    X = rs.uniform(-1, 1, (n, d)).astype(np.float32)
    Y = rs.randint(0, classes, (n,)).astype(np.float32)
    return X, Y


def _init_for(net, data_shapes, label_shapes, seed=11):
    m = mx.mod.Module(net, context=mx.cpu(0))
    m.bind(data_shapes=data_shapes, label_shapes=label_shapes)
    rs = np.random.RandomState(seed)
    skip = {d[0] for d in data_shapes + label_shapes}
    return {n: mx.nd.array(rs.uniform(-0.1, 0.1, a.shape)
                           .astype(np.float32))
            for n, a in m._exec.arg_dict.items() if n not in skip}


def _fit(net, X, Y, init, batch=32, accum=None, epochs=2, opt_params=None,
         **fit_kw):
    it = mx.io.NDArrayIter(X, Y, batch_size=batch,
                           label_name="softmax_label")
    mod = mx.mod.Module(net, context=mx.cpu(0))
    mod.fit(it, num_epoch=epochs,
            arg_params={k: v.copy() for k, v in init.items()},
            optimizer_params=opt_params or {"learning_rate": 0.1},
            grad_accum=accum, **fit_kw)
    arg, aux = mod.get_params()
    return ({n: v.asnumpy() for n, v in arg.items()},
            {n: v.asnumpy() for n, v in aux.items()})


# ----------------------------------------------------- grad accumulation

class TestGradAccum:
    def test_mlp_parity_sum_normalized(self):
        # normalization='null': per-sample grads, accumulation sums —
        # exact up to float reassociation
        net = _mlp()
        X, Y = _data()
        init = _init_for(net, [("data", (32, 8))],
                         [("softmax_label", (32,))])
        p1, _ = _fit(net, X, Y, init)
        with profiler.counter_delta() as d:
            p4, _ = _fit(net, X, Y, init, accum=4)
        assert d.get("accum_steps") == 4 * 4  # 2 epochs x 2 batches x 4
        assert d.get("loop_recompile") == 0
        for n in p1:
            np.testing.assert_allclose(p1[n], p4[n], rtol=0, atol=1e-7,
                                       err_msg=n)

    def test_mlp_parity_batch_normalized(self):
        # normalization='batch': microbatch means averaged (1/N rescale)
        # must equal the full-batch mean exactly
        net = _mlp(normalization="batch")
        X, Y = _data(seed=3)
        init = _init_for(net, [("data", (32, 8))],
                         [("softmax_label", (32,))])
        p1, _ = _fit(net, X, Y, init)
        p4, _ = _fit(net, X, Y, init, accum=4)
        for n in p1:
            np.testing.assert_allclose(p1[n], p4[n], rtol=0, atol=1e-7,
                                       err_msg=n)

    def test_bn_stem_matches_sequential_microbatches(self):
        # BatchNorm: each microbatch normalizes with its own statistics
        # and advances the moving stats sequentially — the documented
        # semantics. Reference: run the two microbatches through a
        # plain executor, sum the grads, apply one SGD update by hand.
        B, C, H = 8, 3, 6
        net = sym.Convolution(sym.Variable("data"), num_filter=4,
                              kernel=(3, 3), pad=(1, 1), name="conv0")
        net = sym.BatchNorm(net, name="bn0")
        net = sym.Activation(net, act_type="relu")
        net = sym.FullyConnected(net, num_hidden=4, name="fc")
        net = sym.SoftmaxOutput(net, sym.Variable("softmax_label"),
                                name="softmax")
        rs = np.random.RandomState(0)
        X = rs.uniform(-1, 1, (B, C, H, H)).astype(np.float32)
        Y = rs.randint(0, 4, (B,)).astype(np.float32)
        init = _init_for(net, [("data", (B, C, H, H))],
                         [("softmax_label", (B,))])
        lr = 0.1

        # accumulated fused step, one batch, one epoch
        it = mx.io.NDArrayIter(X, Y, batch_size=B,
                               label_name="softmax_label")
        mod = mx.mod.Module(net, context=mx.cpu(0))
        mod.fit(it, num_epoch=1,
                arg_params={k: v.copy() for k, v in init.items()},
                optimizer_params={"learning_rate": lr, "wd": 0.0},
                grad_accum=2)
        got_arg, got_aux = mod.get_params()

        # reference: executor at microbatch size with sequential aux
        ex = net.simple_bind(mx.cpu(), grad_req="write",
                             data=(B // 2, C, H, H),
                             softmax_label=(B // 2,))
        for n, v in init.items():
            ex.arg_dict[n][:] = v.asnumpy()
        for n, a in ex.aux_dict.items():
            # fit's initializer seeds moving_var=1 / moving_mean=0;
            # simple_bind leaves zeros — align the starting aux state
            a[:] = np.ones(a.shape, np.float32) if "var" in n else \
                np.zeros(a.shape, np.float32)
        # the fused step folds the step key once more per microbatch;
        # this net is dropout-free so RNG does not matter
        grads = {n: 0.0 for n in init}
        for k in range(2):
            ex.arg_dict["data"][:] = X[k * B // 2:(k + 1) * B // 2]
            ex.arg_dict["softmax_label"][:] = Y[k * B // 2:(k + 1) * B // 2]
            ex.forward(is_train=True)
            ex.backward()
            for n in grads:
                grads[n] = grads[n] + ex.grad_dict[n].asnumpy()
        rescale = 1.0 / B   # init_optimizer's rescale_grad on the FULL batch
        for n, v in init.items():
            want = v.asnumpy() - lr * rescale * grads[n]
            np.testing.assert_allclose(got_arg[n].asnumpy(), want,
                                       rtol=0, atol=2e-6, err_msg=n)
        for n in ex.aux_dict:
            np.testing.assert_allclose(got_aux[n].asnumpy(),
                                       ex.aux_dict[n].asnumpy(),
                                       rtol=0, atol=1e-6, err_msg=n)

    def test_async_window_and_device_metrics_intact(self):
        net = _mlp()
        X, Y = _data(seed=5)
        init = _init_for(net, [("data", (32, 8))],
                         [("softmax_label", (32,))])
        mx.config.set("MXNET_TPU_ASYNC_WINDOW", 2)
        try:
            with profiler.counter_delta() as d:
                p_async, _ = _fit(net, X, Y, init, accum=4, epochs=3)
            assert d.get("loop_recompile") == 0
            assert d.get("loop_host_sync") == 0
        finally:
            mx.config.reset("MXNET_TPU_ASYNC_WINDOW")
        mx.config.set("MXNET_TPU_ASYNC_WINDOW", 0)
        try:
            p_sync, _ = _fit(net, X, Y, init, accum=4, epochs=3)
        finally:
            mx.config.reset("MXNET_TPU_ASYNC_WINDOW")
        for n in p_async:
            np.testing.assert_array_equal(p_async[n], p_sync[n],
                                          err_msg=n)

    def test_indivisible_batch_rejected(self):
        net = _mlp()
        X, Y = _data()
        init = _init_for(net, [("data", (32, 8))],
                         [("softmax_label", (32,))])
        with pytest.raises(MXNetError, match="does not divide"):
            _fit(net, X, Y, init, accum=3)

    def test_valid_normalization_rejected(self):
        net = _mlp(normalization="valid")
        X, Y = _data()
        init = _init_for(net, [("data", (32, 8))],
                         [("softmax_label", (32,))])
        with pytest.raises(MXNetError, match="valid"):
            _fit(net, X, Y, init, accum=4)

    def test_accum_one_is_the_plain_step(self):
        net = _mlp()
        X, Y = _data(seed=9)
        init = _init_for(net, [("data", (32, 8))],
                         [("softmax_label", (32,))])
        p_none, _ = _fit(net, X, Y, init)
        p_one, _ = _fit(net, X, Y, init, accum=1)
        for n in p_none:
            np.testing.assert_array_equal(p_none[n], p_one[n])

    def test_trainer_grad_req_add_accumulation(self):
        # the gluon-side idiom: grad_req='add', N backwards, one step
        from mxnet_tpu.gluon import nn, Trainer
        from mxnet_tpu import autograd

        def build(grad_req):
            net = nn.Dense(4, in_units=8)
            net.initialize(mx.init.Constant(0.05))
            for p in net.collect_params().values():
                p.grad_req = grad_req
            return net

        rs = np.random.RandomState(2)
        xs = [mx.nd.array(rs.uniform(-1, 1, (8, 8)).astype(np.float32))
              for _ in range(2)]
        full = mx.nd.concatenate(xs)

        ref = build("write")
        tr = Trainer(ref.collect_params(), "sgd",
                     {"learning_rate": 0.1, "wd": 0.0})
        with autograd.record():
            loss = ref(full).sum()
        loss.backward()
        tr.step(16)

        acc = build("add")
        tr2 = Trainer(acc.collect_params(), "sgd",
                      {"learning_rate": 0.1, "wd": 0.0})
        for x in xs:
            with autograd.record():
                loss = acc(x).sum()
            loss.backward()
        tr2.step(16)
        for (n0, p0), (n1, p1) in zip(
                sorted(ref.collect_params().items()),
                sorted(acc.collect_params().items())):
            np.testing.assert_allclose(p0.data().asnumpy(),
                                       p1.data().asnumpy(),
                                       rtol=0, atol=1e-7, err_msg=n0)


# ------------------------------------------------------------ remat

def _mlp64():
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=64, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=10, name="fc2")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _fwd_bwd_grads(remat):
    mx.config.set("MXNET_TPU_REMAT", remat)
    try:
        np.random.seed(5)
        seed = mx.mod.Module(_mlp64(), context=mx.cpu())
        seed.bind(data_shapes=[("data", (8, 32))],
                  label_shapes=[("softmax_label", (8,))])
        seed.init_params(mx.init.Uniform(0.07))
        arg0 = {n: mx.nd.array(np.asarray(a.data))
                for n, a in seed._exec.arg_dict.items()}

        rng = np.random.RandomState(0)
        x = rng.uniform(-1, 1, (8, 32)).astype(np.float32)
        y = rng.randint(0, 10, (8,)).astype(np.float32)
        mod = mx.mod.Module(_mlp64(), context=mx.cpu())
        mod.bind(data_shapes=[("data", (8, 32))],
                 label_shapes=[("softmax_label", (8,))])
        mod.init_params(arg_params=arg0, aux_params={})
        db = mx.io.DataBatch(data=[mx.nd.array(x)],
                             label=[mx.nd.array(y)])
        mod.forward_backward(db)
        applied = mod._exec._fwd_bwd_remat is not None
        return ({n: np.asarray(g.data)
                 for n, g in mod._exec.grad_dict.items()}, applied)
    finally:
        mx.config.reset("MXNET_TPU_REMAT")


class TestRemat:
    def test_named_policy_applies_and_preserves_training(self):
        net = _mlp()
        X, Y = _data(seed=13)
        init = _init_for(net, [("data", (32, 8))],
                         [("softmax_label", (32,))])
        p_plain, _ = _fit(net, X, Y, init)
        mx.config.set("MXNET_TPU_REMAT", "dots_with_no_batch_dims_saveable")
        try:
            with profiler.counter_delta() as d:
                p_remat, _ = _fit(net, X, Y, init)
            assert d.get("remat_applied") >= 1
        finally:
            mx.config.reset("MXNET_TPU_REMAT")
        for n in p_plain:
            np.testing.assert_allclose(p_plain[n], p_remat[n], rtol=0,
                                       atol=1e-7, err_msg=n)

    def test_bad_policy_name_raises_naming_valid_ones(self):
        net = _mlp()
        X, Y = _data()
        init = _init_for(net, [("data", (32, 8))],
                         [("softmax_label", (32,))])
        mx.config.set("MXNET_TPU_REMAT", "no_such_policy")
        try:
            with pytest.raises(MXNetError, match="nothing_saveable"):
                _fit(net, X, Y, init)
        finally:
            mx.config.reset("MXNET_TPU_REMAT")

    def test_auto_takes_the_suggested_policy_name(self):
        """MXNET_TPU_REMAT=auto applies the policy the remat-opportunity
        pass names for THIS graph to the whole forward, and trains the
        same parameters."""
        from mxnet_tpu import remat
        from mxnet_tpu.analysis import analyze_symbol
        from mxnet_tpu.models import transformer
        net = transformer.get_symbol(vocab_size=128, num_layers=2,
                                     d_model=32, n_heads=2, seq_len=16)
        shapes = {"data": (2, 16), "softmax_label": (2, 16)}
        sug = analyze_symbol(net, input_shapes=shapes) \
            .extras["remat"]["suggestion"]
        assert sug["est_bytes_saved"] > 0

        def train(mode):
            mx.config.set("MXNET_TPU_REMAT", mode)
            try:
                policy, name = remat.resolve_policy(net, input_shapes=shapes)
                np.random.seed(3)
                rng = np.random.RandomState(0)
                db = mx.io.DataBatch(
                    data=[mx.nd.array(rng.randint(0, 128, (2, 16))
                                      .astype(np.float32))],
                    label=[mx.nd.array(rng.randint(0, 128, (2, 16))
                                       .astype(np.float32))])
                with profiler.counter_delta() as d:
                    # one analysis run and one count per bind: the
                    # fused step reuses the executor's policy
                    m = mx.mod.Module(net, context=mx.cpu(0))
                    m.bind(data_shapes=[("data", (2, 16))],
                           label_shapes=[("softmax_label", (2, 16))])
                    m.init_params(mx.init.Xavier())
                    m.init_optimizer(optimizer="sgd", optimizer_params={
                        "learning_rate": 0.1})
                    m._fit_step(db)
                return (policy, name, d.get("remat_applied"),
                        {n: np.asarray(a.data)
                         for n, a in m._exec.arg_dict.items()})
            finally:
                mx.config.reset("MXNET_TPU_REMAT")

        p_off, n_off, applied_off, w_off = train("off")
        p_auto, n_auto, applied_auto, w_auto = train("auto")
        assert p_off is None and n_off == "off" and not applied_off
        assert p_auto is not None
        assert n_auto == "auto:%s" % sug["policy"]
        assert applied_auto == 1
        for n in w_off:
            np.testing.assert_allclose(w_auto[n], w_off[n], rtol=1e-5,
                                       atol=1e-6, err_msg=n)

    def test_legacy_knob_still_remats(self):
        net = _mlp()
        X, Y = _data()
        init = _init_for(net, [("data", (32, 8))],
                         [("softmax_label", (32,))])
        mx.config.set("MXNET_EXEC_ENABLE_REMAT", "1")
        try:
            with profiler.counter_delta() as d:
                _fit(net, X, Y, init, epochs=1)
            assert d.get("remat_applied") >= 1
        finally:
            mx.config.reset("MXNET_EXEC_ENABLE_REMAT")

    def test_fwd_bwd_remat_parity(self):
        g_off, a_off = _fwd_bwd_grads("off")
        g_on, a_on = _fwd_bwd_grads("dots_with_no_batch_dims_saveable")
        assert not a_off and a_on
        for k in g_off:
            np.testing.assert_array_equal(g_on[k], g_off[k], err_msg=k)
        assert mx.profiler.counters().get("remat_applied", 0) >= 1

    def test_fwd_bwd_remat_zero_cost_when_off(self):
        """MXNET_TPU_REMAT=off builds nothing on the fwd_bwd path."""
        _g, applied = _fwd_bwd_grads("off")
        assert not applied

    def test_fwd_bwd_remat_parity_vs_fused_step(self):
        """The rematted non-fused path trains the same step the fused path
        does (one sgd step, same seed params)."""
        mx.config.set("MXNET_TPU_REMAT", "dots_with_no_batch_dims_saveable")
        try:
            np.random.seed(6)
            seed = mx.mod.Module(_mlp64(), context=mx.cpu())
            seed.bind(data_shapes=[("data", (8, 32))],
                      label_shapes=[("softmax_label", (8,))])
            seed.init_params(mx.init.Uniform(0.07))
            arg0 = {n: mx.nd.array(np.asarray(a.data))
                    for n, a in seed._exec.arg_dict.items()}
            rng = np.random.RandomState(1)
            x = rng.uniform(-1, 1, (8, 32)).astype(np.float32)
            y = rng.randint(0, 10, (8,)).astype(np.float32)
            db = mx.io.DataBatch(data=[mx.nd.array(x)],
                                 label=[mx.nd.array(y)])

            def one_step(fused):
                mod = mx.mod.Module(_mlp64(), context=mx.cpu())
                mod.bind(data_shapes=[("data", (8, 32))],
                         label_shapes=[("softmax_label", (8,))])
                mod.init_params(arg_params=arg0, aux_params={})
                mod.init_optimizer(optimizer="sgd", optimizer_params={
                    "learning_rate": 0.1, "rescale_grad": 1.0 / 8})
                if fused:
                    mod._fit_step(db)
                else:
                    mod.forward_backward(db)
                    mod.update()
                return {n: np.asarray(a.data)
                        for n, a in mod._exec.arg_dict.items()}

            w_fused = one_step(True)
            w_eager = one_step(False)
            for k in w_fused:
                np.testing.assert_allclose(w_fused[k], w_eager[k],
                                           rtol=1e-6, atol=1e-7,
                                           err_msg=k)
        finally:
            mx.config.reset("MXNET_TPU_REMAT")
