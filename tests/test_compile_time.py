"""Compile-time control (ISSUE 9): applied remat, gradient
accumulation and AOT warm starts.

Companions: tests/test_scan_layers.py (the scan transform itself) and
tools/compile_time_smoke.py (the CI job's cross-process gates).
"""
import os
import pickle

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import aot, profiler
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import transformer

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

    def test_auto_round_trip_prediction_within_25pct(self):
        # THE ISSUE 9 satellite: the remat-opportunity suggestion,
        # applied via MXNET_TPU_REMAT=auto (per block, through the scan
        # plan), must move analyze_program_memory's activation
        # high-water by the pass's predicted amount +-25%
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.analysis import (analyze_program_memory,
                                        analyze_symbol)

        net = transformer.get_symbol(vocab_size=128, num_layers=2,
                                     d_model=32, n_heads=2, seq_len=16)
        shapes = {"data": (2, 16), "softmax_label": (2, 16)}
        sug = analyze_symbol(net, input_shapes=shapes,
                             calibrate_remat=True) \
            .extras["remat"]["suggestion"]
        predicted = sug["est_peak_saving"]
        assert predicted > 0
        # a plain bind analysis stays execution-free: no calibration
        plain = analyze_symbol(net, input_shapes=shapes) \
            .extras["remat"]["suggestion"]
        assert "est_peak_saving" not in plain

        def build(remat_mode):
            mx.config.set("MXNET_TPU_SCAN_LAYERS", "2")
            mx.config.set("MXNET_TPU_REMAT", remat_mode)
            try:
                m = mx.mod.Module(net, context=mx.cpu(0))
                m.bind(data_shapes=[("data", (2, 16))],
                       label_shapes=[("softmax_label", (2, 16))])
                m.init_params(mx.init.Xavier())
                return m._exec
            finally:
                mx.config.reset("MXNET_TPU_REMAT")
                mx.config.reset("MXNET_TPU_SCAN_LAYERS")

        def peak(ex):
            fn = ex._fn
            params = {n: a.data for n, a in ex.arg_dict.items()
                      if n not in ("data", "softmax_label")}
            inputs = {n: ex.arg_dict[n].data
                      for n in ("data", "softmax_label")}
            key = jax.random.PRNGKey(0)

            def g(p):
                def loss_fn(p_):
                    return fn({**p_, **inputs}, {}, key, True)
                (outs, new_aux), vjp = jax.vjp(loss_fn, p)
                cts = [jnp.ones_like(o) for o in outs]
                return vjp((cts, {k: jnp.zeros_like(v)
                                  for k, v in new_aux.items()}))[0]

            return analyze_program_memory(g, params).extras[
                "program_memory"]["activation_peak_bytes"]

        ex_plain = build("off")
        assert ex_plain._scan_plan is not None
        ex_remat = build("auto")
        assert ex_remat._scan_plan.body_wrapper is not None
        measured = peak(ex_plain) - peak(ex_remat)
        assert measured > 0
        assert abs(measured - predicted) <= 0.25 * predicted, \
            "predicted %d vs measured %d (%.0f%% off)" % (
                predicted, measured,
                100.0 * abs(measured - predicted) / predicted)

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


# --------------------------------------------------------------- AOT

class TestAot:
    def test_capability_probe(self):
        assert aot.supported() is True

    def test_in_process_store_then_hit(self, tmp_path):
        net = _mlp()
        X, Y = _data(seed=21)
        init = _init_for(net, [("data", (32, 8))],
                         [("softmax_label", (32,))])
        mx.config.set("MXNET_TPU_COMPILE_CACHE", str(tmp_path))
        try:
            with profiler.counter_delta() as d:
                p_cold, _ = _fit(net, X, Y, init, epochs=1)
            assert d.get("aot_store") == 1
            assert d.get("aot_hit") == 0
            files = [f for f in os.listdir(tmp_path)
                     if f.startswith("fused_step-")]
            assert len(files) == 1
            with profiler.counter_delta() as d:
                p_warm, _ = _fit(net, X, Y, init, epochs=1)
            assert d.get("aot_hit") == 1
            assert d.get("aot_store") == 0
            assert d.get("aot_error") == 0
        finally:
            mx.config.reset("MXNET_TPU_COMPILE_CACHE")
        for n in p_cold:
            np.testing.assert_array_equal(p_cold[n], p_warm[n],
                                          err_msg=n)

    def test_corrupt_entry_is_a_miss_not_an_error(self, tmp_path):
        net = _mlp()
        X, Y = _data(seed=22)
        init = _init_for(net, [("data", (32, 8))],
                         [("softmax_label", (32,))])
        mx.config.set("MXNET_TPU_COMPILE_CACHE", str(tmp_path))
        try:
            p_cold, _ = _fit(net, X, Y, init, epochs=1)
            (entry,) = [f for f in os.listdir(tmp_path)
                        if f.startswith("fused_step-")]
            with open(os.path.join(tmp_path, entry), "wb") as f:
                f.write(b"not a pickle")
            with profiler.counter_delta() as d:
                p_again, _ = _fit(net, X, Y, init, epochs=1)
            assert d.get("aot_miss") >= 1
            assert d.get("aot_store") == 1   # re-serialized cleanly
        finally:
            mx.config.reset("MXNET_TPU_COMPILE_CACHE")
        for n in p_cold:
            np.testing.assert_array_equal(p_cold[n], p_again[n])

    def test_stale_fingerprint_is_a_miss(self, tmp_path):
        net = _mlp()
        X, Y = _data(seed=23)
        init = _init_for(net, [("data", (32, 8))],
                         [("softmax_label", (32,))])
        mx.config.set("MXNET_TPU_COMPILE_CACHE", str(tmp_path))
        try:
            _fit(net, X, Y, init, epochs=1)
            (name,) = [f for f in os.listdir(tmp_path)
                       if f.startswith("fused_step-")]
            path = os.path.join(tmp_path, name)
            with open(path, "rb") as f:
                entry = pickle.load(f)
            entry["fingerprint"] = "elsewhere"
            with open(path, "wb") as f:
                pickle.dump(entry, f)
            with profiler.counter_delta() as d:
                _fit(net, X, Y, init, epochs=1)
            assert d.get("aot_miss") >= 1
            assert d.get("aot_hit") == 0
        finally:
            mx.config.reset("MXNET_TPU_COMPILE_CACHE")

    def test_executor_forward_aot_per_bucket_shape(self, tmp_path):
        # the serve path: one executor re-entered with different padded
        # batch geometries — each bucket shape gets its own serialized
        # executable, and a fresh process (executor) loads them all
        net = sym.FullyConnected(sym.Variable("data"), num_hidden=4,
                                 name="fc1")
        mx.config.set("MXNET_TPU_COMPILE_CACHE", str(tmp_path))
        try:
            x4 = np.random.RandomState(0).rand(4, 8).astype(np.float32)
            ex = net.simple_bind(mx.cpu(), data=(4, 8))
            with profiler.counter_delta() as d:
                o4 = ex.forward(is_train=False,
                                data=mx.nd.array(x4))[0].asnumpy()
                ex.forward(is_train=False,
                           data=mx.nd.array(np.ones((2, 8), np.float32)))
            assert d.get("aot_store") == 2      # one per bucket shape
            assert d.get("aot_error") == 0
            ex2 = net.simple_bind(mx.cpu(), data=(4, 8))
            ex2.copy_params_from({"fc1_weight": ex.arg_dict["fc1_weight"],
                                  "fc1_bias": ex.arg_dict["fc1_bias"]},
                                 allow_extra_params=True)
            with profiler.counter_delta() as d:
                o4b = ex2.forward(is_train=False,
                                  data=mx.nd.array(x4))[0].asnumpy()
            assert d.get("aot_hit") == 1
            assert d.get("aot_error") == 0
            np.testing.assert_array_equal(o4, o4b)
        finally:
            mx.config.reset("MXNET_TPU_COMPILE_CACHE")

    def test_multidevice_module_never_serializes(self, tmp_path):
        # THE regression the ISSUE names: multi-device executables must
        # never reach the serialized-executable path
        net = _mlp()
        X, Y = _data(seed=24)
        mx.config.set("MXNET_TPU_COMPILE_CACHE", str(tmp_path))
        try:
            it = mx.io.NDArrayIter(X, Y, batch_size=32,
                                   label_name="softmax_label")
            mod = mx.mod.Module(net,
                                context=[mx.cpu(i) for i in range(8)])
            with profiler.counter_delta() as d:
                mod.fit(it, num_epoch=1,
                        optimizer_params={"learning_rate": 0.1})
            assert d.get("aot_skip_multidevice") >= 1
            assert d.get("aot_store") == 0
            assert d.get("aot_hit") == 0
            assert os.listdir(tmp_path) == []
        finally:
            mx.config.reset("MXNET_TPU_COMPILE_CACHE")
