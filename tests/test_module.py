"""Module API tests.

Reference model (SURVEY.md §4): tests/python/unittest/test_module.py +
tests/python/train/test_mlp.py (train MNIST-like data to an accuracy bar).
"""
import os
import tempfile

import numpy as np
import pytest

import mxnet_tpu as mx


def _xor_like_data(n=800, seed=0):
    """Small separable 2-class problem an MLP must crack."""
    rng = np.random.RandomState(seed)
    x = rng.uniform(-1, 1, (n, 2)).astype(np.float32)
    y = ((x[:, 0] * x[:, 1]) > 0).astype(np.float32)
    return x, y


def _mlp_symbol(num_hidden=16, num_classes=2):
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=num_hidden, name="fc1")
    act1 = mx.sym.Activation(fc1, act_type="tanh", name="tanh1")
    fc2 = mx.sym.FullyConnected(act1, num_hidden=num_classes, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def test_module_bind_and_shapes():
    sym = _mlp_symbol()
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 2))],
             label_shapes=[("softmax_label", (8,))])
    assert mod.binded
    assert mod.data_shapes[0].shape == (8, 2)
    mod.init_params(mx.init.Xavier())
    arg_params, aux_params = mod.get_params()
    assert set(arg_params) == {"fc1_weight", "fc1_bias",
                               "fc2_weight", "fc2_bias"}
    assert aux_params == {}


def test_module_forward_backward_update():
    sym = _mlp_symbol()
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 2))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    x, y = _xor_like_data(8)
    batch = mx.io.DataBatch(data=[mx.nd.array(x)], label=[mx.nd.array(y)])
    w0 = mod.get_params()[0]["fc1_weight"].asnumpy().copy()
    mod.forward(batch, is_train=True)
    out = mod.get_outputs()[0]
    assert out.shape == (8, 2)
    np.testing.assert_allclose(out.asnumpy().sum(axis=1), np.ones(8),
                               rtol=1e-5)
    mod.backward()
    mod.update()
    w1 = mod.get_params()[0]["fc1_weight"].asnumpy()
    assert not np.allclose(w0, w1), "update did not change weights"


def test_module_fit_converges():
    x, y = _xor_like_data(800)
    train = mx.io.NDArrayIter(x, y, batch_size=50, shuffle=True,
                              label_name="softmax_label")
    val = mx.io.NDArrayIter(x, y, batch_size=50,
                            label_name="softmax_label")
    mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
    mod.fit(train, eval_data=val, optimizer="sgd",
            initializer=mx.init.Xavier(),
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9},
            num_epoch=30)
    score = mod.score(val, "acc")
    assert score[0][1] > 0.95, "fit failed to converge: %s" % score


def test_module_fused_matches_eager():
    """Fused jitted step must produce the same updates as
    forward/backward/update (the reference's engine-ops path)."""
    x, y = _xor_like_data(32, seed=3)
    batch = mx.io.DataBatch(data=[mx.nd.array(x)], label=[mx.nd.array(y)])

    def make():
        mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
        mod.bind(data_shapes=[("data", (32, 2))],
                 label_shapes=[("softmax_label", (32,))])
        mod.init_params(mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2))
        np_params = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
        return mod, np_params

    mod_a, params_a = make()
    mod_b, _ = make()
    mod_b.set_params({k: mx.nd.array(v) for k, v in params_a.items()}, {})

    opt_kw = {"learning_rate": 0.1, "momentum": 0.9}
    mod_a.init_optimizer(optimizer="sgd", optimizer_params=opt_kw)
    mod_b.init_optimizer(optimizer="sgd", optimizer_params=opt_kw)

    for _ in range(3):
        mod_a.forward(batch, is_train=True)
        mod_a.backward()
        mod_a.update()
        mod_b._fit_step(batch)

    pa = mod_a.get_params()[0]
    pb = mod_b.get_params()[0]
    for k in pa:
        np.testing.assert_allclose(pa[k].asnumpy(), pb[k].asnumpy(),
                                   rtol=2e-5, atol=2e-6,
                                   err_msg="fused/eager diverged at %s" % k)


def test_module_data_parallel_matches_single():
    """8-device data-parallel step == single-device step (the reference's
    kvstore-summed gradients, SURVEY §2.21; here GSPMD psum)."""
    x, y = _xor_like_data(64, seed=5)
    batch = mx.io.DataBatch(data=[mx.nd.array(x)], label=[mx.nd.array(y)])

    def run(ctxs):
        mod = mx.mod.Module(_mlp_symbol(), context=ctxs)
        mod.bind(data_shapes=[("data", (64, 2))],
                 label_shapes=[("softmax_label", (64,))])
        mod.init_params(mx.init.Uniform(0.07))
        return mod

    mod_1 = run(mx.cpu(0))
    mod_8 = run([mx.cpu(i) for i in range(8)])
    mod_8.set_params({k: v.copyto(mx.cpu(0)) for k, v in
                      mod_1.get_params()[0].items()}, {})

    kw = {"learning_rate": 0.2}
    mod_1.init_optimizer(optimizer="sgd", optimizer_params=kw)
    mod_8.init_optimizer(kvstore="device", optimizer="sgd",
                         optimizer_params=kw)

    for _ in range(2):
        mod_1._fit_step(batch)
        mod_8._fit_step(batch)

    p1 = mod_1.get_params()[0]
    p8 = mod_8.get_params()[0]
    for k in p1:
        np.testing.assert_allclose(p1[k].asnumpy(), p8[k].asnumpy(),
                                   rtol=1e-4, atol=1e-5,
                                   err_msg="data-parallel diverged at %s" % k)


def test_module_save_load_checkpoint(tmp_path):
    sym = _mlp_symbol()
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 2))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer()
    prefix = str(tmp_path / "mlp")
    mod.save_checkpoint(prefix, 3, save_optimizer_states=True)
    assert os.path.exists(prefix + "-symbol.json")
    assert os.path.exists(prefix + "-0003.params")
    assert os.path.exists(prefix + "-0003.states")

    mod2 = mx.mod.Module.load(prefix, 3, context=mx.cpu())
    mod2.bind(data_shapes=[("data", (4, 2))],
              label_shapes=[("softmax_label", (4,))])
    p1 = mod.get_params()[0]
    p2 = mod2.get_params()[0]
    for k in p1:
        np.testing.assert_allclose(p1[k].asnumpy(), p2[k].asnumpy())
    # loaded params must actually drive the executor, not just get_params
    x, y = _xor_like_data(4)
    batch = mx.io.DataBatch(data=[mx.nd.array(x)], label=[mx.nd.array(y)])
    mod.forward(batch, is_train=False)
    mod2.forward(batch, is_train=False)
    np.testing.assert_allclose(mod.get_outputs()[0].asnumpy(),
                               mod2.get_outputs()[0].asnumpy(), rtol=1e-6)


def test_module_fixed_params_not_trained():
    """fixed_param_names must be frozen on both eager and fused paths
    (reference: module.py fixed_param_names → grad_req null)."""
    x, y = _xor_like_data(16)
    batch = mx.io.DataBatch(data=[mx.nd.array(x)], label=[mx.nd.array(y)])
    mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu(),
                        fixed_param_names=["fc1_weight"])
    mod.bind(data_shapes=[("data", (16, 2))],
             label_shapes=[("softmax_label", (16,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.5})
    w_fixed = mod.get_params()[0]["fc1_weight"].asnumpy().copy()
    w_free = mod.get_params()[0]["fc2_weight"].asnumpy().copy()
    mod._fit_step(batch)                      # fused
    mod.forward_backward(batch)
    mod.update()                              # eager
    p = mod.get_params()[0]
    np.testing.assert_allclose(p["fc1_weight"].asnumpy(), w_fixed)
    assert not np.allclose(p["fc2_weight"].asnumpy(), w_free)


def test_module_predict_and_score():
    x, y = _xor_like_data(100)
    it = mx.io.NDArrayIter(x, y, batch_size=25, label_name="softmax_label")
    mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier())
    out = mod.predict(it)
    assert out.shape == (100, 2)
    res = mod.score(it, "acc")
    assert 0.0 <= res[0][1] <= 1.0


@pytest.mark.parametrize("opt_name", ["adam", "nadam", "rmsprop", "adagrad"])
def test_module_fused_matches_eager_stateful_optimizers(opt_name):
    """Stateful optimizers must produce identical updates on the fused
    (traced raw_update) and eager (engine-op) paths."""
    x, y = _xor_like_data(16, seed=11)
    batch = mx.io.DataBatch(data=[mx.nd.array(x)], label=[mx.nd.array(y)])

    def make():
        mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
        mod.bind(data_shapes=[("data", (16, 2))],
                 label_shapes=[("softmax_label", (16,))])
        mod.init_params(mx.init.Uniform(0.1))
        return mod

    mod_a, mod_b = make(), make()
    mod_b.set_params({k: mx.nd.array(v.asnumpy())
                      for k, v in mod_a.get_params()[0].items()}, {})
    for m in (mod_a, mod_b):
        m.init_optimizer(optimizer=opt_name,
                         optimizer_params={"learning_rate": 0.01})
    for _ in range(3):
        mod_a.forward_backward(batch)
        mod_a.update()
        mod_b._fit_step(batch)
    pa, pb = mod_a.get_params()[0], mod_b.get_params()[0]
    for k in pa:
        np.testing.assert_allclose(pa[k].asnumpy(), pb[k].asnumpy(),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg="%s diverged at %s" % (opt_name, k))


def test_module_lr_scheduler_no_retrace():
    """LR schedule changes must not retrigger compilation (traced lr)."""
    x, y = _xor_like_data(32)
    batch = mx.io.DataBatch(data=[mx.nd.array(x)], label=[mx.nd.array(y)])
    mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (32, 2))],
             label_shapes=[("softmax_label", (32,))])
    mod.init_params(mx.init.Xavier())
    sched = mx.lr_scheduler.FactorScheduler(step=1, factor=0.5)
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.4,
                                         "lr_scheduler": sched})
    for _ in range(3):
        mod._fit_step(batch)
    n_compiles = mod._fused_jit._cache_size()
    assert n_compiles == 1, "lr schedule caused %d recompiles" % n_compiles


# ------------------------------------------- the fused step's one program

def _plain_mlp():
    return (_mlp_symbol(num_hidden=16, num_classes=4),
            [("data", (8, 6))], [("softmax_label", (8,))], 4)


def _plain_transformer():
    from mxnet_tpu.models import transformer
    net = transformer.get_symbol(vocab_size=32, num_layers=4, d_model=16,
                                 n_heads=2, seq_len=8)
    return net, [("data", (4, 8))], [("softmax_label", (4, 8))], 32


def _plain_bn_stem():
    net = mx.sym.Convolution(mx.sym.Variable("data"), num_filter=4,
                             kernel=(3, 3), pad=(1, 1), name="conv0")
    net = mx.sym.BatchNorm(net, name="bn0")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    return net, [("data", (8, 3, 6, 6))], [("softmax_label", (8,))], 4


_PLAIN_NETS = {"mlp": _plain_mlp, "transformer4": _plain_transformer,
               "bn_stem": _plain_bn_stem}
_PLAIN_OPTS = {"sgd_momentum": ("sgd", {"learning_rate": 0.05,
                                        "momentum": 0.9}),
               # a bias in front of a normalisation (conv0_bias, the key
               # third of att_qkv_bias) has a gradient of rounding noise;
               # at Adam's default epsilon that noise is a step of the
               # size of the learning rate, in either program
               "adam": ("adam", {"learning_rate": 0.01, "epsilon": 1e-3})}


def _plain_pair(net_name, opt_name):
    """Two modules over the same net from the same parameters, the same
    optimizer on each, and one batch."""
    net, data_shapes, label_shapes, classes = _PLAIN_NETS[net_name]()
    rng = np.random.RandomState(7)
    (_, dshape), (_, lshape) = data_shapes[0], label_shapes[0]
    x = rng.randint(0, classes, dshape) if net_name == "transformer4" \
        else rng.uniform(-1, 1, dshape)
    y = rng.randint(0, classes, lshape)
    batch = mx.io.DataBatch(data=[mx.nd.array(x.astype(np.float32))],
                            label=[mx.nd.array(y.astype(np.float32))])

    def make():
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.bind(data_shapes=data_shapes, label_shapes=label_shapes)
        mod.init_params(mx.init.Xavier())
        return mod

    fused, eager = make(), make()
    arg, aux = fused.get_params()
    eager.set_params({k: mx.nd.array(v.asnumpy()) for k, v in arg.items()},
                     {k: mx.nd.array(v.asnumpy()) for k, v in aux.items()})
    opt, opt_kw = _PLAIN_OPTS[opt_name]
    for m in (fused, eager):
        m.init_optimizer(optimizer=opt, optimizer_params=dict(opt_kw))
    return fused, eager, batch


def _lowered_step(mod):
    """The fused step lowered on the arguments ``run`` would pass next."""
    import jax
    import jax.numpy as jnp
    ex = mod._exec
    param_names = list(mod._fused_states)
    inputs = set(mod._data_names) | set(mod._label_names)
    args = (
        {n: ex.arg_dict[n].data for n in param_names},
        mod._fused_states,
        {n: a.data for n, a in ex.aux_dict.items()},
        {n: ex.arg_dict[n].data for n in inputs},
        {n: a.data for n, a in ex.arg_dict.items()
         if n not in inputs and n not in param_names},
        jax.random.fold_in(ex._base_key, ex._step + 1),
        jnp.asarray(0.01, jnp.float32), jnp.asarray(1, jnp.int32))
    return mod._fused_jit.lower(*args).as_text()


@pytest.mark.parametrize("opt_name", sorted(_PLAIN_OPTS))
@pytest.mark.parametrize("net_name", sorted(_PLAIN_NETS))
def test_fused_step_is_one_plain_program(net_name, opt_name):
    """The fused step compiles one way: three ``fit`` steps leave one
    executable and no recompile, the lowered step has no loop at
    ``grad_accum=1`` (nothing scans over layers or stacks an update),
    and the parameters are those of three eager ``forward_backward`` +
    ``update`` steps."""
    from mxnet_tpu import profiler
    fused, eager, batch = _plain_pair(net_name, opt_name)
    with profiler.counter_delta() as d:
        for _ in range(3):
            fused._fit_step(batch)
            eager.forward_backward(batch)
            eager.update()
    assert fused._fused_jit._cache_size() == 1
    assert not d.get("loop_recompile")
    assert "stablehlo.while" not in _lowered_step(fused)

    (arg_f, aux_f), (arg_e, aux_e) = fused.get_params(), eager.get_params()
    for got, want in ((arg_f, arg_e), (aux_f, aux_e)):
        assert set(got) == set(want)
        for k in want:
            np.testing.assert_allclose(
                got[k].asnumpy(), want[k].asnumpy(), rtol=1e-4, atol=1e-6,
                err_msg="fused/eager diverged at %s" % k)

    # the detector sees a loop where there is one: accumulation's scan
    fused.set_grad_accum(2)
    assert "stablehlo.while" in _lowered_step(fused)


def test_replaced_fused_jit_runs_from_the_next_step():
    """``run`` looks ``_fused_jit`` up on the module at every step: what
    is put there after the build is what the next step calls (the
    benchmark's planted faults, tests/benchmark/faulty_run.py, rely on
    it), and stray attributes on the module are harmless."""
    fused, _eager, batch = _plain_pair("mlp", "sgd_momentum")
    fused._fit_step(batch)
    step, calls = fused._fused_jit, []

    def counted(*args):
        calls.append(len(args))
        return step(*args)

    fused._fused_jit = counted
    fused._fused_call = None
    before = {k: v.asnumpy() for k, v in fused.get_params()[0].items()}
    fused._fit_step(batch)
    fused._fit_step(batch)
    assert calls == [8, 8]
    after = fused.get_params()[0]
    assert any(np.any(after[k].asnumpy() != before[k]) for k in before)
