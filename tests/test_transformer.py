"""Transformer LM (models/transformer.py): shape inference through the
Symbol layer, causality, learning, and the symbolic positional-attr fix
that enables it (sym.reshape(x, shape_tuple)).
"""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.models import transformer


def _build(T=16, V=50):
    sym = transformer.get_symbol(vocab_size=V, num_layers=2, d_model=32,
                                 n_heads=4, seq_len=T)
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, T))],
             label_shapes=[("softmax_label", (4, T))])
    mod.init_params(mx.init.Xavier())
    return mod


def test_transformer_shapes_infer_from_data_alone():
    sym = transformer.get_symbol(vocab_size=50, num_layers=1, d_model=32,
                                 n_heads=4, seq_len=8)
    arg_shapes, out_shapes, _ = sym.infer_shape(data=(2, 8),
                                                softmax_label=(2, 8))
    shapes = dict(zip(sym.list_arguments(), arg_shapes))
    assert shapes["tok_embed_weight"] == (50, 32)
    assert shapes["layer0_att_qkv_weight"] == (96, 32)
    assert shapes["layer0_ln1_gamma"] == (32,)
    assert out_shapes[0] == (2 * 8, 50)


def test_transformer_is_causal():
    mod = _build()
    rng = np.random.RandomState(0)
    x = rng.randint(0, 50, (4, 16)).astype(np.float32)
    y = np.zeros_like(x)
    db = mx.io.DataBatch(data=[mx.nd.array(x)], label=[mx.nd.array(y)])
    mod.forward(db, is_train=False)
    out1 = mod.get_outputs()[0].asnumpy().reshape(4, 16, 50)
    x2 = x.copy()
    x2[:, -1] = (x2[:, -1] + 7) % 50
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(x2)],
                                label=[mx.nd.array(y)]), is_train=False)
    out2 = mod.get_outputs()[0].asnumpy().reshape(4, 16, 50)
    # perturbing the last token must not change logits at positions < T-1
    np.testing.assert_allclose(out1[:, :-1], out2[:, :-1], atol=1e-5)
    assert np.abs(out1[:, -1] - out2[:, -1]).max() > 1e-4


def test_transformer_learns_next_token():
    mod = _build()
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 3e-3})
    rng = np.random.RandomState(0)
    x = rng.randint(0, 50, (4, 16)).astype(np.float32)
    y = (x + 1) % 50
    db = mx.io.DataBatch(data=[mx.nd.array(x)], label=[mx.nd.array(y)])
    for _ in range(150):
        mod._fit_step(db)
    mod.forward(db, is_train=False)
    pred = mod.get_outputs()[0].asnumpy().argmax(1).reshape(4, 16)
    assert (pred == y).mean() > 0.95


def test_symbol_positional_attrs():
    """sym.reshape(x, shape) / sym.transpose(x, axes) positional attrs map
    onto the op's parameters (regression: silently dropped)."""
    x = mx.sym.Variable("x")
    r = mx.sym.reshape(x, (2, 6))
    t = mx.sym.transpose(r, (1, 0))
    _, outs, _ = t.infer_shape(x=(3, 4))
    assert outs[0] == (6, 2)


def test_transformer_flash_attention_matches_dense():
    """attention='flash' (Pallas kernel path) must produce the same
    logits as the dense composition under shared parameters — on one
    device and, sharded by the kernel's own shard_map, over a mesh."""
    one, two = mx.cpu(), [mx.cpu(0), mx.cpu(1)]
    mods = {}
    for name, att, ctx in (("dense", "dense", one), ("flash", "flash", one),
                           ("flash_mesh", "flash", two)):
        sym = transformer.get_symbol(vocab_size=50, num_layers=1,
                                     d_model=32, n_heads=2, seq_len=128,
                                     attention=att)
        mod = mx.mod.Module(sym, context=ctx)
        mod.bind(data_shapes=[("data", (2, 128))],
                 label_shapes=[("softmax_label", (2, 128))])
        mod.init_params(mx.init.Xavier())
        mods[name] = mod
    args, auxs = mods["dense"].get_params()
    mods["flash"].set_params(args, auxs)
    mods["flash_mesh"].set_params(args, auxs)
    assert mods["flash"]._exec._batch_rows is None
    mesh, axes = mods["flash_mesh"]._exec._batch_rows
    assert mesh.devices.size == 2 and axes == ("data",)
    rng = np.random.RandomState(0)
    x = rng.randint(0, 50, (2, 128)).astype(np.float32)
    db = mx.io.DataBatch(data=[mx.nd.array(x)],
                         label=[mx.nd.array(np.zeros_like(x))])
    outs = {}
    for name, mod in mods.items():
        mod.forward(db, is_train=False)
        outs[name] = mod.get_outputs()[0].asnumpy()
    for name in ("flash", "flash_mesh"):
        np.testing.assert_allclose(outs[name], outs["dense"],
                                   rtol=1e-4, atol=1e-5)
