"""Model-parallel stacked LSTM: each layer pinned to its own device.

The capability twin of the reference's ``example/model-parallel-lstm/
lstm.py:65-129`` (there: each LSTM layer's weights created under
``with mx.AttrScope(ctx_group='layer%d')`` and bound with
``group2ctx={'layer0': gpu(0), ...}``). Here the same ``ctx_group`` /
``group2ctx`` surface places layers across the available devices, and the
executor runs the graph op-by-op with boundary transfers — on a real pod,
pipeline placement across chips with ICI hops.

Run on the CPU rig:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/model_parallel_lstm.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_symbol(mx, num_layers, num_hidden, seq_len, vocab):
    """Stacked LSTM LM with each layer in its own ctx group."""
    with mx.AttrScope(ctx_group="embed"):
        data = mx.sym.Variable("data")                      # (N, T)
        weight = mx.sym.Variable("embed_weight")
        emb = mx.sym.Embedding(data, weight, input_dim=vocab,
                               output_dim=num_hidden, name="embed")
    hidden = mx.sym.SwapAxis(emb, dim1=0, dim2=1)           # (T, N, H)
    stack = []
    for i in range(num_layers):
        with mx.AttrScope(ctx_group="layer%d" % i):
            cell = mx.rnn.LSTMCell(num_hidden, prefix="lstm%d_" % i)
            outs, _ = cell.unroll(seq_len, inputs=hidden, layout="TNC",
                                  merge_outputs=True)
            hidden = outs
            stack.append(cell)
    with mx.AttrScope(ctx_group="head"):
        flat = mx.sym.Reshape(hidden, shape=(-1, num_hidden))
        logits = mx.sym.FullyConnected(flat, num_hidden=vocab, name="cls")
        label = mx.sym.Reshape(mx.sym.SwapAxis(mx.sym.Variable("label"),
                                               dim1=0, dim2=1), shape=(-1,))
        out = mx.sym.SoftmaxOutput(logits, label, normalization="valid",
                                   name="softmax")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-layers", type=int, default=2)
    ap.add_argument("--num-hidden", type=int, default=48)
    ap.add_argument("--seq-len", type=int, default=8)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--vocab", type=int, default=50)
    ap.add_argument("--steps", type=int, default=25)
    args = ap.parse_args()

    import jax
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        # also covers a JAX imported before this script set the variable
        jax.config.update("jax_platforms", "cpu")
    import mxnet_tpu as mx

    n_dev = mx.num_devices("tpu") or mx.num_devices("cpu")
    kind = mx.tpu if mx.num_devices("tpu") else mx.cpu
    # embed + layers + head, round-robin over what we have
    groups = ["embed"] + ["layer%d" % i for i in range(args.num_layers)] \
        + ["head"]
    group2ctx = {g: kind(i % n_dev) for i, g in enumerate(groups)}
    print("placement:", {g: str(c) for g, c in group2ctx.items()})

    np.random.seed(7)     # initializers draw from numpy's global RNG
    mx.random.seed(7)
    sym = build_symbol(mx, args.num_layers, args.num_hidden, args.seq_len,
                       args.vocab)
    # explicit init-state shapes, like the reference's init_c/init_h inputs
    state_shapes = {n: (args.batch, args.num_hidden)
                    for n in sym.list_arguments() if "begin_state" in n}
    ex = sym.simple_bind(ctx=kind(0), grad_req="write",
                         group2ctx=group2ctx,
                         data=(args.batch, args.seq_len),
                         label=(args.batch, args.seq_len), **state_shapes)
    init = mx.init.Xavier()
    for name, arr in ex.arg_dict.items():
        if name in ("data", "label"):
            continue
        if "begin_state" in name:
            arr[:] = 0
        else:
            init(name, arr)

    # learnable synthetic LM task: the next token is (current + 1) % vocab
    rng = np.random.RandomState(0)
    x = rng.randint(1, args.vocab, (args.batch, args.seq_len))
    y = ((x + 1) % args.vocab).astype(np.float32)
    x = x.astype(np.float32)
    ex.arg_dict["data"][:] = x
    ex.arg_dict["label"][:] = y

    lr, mom = 5.0, 0.9
    vel = {}
    first = last = None
    for step in range(args.steps):
        out = ex.forward(is_train=True)[0]
        probs = out.asnumpy().reshape(args.seq_len, args.batch, args.vocab)
        flat_label = y.T.reshape(-1).astype(int)
        nll = -np.log(np.maximum(
            probs.reshape(-1, args.vocab)[np.arange(flat_label.size),
                                          flat_label], 1e-12)).mean()
        ex.backward()
        for name, grad in ex.grad_dict.items():
            if name in ("data", "label") or grad is None:
                continue
            v = vel.get(name)
            v = mom * v - lr * grad if v is not None else -lr * grad
            vel[name] = v
            ex.arg_dict[name][:] = ex.arg_dict[name] + v
        if first is None:
            first = nll
        last = nll
        if step % 5 == 0 or step == args.steps - 1:
            print("step %3d  nll %.4f" % (step, nll))
    assert last < first * 0.7, "model-parallel LSTM failed to learn " \
        "(nll %.4f -> %.4f)" % (first, last)
    print("ok: nll %.4f -> %.4f across %d devices" % (first, last, n_dev))


if __name__ == "__main__":
    main()
