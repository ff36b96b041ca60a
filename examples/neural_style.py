"""Neural style transfer: optimize the INPUT image, not the weights.

Capability twin of the reference's ``example/neural-style`` (Gatys et
al.): a fixed convolutional feature extractor defines a content loss
(deep feature match) and a style loss (Gram-matrix match), and
gradient descent runs on the *image pixels* — ``x.attach_grad()`` +
``autograd.record`` + manual updates, the gradient-wrt-input capability
the training APIs never exercise.

Fixed random conv features stand in for VGG (random-feature style
statistics are a known-good approximation, and the example needs no
pretrained download); the gate checks the optimization moved the
image's Gram statistics decisively toward the style target while
keeping content correlation.

Run:  python examples/neural_style.py --num-steps 120
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def make_images(size=48, seed=0):
    """Content: centered disc. Style: diagonal stripes."""
    rng = np.random.RandomState(seed)
    yy, xx = np.mgrid[0:size, 0:size] / float(size)
    content = np.stack([
        ((yy - 0.5) ** 2 + (xx - 0.5) ** 2 < 0.09).astype(np.float32),
        ((yy - 0.5) ** 2 + (xx - 0.5) ** 2 < 0.04).astype(np.float32),
        np.zeros((size, size), np.float32)])
    stripes = (np.sin((yy + xx) * 40) > 0).astype(np.float32)
    style = np.stack([stripes, 1 - stripes,
                      0.5 * np.ones((size, size), np.float32)])
    content += 0.05 * rng.rand(3, size, size).astype(np.float32)
    style += 0.05 * rng.rand(3, size, size).astype(np.float32)
    return content[None], style[None]


def main():
    p = argparse.ArgumentParser(description="neural style transfer")
    p.add_argument("--num-steps", type=int, default=120)
    p.add_argument("--size", type=int, default=48)
    p.add_argument("--lr", type=float, default=0.02)
    p.add_argument("--style-weight", type=float, default=30.0)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()
    np.random.seed(args.seed)

    import mxnet_tpu as mx

    content, style = make_images(args.size)

    # fixed random conv stack: 2 feature levels
    rng = np.random.RandomState(3)
    W1 = mx.nd.array(rng.randn(16, 3, 3, 3).astype(np.float32) * 0.4)
    W2 = mx.nd.array(rng.randn(32, 16, 3, 3).astype(np.float32) * 0.2)

    def features(x):
        h1 = mx.nd.Activation(
            mx.nd.Convolution(x, W1, num_filter=16, kernel=(3, 3),
                              pad=(1, 1), no_bias=True),
            act_type="relu")
        h2 = mx.nd.Activation(
            mx.nd.Convolution(mx.nd.Pooling(h1, kernel=(2, 2),
                                            stride=(2, 2),
                                            pool_type="avg"),
                              W2, num_filter=32, kernel=(3, 3),
                              pad=(1, 1), no_bias=True),
            act_type="relu")
        return h1, h2

    def gram(f):
        n, c = f.shape[0], f.shape[1]
        flat = mx.nd.reshape(f, (n, c, -1))
        hw = flat.shape[2]
        return mx.nd.batch_dot(flat, flat, transpose_b=True) / float(hw)

    c_feats = [f.detach() if hasattr(f, "detach") else f
               for f in features(mx.nd.array(content))]
    s_grams = [gram(f) for f in features(mx.nd.array(style))]

    x = mx.nd.array(content.copy())
    x.attach_grad()

    def losses():
        f1, f2 = features(x)
        closs = mx.nd.mean(mx.nd.square(f2 - c_feats[1]))
        sloss = mx.nd.mean(mx.nd.square(gram(f1) - s_grams[0])) + \
            mx.nd.mean(mx.nd.square(gram(f2) - s_grams[1]))
        return closs, sloss

    c0, s0 = (float(v.asnumpy()) for v in losses())
    # the natural scale for "content survived": how far the STYLE image
    # is from the content features — the stylized result must stay much
    # closer to the content than that
    sf1, sf2 = features(mx.nd.array(style))
    c_of_style = float(mx.nd.mean(
        mx.nd.square(sf2 - c_feats[1])).asnumpy())
    for step in range(args.num_steps):
        with mx.autograd.record():
            closs, sloss = losses()
            loss = closs + args.style_weight * sloss
        loss.backward()
        # normalized gradient descent on the pixels (the reference uses
        # lr-decayed SGD over Adam-scale gradients; normalizing by the
        # mean |grad| makes the step size image-scale like theirs)
        g = x.grad.asnumpy()
        g /= np.abs(g).mean() + 1e-8
        x = mx.nd.array(np.clip(x.asnumpy() - args.lr * g, -0.2, 1.4))
        x.attach_grad()
        if step % 30 == 0:
            print("step %3d  content=%.5f style=%.5f"
                  % (step, float(closs.asnumpy()),
                     float(sloss.asnumpy())), flush=True)

    c1, s1 = (float(v.asnumpy()) for v in losses())
    print("style loss %.5f -> %.5f (%.1fx down); content %.5f "
          "(style image itself: %.5f)" % (s0, s1, s0 / max(s1, 1e-12),
                                          c1, c_of_style))
    assert s1 < 0.25 * s0, "style statistics did not move to the target"
    assert c1 < 0.5 * c_of_style, "content was destroyed"
    return 0


if __name__ == "__main__":
    sys.exit(main())
