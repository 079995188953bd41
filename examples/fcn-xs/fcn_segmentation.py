#!/usr/bin/env python
"""FCN semantic segmentation with skip connections (reference example/fcn-xs).

The reference builds FCN-32s/16s/8s on VGG-16: score heads at several
strides, 2x `Deconvolution` upsampling initialized to bilinear
interpolation, `Crop` to align skip branches, and a per-pixel
`SoftmaxOutput(multi_output=True, use_ignore=True, ignore_label=255)`
(reference example/fcn-xs/symbol_fcnxs.py:139-190, bilinear filler
init_fcnxs.py). This example exercises the same surface TPU-natively on a
synthetic shapes dataset: a small conv encoder at stride 4, an FCN-8s-style
skip fusion (score head at stride 4 + stride-2 head), bilinear-initialized
deconvolutions, Crop alignment, and ignore-label pixels at the image rim.

    python examples/fcn-xs/fcn_segmentation.py --steps 40
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

NUM_CLASS = 3
IGNORE = 255


def make_dataset(n, size, rng):
    """Images with a filled rectangle (class 1) and a filled disc (class 2)
    on background (class 0); a 2-pixel rim is labelled IGNORE to exercise
    use_ignore the way VOC's void border does."""
    import numpy as np

    x = np.zeros((n, 3, size, size), dtype=np.float32)
    y = np.zeros((n, size, size), dtype=np.float32)
    yy, xx = np.mgrid[0:size, 0:size]
    for i in range(n):
        x[i] = rng.normal(0, 0.1, (3, size, size))
        # rectangle
        h0, w0 = rng.randint(2, size // 2, 2)
        h1 = h0 + rng.randint(4, size // 2)
        w1 = w0 + rng.randint(4, size // 2)
        rect = (yy >= h0) & (yy < h1) & (xx >= w0) & (xx < w1)
        x[i, 0][rect] += 1.0
        y[i][rect] = 1
        # disc (drawn second, occludes)
        cy, cx = rng.randint(size // 4, 3 * size // 4, 2)
        r = rng.randint(3, size // 4)
        disc = (yy - cy) ** 2 + (xx - cx) ** 2 <= r * r
        x[i, 1][disc] += 1.0
        y[i][disc] = 2
        y[i, :2, :] = y[i, -2:, :] = IGNORE
        y[i, :, :2] = y[i, :, -2:] = IGNORE
    return x, y


def conv_relu(data, num_filter, name, stride=(1, 1)):
    import mxnet_tpu as mx
    c = mx.sym.Convolution(data, kernel=(3, 3), pad=(1, 1), stride=stride,
                           num_filter=num_filter, name=name)
    return mx.sym.Activation(c, act_type="relu")


def fcn8s_symbol():
    """Encoder to stride 4 with a stride-2 skip, FCN-style decoder."""
    import mxnet_tpu as mx

    data = mx.sym.Variable("data")
    c1 = conv_relu(data, 16, "conv1")
    p1 = mx.sym.Pooling(c1, pool_type="max", kernel=(2, 2), stride=(2, 2))
    c2 = conv_relu(p1, 32, "conv2")                      # stride 2
    p2 = mx.sym.Pooling(c2, pool_type="max", kernel=(2, 2), stride=(2, 2))
    c3 = conv_relu(p2, 64, "conv3")                      # stride 4
    # score heads (1x1 convs), reference symbol_fcnxs.py score/score_pool4
    score4 = mx.sym.Convolution(c3, kernel=(1, 1), num_filter=NUM_CLASS,
                                name="score_s4")
    score2 = mx.sym.Convolution(c2, kernel=(1, 1), num_filter=NUM_CLASS,
                                name="score_s2")
    # upsample stride-4 head 2x with a bilinear-initialized deconv, crop to
    # the stride-2 head, fuse (reference fcnxs lines 160-180)
    up2 = mx.sym.Deconvolution(score4, kernel=(4, 4), stride=(2, 2),
                               pad=(1, 1), num_filter=NUM_CLASS,
                               num_group=NUM_CLASS, no_bias=True,
                               name="up_s4_bilinear")
    up2c = mx.sym.Crop(up2, score2, num_args=2, name="up_s4_crop")
    fused = up2c + score2
    # final 2x upsample back to input resolution
    up1 = mx.sym.Deconvolution(fused, kernel=(4, 4), stride=(2, 2),
                               pad=(1, 1), num_filter=NUM_CLASS,
                               num_group=NUM_CLASS, no_bias=True,
                               name="up_final_bilinear")
    up1c = mx.sym.Crop(up1, data, num_args=2, name="up_final_crop")
    return mx.sym.SoftmaxOutput(up1c, mx.sym.Variable("softmax_label"),
                                multi_output=True, use_ignore=True,
                                ignore_label=IGNORE, normalization="valid",
                                name="softmax")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=40)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--size", type=int, default=32)
    args = p.parse_args()

    import numpy as np
    np.random.seed(0)  # deterministic param init (CI quality bars)
    import mxnet_tpu as mx

    rng = np.random.RandomState(0)
    x, y = make_dataset(256, args.size, rng)
    it = mx.io.NDArrayIter(x, y, batch_size=args.batch_size, shuffle=True)

    net = fcn8s_symbol()
    mod = mx.mod.Module(net)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    # bilinear-filler deconv init, the fcn-xs init_fcnxs.py recipe
    mod.init_params(mx.initializer.Mixed(
        [".*bilinear.*weight", ".*"],
        [mx.initializer.Bilinear(), mx.initializer.Xavier()]))
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 2e-3})

    losses, accs = [], []
    metric = mx.metric.create("acc")
    epochs = max(1, -(-args.steps * args.batch_size // 256))
    step = 0
    for _ in range(epochs):
        it.reset()
        for batch in it:
            if step >= args.steps:
                break
            mod.forward_backward(batch)
            mod.update()
            prob = mod.get_outputs()[0].asnumpy()
            lab = batch.label[0].asnumpy()
            valid = lab != IGNORE
            pred = prob.argmax(axis=1)
            accs.append(float((pred[valid] == lab[valid]).mean()))
            pix = np.clip(
                prob.transpose(0, 2, 3, 1).reshape(-1, NUM_CLASS)[
                    np.arange(lab.size),
                    np.where(valid, lab, 0).reshape(-1).astype(int)],
                1e-8, None)
            losses.append(float(-(np.log(pix) * valid.reshape(-1)).sum()
                                / max(valid.sum(), 1)))
            step += 1
    first, last = np.mean(losses[:5]), np.mean(losses[-5:])
    print("fcn pixel-softmax loss %.4f -> %.4f, pixel acc %.3f"
          % (first, last, np.mean(accs[-5:])))
    ok = last < first and np.mean(accs[-5:]) > 0.80
    print("fcn-xs %s" % ("decreasing" if ok else "NOT decreasing"))


if __name__ == "__main__":
    main()
