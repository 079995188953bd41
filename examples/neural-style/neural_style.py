#!/usr/bin/env python
"""Neural style transfer — pretrained-model surgery + imperative autograd.

Analogue of the reference's example/neural-style (nstyle.py +
model_vgg19.py): take a trained VGG classifier, SURGERY out its internal
relu activations with ``get_internals()``, build content + style
(Gram-matrix) losses ON TOP of the tapped sub-graph symbolically, and
optimize the INPUT IMAGE (not the weights) by gradient descent. The
total-variation smoothness term is computed IMPERATIVELY with
``mx.nd`` ops under ``autograd.record()`` on the same image array —
the two autograd worlds (symbolic executor backward, imperative tape)
cooperating on one optimization, which is exactly the part of the API
surface no other example touches.

The VGG weights here are random (no zoo download in this environment) —
the mechanics are identical; with a real checkpoint
(mx.model.load_checkpoint, including reference-format files via
interop.py) the same script produces stylized images.

    python examples/neural-style/neural_style.py --steps 40 --size 32
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

STYLE_LAYERS = ["relu1_1_output", "relu2_1_output"]
CONTENT_LAYER = "relu3_1_output"


def build_loss_symbol():
    """VGG-11 internals -> symbolic Gram/content losses vs reference
    Variables (the reference's style_out/content_out executors fused
    into one loss graph)."""
    import mxnet_tpu as mx
    from mxnet_tpu import models

    vgg = models.get_symbol("vgg", num_layers=11, num_classes=10)
    internals = vgg.get_internals()
    loss = None
    for i, name in enumerate(STYLE_LAYERS):
        f = internals[name]                       # (1, C, H, W)
        # -3 merges (batch=1, C) into C; -1 flattens space: (C, H*W)
        fm = mx.sym.Reshape(f, shape=(-3, -1))
        g = mx.sym.dot(fm, fm, transpose_b=True)  # (C, C) Gram
        ref = mx.sym.Variable("style_ref_%d" % i)
        sl = mx.sym.mean(mx.sym.square(g - ref))
        loss = sl if loss is None else loss + sl
    c = internals[CONTENT_LAYER]
    cref = mx.sym.Variable("content_ref")
    loss = loss + mx.sym.mean(mx.sym.square(c - cref))
    return mx.sym.MakeLoss(loss, name="style_loss")


def tv_grad(img):
    """Total-variation regularizer gradient (unweighted; the caller
    applies tv-weight), computed IMPERATIVELY: nd ops under
    autograd.record, backward on the array tape."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd

    x = mx.nd.array(img.asnumpy())
    x.attach_grad()
    with autograd.record():
        dh = mx.nd.slice_axis(x, axis=2, begin=1, end=None) \
            - mx.nd.slice_axis(x, axis=2, begin=0, end=-1)
        dw = mx.nd.slice_axis(x, axis=3, begin=1, end=None) \
            - mx.nd.slice_axis(x, axis=3, begin=0, end=-1)
        tv = mx.nd.mean(dh * dh) + mx.nd.mean(dw * dw)
    tv.backward()
    return x.grad, float(tv.asnumpy())


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--size", type=int, default=32)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--tv-weight", type=float, default=0.1)
    args = p.parse_args()

    import numpy as np
    import mxnet_tpu as mx

    shape = (1, 3, args.size, args.size)
    loss_sym = build_loss_symbol()
    rng = np.random.RandomState(0)

    # feature-only executor first: its output shapes give the Gram /
    # content reference shapes the loss graph binds against
    from mxnet_tpu import models
    feats = models.get_symbol("vgg", num_layers=11,
                              num_classes=10).get_internals()
    fsym = mx.sym.Group([feats[n] for n in STYLE_LAYERS + [CONTENT_LAYER]])
    fexe = fsym.simple_bind(mx.cpu(), grad_req="null", data=shape)
    init = mx.initializer.Xavier()
    for n, a in fexe.arg_dict.items():
        if n != "data":
            init(mx.initializer.InitDesc(n), a)
    _, fout_shapes, _ = fsym.infer_shape(data=shape)
    ref_shapes = {"style_ref_%d" % i: (s[1], s[1])
                  for i, s in enumerate(fout_shapes[:len(STYLE_LAYERS)])}
    ref_shapes["content_ref"] = fout_shapes[-1]

    # loss executor: grad ONLY on the image; weights frozen (null) and
    # SHARED with the feature executor (pretrained-model surgery)
    grad_req = {n: ("write" if n == "data" else "null")
                for n in loss_sym.list_arguments()}
    exe = loss_sym.simple_bind(mx.cpu(), grad_req=grad_req, data=shape,
                               **ref_shapes)
    for n, a in exe.arg_dict.items():
        if n in fexe.arg_dict and n != "data":
            a._data = fexe.arg_dict[n]._data

    content_img = rng.uniform(-1, 1, shape).astype(np.float32)
    style_img = rng.uniform(-1, 1, shape).astype(np.float32)

    def run_feats(img):
        fexe.arg_dict["data"]._data = mx.nd.array(img)._data
        outs = fexe.forward(is_train=False)
        grams = []
        for f in outs[:len(STYLE_LAYERS)]:
            c = f.shape[1]
            fm = f.asnumpy().reshape(c, -1)
            grams.append(fm @ fm.T)
        return grams, outs[-1].asnumpy()

    style_grams, _ = run_feats(style_img)
    _, content_feat = run_feats(content_img)
    for i, g in enumerate(style_grams):
        exe.arg_dict["style_ref_%d" % i]._data = mx.nd.array(g)._data
    exe.arg_dict["content_ref"]._data = mx.nd.array(content_feat)._data

    img = mx.nd.array(content_img + 0.1 * rng.randn(*shape)
                      .astype(np.float32))
    losses = []
    for step in range(args.steps):
        exe.arg_dict["data"]._data = img._data
        out = exe.forward(is_train=True)
        exe.backward()
        g_sym = exe.grad_dict["data"]
        g_tv, tv_val = tv_grad(img)
        losses.append(float(out[0].asnumpy()) + args.tv_weight * tv_val)
        # normalized gradient step (the reference nstyle's lr-on-
        # normalized-grad trick): Gram losses scale with the random
        # init, so a raw step size has no stable meaning
        g = g_sym._data + args.tv_weight * g_tv._data
        g = g / (np.abs(np.asarray(g)).max() + 1e-8)
        img = mx.nd.array(img._data - args.lr * g)
        if step % 10 == 0:
            print("step %d  loss %.5f" % (step, losses[-1]))
    assert losses[-1] < losses[0], (losses[0], losses[-1])
    print("neural-style OK: loss %.5f -> %.5f over %d steps"
          % (losses[0], losses[-1], args.steps))


if __name__ == "__main__":
    main()
