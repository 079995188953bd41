#!/usr/bin/env python
"""Faster-RCNN RPN training on synthetic detection data.

Analogue of the reference's example/rcnn training stage 1 (RPN): a conv
backbone feeds 1x1 cls/bbox heads; anchor targets are assigned by IoU
(positive IoU >= 0.5 or best-match, negative < 0.3, rest ignored), cls trains with
SoftmaxOutput(use_ignore, multi_output) and bbox regression with
masked smooth-L1 MakeLoss — the same loss structure the reference wires
in example/rcnn/rcnn/symbol. Runs a few steps on synthetic one-box
images and checks the combined loss decreases:

    python examples/rcnn/train.py --steps 12
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def generate_anchors(feat_size, stride, scales=(8, 16), ratios=(0.5, 1, 2)):
    """(A*F*F, 4) anchors in image pixels, corner format."""
    import numpy as np

    base = []
    for s in scales:
        for r in ratios:
            size = s * stride
            w = size * (r ** 0.5)
            h = size / (r ** 0.5)
            base.append([-w / 2, -h / 2, w / 2, h / 2])
    base = np.array(base, np.float32)  # (A, 4)
    shifts = np.arange(feat_size) * stride + stride / 2
    sx, sy = np.meshgrid(shifts, shifts)
    shift = np.stack([sx.ravel(), sy.ravel(), sx.ravel(), sy.ravel()], 1)
    return (base[None, :, :] + shift[:, None, :]).reshape(-1, 4)


def assign_targets(anchors, gt, img_size, pos_iou=0.5, neg_iou=0.3,
                   n_sample=64, rng=None):
    """RPN anchor assignment (reference rcnn AnchorLoader): labels in
    {1 pos, 0 neg, -1 ignore} + bbox regression targets for positives."""
    import numpy as np

    n = len(anchors)
    labels = -np.ones(n, np.float32)
    targets = np.zeros((n, 4), np.float32)
    ax1, ay1, ax2, ay2 = anchors.T
    gx1, gy1, gx2, gy2 = gt
    ix1 = np.maximum(ax1, gx1)
    iy1 = np.maximum(ay1, gy1)
    ix2 = np.minimum(ax2, gx2)
    iy2 = np.minimum(ay2, gy2)
    inter = np.maximum(ix2 - ix1, 0) * np.maximum(iy2 - iy1, 0)
    area_a = (ax2 - ax1) * (ay2 - ay1)
    area_g = (gx2 - gx1) * (gy2 - gy1)
    iou = inter / np.maximum(area_a + area_g - inter, 1e-6)
    inside = (ax1 >= -8) & (ay1 >= -8) & (ax2 <= img_size + 8) & (ay2 <= img_size + 8)
    pos = (iou >= pos_iou) & inside
    pos[np.argmax(iou)] = True  # best anchor always positive
    neg = (iou < neg_iou) & inside & ~pos
    neg_idx = np.flatnonzero(neg)
    rng = rng or np.random
    keep = rng.permutation(neg_idx)[:max(n_sample - pos.sum(), 1)]
    labels[pos] = 1
    labels[keep] = 0
    # bbox targets (dx, dy, dw, dh) for positives
    aw, ah = ax2 - ax1, ay2 - ay1
    acx, acy = ax1 + aw / 2, ay1 + ah / 2
    gw, gh = gx2 - gx1, gy2 - gy1
    gcx, gcy = gx1 + gw / 2, gy1 + gh / 2
    targets[pos, 0] = (gcx - acx[pos]) / aw[pos]
    targets[pos, 1] = (gcy - acy[pos]) / ah[pos]
    targets[pos, 2] = np.log(gw / aw[pos])
    targets[pos, 3] = np.log(gh / ah[pos])
    return labels, targets


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--image-size", type=int, default=128)
    p.add_argument("--feat-stride", type=int, default=16)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--steps", type=int, default=12)
    p.add_argument("--lr", type=float, default=0.02)
    args = p.parse_args()

    import numpy as np
    np.random.seed(0)  # deterministic param init (CI quality bars)
    import mxnet_tpu as mx

    S, stride = args.image_size, args.feat_stride
    F = S // stride
    scales, ratios = (8, 16), (0.5, 1, 2)
    A = len(scales) * len(ratios)
    anchors = generate_anchors(F, stride, scales, ratios)

    data = mx.sym.Variable("data")
    feat = mx.sym.Convolution(data, num_filter=16, kernel=(3, 3), pad=(1, 1),
                              stride=(stride, stride), name="backbone")
    feat = mx.sym.Activation(feat, act_type="relu")
    cls = mx.sym.Convolution(feat, num_filter=2 * A, kernel=(1, 1),
                             name="rpn_cls")
    # (B, 2A, F, F) -> (B, 2, A*F*F): class axis for multi-output softmax
    cls = mx.sym.Reshape(cls, shape=(0, 2, -1))
    cls_prob = mx.sym.SoftmaxOutput(cls, mx.sym.Variable("rpn_label"),
                                    multi_output=True, use_ignore=True,
                                    ignore_label=-1.0, normalization="valid",
                                    name="rpn_cls_prob")
    bbox = mx.sym.Convolution(feat, num_filter=4 * A, kernel=(1, 1),
                              name="rpn_bbox")
    bbox = mx.sym.Reshape(bbox, shape=(0, -1))
    diff = mx.sym._mul(mx.sym.Variable("rpn_bbox_mask"),
                       mx.sym._minus(bbox, mx.sym.Variable("rpn_bbox_target")))
    bbox_loss = mx.sym.MakeLoss(mx.sym.smooth_l1(diff, scalar=3.0),
                                grad_scale=1.0 / 64, name="rpn_bbox_loss")
    net = mx.sym.Group([cls_prob, bbox_loss])

    mod = mx.mod.Module(net, data_names=("data",),
                        label_names=("rpn_label", "rpn_bbox_target",
                                     "rpn_bbox_mask"))
    n_anchor = A * F * F
    mod.bind(data_shapes=[("data", (args.batch, 3, S, S))],
             label_shapes=[("rpn_label", (args.batch, n_anchor)),
                           ("rpn_bbox_target", (args.batch, 4 * n_anchor)),
                           ("rpn_bbox_mask", (args.batch, 4 * n_anchor))])
    mod.init_params(mx.initializer.Xavier(magnitude=2.0))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": args.lr,
                                         "momentum": 0.9})

    rng = np.random.RandomState(0)

    def make_batch():
        imgs = rng.uniform(-0.2, 0.2, (args.batch, 3, S, S)).astype(np.float32)
        labels = np.zeros((args.batch, n_anchor), np.float32)
        targets = np.zeros((args.batch, 4 * n_anchor), np.float32)
        masks = np.zeros((args.batch, 4 * n_anchor), np.float32)
        for b in range(args.batch):
            x1, y1 = rng.uniform(0.1 * S, 0.4 * S, 2)
            w, h = rng.uniform(0.3 * S, 0.5 * S, 2)
            gt = np.array([x1, y1, min(x1 + w, S - 1), min(y1 + h, S - 1)],
                          np.float32)
            imgs[b, :, int(gt[1]):int(gt[3]), int(gt[0]):int(gt[2])] += 1.0
            lab, tgt = assign_targets(anchors, gt, S, rng=rng)
            # anchors enumerate (position, anchor) = (F*F, A); the cls
            # head flattens as (A, F*F) and the bbox head as
            # (A, 4, F*F) (conv channels are a*4+coord) — match both
            lab2 = lab.reshape(F * F, A).T.reshape(-1)
            tgt2 = tgt.reshape(F * F, A, 4).transpose(1, 2, 0)  # (A,4,F*F)
            labels[b] = lab2
            targets[b] = tgt2.reshape(-1)
            m = (lab == 1).astype(np.float32).reshape(F * F, A).T  # (A,F*F)
            masks[b] = np.repeat(m.reshape(A, 1, F * F), 4,
                                 axis=1).reshape(-1)
        return mx.io.DataBatch(
            [mx.nd.array(imgs)],
            [mx.nd.array(labels), mx.nd.array(targets), mx.nd.array(masks)])

    def batch_loss():
        outs = mod.get_outputs()
        prob = outs[0].asnumpy()           # (B, 2, n_anchor)
        loss_bbox = float(outs[1].asnumpy().sum())
        lab = np.asarray(last_labels)
        sel = lab >= 0
        p = np.clip(prob[:, 1, :], 1e-12, 1.0)
        pn = np.clip(prob[:, 0, :], 1e-12, 1.0)
        ce = -(lab[sel] * np.log(p[sel]) + (1 - lab[sel]) * np.log(pn[sel]))
        return float(ce.mean() + loss_bbox / max(sel.sum(), 1))

    losses = []
    for step in range(args.steps):
        batch = make_batch()
        last_labels = batch.label[0].asnumpy()
        mod.forward_backward(batch)
        mod.update()
        losses.append(batch_loss())
        print("step %d loss %.4f" % (step, losses[-1]))

    first, last = losses[0], float(np.mean(losses[-3:]))
    print("RPN train: loss %.4f -> %.4f over %d steps (%s)"
          % (first, last, len(losses),
             "decreasing" if last < first else "NOT decreasing"))
    if last >= first:
        raise SystemExit("loss did not decrease")


if __name__ == "__main__":
    main()
