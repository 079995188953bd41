#!/usr/bin/env python
"""Faster-RCNN building blocks: RPN Proposal + ROIPooling in one graph.

Analogue of the reference's example/rcnn (backed by the contrib Proposal
op and ROIPooling, SURVEY §2.1 item 19): a tiny conv backbone produces RPN
class scores and bbox deltas; `Proposal` decodes anchors + NMS into ROIs;
`ROIPooling` crops per-ROI features for the (here: toy) head.

    python examples/rcnn/demo.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--image-size", type=int, default=128)
    p.add_argument("--feat-stride", type=int, default=16)
    args = p.parse_args()

    import numpy as np
    import jax
    import mxnet_tpu as mx

    S = args.image_size
    F = S // args.feat_stride
    n_anchor = 12  # len(scales)*len(ratios) of the Proposal op defaults

    data = mx.sym.Variable("data")
    feat = mx.sym.Convolution(data, num_filter=16, kernel=(3, 3), pad=(1, 1),
                              stride=(args.feat_stride, args.feat_stride),
                              name="backbone")
    feat = mx.sym.Activation(feat, act_type="relu")
    cls = mx.sym.Convolution(feat, num_filter=2 * n_anchor, kernel=(1, 1),
                             name="rpn_cls")
    cls_prob = mx.sym.Reshape(cls, shape=(0, 2, -1, F))
    cls_prob = mx.sym.softmax(cls_prob, axis=1)
    cls_prob = mx.sym.Reshape(cls_prob, shape=(0, 2 * n_anchor, -1, F))
    bbox = mx.sym.Convolution(feat, num_filter=4 * n_anchor, kernel=(1, 1),
                              name="rpn_bbox")
    rois = mx.sym.Proposal(cls_prob, bbox, mx.sym.Variable("im_info"),
                           feature_stride=args.feat_stride,
                           rpn_pre_nms_top_n=64, rpn_post_nms_top_n=16,
                           threshold=0.7, name="proposal")
    pooled = mx.sym.ROIPooling(feat, rois, pooled_size=(4, 4),
                               spatial_scale=1.0 / args.feat_stride,
                               name="roi_pool")

    net = mx.sym.Group([rois, pooled])
    dev = (mx.Context("tpu", 0) if jax.default_backend() != "cpu"
           else mx.cpu())
    exe = net.simple_bind(dev, grad_req="null", data=(1, 3, S, S),
                          im_info=(1, 3))
    init = mx.initializer.Xavier()
    rng = np.random.RandomState(0)
    for n, a in exe.arg_dict.items():
        if n in ("data", "im_info"):
            continue
        init(mx.initializer.InitDesc(n), a)
    import jax.numpy as jnp
    exe.arg_dict["data"]._data = jnp.asarray(
        rng.uniform(-1, 1, (1, 3, S, S)).astype(np.float32))
    exe.arg_dict["im_info"]._data = jnp.asarray(
        np.array([[S, S, 1.0]], np.float32))
    rois_out, pooled_out = exe.forward(is_train=False)
    r = rois_out.asnumpy()
    print("proposals (batch_idx x1 y1 x2 y2), first 4 of %d:" % r.shape[0])
    for row in r[:4]:
        print("  " + " ".join("%7.2f" % v for v in row))
    print("ROI-pooled features:", pooled_out.shape)


if __name__ == "__main__":
    main()
