#!/usr/bin/env python
"""SSD-VGG16 detection: forward + multibox decode + NMS.

Analogue of the reference's example/ssd (SSD detection stack, SURVEY §2.1
item 19: MultiBoxPrior/Target/Detection). Binds the ssd-vgg16 zoo model,
runs a random image through it, decodes anchors with MultiBoxDetection
(NMS included) and prints the top detections.

    python examples/ssd/demo.py --image-size 300
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--image-size", type=int, default=300)
    p.add_argument("--num-classes", type=int, default=20)
    p.add_argument("--batch", type=int, default=1)
    args = p.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import models

    sym = models.get_symbol("ssd-vgg16", num_classes=args.num_classes,
                            mode="detect")
    shape = (args.batch, 3, args.image_size, args.image_size)
    dev = (mx.Context("tpu", 0) if jax.default_backend() != "cpu"
           else mx.cpu())
    exe = sym.simple_bind(dev, grad_req="null", data=shape)
    init = mx.initializer.Xavier()
    for n, a in exe.arg_dict.items():
        if n == "data":
            continue
        init(mx.initializer.InitDesc(n), a)
    rng = np.random.RandomState(0)
    exe.arg_dict["data"]._data = jnp.asarray(
        rng.uniform(-1, 1, shape).astype(np.float32))
    outs = exe.forward(is_train=False)
    det = outs[0].asnumpy()  # (batch, num_det, 6): [cls, score, x1,y1,x2,y2]
    kept = det[0][det[0, :, 0] >= 0]
    order = np.argsort(-kept[:, 1])[:5]
    print("top detections (class score x1 y1 x2 y2):")
    for row in kept[order]:
        print("  %2d %.3f  %.3f %.3f %.3f %.3f" % tuple(row))


if __name__ == "__main__":
    main()
