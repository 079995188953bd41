#!/usr/bin/env python
"""SSD detection training over the det record data plane.

Analogue of the reference's example/ssd training path: ImageDetRecordIter
(iter_image_recordio_2.cc:579 det variant) feeds box-aware-augmented
batches into the ssd-vgg16 training graph (MultiBoxTarget +
SoftmaxOutput(cls) + smooth-L1 MakeLoss(loc)), trained with Module.

With --rec absent, a small synthetic detection .rec is packed first (one
colored rectangle per image, label in the reference det layout
[header_width, object_width, class, x1, y1, x2, y2]) so the whole data
plane — pack, read, decode, augment, target-match, train — runs
end-to-end anywhere:

    python examples/ssd/train.py --steps 8 --image-size 96
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def make_synthetic_rec(path, n, size, num_classes):
    """Pack n images, each with one axis-aligned colored box of a
    class-specific color, into a det .rec."""
    import cv2
    import numpy as np
    from mxnet_tpu import recordio

    rng = np.random.RandomState(0)
    w = recordio.MXRecordIO(path, "w")
    colors = rng.randint(64, 255, (num_classes, 3))
    for i in range(n):
        cls = i % num_classes
        img = rng.randint(0, 40, (size, size, 3), np.uint8)
        x1, y1 = rng.uniform(0.05, 0.4, 2)
        x2, y2 = x1 + rng.uniform(0.3, 0.5), y1 + rng.uniform(0.3, 0.5)
        x2, y2 = min(x2, 0.95), min(y2, 0.95)
        img[int(y1 * size):int(y2 * size),
            int(x1 * size):int(x2 * size)] = colors[cls]
        label = np.array([2, 5, cls, x1, y1, x2, y2], np.float32)
        ok, enc = cv2.imencode(".jpg", img)
        assert ok
        w.write(recordio.pack(recordio.IRHeader(0, label, i, 0),
                              enc.tobytes()))
    w.close()
    return path


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--rec", default=None, help=".rec file (synthetic if absent)")
    p.add_argument("--image-size", type=int, default=96)
    p.add_argument("--num-classes", type=int, default=3)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--num-records", type=int, default=32)
    args = p.parse_args()

    import numpy as np
    np.random.seed(0)  # deterministic param init (CI quality bars)
    import mxnet_tpu as mx
    from mxnet_tpu import models

    rec = args.rec
    if rec is None:
        rec = os.path.join(tempfile.mkdtemp(), "ssd_synth.rec")
        make_synthetic_rec(rec, args.num_records, max(args.image_size, 64),
                           args.num_classes)

    it = mx.io.ImageDetRecordIter(
        path_imgrec=rec, data_shape=(3, args.image_size, args.image_size),
        batch_size=args.batch, max_objs=4, shuffle=True, rand_mirror=True,
        mean_r=127.0, mean_g=127.0, mean_b=127.0,
        std_r=64.0, std_g=64.0, std_b=64.0)

    net = models.get_symbol("ssd-vgg16", num_classes=args.num_classes,
                            mode="train")
    mod = mx.mod.Module(net, data_names=("data",), label_names=("label",))
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier(magnitude=2.0))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": args.lr,
                                         "momentum": 0.9, "wd": 5e-4})

    def batch_loss(outputs):
        """cls cross-entropy on valid anchors + masked loc smooth-L1 —
        the quantities the two loss heads backpropagate."""
        cls_prob = outputs[0].asnumpy()       # (B, C, A)
        loc_loss = outputs[1].asnumpy()       # masked smooth-L1 values
        cls_target = outputs[2].asnumpy()     # (B, A) with -1 ignore
        b, c, a = cls_prob.shape
        probs = np.moveaxis(cls_prob, 1, 2).reshape(-1, c)
        tgt = cls_target.reshape(-1)
        sel = tgt >= 0
        ce = -np.log(np.clip(probs[sel, tgt[sel].astype(int)], 1e-12, 1.0))
        return float(ce.mean() + loc_loss.sum() / max(sel.sum(), 1))

    losses = []
    step = 0
    while step < args.steps:
        it.reset()
        produced = 0
        for batch in it:
            if step >= args.steps:
                break
            mod.forward_backward(batch)
            mod.update()
            losses.append(batch_loss(mod.get_outputs()))
            print("step %d loss %.4f" % (step, losses[-1]))
            step += 1
            produced += 1
        if produced == 0:
            raise SystemExit("record iterator yielded no batches")

    if not losses:
        raise SystemExit("no training steps ran (--steps %d)" % args.steps)
    first, last = losses[0], np.mean(losses[-2:])
    print("SSD train: loss %.4f -> %.4f over %d steps (%s)"
          % (first, last, len(losses),
             "decreasing" if last < first else "NOT decreasing"))
    if last >= first:
        raise SystemExit("loss did not decrease")


if __name__ == "__main__":
    main()
