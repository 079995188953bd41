#!/usr/bin/env python
"""Kaggle NDSB (plankton) pipeline lite (reference example/kaggle-ndsb1:
gen_img_list.py + im2rec + train_dsb.py + predict_dsb.py +
submission_dsb.py). The competition's pipeline shape end-to-end on
synthetic plankton-like images (zero-egress CI): class-directory corpus
-> train/val .lst split -> RecordIO pack -> ImageRecordIter with
augmentation -> train -> predict the "test" set -> write the
class-probability submission CSV.

    python examples/kaggle-ndsb1/train_dsb.py --epochs 3
"""
import argparse
import csv
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

CLASSES = ["amphipod", "copepod", "diatom", "fish_larvae"]
SIZE = 32


def make_corpus(root, rng, n_per_class):
    """Synthetic plankton: each class a distinct blob geometry."""
    import numpy as np
    cv2 = __import__("cv2")

    paths = []
    for ci, cname in enumerate(CLASSES):
        d = os.path.join(root, cname)
        os.makedirs(d, exist_ok=True)
        for i in range(n_per_class):
            img = np.zeros((SIZE, SIZE), np.float32)
            yy, xx = np.mgrid[:SIZE, :SIZE]
            cy, cx = rng.uniform(10, 22, 2)
            if ci == 0:      # elongated ellipse
                img = np.exp(-(((yy - cy) / 9.0) ** 2 + ((xx - cx) / 3.0) ** 2))
            elif ci == 1:    # round blob + tail
                img = np.exp(-(((yy - cy) / 4.0) ** 2 + ((xx - cx) / 4.0) ** 2))
                img += np.exp(-(((yy - cy) / 1.5) ** 2
                                + ((xx - cx - 8) / 6.0) ** 2)) * 0.7
            elif ci == 2:    # ring
                r = np.sqrt((yy - cy) ** 2 + (xx - cx) ** 2)
                img = np.exp(-((r - 8) / 2.0) ** 2)
            else:            # two lobes
                img = np.exp(-(((yy - cy) / 3.0) ** 2 + ((xx - cx - 5) / 3.0) ** 2))
                img += np.exp(-(((yy - cy) / 3.0) ** 2 + ((xx - cx + 5) / 3.0) ** 2))
            img = (img / img.max() * 200 + rng.rand(SIZE, SIZE) * 40)
            p = os.path.join(d, "%s_%03d.jpg" % (cname, i))
            cv2.imwrite(p, np.clip(img, 0, 255).astype(np.uint8))
            paths.append((p, ci))
    return paths


def gen_img_list(paths, root, prefix, rng, val_frac=0.2):
    """reference gen_img_list.py: shuffled class-balanced train/val .lst."""
    order = list(range(len(paths)))
    rng.shuffle(order)
    n_val = int(len(order) * val_frac)
    splits = {"val": order[:n_val], "train": order[n_val:]}
    for split, idxs in splits.items():
        with open("%s_%s.lst" % (prefix, split), "w") as f:
            for k, i in enumerate(idxs):
                p, ci = paths[i]
                f.write("%d\t%d\t%s\n" % (k, ci, os.path.relpath(p, root)))
    return splits


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=10)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--n-per-class", type=int, default=48)
    args = p.parse_args()

    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import native

    np.random.seed(0)
    rng = np.random.RandomState(0)
    work = tempfile.mkdtemp()
    root = os.path.join(work, "imgs")
    os.makedirs(root)
    paths = make_corpus(root, rng, args.n_per_class)
    prefix = os.path.join(work, "dsb")
    gen_img_list(paths, root, prefix, rng)

    for split in ("train", "val"):
        native.im2rec_pack("%s_%s.lst" % (prefix, split), root,
                           "%s_%s.rec" % (prefix, split),
                           "%s_%s.idx" % (prefix, split), nthreads=2)

    norm = dict(mean_r=40.0, mean_g=40.0, mean_b=40.0,
                std_r=60.0, std_g=60.0, std_b=60.0)
    train = mx.io.ImageRecordIter(
        path_imgrec=prefix + "_train.rec", data_shape=(3, SIZE, SIZE),
        batch_size=args.batch_size, shuffle=True, rand_mirror=True,
        **norm)
    val = mx.io.ImageRecordIter(
        path_imgrec=prefix + "_val.rec", data_shape=(3, SIZE, SIZE),
        batch_size=args.batch_size, **norm)

    # small conv net (the reference's symbol_dsb is a custom convnet)
    net = mx.sym.Variable("data")
    for i, nf in enumerate((16, 32)):
        net = mx.sym.Convolution(net, kernel=(3, 3), pad=(1, 1),
                                 num_filter=nf, name="conv%d" % i)
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                             pool_type="max")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=64)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=len(CLASSES))
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(train, eval_data=val, num_epoch=args.epochs,
            optimizer="adam", optimizer_params={"learning_rate": 2e-3},
            initializer=mx.initializer.Xavier())
    val.reset()
    m = mx.metric.create("acc")
    mod.score(val, m)
    acc = m.get()[1]

    # predict_dsb + submission_dsb: class probabilities for the val set
    # as the Kaggle CSV (image,prob_class0,...)
    val.reset()
    probs = mod.predict(val).asnumpy()
    sub = os.path.join(work, "submission.csv")
    with open(sub, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["image"] + CLASSES)
        for i, row in enumerate(probs):
            w.writerow(["img_%d.jpg" % i] + ["%.6f" % v for v in row])
    n_rows = sum(1 for _ in open(sub)) - 1
    print("ndsb pipeline: val acc %.3f, submission rows %d" % (acc, n_rows))
    if acc < 0.85:
        raise SystemExit("plankton classifier failed to converge")
    assert n_rows == len(probs)
    print("kaggle-ndsb OK")


if __name__ == "__main__":
    main()
