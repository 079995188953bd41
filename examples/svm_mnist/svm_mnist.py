#!/usr/bin/env python
"""MLP classifier trained with an SVM objective (reference example/svm_mnist).

The reference swaps a softmax head for `SVMOutput` — L2-SVM by default,
L1 (linear hinge) via use_linear — on PCA-compressed noisy MNIST
(reference example/svm_mnist/svm_mnist.py:19-31). Same capability here on
a synthetic Gaussian-blobs task small enough for CI: an MLP scored by
SVMOutput in both margin modes, trained with Module.fit, accuracy
compared between the two heads.

    python examples/svm_mnist/svm_mnist.py --epochs 8
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

NUM_CLASS = 5


def svm_mlp(use_linear):
    import mxnet_tpu as mx

    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=64, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=NUM_CLASS, name="fc2")
    return mx.sym.SVMOutput(h, mx.sym.Variable("svm_label"),
                            use_linear=use_linear,
                            regularization_coefficient=1e-3, name="svm")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=8)
    p.add_argument("--batch-size", type=int, default=64)
    args = p.parse_args()

    import numpy as np
    import mxnet_tpu as mx

    rng = np.random.RandomState(7)
    centers = rng.normal(0, 3.0, (NUM_CLASS, 20)).astype(np.float32)
    y = rng.randint(0, NUM_CLASS, 2048).astype(np.float32)
    x = centers[y.astype(int)] + rng.normal(0, 1.0, (2048, 20)).astype(
        np.float32)
    n_train = 1536

    accs = {}
    for use_linear in (False, True):
        it = mx.io.NDArrayIter(x[:n_train], y[:n_train],
                               batch_size=args.batch_size, shuffle=True,
                               label_name="svm_label")
        val = mx.io.NDArrayIter(x[n_train:], y[n_train:],
                                batch_size=args.batch_size,
                                label_name="svm_label")
        mod = mx.mod.Module(svm_mlp(use_linear), label_names=("svm_label",))
        mod.fit(it, num_epoch=args.epochs, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                initializer=mx.initializer.Xavier(), eval_metric="acc")
        acc = dict(mod.score(val, "acc"))["accuracy"]
        accs["L1" if use_linear else "L2"] = acc
        print("SVM head %s: val accuracy %.3f"
              % ("L1(linear)" if use_linear else "L2(squared)", acc))
    assert min(accs.values()) > 0.9, accs
    print("svm_mnist OK")


if __name__ == "__main__":
    main()
