#!/usr/bin/env python
"""PythonLossModule walkthrough (reference example/module/python_loss.py):
an MLP Module chained to a LOSS WRITTEN IN NUMPY — the multiclass hinge
loss gradient computed host-side — through SequentialModule. The
symbolic tower never sees the loss; the python module injects the
gradient at the seam.

    python examples/module/python_loss.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def mc_hinge_grad(scores, labels):
    """Crammer-Singer multiclass hinge gradient, pure numpy (the
    reference used numba; the math is identical)."""
    import numpy as np

    scores = scores.asnumpy()
    labels = labels.asnumpy().astype(int)
    n, _ = scores.shape
    grad = np.zeros_like(scores)
    for i in range(n):
        score = 1 + scores[i] - scores[i, labels[i]]
        score[labels[i]] = 0
        ind_pred = score.argmax()
        if score[ind_pred] > 0:
            grad[i, labels[i]] -= 1
            grad[i, ind_pred] += 1
    return grad / n


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=4)
    args = p.parse_args()

    import numpy as np
    import mxnet_tpu as mx

    np.random.seed(0)
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=128)
    act1 = mx.sym.Activation(fc1, name="relu1", act_type="relu")
    fc2 = mx.sym.FullyConnected(act1, name="fc2", num_hidden=10)

    mlp = mx.mod.Module(fc2, label_names=[], context=mx.cpu())
    loss = mx.mod.PythonLossModule(grad_func=mc_hinge_grad)
    mod = mx.mod.SequentialModule()
    mod.add(mlp).add(loss, take_labels=True, auto_wiring=True)

    X, y = mx.test_utils.synthetic_digits(2048, flat=True)
    it = mx.io.NDArrayIter(X, y.astype(np.float32), batch_size=64,
                           shuffle=True, label_name="softmax_label")
    mod.fit(it, num_epoch=args.epochs,
            optimizer_params={"learning_rate": 0.2, "momentum": 0.9},
            initializer=mx.initializer.Xavier(),
            eval_metric=mx.metric.create("acc"))
    it.reset()
    m = mx.metric.create("acc")
    mod.score(it, m)
    acc = m.get()[1]
    print("python-loss (numpy hinge) acc %.3f" % acc)
    if acc < 0.9:
        raise SystemExit("hinge training failed — host gradient not "
                         "reaching the tower?")
    print("python_loss OK")


if __name__ == "__main__":
    main()
