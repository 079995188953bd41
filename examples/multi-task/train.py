#!/usr/bin/env python
"""Multi-task training: one trunk, two heads (reference
example/multi-task): softmax classification + regression, trained
jointly through a Group symbol with per-head labels and a composite
metric.

    python examples/multi-task/train.py --epochs 8
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=8)
    p.add_argument("--batch-size", type=int, default=64)
    args = p.parse_args()

    import numpy as np
    import mxnet_tpu as mx

    rng = np.random.RandomState(0)
    X = rng.uniform(-1, 1, (800, 16)).astype(np.float32)
    Wc = rng.uniform(-1, 1, (16, 4)).astype(np.float32)
    y_cls = np.argmax(X @ Wc, axis=1).astype(np.float32)
    y_reg = (X ** 2).sum(axis=1, keepdims=True).astype(np.float32)

    it = mx.io.NDArrayIter({"data": X},
                           {"softmax_label": y_cls, "reg_label": y_reg},
                           batch_size=args.batch_size, shuffle=True)

    d = mx.sym.Variable("data")
    trunk = mx.sym.FullyConnected(d, num_hidden=64, name="trunk")
    trunk = mx.sym.Activation(trunk, act_type="relu")
    cls = mx.sym.FullyConnected(trunk, num_hidden=4, name="cls")
    cls = mx.sym.SoftmaxOutput(cls, mx.sym.Variable("softmax_label"),
                               name="softmax")
    reg = mx.sym.FullyConnected(trunk, num_hidden=1, name="reg")
    reg = mx.sym.LinearRegressionOutput(reg, mx.sym.Variable("reg_label"),
                                        grad_scale=0.1, name="linreg")
    net = mx.sym.Group([cls, reg])

    # per-head metric over the grouped outputs (the reference's
    # example/multi-task Multi_Accuracy pattern: a custom EvalMetric that
    # indexes specific outputs/labels)
    class MultiTaskMetric(mx.metric.EvalMetric):
        def __init__(self):
            super().__init__("multi", num=2)

        def update(self, labels, preds):
            cls_l = labels[0].asnumpy()
            cls_p = preds[0].asnumpy()
            self.sum_metric[0] += float((cls_p.argmax(1) == cls_l).sum())
            self.num_inst[0] += len(cls_l)
            reg_l = labels[1].asnumpy()
            reg_p = preds[1].asnumpy()
            self.sum_metric[1] += float(np.abs(reg_p - reg_l).sum())
            self.num_inst[1] += reg_l.size

    mod = mx.mod.Module(net, label_names=("softmax_label", "reg_label"))
    mod.fit(it, num_epoch=args.epochs, optimizer="adam",
            optimizer_params={"learning_rate": 0.01},
            initializer=mx.initializer.Xavier(),
            eval_metric=MultiTaskMetric())
    it.reset()
    vals = dict(mod.score(it, MultiTaskMetric()))
    acc, mae = vals["multi_0"], vals["multi_1"]
    print("multi-task: accuracy %.3f  reg MAE %.3f" % (acc, mae))
    assert acc > 0.85, acc
    print("multi-task OK")


if __name__ == "__main__":
    main()
