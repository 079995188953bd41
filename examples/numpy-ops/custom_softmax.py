#!/usr/bin/env python
"""Train through a numpy-implemented custom loss op (reference
example/numpy-ops).

The reference's custom_softmax.py defines the softmax loss entirely in
Python/numpy via `mx.operator.CustomOp` — no gradient from the engine
(need_top_grad=False), forward computes softmax, backward writes
``prob - one_hot`` — registers it, and trains an MLP with it as the head
(reference example/numpy-ops/custom_softmax.py:8-45,
weighted_logistic_regression.py). Same here: the host-side numpy op runs
inside the jitted graph through the pure_callback custom-op bridge, and
an MLP trains to high accuracy through it. (Requires a runtime with host
send/recv callbacks: a TPU host or the CPU backend.)

    python examples/numpy-ops/custom_softmax.py --epochs 6
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402


@mx.operator.register("numpy_softmax")
class NumpySoftmaxProp(mx.operator.CustomOpProp):
    def __init__(self):
        # loss layer: the head gradient is defined by the op itself
        super().__init__(need_top_grad=False)

    def list_arguments(self):
        return ["data", "label"]

    def list_outputs(self):
        return ["output"]

    def infer_shape(self, in_shape):
        data_shape = in_shape[0]
        label_shape = (in_shape[0][0],)
        return [data_shape, label_shape], [data_shape], []

    def create_operator(self, ctx, shapes, dtypes):
        return NumpySoftmax()


class NumpySoftmax(mx.operator.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        x = in_data[0].asnumpy()
        x = x - x.max(axis=1, keepdims=True)
        e = np.exp(x)
        self.assign(out_data[0], req[0], e / e.sum(axis=1, keepdims=True))

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        prob = out_data[0].asnumpy().copy()
        label = in_data[1].asnumpy().astype(int)
        prob[np.arange(label.size), label] -= 1.0
        self.assign(in_grad[0], req[0], prob / label.size)
        self.assign(in_grad[1], req[1], np.zeros_like(in_data[1].asnumpy()))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=6)
    p.add_argument("--batch-size", type=int, default=64)
    args = p.parse_args()

    rng = np.random.RandomState(0)
    centers = rng.normal(0, 3.0, (4, 16)).astype(np.float32)
    y = rng.randint(0, 4, 1536).astype(np.float32)
    x = (centers[y.astype(int)]
         + rng.normal(0, 1.0, (1536, 16))).astype(np.float32)

    it = mx.io.NDArrayIter(x[:1024], y[:1024], batch_size=args.batch_size,
                           shuffle=True)
    val = mx.io.NDArrayIter(x[1024:], y[1024:], batch_size=args.batch_size)

    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=4, name="fc2")
    net = mx.sym.Custom(data=h, label=mx.sym.Variable("softmax_label"),
                        op_type="numpy_softmax", name="softmax")

    mod = mx.mod.Module(net)
    mod.fit(it, num_epoch=args.epochs, optimizer="sgd",
            optimizer_params={"learning_rate": 0.2, "momentum": 0.9},
            initializer=mx.initializer.Xavier(), eval_metric="acc")
    acc = dict(mod.score(val, "acc"))["accuracy"]
    print("numpy-softmax custom op: val accuracy %.3f" % acc)
    assert acc > 0.9, acc
    print("numpy-ops OK")


if __name__ == "__main__":
    main()
