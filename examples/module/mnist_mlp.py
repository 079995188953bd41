#!/usr/bin/env python
"""Module API walkthrough at three levels (reference example/module/
mnist_mlp.py): the intermediate API (explicit forward/backward/update/
metric loop), the high-level API (Module.fit), and inference
(predict/score) — same MLP, same data, all three agreeing.

    python examples/module/mnist_mlp.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def build_mlp():
    import mxnet_tpu as mx

    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=128)
    act1 = mx.sym.Activation(fc1, name="relu1", act_type="relu")
    fc2 = mx.sym.FullyConnected(act1, name="fc2", num_hidden=64)
    act2 = mx.sym.Activation(fc2, name="relu2", act_type="relu")
    fc3 = mx.sym.FullyConnected(act2, name="fc3", num_hidden=10)
    return mx.sym.SoftmaxOutput(fc3, name="softmax")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--batch-size", type=int, default=100)
    args = p.parse_args()

    import numpy as np
    import mxnet_tpu as mx

    np.random.seed(0)
    X, y = mx.test_utils.synthetic_digits(4096, flat=True)
    split = len(X) * 7 // 8
    train = mx.io.NDArrayIter(X[:split], y[:split].astype(np.float32),
                              batch_size=args.batch_size, shuffle=True,
                              label_name="softmax_label")
    val = mx.io.NDArrayIter(X[split:], y[split:].astype(np.float32),
                            batch_size=args.batch_size,
                            label_name="softmax_label")

    # ---- intermediate-level API: the explicit training loop ----------
    mod = mx.mod.Module(build_mlp(), context=mx.cpu())
    mod.bind(data_shapes=train.provide_data,
             label_shapes=train.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    metric = mx.metric.create("acc")
    for epoch in range(args.epochs):
        train.reset()
        metric.reset()
        for batch in train:
            mod.forward(batch, is_train=True)
            mod.update_metric(metric, batch.label)
            mod.backward()
            mod.update()
        print("intermediate epoch %d: %s=%.4f"
              % (epoch, *metric.get()))
    val.reset()
    vm = mx.metric.create("acc")
    mod.score(val, vm)
    acc_mid = vm.get()[1]

    # ---- high-level API: Module.fit ----------------------------------
    train.reset()
    mod2 = mx.mod.Module(build_mlp(), context=mx.cpu())
    mod2.fit(train, eval_data=val, num_epoch=args.epochs,
             optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
             initializer=mx.initializer.Xavier())
    val.reset()
    vm2 = mx.metric.create("acc")
    mod2.score(val, vm2)
    acc_fit = vm2.get()[1]

    # ---- inference: predict returns per-batch outputs ---------------
    val.reset()
    preds = mod2.predict(val)
    assert preds.shape[1] == 10

    print("module-mlp intermediate acc %.3f, fit acc %.3f" % (acc_mid,
                                                              acc_fit))
    if min(acc_mid, acc_fit) < 0.95:
        raise SystemExit("walkthrough failed to converge")
    print("module mnist_mlp OK")


if __name__ == "__main__":
    main()
