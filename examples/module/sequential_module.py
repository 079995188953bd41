#!/usr/bin/env python
"""SequentialModule walkthrough (reference example/module/
sequential_module.py): a network split into TWO Modules chained by a
container — module 1 computes features, module 2 the head — with
gradients flowing back across the seam (take_labels on the head,
auto_wiring of data shapes).

    python examples/module/sequential_module.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=3)
    args = p.parse_args()

    import numpy as np
    import mxnet_tpu as mx

    np.random.seed(0)
    # module 1: the feature tower
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=128)
    act1 = mx.sym.Activation(fc1, name="relu1", act_type="relu")
    mod1 = mx.mod.Module(act1, label_names=[], context=mx.cpu())

    # module 2: the classifier head (its own "data" = module 1's output)
    data2 = mx.sym.Variable("data")
    fc2 = mx.sym.FullyConnected(data2, name="fc2", num_hidden=64)
    act2 = mx.sym.Activation(fc2, name="relu2", act_type="relu")
    fc3 = mx.sym.FullyConnected(act2, name="fc3", num_hidden=10)
    softmax = mx.sym.SoftmaxOutput(fc3, name="softmax")
    mod2 = mx.mod.Module(softmax, context=mx.cpu())

    mod_seq = mx.mod.SequentialModule()
    mod_seq.add(mod1).add(mod2, take_labels=True, auto_wiring=True)

    X, y = mx.test_utils.synthetic_digits(2048, flat=True)
    it = mx.io.NDArrayIter(X, y.astype(np.float32), batch_size=64,
                           shuffle=True, label_name="softmax_label")
    mod_seq.fit(it, num_epoch=args.epochs,
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                initializer=mx.initializer.Xavier())
    it.reset()
    m = mx.metric.create("acc")
    mod_seq.score(it, m)
    acc = m.get()[1]
    print("sequential-module acc %.3f" % acc)
    if acc < 0.95:
        raise SystemExit("chained modules failed to converge — gradients "
                         "not flowing across the module seam?")
    print("sequential_module OK")


if __name__ == "__main__":
    main()
