#!/usr/bin/env python
"""Stochastic-depth residual training (reference example/stochastic-depth).

The reference implements Huang et al.'s stochastic depth by wrapping each
residual block in a module that flips a Bernoulli coin per batch and skips
the block's compute when it dies, scaling by the survival rate at test
time (reference example/stochastic-depth/sd_module.py, sd_mnist.py). Under
XLA the idiomatic form is data-dependent *values*, not Python control
flow: each block's gate is an extra scalar input stream drawn per batch on
the host, the graph computes ``x + gate * block(x)``, and a dead gate
makes XLA's multiply-by-zero the skip. Linearly-decayed survival
probabilities per depth, train-time sampling vs test-time expectation,
accuracy asserted on held-out data.

    python examples/stochastic-depth/sd_mnist.py --steps 120
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

NUM_CLASS = 4
NUM_BLOCKS = 3


def sd_net():
    """Tiny residual conv net; block i survives with prob p_i and its
    output is weighted by the per-batch gate input ``gate<i>``."""
    import mxnet_tpu as mx

    x = mx.sym.Convolution(mx.sym.Variable("data"), kernel=(3, 3),
                           pad=(1, 1), num_filter=16, name="stem")
    x = mx.sym.Activation(x, act_type="relu")
    for i in range(NUM_BLOCKS):
        gate = mx.sym.Variable("gate%d" % i)
        b = mx.sym.Convolution(x, kernel=(3, 3), pad=(1, 1), num_filter=16,
                               name="block%d_conv" % i)
        b = mx.sym.BatchNorm(b, name="block%d_bn" % i)
        b = mx.sym.Activation(b, act_type="relu")
        x = x + mx.sym.broadcast_mul(
            b, mx.sym.Reshape(gate, shape=(1, 1, 1, 1)))
    x = mx.sym.Pooling(x, global_pool=True, pool_type="avg", kernel=(1, 1))
    x = mx.sym.FullyConnected(mx.sym.Flatten(x), num_hidden=NUM_CLASS,
                              name="fc")
    return mx.sym.SoftmaxOutput(x, mx.sym.Variable("softmax_label"),
                                name="softmax")


def survival_probs():
    # linear decay 1.0 -> 0.5 with depth (stochastic-depth paper rule)
    return [1.0 - 0.5 * (i + 1) / NUM_BLOCKS for i in range(NUM_BLOCKS)]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=120)
    p.add_argument("--batch-size", type=int, default=32)
    args = p.parse_args()

    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.io import DataBatch, DataDesc

    rng = np.random.RandomState(0)
    # synthetic "digits": class = which quadrant holds the bright patch
    n = 1024
    x = rng.normal(0, 0.3, (n, 1, 16, 16)).astype(np.float32)
    y = rng.randint(0, NUM_CLASS, n).astype(np.float32)
    for i in range(n):
        qr, qc = divmod(int(y[i]), 2)
        x[i, 0, qr * 8:qr * 8 + 8, qc * 8:qc * 8 + 8] += 1.0
    n_train = 768

    probs = survival_probs()
    gate_descs = [DataDesc("gate%d" % i, (1,)) for i in range(NUM_BLOCKS)]
    data_descs = [DataDesc("data", (args.batch_size, 1, 16, 16))] + gate_descs

    mod = mx.mod.Module(sd_net(),
                        data_names=["data"] + ["gate%d" % i
                                               for i in range(NUM_BLOCKS)])
    mod.bind(data_shapes=data_descs,
             label_shapes=[DataDesc("softmax_label", (args.batch_size,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 2e-3})

    def batch_of(idx, gates):
        return DataBatch(
            data=[mx.nd.array(x[idx])] + [mx.nd.array([g]) for g in gates],
            label=[mx.nd.array(y[idx])])

    alive_counts = np.zeros(NUM_BLOCKS)
    for step in range(args.steps):
        idx = rng.randint(0, n_train, args.batch_size)
        gates = [float(rng.rand() < p) for p in probs]  # train: sample
        alive_counts += gates
        mod.forward_backward(batch_of(idx, gates))
        mod.update()

    # test: expectation — gate_i = p_i (the paper's inference rule)
    correct = total = 0
    for s in range(n_train, n, args.batch_size):
        idx = np.arange(s, min(s + args.batch_size, n))
        if len(idx) < args.batch_size:
            break
        mod.forward(batch_of(idx, probs), is_train=False)
        pred = mod.get_outputs()[0].asnumpy().argmax(1)
        correct += int((pred == y[idx]).sum())
        total += len(idx)
    acc = correct / total
    print("stochastic-depth: survival probs %s, train-time alive rates %s"
          % (np.round(probs, 2), np.round(alive_counts / args.steps, 2)))
    print("held-out accuracy %.3f" % acc)
    assert acc > 0.9, acc
    print("stochastic-depth OK")


if __name__ == "__main__":
    main()
