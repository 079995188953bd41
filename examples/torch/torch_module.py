#!/usr/bin/env python
"""Hybrid torch/mxnet training: torch nn.Modules as graph operators.

Analogue of the reference's example/torch/torch_module.py (an MLP whose
layers are TorchModule ops trained through mx.model.FeedForward,
torch_module.cc). Here the torch plugin wraps torch.nn modules as Custom
ops (mxnet_tpu/torch.py module_op): forward runs torch on host inside the
jitted graph via the custom-op bridge, backward drives torch autograd —
torch-side parameters train with a torch optimizer stepping alongside the
mx loop, exactly the reference's division of labor (torch weights belong
to torch).

    python examples/torch/torch_module.py --steps 40
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=40)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--lr", type=float, default=0.1)
    args = p.parse_args()

    import numpy as np
    try:
        import torch as th
    except ImportError:
        raise SystemExit("torch_module example requires torch (CPU build)")
    import mxnet_tpu as mx
    from mxnet_tpu import autograd

    th.manual_seed(0)
    np.random.seed(0)
    # the reference's MLP: Linear(784,128)/ReLU/Linear(128,64)/ReLU/
    # Linear(64,10) — as ONE wrapped torch module
    mlp = th.nn.Sequential(
        th.nn.Linear(784, 128), th.nn.ReLU(),
        th.nn.Linear(128, 64), th.nn.ReLU(),
        th.nn.Linear(64, 10))
    mx.torch.module_op(mlp, "torch_mlp")
    opt = th.optim.SGD(mlp.parameters(), lr=args.lr, momentum=0.9)

    X, y = mx.test_utils.synthetic_digits(2048, flat=True)
    losses = []
    for step in range(args.steps):
        i = (step * args.batch) % (len(X) - args.batch)
        xb = mx.nd.array(X[i:i + args.batch])
        # mx autograd needs a marked root; the input grad is discarded —
        # the gradients that matter land on the torch parameters via the
        # custom op's torch.autograd.backward
        xb.attach_grad()
        yb = y[i:i + args.batch]
        onehot = np.zeros((args.batch, 10), np.float32)
        onehot[np.arange(args.batch), yb] = 1.0
        opt.zero_grad()
        with autograd.record():
            logits = mx.nd.Custom(xb, op_type="torch_mlp")
            logp = mx.nd.log_softmax(logits, axis=-1)
            loss = -(logp * mx.nd.array(onehot)).sum() / args.batch
        loss.backward()   # mx autograd -> custom-op bridge -> torch .grad
        # backward dispatches asynchronously; the torch .grad accumulation
        # happens inside that program's host callback. Fence on the input
        # grad (an output of the same program) before opt.step() mutates
        # the torch parameters in place, or step races the callback.
        xb.grad.wait_to_read()
        opt.step()        # torch updates its own weights
        losses.append(float(loss.asnumpy()))

    # accuracy with the trained torch weights, evaluated through mx
    logits = mx.nd.Custom(mx.nd.array(X[:512]), op_type="torch_mlp")
    acc = float((logits.asnumpy().argmax(1) == y[:512]).mean())
    print("torch-module MLP: loss %.4f -> %.4f, acc %.3f"
          % (np.mean(losses[:3]), np.mean(losses[-3:]), acc))
    if acc < 0.9:
        raise SystemExit("hybrid training failed to converge")
    print("torch_module OK")


if __name__ == "__main__":
    main()
