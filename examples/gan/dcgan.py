#!/usr/bin/env python
"""DCGAN: alternating generator/discriminator training with two Modules.

Analogue of the reference's example/gan/dcgan.py: generator made of
Deconvolution+BatchNorm+Activation, discriminator of Convolution+LeakyReLU;
the two Modules train alternately with the discriminator's input gradient
flowing back into the generator (`inputs_need_grad=True` + manual
backward), exactly the reference's training pattern.

    python examples/gan/dcgan.py --epochs 1
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def make_generator(ngf, z_dim):
    import mxnet_tpu as mx
    z = mx.sym.Variable("rand")
    g = mx.sym.Deconvolution(z, num_filter=ngf * 2, kernel=(4, 4),
                             name="g1")
    g = mx.sym.BatchNorm(g, name="gbn1")
    g = mx.sym.Activation(g, act_type="relu")
    g = mx.sym.Deconvolution(g, num_filter=ngf, kernel=(4, 4), stride=(2, 2),
                             pad=(1, 1), name="g2")
    g = mx.sym.BatchNorm(g, name="gbn2")
    g = mx.sym.Activation(g, act_type="relu")
    g = mx.sym.Deconvolution(g, num_filter=1, kernel=(4, 4), stride=(2, 2),
                             pad=(1, 1), name="g3")
    return mx.sym.Activation(g, act_type="tanh", name="gout")


def make_discriminator(ndf):
    import mxnet_tpu as mx
    data = mx.sym.Variable("data")
    d = mx.sym.Convolution(data, num_filter=ndf, kernel=(4, 4), stride=(2, 2),
                           pad=(1, 1), name="d1")
    d = mx.sym.LeakyReLU(d, act_type="leaky", slope=0.2)
    d = mx.sym.Convolution(d, num_filter=ndf * 2, kernel=(4, 4), stride=(2, 2),
                           pad=(1, 1), name="d2")
    d = mx.sym.BatchNorm(d, name="dbn2")
    d = mx.sym.LeakyReLU(d, act_type="leaky", slope=0.2)
    d = mx.sym.Flatten(d)
    d = mx.sym.FullyConnected(d, num_hidden=1, name="d3")
    return mx.sym.LogisticRegressionOutput(d, mx.sym.Variable("label"),
                                           name="dloss")


def main():
    import logging
    logging.basicConfig(level=logging.INFO)
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--z-dim", type=int, default=16)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--batches", type=int, default=30)
    p.add_argument("--lr", type=float, default=0.02)
    args = p.parse_args()

    import numpy as np
    import jax
    import mxnet_tpu as mx

    B, Z = args.batch_size, args.z_dim
    dev = (mx.Context("tpu", 0) if jax.default_backend() != "cpu"
           else mx.cpu())
    rng = np.random.RandomState(0)

    gen = mx.mod.Module(make_generator(8, Z), data_names=("rand",),
                        label_names=None, context=dev)
    gen.bind(data_shapes=[("rand", (B, Z, 1, 1))], label_shapes=None,
             inputs_need_grad=False)
    gen.init_params(mx.initializer.Normal(0.02))
    gen.init_optimizer(kvstore=None, optimizer="adam",
                       optimizer_params={"learning_rate": args.lr,
                                         "beta1": 0.5})

    dis = mx.mod.Module(make_discriminator(8), label_names=("label",),
                        context=dev)
    dis.bind(data_shapes=[("data", (B, 1, 16, 16))],
             label_shapes=[("label", (B, 1))], inputs_need_grad=True)
    dis.init_params(mx.initializer.Normal(0.02))
    dis.init_optimizer(kvstore=None, optimizer="adam",
                       optimizer_params={"learning_rate": args.lr,
                                         "beta1": 0.5})

    # "real" data: smooth blobs the generator must learn to imitate
    def real_batch():
        c = rng.randint(4, 12, (B, 2))
        yy, xx = np.mgrid[0:16, 0:16]
        img = np.exp(-(((xx[None] - c[:, 0, None, None]) ** 2
                        + (yy[None] - c[:, 1, None, None]) ** 2) / 8.0))
        return (img[:, None] * 2 - 1).astype(np.float32)

    ones = mx.nd.array(np.ones((B, 1), np.float32))
    zeros = mx.nd.array(np.zeros((B, 1), np.float32))
    metric = mx.metric.create("acc")

    for epoch in range(args.epochs):
        metric.reset()
        for it in range(args.batches):
            z = mx.nd.array(rng.randn(B, Z, 1, 1).astype(np.float32))
            gen.forward(mx.io.DataBatch(data=[z], label=[]), is_train=True)
            fake = gen.get_outputs()[0]

            # D step: real=1, fake=0
            dis.forward_backward(mx.io.DataBatch(data=[fake], label=[zeros]))
            dis.update()
            dis.forward_backward(mx.io.DataBatch(
                data=[mx.nd.array(real_batch())], label=[ones]))
            dis.update()

            # G step: fool D (label=1), push D's input grad through G
            dis.forward(mx.io.DataBatch(data=[fake], label=[ones]),
                        is_train=True)
            dis.backward()
            d_in_grad = dis.get_input_grads()[0]
            gen.backward([d_in_grad])
            gen.update()

            out = dis.get_outputs()[0]
            pred = (out.asnumpy() > 0.5).astype(np.float32)
            # track how often D is fooled after the G step
            metric.update([ones], [mx.nd.array(np.concatenate(
                [1 - pred, pred], axis=1))])
        logging.info("epoch %d: D-fooled-rate %s", epoch,
                     metric.get_name_value())
    print("dcgan alternating training ran %d batches OK"
          % (args.epochs * args.batches))


if __name__ == "__main__":
    main()
