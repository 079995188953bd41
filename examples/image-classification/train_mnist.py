#!/usr/bin/env python
"""Train an MLP or LeNet on MNIST with Module.fit.

Analogue of the reference's example/image-classification/train_mnist.py
(BASELINE config 1). Uses the MNISTIter if the idx/ubyte files are present
(``--data-dir``); otherwise falls back to a synthetic digits-like dataset
so the script is runnable anywhere.

    python examples/image-classification/train_mnist.py --network mlp \
        --num-epochs 10 --lr 0.1
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def get_iters(args):
    import numpy as np
    import mxnet_tpu as mx

    flat = args.network == "mlp"
    train_img = os.path.join(args.data_dir, "train-images-idx3-ubyte")
    if os.path.exists(train_img):
        train = mx.io.MNISTIter(
            image=train_img,
            label=os.path.join(args.data_dir, "train-labels-idx1-ubyte"),
            batch_size=args.batch_size, shuffle=True, flat=flat)
        val = mx.io.MNISTIter(
            image=os.path.join(args.data_dir, "t10k-images-idx3-ubyte"),
            label=os.path.join(args.data_dir, "t10k-labels-idx1-ubyte"),
            batch_size=args.batch_size, shuffle=False, flat=flat)
        return train, val
    # synthetic fallback: the shared 10-gaussian-blob task
    # (mx.test_utils.synthetic_digits — same definition the CI
    # convergence bars are calibrated on)
    n = 4096
    X, y = mx.test_utils.synthetic_digits(n, flat=flat)
    split = n * 7 // 8
    train = mx.io.NDArrayIter(X[:split], y[:split].astype(np.float32),
                              batch_size=args.batch_size, shuffle=True,
                              label_name="softmax_label")
    val = mx.io.NDArrayIter(X[split:], y[split:].astype(np.float32),
                            batch_size=args.batch_size,
                            label_name="softmax_label")
    return train, val


def main():
    import logging
    logging.basicConfig(level=logging.INFO)
    p = argparse.ArgumentParser()
    p.add_argument("--network", default="mlp", choices=["mlp", "lenet"])
    p.add_argument("--data-dir", default="mnist_data")
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--num-epochs", type=int, default=5)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--kvstore", default=None)
    p.add_argument("--model-prefix", default=None)
    p.add_argument("--load-epoch", type=int, default=None)
    args = p.parse_args()

    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import models

    np.random.seed(0)  # deterministic param init (CI quality bars)
    train, val = get_iters(args)
    sym = models.get_symbol(args.network, num_classes=10)
    dev = (mx.Context("tpu", 0) if jax.default_backend() != "cpu"
           else mx.cpu())

    arg_params = aux_params = None
    begin_epoch = 0
    if args.model_prefix and args.load_epoch is not None:
        sym, arg_params, aux_params = mx.model.load_checkpoint(
            args.model_prefix, args.load_epoch)
        begin_epoch = args.load_epoch

    mod = mx.mod.Module(sym, context=dev)
    cbs = [mx.callback.Speedometer(args.batch_size, 50)]
    epoch_cbs = ([mx.callback.do_checkpoint(args.model_prefix)]
                 if args.model_prefix else None)
    mod.fit(train, eval_data=val, num_epoch=args.num_epochs,
            arg_params=arg_params, aux_params=aux_params,
            begin_epoch=begin_epoch,
            optimizer="sgd",
            optimizer_params={"learning_rate": args.lr, "momentum": 0.9},
            initializer=mx.initializer.Xavier(),
            batch_end_callback=cbs, epoch_end_callback=epoch_cbs,
            kvstore=args.kvstore)
    print("final validation:", mod.score(val, mx.metric.create("acc")))


if __name__ == "__main__":
    main()
