#!/usr/bin/env python
"""FGSM adversarial examples (reference example/adversary): train a small
MLP, then perturb inputs along sign(dL/dx) and show accuracy collapse —
exercises input gradients (grad_req on data) through the executor.

    python examples/adversary/fgsm.py --epsilon 0.15
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epsilon", type=float, default=0.15)
    p.add_argument("--epochs", type=int, default=8)
    args = p.parse_args()

    import numpy as np
    import mxnet_tpu as mx

    rng = np.random.RandomState(0)
    X = rng.uniform(-1, 1, (800, 20)).astype(np.float32)
    W = rng.uniform(-1, 1, (20, 4)).astype(np.float32)
    y = np.argmax(X @ W, axis=1).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=64, shuffle=True,
                           label_name="softmax_label")

    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=64, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net)
    mod.fit(it, num_epoch=args.epochs, optimizer="adam",
            optimizer_params={"learning_rate": 0.01},
            initializer=mx.initializer.Xavier())
    it.reset()
    clean_acc = dict(mod.score(it, "acc"))["accuracy"]

    # rebind with grad on data (the adversary's executor)
    arg_params, aux_params = mod.get_params()
    arg_shapes = {"data": (800, 20), "softmax_label": (800,)}
    grad_req = {n: ("write" if n == "data" else "null")
                for n in net.list_arguments()}
    exe = net.simple_bind(mx.cpu(), grad_req=grad_req, **arg_shapes)
    exe.copy_params_from(arg_params, aux_params)
    exe.arg_dict["data"][:] = X
    exe.arg_dict["softmax_label"][:] = y
    exe.forward(is_train=True)
    exe.backward()
    g = exe.grad_dict["data"].asnumpy()
    X_adv = X + args.epsilon * np.sign(g)

    it_adv = mx.io.NDArrayIter(X_adv, y, batch_size=64,
                               label_name="softmax_label")
    adv_acc = dict(mod.score(it_adv, "acc"))["accuracy"]
    print("clean acc %.3f -> adversarial acc %.3f (eps=%.2f)"
          % (clean_acc, adv_acc, args.epsilon))
    assert clean_acc > 0.9 and adv_acc < clean_acc - 0.1, (clean_acc, adv_acc)
    print("fgsm OK")


if __name__ == "__main__":
    main()
