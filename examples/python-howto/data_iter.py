#!/usr/bin/env python
"""How-to: write a custom DataIter (reference example/python-howto/
data_iter.py) — subclass mx.io.DataIter, declare provide_data/
provide_label, yield DataBatch, and feed it straight into Module.fit.

    python examples/python-howto/data_iter.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main():
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.io import DataBatch, DataDesc, DataIter

    np.random.seed(0)

    class XorIter(DataIter):
        """Streams noisy XOR batches — generated on the fly, nothing
        materialized up front (the point of a custom iterator)."""

        def __init__(self, batch_size, n_batches):
            super().__init__(batch_size)
            self.n_batches = n_batches
            self._i = 0
            self._rng = np.random.RandomState(7)

        @property
        def provide_data(self):
            return [DataDesc("data", (self.batch_size, 2))]

        @property
        def provide_label(self):
            return [DataDesc("softmax_label", (self.batch_size,))]

        def reset(self):
            self._i = 0

        def next(self):
            if self._i >= self.n_batches:
                raise StopIteration
            self._i += 1
            bits = self._rng.randint(0, 2, (self.batch_size, 2))
            x = bits + 0.15 * self._rng.randn(self.batch_size, 2)
            y = (bits[:, 0] ^ bits[:, 1]).astype(np.float32)
            return DataBatch([mx.nd.array(x.astype(np.float32))],
                             [mx.nd.array(y)])

    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=16)
    net = mx.sym.Activation(net, act_type="tanh")
    net = mx.sym.FullyConnected(net, num_hidden=2)
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    it = XorIter(batch_size=64, n_batches=32)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=12,
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9},
            initializer=mx.initializer.Xavier())
    it.reset()
    m = mx.metric.create("acc")
    mod.score(it, m)
    acc = m.get()[1]
    print("custom-iter XOR acc %.3f" % acc)
    if acc < 0.95:
        raise SystemExit("custom iterator training failed")
    print("data_iter OK")


if __name__ == "__main__":
    main()
