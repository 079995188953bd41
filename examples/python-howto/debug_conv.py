#!/usr/bin/env python
"""How-to: poke a single operator with a hand-made batch (reference
example/python-howto/debug_conv.py) — bind one Convolution, feed ones,
inspect the raw output.

    python examples/python-howto/debug_conv.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


class SimpleData(object):
    def __init__(self, data):
        self.data = data
        self.label = []
        self.pad = 0


def main():
    import numpy as np
    import mxnet_tpu as mx

    data_shape = (1, 3, 5, 5)
    data = mx.sym.Variable("data")
    conv = mx.sym.Convolution(data=data, kernel=(3, 3), pad=(1, 1),
                              stride=(1, 1), num_filter=1)
    mod = mx.mod.Module(conv, label_names=[])
    mod.bind(data_shapes=[("data", data_shape)])
    mod.init_params(mx.initializer.One())
    mod.forward(SimpleData([mx.nd.ones(data_shape)]), is_train=False)
    res = mod.get_outputs()[0].asnumpy()
    print(res)
    # all-ones weights over all-ones input: each output = #taps in window
    assert res.shape == (1, 1, 5, 5)
    assert res[0, 0, 2, 2] == 3 * 3 * 3  # full 3x3x3 window interior
    assert res[0, 0, 0, 0] == 3 * 2 * 2  # corner sees 2x2 spatial taps
    print("debug_conv OK")


if __name__ == "__main__":
    main()
