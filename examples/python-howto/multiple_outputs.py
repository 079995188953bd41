#!/usr/bin/env python
"""How-to: multiple-output configurations (reference example/python-howto/
multiple_outputs.py) — Group an internal layer with the head, bind the
group, and read both outputs from one forward.

    python examples/python-howto/multiple_outputs.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main():
    import numpy as np
    import mxnet_tpu as mx

    net = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data=net, name="fc1", num_hidden=128)
    net = mx.sym.Activation(data=fc1, name="relu1", act_type="relu")
    net = mx.sym.FullyConnected(data=net, name="fc2", num_hidden=64)
    out = mx.sym.SoftmaxOutput(data=net, name="softmax")
    group = mx.sym.Group([fc1, out])
    print("group outputs:", group.list_outputs())
    assert group.list_outputs() == ["fc1_output", "softmax_output"]

    exe = group.simple_bind(mx.cpu(), grad_req="null", data=(2, 20),
                            softmax_label=(2,))
    exe.arg_dict["data"][:] = np.random.RandomState(0).randn(2, 20)
    exe.forward(is_train=False)
    assert exe.outputs[0].shape == (2, 128)   # fc1 tap
    assert exe.outputs[1].shape == (2, 64)    # softmax over fc2
    np.testing.assert_allclose(exe.outputs[1].asnumpy().sum(1),
                               np.ones(2), rtol=1e-5)
    print("multiple_outputs OK: fc1 tap %s + softmax %s from one forward"
          % (exe.outputs[0].shape, exe.outputs[1].shape))


if __name__ == "__main__":
    main()
