#!/usr/bin/env python
"""Torch tensor functions as graph operators.

Analogue of the reference's example/torch/torch_function.py (mx.th.abs /
cdiv tensor math on mx NDArrays). The plugin's function_op wraps any pure
torch function as a Custom op with torch-autograd backward
(mxnet_tpu/torch.py), so torch's math composes into mx graphs with exact
gradients.

    python examples/torch/torch_function.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main():
    import numpy as np
    try:
        import torch as th
    except ImportError:
        raise SystemExit("torch_function example requires torch (CPU build)")
    import mxnet_tpu as mx
    from mxnet_tpu import autograd

    rng = np.random.RandomState(0)
    x = rng.randn(2, 2).astype(np.float32)

    # the reference's demo ops: abs and elementwise division
    mx.torch.function_op(th.abs, "th_abs")
    mx.torch.function_op(lambda a, b: a / b, "th_cdiv", n_inputs=2)

    xa = mx.nd.array(x)
    print("x =\n%s" % xa.asnumpy())
    y = mx.nd.Custom(xa, op_type="th_abs")
    print("th.abs(x) =\n%s" % y.asnumpy())
    np.testing.assert_allclose(y.asnumpy(), np.abs(x), rtol=1e-6)

    ones = mx.nd.array(np.ones((2, 2), np.float32))
    twos = mx.nd.array(2 * np.ones((2, 2), np.float32))
    q = mx.nd.Custom(ones, twos, op_type="th_cdiv")
    print("th.cdiv(1, 2) =\n%s" % q.asnumpy())
    np.testing.assert_allclose(q.asnumpy(), 0.5 * np.ones((2, 2)))

    # gradients flow torch -> mx: d/dx sum(abs(x)) = sign(x)
    xa.attach_grad()
    with autograd.record():
        z = mx.nd.Custom(xa, op_type="th_abs").sum()
    z.backward()
    np.testing.assert_allclose(xa.grad.asnumpy(), np.sign(x), rtol=1e-6)
    print("gradient check (sign(x)) passed")
    print("torch_function OK")


if __name__ == "__main__":
    main()
