#!/usr/bin/env python
"""Caffe layers inside an mxnet_tpu network.

Analogue of the reference's example/caffe/caffe_net.py (an MLP whose
layers are CaffeOp prototxt ops trained through mx, plugin/caffe). Here
the caffe plugin (mxnet_tpu/plugins/caffe.py) hosts a pycaffe Net for a
user-written prototxt layer inside the Custom-op bridge: forward/backward
marshal blobs through pycaffe, so a caffe layer drops into an mx graph.

Without pycaffe installed (this CI image), the example runs against the
bundled pycaffe-CONTRACT stub (a ReLU layer implementing the exact
pycaffe surface the plugin touches) so the plugin's real marshaling code
executes either way — the same seam tests/test_plugins.py pins.

    python examples/caffe/caffe_net.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def _install_pycaffe_stub():
    """Minimal pycaffe contract: caffe.Net(path, phase) with .blobs of
    .data/.diff/.reshape, forward(), backward() — a host-side ReLU."""
    import collections
    import re
    import types

    import numpy as np

    class _Blob:
        def __init__(self, shape):
            self.data = np.zeros(shape, np.float32)
            self.diff = np.zeros(shape, np.float32)

        def reshape(self, *shape):
            self.data = np.zeros(shape, np.float32)
            self.diff = np.zeros(shape, np.float32)

    class _Net:
        def __init__(self, path, phase):
            text = open(path).read()
            assert 'type: "ReLU"' in text, (
                "the stub implements ReLU only; install pycaffe for "
                "other layer types")
            dims = [int(d) for d in re.findall(r"dim:\s*(\d+)", text)]
            top = re.search(r'top:\s*"(\w+)"', text).group(1)
            self.blobs = collections.OrderedDict(
                [("data", _Blob(tuple(dims))), (top, _Blob(tuple(dims)))])
            self._top = top

        def forward(self):
            import numpy as np
            self.blobs[self._top].reshape(*self.blobs["data"].data.shape)
            self.blobs[self._top].data = np.maximum(
                self.blobs["data"].data, 0)

        def backward(self):
            self.blobs["data"].diff = (
                self.blobs[self._top].diff
                * (self.blobs["data"].data > 0))

    fake = types.ModuleType("caffe")
    fake.Net = _Net
    fake.TEST = 1
    sys.modules["caffe"] = fake
    return "pycaffe-contract stub"


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--lr", type=float, default=0.05)
    args = p.parse_args()

    import numpy as np
    import mxnet_tpu as mx

    try:
        import caffe
        if not hasattr(caffe, "Net"):
            # this very directory is importable as a namespace package
            # named "caffe" — that is not pycaffe
            raise ImportError("not pycaffe")
        backend = "pycaffe"
    except ImportError:
        backend = _install_pycaffe_stub()

    np.random.seed(0)
    # the reference MLP with caffe activations between mx FC layers:
    # FC -> CaffeOp(ReLU) -> FC -> CaffeOp(ReLU) -> FC -> SoftmaxOutput
    mx.plugins.caffe.layer_op(
        'layer { name: "act1" type: "ReLU" bottom: "data" top: "act1" }',
        "caffe_act1", input_shape=(args.batch, args.hidden))
    mx.plugins.caffe.layer_op(
        'layer { name: "act2" type: "ReLU" bottom: "data" top: "act2" }',
        "caffe_act2", input_shape=(args.batch, args.hidden))

    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=args.hidden, name="fc1")
    h = mx.sym.Custom(h, op_type="caffe_act1")
    h = mx.sym.FullyConnected(h, num_hidden=args.hidden, name="fc2")
    h = mx.sym.Custom(h, op_type="caffe_act2")
    h = mx.sym.FullyConnected(h, num_hidden=10, name="fc3")
    net = mx.sym.SoftmaxOutput(h, name="softmax")

    X, y = mx.test_utils.synthetic_digits(2048, flat=True)
    it = mx.io.NDArrayIter(X, y.astype(np.float32), batch_size=args.batch,
                           shuffle=True, label_name="softmax_label")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": args.lr,
                                         "momentum": 0.9})
    metric = mx.metric.Accuracy()
    steps = 0
    while steps < args.steps:
        it.reset()
        for batch in it:
            mod.forward_backward(batch)
            mod.update()
            steps += 1
            if steps >= args.steps:
                break
    it.reset()
    mod.score(it, metric)
    acc = metric.get()[1]
    print("caffe-net MLP (%s): acc %.3f after %d steps"
          % (backend, acc, steps))
    if acc < 0.9:
        raise SystemExit("caffe-net failed to converge")
    print("caffe_net OK")


if __name__ == "__main__":
    main()
