#!/usr/bin/env python
"""How-to: watch per-op tensors during training (reference
example/python-howto/monitor_weights.py) — install a Monitor with a
custom stat (norm/sqrt(size)) and print activations/weights/gradients
every N batches.

    python examples/python-howto/monitor_weights.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main():
    import numpy as np
    import mxnet_tpu as mx

    np.random.seed(0)

    def norm_stat(d):
        return mx.nd.norm(d) / np.sqrt(d.size)

    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=32)
    act1 = mx.sym.Activation(fc1, name="relu1", act_type="relu")
    fc2 = mx.sym.FullyConnected(act1, name="fc2", num_hidden=10)
    mlp = mx.sym.SoftmaxOutput(fc2, name="softmax")

    X, y = mx.test_utils.synthetic_digits(256, flat=True)
    it = mx.io.NDArrayIter(X, y.astype(np.float32), batch_size=64,
                           label_name="softmax_label")
    mod = mx.mod.Module(mlp, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    mon = mx.Monitor(1, norm_stat)
    mod.install_monitor(mon)

    tapped = 0
    for batch in it:
        mon.tic()
        mod.forward_backward(batch)
        mod.update()
        results = mon.toc()
        for n, k, v in results:
            print("Batch: %7d %30s %s" % (n, k, v))
        tapped += len(results)
    assert tapped > 0, "monitor produced no stats"
    names = [n for _, n, _ in results]
    assert any("fc1" in n for n in names), names
    print("monitor_weights OK: %d stats tapped over the epoch" % tapped)


if __name__ == "__main__":
    main()
