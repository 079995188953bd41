#!/usr/bin/env python
"""Model-parallel LSTM: layers placed on different devices.

Analogue of the reference's example/model-parallel-lstm/ (SURVEY §2.2
"Model parallelism"): there, symbol variables are tagged with ``ctx_group``
under AttrScope and ``bind(group2ctx=...)`` maps groups onto GPUs, with the
engine pipelining the per-device work. Here the same AttrScope tagging
flows into mesh shardings: each layer group is placed on a device of a
``jax.sharding.Mesh``, and XLA overlaps the per-stage compute exactly as
the reference's dataflow engine did (SURVEY §7 translation table).

Run on a virtual mesh without hardware:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python examples/model-parallel-lstm/lstm_model_parallel.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main():
    import logging
    logging.basicConfig(level=logging.INFO)
    p = argparse.ArgumentParser()
    p.add_argument("--num-layers", type=int, default=4)
    p.add_argument("--num-hidden", type=int, default=64)
    p.add_argument("--seq-len", type=int, default=16)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--vocab", type=int, default=32)
    p.add_argument("--steps", type=int, default=10)
    args = p.parse_args()

    import numpy as np
    import jax
    import mxnet_tpu as mx

    n_dev = max(1, min(args.num_layers, len(jax.devices())))
    group2ctx = {"layer%d" % i: mx.Context(jax.default_backend(), i % n_dev)
                 for i in range(args.num_layers)}

    # build the stacked LSTM with each layer's params tagged to a ctx_group
    data = mx.sym.Variable("data")
    embed = mx.sym.Embedding(data, input_dim=args.vocab,
                             output_dim=args.num_hidden, name="embed")
    inputs = embed
    for i in range(args.num_layers):
        with mx.AttrScope(ctx_group="layer%d" % i):
            cell = mx.rnn.LSTMCell(num_hidden=args.num_hidden,
                                   prefix="lstm_l%d_" % i)
            inputs, _ = cell.unroll(args.seq_len, inputs=inputs,
                                    merge_outputs=True)
    pred = mx.sym.Reshape(inputs, shape=(-1, args.num_hidden))
    pred = mx.sym.FullyConnected(pred, num_hidden=args.vocab, name="pred")
    label = mx.sym.Reshape(mx.sym.Variable("softmax_label"), shape=(-1,))
    net = mx.sym.SoftmaxOutput(pred, label=label, name="softmax")

    exe = net.simple_bind(mx.cpu() if jax.default_backend() == "cpu"
                          else mx.Context("tpu", 0),
                          group2ctx=group2ctx,
                          data=(args.batch_size, args.seq_len),
                          softmax_label=(args.batch_size, args.seq_len))
    init = mx.initializer.Xavier()
    for n, a in exe.arg_dict.items():
        if n in ("data", "softmax_label"):
            continue
        init(mx.initializer.InitDesc(n), a)
    rng = np.random.RandomState(0)
    import jax.numpy as jnp
    exe.arg_dict["data"]._data = jnp.asarray(
        rng.randint(0, args.vocab, (args.batch_size, args.seq_len))
        .astype(np.float32))
    exe.arg_dict["softmax_label"]._data = jnp.asarray(
        rng.randint(0, args.vocab, (args.batch_size, args.seq_len))
        .astype(np.float32))

    for step in range(args.steps):
        exe.forward_backward()
        for n, g in exe.grad_dict.items():
            if n in ("data", "softmax_label"):
                continue
            exe.arg_dict[n]._data = exe.arg_dict[n]._data - 0.1 * g._data
    out = exe.outputs[0].asnumpy()
    print("ran %d model-parallel train steps over %d devices; out shape %s"
          % (args.steps, n_dev, out.shape))


if __name__ == "__main__":
    main()
