#!/usr/bin/env python
"""Bidirectional-LSTM sequence sorting (reference example/bi-lstm-sort).

The task: given a sequence of digit tokens, emit the SAME tokens in
sorted order — a pure sequence-to-sequence transduction that a
unidirectional model cannot solve (position t of the output depends on
the whole input), which is exactly what ``mx.rnn.BidirectionalCell``
exists for. Per-position softmax over the vocabulary, trained with
Module.fit on synthetic data.

    python examples/bi-lstm-sort/sort_io.py --epochs 4
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def make_data(n, seq_len, vocab, seed=0):
    import numpy as np

    rng = np.random.RandomState(seed)
    x = rng.randint(1, vocab, (n, seq_len)).astype(np.float32)
    y = np.sort(x, axis=1)
    return x, y


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=6)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--seq-len", type=int, default=6)
    p.add_argument("--vocab", type=int, default=10)
    p.add_argument("--num-hidden", type=int, default=32)
    p.add_argument("--num-embed", type=int, default=16)
    p.add_argument("--lr", type=float, default=0.02)
    args = p.parse_args()

    import numpy as np
    import jax
    import mxnet_tpu as mx

    x, y = make_data(1024, args.seq_len, args.vocab)
    train = mx.io.NDArrayIter(x, y, batch_size=args.batch_size,
                              label_name="softmax_label")

    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    embed = mx.sym.Embedding(data, input_dim=args.vocab,
                             output_dim=args.num_embed, name="embed")
    bi = mx.rnn.BidirectionalCell(
        mx.rnn.LSTMCell(num_hidden=args.num_hidden, prefix="l_"),
        mx.rnn.LSTMCell(num_hidden=args.num_hidden, prefix="r_"))
    outputs, _ = bi.unroll(args.seq_len, inputs=embed, merge_outputs=True)
    pred = mx.sym.Reshape(outputs, shape=(-1, 2 * args.num_hidden))
    pred = mx.sym.FullyConnected(pred, num_hidden=args.vocab, name="pred")
    lab = mx.sym.Reshape(label, shape=(-1,))
    net = mx.sym.SoftmaxOutput(pred, label=lab, name="softmax")

    dev = (mx.Context("tpu", 0) if jax.default_backend() != "cpu"
           else mx.cpu())
    mod = mx.mod.Module(net, context=dev)
    acc = mx.metric.Accuracy()
    mod.fit(train, num_epoch=args.epochs, eval_metric=acc,
            optimizer="adam", optimizer_params={"learning_rate": args.lr},
            initializer=mx.initializer.Xavier())
    train.reset()
    acc.reset()
    mod.score(train, acc)
    name, val = acc.get()
    print("bi-lstm-sort OK: per-position %s %.3f" % (name, val))
    assert val > 0.7, val


if __name__ == "__main__":
    main()
