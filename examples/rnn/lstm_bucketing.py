#!/usr/bin/env python
"""LSTM language model with bucketed variable-length sequences.

Analogue of the reference's example/rnn/lstm_bucketing.py: a
``sym_gen(bucket_key)`` builds one unrolled LSTM per bucket and
BucketingModule shares parameter memory across buckets (the compile cache
keyed on padded shape replaces per-bucket executor sharing,
SURVEY §5.7). Trains on PTB if ``--data`` points at a tokenized text file,
else on a synthetic integer language.

    python examples/rnn/lstm_bucketing.py --num-epochs 2
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

BUCKETS = [8, 16, 24, 32]


def synthetic_sentences(vocab, n=2000, seed=0):
    import numpy as np
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        length = rng.randint(4, BUCKETS[-1] + 1)
        # a Markov-ish chain so the LM has something to learn
        s = [int(rng.randint(1, vocab))]
        for _ in range(length - 1):
            s.append((s[-1] * 31 + 7) % (vocab - 1) + 1
                     if rng.rand() < 0.8 else int(rng.randint(1, vocab)))
        out.append(s)
    return out


def main():
    import logging
    logging.basicConfig(level=logging.INFO)
    p = argparse.ArgumentParser()
    p.add_argument("--data", default=None, help="tokenized text, one sentence/line")
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--num-hidden", type=int, default=128)
    p.add_argument("--num-embed", type=int, default=64)
    p.add_argument("--num-layers", type=int, default=1)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--num-epochs", type=int, default=2)
    p.add_argument("--lr", type=float, default=0.1)
    args = p.parse_args()

    import jax
    import mxnet_tpu as mx

    if args.data and os.path.exists(args.data):
        sentences, vocab = mx.rnn.encode_sentences(
            [line.split() for line in open(args.data)])
        vocab_size = len(vocab) + 1
    else:
        sentences = synthetic_sentences(args.vocab)
        vocab_size = args.vocab

    # pad with 0 (tokens are 1..vocab-1) and ignore it in the metric
    train = mx.rnn.BucketSentenceIter(sentences, args.batch_size,
                                      buckets=BUCKETS, invalid_label=0)

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        label = mx.sym.Variable("softmax_label")
        embed = mx.sym.Embedding(data, input_dim=vocab_size,
                                 output_dim=args.num_embed, name="embed")
        stack = mx.rnn.SequentialRNNCell()
        for i in range(args.num_layers):
            stack.add(mx.rnn.LSTMCell(num_hidden=args.num_hidden,
                                      prefix="lstm_l%d_" % i))
        outputs, _ = stack.unroll(seq_len, inputs=embed, merge_outputs=True)
        pred = mx.sym.Reshape(outputs, shape=(-1, args.num_hidden))
        pred = mx.sym.FullyConnected(pred, num_hidden=vocab_size, name="pred")
        label = mx.sym.Reshape(label, shape=(-1,))
        pred = mx.sym.SoftmaxOutput(pred, label=label, name="softmax")
        return pred, ("data",), ("softmax_label",)

    dev = (mx.Context("tpu", 0) if jax.default_backend() != "cpu"
           else mx.cpu())
    mod = mx.mod.BucketingModule(sym_gen,
                                 default_bucket_key=train.default_bucket_key,
                                 context=dev)
    mod.fit(train, num_epoch=args.num_epochs,
            eval_metric=mx.metric.Perplexity(ignore_label=0),
            optimizer="sgd",
            optimizer_params={"learning_rate": args.lr, "momentum": 0.9},
            initializer=mx.initializer.Xavier(),
            batch_end_callback=[mx.callback.Speedometer(args.batch_size, 20)])


if __name__ == "__main__":
    main()
