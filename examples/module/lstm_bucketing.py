#!/usr/bin/env python
"""BucketingModule + LSTM LM walkthrough (reference example/module/
lstm_bucketing.py: PTB sentences bucketed by length, one shared
parameter set across per-bucket unrolled graphs). Synthetic Markov
sentences stand in for PTB (zero-egress CI); the API surface is the
point: BucketSentenceIter -> sym_gen(seq_len) -> BucketingModule.fit.

    python examples/module/lstm_bucketing.py --epochs 2
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

VOCAB = 40
BUCKETS = [8, 16, 24]


def synth_sentences(n, rng):
    """Order-1 Markov sentences of varying length — learnable structure
    so perplexity demonstrably drops."""
    import numpy as np

    trans = np.full((VOCAB, VOCAB), 1e-3)
    for v in range(VOCAB):
        trans[v, rng.choice(VOCAB, 3, replace=False)] = 1.0
    trans /= trans.sum(1, keepdims=True)
    out = []
    for _ in range(n):
        ln = rng.randint(5, max(BUCKETS) + 1)
        s = [int(rng.randint(1, VOCAB))]
        for _ in range(ln - 1):
            s.append(int(rng.choice(VOCAB, p=trans[s[-1]])))
        out.append(s)
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--num-hidden", type=int, default=64)
    args = p.parse_args()

    import numpy as np
    import mxnet_tpu as mx

    np.random.seed(0)
    rng = np.random.RandomState(0)
    sentences = synth_sentences(600, rng)
    # the iterator's LM convention: label = sentence shifted left by one
    it = mx.rnn.BucketSentenceIter(sentences, args.batch_size,
                                   buckets=BUCKETS, invalid_label=0,
                                   label_name="softmax_label")

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        label = mx.sym.Variable("softmax_label")
        embed = mx.sym.Embedding(data, input_dim=VOCAB, output_dim=32,
                                 name="embed")
        stack = mx.rnn.FusedRNNCell(args.num_hidden, num_layers=1,
                                    mode="lstm", prefix="lstm_")
        outputs, _ = stack.unroll(seq_len, inputs=embed,
                                  merge_outputs=True, layout="NTC")
        pred = mx.sym.Reshape(outputs, shape=(-1, args.num_hidden))
        pred = mx.sym.FullyConnected(pred, num_hidden=VOCAB, name="pred")
        label = mx.sym.Reshape(label, shape=(-1,))
        net = mx.sym.SoftmaxOutput(pred, label=label, name="softmax")
        return net, ("data",), ("softmax_label",)

    mod = mx.mod.BucketingModule(sym_gen,
                                 default_bucket_key=it.default_bucket_key)
    metric = mx.metric.Perplexity(ignore_label=None)
    mod.fit(it, num_epoch=args.epochs, eval_metric=metric,
            optimizer="adam", optimizer_params={"learning_rate": 3e-3},
            initializer=mx.initializer.Xavier())
    it.reset()
    m = mx.metric.Perplexity(ignore_label=None)
    mod.score(it, m)
    ppl = m.get()[1]
    print("lstm-bucketing perplexity %.2f over %d buckets (vocab %d)"
          % (ppl, len(BUCKETS), VOCAB))
    if ppl > 0.8 * VOCAB:
        raise SystemExit("perplexity did not improve over uniform")
    print("lstm_bucketing OK")


if __name__ == "__main__":
    main()
