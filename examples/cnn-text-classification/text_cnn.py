#!/usr/bin/env python
"""Kim-CNN sentence classification with BUCKETING on a non-RNN graph.

Analogue of the reference's example/cnn_text_classification/text_cnn.py:
embedding -> parallel Convolutions with window sizes (3,4,5) over the
(seq_len, embed) plane -> max-pool-over-time -> concat -> dropout -> FC.
The point, beyond the model family, is that BucketingModule's
shared-parameter bucket switching is NOT an RNN-only mechanism: the
sym_gen here emits a pure conv graph per sentence-length bucket and the
same weights serve every bucket (the compile-cache/bucketing story of
SURVEY §5.7 on a CNN).

Synthetic task: class = which token id range dominates the sentence, so
a real signal exists at every bucket length.

    python examples/cnn-text-classification/text_cnn.py --epochs 3
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

BUCKETS = [8, 12, 16]
FILTERS = (3, 4, 5)


def synthetic_sentences(vocab, n=600, n_classes=3, seed=0):
    import numpy as np

    rng = np.random.RandomState(seed)
    sentences, labels = [], []
    third = (vocab - 1) // n_classes
    for _ in range(n):
        ln = int(rng.choice(BUCKETS)) - int(rng.randint(0, 3))
        cls = int(rng.randint(n_classes))
        lo = 1 + cls * third
        toks = rng.randint(lo, lo + third, ln)
        noise = rng.randint(1, vocab, ln)
        keep = rng.rand(ln) < 0.7
        sentences.append(list(np.where(keep, toks, noise)))
        labels.append(cls)
    return sentences, labels


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=5)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--num-embed", type=int, default=16)
    p.add_argument("--num-filter", type=int, default=8)
    p.add_argument("--classes", type=int, default=3)
    p.add_argument("--lr", type=float, default=0.05)
    args = p.parse_args()

    import numpy as np
    import jax
    import mxnet_tpu as mx

    sentences, labels = synthetic_sentences(args.vocab,
                                            n_classes=args.classes)
    train = mx.rnn.BucketSentenceIter(
        sentences, args.batch_size, buckets=BUCKETS, invalid_label=0,
        sequence_labels=labels)

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        label = mx.sym.Variable("softmax_label")
        embed = mx.sym.Embedding(data, input_dim=args.vocab,
                                 output_dim=args.num_embed, name="embed")
        # (B, T, E) -> (B, 1, T, E): conv windows span full embed width
        x = mx.sym.Reshape(embed, shape=(0, 1, seq_len, args.num_embed))
        pooled = []
        for f in FILTERS:
            c = mx.sym.Convolution(x, kernel=(f, args.num_embed),
                                   num_filter=args.num_filter,
                                   name="conv%d" % f)
            c = mx.sym.Activation(c, act_type="relu")
            # max over time: window = remaining sequence extent
            c = mx.sym.Pooling(c, kernel=(seq_len - f + 1, 1),
                               pool_type="max")
            pooled.append(mx.sym.Flatten(c))
        h = mx.sym.Concat(*pooled, dim=1)
        h = mx.sym.Dropout(h, p=0.3)
        fc = mx.sym.FullyConnected(h, num_hidden=args.classes, name="fc")
        return (mx.sym.SoftmaxOutput(fc, label=label, name="softmax"),
                ("data",), ("softmax_label",))

    dev = (mx.Context("tpu", 0) if jax.default_backend() != "cpu"
           else mx.cpu())
    mod = mx.mod.BucketingModule(sym_gen,
                                 default_bucket_key=train.default_bucket_key,
                                 context=dev)
    acc = mx.metric.Accuracy()
    mod.fit(train, num_epoch=args.epochs, eval_metric=acc,
            optimizer="adam", optimizer_params={"learning_rate": args.lr},
            initializer=mx.initializer.Xavier())
    train.reset()
    acc.reset()
    mod.score(train, acc)
    name, val = acc.get()
    print("text-cnn OK: %d buckets, final %s %.3f"
          % (len(BUCKETS), name, val))
    assert val > 0.6, val


if __name__ == "__main__":
    main()
