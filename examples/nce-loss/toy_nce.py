#!/usr/bin/env python
"""Noise-contrastive estimation over a large embedding table.

Analogue of the reference's example/nce-loss/toy_nce.py: instead of a
full-vocab softmax (a (hidden, vocab) matmul), each example scores its
true class embedding against a handful of sampled noise classes — the
NCE trick that makes 10k+ vocabularies trainable. This drives
``Embedding``'s gather forward and scatter-add backward at vocabulary
scale, which nothing else in the example suite exercises.

Model (reference nce.py nce_loss): input one-hot-ish feature ->
FullyConnected hidden -> dot(hidden, Embedding(label_i)) + bias_i for the
true label and num_label-1 noise labels -> per-candidate logistic loss
with label_weight 1 for the true class, 0 for noise:

    python examples/nce-loss/toy_nce.py --steps 12 --vocab 12000
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def nce_loss(data, label, label_weight, vocab_size, num_hidden, num_label):
    """The reference's nce.py nce_loss graph, TPU-native ops only:
    Embedding-gather the candidate class vectors + biases, dot with the
    hidden state, logistic loss weighted 1/true 0/noise."""
    import mxnet_tpu as mx

    embed = mx.sym.Embedding(label, mx.sym.Variable("class_embed_weight"),
                             input_dim=vocab_size, output_dim=num_hidden,
                             name="class_embed")        # (B, L, H)
    bias = mx.sym.Embedding(label, mx.sym.Variable("class_bias_weight"),
                            input_dim=vocab_size, output_dim=1,
                            name="class_bias")          # (B, L, 1)
    pred = mx.sym.Reshape(data, shape=(-1, 1, num_hidden))
    scores = mx.sym.sum(mx.sym.broadcast_mul(embed, pred), axis=2) \
        + mx.sym.Reshape(bias, shape=(-1, num_label))   # (B, L)
    # logistic NCE objective: -[w*log σ(s) + (1-w)*log σ(-s)]
    logsig = -mx.sym.Activation(-scores, act_type="softrelu")   # log σ(s)
    lognot = -mx.sym.Activation(scores, act_type="softrelu")    # log σ(-s)
    loss = -(label_weight * logsig + (1 - label_weight) * lognot)
    return mx.sym.MakeLoss(mx.sym.mean(loss, axis=1), name="nce")


def make_batch(rng, batch, vocab, feat, num_label, num_true=50):
    """Mock task from the reference toy_nce DataIter: 3 active features
    determine the true class. True classes concentrate in [0, num_true)
    so a short run can learn them, while noise classes sample the FULL
    vocabulary — the scatter-add backward still touches the whole
    (vocab, hidden) table."""
    import numpy as np

    data = np.zeros((batch, feat), np.float32)
    label = np.zeros((batch, num_label), np.float32)
    weight = np.zeros((batch, num_label), np.float32)
    for b in range(batch):
        active = rng.choice(feat, 3, replace=False)
        data[b, active] = 1.0
        s = 0
        for k in sorted(active):
            s = s * feat + int(k)
        label[b, 0] = s % num_true
        label[b, 1:] = rng.randint(0, vocab, num_label - 1)
        weight[b, 0] = 1.0
    return data, label, weight


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--vocab", type=int, default=12000)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--feat", type=int, default=32)
    p.add_argument("--num-label", type=int, default=6)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=0.5)
    args = p.parse_args()

    import numpy as np
    np.random.seed(0)  # deterministic param init (CI quality bars)

    import mxnet_tpu as mx

    rng = np.random.RandomState(0)
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("label")
    label_weight = mx.sym.Variable("label_weight")
    hiddenl = mx.sym.FullyConnected(data, num_hidden=args.hidden, name="fc")
    net = nce_loss(hiddenl, label, label_weight, args.vocab, args.hidden,
                   args.num_label)

    mod = mx.mod.Module(net, data_names=("data",),
                        label_names=("label", "label_weight"))
    mod.bind(data_shapes=[("data", (args.batch, args.feat))],
             label_shapes=[("label", (args.batch, args.num_label)),
                           ("label_weight", (args.batch, args.num_label))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": args.lr,
                                         "momentum": 0.9})

    losses = []
    for step in range(args.steps):
        x, lab, w = make_batch(rng, args.batch, args.vocab, args.feat,
                               args.num_label)
        batch = mx.io.DataBatch(data=[mx.nd.array(x)],
                                label=[mx.nd.array(lab), mx.nd.array(w)])
        mod.forward_backward(batch)
        mod.update()
        loss = float(mod.get_outputs()[0].asnumpy().mean())
        losses.append(loss)
        print("step %d nce loss %.4f" % (step, loss))

    # the embedding table really trained at vocab scale: rows touched by
    # training moved, untouched rows kept their init
    emb = mod.get_params()[0]["class_embed_weight"].asnumpy()
    assert emb.shape == (args.vocab, args.hidden)
    first, last = np.mean(losses[:2]), np.mean(losses[-2:])
    print("NCE train: loss %.4f -> %.4f over %d steps, vocab %d (%s)"
          % (first, last, len(losses), args.vocab,
             "decreasing" if last < first else "NOT decreasing"))
    if last >= first:
        raise SystemExit("loss did not decrease")


if __name__ == "__main__":
    main()
