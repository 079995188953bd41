#!/usr/bin/env python
"""LSTM + CTC sequence recognition on synthetic digit strings.

Analogue of the reference's example/warpctc/lstm_ocr.py (captcha digit
strings -> unrolled LSTM -> warp-ctc loss). Instead of rendering captchas
(an external dependency), each digit emits a short burst of a
digit-specific feature pattern along the time axis, with noise — the same
learning problem (unsegmented sequence labeling, CTC alignment over an
unknown segmentation) without the image dependency.

Pipeline: synthetic (T, B, F) sequences -> sym.RNN(mode='lstm') ->
per-frame projection to alphabet logits -> sym.ctc_loss (blank=0, labels
1..10) -> MakeLoss. Loss must decrease:

    python examples/warpctc/lstm_ocr.py --steps 12
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

NUM_DIGITS = 10          # classes 1..10; 0 is the CTC blank
FEAT = 16                # per-frame feature size
SEQ_LEN = 20             # frames per sample
LABEL_LEN = 4            # max digits per string (0-padded below)


def make_batch(rng, batch):
    """Digit string of length 3-4; digit d emits 4 frames of pattern(d)."""
    import numpy as np

    pats = np.eye(NUM_DIGITS, FEAT, dtype=np.float32)  # digit signatures
    data = np.zeros((SEQ_LEN, batch, FEAT), np.float32)
    label = np.zeros((batch, LABEL_LEN), np.float32)
    for b in range(batch):
        n = rng.randint(3, LABEL_LEN + 1)
        digits = rng.randint(0, NUM_DIGITS, n)
        t = 0
        for i, d in enumerate(digits):
            span = rng.randint(3, 5)
            data[t:t + span, b] = pats[d]
            t += span + rng.randint(0, 2)  # optional silent gap
            label[b, i] = d + 1            # CTC labels are 1-based
    data += rng.randn(*data.shape).astype(np.float32) * 0.1
    return data, label


def build_net(hidden):
    import mxnet_tpu as mx

    data = mx.sym.Variable("data")          # (T, B, F)
    label = mx.sym.Variable("label")        # (B, L), 0-padded
    rnn = mx.sym.RNN(data, mx.sym.Variable("lstm_parameters"),
                     mx.sym.Variable("rnn_state"),
                     mx.sym.Variable("rnn_state_cell"),
                     mode="lstm", state_size=hidden, num_layers=1,
                     name="lstm")           # (T, B, H)
    proj = mx.sym.FullyConnected(mx.sym.Reshape(rnn, shape=(-1, hidden)),
                                 num_hidden=NUM_DIGITS + 1, flatten=False,
                                 name="cls")
    logits = mx.sym.Reshape(proj, shape=(SEQ_LEN, -1, NUM_DIGITS + 1))
    loss = mx.sym.ctc_loss(logits, label)
    return mx.sym.MakeLoss(loss, name="ctc")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--hidden", type=int, default=32)
    p.add_argument("--steps", type=int, default=12)
    p.add_argument("--lr", type=float, default=0.02)
    args = p.parse_args()

    import numpy as np
    np.random.seed(0)  # deterministic param init (CI quality bars)

    import mxnet_tpu as mx

    rng = np.random.RandomState(0)
    net = build_net(args.hidden)
    mod = mx.mod.Module(net, data_names=("data", "rnn_state",
                                         "rnn_state_cell"),
                        label_names=("label",))
    zeros_h = np.zeros((1, args.batch, args.hidden), np.float32)
    data_shapes = [("data", (SEQ_LEN, args.batch, FEAT)),
                   ("rnn_state", zeros_h.shape),
                   ("rnn_state_cell", zeros_h.shape)]
    mod.bind(data_shapes=data_shapes,
             label_shapes=[("label", (args.batch, LABEL_LEN))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": args.lr})

    losses = []
    for step in range(args.steps):
        x, lab = make_batch(rng, args.batch)
        batch = mx.io.DataBatch(
            data=[mx.nd.array(x), mx.nd.array(zeros_h),
                  mx.nd.array(zeros_h)],
            label=[mx.nd.array(lab)])
        mod.forward_backward(batch)
        mod.update()
        loss = float(mod.get_outputs()[0].asnumpy().mean())
        losses.append(loss)
        print("step %d ctc loss %.4f" % (step, loss))

    first, last = np.mean(losses[:2]), np.mean(losses[-2:])
    print("CTC train: loss %.4f -> %.4f over %d steps (%s)"
          % (first, last, len(losses),
             "decreasing" if last < first else "NOT decreasing"))
    if last >= first:
        raise SystemExit("loss did not decrease")


if __name__ == "__main__":
    main()
