#!/usr/bin/env python
"""DeepSpeech-lite: bucketed variable-length audio -> conv front-end ->
bidirectional LSTM -> CTC.

Analogue of the reference's example/speech_recognition (arch_deepspeech.py:
conv2d front-end over time x frequency, stacked BiRNNs, warp-CTC, with a
bucketing iterator over utterance lengths) — the one reference family that
exercises bucketing, CTC, and variable-length audio TOGETHER. Real
LibriSpeech is replaced by synthetic utterances (zero-egress CI): each
"phoneme" class emits a characteristic spectral band for a few frames, so
the unsegmented-sequence-labeling problem (CTC alignment over an unknown
segmentation) is the same, without the corpus.

Pipeline: synthetic (B, 1, T, F) filterbank batches bucketed by utterance
length -> BucketingModule whose sym_gen builds, per bucket T:
conv(stride 2 in time) x2 -> (T/4, B, feat) -> RNN(bidirectional lstm) ->
per-frame FC -> ctc_loss -> MakeLoss. Loss must decrease:

    python examples/speech_recognition/train.py --steps 10
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

N_PHONES = 8             # classes 1..8; 0 is the CTC blank
FEAT = 32                # filterbank bins per frame
BUCKETS = (48, 80)       # utterance lengths (frames), bucketed
LABEL_LEN = 6            # max phonemes per utterance (0-padded)


def make_utterance_batch(rng, batch, T):
    """Each phoneme holds a band of the spectrum for 6-9 frames; phones
    are separated by optional silence. (B, 1, T, F) + (B, L) labels."""
    import numpy as np

    data = np.zeros((batch, 1, T, FEAT), np.float32)
    label = np.zeros((batch, LABEL_LEN), np.float32)
    band = FEAT // N_PHONES
    n_max = min(LABEL_LEN, T // 10)
    for b in range(batch):
        n = rng.randint(2, n_max + 1)
        t = rng.randint(0, 4)
        for i in range(n):
            ph = rng.randint(0, N_PHONES)
            span = rng.randint(6, 10)
            data[b, 0, t:t + span, ph * band:(ph + 1) * band] = 1.0
            t += span + rng.randint(0, 3)
            label[b, i] = ph + 1
    data += rng.randn(*data.shape).astype(np.float32) * 0.15
    return data, label


def sym_gen_factory(hidden):
    """Per-bucket symbol: the DeepSpeech layering at lite scale."""
    import mxnet_tpu as mx

    def sym_gen(T):
        data = mx.sym.Variable("data")    # (B, 1, T, F)
        label = mx.sym.Variable("label")  # (B, L)
        # conv front-end, stride 2 in TIME on both layers (the
        # reference's conv1/conv2 time-striding that makes the RNN see
        # T/4 frames)
        h = mx.sym.Convolution(data, kernel=(5, 5), stride=(2, 2),
                               pad=(2, 2), num_filter=16, name="conv1")
        h = mx.sym.Activation(h, act_type="relu")
        h = mx.sym.Convolution(h, kernel=(5, 3), stride=(2, 1),
                               pad=(2, 1), num_filter=16, name="conv2")
        h = mx.sym.Activation(h, act_type="relu")
        t2, f2 = T // 4, FEAT // 2        # conv output time/freq extents
        # (B, C, T', F') -> (T', B, C*F') frame-major for the RNN
        h = mx.sym.transpose(h, axes=(2, 0, 1, 3))
        h = mx.sym.Reshape(h, shape=(t2, -1, 16 * f2))
        rnn = mx.sym.RNN(h, mx.sym.Variable("lstm_parameters"),
                         mx.sym.Variable("rnn_state"),
                         mx.sym.Variable("rnn_state_cell"),
                         mode="lstm", state_size=hidden, num_layers=1,
                         bidirectional=True, name="birnn")  # (T', B, 2H)
        proj = mx.sym.FullyConnected(
            mx.sym.Reshape(rnn, shape=(-1, 2 * hidden)),
            num_hidden=N_PHONES + 1, flatten=False, name="cls")
        logits = mx.sym.Reshape(proj, shape=(t2, -1, N_PHONES + 1))
        loss = mx.sym.ctc_loss(logits, label)
        net = mx.sym.MakeLoss(loss, name="ctc")
        return (net, ("data", "rnn_state", "rnn_state_cell"), ("label",))

    return sym_gen


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--hidden", type=int, default=48)
    p.add_argument("--steps", type=int, default=10,
                   help="steps PER bucket (buckets alternate)")
    p.add_argument("--lr", type=float, default=0.01)
    args = p.parse_args()

    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.io import DataBatch, DataDesc

    np.random.seed(0)  # deterministic param init (CI quality bars)
    rng = np.random.RandomState(0)
    state_shape = (2, args.batch, args.hidden)  # 1 layer x 2 directions
    zeros_h = np.zeros(state_shape, np.float32)

    mod = mx.mod.BucketingModule(sym_gen_factory(args.hidden),
                                 default_bucket_key=max(BUCKETS))

    def shapes(T):
        return ([DataDesc("data", (args.batch, 1, T, FEAT)),
                 DataDesc("rnn_state", state_shape),
                 DataDesc("rnn_state_cell", state_shape)],
                [DataDesc("label", (args.batch, LABEL_LEN))])

    data_shapes, label_shapes = shapes(max(BUCKETS))
    mod.bind(data_shapes=data_shapes, label_shapes=label_shapes)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": args.lr})

    losses = {T: [] for T in BUCKETS}
    for step in range(args.steps):
        for T in BUCKETS:  # alternate buckets: every step switches
            x, lab = make_utterance_batch(rng, args.batch, T)
            ds, ls = shapes(T)
            batch = DataBatch(
                data=[mx.nd.array(x), mx.nd.array(zeros_h),
                      mx.nd.array(zeros_h)],
                label=[mx.nd.array(lab)],
                bucket_key=T, provide_data=ds, provide_label=ls)
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
            loss = float(mod.get_outputs()[0].asnumpy().mean())
            losses[T].append(loss)
            print("step %d bucket T=%d ctc loss %.4f" % (step, T, loss))

    for T in BUCKETS:
        first, last = np.mean(losses[T][:2]), np.mean(losses[T][-2:])
        print("deepspeech-lite bucket %d: loss %.4f -> %.4f (%s)"
              % (T, first, last,
                 "decreasing" if last < first else "NOT decreasing"))
        if last >= first:
            raise SystemExit("bucket %d loss did not decrease" % T)
    print("deepspeech-lite OK: %d buckets trained through one shared "
          "parameter set" % len(BUCKETS))


if __name__ == "__main__":
    main()
