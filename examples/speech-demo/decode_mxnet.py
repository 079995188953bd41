#!/usr/bin/env python
"""Speech DECODING demo (reference example/speech-demo/decode_mxnet.py:
run the trained acoustic model over held-out feature archives and emit
transcriptions). The kaldi I/O of the reference is replaced by the
synthetic filterbank utterances of examples/speech_recognition (zero
egress); the demo's substance is the decode side the training example
doesn't cover: greedy CTC decoding (argmax per frame, collapse repeats,
drop blanks) and phoneme-error-rate scoring against the references.

    python examples/speech-demo/decode_mxnet.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "speech_recognition"))


def greedy_ctc_decode(logits):
    """(T, B, C) logits -> per-utterance label sequences: frame argmax,
    collapse repeats, strip blanks (class 0)."""
    import numpy as np

    path = logits.argmax(axis=2)  # (T, B)
    out = []
    for b in range(path.shape[1]):
        seq, prev = [], -1
        for t in range(path.shape[0]):
            c = int(path[t, b])
            if c != prev and c != 0:
                seq.append(c)
            prev = c
        out.append(seq)
    return out


def edit_distance(a, b):
    import numpy as np

    d = np.zeros((len(a) + 1, len(b) + 1), np.int32)
    d[:, 0] = np.arange(len(a) + 1)
    d[0, :] = np.arange(len(b) + 1)
    for i in range(1, len(a) + 1):
        for j in range(1, len(b) + 1):
            d[i, j] = min(d[i - 1, j] + 1, d[i, j - 1] + 1,
                          d[i - 1, j - 1] + (a[i - 1] != b[j - 1]))
    return int(d[len(a), len(b)])


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--train-steps", type=int, default=80)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--hidden", type=int, default=48)
    p.add_argument("--utts", type=int, default=16)
    args = p.parse_args()

    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.io import DataBatch, DataDesc
    import train as sr  # examples/speech_recognition/train.py

    np.random.seed(0)
    rng = np.random.RandomState(0)
    T = max(sr.BUCKETS)
    state_shape = (2, args.batch, args.hidden)
    zeros_h = np.zeros(state_shape, np.float32)

    # --- train the acoustic model briefly (single bucket suffices) ----
    sym, data_names, label_names = sr.sym_gen_factory(args.hidden)(T)
    mod = mx.mod.Module(sym, data_names=data_names,
                        label_names=label_names, context=mx.cpu())
    ds = [DataDesc("data", (args.batch, 1, T, sr.FEAT)),
          DataDesc("rnn_state", state_shape),
          DataDesc("rnn_state_cell", state_shape)]
    ls = [DataDesc("label", (args.batch, sr.LABEL_LEN))]
    mod.bind(data_shapes=ds, label_shapes=ls)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 0.01})
    for _ in range(args.train_steps):
        x, lab = sr.make_utterance_batch(rng, args.batch, T)
        mod.forward(DataBatch([mx.nd.array(x), mx.nd.array(zeros_h),
                               mx.nd.array(zeros_h)],
                              [mx.nd.array(lab)]), is_train=True)
        mod.backward()
        mod.update()

    # --- decode held-out utterances through the LOGITS tap ------------
    # (the reference decode_mxnet.py likewise binds the acoustic model's
    # output layer and streams archives through it)
    logits_sym = sym.get_internals()["cls_output"]
    dec = mx.mod.Module(logits_sym, data_names=data_names, label_names=[],
                        context=mx.cpu())
    dec.bind(data_shapes=ds, for_training=False)
    dec.set_params(*mod.get_params())

    total_err = total_len = 0
    shown = 0
    for _ in range(args.utts // args.batch):
        x, lab = sr.make_utterance_batch(rng, args.batch, T)
        dec.forward(DataBatch([mx.nd.array(x), mx.nd.array(zeros_h),
                               mx.nd.array(zeros_h)], []), is_train=False)
        flat = dec.get_outputs()[0].asnumpy()      # (T/4 * B, C)
        logits = flat.reshape(T // 4, -1, sr.N_PHONES + 1)
        hyps = greedy_ctc_decode(logits)
        for b, hyp in enumerate(hyps):
            ref = [int(v) for v in lab[b] if v > 0]
            total_err += edit_distance(hyp, ref)
            total_len += len(ref)
            if shown < 4:
                print("utt %d  ref %s  hyp %s" % (shown, ref, hyp))
                shown += 1
    per = total_err / max(total_len, 1)
    print("decode: phoneme error rate %.2f over %d utterances"
          % (per, args.utts))
    if per > 0.5:
        raise SystemExit("decoding no better than noise")
    print("speech-demo decode OK")


if __name__ == "__main__":
    main()
