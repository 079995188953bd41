#!/usr/bin/env python
"""Time-major RNN training (reference example/rnn-time-major).

The reference demonstrates unrolling RNN cells over time-major ``(T, N, C)``
batches — the layout the fused cuDNN kernels prefer — via
``unroll(..., layout='TNC')`` and a time-major bucket iterator (reference
example/rnn-time-major/rnn_cell_demo.py, bucket_io.py). Here the same
model is unrolled in BOTH layouts: the time-major program must produce
identical losses to the batch-major one given transposed data (layout is
a view of the same computation — on TPU the scan carries (N, C) slices
either way), and the time-major variant trains a toy copy task to low
perplexity.

    python examples/rnn-time-major/rnn_time_major.py --steps 60
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

VOCAB = 16
SEQ = 12
HID = 32


def lm_symbol(layout):
    """Embedding -> LSTM unroll(layout) -> per-step FC -> softmax."""
    import mxnet_tpu as mx

    data = mx.sym.Variable("data")  # NTC: (N, T); TNC: (T, N) of token ids
    emb = mx.sym.Embedding(data, input_dim=VOCAB, output_dim=HID,
                           name="embed")
    cell = mx.rnn.LSTMCell(num_hidden=HID, prefix="lstm_")
    outputs, _ = cell.unroll(SEQ, inputs=emb, layout=layout,
                             merge_outputs=True)
    # merged outputs: NTC -> (N, T, H); TNC -> (T, N, H)
    flat = mx.sym.Reshape(outputs, shape=(-1, HID))
    logits = mx.sym.FullyConnected(flat, num_hidden=VOCAB, name="pred")
    label = mx.sym.Variable("softmax_label")
    return mx.sym.SoftmaxOutput(logits, mx.sym.Reshape(label, shape=(-1,)),
                                name="softmax")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=80)
    p.add_argument("--batch-size", type=int, default=32)
    args = p.parse_args()

    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.io import DataBatch, DataDesc

    rng = np.random.RandomState(0)
    # delayed-echo task: emit the token seen one step earlier (requires
    # carrying state through the recurrence; learnable to ~zero loss)
    seqs = rng.randint(1, VOCAB, (1024, SEQ)).astype(np.float32)
    x_nt = seqs
    y_nt = np.concatenate([np.zeros((1024, 1), np.float32),
                           seqs[:, :-1]], axis=1)

    def make_module(layout):
        shapes = {"NTC": ((args.batch_size, SEQ), (args.batch_size, SEQ)),
                  "TNC": ((SEQ, args.batch_size), (SEQ, args.batch_size))}
        dsh, lsh = shapes[layout]
        mod = mx.mod.Module(lm_symbol(layout))
        mod.bind(data_shapes=[DataDesc("data", dsh)],
                 label_shapes=[DataDesc("softmax_label", lsh)])
        mod.init_params(mx.initializer.Xavier())
        mod.init_optimizer(optimizer="adam",
                           optimizer_params={"learning_rate": 5e-3})
        return mod

    def loss_of(mod, layout, idx, backward=True):
        xb, yb = x_nt[idx], y_nt[idx]
        if layout == "TNC":
            xb, yb = xb.T, yb.T
        batch = DataBatch(data=[mx.nd.array(xb)],
                          label=[mx.nd.array(yb)])
        if backward:
            mod.forward_backward(batch)
        else:
            mod.forward(batch, is_train=True)
        prob = mod.get_outputs()[0].asnumpy()
        # both layouts flatten to (T*N,) resp. (N*T,) in the same order the
        # per-step logits were merged, so the label flatten matches
        flat_lab = yb.reshape(-1).astype(int)
        return float(-np.log(np.clip(
            prob[np.arange(flat_lab.size), flat_lab], 1e-8, None)).mean())

    # 1) layout equivalence: same params, same batch, transposed data
    m_nt, m_tn = make_module("NTC"), make_module("TNC")
    params, _ = m_nt.get_params()
    m_tn.set_params(params, {})
    idx = rng.randint(0, 1024, args.batch_size)
    l_nt = loss_of(m_nt, "NTC", idx, backward=False)
    l_tn = loss_of(m_tn, "TNC", idx, backward=False)
    print("layout equivalence: NTC loss %.6f vs TNC loss %.6f" % (l_nt, l_tn))
    assert abs(l_nt - l_tn) < 1e-4, (l_nt, l_tn)

    # 2) train the time-major module
    losses = []
    for step in range(args.steps):
        idx = rng.randint(0, 1024, args.batch_size)
        loss = loss_of(m_tn, "TNC", idx)
        m_tn.update()
        losses.append(loss)
    first, last = np.mean(losses[:5]), np.mean(losses[-5:])
    ppl = np.exp(last)
    print("time-major LSTM: loss %.3f -> %.3f (ppl %.1f)"
          % (first, last, ppl))
    assert last < first and ppl < VOCAB, (first, last)
    print("rnn-time-major OK")


if __name__ == "__main__":
    main()
