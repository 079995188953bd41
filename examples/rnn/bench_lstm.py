#!/usr/bin/env python
"""LSTM benchmarks: Pallas fast-path microbench + PTB-class LM training.

Two measurements (the cuDNN-RNN parity story, SURVEY §2.1 #16 /
cudnn_rnn-inl.h:22):

1. micro: the fused RNN op's per-layer scan with the Pallas step kernel
   (ops/pallas/lstm.py — recurrent matmul + gates in one VMEM pass)
   against the plain XLA scan, same shapes. The fast path must not lose —
   the autotune-registry contract.
2. PTB-class LM training throughput: 2-layer LSTM LM (vocab 10k) via the
   fused RNN op inside Module's single-program fit step; reports
   samples/sec and tokens/sec (the reference measures this workload with
   example/rnn/ lstm_bucketing on cuDNN).

    python examples/rnn/bench_lstm.py
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def micro(args):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import rnn_fused
    from mxnet_tpu.ops.pallas import lstm as pl_lstm

    N, H, T = args.batch_size, args.num_hidden, args.seq_len
    rng = np.random.RandomState(0)
    ib = jnp.asarray(rng.randn(T, N, 4 * H).astype(np.float32) * 0.1)
    h0 = jnp.zeros((N, H), jnp.float32)
    c0 = jnp.zeros((N, H), jnp.float32)
    wh = jnp.asarray(rng.randn(4 * H, H).astype(np.float32) * 0.1)

    fused = jax.jit(lambda ib, h0, c0, wh:
                    rnn_fused._lstm_scan_fused(ib, h0, c0, wh)[1])
    plain = jax.jit(lambda ib, h0, c0, wh:
                    rnn_fused._lstm_scan_jnp(ib, h0, c0, wh, H)[1])

    def timeit(f, reps=20, outer=5):
        r = f(ib, h0, c0, wh)
        np.asarray(jnp.reshape(r, (-1,))[0])
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(outer * reps):
                r = f(ib, h0, c0, wh)
            np.asarray(jnp.reshape(r, (-1,))[0])
            t = (time.perf_counter() - t0) / (outer * reps)
            best = t if best is None else min(best, t)
        return best

    selected = pl_lstm.use_for(N, H)
    t_plain = timeit(plain)
    t_fused = timeit(fused) if selected else float("nan")
    print("micro N=%d H=%d T=%d: plain-scan %.3f ms  pallas %.3f ms  "
          "(fast path %s, speedup %.2fx)"
          % (N, H, T, t_plain * 1e3, t_fused * 1e3,
             "SELECTED" if selected else "not selected (shape/backend)",
             (t_plain / t_fused) if selected else float("nan")))
    return selected, t_plain, t_fused


def _lm_loss_symbol(vocab, seq_len, num_hidden):
    """LM with a SCALAR loss head (log-softmax pick via one-hot +
    MakeLoss). Same compute as SoftmaxOutput, but the step's only fresh
    output is the loss scalar, not a (batch*seq, vocab) probability
    buffer per step."""
    import mxnet_tpu as mx
    from mxnet_tpu.rnn import rnn_cell

    sym = mx.sym
    data = sym.Variable("data")
    embed = sym.Embedding(data=data, input_dim=vocab,
                          output_dim=num_hidden, name="embed")
    stack = rnn_cell.FusedRNNCell(num_hidden, num_layers=2, mode="lstm",
                                  prefix="lstm_")
    outputs, _ = stack.unroll(seq_len, inputs=embed, merge_outputs=True,
                              layout="NTC")
    pred = sym.Reshape(data=outputs, shape=(-1, num_hidden))
    pred = sym.FullyConnected(data=pred, num_hidden=vocab, name="pred")
    logp = sym.log_softmax(pred, axis=-1)
    label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
    onehot = sym.one_hot(label, depth=vocab)
    nll = sym._mul_scalar(sym.mean(sym.sum(sym._mul(logp, onehot), axis=1)),
                          scalar=-1.0)
    return sym.MakeLoss(nll, name="loss")


def ptb_lm(args):
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import models

    N, T = args.batch_size, args.seq_len
    if args.loss_head:
        sym = _lm_loss_symbol(args.vocab, T, args.num_hidden)
    else:
        sym = models.get_symbol("lstm-lm", num_classes=args.vocab,
                                seq_len=T, num_embed=args.num_hidden,
                                num_hidden=args.num_hidden, num_layers=2,
                                fused=True)
    dev = (mx.Context("tpu", 0) if jax.default_backend() != "cpu"
           else mx.cpu())
    mod = mx.mod.Module(sym, context=dev)
    mod.bind(data_shapes=[("data", (N, T))],
             label_shapes=[("softmax_label", (N, T))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.5})
    rng = np.random.RandomState(0)
    batch = mx.io.DataBatch(
        [mx.nd.array(rng.randint(0, args.vocab, (N, T)).astype(np.float32))],
        [mx.nd.array(rng.randint(0, args.vocab, (N, T)).astype(np.float32))])

    def sync():
        np.asarray(mod.get_outputs()[0].asnumpy().reshape(-1)[0])

    for _ in range(3):
        mod.fit_step(batch)
    sync()
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(args.steps):
            mod.fit_step(batch)
        sync()
        times.append((time.perf_counter() - t0) / args.steps)
    t = sorted(times)[len(times) // 2]
    print("ptb-lm%s 2xLSTM(%d) vocab=%d bs=%d seq=%d: %.2f ms/step  "
          "%.0f samples/s  %.0f tokens/s"
          % ("(loss-head)" if args.loss_head else "", args.num_hidden,
             args.vocab, N, T, t * 1e3, N / t, N * T / t))
    return t


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--num-hidden", type=int, default=256)
    p.add_argument("--seq-len", type=int, default=35)
    p.add_argument("--vocab", type=int, default=10000)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--skip-micro", action="store_true")
    p.add_argument("--loss-head", action="store_true",
                   help="scalar loss output instead of full softmax "
                        "probabilities (no per-step (batch*seq, vocab) "
                        "output buffer)")
    args = p.parse_args()
    if not args.skip_micro:
        micro(args)
    ptb_lm(args)


if __name__ == "__main__":
    main()
