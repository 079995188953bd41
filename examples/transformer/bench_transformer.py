#!/usr/bin/env python
"""Transformer benchmarks: flash-attention fast path + LM training.

Three measurements (the cuDNN-fast-path layering extended to attention,
SURVEY §7 / cudnn_rnn-inl.h:22 contract — the fast path must not lose
where it is selected):

1. micro: the Pallas flash-attention kernel
   (ops/pallas/flash_attention.py) vs the plain XLA einsum attention
   (ops/attention.py dot_product_attention) at several (batch, heads,
   seq, head_dim) shapes, forward pass, bf16 — plus an on-chip numeric
   equivalence check (the kernel is otherwise only correctness-tested in
   interpret mode on CPU).
2. decoder-only transformer-LM training throughput (models/transformer
   blocks with a scalar-loss head; head_dim 128 so the flash path is
   selected), flash on vs off in the SAME training program.

3. kernels (``--kernels``, alone): the flash kernels' milliseconds
   (forward; dq and dkv, the two-kernel backward; the fused backward that
   replaces them wherever its accumulators fit VMEM at some query
   superblock), each alone, at the shape of the benchmark cell
   ``lm_train_4k`` — the measurement PERF.md's findings for PRs 26 and 32
   start from — and at the two 8192-token cells' shapes, where the fused
   backward runs in query superblocks.

    python examples/transformer/bench_transformer.py
    python examples/transformer/bench_transformer.py --kernels
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def _min_time(jf, xs, reps):
    """min-of-3 timed blocks of ``reps`` calls with a scalar-readback
    sync. ``jf`` must reduce to a scalar INSIDE the jit, so the timed
    call allocates no fresh (B,H,S,D) output buffer."""
    import numpy as np
    import jax.numpy as jnp

    r = jf(*xs)
    np.asarray(jnp.reshape(r, (-1,))[0])
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            r = jf(*xs)
        np.asarray(jnp.reshape(r, (-1,))[0])
        t = (time.perf_counter() - t0) / reps
        best = t if best is None else min(best, t)
    return best


def _fb_scalar(f):
    """fwd+bwd closure: grads wrt ALL of q,k,v (argnums=0 alone would
    let DCE drop the dkv kernel entirely), reduced to a scalar inside
    the jit (same rule as the forward closures)."""
    import jax
    import jax.numpy as jnp

    def scalar(q, k, v):
        g = jax.grad(lambda q, k, v: jnp.sum(jnp.square(
            f(q, k, v).astype(jnp.float32))),
            argnums=(0, 1, 2))(q, k, v)
        return sum(jnp.sum(x.astype(jnp.float32)) for x in g)
    return jax.jit(scalar)


def micro(args):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as att
    from mxnet_tpu.ops.pallas import flash_attention as fa

    # off-TPU (CPU smoke) the kernel runs in interpret mode at tiny shapes
    on_cpu = jax.default_backend() == "cpu"
    interp = True if on_cpu else False
    shapes = ([(1, 2, 256, 128)] if on_cpu else
              [(8, 16, 2048, 128), (4, 8, 4096, 128), (8, 16, 512, 128),
               (16, 16, 256, 128)])  # last: the selection-gate boundary
    # the micro documents KERNEL-vs-plain, including at shapes the
    # selection gate excludes (that's how the gate placement is
    # justified) — bypass MIN_SEQ for the measurement and restore after
    saved_min_seq = fa.MIN_SEQ
    fa.MIN_SEQ = 0
    rows = []
    for (B, H, S, D) in shapes:
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32),
                        dtype=jnp.bfloat16)
        k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32),
                        dtype=jnp.bfloat16)
        v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32),
                        dtype=jnp.bfloat16)

        flash_full = jax.jit(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=args.causal, interpret=interp))
        plain_full = jax.jit(lambda q, k, v: att.dot_product_attention(
            q, k, v, causal=args.causal))
        # timing closures reduce to a SCALAR: no fresh (B,H,S,D) output
        # buffer per timed execution
        flash = jax.jit(lambda q, k, v: jnp.sum(fa.flash_attention(
            q, k, v, causal=args.causal, interpret=interp)
            .astype(jnp.float32)))
        plain = jax.jit(lambda q, k, v: jnp.sum(att.dot_product_attention(
            q, k, v, causal=args.causal).astype(jnp.float32)))

        # on-chip numeric equivalence (f32 softmax inside both paths)
        of = np.asarray(flash_full(q, k, v), np.float32)
        op = np.asarray(plain_full(q, k, v), np.float32)
        maxdiff = np.abs(of - op).max()

        reps = 3 if on_cpu else 200
        t_plain = _min_time(plain, (q, k, v), reps)
        t_flash = _min_time(flash, (q, k, v), reps)
        # attention FLOPs: 2 matmuls of 2*B*H*S*S*D each (causal halves)
        flops = 4 * B * H * S * S * D * (0.5 if args.causal else 1.0)
        rows.append((B, H, S, D, t_plain, t_flash, maxdiff))
        print("micro B=%d H=%d S=%d D=%d causal=%s: plain %.3f ms "
              "(%.0f TF/s)  flash %.3f ms (%.0f TF/s)  speedup %.2fx  "
              "maxdiff %.4f"
              % (B, H, S, D, args.causal, t_plain * 1e3,
                 flops / t_plain / 1e12, t_flash * 1e3,
                 flops / t_flash / 1e12, t_plain / t_flash, maxdiff))

        tb_plain = _min_time(_fb_scalar(lambda q, k, v:
            att.dot_product_attention(q, k, v, causal=args.causal)),
            (q, k, v), reps)
        tb_flash = _min_time(_fb_scalar(lambda q, k, v:
            fa.flash_attention(q, k, v, causal=args.causal,
                               interpret=interp)), (q, k, v), reps)
        # USEFUL work (same for both paths): bwd = 2.5x fwd (5 necessary
        # matmuls vs 2), total 3.5x — the flash kernels' score recompute
        # is deliberately NOT credited (standard flash accounting)
        fb_flops = flops * 3.5
        print("  fwd+bwd: plain %.3f ms (%.0f TF/s)  flash %.3f ms "
              "(%.0f TF/s)  speedup %.2fx"
              % (tb_plain * 1e3, fb_flops / tb_plain / 1e12,
                 tb_flash * 1e3, fb_flops / tb_flash / 1e12,
                 tb_plain / tb_flash))
    fa.MIN_SEQ = saved_min_seq
    return rows


def kernel_times(fa, batch, heads, kv_heads, seq, head_dim, causal=True,
                 reps=20, seed=0, only=("fwd", "dq", "dkv", "fused"),
                 window=0, q_super=None):
    """Milliseconds of each flash kernel alone (forward with lse; the
    two-kernel backward's dq and dkv; the fused backward, at a query
    superblock of ``q_super`` rows, the whole sequence by default) at one
    shape, bfloat16, under a band of ``window`` keys if one is given: each
    closure keeps ONE of the kernels (XLA drops a pallas_call whose
    results nobody reads; checked in the compiled text), runs ``reps``
    times back to back and is read once; the least of three such blocks.
    The backward arms call the two paths directly, whatever
    `_fa_backward` would choose at the shape. ``fa`` is the kernel module.
    Returns ({"fwd": ms, ...}, {"fwd": (o, lse), "dq": dq, "dkv": (dk,
    dv), "fused": (dq, dk, dv)}) for the kernels in ``only``."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    interp = jax.default_backend() == "cpu"
    g, rows = heads // kv_heads, batch * kv_heads
    rng = np.random.RandomState(seed)

    def rand(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32),
                           dtype=jnp.bfloat16)

    q, do = rand(rows, g, seq, head_dim), rand(rows, g, seq, head_dim)
    k, v = rand(rows, seq, head_dim), rand(rows, seq, head_dim)
    scale = head_dim ** -0.5
    fwd = jax.jit(lambda q, k, v: fa._fa_forward(
        q, k, v, causal, scale, interp, with_lse=True, window=window))
    o, lse = fwd(q, k, v)
    # flash's row sums D, the XLA pass `_fa_backward` makes before either
    # path: outside the timed kernels
    bwd_args = (q, k, v, do, lse, fa._row_sums(o, do))

    def split(*a):
        return fa._fa_backward_split(a, causal, scale, interp, window)

    arms = {"fwd": (fwd, (q, k, v)),
            "dq": (jax.jit(lambda *a: split(*a)[0]), bwd_args),
            "dkv": (jax.jit(lambda *a: split(*a)[1:]), bwd_args),
            "fused": (jax.jit(lambda *a: fa._fa_backward_fused(
                a, causal, scale, interp, window, q_super)), bwd_args)}
    ms, outs = {}, {}
    for name in only:
        f, xs = arms[name]
        if not interp:
            n = f.lower(*xs).compile().as_text().count(
                'custom_call_target="tpu_custom_call"')
            assert n == 1, "%s: %d kernels in the program, not 1" % (name, n)
        r = outs[name] = jax.block_until_ready(f(*xs))
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(reps):
                r = f(*xs)
            jax.block_until_ready(r)
            t = (time.perf_counter() - t0) / reps
            best = t if best is None else min(best, t)
        ms[name] = best * 1e3
    return ms, outs


def kernels(args):
    """The flash kernels' milliseconds at the benchmark cell's shape
    (lm_train_4k: batch 2, 24 heads over 2 KV heads, 4096, 128, causal,
    bfloat16): one layer's calls, the fused backward beside the dq and dkv
    kernels it replaces there, and how far its three results lie from
    theirs (norm of the difference over the norm). PERF.md (Findings, PRs
    26 and 32) has the readings this repeats. Then the same at the two
    8192-token cells' shapes (PERF.md, Findings), where the fused backward
    runs in the query superblocks `_fa_backward` chooses (and at half of
    that, to see what a superblock's length costs)."""
    import numpy as np
    import jax
    from mxnet_tpu.ops.pallas import flash_attention as fa

    on_cpu = jax.default_backend() == "cpu"
    shape = (1, 2, 1, 256, 128) if on_cpu else (2, 24, 2, 4096, 128)
    ms, outs = kernel_times(fa, *shape, causal=args.causal,
                            reps=1 if on_cpu else 50)
    print("kernels B=%d H=%d HKV=%d S=%d D=%d causal=%s: fwd %.3f ms  "
          "dq %.3f ms  dkv %.3f ms  fused %.3f ms  fused under dq+dkv by "
          "%.3f ms"
          % (shape + (args.causal, ms["fwd"], ms["dq"], ms["dkv"],
                      ms["fused"], ms["dq"] + ms["dkv"] - ms["fused"])))

    def gap(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    def gaps(outs):
        split = (outs["dq"],) + tuple(outs["dkv"])
        return "fused against dq/dkv, |difference| / |dq/dkv|: " + "  ".join(
            "%s %.3g" % (n, gap(a, b)) for n, a, b in
            zip(("dq", "dk", "dv"), outs["fused"], split))

    print(gaps(outs))
    # smallthinker_train_8k: 28 heads over 4 KV heads of 128, a global
    # layer and a 4096 band; lfm2_train_8k: two sequences, 32 heads over 8
    # KV heads of 64
    cells = ([("smallthinker_global", (1, 8, 2, 256, 128), 0),
              ("smallthinker_window", (1, 8, 2, 256, 128), 128),
              ("lfm2", (1, 8, 2, 256, 64), 0)] if on_cpu else
             [("smallthinker_global", (1, 28, 4, 8192, 128), 0),
              ("smallthinker_window", (1, 28, 4, 8192, 128), 4096),
              ("lfm2", (2, 32, 8, 8192, 64), 0)])
    for name, shape, window in cells:
        seq, head_dim = shape[3], shape[4]
        q_super = fa._fused_q_super(seq, seq, head_dim, 2)
        m, outs = kernel_times(fa, *shape, causal=args.causal,
                               reps=1 if on_cpu else 20, window=window,
                               q_super=q_super)
        half = ""
        if (q_super // 2) % fa._pick_block(seq, fa.BLOCK_Q) == 0:
            h, _ = kernel_times(fa, *shape, causal=args.causal,
                                reps=1 if on_cpu else 20, window=window,
                                q_super=q_super // 2, only=("fused",))
            half = "  fused at %d rows %.3f ms" % (q_super // 2, h["fused"])
        print("cell %s B=%d H=%d HKV=%d S=%d D=%d window=%d causal=%s: "
              "fwd %.3f ms  dq %.3f ms  dkv %.3f ms  fused at %d rows "
              "%.3f ms  fused under dq+dkv by %.3f ms%s"
              % ((name,) + shape + (window, args.causal, m["fwd"], m["dq"],
                                    m["dkv"], q_super, m["fused"],
                                    m["dq"] + m["dkv"] - m["fused"], half)))
        print("  " + gaps(outs))
        ms[name] = m
    return ms


def gqa(args):
    """Grouped-query attention: native narrow-kv flash kernel vs (a) the
    old repeat-kv-to-full-H flash path and (b) the XLA grouped einsum.
    The native kernel's win is KV HBM traffic (h/hkv fewer K/V bytes),
    so the gap grows with S and shrinks with hkv."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as att
    from mxnet_tpu.ops.pallas import flash_attention as fa

    on_cpu = jax.default_backend() == "cpu"
    interp = True if on_cpu else False
    configs = ([(1, 4, 2, 256, 128)] if on_cpu else
               [(4, 16, 4, 2048, 128), (4, 16, 2, 2048, 128),
                (4, 16, 4, 4096, 128), (4, 16, 1, 4096, 128),
                (1, 16, 2, 8192, 128)])
    for (B, H, HKV, S, D) in configs:
        g = H // HKV
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32),
                        dtype=jnp.bfloat16)
        k = jnp.asarray(rng.randn(B, HKV, S, D).astype(np.float32),
                        dtype=jnp.bfloat16)
        v = jnp.asarray(rng.randn(B, HKV, S, D).astype(np.float32),
                        dtype=jnp.bfloat16)

        def native(q, k, v):
            return fa.flash_attention(q, k, v, causal=args.causal,
                                      interpret=interp)

        def repeat(q, k, v):
            return fa.flash_attention(q, jnp.repeat(k, g, axis=1),
                                      jnp.repeat(v, g, axis=1),
                                      causal=args.causal, interpret=interp)

        def einsum(q, k, v):
            return att._grouped_attention(q, k, v, HKV, args.causal)

        # on-chip equivalence first
        base = np.asarray(jax.jit(einsum)(q, k, v), np.float32)
        for name, f in (("native", native), ("repeat", repeat)):
            out = np.asarray(jax.jit(f)(q, k, v), np.float32)
            md = np.abs(out - base).max()
            assert md < 3e-2, (name, md)

        def timeit(f, reps=3 if on_cpu else 100):
            return _min_time(jax.jit(lambda q, k, v: jnp.sum(
                f(q, k, v).astype(jnp.float32))), (q, k, v), reps)

        def timeit_fb(f, reps=3 if on_cpu else 50):
            return _min_time(_fb_scalar(f), (q, k, v), reps)

        tn, tr, te = timeit(native), timeit(repeat), timeit(einsum)
        print("gqa B=%d H=%d HKV=%d S=%d D=%d causal=%s fwd: "
              "native %.3f ms  repeat %.3f ms (%.2fx)  einsum %.3f ms "
              "(%.2fx)"
              % (B, H, HKV, S, D, args.causal, tn * 1e3, tr * 1e3,
                 tr / tn, te * 1e3, te / tn))
        tbn, tbr, tbe = (timeit_fb(native), timeit_fb(repeat),
                         timeit_fb(einsum))
        print("  fwd+bwd: native %.3f ms  repeat %.3f ms (%.2fx)  "
              "einsum %.3f ms (%.2fx)"
              % (tbn * 1e3, tbr * 1e3, tbr / tbn, tbe * 1e3, tbe / tbn))


def _lm_symbol(vocab, num_layers, num_heads, dm, dff, use_flash,
               num_kv_heads=0):
    """Decoder-only LM (models/transformer blocks, use_flash switchable)
    with a SCALAR loss head: the step's only fresh output is the loss,
    not a (batch*seq, vocab) probability buffer."""
    import mxnet_tpu as mx

    sym = mx.sym
    data = sym.Variable("data")
    x = sym.Embedding(data=data, input_dim=vocab, output_dim=dm,
                      name="embed")
    for i in range(num_layers):
        name = "layer%d" % i
        ln1_g = sym.Variable(name + "_ln1_gamma", shape=(dm,))
        ln1_b = sym.Variable(name + "_ln1_beta", shape=(dm,))
        h = sym.LayerNorm(data=x, gamma=ln1_g, beta=ln1_b,
                          name=name + "_ln1")
        # GQA: k/v projections shrink to num_kv_heads*head_dim and the
        # flash kernel streams them narrow (ops/attention.py)
        dkv = dm if not num_kv_heads else dm // num_heads * num_kv_heads
        q = sym.FullyConnected(data=h, num_hidden=dm, flatten=False,
                               no_bias=True, name=name + "_q")
        k = sym.FullyConnected(data=h, num_hidden=dkv, flatten=False,
                               no_bias=True, name=name + "_k")
        v = sym.FullyConnected(data=h, num_hidden=dkv, flatten=False,
                               no_bias=True, name=name + "_v")
        a = sym.MultiHeadAttention(query=q, key=k, value=v,
                                   num_heads=num_heads,
                                   num_kv_heads=num_kv_heads, causal=True,
                                   use_rope=True, use_flash=use_flash,
                                   name=name + "_attn")
        a = sym.FullyConnected(data=a, num_hidden=dm, flatten=False,
                               no_bias=True, name=name + "_o")
        x = x + a
        ln2_g = sym.Variable(name + "_ln2_gamma", shape=(dm,))
        ln2_b = sym.Variable(name + "_ln2_beta", shape=(dm,))
        h = sym.LayerNorm(data=x, gamma=ln2_g, beta=ln2_b,
                          name=name + "_ln2")
        h = sym.FullyConnected(data=h, num_hidden=dff, flatten=False,
                               name=name + "_ffn1")
        h = sym.Activation(data=h, act_type="gelu", name=name + "_gelu")
        h = sym.FullyConnected(data=h, num_hidden=dm, flatten=False,
                               name=name + "_ffn2")
        x = x + h
    lnf_g = sym.Variable("lnf_gamma", shape=(dm,))
    lnf_b = sym.Variable("lnf_beta", shape=(dm,))
    x = sym.LayerNorm(data=x, gamma=lnf_g, beta=lnf_b, name="lnf")
    pred = sym.Reshape(data=x, shape=(-1, dm))
    pred = sym.FullyConnected(data=pred, num_hidden=vocab, name="pred")
    logp = sym.log_softmax(pred, axis=-1)
    label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
    onehot = sym.one_hot(label, depth=vocab)
    nll = sym._mul_scalar(sym.mean(sym.sum(sym._mul(logp, onehot), axis=1)),
                          scalar=-1.0)
    return sym.MakeLoss(nll, name="loss")


def lm_train(args, use_flash, num_kv_heads=0, remat=False, steps=None,
             quiet=False):
    _remat_set_here = remat and not os.environ.get("MXNET_BACKWARD_DO_MIRROR")
    if _remat_set_here:
        os.environ["MXNET_BACKWARD_DO_MIRROR"] = "1"
    try:
        return _lm_train_inner(args, use_flash, num_kv_heads, steps, quiet)
    finally:
        # never strip a USER-set env var, and never leak ours past an
        # OOM
        if _remat_set_here:
            os.environ.pop("MXNET_BACKWARD_DO_MIRROR", None)


def _lm_train_inner(args, use_flash, num_kv_heads, steps, quiet):
    import numpy as np
    import jax
    import mxnet_tpu as mx

    N, T = args.batch_size, args.seq_len
    sym = _lm_symbol(args.vocab, args.num_layers, args.num_heads,
                     args.model_dim, 4 * args.model_dim, use_flash,
                     num_kv_heads=num_kv_heads)
    dev = (mx.Context("tpu", 0) if jax.default_backend() != "cpu"
           else mx.cpu())
    mod = mx.mod.Module(sym, context=dev,
                        compute_dtype=os.environ.get("BENCH_DTYPE",
                                                     "bfloat16"))
    mod.bind(data_shapes=[("data", (N, T))],
             label_shapes=[("softmax_label", (N, T))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
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
    nsteps = steps or args.steps
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(nsteps):
            mod.fit_step(batch)
        sync()
        times.append((time.perf_counter() - t0) / nsteps)
    t = sorted(times)[len(times) // 2]
    # sample memory stats while the module's buffers are LIVE (callers
    # reading stats after return would see the post-free residual)
    mem = {}
    try:
        mem = jax.devices()[0].memory_stats() or {}
    except Exception:
        pass
    mfu = lm_mfu(sym, N, T, t)
    if not quiet:
        print("transformer-lm(flash=%s) L=%d dm=%d heads=%d vocab=%d bs=%d "
              "seq=%d: %.2f ms/step  %.0f tokens/s  %s"
              % (use_flash, args.num_layers, args.model_dim, args.num_heads,
                 args.vocab, N, T, t * 1e3, N * T / t, _mfu_str(mfu)))
    return t, mem, mfu


def lm_mfu(sym, batch, seq, step_s):
    """Model FLOPs utilization of one training step: analytic matmul
    FLOPs over the LM graph (flops.count_flops — FC projections + the
    MultiHeadAttention node at its USEFUL causal count), 3x for the
    training step, against the chip's nominal bf16 peak. None (not a
    number) on the CPU backend and for non-bf16 compute (the bf16
    denominator would be wrong), and the BENCH_PEAK_TFLOPS calibration
    override is honored. An accelerator
    missing from flops.CHIP_PEAK_BF16 raises."""
    import jax
    from mxnet_tpu import flops as _flops

    if os.environ.get("BENCH_DTYPE", "bfloat16") != "bfloat16":
        return None
    fwd = _flops.count_flops(sym, data=(batch, seq),
                             softmax_label=(batch, seq))["total"]
    if os.environ.get("BENCH_PEAK_TFLOPS"):
        peak = float(os.environ["BENCH_PEAK_TFLOPS"]) * 1e12
    elif jax.devices()[0].platform == "cpu":  # CPU smoke: no MFU
        return None
    else:
        peak, _ = _flops.chip_peak_flops(jax.devices()[0])
    return 100.0 * _flops.training_flops(fwd) / step_s / peak


def _mfu_str(mfu):
    return "MFU n/a" if mfu is None else "%.1f%% MFU" % mfu


def long_context(args):
    """Single-chip long-context training table (SURVEY §5.7: flash
    backward + narrow-kv GQA — and remat only where it actually buys
    reach — replace bucketing at scale): every row prints EXACT ms/step,
    tokens/s, and MFU (5-step blocks, median of 3, same methodology as
    every other table in docs/perf.md), plus a plain-XLA-attention
    comparison wherever that program compiles ("OOM" stated where the
    S^2 buffers do not).

    The published docs/perf.md table is
    ``bench_transformer.py --long --num-layers 2`` (L=2, d_model 1024,
    8 heads, GQA hkv=2)."""
    rows = []
    # (seq, batch, remat): bs>1 "packed" rows are the throughput-optimal
    # configs; remat=False rows show everything through 64k fits HBM
    # without recompute at this model size (activations scale ~S)
    cfgs = ((16384, 1, True), (16384, 4, False), (32768, 1, False),
            (32768, 2, False), (65536, 1, True), (65536, 1, False))
    if os.environ.get("BENCH_LONG_SEQS"):  # CPU smoke / custom sweeps
        cfgs = tuple((int(s), 1, True) for s in
                     os.environ["BENCH_LONG_SEQS"].split(","))
    kv_heads = 2
    for seq, batch, remat in cfgs:
        args.seq_len = seq
        args.batch_size = batch
        try:
            t, stats, mfu = lm_train(args, use_flash=True,
                                     num_kv_heads=kv_heads, remat=remat,
                                     steps=5, quiet=True)
        except Exception as e:
            print("long-context seq=%d bs=%d remat=%s FAILED: %s: %s"
                  % (seq, batch, remat, type(e).__name__, str(e)[:120]))
            continue
        used = stats.get("peak_bytes_in_use",
                         stats.get("bytes_in_use", 0)) / 1e9
        limit = stats.get("bytes_limit", 0) / 1e9
        hbm = ("HBM %.2f/%.2f GB" % (used, limit) if limit
               else "HBM n/a (runtime exposes no memory_stats)")
        plain = ""
        if not os.environ.get("BENCH_LONG_SKIP_PLAIN"):
            # plain-XLA column for EVERY row: same model,
            # use_flash=False; expected to stop compiling once the S^2
            # score buffers exceed HBM. Real OOMs are labeled as such;
            # anything else prints its error so a harness bug cannot
            # masquerade as a performance claim.
            try:
                tp, _, _ = lm_train(args, use_flash=False,
                                    num_kv_heads=kv_heads, remat=remat,
                                    steps=3, quiet=True)
                plain = "  plain-XLA %.1f ms (flash %.2fx)" % (tp * 1e3,
                                                               tp / t)
            except Exception as e:
                msg = "%s: %s" % (type(e).__name__, e)
                if ("memory" in msg.lower() or "hbm" in msg.lower()
                        or "RESOURCE_EXHAUSTED" in msg
                        or "compile" in msg.lower()):
                    plain = "  plain-XLA: does not compile (S^2 OOM)"
                else:
                    plain = "  plain-XLA FAILED (%s)" % msg[:100]
        rows.append((seq, batch, batch * seq / t, t * 1e3, used, limit))
        print("long-context seq=%d bs=%d remat=%s (GQA hkv=%d): "
              "%.1f ms/step  %.0f tokens/s  %s  %s%s"
              % (seq, batch, remat, kv_heads, t * 1e3, batch * seq / t,
                 _mfu_str(mfu), hbm, plain))
    return rows


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=1024)
    p.add_argument("--vocab", type=int, default=10000)
    p.add_argument("--num-layers", type=int, default=4)
    p.add_argument("--num-heads", type=int, default=8)
    p.add_argument("--model-dim", type=int, default=1024,
                   help="head_dim = model_dim/num_heads; 1024/8 = 128 "
                        "selects the flash kernel")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--causal", action=argparse.BooleanOptionalAction,
                   default=True)
    p.add_argument("--skip-micro", action="store_true")
    p.add_argument("--skip-train", action="store_true")
    p.add_argument("--gqa", action="store_true",
                   help="run ONLY the grouped-query attention micro")
    p.add_argument("--kernels", action="store_true",
                   help="run ONLY the flash kernels' timing at the "
                        "lm_train_4k cell's shape")
    p.add_argument("--long", action="store_true",
                   help="run ONLY the long-context 16k/32k LM headline")
    args = p.parse_args()
    if args.kernels:
        kernels(args)
        return
    if args.gqa:
        gqa(args)
        return
    if args.long:
        long_context(args)
        return
    if not args.skip_micro:
        micro(args)
    if not args.skip_train:
        t_flash = lm_train(args, use_flash=True)[0]
        t_plain = lm_train(args, use_flash=False)[0]
        print("flash-vs-plain in training: %.2fx" % (t_plain / t_flash))


if __name__ == "__main__":
    main()
