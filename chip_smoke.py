#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, from the root of the repo, no arguments: drives the main path
once at the full width of the decoder LM (d_model 2048, 16 heads x 128, 4 kv
heads, ffn 8192, 4 layers, vocab 10000; weights random from a seed) through
the entry points a user calls, and checks what comes out by the repo's own
means.

    kernels    each Pallas kernel compiled (not interpreted) at a shape the
               package selects it for, against the XLA math at bf16 tolerance
    train      Module.fit at bs32 x seq2048 on the fused step, then
               save_checkpoint
    serve      load_checkpoint -> InferenceServer(decode=...) -> HttpFrontend
               -> concurrent POST /v1/generate SSE streams from threads of
               this process, once on the slot-slab KV arm and once paged
    multichip  the trainer again over four devices (ZeRO-1), when the
               process sees four; otherwise "multichip: skipped"

It refuses to run, before building any model, when
``jax.devices()[0].platform != "tpu"``; it builds both native libraries from
``native/*.cc`` first; a failing phase raises and the process exits non-zero.
The last line of standard output is the verdict, one JSON object.

The phases are functions of a :class:`Sizes`, so that a tier-1 test calls
them tiny on the CPU mesh with the kernels in interpret mode
(tests/test_zz_chip_smoke.py). ``python chip_smoke.py`` always runs
:data:`FULL`. ``--phases a,b`` restricts a debugging run on chip time and
prints no verdict.
"""
import argparse
import concurrent.futures
import dataclasses
import gc
import http.client
import importlib.metadata
import json
import sys
import tempfile
import threading
import time

import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    # the model
    d_model: int = 2048
    heads: int = 16
    kv_heads: int = 4
    ffn: int = 8192
    layers: int = 4
    vocab: int = 10000
    # trainer
    batch: int = 32
    seq: int = 2048
    steps: int = 4
    # server: prompt lengths, several in flight at once over `slots`
    prompts: tuple = (128, 300, 512, 777, 1024)
    prefill_buckets: tuple = (128, 512, 1024)
    new_tokens: int = 64
    slots: int = 4
    block_tokens: int = 16
    # kernels: flash at the LM's shape (B*Hkv 128, G 4), streaming beyond
    # the resident regime (0 skips it), LSTM step
    flash_batch: int = 32
    stream_seq: int = 16384
    # flash at head size 64 (64-lane blocks), 4 query heads a KV head:
    # a sequence the fused backward takes whole, and one it takes in
    # query superblocks
    head64_seqs: tuple = (2048, 8192)
    lstm_n: int = 128
    lstm_h: int = 512
    # the expert layer's grouped products: sorted rows, each expert's width
    expert_rows: int = 8192
    expert_ffn: int = 512
    # True: Pallas interpreter (the CPU test); False: compiled for the chip
    interpret: bool = False

    @property
    def head_dim(self):
        return self.d_model // self.heads

    @property
    def max_context(self):
        blocks = -(-(max(self.prompts) + self.new_tokens) // self.block_tokens)
        return blocks * self.block_tokens


FULL = Sizes()
PHASES = ("kernels", "train", "serve", "multichip")
PALLAS_CALL = "tpu_custom_call"  # what a compiled Pallas kernel lowers to


class CompileMeter:
    """XLA backend compiles as JAX itself reports them: seconds, how many
    programs went through the compiler's entry (a persistent-cache hit
    passes through it too), and how many of those the cache answered."""

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self.secs, self.programs, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.secs += secs
                self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def snapshot(self):
        with self._lock:
            return self.secs, self.programs, self.cache_hits


def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.isfinite(got).all(), "non-finite values"
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-6))


def _peak_hbm(devices):
    """Largest ``peak_bytes_in_use`` over ``devices``; None where the
    backend keeps no memory statistics (the CPU)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return max(s["peak_bytes_in_use"] for s in stats)


# --- kernels -----------------------------------------------------------------

BF16_TOL = 3e-2  # max |kernel - XLA| over max |XLA|, bf16 operands


def check_flash(sizes, seq, batch, heads, kv_heads, ref_batch,
                head_dim=None):
    """Flash attention forward and backward, causal GQA, bf16, against the
    grouped-einsum XLA math. The kernel runs at the full (batch, heads, seq)
    shape; the reference, whose score matrix is quadratic in seq, on the
    first ``ref_batch`` batch rows (rows are independent grid cells).
    ``head_dim``: another head size than the model's."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention
    from mxnet_tpu.ops.pallas import flash_attention as fa

    d = head_dim or sizes.head_dim
    rng = np.random.RandomState(seq)
    q = jnp.asarray(rng.randn(batch, heads, seq, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(batch, kv_heads, seq, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(batch, kv_heads, seq, d), jnp.bfloat16)
    w = jnp.asarray(rng.randn(batch, heads, seq, d), jnp.bfloat16)
    # None leaves the choice to the package's own gate; the interpreter has
    # to be asked for by name
    mode = True if sizes.interpret else None

    def kernel(q, k, v, w):
        out = fa.flash_attention(q, k, v, causal=True, interpret=mode)
        return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)), out

    def xla(q, k, v, w):
        out = attention._grouped_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), kv_heads, True, scale=1.0 / d ** 0.5)
        return jnp.sum(out * w.astype(jnp.float32)), out

    step = jax.jit(jax.value_and_grad(kernel, argnums=(0, 1, 2),
                                      has_aux=True))
    if not sizes.interpret:
        text = step.lower(q, k, v, w).as_text()
        # forward and backward: one fused kernel, or dq and dkv where its
        # accumulators do not fit VMEM
        assert text.count(PALLAS_CALL) >= 2, (
            "flash forward and backward not both selected at seq %d: %d "
            "Pallas calls in the lowered program"
            % (seq, text.count(PALLAS_CALL)))
    (_, out), grads = step(q, k, v, w)
    r = slice(0, ref_batch)
    (_, want), want_grads = jax.jit(jax.value_and_grad(
        xla, argnums=(0, 1, 2), has_aux=True))(q[r], k[r], v[r], w[r])
    errs = {"out": _rel_err(out[r], want)}
    for name, g, wg in zip(("dq", "dk", "dv"), grads, want_grads):
        errs[name] = _rel_err(g[r], wg)
    assert max(errs.values()) <= BF16_TOL, (seq, errs)
    return errs


def check_lstm(sizes, ctx):
    """The Pallas LSTM step alone, then through the RNN op (which selects it
    from the shape on the TPU), against the jnp scan."""
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.ops import rnn_fused
    from mxnet_tpu.ops.pallas import lstm

    n, h, t = sizes.lstm_n, sizes.lstm_h, 8
    rng = np.random.RandomState(1)
    ib = jnp.asarray(rng.randn(n, 4 * h) * 0.5, jnp.float32)
    h0 = jnp.asarray(rng.randn(n, h) * 0.5, jnp.float32)
    c0 = jnp.asarray(rng.randn(n, h) * 0.5, jnp.float32)
    wh = jnp.asarray(rng.randn(4 * h, h) * 0.05, jnp.float32)
    got = lstm.lstm_step(ib, h0, c0, wh, interpret=sizes.interpret)
    (want_h, want_c), _ = rnn_fused._lstm_scan_jnp(ib[None], h0, c0, wh, h)
    errs = {"step_h": _rel_err(got[0], want_h),
            "step_c": _rel_err(got[1], want_c)}

    assert lstm.use_for(n, h) == (not sizes.interpret), \
        "RNN op's Pallas gate at N %d H %d: %r" % (n, h, lstm.use_for(n, h))
    x = rng.randn(t, n, h).astype(np.float32) * 0.5
    blob = rng.randn(rnn_fused.rnn_param_size(1, h, h, "lstm")) \
        .astype(np.float32) * 0.05
    out = mx.nd.RNN(mx.nd.array(x, ctx=ctx), mx.nd.array(blob, ctx=ctx),
                    mx.nd.array(np.asarray(h0)[None], ctx=ctx),
                    mx.nd.array(np.asarray(c0)[None], ctx=ctx),
                    state_size=h, num_layers=1, mode="lstm").asnumpy()
    wi, whh, bi, bh = rnn_fused._unpack_params(
        jnp.asarray(blob), 1, h, h, "lstm", 1)[0][0]
    _, want = rnn_fused._lstm_scan_jnp(
        jnp.asarray(x) @ wi.T + (bi + bh), h0, c0, whh, h)
    errs["rnn_op"] = _rel_err(out, want)
    # f32 operands; the MXU's default f32 matmul rounds them to bf16
    assert max(errs.values()) <= BF16_TOL, errs
    return errs


def check_grouped(sizes):
    """The expert layer's grouped products (``ops/pallas/grouped_matmul``:
    forward, dX and dW over sorted rows cut into eight uneven groups, one
    of them empty) against ``jax.lax.ragged_dot``, bf16; on the chip the
    layer's own gate must pick them at this shape."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import moe
    from mxnet_tpu.ops.pallas import grouped_matmul as gm

    rows, k, n, e = sizes.expert_rows, sizes.d_model, sizes.expert_ffn, 8
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(rows, k), jnp.bfloat16)
    w = jnp.asarray(rng.randn(e, n, k) * k ** -0.5, jnp.bfloat16)
    ct = jnp.asarray(rng.randn(rows, n), jnp.bfloat16)
    cuts = np.sort(rng.randint(0, rows // 4, e - 2))
    groups = np.diff(np.concatenate([[0], cuts, [rows // 4, rows // 4]]))
    groups[-1] = rows - rows // 4  # stretched over the empty rows
    groups = jnp.asarray(groups, jnp.int32)
    path = moe.product_path(rows, k, n, x.dtype)
    assert path == ("ragged_dot" if sizes.interpret else "pallas"), path

    def grads(product):
        def loss(x, w):
            y = product(x, w)
            return jnp.sum(y.astype(jnp.float32) * ct.astype(jnp.float32)), y
        (_, y), g = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(x, w)
        return (y,) + g

    got = grads(lambda x, w: gm.grouped_matmul(x, w, groups,
                                               sizes.interpret))
    want = grads(lambda x, w: moe._ragged(x, w, groups))
    errs = {name: _rel_err(a, b)
            for name, a, b in zip(("out", "dx", "dw"), got, want)}
    assert max(errs.values()) <= BF16_TOL, errs
    return errs


def check_short_conv(sizes):
    """The gated short convolution's elementwise part
    (``ops/pallas/short_conv``: one pass forward, one backward, an
    eight-row halo a tile) against the XLA formulation, bf16, two
    sequences; on the chip the op's own gate must pick the kernels."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import shortconv
    from mxnet_tpu.ops.pallas import short_conv

    d, taps = sizes.d_model, 3
    seq = max(min(sizes.seq, 2048), 2 * short_conv.ROWS)  # tiles with a halo
    rng = np.random.RandomState(6)
    u = jnp.asarray(rng.randn(2, seq, 3 * d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(d, taps) * 0.5, jnp.bfloat16)
    w = jnp.asarray(rng.randn(2, seq, d), jnp.bfloat16)
    path = shortconv.conv_path((2, seq, d), taps)
    assert path == ("xla" if sizes.interpret else "pallas"), path

    def grads(conv):
        def loss(u, k):
            y = conv(u, k)
            return jnp.sum(y.astype(jnp.float32) * w.astype(jnp.float32)), y
        (_, y), g = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(u, k)
        return (y,) + g

    got = grads(lambda u, k: short_conv.gated_conv(u, k, sizes.interpret))
    want = grads(shortconv._gated_conv)
    errs = {name: _rel_err(a, b)
            for name, a, b in zip(("out", "du", "dk"), got, want)}
    assert max(errs.values()) <= BF16_TOL, errs
    return errs


def check_rtc(ctx):
    """One runtime-compiled user Pallas kernel (mx.rtc), compiled for the
    chip when there is one."""
    import mxnet_tpu as mx

    rng = np.random.RandomState(2)
    x = rng.randn(256, 512).astype(np.float32)
    y = rng.randn(256, 512).astype(np.float32)
    out = mx.nd.zeros((256, 512), ctx=ctx)
    mx.rtc.create("axpy", ["x", "y"], ["out"], """
        def kernel(x_ref, y_ref, out_ref):
            out_ref[...] = x_ref[...] * 2.0 + y_ref[...]
    """).push([mx.nd.array(x, ctx=ctx), mx.nd.array(y, ctx=ctx)], [out])
    err = _rel_err(out.asnumpy(), x * 2.0 + y)
    assert err <= 1e-6, err
    return {"axpy": err}


def check_flash_partitioned(sizes, devices):
    """The flash kernel inside a program partitioned over ``devices``: XLA's
    SPMD pass cannot split a Mosaic kernel, so the op splits itself over the
    announced mesh. Same values as on one device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from mxnet_tpu.ops.pallas import flash_attention as fa
    from mxnet_tpu.parallel.mesh import partitioned_over

    mesh = Mesh(np.array(devices), ("data",))
    shape = (2 * len(devices), sizes.kv_heads, sizes.seq, sizes.head_dim)
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
               for _ in range(3))
    mode = True if sizes.interpret else None

    def attend(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, interpret=mode)

    def attend_partitioned(q, k, v):
        with partitioned_over(mesh):
            return attend(q, k, v)

    rows = NamedSharding(mesh, PartitionSpec("data"))
    got = jax.jit(attend_partitioned)(*(jax.device_put(a, rows)
                                        for a in (q, k, v)))
    assert got.sharding.is_equivalent_to(rows, got.ndim), got.sharding
    err = _rel_err(got, jax.jit(attend)(q, k, v))
    assert err <= 1e-6, err
    return {"partitioned_vs_one_device": err}


def phase_kernels(sizes, ctx):
    from mxnet_tpu.ops.pallas import flash_attention as fa

    facts = {"flash_resident": check_flash(
        sizes, sizes.seq, sizes.flash_batch, sizes.heads, sizes.kv_heads,
        ref_batch=min(2, sizes.flash_batch))}
    if sizes.stream_seq:
        assert sizes.stream_seq > fa._RESIDENT_MAX
        facts["flash_streaming"] = check_flash(
            sizes, sizes.stream_seq, 1, 2, 1, ref_batch=1)
    for seq in sizes.head64_seqs:
        facts["flash_head64_%d" % seq] = check_flash(
            sizes, seq, 1, 8, 2, ref_batch=1, head_dim=64)
    facts["lstm"] = check_lstm(sizes, ctx)
    facts["grouped_matmul"] = check_grouped(sizes)
    facts["short_conv"] = check_short_conv(sizes)
    facts["rtc"] = check_rtc(ctx)
    return facts


# --- trainer -----------------------------------------------------------------

def _lm_symbol(sizes, scalar_loss):
    from mxnet_tpu import models

    return models.get_symbol(
        "transformer-lm", num_classes=sizes.vocab, num_layers=sizes.layers,
        num_heads=sizes.heads, model_dim=sizes.d_model, ffn_dim=sizes.ffn,
        num_kv_heads=sizes.kv_heads, scalar_loss=scalar_loss)


def _token_batch(sizes):
    """One (batch, seq) batch of token ids and next-token labels from the
    seed; the trainer repeats it and the server takes its prompts from it."""
    rng = np.random.RandomState(0)
    x = rng.randint(0, sizes.vocab, (sizes.batch, sizes.seq))
    return x.astype(np.float32), np.roll(x, -1, axis=1).astype(np.float32)


def train(sizes, contexts, prefix, meter):
    """bind / init_params / init_optimizer / ``steps`` fit steps on one
    repeated batch / save_checkpoint, through Module.fit. Returns the facts
    to print and the live ``(params, states)``; itself asserts what holds
    on any device list: fused path, loss finite and falling, state on
    exactly those devices, nothing compiled after the first step."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.analysis import compile_witness

    devices = {c.jax_device() for c in contexts}
    x, y = _token_batch(sizes)
    it = mx.io.NDArrayIter(np.tile(x, (sizes.steps, 1)),
                           np.tile(y, (sizes.steps, 1)),
                           batch_size=sizes.batch,
                           label_name="softmax_label")
    mx.random.seed(0)
    mod = mx.mod.Module(_lm_symbol(sizes, scalar_loss=True), context=contexts,
                        compute_dtype="bfloat16")
    losses, programs_after_first = [], []

    def batch_end(param):
        losses.append(float(param.eval_metric.get()[1]))
        param.eval_metric.reset()
        if param.nbatch == 0:
            compile_witness.steady_state()
        programs_after_first.append(meter.snapshot()[1])

    compile_witness.reset()
    # kvstore=None: the fused step owns the gradient reduction (ZeRO-1 over
    # a device list); a kvstore would send fit_step down the unfused path.
    # The loss is already a mean over the batch, so no 1/batch rescale.
    mod.fit(it, num_epoch=1, kvstore=None, eval_metric=mx.metric.Loss(),
            initializer=mx.initializer.Xavier(factor_type="in",
                                              magnitude=2.0),
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9,
                              "rescale_grad": 1.0},
            batch_end_callback=batch_end)
    assert mod.fit_step_path == "fused", \
        "fit_step took the %s path" % mod.fit_step_path
    assert len(losses) == sizes.steps and np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], "loss did not fall: %s" % losses
    assert programs_after_first[-1] == programs_after_first[0], (
        "%d program(s) compiled after the first step"
        % (programs_after_first[-1] - programs_after_first[0]))
    assert compile_witness.compiles_after_steady_total() == 0, \
        compile_witness.violations()

    params, states = mod.fit_step_arrays()
    leaves = jax.tree_util.tree_leaves((params, states))
    for leaf in leaves:
        assert leaf.sharding.device_set <= devices, \
            (leaf.shape, leaf.sharding)
    assert {d for leaf in leaves for d in leaf.sharding.device_set} \
        == devices
    lowered = mod.lower_fit_step().as_text()
    mod.save_checkpoint(prefix, 1)
    facts = {"losses": losses, "pallas_calls": lowered.count(PALLAS_CALL),
             "n_params": int(sum(np.prod(p.shape) for p in params.values()))}
    return facts, (params, states)


def phase_train(sizes, ctx, prefix, meter):
    from mxnet_tpu.ops import pallas

    facts, _ = train(sizes, [ctx], prefix, meter)
    if pallas.on_tpu():
        # at least 2 kernels (fwd, fused backward) per layer
        assert facts["pallas_calls"] >= 2 * sizes.layers, \
            "Pallas flash attention missing from the lowered train step " \
            "(%d calls)" % facts["pallas_calls"]
    return facts


# --- server ------------------------------------------------------------------

def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        resp.read()
        return resp.status
    finally:
        conn.close()


def _generate(port, prompt, new_tokens):
    """POST /v1/generate, read the SSE stream to its end, return the ids."""
    from mxnet_tpu.serving.frontend import iter_sse

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    try:
        conn.request("POST", "/v1/generate",
                     json.dumps({"prompt": prompt,
                                 "max_new_tokens": new_tokens}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200, (resp.status, resp.read())
        events = list(iter_sse(resp))
    finally:
        conn.close()
    assert events[-1][0] == "done", events[-1]
    tokens = [d["token"] for e, d in events if e == "token"]
    assert len(tokens) == new_tokens == events[-1][1]["tokens"], events[-1]
    return tokens


# The decode programs and the Predictor are different XLA programs over the
# same weights, and on the TPU an f32 matmul rounds its operands to bf16: the
# two may order a near-tie differently. The server's first token has to be
# the reference's argmax or within this many nats of it (a token picked at
# random sits several nats below).
FIRST_TOKEN_TOL = 2e-2


def serve_arm(sizes, sym, arg_params, prompts, ref_logp, paged, meter):
    """One server life on one KV arm: ready, each prompt alone (which
    builds every program the arm needs), then all at once."""
    from mxnet_tpu import serving
    from mxnet_tpu.analysis import compile_witness

    decode = serving.GenerateConfig(
        num_heads=sizes.heads, num_kv_heads=sizes.kv_heads,
        slots=sizes.slots, max_context=sizes.max_context,
        prefill_buckets=sizes.prefill_buckets,
        max_new_tokens=sizes.new_tokens, paged=paged,
        block_tokens=sizes.block_tokens)
    fixed = min(sizes.prefill_buckets)  # the fixed-shape path's one rung
    compile_witness.reset()
    t0 = time.perf_counter()
    server = serving.InferenceServer(
        sym, arg_params, {"data": (fixed,), "softmax_label": (fixed,)},
        config=serving.ServingConfig(buckets=(1,)), decode=decode)
    frontend = serving.HttpFrontend(server, serving.FrontendConfig(port=0))
    frontend.start(wait_ready=True, ready_timeout_s=600)
    try:
        port = frontend.port
        assert _get(port, "/readyz") == 200
        alone = [_generate(port, p, sizes.new_tokens) for p in prompts]
        compile_witness.steady_state()
        programs = meter.snapshot()[1]
        with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
            futures = [pool.submit(_generate, port, p, sizes.new_tokens)
                       for p in prompts]
            together = [f.result() for f in futures]
        stats = server.decode_stats()
    finally:
        frontend.stop(drain=True)
    assert not server.ready(), "stop(drain=True) left the server ready"
    for n, (a, b) in enumerate(zip(alone, together)):
        assert a == b, "prompt %d: stream in a full batch differs from " \
            "the stream alone:\n%s\n%s" % (n, a, b)
    first_gap = [float(lp.max() - lp[t[0]]) for lp, t in zip(ref_logp, alone)]
    assert max(first_gap) <= FIRST_TOKEN_TOL, (
        "first tokens %s sit %s nats below the Predictor's argmax %s"
        % ([t[0] for t in alone], first_gap,
           [int(lp.argmax()) for lp in ref_logp]))
    assert meter.snapshot()[1] == programs, \
        "%d program(s) compiled after steady" % (
            meter.snapshot()[1] - programs)
    assert compile_witness.compiles_after_steady_total() == 0, \
        compile_witness.violations()
    # the decode program set logs and falls to plain jit when its AOT build
    # fails; here that is a failure
    built = [r["key"] for r in
             compile_witness.compile_witness_report()["records"]]
    assert not [k for k in built if k.endswith(":jit_fallback")], built
    return {"decode_programs": stats["compiles"], "steps": stats["steps"],
            "first_token_gap_nats": max(first_gap),
            "wall_s": round(time.perf_counter() - t0, 1)}


def phase_serve(sizes, prefix, meter):
    import mxnet_tpu as mx
    from mxnet_tpu import predict

    _, arg_params, _ = mx.model.load_checkpoint(prefix, 1)
    sym = _lm_symbol(sizes, scalar_loss=False)
    x, _ = _token_batch(sizes)
    prompts = [[int(t) for t in x[i % sizes.batch, :n]]
               for i, n in enumerate(sizes.prompts)]
    # the reference: Predictor.forward at each prompt's last position
    # (causal, so the zero padding behind it changes nothing)
    width = max(sizes.prefill_buckets)
    ref = predict.Predictor(sym.tojson(), arg_params,
                            {"data": (1, width), "softmax_label": (1, width)})
    ref_logp = []
    for p in prompts:
        ids = np.zeros((1, width), np.float32)
        ids[0, :len(p)] = p
        probs = ref.forward(
            data=ids, softmax_label=np.zeros((1, width), np.float32)
        )[0].asnumpy()
        assert probs.shape == (width, sizes.vocab) \
            and np.isfinite(probs).all()
        ref_logp.append(np.log(probs[len(p) - 1]))
    del ref
    gc.collect()
    return {"slab": serve_arm(sizes, sym, arg_params, prompts, ref_logp,
                              False, meter),
            "paged": serve_arm(sizes, sym, arg_params, prompts, ref_logp,
                               True, meter)}


# --- four devices ------------------------------------------------------------

def phase_multichip(sizes, contexts, prefix, one_chip_losses, meter):
    """The trainer over four devices: ZeRO-1 state on all four, every one
    holding bytes, the one-chip losses reproduced; and a decode server asked
    for one replica per device refuses rather than stack them on device 0."""
    import mxnet_tpu as mx
    from mxnet_tpu import serving

    devices = [c.jax_device() for c in contexts]
    flash = check_flash_partitioned(sizes, devices)
    facts, (params, states) = train(sizes, contexts, prefix + "-dp4", meter)
    facts["flash"] = flash
    sharded = [n for n, p in params.items()
               if len(p.sharding.device_set) == 4 and not
               p.sharding.is_fully_replicated]
    assert sharded, "no parameter is sharded 1/4 (ZeRO-1 not in effect)"
    for n in sharded:
        for leaf in (states[n] if isinstance(states[n], tuple)
                     else (states[n],)):
            assert leaf is None or leaf.sharding == params[n].sharding, n
    in_use = [d.memory_stats() for d in devices]
    if all(s is not None for s in in_use):
        assert all(s["bytes_in_use"] > 0 for s in in_use), in_use
    np.testing.assert_allclose(facts["losses"], one_chip_losses, rtol=2e-2)
    facts["sharded_params"] = len(sharded)
    del params, states

    _, arg_params, _ = mx.model.load_checkpoint(prefix, 1)
    fixed = min(sizes.prefill_buckets)
    try:
        serving.InferenceServer(
            _lm_symbol(sizes, scalar_loss=False), arg_params,
            {"data": (fixed,), "softmax_label": (fixed,)},
            config=serving.ServingConfig(buckets=(1,), replicas=4),
            devices=devices,
            decode=serving.GenerateConfig(num_heads=sizes.heads,
                                          num_kv_heads=sizes.kv_heads))
    except serving.ServingError as e:
        facts["decode_replicas_per_device"] = "refused: %s" % e
    else:
        raise AssertionError("decode server took replicas=4 over four "
                             "devices without placing them per device")
    return facts


# --- the run -----------------------------------------------------------------

def _run_phase(name, fn, meter, devices):
    t0 = time.perf_counter()
    s0, p0, h0 = meter.snapshot()
    facts = fn()
    s1, p1, h1 = meter.snapshot()
    peak = _peak_hbm(devices)
    print("%s: ok wall %.1fs compile %.1fs (%d programs, %d from the "
          "persistent cache) peak_hbm %s %s"
          % (name, time.perf_counter() - t0, s1 - s0, p1 - p0, h1 - h0,
             "n/a" if peak is None else "%.2fGiB" % (peak / 2 ** 30),
             json.dumps(facts, default=float)), flush=True)
    return facts


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma list out of %s; a restricted run is for "
                         "debugging and prints no verdict" % (PHASES,))
    phases = ap.parse_args(argv).phases.split(",")
    assert set(phases) <= set(PHASES), phases

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("chip_smoke: refusing to run: jax.devices()[0].platform is "
              "%r, not 'tpu' (%d %s device(s))"
              % (dev.platform, len(jax.devices()), dev.device_kind),
              file=sys.stderr)
        return 2

    import jaxlib
    import mxnet_tpu as mx
    from mxnet_tpu import base, engine, flops, native
    from mxnet_tpu.analysis import compile_witness
    from mxnet_tpu.ops import pallas

    native.build(force=True)  # never a .so that happens to lie on the disk
    cache_dir = base.init_compile_cache()
    meter = CompileMeter()
    compile_witness.enable(True)
    devices = jax.devices()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print("device: %s jax %s jaxlib %s libtpu %s engine %s native_io %s "
          "compile_cache %s peak_bf16 %.0f TFLOP/s"
          % (json.dumps(device), jax.__version__, jaxlib.__version__,
             importlib.metadata.version("libtpu"),
             type(engine.get()).__name__, native.available(), cache_dir,
             flops.chip_peak_flops(dev)[0] / 1e12), flush=True)
    assert isinstance(engine.get(), engine.NativeEngine), \
        "engine.get() is %s" % type(engine.get()).__name__
    assert native.available(), "native io library did not load"
    assert pallas.on_tpu()

    t0 = time.perf_counter()
    ctx = mx.tpu(0)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        prefix = work + "/lm"
        losses = None
        if "kernels" in phases:
            _run_phase("kernels", lambda: phase_kernels(FULL, ctx), meter,
                       devices)
        if "train" in phases:
            losses = _run_phase(
                "train", lambda: phase_train(FULL, ctx, prefix, meter),
                meter, devices)["losses"]
            gc.collect()
        if "serve" in phases:
            _run_phase("serve", lambda: phase_serve(FULL, prefix, meter),
                       meter, devices)
            gc.collect()
        if "multichip" in phases:
            if len(devices) < 4:
                print("multichip: skipped, %d device(s)" % len(devices),
                      flush=True)
            else:
                _run_phase("multichip", lambda: phase_multichip(
                    FULL, [mx.tpu(i) for i in range(4)], prefix, losses,
                    meter), meter, devices)
    secs, programs, hits = meter.snapshot()
    print("total: wall %.1fs compile %.1fs (%d programs, %d from the "
          "persistent cache)" % (time.perf_counter() - t0, secs, programs,
                                 hits), flush=True)
    if tuple(phases) != PHASES:
        print("restricted run (%s): no verdict" % ",".join(phases))
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
