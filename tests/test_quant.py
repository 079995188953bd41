"""mxnet_tpu.quant — post-training quantization accuracy/plumbing gates.

Acceptance gates (ISSUE 14): (a) per-channel symmetric quantization math
round-trips within the dtype's resolution and beats per-tensor; (b) the
quantized matmul paths (native int8 W8A8, dequant-on-load) track the f32
GEMM; (c) accuracy-drift arms vs the f32 decode reference — int8-weight,
fp8-weight, bf16-KV, int8-KV — teacher-forced so per-step logit drift is
measured, not post-divergence garbage; (d) quantization OFF leaves the
f32 path untouched (no scale slabs, identical streams); (e) labeled
telemetry gauges round-trip through the Prometheus exposition; (f)
QuantizedPredictor matches Predictor within PTQ tolerance and shares one
quantization pass across the reshape ladder.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import quant, telemetry
from mxnet_tpu import predict
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import transformer as transformer_model
from mxnet_tpu.ops.contrib import dequantize_symmetric, quantize_symmetric
from mxnet_tpu.ops.matrix import quantized_matmul
from mxnet_tpu.serving.generate import (DecodeModel, DecodePrograms,
                                        DecodeScheduler, DecodeSpec,
                                        GenerateConfig)

V, D, L, F, H, HKV = 32, 16, 2, 32, 4, 2


def _lm_params(seed=0):
    """Random weights under the models/transformer.py naming."""
    rng = np.random.RandomState(seed)
    dkv = D // H * HKV
    p = {"embed_weight": rng.randn(V, D).astype(np.float32) * 0.3}
    for i in range(L):
        pre = "layer%d" % i
        p[pre + "_ln1_gamma"] = np.ones(D, np.float32)
        p[pre + "_ln1_beta"] = np.zeros(D, np.float32)
        p[pre + "_q_weight"] = rng.randn(D, D).astype(np.float32) * 0.2
        p[pre + "_k_weight"] = rng.randn(dkv, D).astype(np.float32) * 0.2
        p[pre + "_v_weight"] = rng.randn(dkv, D).astype(np.float32) * 0.2
        p[pre + "_o_weight"] = rng.randn(D, D).astype(np.float32) * 0.2
        p[pre + "_ln2_gamma"] = np.ones(D, np.float32)
        p[pre + "_ln2_beta"] = np.zeros(D, np.float32)
        p[pre + "_ffn1_weight"] = rng.randn(F, D).astype(np.float32) * 0.2
        p[pre + "_ffn1_bias"] = np.zeros(F, np.float32)
        p[pre + "_ffn2_weight"] = rng.randn(D, F).astype(np.float32) * 0.2
        p[pre + "_ffn2_bias"] = np.zeros(D, np.float32)
    p["lnf_gamma"] = np.ones(D, np.float32)
    p["lnf_beta"] = np.zeros(D, np.float32)
    p["pred_weight"] = rng.randn(V, D).astype(np.float32) * 0.2
    p["pred_bias"] = np.zeros(V, np.float32)
    return p


def _decode_model(seed=0):
    return DecodeModel.from_arg_params(
        _lm_params(seed), DecodeSpec(num_heads=H, num_kv_heads=HKV))


def _config(**kw):
    kw.setdefault("slots", 3)
    kw.setdefault("max_context", 24)
    kw.setdefault("prefill_buckets", (4, 8))
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("block_tokens", 4)
    kw.setdefault("num_blocks", 0)
    return GenerateConfig(num_heads=H, num_kv_heads=HKV, **kw)


def _run_streams(model, prompts, **cfg_kw):
    sched = DecodeScheduler(model, _config(**cfg_kw))
    sched.start()
    try:
        streams = [sched.submit(p) for p in prompts]
        outs = [list(s) for s in streams]
        stats = sched.stats()
    finally:
        sched.stop()
    return outs, stats


# --- (a) quantization math --------------------------------------------------

def test_per_channel_beats_per_tensor():
    """Per-channel (axis=0) int8 round-trip error is strictly below
    per-tensor on a weight whose channels have very different ranges —
    the reason the PTQ pass is per-channel."""
    rng = np.random.RandomState(0)
    w = rng.randn(8, 64).astype(np.float32)
    w *= (10.0 ** np.arange(8))[:, None] * 1e-3   # 4 decades of spread
    import jax.numpy as jnp
    q_pc, s_pc = quantize_symmetric(jnp.asarray(w), "int8", axis=0)
    q_pt, s_pt = quantize_symmetric(jnp.asarray(w), "int8", axis=None)
    assert q_pc.dtype == np.int8
    assert s_pc.shape == (8, 1)
    # per-ROW relative error: per-tensor crushes the small channels (its
    # one scale is sized for the largest), per-channel keeps every row
    # at int8 resolution of its own range
    amax = np.abs(w).max(axis=1)
    rel_pc = (np.abs(np.asarray(dequantize_symmetric(q_pc, s_pc)) - w)
              .max(axis=1) / amax)
    rel_pt = (np.abs(np.asarray(dequantize_symmetric(q_pt, s_pt)) - w)
              .max(axis=1) / amax)
    assert rel_pc.max() <= 0.5001 / 127.0
    assert rel_pt[0] > rel_pc[0] * 10   # smallest channel, 4 decades down
    # and within int8 resolution of each channel's own range
    per_chan_bound = np.abs(w).max(axis=1) / 127.0
    err_rows = np.abs(np.asarray(dequantize_symmetric(q_pc, s_pc)) - w
                      ).max(axis=1)
    assert (err_rows <= per_chan_bound * 0.5001).all()


def test_quantize_weight_scale_shapes():
    """quantize_weight squeezes keepdims scales to the kept channel axes
    (flat (O, I) -> (O,); stacked (L, O, I) -> (L, O))."""
    rng = np.random.RandomState(1)
    q, s = quant.quantize_weight(rng.randn(6, 5).astype(np.float32), "int8",
                                 axis=0)
    assert q.shape == (6, 5) and s.shape == (6,)
    q, s = quant.quantize_weight(rng.randn(3, 6, 5).astype(np.float32),
                                 "int8", axis=(0, 1))
    assert q.shape == (3, 6, 5) and s.shape == (3, 6)
    deq = np.asarray(quant.dequantize_weight(q, s))
    assert deq.shape == (3, 6, 5)


def test_fp8_weight_roundtrip():
    """fp8-e4m3 keeps ~2 decimal digits: round-trip relative error within
    e4m3 resolution (2^-3 worst-case spacing at the bin top)."""
    rng = np.random.RandomState(2)
    w = rng.randn(16, 32).astype(np.float32) * 0.1
    q, s = quant.quantize_weight(w, "fp8_e4m3", axis=0)
    assert str(q.dtype) == "float8_e4m3fn"
    deq = np.asarray(quant.dequantize_weight(q, s))
    rel = np.abs(deq - w).max() / np.abs(w).max()
    assert rel < 0.13


def test_dtype_normalization_and_errors():
    assert quant.normalize_weight_dtype("fp8") == "fp8_e4m3"
    assert quant.normalize_kv_dtype("f32") == "float32"
    assert quant.normalize_kv_dtype("bf16") == "bfloat16"
    with pytest.raises(MXNetError):
        quant.normalize_weight_dtype("int4")
    with pytest.raises(MXNetError):
        quant.normalize_kv_dtype("fp8")
    with pytest.raises(MXNetError):
        quant.QuantConfig(weight_dtype="int8", act_dtype="int4")


# --- (b) quantized matmul paths ---------------------------------------------

def test_quantized_matmul_paths_track_f32():
    rng = np.random.RandomState(3)
    import jax.numpy as jnp
    w = rng.randn(24, 48).astype(np.float32) * 0.1
    x = rng.randn(5, 48).astype(np.float32)
    ref = x @ w.T
    qw, s = quant.quantize_weight(w, "int8", axis=0)
    for act in ("int8", "float32", "bf16"):
        got = np.asarray(quantized_matmul(jnp.asarray(x), qw, s, act))
        assert got.shape == ref.shape
        atol = np.abs(got - ref).max()
        assert atol < 0.05 * np.abs(ref).max() + 1e-3, (act, atol)
    qw8, s8 = quant.quantize_weight(w, "fp8_e4m3", axis=0)
    got = np.asarray(quantized_matmul(jnp.asarray(x), qw8, s8, "int8"))
    assert np.abs(got - ref).max() < 0.1 * np.abs(ref).max()


def test_quantized_fully_connected_op():
    """The symbol-level QuantizedFullyConnected op (MXNet-parity contrib
    surface) matches FullyConnected over the dequantized weight."""
    rng = np.random.RandomState(4)
    w = rng.randn(8, 12).astype(np.float32) * 0.2
    x = rng.randn(3, 12).astype(np.float32)
    b = rng.randn(8).astype(np.float32)
    qw, s = quant.quantize_weight(w, "int8", axis=0)
    deq = np.asarray(quant.dequantize_weight(qw, s))
    ref = x @ deq.T + b
    got = mx.nd.QuantizedFullyConnected(
        mx.nd.array(x), mx.nd.array(np.asarray(qw)),
        mx.nd.array(np.asarray(s)), mx.nd.array(b), num_hidden=8,
        act_dtype="float32").asnumpy()
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)
    # native-int8 activation path stays within dynamic-quantization drift
    got8 = mx.nd.QuantizedFullyConnected(
        mx.nd.array(x), mx.nd.array(np.asarray(qw)),
        mx.nd.array(np.asarray(s)), mx.nd.array(b), num_hidden=8,
        act_dtype="int8").asnumpy()
    assert np.abs(got8 - ref).max() < 0.05 * np.abs(ref).max() + 1e-3


# --- (c) accuracy-drift arms vs the f32 decode reference --------------------

def _teacher_forced_logits(model, kv_dtype, prompt, forced):
    """Prefill + decode the FORCED token stream, returning per-step
    logits — every arm sees identical inputs, so the comparison measures
    drift, not post-divergence garbage."""
    slots, cap = 2, 16
    progs = DecodePrograms(model, slots, cap, (8,), kv_dtype=kv_dtype)
    k, v = progs.fresh_slabs()
    scales = progs.fresh_scale_slabs()
    ks, vs = scales if scales else (None, None)
    pre = progs.prefill(prompt)
    logits0 = pre[0]
    if len(pre) == 5:
        k, v, ks, vs = progs.admit(k, v, pre[1], pre[2], 0, ks_slab=ks,
                                   vs_slab=vs, ks_new=pre[3], vs_new=pre[4])
    else:
        k, v = progs.admit(k, v, pre[1], pre[2], 0)
    out_logits = [np.asarray(logits0).reshape(-1)]
    lengths = np.zeros(slots, np.int32)
    lengths[0] = len(prompt)
    tokens = np.zeros(slots, np.int32)
    for tok in forced:
        tokens[0] = tok
        # copies: dispatch is asynchronous and the CPU client may alias a
        # numpy argument's memory, so the in-place updates below must not
        # touch what the running step reads
        out = progs.decode(k, v, lengths.copy(), tokens.copy(),
                           ks_slab=ks, vs_slab=vs)
        if len(out) == 5:
            k, v, ks, vs = out[1:]
        else:
            k, v = out[1:]
        lengths[0] += 1
        out_logits.append(np.asarray(out[0])[0])
    return np.stack(out_logits)


def _drift_gate(got, ref, atol, label):
    worst = np.abs(got - ref).max()
    assert worst <= atol, (label, worst)
    top5 = np.argsort(-ref, axis=-1)[:, :5]
    am = np.argmax(got, axis=-1)
    hits = sum(1 for i in range(ref.shape[0]) if am[i] in top5[i])
    assert hits == ref.shape[0], (label, hits, ref.shape[0])


def test_accuracy_arms_vs_f32_reference():
    model = _decode_model()
    prompt = [3, 7, 1, 9, 4]
    ref = _teacher_forced_logits(model, "float32", prompt, [])
    forced = [int(np.argmax(ref[-1]))]
    for _ in range(5):
        ref = _teacher_forced_logits(model, "float32", prompt, forced)
        forced.append(int(np.argmax(ref[-1])))
    forced = forced[:-1]
    ref = _teacher_forced_logits(model, "float32", prompt, forced)

    # KV-cache arms: the stored state narrows, the math stays f32
    got = _teacher_forced_logits(model, "bfloat16", prompt, forced)
    _drift_gate(got, ref, 5e-2, "bf16-kv")
    got = _teacher_forced_logits(model, "int8", prompt, forced)
    _drift_gate(got, ref, 5e-2, "int8-kv")

    # weight arms (per-channel PTQ + W8A8 / dequant-on-load)
    qm = quant.quantize_decode_model(
        model, quant.QuantConfig(weight_dtype="int8"))
    got = _teacher_forced_logits(qm, "float32", prompt, forced)
    _drift_gate(got, ref, 2.5e-1, "int8-weight")
    qm = quant.quantize_decode_model(
        model, quant.QuantConfig(weight_dtype="fp8_e4m3"))
    got = _teacher_forced_logits(qm, "float32", prompt, forced)
    _drift_gate(got, ref, 5e-1, "fp8-weight")


def test_combined_weight_and_kv_streams():
    """End-to-end scheduler streams: every quantized arm still greedy-
    decodes the same tokens as f32 on this model, and the paged int8-KV
    arm (scale blocks CoW-forked alongside value blocks) is bitwise the
    unpaged int8-KV arm."""
    model = _decode_model()
    pa = [3, 7, 1, 9, 4, 2, 8, 5]
    pb = [3, 7, 1, 9, 4, 2, 8, 6]     # shared 4-token block prefix
    prompts = [pa, pb, [5, 2, 8]]
    ref, _ = _run_streams(model, prompts, paged=False)
    unpaged_i8, _ = _run_streams(model, prompts, paged=False,
                                 kv_dtype="int8")
    paged_i8, stats = _run_streams(model, prompts, paged=True,
                                   kv_dtype="int8")
    assert paged_i8 == unpaged_i8
    assert stats["cow_forks"] >= 1          # fork copied scale blocks too
    assert stats["kv_dtype"] == "int8"
    w_and_kv, stats = _run_streams(model, prompts, paged=True,
                                   kv_dtype="int8", quant_weights="int8")
    assert stats["quant_weights"] == "int8"
    # weight+KV arm: drift is allowed, but the streams stay well-formed
    assert [len(s) for s in w_and_kv] == [len(s) for s in ref]


# --- (d) default-OFF: the f32 path is untouched -----------------------------

def test_quant_off_no_scale_slabs_and_parity():
    model = _decode_model()
    progs = DecodePrograms(model, 2, 16, (8,))
    assert progs.fresh_scale_slabs() is None
    assert progs.kv_dtype == "float32"
    pre = progs.prefill([3, 7, 1])
    assert len(pre) == 3                    # no scale outputs
    # explicit f32 spellings are the same arm as the default
    ref, _ = _run_streams(model, [[3, 7, 1, 9]])
    explicit, stats = _run_streams(model, [[3, 7, 1, 9]], kv_dtype="f32",
                                   quant_weights="")
    assert explicit == ref
    assert stats["kv_dtype"] == "float32"
    assert stats["quant_weights"] == "off"


def test_quant_off_model_params_untouched():
    """quantize_decode_model returns a NEW model; the source params keep
    f32 dtypes and gain no scale siblings."""
    model = _decode_model()
    before = {k: str(v.dtype) for k, v in model.params.items()}
    qm = quant.quantize_decode_model(model,
                                     quant.QuantConfig(weight_dtype="int8"))
    after = {k: str(v.dtype) for k, v in model.params.items()}
    assert before == after
    assert "wq_scale" not in model.params
    assert str(qm.params["wq"].dtype) == "int8"
    assert qm.params["wq_scale"].shape == (L, D)


# --- (e) telemetry: labeled gauges + exposition round-trip ------------------

def test_labeled_gauge_exposition_roundtrip():
    reg = telemetry.registry
    g_plain = reg.gauge("quant_test_bytes", help="plain")
    g_i8 = reg.gauge("quant_test_bytes", labels={"dtype": "int8"})
    g_f8 = reg.gauge("quant_test_bytes", labels={"dtype": "fp8_e4m3"})
    assert g_i8 is not g_f8 and g_i8 is not g_plain
    # get-or-create returns the same series for the same label set
    assert reg.gauge("quant_test_bytes", labels={"dtype": "int8"}) is g_i8
    g_plain.set(1); g_i8.set(2); g_f8.set(3)
    text = reg.exposition()
    lines = text.splitlines()
    assert 'quant_test_bytes 1' in lines
    assert 'quant_test_bytes{dtype="int8"} 2' in lines
    assert 'quant_test_bytes{dtype="fp8_e4m3"} 3' in lines
    # TYPE emitted once per family, and before every series of it
    type_lines = [i for i, l in enumerate(lines)
                  if l == "# TYPE quant_test_bytes gauge"]
    assert len(type_lines) == 1
    # every sample line still parses with the name/value rsplit convention
    for line in lines:
        if line.startswith("quant_test_bytes"):
            name, value = line.rsplit(" ", 1)
            float(value)
    # round-trip: parse back the labeled series values
    parsed = {}
    for line in lines:
        if line.startswith("quant_test_bytes") and " " in line:
            name, value = line.rsplit(" ", 1)
            parsed[name] = float(value)
    assert parsed == {"quant_test_bytes": 1.0,
                      'quant_test_bytes{dtype="int8"}': 2.0,
                      'quant_test_bytes{dtype="fp8_e4m3"}': 3.0}


def test_scheduler_kv_gauges_labeled_by_dtype():
    model = _decode_model()
    _, _stats = _run_streams(model, [[3, 7, 1]], paged=True,
                             kv_dtype="int8")
    text = telemetry.registry.exposition()
    assert 'kv_bytes{dtype="int8"}' in text
    assert 'decode_kv_dtype="int8"' in text   # kv_blocks_* label


# --- (f) QuantizedPredictor -------------------------------------------------

def _predictor_pair(wd):
    sym = transformer_model.get_symbol(
        num_classes=V, num_layers=L, num_heads=H, model_dim=D, ffn_dim=F,
        num_kv_heads=HKV)
    params = _lm_params()
    shapes = {"data": (1, 8), "softmax_label": (1, 8)}
    pred = predict.Predictor(sym.tojson(), params, shapes)
    return pred, pred.quantize(wd)


def test_quantized_predictor_matches_f32():
    pred, qpred = _predictor_pair("int8")
    ids = np.array([[3, 7, 1, 9, 4, 0, 0, 0]], np.float32)
    lab = np.zeros((1, 8), np.float32)
    ref = pred.forward(data=ids, softmax_label=lab)[0].asnumpy()
    got = qpred.forward(data=ids, softmax_label=lab)[0].asnumpy()
    assert np.abs(got - ref).max() < 5e-2       # post-softmax probs
    top5 = np.argsort(-ref, axis=-1)[:, :5]
    am = np.argmax(got, -1)
    assert all(am[i] in top5[i] for i in range(ref.shape[0]))


def test_quantized_predictor_reshape_shares_quantization():
    _pred, qpred = _predictor_pair("int8")
    r = qpred.reshape({"data": (2, 8), "softmax_label": (2, 8)})
    assert r._qvals is qpred._qvals             # one PTQ pass per ladder
    ids = np.tile(np.array([[3, 7, 1, 9, 4, 0, 0, 0]], np.float32), (2, 1))
    out = r.forward(data=ids,
                    softmax_label=np.zeros((2, 8), np.float32))[0].asnumpy()
    assert out.shape[0] == 16                   # (2*8, V) softmax rows


def test_quantized_predictor_export_refuses():
    _pred, qpred = _predictor_pair("int8")
    with pytest.raises(MXNetError):
        qpred.export("/tmp/should_not_exist_quant_export")


def test_quant_params_bytes_accounted():
    before = quant.quant_params_bytes().get("fp8_e4m3", 0)
    _pred, _qpred = _predictor_pair("fp8_e4m3")
    after = quant.quant_params_bytes()["fp8_e4m3"]
    assert after > before
