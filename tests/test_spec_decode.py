"""Speculative decoding (ISSUE 16) — draft-k-then-verify on the bounded
decode engine.

Acceptance gates: (a) greedy speculative streams are TOKEN-IDENTICAL to
vanilla decode (paged and unpaged, including mid-stream admits) — greedy
rejection sampling is longest-matching-prefix plus the target's own
correction, so speculation may change only throughput, never content;
(b) acceptance math units — accept-0, accept-k, k_eff=0, Leviathan
accept/reject/residual; (c) the int8 self-draft earns a high acceptance
rate while the program set stays at ladder + 2 (paged; unpaged rides its
standalone admit along at ladder + 3); (d) rewind is a refcount-safe
block-table/length edit — ``truncate()`` under copy-on-write sharing
never frees another sequence's prefix blocks and is idempotent; (e) spec
composes with ``stop(drain=True)`` and per-stream deadlines; (f) sampled streams are seed-deterministic and the
``decode_spec_accept_rate`` / ``decode_tokens_per_step`` gauges plus the
``decode.draft``/``decode.verify`` spans are live.
"""
import os
import shutil
import tempfile

import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.serving import ServingError
from mxnet_tpu.serving.generate import (DecodeModel, DecodePrograms,
                                        DecodeScheduler, DecodeSpec,
                                        GenerateConfig, KVCacheManager,
                                        PagedDecodePrograms,
                                        PagedKVCacheManager, accept_greedy,
                                        accept_sampled, sample_token)

V, D, L, F, H, HKV = 32, 16, 2, 32, 4, 2


@pytest.fixture(autouse=True, scope="module")
def _shared_progcache():
    """One progcache dir for the whole module: the many schedulers these
    tests build share identical programs, so everything after the first
    compile disk-loads (this is also a standing test that spec programs
    are progcache-clean)."""
    prev = os.environ.get("MXNET_PROGCACHE_DIR")
    d = tempfile.mkdtemp(prefix="spec_progcache_")
    os.environ["MXNET_PROGCACHE_DIR"] = d
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("MXNET_PROGCACHE_DIR", None)
        else:
            os.environ["MXNET_PROGCACHE_DIR"] = prev
        shutil.rmtree(d, ignore_errors=True)


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
    kw.setdefault("prefix_share", True)
    return GenerateConfig(num_heads=H, num_kv_heads=HKV, **kw)


def _run(model, prompts, **cfg_kw):
    """Generate all prompts (submitted together) and return their token
    streams plus the final scheduler stats."""
    sched = DecodeScheduler(model, _config(**cfg_kw))
    sched.start()
    try:
        streams = [sched.submit(p) for p in prompts]
        outs = [list(s) for s in streams]
        stats = sched.stats()
    finally:
        sched.stop(drain=True)
    return outs, stats


PROMPTS = [[3, 7, 1, 9, 4], [5, 2, 8], [9, 4, 1, 2, 11, 6]]

_REFS = {}


def _vanilla_ref(model, prompts, **cfg_kw):
    """Memoized vanilla (non-spec) reference streams — several tests
    compare against the same baseline arm."""
    key = (tuple(tuple(p) for p in prompts),
           tuple(sorted(cfg_kw.items())))
    if key not in _REFS:
        _REFS[key] = _run(model, prompts, **cfg_kw)[0]
    return _REFS[key]


# --- (a) greedy spec streams are bitwise vanilla ---------------------------

@pytest.mark.parametrize("paged", [True, False],
                         ids=["paged", "unpaged"])
@pytest.mark.parametrize("draft", ["int8", "self"])
def test_greedy_spec_matches_vanilla(paged, draft):
    """k-draft-then-verify with either draft never changes a greedy
    stream: acceptance is longest-matching-prefix and the correction /
    bonus token is the TARGET's argmax, i.e. exactly what vanilla decode
    would have emitted. Programs stay at ladder + 2 paged / ladder + 3
    unpaged (the draft step replaces the vanilla step; unpaged keeps its
    standalone admit), and both drafts earn their keep: int8 tracks the
    target (>= 0.5 acceptance), a same-precision self-draft is
    near-perfect (>= 0.8 — the only misses are batched-verify
    numerics)."""
    model = _decode_model()
    ref = _vanilla_ref(model, PROMPTS, paged=paged)
    got, st = _run(model, PROMPTS, paged=paged, spec=True, spec_tokens=4,
                   spec_draft=draft)
    assert got == ref
    assert st["spec"] == "%s k=4" % draft
    bound = len((4, 8)) + (2 if paged else 3)
    assert st["compiles"] + st["disk_hits"] <= bound, st
    rate = st["accepted_tokens"] / max(1, st["drafted_tokens"])
    assert rate >= (0.8 if draft == "self" else 0.5), rate
    # every sequence iteration commits >= 1 token (correction/bonus),
    # and speculation actually paid: > 1 token per iteration on average
    assert st["step_tokens"] > st["seq_steps"]


def test_greedy_spec_matches_vanilla_mid_stream_admits():
    """More prompts than slots: late arrivals prefill into a batch whose
    other rows are mid-speculation; every stream still matches vanilla
    (staggered finishes exercise keff clamping near max_new_tokens)."""
    model = _decode_model()
    prompts = PROMPTS + [[4, 4], [8, 1, 3, 3, 7, 2, 6], [3, 7, 1, 9, 4]]
    ref = _vanilla_ref(model, prompts, paged=True)
    got, _ = _run(model, prompts, paged=True, spec=True, spec_tokens=3)
    assert got == ref


def test_spec_tokens_one_and_single_token_budget():
    """Edge geometries: k=1 (minimal window) and max_new_tokens=1
    (keff clamps to 0 — the verify IS the vanilla step)."""
    model = _decode_model()
    ref = _vanilla_ref(model, PROMPTS, paged=True)
    got, _ = _run(model, PROMPTS, paged=True, spec=True, spec_tokens=1)
    assert got == ref
    ref1 = _vanilla_ref(model, PROMPTS, paged=True, max_new_tokens=1)
    got1, st1 = _run(model, PROMPTS, paged=True, max_new_tokens=1,
                     spec=True, spec_tokens=4)
    assert got1 == ref1
    assert st1["accepted_tokens"] == 0      # keff was 0 throughout


# --- (b) acceptance math units ---------------------------------------------

def _logits_for(tokens):
    """(len(tokens), V) logits whose argmax row j is tokens[j]."""
    z = np.zeros((len(tokens), V), np.float32)
    for j, t in enumerate(tokens):
        z[j, t] = 5.0
    return z


def test_accept_greedy_full_window_and_bonus():
    vlogits = _logits_for([7, 9, 2, 4])
    acc, emitted = accept_greedy([7, 9, 2], vlogits, 3)
    assert acc == 3
    assert emitted == [7, 9, 2, 4]          # k accepted + bonus


def test_accept_greedy_first_mismatch_is_accept_zero():
    vlogits = _logits_for([8, 9, 2, 4])
    acc, emitted = accept_greedy([7, 9, 2], vlogits, 3)
    assert acc == 0
    assert emitted == [8]                   # the target's correction


def test_accept_greedy_partial_prefix():
    vlogits = _logits_for([7, 9, 6, 4])
    acc, emitted = accept_greedy([7, 9, 2], vlogits, 3)
    assert acc == 2
    assert emitted == [7, 9, 6]             # 2 accepted + correction


def test_accept_greedy_keff_zero_is_vanilla_step():
    vlogits = _logits_for([5])
    acc, emitted = accept_greedy([], vlogits, 0)
    assert (acc, emitted) == (0, [5])


class _FixedRng:
    """Deterministic random_sample() stream for acceptance-math units."""

    def __init__(self, values):
        self._values = list(values)

    def random_sample(self):
        return self._values.pop(0)


def test_accept_sampled_accepts_when_target_agrees():
    """p == q at the drafted token -> acceptance probability 1; a fully
    accepted window earns one bonus draw from the target's position k."""
    p_logits = _logits_for([7, 9, 3])
    q = _softmax_rows(p_logits[:2])
    acc, emitted = accept_sampled(
        [7, 9], q, p_logits, 2, 1.0, _FixedRng([0.99, 0.99, 0.5]))
    assert acc == 2
    assert emitted[:2] == [7, 9]
    assert emitted[2] == 3                  # bonus: p[2] is ~one-hot on 3


def test_accept_sampled_rejects_and_resamples_residual():
    """q concentrated where p has no mass -> ratio ~0, first draw
    rejects, and the replacement comes from max(p - q, 0) — which here
    is p itself."""
    p_logits = _logits_for([8, 9])
    q0 = np.zeros(V)
    q0[7] = 1.0                             # draft proposed 7; p[7] ~ 0
    acc, emitted = accept_sampled(
        [7], [q0], p_logits, 1, 1.0, _FixedRng([0.5, 0.5]))
    assert acc == 0
    assert len(emitted) == 1
    assert emitted[0] == 8                  # residual ~ p, one-hot on 8


def test_accept_sampled_threshold():
    """Acceptance draws against min(1, p[d]/q[d]) exactly: with the
    ratio pinned at ~0.5, u=0.4 accepts and u=0.6 rejects."""
    p = np.full(V, 1e-9)
    p[7], p[8] = 0.5, 0.5 - 1e-9 * (V - 2)
    q = np.zeros(V)
    q[7] = 1.0
    p_logits = np.log(np.stack([p, p]) + 1e-300).astype(np.float64)
    acc_lo, em_lo = accept_sampled([7], [q], p_logits, 1, 1.0,
                                   _FixedRng([0.4, 0.0, 0.0]))
    acc_hi, em_hi = accept_sampled([7], [q], p_logits, 1, 1.0,
                                   _FixedRng([0.6, 0.5]))
    assert acc_lo == 1 and em_lo[0] == 7
    assert acc_hi == 0 and em_hi[0] == 8    # residual excludes q's token


def test_sample_token_greedy_and_seeded():
    logits = np.zeros(V)
    logits[13] = 3.0
    assert sample_token(logits, 0.0, None) == 13
    r1 = sample_token(logits, 1.0, np.random.RandomState(7))
    r2 = sample_token(logits, 1.0, np.random.RandomState(7))
    assert r1 == r2


def _softmax_rows(logits):
    z = np.asarray(logits, np.float64)
    z = z - z.max(axis=-1, keepdims=True)
    e = np.exp(z)
    return list(e / e.sum(axis=-1, keepdims=True))


# --- (d) rewind: refcount-safe truncate ------------------------------------

def _paged_manager(model, slots=3, capacity=24, block_tokens=4,
                   num_blocks=0, prefix_share=True, buckets=(4, 8)):
    blocks = num_blocks or slots * (-(-capacity // block_tokens))
    progs = PagedDecodePrograms(model, slots, capacity, buckets,
                                block_tokens, blocks)
    return PagedKVCacheManager(progs, replica=0, prefix_share=prefix_share)


def test_paged_truncate_keeps_admission_reservation():
    """The default rewind is a pure length edit: blocks reserved by
    try_admit stay with the sequence (the no-mid-stream-eviction
    invariant), so speculate/reject cycles never touch the pool."""
    model = _decode_model()
    cache = _paged_manager(model, slots=2)
    free0 = cache.blocks_free()
    plan = cache.try_admit("a", [3, 7, 1, 9, 4], max_new=6)
    held = free0 - cache.blocks_free()
    cache.truncate(plan.slot, 5)                # reject everything drafted
    assert cache.length(plan.slot) == 5
    assert cache.blocks_free() == free0 - held  # reservation intact
    cache.truncate(plan.slot, 7)                # accept 2 next iteration
    assert cache.length(plan.slot) == 7
    assert cache.blocks_free() == free0 - held
    cache.free(plan.slot)
    assert cache.blocks_free() == free0


def test_paged_truncate_release_returns_tail_blocks_idempotently():
    """release=True (slot teardown path) trims the table past
    ceil(new_len/T); repeating the call finds TRASH entries and is a
    no-op."""
    model = _decode_model()
    cache = _paged_manager(model, slots=2, capacity=24, block_tokens=4)
    free0 = cache.blocks_free()
    plan = cache.try_admit("a", [3, 7, 1, 9, 4], max_new=11)  # 4 blocks
    assert cache.blocks_free() == free0 - 4
    cache.truncate(plan.slot, 5, release=True)  # keep ceil(5/4) = 2
    assert cache.blocks_free() == free0 - 2
    cache.truncate(plan.slot, 5, release=True)  # idempotent
    assert cache.blocks_free() == free0 - 2
    assert cache.length(plan.slot) == 5
    cache.free(plan.slot)
    assert cache.blocks_free() == free0


def test_paged_truncate_never_frees_shared_prefix_blocks():
    """Fork-then-reject: rewinding one sharer of a CoW prefix decrefs its
    table entries but the shared full block survives for the other owner
    — and reads back intact."""
    model = _decode_model()
    cache = _paged_manager(model, slots=3, capacity=24, block_tokens=4)
    free0 = cache.blocks_free()
    a = cache.try_admit("a", [3, 7, 1, 9, 4, 2], max_new=6)
    b = cache.try_admit("b", [3, 7, 1, 9, 4, 2, 5, 8], max_new=6)
    assert b.forked and int(b.table[0]) == int(a.table[0])
    shared = int(a.table[0])
    # rewind b BELOW the shared block boundary with release: b's entry
    # for the shared block is decref'd, but a still references it
    cache.truncate(b.slot, 0, release=True)
    assert cache.blocks_free() == free0 - 3     # only a's 3 stay allocated
    assert int(cache._tables[a.slot][0]) == shared
    assert cache._ref[shared] == 1
    cache.free(a.slot)
    cache.free(b.slot)
    assert cache.blocks_free() == free0


def test_unpaged_truncate_is_length_rollback():
    model = _decode_model()
    progs = DecodePrograms(model, slots=2, capacity=16,
                           prefill_buckets=(8,))
    cache = KVCacheManager(progs, replica=0)
    plan = cache.try_admit("a", [5, 4, 3], max_new=6)
    n0 = cache.length(plan.slot)
    cache.truncate(plan.slot, n0 + 2)
    assert cache.length(plan.slot) == n0 + 2
    cache.truncate(plan.slot, n0)
    assert cache.length(plan.slot) == n0


# --- (e) composition: drain / deadline -------------------------------------

def test_spec_drain_and_deadline():
    """stop(drain=True) finishes mid-flight speculative streams; a
    deadline retire mid-speculation surfaces as deadline_exceeded
    without wedging the batch."""
    model = _decode_model()
    sched = DecodeScheduler(model, _config(paged=True, max_new_tokens=24,
                                           max_context=32, spec=True,
                                           spec_tokens=4))
    sched.start()
    s1 = sched.submit([3, 7, 1], max_new_tokens=20)
    s2 = sched.submit([5, 2, 8, 6], timeout_ms=0.0)   # already expired
    sched.stop(drain=True)
    toks = s1.tokens()
    assert s1.finish_reason == "max_tokens" and len(toks) == 20
    with pytest.raises(ServingError) as ei:
        s2.tokens()
    assert ei.value.code == "deadline_exceeded"


def test_config_validation():
    model = _decode_model()
    with pytest.raises(ServingError):
        DecodeScheduler(model, _config(spec=True, spec_tokens=0))
    with pytest.raises(ServingError):
        DecodeScheduler(model, _config(spec=True, spec_draft="fp4"))


# --- (f) sampling determinism + observability ------------------------------

def test_sampled_spec_is_seed_deterministic():
    model = _decode_model()

    def arm():
        sched = DecodeScheduler(model, _config(paged=True, spec=True,
                                               spec_tokens=3))
        sched.start()
        try:
            ss = [sched.submit([3, 7, 1], max_new_tokens=4,
                               temperature=1.0, seed=s) for s in range(5)]
            return [list(s) for s in ss]
        finally:
            sched.stop(drain=True)

    one = arm()
    assert arm() == one
    assert len({tuple(t) for t in one}) > 1   # seeds actually differ


def test_spec_gauges_and_spans():
    """decode_spec_accept_rate / decode_tokens_per_step are registry
    gauges; decode.draft and decode.verify spans nest inside each
    decode.step."""
    telemetry.enable_spans("serving")
    try:
        model = _decode_model()
        _, st = _run(model, PROMPTS, paged=True, spec=True, spec_tokens=3)
        events = telemetry.drain_events()
    finally:
        telemetry.disable_spans()
        telemetry.drain_events()
    by_name = {}
    for ev in events:
        ph, name, domain = ev[0], ev[1], ev[2]
        by_name.setdefault(name, []).append(ev)
    steps = [e for e in by_name.get("decode.step", [])
             if e[5].get("spec") == 3]
    assert steps, "no spec-annotated decode.step spans"
    assert len(by_name.get("decode.draft", [])) >= len(steps)
    assert len(by_name.get("decode.verify", [])) >= len(steps)
    assert all(e[5].get("k") == 3 for e in by_name["decode.draft"])
    assert all(e[5].get("window") == 4 for e in by_name["decode.verify"])
    # draft/verify run inside the step span (same engine worker thread)
    step_tids = {e[6] for e in steps}
    assert {e[6] for e in by_name["decode.draft"]} <= step_tids
    expo = telemetry.registry.exposition()
    assert "decode_spec_accept_rate" in expo
    assert "decode_tokens_per_step" in expo
