"""Registry-wide cross-precision / cross-path consistency sweep.

The reference's GPU suite runs every operator across device/precision
variants via ``check_consistency`` (tests/python/gpu/test_operator_gpu.py,
python/mxnet/test_utils.py:705: cpu vs gpu vs cudnn vs fp16). The
TPU-native variant axes are:

1. **f32 vs bf16 compute** — the executor's ``compute_dtype`` mixed-
   precision path (f32 master weights, bf16 compute, f32 outputs/grads)
   must stay within bf16 tolerance of the f32 run for EVERY float op.
2. **Pallas kernels: interpret vs plain XLA** — every kernel in
   ``ops/pallas`` must match its plain-jnp reference implementation
   (the cudnn-vs-plain layering contract, cudnn_algoreg-inl.h).

Input construction reuses the registry-wide case builders from
``test_operator_gradients`` (same shapes/domains), so coverage tracks the
registry automatically; a completeness gate fails when a float op has
neither a consistency case nor an explicit, justified skip.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu.ops import OP_REGISTRY

from test_operator_gradients import (CUSTOM_BWD, FWD_CASES, GRAD_CASES,
                                     SKIP, V, _u)

# ---------------------------------------------------------------------------
# bf16-vs-f32 sweep over the registry cases
# ---------------------------------------------------------------------------

# ops whose outputs are NOT meaningfully comparable across compute dtypes,
# each with the reason (mirrors the gradient suite's SKIP discipline)
BF16_SKIP = {
    "quantize": "int8 rounding boundaries: one ulp of bf16 input noise "
                "legally flips a quantized bucket",
    "dequantize": "inverse of the above; exactness is tested in "
                  "tests/test_contrib.py against closed-form values",
    "Proposal": "NMS order: bf16 score noise can reorder near-equal "
                "proposals (forward-only contrib op; test_detection.py)",
    "MultiBoxDetection": "same NMS reordering sensitivity",
    "MultiBoxTarget": "anchor matching argmax over near-equal IoUs",
    "argsort": "sort order of values closer than one bf16 ulp is "
               "legitimately unstable across compute dtypes",
    "topk": "same tie instability as argsort",
    "ExpertFFN": "which experts a token takes is a top-k over the router's "
                 "logits: one bf16 ulp flips a near-tied choice "
                 "(tests/test_smallthinker.py holds it to a dense loop)",
    "_random_uniform": "PRNG bits are generated in the compute dtype: "
                       "sequences differ by design (freshness is tested "
                       "in test_random.py)",
    "_random_normal": "same PRNG dtype dependence",
    "_random_exponential": "same PRNG dtype dependence",
    "_random_gamma": "same PRNG dtype dependence",
}

# forward-compared-only under bf16: the forward is consistent, but the
# backward routes through comparisons/cell-selection on rounded values, so
# subgradient choice legitimately differs when bf16 rounding creates ties
BF16_FWD_ONLY = {
    "broadcast_maximum": "ties after bf16 rounding flip subgradient routing",
    "broadcast_minimum": "ties after bf16 rounding flip subgradient routing",
    "SpatialTransformer": "bilinear cell selection flips when sampling "
                          "coords round across a pixel boundary",
}

# per-op tolerance overrides (keyed by registry name before the ":")
BF16_TOL = {
    # long reductions / recurrences accumulate bf16 rounding
    "RNN": dict(atol=8e-2, rtol=8e-2),
    "ctc_loss": dict(atol=8e-2, rtol=8e-2),
    "Convolution": dict(atol=6e-2, rtol=6e-2),
    "Deconvolution": dict(atol=6e-2, rtol=6e-2),
    "Correlation": dict(atol=6e-2, rtol=6e-2),
    "fft": dict(atol=6e-2, rtol=6e-2),
    "ifft": dict(atol=6e-2, rtol=6e-2),
    "norm": dict(atol=5e-2, rtol=5e-2),
    "LRN": dict(atol=5e-2, rtol=5e-2),
    "erfinv": dict(atol=6e-2, rtol=6e-2),   # steep near the domain edge
    "tan": dict(atol=6e-2, rtol=6e-2),
    "gamma": dict(atol=6e-2, rtol=6e-2),
    "count_sketch": dict(atol=6e-2, rtol=6e-2),
}
_DEFAULT_TOL = dict(atol=4e-2, rtol=4e-2)


def _opname(cid):
    return cid.split(":")[0]


def _run(build, compute_dtype, with_grad):
    """Forward (+backward with all-ones head grads) under one compute
    dtype; fresh executor per run, same inputs (numpy from the builder)."""
    got = build()
    s, loc = got[0], got[1]
    if not loc:  # creation ops bind with no args
        exe = s.bind(mx.cpu(), {}, grad_req="null",
                     compute_dtype=compute_dtype)
        outs = exe.forward(is_train=False)
        return [np.asarray(o.asnumpy(), np.float64) for o in outs], {}
    grad_req = "write" if with_grad else "null"
    ctx = mx.cpu()
    args = {k: nd.array(v, ctx=ctx) for k, v in loc.items()}
    grads = ({k: nd.zeros(np.shape(v), ctx=ctx) for k, v in loc.items()}
             if with_grad else None)
    aux_names = s.list_auxiliary_states()
    aux = {}
    if aux_names:
        shapes = {k: np.shape(v) for k, v in loc.items()}
        _, _, aux_shapes = s.infer_shape(**shapes)
        aux = {n: nd.zeros(sh) for n, sh in zip(aux_names, aux_shapes)}
    exe = s.bind(ctx, args, grads, grad_req, aux,
                 compute_dtype=compute_dtype)
    outs = exe.forward(is_train=with_grad)
    gdict = {}
    if with_grad:
        exe.backward([nd.array(np.ones(o.shape, np.float32))
                      for o in outs])
        gdict = {k: np.asarray(v.asnumpy(), np.float64)
                 for k, v in exe.grad_dict.items()}
    return [np.asarray(o.asnumpy(), np.float64) for o in outs], gdict


def _check_case(cid, build, with_grad):
    op = _opname(cid)
    if op in BF16_SKIP:
        pytest.skip("bf16 consistency n/a: %s" % BF16_SKIP[op])
    if with_grad and op in BF16_FWD_ONLY:
        with_grad = False
    # identical inputs for both runs: freeze the builder's randomness
    state = np.random.get_state()
    np.random.seed(11)
    try:
        import test_operator_gradients as tog

        tog.R.seed(13)
        o32, g32 = _run(build, None, with_grad)
        tog.R.seed(13)
        o16, g16 = _run(build, "bfloat16", with_grad)
    finally:
        np.random.set_state(state)
    tol = BF16_TOL.get(op, _DEFAULT_TOL)
    for i, (a, b) in enumerate(zip(o32, o16)):
        np.testing.assert_allclose(
            a, b, err_msg="%s output %d f32-vs-bf16" % (cid, i), **tol)
    for k in g32:
        np.testing.assert_allclose(
            g32[k], g16[k], err_msg="%s grad %s f32-vs-bf16" % (cid, k),
            **tol)


@pytest.mark.parametrize("cid,build", GRAD_CASES,
                         ids=[c[0] for c in GRAD_CASES])
def test_bf16_consistency_grad_ops(cid, build):
    _check_case(cid, build, with_grad=True)


@pytest.mark.parametrize("cid,build", FWD_CASES,
                         ids=[c[0] for c in FWD_CASES])
def test_bf16_consistency_forward_ops(cid, build):
    _check_case(cid, build, with_grad=False)


# custom-backward loss family: closed-form backward must also hold in bf16
_LOSS_CASES = [
    ("SoftmaxOutput", lambda: (mx.sym.SoftmaxOutput(V("data"), V("label")),
                               {"data": _u((3, 4)),
                                "label": np.array([0, 2, 1], np.float32)})),
    ("LinearRegressionOutput",
     lambda: (mx.sym.LinearRegressionOutput(V("data"), V("label")),
              {"data": _u((3, 2)), "label": _u((3, 2))})),
    ("LogisticRegressionOutput",
     lambda: (mx.sym.LogisticRegressionOutput(V("data"), V("label")),
              {"data": _u((3, 2)), "label": _u((3, 2), 0, 1)})),
    ("MAERegressionOutput",
     lambda: (mx.sym.MAERegressionOutput(V("data"), V("label")),
              {"data": _u((3, 2)), "label": _u((3, 2))})),
    ("SVMOutput", lambda: (mx.sym.SVMOutput(V("data"), V("label")),
                           {"data": _u((3, 4)),
                            "label": np.array([0, 2, 1], np.float32)})),
    ("MakeLoss", lambda: (mx.sym.MakeLoss(V("data"), grad_scale=2.0),
                          {"data": _u((2, 3), 0.5, 1.5)})),
    ("BlockGrad", lambda: (mx.sym.BlockGrad(V("data")) * V("w"),
                           {"data": _u((2, 3)), "w": _u((2, 3))})),
    ("IdentityAttachKLSparseReg",
     lambda: (mx.sym.IdentityAttachKLSparseReg(V("data"),
                                               sparseness_target=0.1,
                                               penalty=0.01),
              {"data": _u((2, 4), 0.1, 0.9)})),
]


@pytest.mark.parametrize("cid,build", _LOSS_CASES,
                         ids=[c[0] for c in _LOSS_CASES])
def test_bf16_consistency_loss_ops(cid, build):
    _check_case(cid + ":loss", build, with_grad=True)


def test_bf16_registry_coverage_is_complete():
    """Every distinct float-capable registry op must be covered by a
    consistency case (via the shared case lists) or carry an explicit
    skip with a reason — mirroring the gradient suite's gate."""
    covered = {_opname(cid) for cid, _ in GRAD_CASES}
    covered |= {_opname(cid) for cid, _ in FWD_CASES}
    covered |= {cid for cid, _ in _LOSS_CASES}
    # make_loss/stop_gradient are pure aliases tested through their
    # canonical names; Custom is per-user-op (test_custom_op.py runs one)
    covered |= set(CUSTOM_BWD) | set(SKIP) | set(BF16_SKIP)

    uncovered = []
    seen = set()
    for name, op in OP_REGISTRY.items():
        if id(op) in seen:
            continue
        seen.add(id(op))
        aliases = {n for n, o in OP_REGISTRY.items() if o is op}
        if not (aliases & covered):
            uncovered.append(sorted(aliases)[0])
    assert not uncovered, (
        "registry ops with no f32-vs-bf16 consistency coverage (add a "
        "case or an explicit BF16_SKIP with a reason): %s"
        % sorted(uncovered))


# ---------------------------------------------------------------------------
# Pallas kernels: interpret-mode kernel vs plain-XLA reference
# ---------------------------------------------------------------------------

def _plain_attention(q, k, v, causal=False, scale=None):
    import jax.numpy as jnp

    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / np.sqrt(d)
    logits = jnp.einsum("...qd,...kd->...qk", q, k) * s
    if causal:
        n = logits.shape[-1]
        mask = np.tril(np.ones((n, n), bool))
        logits = jnp.where(mask, logits, -1e30)
    return jnp.einsum("...qk,...kd->...qd", jax.nn.softmax(logits, -1), v)


import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def test_pallas_flash_attention_matches_plain():
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(0)
    for (B, H, S, D), causal in (((2, 2, 16, 8), False),
                                 ((1, 2, 32, 8), True)):
        q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
        got = flash_attention(q, k, v, causal=causal, interpret=True)
        want = _plain_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        # gradients flow identically through the custom-vjp kernel
        gk = jax.grad(lambda q: jnp.sum(
            flash_attention(q, k, v, causal=causal, interpret=True) ** 2))(q)
        gp = jax.grad(lambda q: jnp.sum(
            _plain_attention(q, k, v, causal=causal) ** 2))(q)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gp),
                                   rtol=2e-3, atol=2e-3)


def test_pallas_lstm_step_matches_plain():
    from mxnet_tpu.ops.pallas.lstm import lstm_step

    rng = np.random.RandomState(1)
    B, Hn = 4, 8
    ib = jnp.asarray(rng.randn(B, 4 * Hn).astype(np.float32))
    h = jnp.asarray(rng.randn(B, Hn).astype(np.float32))
    c = jnp.asarray(rng.randn(B, Hn).astype(np.float32))
    wh = jnp.asarray(rng.randn(4 * Hn, Hn).astype(np.float32) * 0.1)
    h2, c2 = lstm_step(ib, h, c, wh, interpret=True)
    # plain reference: gates = ib + h @ wh^T (wh is (4H, H)), [i,f,g,o]
    gates = np.asarray(ib) + np.asarray(h) @ np.asarray(wh).T
    i, f, g, o = np.split(np.asarray(gates), 4, axis=1)
    sig = lambda x: 1 / (1 + np.exp(-x))  # noqa: E731
    c_want = sig(f) * np.asarray(c) + sig(i) * np.tanh(g)
    h_want = sig(o) * np.tanh(c_want)
    np.testing.assert_allclose(np.asarray(c2), c_want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h2), h_want, rtol=1e-5, atol=1e-5)


def test_pallas_grouped_matmul_matches_plain():
    """The expert layer's grouped products, interpreted, against one plain
    product a group (an empty group, a group boundary inside a row tile);
    tests/test_grouped_matmul.py has the rest."""
    from mxnet_tpu.ops.pallas import grouped_matmul as gm

    rng = np.random.RandomState(2)
    sizes = [5, 0, 20, 39]
    x = rng.randn(64, 128).astype(np.float32)
    w = rng.randn(4, 256, 128).astype(np.float32)
    ct = rng.randn(64, 256).astype(np.float32)
    at = np.concatenate([[0], np.cumsum(sizes)])
    groups = [slice(a, b) for a, b in zip(at[:-1], at[1:])]
    s = jnp.asarray(sizes, jnp.int32)
    with jax.default_matmul_precision("highest"):
        y = gm.gmm(jnp.asarray(x), jnp.asarray(w), s, tm=16, chunk=128,
                   interpret=True)
        dx = gm.gmm(jnp.asarray(ct), jnp.asarray(w), s, transposed=False,
                    tm=16, chunk=128, interpret=True)
        dw = gm.tgmm(jnp.asarray(ct), jnp.asarray(x), s, tm=16,
                     interpret=True)
    for got, want in (
            (y, np.concatenate([x[g] @ w[i].T for i, g in enumerate(groups)])),
            (dx, np.concatenate([ct[g] @ w[i] for i, g in enumerate(groups)])),
            (dw, np.stack([ct[g].T @ x[g] for g in groups]))):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                                   atol=1e-4)


def test_pallas_kernel_coverage_is_complete():
    """Every public Pallas kernel entry point must have an interpret-vs-
    plain consistency test above (fails when a kernel is added without
    one — the must-not-lose fast-path contract needs a correctness
    anchor first)."""
    import inspect
    import pkgutil

    from mxnet_tpu.ops import pallas

    tested = {"flash_attention", "lstm_step", "gmm", "tgmm",
              "grouped_matmul",
              # tests/test_lfm2.py: interpreted against the XLA formulation
              "gated_conv",
              # tests/test_qwen3_next.py: interpreted against the plain path
              "gated_delta"}
    helpers = {"on_tpu", "use_for", "kernel_qualifies", "fits",
               # selection predicates and what they count, not kernels
               "gmm_vmem_bytes", "tgmm_vmem_bytes", "group_visits",
               "gmm_column_blocks", "tgmm_wide"}
    public = set()
    # enumerate the PACKAGE, not a hardcoded list, so a kernel added in a
    # new ops/pallas module cannot escape the gate
    for info in pkgutil.iter_modules(pallas.__path__):
        mod = __import__("mxnet_tpu.ops.pallas.%s" % info.name,
                         fromlist=[info.name])
        for name, fn in vars(mod).items():
            # a kernel's entry point may be wrapped (jax.jit, custom_vjp)
            if (inspect.isfunction(inspect.unwrap(fn))
                    and not name.startswith("_")
                    and getattr(fn, "__module__", None) == mod.__name__):
                public.add(name)
    missing = public - tested - helpers
    assert not missing, (
        "Pallas kernels without an interpret-vs-plain consistency test: %s"
        % sorted(missing))


def test_pallas_flash_backward_multiblock_causal():
    """S=512 = 2 query x 2 key blocks: exercises the blocked backward's
    causal loop bounds (dq's `hi`, dkv's `lo`) which single-block shapes
    never touch; all THREE grads checked vs the XLA vjp in exact f32."""
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention
    from mxnet_tpu.ops.attention import dot_product_attention

    rng = np.random.RandomState(5)
    B, H, S, D = 1, 1, 512, 8
    q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    for causal in (True, False):
        gf = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=causal, interpret=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gp = jax.grad(lambda q, k, v: jnp.sum(dot_product_attention(
            q, k, v, causal=causal) ** 2), argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gp):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4,
                err_msg="%s causal=%s" % (name, causal))


def test_pallas_flash_gqa_matches_grouped_einsum():
    """Narrow-kv (GQA/MQA) flash: the kernel grids query-head groups
    over one VMEM-resident kv block — fwd and all three grads must match
    the XLA grouped einsum, with dk/dv at the NARROW (hkv) width (summed
    over each group inside the kernel)."""
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention
    from mxnet_tpu.ops.attention import _grouped_attention

    rng = np.random.RandomState(11)
    B, D = 2, 8
    for h, hkv, tq, tk, causal in ((4, 2, 256, 256, True),
                                   (4, 2, 256, 512, True),
                                   (8, 1, 256, 256, False),   # MQA
                                   (6, 3, 512, 512, True)):   # 2 q-blocks
        q = jnp.asarray(rng.randn(B, h, tq, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, hkv, tk, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, hkv, tk, D).astype(np.float32))
        got = flash_attention(q, k, v, causal=causal, interpret=True)
        want = _grouped_attention(q, k, v, hkv, causal)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4,
            err_msg="fwd h=%d hkv=%d tq=%d tk=%d" % (h, hkv, tq, tk))
        gf = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=causal, interpret=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gp = jax.grad(lambda q, k, v: jnp.sum(_grouped_attention(
            q, k, v, hkv, causal) ** 2), argnums=(0, 1, 2))(q, k, v)
        assert gf[1].shape == (B, hkv, tk, D)  # narrow kv grads
        for name, a, b in zip("qkv", gf, gp):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=5e-4,
                err_msg="%s h=%d hkv=%d tq=%d tk=%d" % (name, h, hkv,
                                                        tq, tk))


def test_pallas_flash_causal_cross_length_matches_xla():
    """tq != tk with causal: the kernels offset queries by (tk - tq) so
    the LAST query aligns with the last key — identical to the XLA
    paths' kv-cache-decode convention (attention.py:80). Regression for
    the round-3 advisor finding that the two paths silently disagreed."""
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention
    from mxnet_tpu.ops.attention import dot_product_attention

    rng = np.random.RandomState(7)
    B, H, D = 1, 2, 8
    for tq, tk in ((256, 512), (512, 768), (128, 256)):
        q = jnp.asarray(rng.randn(B, H, tq, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, H, tk, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, H, tk, D).astype(np.float32))
        got = flash_attention(q, k, v, causal=True, interpret=True)
        want = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg="fwd tq=%d tk=%d" % (tq, tk))
        gf = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gp = jax.grad(lambda q, k, v: jnp.sum(dot_product_attention(
            q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gp):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4,
                err_msg="%s tq=%d tk=%d" % (name, tq, tk))
    # tq > tk causal: fully-masked leading query rows (the kernel would
    # NaN on l=0) — kernel_qualifies refuses and the wrapper falls back
    # to the XLA path's finite uniform-attention degradation
    from mxnet_tpu.ops.pallas.flash_attention import kernel_qualifies
    assert not kernel_qualifies(512, 256, 8, compiled=False, causal=True)
    q = jnp.asarray(rng.randn(B, H, 512, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, 256, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, 256, D).astype(np.float32))
    got = flash_attention(q, k, v, causal=True, interpret=True)
    want = dot_product_attention(q, k, v, causal=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_pallas_flash_streaming_regime_matches_xla(monkeypatch):
    """The streaming kernels (seq > _RESIDENT_MAX: K/V — and Q in the
    dkv kernel — cross the grid one superblock at a time with the
    online-softmax / gradient carry in VMEM scratch) must agree with the
    XLA reference exactly like the resident ones. _RESIDENT_MAX and
    SUPER_TARGET are forced down so CI-sized shapes cross the boundary
    and every superblock case runs: multiple supersteps, GQA group
    accumulation, causal superstep skipping, and the tq != tk offset."""
    from mxnet_tpu.ops.pallas import flash_attention as fa
    from mxnet_tpu.ops.attention import _grouped_attention
    from mxnet_tpu.ops.attention import dot_product_attention

    # without the TPU pallas backend flash_attention falls back to the
    # XLA path for streaming shapes and this test would compare the
    # reference against itself
    assert fa.pltpu is not None, "pltpu missing; streaming path untestable"
    monkeypatch.setattr(fa, "_RESIDENT_MAX", 256)
    monkeypatch.setattr(fa, "SUPER_TARGET", 512)
    # at real sizes a streaming shape's accumulators do not fit the scoped
    # VMEM the fused backward lives in; at CI sizes the byte count has to
    # be told so
    monkeypatch.setattr(fa, "_SCOPED_VMEM", 0)
    rng = np.random.RandomState(13)
    B, D = 1, 8
    # (h, hkv, tq, tk, causal): all > 256 shapes take the streaming path.
    # The sweep runs at BOTH tile widths: bk=256 keeps inner=2 tiles per
    # superblock (the in-superblock fori_loop's causal partial bound),
    # which the default bk=512 collapses to inner=1 at these CI sizes;
    # bk=512 covers the production tile and _pick_block's 512->256
    # fallback on the odd-multiple tk=768 case.
    cases = ((2, 2, 1024, 1024, True),    # 2 supersteps, causal skip
             (2, 2, 1024, 1024, False),
             (4, 2, 512, 1024, True),     # GQA + offset + streaming
             (4, 1, 512, 1024, True),     # MQA: whole-group accumulation
             (2, 2, 512, 512, True),      # single superstep boundary
             (2, 2, 512, 768, True))      # tk an odd multiple of 256
    for bk in (256, fa.BLOCK_K):
        monkeypatch.setattr(fa, "BLOCK_K", bk)
        _run_streaming_cases(fa, rng, B, D, cases)


def _run_streaming_cases(fa, rng, B, D, cases):
    from mxnet_tpu.ops.attention import _grouped_attention
    from mxnet_tpu.ops.attention import dot_product_attention

    for h, hkv, tq, tk, causal in cases:
        q = jnp.asarray(rng.randn(B, h, tq, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, hkv, tk, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, hkv, tk, D).astype(np.float32))

        def ref(q, k, v, causal=causal, hkv=hkv):
            if hkv != q.shape[1]:
                return _grouped_attention(q, k, v, hkv, causal)
            return dot_product_attention(q, k, v, causal=causal)

        got = fa.flash_attention(q, k, v, causal=causal, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref(q, k, v)), rtol=2e-4,
            atol=2e-4, err_msg="fwd %s" % ((h, hkv, tq, tk, causal),))
        gf = jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
            q, k, v, causal=causal, interpret=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gp = jax.grad(lambda q, k, v: jnp.sum(ref(q, k, v) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gp):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=5e-4,
                err_msg="%s %s" % (name, (h, hkv, tq, tk, causal)))


# (h, hkv, tq, tk, causal, dtype, blocks (BLOCK_Q, BLOCK_K) or None for the
# module's, streaming (_RESIDENT_MAX, SUPER_TARGET) or None for resident)
_FLASH_CASES = {
    # 3 x 3 tiles of 256: q-block 0 walks one diagonal tile, q-block 2 two
    # interior tiles and a diagonal one; k-block 0 the same way round in dkv
    "causal_diagonal_and_interior_tiles":
        (2, 2, 768, 768, True, "float32", (256, 256), None),
    # offset = tk - tq = 384, no multiple of block_k 256: the diagonal
    # crosses the middle of a key tile
    "causal_offset_inside_a_key_tile":
        (2, 2, 128, 512, True, "float32", (256, 256), None),
    "causal_offset_rectangular_tiles":
        (2, 1, 256, 1024, True, "float32", (256, 512), None),
    # the module's own 512 x 512 tiles, 2 x 2 of them
    "causal_default_tiles": (1, 1, 1024, 1024, True, "float32", None, None),
    "noncausal_default_tiles": (2, 1, 512, 1024, False, "float32", None,
                                None),
    "group1_noncausal": (2, 2, 512, 512, False, "float32", (256, 256), None),
    "group3_causal": (6, 2, 512, 512, True, "float32", (256, 256), None),
    "mqa_noncausal_one_query_tile":
        (4, 1, 256, 768, False, "float32", (256, 256), None),
    # one grid step is one superblock of two tiles; the state crosses
    # supersteps and group heads in the same VMEM scratch
    "streaming_causal_gqa_offset":
        (4, 2, 512, 1024, True, "float32", (256, 256), (256, 512)),
    "streaming_noncausal_group1":
        (2, 2, 1024, 512, False, "float32", (256, 256), (256, 512)),
    "streaming_causal_one_tile_superblocks":
        (2, 1, 768, 768, True, "float32", (256, 256), (256, 256)),
    # bfloat16 in, bfloat16 out: against the bf16 XLA path
    "bf16_causal_gqa": (4, 2, 512, 512, True, "bfloat16", (256, 256), None),
    "bf16_noncausal_default_tiles":
        (2, 2, 512, 1024, False, "bfloat16", None, None),
    "bf16_streaming_causal":
        (2, 1, 512, 1024, True, "bfloat16", (256, 256), (256, 512)),
}


def _flash_case_setup(monkeypatch, fa, blocks, streaming, path="split",
                      shape=None):
    """Tiles, regime and backward path of one case. The path follows from
    the shapes alone (`_fused_q_super`: `_fused_bwd_vmem_bytes` against
    `_SCOPED_VMEM`), and every CI-sized shape fits whole: "split" tells
    the byte count that nothing does, which is what a streaming shape
    reads at its real size; "superblocked" (``shape``: tq, tk, dtype)
    that a superblock of one query tile does and no longer one, as 8192
    tokens read at theirs a superblock of 2048."""
    if blocks:
        monkeypatch.setattr(fa, "BLOCK_Q", blocks[0])
        monkeypatch.setattr(fa, "BLOCK_K", blocks[1])
    if streaming:
        monkeypatch.setattr(fa, "_RESIDENT_MAX", streaming[0])
        monkeypatch.setattr(fa, "SUPER_TARGET", streaming[1])
    if path == "split":
        monkeypatch.setattr(fa, "_SCOPED_VMEM", 0)
    if path == "superblocked":
        tq, tk, dtype = shape
        monkeypatch.setattr(fa, "_SCOPED_VMEM", fa._fused_bwd_vmem_bytes(
            tq, tk, fa._LANES, jnp.dtype(dtype).itemsize,
            fa._pick_block(tq, fa.BLOCK_Q)))


@pytest.fixture
def built():
    """What the kernels traced inside the test tell of themselves
    (``note_built``), the backward's choice among it."""
    from mxnet_tpu.ops.registry import built_layers

    with built_layers() as into:
        yield into.layers


def _assert_built_one(built, path, tq=None):
    """One backward was traced, on ``path``: "superblocked" is the fused
    kernel at a query superblock under ``tq``, "fused" at ``tq`` whole."""
    got = [(r["backward"], r["q_super"]) for r in built if r.get("backward")]
    assert len(got) == 1 and got[0][0] == {"superblocked": "fused"}.get(
        path, path), got
    if path == "superblocked":
        assert got[0][1] < tq, got
    elif path == "fused" and tq is not None:
        assert got[0][1] == tq, got


def _case_paths(cases, is_streaming, query_tiles):
    """(case, path) pairs: every case through the dq and dkv kernels, the
    resident ones through the fused kernel too, and those of more than
    one query tile through it at a superblock of one tile."""
    return [(c, p) for c in sorted(cases)
            for p in ("split", "fused", "superblocked")
            if p == "split" or not is_streaming(cases[c])
            and (p == "fused" or query_tiles(cases[c]) > 1)]


@pytest.mark.parametrize("case,path", _case_paths(
    _FLASH_CASES, lambda c: c[7] is not None,
    lambda c: c[2] // (c[6][0] if c[6] else 512)))
def test_pallas_flash_cases_match_xla(case, path, monkeypatch, built):
    """Forward and all three gradients of the flash kernels against the
    XLA reference, one case per way the tile walk can go: which tiles a
    block visits, where the diagonal crosses them, the group's sum in
    dk/dv, the superblock regime, and bfloat16 operands; the backward
    through the two kernels and through the fused one, and the kernels'
    own record says which was built."""
    from mxnet_tpu.ops.pallas import flash_attention as fa
    from mxnet_tpu.ops.attention import _grouped_attention

    h, hkv, tq, tk, causal, dtype, blocks, streaming = _FLASH_CASES[case]
    _flash_case_setup(monkeypatch, fa, blocks, streaming, path,
                      (tq, tk, dtype))
    rng = np.random.RandomState(sorted(_FLASH_CASES).index(case))
    B, D = 1, 8
    q = jnp.asarray(rng.randn(B, h, tq, D).astype(np.float32), dtype)
    k = jnp.asarray(rng.randn(B, hkv, tk, D).astype(np.float32), dtype)
    v = jnp.asarray(rng.randn(B, hkv, tk, D).astype(np.float32), dtype)

    def loss(att):
        return lambda q, k, v: jnp.sum(
            att(q, k, v).astype(jnp.float32) ** 2)

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal, interpret=True)

    def ref(q, k, v):
        return _grouped_attention(q, k, v, hkv, causal)

    # float32: exact up to summation order; bfloat16: the XLA path rounds
    # its probabilities to bfloat16 and every result is stored in it
    tol = (dict(rtol=2e-4, atol=2e-4), dict(rtol=2e-3, atol=5e-4)) \
        if dtype == "float32" else (dict(rtol=2e-2, atol=2e-2),
                                    dict(rtol=4e-2, atol=6e-2))
    got, want = kernel(q, k, v), ref(q, k, v)
    assert got.dtype == q.dtype and got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               err_msg="fwd", **tol[0])
    gk = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    assert gk[1].shape == k.shape and gk[1].dtype == k.dtype
    for name, a, b in zip("qkv", gk, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   err_msg="d" + name, **tol[1])
    _assert_built_one(built, path, tq)


_FLASH_LSE_CASES = {
    # (group, tq, tk, causal, streaming)
    "resident_causal_gqa": (2, 512, 512, True, None),
    "resident_noncausal": (1, 256, 512, False, None),
    "streaming_causal_offset": (2, 512, 1024, True, (256, 512)),
}


@pytest.mark.parametrize("case,path", _case_paths(
    _FLASH_LSE_CASES, lambda c: c[4] is not None, lambda c: c[1] // 256))
def test_pallas_flash_with_lse_cotangent(case, path, monkeypatch, built):
    """`_flash_with_lse` (ring attention's per-shard call): lse is a real
    output, and a non-zero cotangent on it folds into D in the backward,
    before either backward path. A loss over BOTH outputs against the same
    loss through plain jnp."""
    from mxnet_tpu.ops.pallas import flash_attention as fa

    g, tq, tk, causal, streaming = _FLASH_LSE_CASES[case]
    _flash_case_setup(monkeypatch, fa, (256, 256), streaming, path,
                      (tq, tk, "float32"))
    rng = np.random.RandomState(3)
    rows, D = 2, 8
    scale = 0.4
    q = jnp.asarray(rng.randn(rows, g, tq, D).astype(np.float32))
    k = jnp.asarray(rng.randn(rows, tk, D).astype(np.float32))
    v = jnp.asarray(rng.randn(rows, tk, D).astype(np.float32))
    w = jnp.asarray(rng.randn(rows, g, 1, tq).astype(np.float32))

    def ref(q, k, v):
        s = jnp.einsum("rgqd,rkd->rgqk", q, k) * scale
        if causal:
            keep = (jnp.arange(tq)[:, None] + (tk - tq)
                    >= jnp.arange(tk)[None, :])
            s = jnp.where(keep, s, -1e30)
        lse = jax.nn.logsumexp(s, axis=-1)
        out = jnp.einsum("rgqk,rkd->rgqd", jnp.exp(s - lse[..., None]), v)
        return out, lse[:, :, None, :]

    def kernel(q, k, v):
        return fa._flash_with_lse(q, k, v, causal, scale, True)

    def loss(f):
        def fn(q, k, v):
            out, lse = f(q, k, v)
            return jnp.sum(out ** 2) + jnp.sum(w * lse)
        return fn

    (out, lse), (out_r, lse_r) = kernel(q, k, v), ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r),
                               rtol=2e-4, atol=2e-4)
    gk = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=5e-4, err_msg="d" + name)
    _assert_built_one(built, path, tq)


# (rows, group, tq, tk, causal, dtype, blocks[, query superblock rows,
# window]): without a superblock, the whole query sequence is one
_FUSED_CASES = {
    # 3 x 3 tiles: key block 0 walks a diagonal tile and two interior
    # ones, key block 2 the diagonal one alone
    "causal_tiles_on_and_off_the_diagonal":
        (2, 2, 768, 768, True, "float32", (256, 256)),
    # offset 384 = tk - tq: the diagonal crosses the middle of a key tile
    "causal_offset_tq_under_tk": (1, 2, 128, 512, True, "float32",
                                  (256, 256)),
    "causal_offset_rectangular_tiles":
        (2, 1, 256, 1024, True, "float32", (256, 512)),
    "group1_default_tiles": (1, 1, 1024, 1024, True, "float32", None),
    "group3_causal": (2, 3, 512, 512, True, "float32", (256, 256)),
    "mqa_noncausal": (1, 4, 256, 768, False, "float32", (256, 256)),
    "noncausal_default_tiles": (2, 1, 512, 1024, False, "float32", None),
    "bf16_causal_gqa": (2, 2, 512, 512, True, "bfloat16", (256, 256)),
    # query superblocks of one or two tiles: the diagonal crosses each
    # superblock, and key blocks past it are dead steps of the grid
    "superblock_causal_diagonal_crosses_boundaries":
        (2, 2, 768, 768, True, "float32", (256, 256), 256),
    # a band of 200 keys: key block 0 is live for superblock 0 and dead for
    # superblock 1 (its first query's band starts at 313), key block 1's
    # reach ends inside superblock 1 (at query 710), and key blocks past
    # superblock 0's diagonal are dead for it
    "superblock_band_ends_inside_a_superblock":
        (1, 2, 1024, 1024, True, "float32", (256, 256), 512, 200),
    "superblock_group3_causal":
        (2, 3, 1024, 1024, True, "float32", (256, 256), 256),
    # offset 512 = tk - tq: superblocks at query positions 512 and 768
    "superblock_offset_tq_under_tk":
        (2, 2, 512, 1024, True, "float32", (256, 256), 256),
    "superblock_noncausal": (1, 2, 512, 768, False, "float32", (256, 256),
                             256),
    "superblock_bf16_causal_gqa":
        (2, 2, 1024, 1024, True, "bfloat16", (256, 256), 512),
}


@pytest.mark.parametrize("case", sorted(_FUSED_CASES))
def test_pallas_flash_fused_backward_equals_two_kernel_path(case,
                                                            monkeypatch):
    """The fused backward against the dq and dkv kernels on the SAME
    inputs (q, k, v, dO, lse, D), both called directly, whole or in query
    superblocks: dk and dv walk the same tiles in the same order (group
    head, superblock, tile) and are equal to the last bit; dq adds the
    same per-tile products, from transposed scores."""
    from mxnet_tpu.ops.pallas import flash_attention as fa

    rows, g, tq, tk, causal, dtype, blocks = _FUSED_CASES[case][:7]
    q_super, window = (_FUSED_CASES[case][7:] + (None, 0))[:2]
    _flash_case_setup(monkeypatch, fa, blocks, None, "fused")
    rng = np.random.RandomState(sorted(_FUSED_CASES).index(case))
    D, scale = 8, 0.35

    def rand(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32), dtype)

    q, do = rand(rows, g, tq, D), rand(rows, g, tq, D)
    k, v = rand(rows, tk, D), rand(rows, tk, D)
    o, lse = fa._fa_forward(q, k, v, causal, scale, True, with_lse=True,
                            window=window)
    args = (q, k, v, do, lse, fa._row_sums(o, do))
    fused = fa._fa_backward_fused(args, causal, scale, True, window, q_super)
    split = fa._fa_backward_split(args, causal, scale, True, window)
    for name, a, b in zip(("dq", "dk", "dv"), fused, split):
        assert a.shape == b.shape and a.dtype == b.dtype, name
    np.testing.assert_array_equal(np.asarray(fused[1], np.float32),
                                  np.asarray(split[1], np.float32))
    np.testing.assert_array_equal(np.asarray(fused[2], np.float32),
                                  np.asarray(split[2], np.float32))
    tol = dict(rtol=1e-5, atol=1e-6) if dtype == "float32" \
        else dict(rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(fused[0], np.float32),
                               np.asarray(split[0], np.float32), **tol)


@pytest.mark.parametrize("shape,want,q_super", [
    # (rows, group, tq, tk, dtype, head size, window): the benchmark cell
    # lm_train_4k, 15.5 MiB of the 16 a kernel gets, the whole sequence ...
    ((4, 12, 4096, 4096, "bfloat16", 128, 0), "fused", 4096),
    # ... and the same lengths in float32: 22.5 whole, 15.25 at 2048 rows
    ((4, 12, 4096, 4096, "float32", 128, 0), "fused", 2048),
    ((1, 2, 8192, 8192, "bfloat16", 128, 0), "fused", 2048),
    ((1, 2, 8192, 8192, "float32", 128, 0), "fused", 1024),
    # dk's and dv's whole-sequence accumulators alone take the 16 MiB
    ((1, 2, 16384, 16384, "bfloat16", 128, 0), "split", None),
    # few queries against a long cache: K and V arrive block by block, so
    # only dk's and dv's accumulators grow with it (15.1 MiB)
    ((2, 12, 1024, 10240, "bfloat16", 128, 0), "fused", 1024),
    ((2, 12, 512, 4096, "bfloat16", 128, 0), "fused", 512),
    ((2, 1, 768, 1280, "float32", 128, 0), "fused", 768),
    # the cells smallthinker_train_8k (a global layer and a 4096 band) and
    # lfm2_train_8k (two sequences at head size 64, counted at 128)
    ((4, 7, 8192, 8192, "bfloat16", 128, 0), "fused", 2048),
    ((4, 7, 8192, 8192, "bfloat16", 128, 4096), "fused", 2048),
    ((16, 4, 8192, 8192, "bfloat16", 64, 0), "fused", 2048),
])
def test_pallas_flash_backward_path_follows_from_the_shapes(shape, want,
                                                            q_super, built):
    """`_fa_backward` takes the fused kernel at the longest query
    superblock whose blocks, accumulators and tiles the byte count fits
    into a kernel's scoped VMEM, and the dq and dkv kernels where none
    does, and says which path it built, and at which superblock, as the
    program is traced (nothing runs here: the real shapes are only
    traced)."""
    from mxnet_tpu.ops.pallas import flash_attention as fa

    rows, g, tq, tk, dtype, d, window = shape
    itemsize = jnp.dtype(dtype).itemsize
    assert fa._fused_q_super(tq, tk, d, itemsize) == q_super
    if q_super:
        assert fa._fused_bwd_vmem_bytes(
            tq, tk, d, itemsize, q_super) <= fa._SCOPED_VMEM
    if q_super != tq:
        # the next longer superblock, or one query tile, does not fit
        longer = 2 * q_super if q_super else fa._pick_block(tq, fa.BLOCK_Q)
        assert fa._fused_bwd_vmem_bytes(
            tq, tk, d, itemsize, longer) > fa._SCOPED_VMEM
    q = jax.ShapeDtypeStruct((rows, g, tq, d), jnp.dtype(dtype))
    kv = jax.ShapeDtypeStruct((rows, tk, d), jnp.dtype(dtype))
    lse = jax.ShapeDtypeStruct((rows, g, 1, tq), jnp.float32)
    dq, dk, dv = jax.eval_shape(
        lambda q, k, v, o, lse, do: fa._fa_backward(
            q, k, v, o, lse, do, True, d ** -0.5, True, window=window),
        q, kv, kv, q, lse, q)
    assert (dq.shape, dq.dtype) == (q.shape, q.dtype)
    assert (dk.shape, dv.shape, dk.dtype) == (kv.shape, kv.shape, kv.dtype)
    _assert_built_one(built, want if q_super in (None, tq)
                      else "superblocked", tq)


@pytest.mark.parametrize("t,pref,want", [
    (4096, 512, 512), (768, 512, 256), (1280, 512, 256), (2816, 512, 256),
    (1024, 512, 512), (128, 512, 128), (512, 256, 256)])
def test_pallas_flash_pick_block(t, pref, want):
    """The tile is the largest of {pref, pref/2, ..., 256} that divides
    the length (the length itself under 256): raising the preferred tile
    never narrows which lengths the kernels take."""
    from mxnet_tpu.ops.pallas import flash_attention as fa

    assert fa._pick_block(t, pref) == want
    assert fa.kernel_qualifies(t, t, 128)
    assert t % want == 0
