"""What SmallThinker-21BA3B forced into the trainer (ISSUE 33): a window and a
RoPE base on ``MultiHeadAttention`` (einsum paths and the four flash kernels,
interpreted here), the ``ExpertFFN`` op (a chip's share of a top-k expert
layer: nothing dropped, grouped products, shares that add up), and the block
builder's per-layer kinds with today's graph unchanged."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.ops import moe
from mxnet_tpu.ops.registry import get_op


# --- attention: a band and a base ------------------------------------------------

def _plain_attention(q, k, v, window, rope_base=None):
    """(B, H, T, D) against (B, Hkv, T, D): causal softmax under a plain
    band mask, in float32, the kv heads repeated."""
    b, h, t, d = q.shape
    if rope_base:
        half = d // 2
        ang = (np.arange(t)[:, None]
               * rope_base ** (-np.arange(half) / half)).astype(np.float32)

        def rot(x):
            x1, x2 = x[..., :half], x[..., half:]
            return jnp.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                                    x2 * np.cos(ang) + x1 * np.sin(ang)], -1)

        q, k = rot(q), rot(k)
    k = jnp.repeat(k, h // k.shape[1], 1)
    v = jnp.repeat(v, h // v.shape[1], 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    keep = (j <= i) & ((j > i - window) if window else True)
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _qkv(t, h, hkv, d, seed):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(1, n, t, d).astype(np.float32))
            for n in (h, hkv, hkv, h)]


def _close(f, ref, args, w, tol=3e-5):
    out, vjp = jax.vjp(f, *args)
    want, vjp_ref = jax.vjp(ref, *args)
    np.testing.assert_allclose(out, want, atol=tol, rtol=tol)
    for a, b in zip(vjp(w), vjp_ref(w)):
        np.testing.assert_allclose(a, b, atol=tol, rtol=tol)


@pytest.mark.parametrize("hkv", [4, 2])
@pytest.mark.parametrize("window", [0, 5, 16, 40])
@pytest.mark.parametrize("rope_base", [None, 10000.0, 1.5e6])
def test_windowed_attention_op_einsum_paths(hkv, window, rope_base):
    """The op off the kernel (what the CPU runs): a window under, at and
    over the sequence, with and without RoPE, at a second base, against a
    plain mask: output and the gradients of q, k and v."""
    t, h, d = 16, 4, 8
    q, k, v, w = _qkv(t, h, hkv, d, 3 + window)
    op = get_op("MultiHeadAttention")
    attrs = op.parse_attrs(dict(
        num_heads=h, num_kv_heads=hkv, causal=True, use_flash=False,
        window=window, use_rope=rope_base is not None,
        **({"rope_base": rope_base} if rope_base else {})))

    def merged(x):
        return x.transpose(0, 2, 1, 3).reshape(1, t, -1)

    def f(q, k, v):
        (out,), _ = op.impl(attrs, (merged(q), merged(k), merged(v)), (),
                            None)
        return out.reshape(1, t, h, d).transpose(0, 2, 1, 3)

    _close(f, lambda q, k, v: _plain_attention(q, k, v, window, rope_base),
           (q, k, v), w)


def test_window_needs_causal():
    op = get_op("MultiHeadAttention")
    x = jnp.zeros((1, 8, 8))
    with pytest.raises(ValueError, match="causal"):
        op.impl(op.parse_attrs(dict(num_heads=2, window=4)), (x, x, x), (),
                None)


# (t, window, regime): 256-wide tiles; resident takes the fused backward,
# streaming (512-key superblocks) the dq and dkv kernels
_BAND_CASES = [
    (768, 100, "resident"), (768, 256, "resident"), (768, 300, "resident"),
    (768, 2000, "resident"), (512, 0, "resident"),
    (1536, 100, "streaming"), (1536, 512, "streaming"),
    (1536, 700, "streaming"), (1024, 256, "streaming"),
]


@pytest.mark.parametrize("t,window,regime", _BAND_CASES)
def test_windowed_flash_kernels_interpreted(t, window, regime, monkeypatch):
    """The four kernels in interpret mode against a plain mask: a window
    under, at and over a tile and over the sequence; one superblock with
    the fused backward, and streamed superblocks with the split one, whose
    index maps clamp to the band from both sides."""
    from mxnet_tpu.ops.pallas import flash_attention as fa
    from mxnet_tpu.ops.registry import built_layers

    monkeypatch.setattr(fa, "BLOCK_Q", 256)
    monkeypatch.setattr(fa, "BLOCK_K", 256)
    if regime == "streaming":
        monkeypatch.setattr(fa, "_RESIDENT_MAX", 256)
        monkeypatch.setattr(fa, "SUPER_TARGET", 512)
        monkeypatch.setattr(fa, "_SCOPED_VMEM", 0)
    band = window if 0 < window < t else None
    q, k, v, w = _qkv(t, 4, 2, 8, t + window)
    with built_layers() as built:
        _close(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, interpret=True, window=window),
            lambda q, k, v: _plain_attention(q, k, v, window), (q, k, v), w)
    bands = [r["window"] for r in built.layers if r.get("kernel")]
    assert bands and set(bands) == {band}


def test_band_tile_walk_skips_what_the_band_does_not_reach():
    """The walk's bounds, from positions alone: at 8192 tokens and 512 tiles
    a 4096 window's query tile walks at most 9 key tiles where the causal
    walk reaches 16, and a key tile is walked by at most 9 query tiles."""
    from mxnet_tpu.ops.pallas import flash_attention as fa

    n, b, win = 16, 512, 4096
    walked = causal = 0
    for qi in range(n):
        lo, hi = fa._key_tiles(True, qi * b, b, 0, b, n, win)
        walked += int(hi) - int(lo)
        causal += int(fa._key_tiles(True, qi * b, b, 0, b, n)[1])
        assert int(hi) - int(lo) <= win // b + 1
        # every tile the band reaches is walked
        assert int(lo) * b <= max(qi * b - win + 1, 0)
    assert causal == n * (n + 1) // 2 and walked == 108
    back = sum(int(hi) - int(lo) for lo, hi in (
        fa._query_tiles(True, ki * b, b, 0, b, n, win) for ki in range(n)))
    assert back == walked


# --- the expert layer ------------------------------------------------------------

def _expert_args(e, held, d, f, tokens, seed, router_scale=0.3):
    rng = np.random.RandomState(seed)

    def r(*shape, scale=0.3):
        return jnp.asarray(scale * rng.randn(*shape).astype(np.float32))

    return (r(2, tokens // 2, d, scale=1.0), r(2, tokens // 2, d, scale=1.0),
            r(e, d, scale=router_scale), r(held, f, d), r(held, f, d),
            r(held, d, f))


def _dense_experts(x, xr, wr, wg, wu, wd, first, top_k):
    """Every token through every held expert, weighted by a mask built
    from the k-th largest logit: no sort, no gather, no ragged product."""
    hp = jax.lax.Precision.HIGHEST
    x2, xr2 = x.reshape(-1, x.shape[-1]), xr.reshape(-1, x.shape[-1])
    r = jnp.einsum("td,ed->te", xr2, wr, precision=hp)
    kth = jnp.sort(r, axis=-1)[:, -top_k][:, None]
    w = jax.nn.softmax(jnp.where(r >= kth, r, -jnp.inf), axis=-1)
    y = jnp.zeros_like(x2)
    for e in range(wg.shape[0]):
        h = jax.nn.relu(jnp.einsum("td,fd->tf", x2, wg[e], precision=hp)) \
            * jnp.einsum("td,fd->tf", x2, wu[e], precision=hp)
        y = y + w[:, first + e][:, None] * jnp.einsum(
            "tf,df->td", h, wd[e], precision=hp)
    return y.reshape(x.shape), jnp.sum(
        (r >= kth)[:, first:first + wg.shape[0]], axis=0)


def _expert_op(e, held, first, top_k):
    op = get_op("ExpertFFN")
    attrs = op.parse_attrs(dict(num_experts=e, experts_held=held,
                                first_expert=first, top_k=top_k))

    def f(*args):
        (y, counts), _ = op.impl(attrs, args, (), None)
        return y, counts

    return f


@pytest.mark.parametrize("e,held,first,top_k", [
    (8, 4, 2, 3), (8, 8, 0, 2), (64, 16, 0, 6), (64, 16, 16, 6),
    (16, 4, 12, 2), (8, 2, 6, 3)])
def test_expert_layer_against_a_dense_loop(e, held, first, top_k):
    """Output, the per-expert count and all six gradients, float32, against
    every token through every held expert (<= 1e-5), through a buffer of
    the worst case (fewer experts held than a token chooses: of those)."""
    tokens, d, f = 48, 16, 24
    assert moe.buffer_rows(tokens, top_k, held, e) == (
        tokens * min(top_k, held), tokens * top_k * held / e)
    args = _expert_args(e, held, d, f, tokens, seed=e + first)
    with jax.default_matmul_precision("highest"):
        (y, counts), vjp = jax.vjp(_expert_op(e, held, first, top_k), *args)
        (want, want_counts), vjp_ref = jax.vjp(
            lambda *a: _dense_experts(*a, first, top_k), *args)
        ct = jnp.asarray(np.random.RandomState(0).randn(*y.shape)
                         .astype(np.float32))
        got = vjp((ct, jnp.zeros_like(counts)))
        ref = vjp_ref((ct, np.zeros(want_counts.shape, jax.dtypes.float0)))
    np.testing.assert_allclose(y, want, atol=1e-5)
    np.testing.assert_array_equal(counts, want_counts)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("to_held", [True, False])
def test_expert_layer_drops_nothing(to_held):
    """Every token's every choice on the held experts (the worst case: the
    buffer is full), and none at all (the products walk a buffer of
    zeros): the count says so and the output is the dense sum's."""
    e, held, top_k, tokens, d, f = 16, 4, 3, 40, 16, 24
    x, xr, wr, wg, wu, wd = _expert_args(e, held, d, f, tokens, seed=5)
    # a router that only ever prefers the held four, or never does
    xr = jnp.abs(xr)
    sign = jnp.where(jnp.arange(e) < held, 1.0, -1.0) * (1 if to_held else -1)
    wr = jnp.abs(wr) * sign[:, None]
    args = (x, xr, wr, wg, wu, wd)
    assert moe.buffer_rows(tokens, top_k, held, e)[0] == tokens * top_k
    with jax.default_matmul_precision("highest"):
        y, counts = jax.jit(_expert_op(e, held, 0, top_k))(*args)
        want, _ = _dense_experts(*args, 0, top_k)
        g = jax.grad(lambda *a: jnp.sum(_expert_op(e, held, 0, top_k)(*a)[0]
                                        ** 2), argnums=(0, 3))(*args)
        g_ref = jax.grad(lambda *a: jnp.sum(_dense_experts(*a, 0, top_k)[0]
                                            ** 2), argnums=(0, 3))(*args)
    assert float(counts.sum()) == (tokens * top_k if to_held else 0)
    np.testing.assert_allclose(y, want, atol=1e-5)
    if not to_held:
        assert not np.asarray(y).any()
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_expert_shares_add_up_to_the_uncut_layer():
    """The four shares ``first_expert`` 0, 16, 32, 48 of a layer, each
    with its own experts and the whole router, sum to what one holder of
    all 64 computes: the router is counted once a share and nothing else
    is shared."""
    e, top_k, tokens, d, f = 64, 6, 32, 16, 24
    x, xr, wr, wg, wu, wd = _expert_args(e, e, d, f, tokens, seed=9)
    with jax.default_matmul_precision("highest"):
        whole, counts = _expert_op(e, e, 0, top_k)(x, xr, wr, wg, wu, wd)
        parts = [_expert_op(e, 16, first, top_k)(
            x, xr, wr, wg[first:first + 16], wu[first:first + 16],
            wd[first:first + 16]) for first in (0, 16, 32, 48)]
        uncut, _ = _dense_experts(x, xr, wr, wg, wu, wd, 0, top_k)
    np.testing.assert_allclose(sum(y for y, _ in parts), whole, atol=1e-5)
    np.testing.assert_allclose(whole, uncut, atol=1e-5)
    np.testing.assert_array_equal(jnp.concatenate([c for _, c in parts]),
                                  counts)
    assert float(counts.sum()) == tokens * top_k


def test_expert_layer_refuses_wrong_shares():
    args = _expert_args(8, 4, 8, 8, 8, seed=1)
    with pytest.raises(ValueError, match="experts_held"):
        _expert_op(8, 3, 0, 2)(*args)
    with pytest.raises(ValueError, match="top_k"):
        _expert_op(8, 4, 6, 2)(*args)


def _moves_at_the_parent():
    """``_spread`` and ``_collect`` as they stood before PR 36, the plain
    reference: the combine takes with the ``(N, k)`` index into an
    ``(N, k, d)`` array, masks it and sums over axis 1; ``_spread`` masks
    its rows always."""
    def take(src, index, mask):
        out = jnp.take(src, index, axis=0)
        return jnp.where(mask.reshape(mask.shape + (1,) * (src.ndim - 1)),
                         out, 0)

    @jax.custom_vjp
    def spread(x, plan):
        return take(x, plan["tok"], plan["valid"])

    @jax.custom_vjp
    def collect(rows, plan):
        got = take(rows, plan["slot"], plan["ok"])
        return jnp.sum(got, axis=1, dtype=jnp.float32).astype(rows.dtype)

    spread.defvjp(lambda x, plan: (spread(x, plan), plan),
                  lambda plan, g: (collect(g, plan), None))
    collect.defvjp(lambda rows, plan: (collect(rows, plan), plan),
                   lambda plan, g: (spread(g, plan), None))
    return spread, collect


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-6), ("bfloat16", 0.03)])
@pytest.mark.parametrize("tokens,top_k,held,e,prefers", [
    (48, 3, 4, 8, "any"),      # a row an assignment: _spread unmasked
    (64, 6, 16, 64, "any"),    # smallthinker_train_8k's ratios
    (32, 4, 8, 64, "any"),     # lfm2_train_8k's
    (48, 3, 2, 8, "any"),      # experts_held < top_k: clamped slots
    (40, 4, 1, 8, "any"),      # one held expert, a buffer of N rows
    (40, 3, 4, 16, "held"),    # every assignment held: total == rows
    (40, 3, 4, 16, "others"),  # none: the products walk no assignment
    (40, 3, 2, 16, "held"),    # a full buffer and every slot past it clamped
])
def test_expert_moves_against_the_parents_formulation(
        tokens, top_k, held, e, prefers, dtype, tol, monkeypatch):
    """The flat choice-major combine, and ``_spread`` without its mask
    where a row stands for every assignment, against the moves as the
    parent wrote them: the output and the gradients of ``data``, the
    router matrix and all three expert matrices, in float32 equal to
    rounding and in bfloat16 within the op's gate
    (``test_expert_layer_down_the_kernel_path``)."""
    d, f = 16, 24
    x, xr, wr, wg, wu, wd = _expert_args(e, held, d, f, tokens,
                                         seed=tokens + top_k + held)
    if prefers != "any":  # a router that only ever prefers the held, or never
        sign = jnp.where(jnp.arange(e) < held, 1.0, -1.0)
        xr, wr = jnp.abs(xr), jnp.abs(wr) * (
            sign if prefers == "held" else -sign)[:, None]
    args = tuple(a.astype(dtype) for a in (x, xr)) + (wr,) + tuple(
        a.astype(dtype) for a in (wg, wu, wd))
    ct = jnp.asarray(np.random.RandomState(1).randn(*x.shape), dtype)
    rows = moe.buffer_rows(tokens, top_k, held, e)[0]
    layer = _expert_op(e, held, 0, top_k)

    def run():
        with jax.default_matmul_precision("highest"):
            (y, counts), vjp = jax.vjp(layer, *args)
            grads = vjp((ct, jnp.zeros_like(counts)))
        return (y, counts) + tuple(grads[i] for i in (0, 2, 3, 4, 5))

    got = run()
    # the mask is gone from _spread exactly where a row stands for every
    # assignment, a fact of the shapes
    every = jnp.arange(tokens * top_k, dtype=jnp.int32)
    plan = moe._plan(every, every, 0, rows, top_k)
    assert (plan["spread_mask"] is None) == (rows == tokens * top_k)
    assert plan["slot"].shape == (tokens, top_k)
    for name, move in zip(("_spread", "_collect"), _moves_at_the_parent()):
        monkeypatch.setattr(moe, name, move)
    want = run()
    total = float(want[1].sum())
    assert prefers == "any" or total == (
        tokens * min(top_k, held) if prefers == "held" else 0)
    assert prefers != "held" or total == rows
    np.testing.assert_array_equal(got[1], want[1])
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        b = np.asarray(b, np.float32)
        assert np.isfinite(b).all()
        np.testing.assert_allclose(np.asarray(a, np.float32), b, rtol=0,
                                   atol=tol * max(1.0, np.abs(b).max()))


# --- the block builder -----------------------------------------------------------

def test_todays_transformer_lm_graph_is_unchanged():
    """``get_symbol("transformer-lm")`` with today's arguments: the
    argument list, its shapes and the attention nodes' attributes are what
    they were before the block took kinds."""
    sym = models.get_symbol("transformer-lm", num_classes=50, num_layers=2,
                            num_heads=4, model_dim=16, ffn_dim=32,
                            num_kv_heads=2, scalar_loss=True)
    want = ["data", "embed_weight"]
    for i in range(2):
        want += ["layer%d_%s" % (i, n) for n in (
            "ln1_gamma", "ln1_beta", "q_weight", "k_weight", "v_weight",
            "o_weight", "ln2_gamma", "ln2_beta", "ffn1_weight", "ffn1_bias",
            "ffn2_weight", "ffn2_bias")]
    want += ["lnf_gamma", "lnf_beta", "pred_weight", "pred_bias",
             "softmax_label"]
    assert sym.list_arguments() == want
    shapes, _, _ = sym.infer_shape(data=(2, 8), softmax_label=(2, 8))
    got = dict(zip(want, shapes))
    assert got["layer1_k_weight"] == (8, 16) and got["pred_bias"] == (50,)
    assert got["layer0_ffn1_weight"] == (32, 16)
    for node in sym._nodes():
        if not node.is_var and node.op.name == "MultiHeadAttention":
            given = {k for k, v in node.attrs.items()
                     if v != node.op.param_spec[k]}
            assert given == {"num_heads", "num_kv_heads", "causal",
                             "use_rope"}


def _mixed_symbol(**kw):
    kinds = [{"norm": "rms", "ffn": "experts", "window": w, "rope": r,
              "rope_base": 1.5e6} for w, r in ((0, False), (4, True))]
    return models.get_symbol(
        "transformer-lm", num_classes=50, num_layers=2, num_heads=4,
        head_dim=8, model_dim=16, ffn_dim=12, num_kv_heads=2, layers=kinds,
        experts={"num_experts": 8, "experts_held": 4, "first_expert": 0,
                 "top_k": 2}, final_norm="rms", head_bias=False, **kw)


def test_block_kinds_build_window_and_nope_layers_in_one_model():
    sym = _mixed_symbol(scalar_loss=True)
    args = sym.list_arguments()
    assert "layer0_ln1_beta" not in args and "pred_bias" not in args
    shapes = dict(zip(args, sym.infer_shape(data=(2, 8),
                                            softmax_label=(2, 8))[0]))
    assert shapes["layer0_q_weight"] == (32, 16)      # 4 heads of 8 over 16
    assert shapes["layer0_o_weight"] == (16, 32)
    assert shapes["layer1_router_weight"] == (8, 16)
    assert shapes["layer1_gate_weight"] == (4, 12, 16)
    assert shapes["layer1_down_weight"] == (4, 16, 12)
    attn = {n.name: n.attrs for n in sym._nodes()
            if not n.is_var and n.op.name == "MultiHeadAttention"}
    assert not attn["layer0_attn"]["use_rope"]
    assert attn["layer0_attn"]["window"] == 0
    assert attn["layer1_attn"]["window"] == 4
    assert attn["layer1_attn"]["rope_base"] == 1.5e6
    with pytest.raises(ValueError, match="unknown"):
        models.get_symbol("transformer-lm", num_layers=1,
                          layers=[{"windw": 4}])
    with pytest.raises(ValueError, match="num_layers"):
        models.get_symbol("transformer-lm", num_layers=2, layers=[{}])


def test_mixed_model_trains_through_the_fused_step():
    """``simple_bind`` + ``make_train_step`` on the mixed model: the loss
    falls, and the step's span carries the expert layers' attributes."""
    from mxnet_tpu import telemetry

    sym = _mixed_symbol(scalar_loss=True)
    names = sym.list_arguments()
    inputs = {"data": (2, 8), "softmax_label": (2, 8)}
    exe = sym.simple_bind(
        mx.cpu(), grad_req={n: "null" if n in inputs else "write"
                            for n in names},
        type_dict=dict.fromkeys(inputs, "int32"), **inputs)
    shapes = dict(zip(names, sym.infer_shape(**inputs)[0]))
    rng = np.random.RandomState(0)
    params = {n: jnp.asarray(
        np.ones(s, np.float32) if n.endswith("gamma")
        else 0.1 * rng.randn(*s).astype(np.float32))
        for n, s in shapes.items() if n not in inputs}
    step = exe.make_train_step(lambda p, g, s: (
        {n: p[n] - 0.5 * g[n] for n in p}, s))
    ids = rng.randint(0, 50, (2, 9)).astype(np.int32)
    feed = {"data": ids[:, :-1], "softmax_label": ids[:, 1:]}
    losses = []
    states = {}
    telemetry.drain_events()
    for _ in range(8):
        outs, params, states = step(params, states, feed)
        losses.append(float(np.asarray(outs[0]).reshape(-1)[0]))
    assert losses[-1] < 0.7 * losses[0]
    spans = [args for ph, name, _d, _t, _dur, args, *_ in
             telemetry.drain_events(clear=False)
             if name == "executor.train_step"]
    assert len(spans) == 8
    for args in spans:
        assert args["moe_layers"] == 2 and args["moe_experts_held"] == 4
        assert args["moe_top_k"] == 2 and args["moe_buffer_rows"] == 32
        assert args["moe_expected_rows"] == 16 * 2 * 4 / 8


@pytest.mark.parametrize("takes", [True, False])
def test_relaid_keeps_a_leaf_that_does_not_take_its_layout(takes, monkeypatch):
    """The step's relayout hands back a copy only where the copy reports
    the layout asked for; a leaf that does not stays the caller's array in
    the layout it has, and the caller is told."""
    from jax.experimental.layout import Format, Layout
    from mxnet_tpu import executor

    tree = {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones(3)}
    own = jax.tree_util.tree_map(lambda a: a.format, tree)
    asked = dict(own)
    if not takes:  # a layout the copy will not report
        asked["w"] = Format(Layout(major_to_minor=(0, 1), tiling=((8, 128),)),
                            own["w"].sharding)
        monkeypatch.setattr(jax, "device_put", lambda a, fmt: a + 0)
    out, had, whole = executor._relaid(tree, asked)
    assert whole is takes
    assert (out["w"] is tree["w"]) is (not takes)
    assert had["w"] == (asked["w"] if takes else own["w"])
    assert had["b"] == own["b"] and not out["w"].is_deleted()
    np.testing.assert_array_equal(out["w"], np.arange(6.0).reshape(2, 3))


def test_decode_builders_refuse_the_new_kinds():
    """The decode builders build the dense LayerNorm block alone, and say
    so when handed a checkpoint of another kind."""
    from mxnet_tpu.serving.batcher import ServingError
    from mxnet_tpu.serving.generate.model import DecodeModel, DecodeSpec

    sym = _mixed_symbol()
    names = sym.list_arguments()
    shapes = dict(zip(names, sym.infer_shape(data=(1, 8),
                                             softmax_label=(1, 8))[0]))
    params = {n: np.zeros(s, np.float32) for n, s in shapes.items()}
    with pytest.raises(ServingError, match="models/transformer.py"):
        DecodeModel.from_arg_params(params, DecodeSpec(num_heads=4,
                                                       num_kv_heads=2))
