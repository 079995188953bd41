"""What LFM2-24B-A2B forced into the trainer (ISSUE 35): the ``ShortConv`` op
(a gated short convolution, its elementwise part two kernels on the TPU),
head-wise RMSNorm of q and k in ``MultiHeadAttention``, the flash kernels at
head size 64 (interpreted here), ``ExpertFFN``'s sigmoid-and-bias route with the bias as
float32 auxiliary state, and the block builder's mixer kind, dense gated
feed-forward, a width a layer, a model-wide ``eps`` and a tied head. Each op
and kind against the benchmark family's plain reference
(``benchmark/families/lfm2_moe_lm.py``) on seeded weights, forward and
gradients."""
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.ops import moe, shortconv
from mxnet_tpu.ops.registry import get_op

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
HP = jax.lax.Precision.HIGHEST


@pytest.fixture(scope="module")
def fam():
    """The benchmark's family file, loaded by path as ``run.py`` loads it."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "lfm2_family", os.path.join(BENCH, "families", "lfm2_moe_lm.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def toy():
    with open(os.path.join(BENCH, "tests", "data", "toy_lfm2.json")) as f:
        return json.load(f)


def _rand(rng, *shape, scale=1.0):
    return jnp.asarray(scale * rng.randn(*shape).astype(np.float32))


def _close(f, ref, args, tol=2e-5):
    out, vjp = jax.vjp(f, *args)
    want, vjp_ref = jax.vjp(ref, *args)
    w = _rand(np.random.RandomState(99), *out.shape)
    np.testing.assert_allclose(out, want, atol=tol, rtol=tol)
    for a, b in zip(vjp(w), vjp_ref(w)):
        np.testing.assert_allclose(a, b, atol=tol, rtol=tol)


# --- the gated short convolution ------------------------------------------------

@pytest.mark.parametrize("taps", [3, 1, 4])
def test_short_conv_op_against_the_reference(taps, fam):
    """Output and the gradients of the data and all three weights, float32,
    against the family's ``_conv_mixer`` one sequence at a time: the order
    B, C, X, the last tap on the token itself, zeros before each
    sequence's start (a batch of two: nothing leaks from one into the
    other)."""
    rng = np.random.RandomState(taps)
    b, t, d = 2, 12, 8
    args = (_rand(rng, b, t, d), _rand(rng, 3 * d, d, scale=0.4),
            _rand(rng, d, taps, scale=0.6), _rand(rng, d, d, scale=0.4))
    op = get_op("ShortConv")
    attrs = op.parse_attrs({"kernel": taps})

    def f(x, wi, k, wo):
        (out,), _ = op.impl(attrs, (x, wi, k, wo), (), None)
        return out

    def ref(x, wi, k, wo):
        lp = {"conv_in_weight": wi, "conv_weight": k, "conv_out_weight": wo}
        return jnp.stack([fam._conv_mixer(x[i], lp, False)
                          for i in range(b)])

    with jax.default_matmul_precision("highest"):
        _close(f, ref, args)


def test_gated_conv_is_the_plain_mathematics():
    """The op's formulation (pads with a negative edge, the gate stored in
    the projection's dtype) against the same mathematics written with
    rolls and masks, result and autodiff's gradients, in float32; and in
    bfloat16 the result is stored in bfloat16 and stays within its
    rounding of the float32 one."""
    rng = np.random.RandomState(4)
    u, k = _rand(rng, 2, 9, 3 * 16), _rand(rng, 16, 3)

    def plain(u, k):
        b, c, x = jnp.split(u, 3, axis=-1)
        a = b * x
        t = jnp.arange(a.shape[1])[None, :, None]
        conv = sum(k[:, j] * jnp.where(t >= 2 - j,
                                       jnp.roll(a, 2 - j, axis=1), 0.0)
                   for j in range(3))
        return c * conv

    _close(shortconv._gated_conv, plain, (u, k), tol=1e-5)
    low = shortconv._gated_conv(u.astype(jnp.bfloat16),
                                k.astype(jnp.bfloat16))
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(low.astype(np.float32), plain(u, k),
                               atol=0.08, rtol=0.03)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("batch,t,d,taps", [
    (2, 256, 256, 3), (1, 384, 128, 4), (2, 128, 640, 1), (1, 256, 128, 8)])
def test_short_conv_kernels_interpreted(batch, t, d, taps, dtype):
    """``short_conv_fwd`` and ``short_conv_bwd`` in interpret mode against
    the plain formulation of the same function and autodiff's backward: several
    tiles a sequence (the halo before and after a tile, masked at a
    sequence's two ends), two sequences, a width of several column chunks,
    one tap and as many as the halo holds."""
    from mxnet_tpu.ops.pallas import short_conv as kernels

    assert kernels.fits(t, d, taps)
    rng = np.random.RandomState(t + d + taps)
    u = _rand(rng, batch, t, 3 * d).astype(dtype)
    k = _rand(rng, d, taps, scale=0.6).astype(dtype)
    w = _rand(rng, batch, t, d).astype(dtype)
    got, vjp = jax.vjp(lambda u, k: kernels.gated_conv(u, k, True), u, k)
    want, vjp_ref = jax.vjp(shortconv._gated_conv, u, k)
    assert got.dtype == want.dtype == jnp.dtype(dtype)
    tol = 1e-5 if dtype == "float32" else 0.02
    for a, b in zip((got,) + vjp(w), (want,) + vjp_ref(w)):
        b = np.asarray(b, np.float32)
        np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                   atol=tol * max(1.0, np.abs(b).max()))


def test_short_conv_path_follows_what_the_code_can_observe(monkeypatch):
    """On the CPU the XLA formulation; on the TPU the kernels where the
    sequence is whole row tiles, the width whole lanes and the taps within
    the halo, and the op's result through them is the XLA path's."""
    from jax.experimental.pallas import tpu as pltpu
    from mxnet_tpu.ops import pallas

    assert shortconv.conv_path((2, 256, 128), 3) == "xla"      # the CPU
    monkeypatch.setattr(pallas, "on_tpu", lambda: True)
    assert shortconv.conv_path((2, 256, 128), 3) == "pallas"
    assert shortconv.conv_path((2, 8192, 2048), 3) == "pallas"  # the cell
    assert shortconv.conv_path((2, 200, 128), 3) == "xla"
    assert shortconv.conv_path((2, 256, 100), 3) == "xla"
    assert shortconv.conv_path((2, 256, 128), 9) == "xla"
    rng = np.random.RandomState(1)
    args = (_rand(rng, 1, 128, 128), _rand(rng, 384, 128, scale=0.2),
            _rand(rng, 128, 3, scale=0.6), _rand(rng, 128, 128, scale=0.2))
    op = get_op("ShortConv")

    def f(*a):
        (out,), _ = op.impl(op.parse_attrs({}), a, (), None)
        return out

    with jax.default_matmul_precision("highest"):
        with pltpu.force_tpu_interpret_mode():
            got, vjp = jax.vjp(f, *args)
            grads = vjp(jnp.ones_like(got))
        monkeypatch.setattr(pallas, "on_tpu", lambda: False)
        want, vjp = jax.vjp(f, *args)
    np.testing.assert_allclose(got, want, atol=2e-5)
    for a, b in zip(grads, vjp(jnp.ones_like(want))):
        np.testing.assert_allclose(a, b, atol=2e-4)


def test_short_conv_refuses_wrong_shapes():
    op = get_op("ShortConv")
    x = jnp.zeros((1, 4, 8))
    with pytest.raises(ValueError, match="in_weight"):
        op.impl(op.parse_attrs({}), (x, jnp.zeros((16, 8)), jnp.zeros((8, 3)),
                                     jnp.zeros((8, 8))), (), None)


# --- attention: head norms, and the kernels at head size 64 ----------------------

@pytest.mark.parametrize("hkv", [4, 2])
def test_head_norm_attention_against_the_reference(hkv, fam):
    """q, k, v, RMSNorm of every q and k head before RoPE at base 1e6,
    causal attention, o: the program's nodes (three FullyConnected,
    MultiHeadAttention with ``qk_norm``, one more) against the family's
    ``_attention_mixer``; output and every gradient, the two scales'
    among them."""
    h, dh, d, t = 4, 8, 16, 10
    cfg = {"num_attention_heads": h, "num_key_value_heads": hkv,
           "head_dim": dh, "hidden_size": d, "norm_eps": 1e-5,
           "rope_parameters": {"rope_theta": 1e6}}
    rng = np.random.RandomState(hkv)
    lp = {"q_weight": _rand(rng, h * dh, d, scale=0.3),
          "k_weight": _rand(rng, hkv * dh, d, scale=0.3),
          "v_weight": _rand(rng, hkv * dh, d, scale=0.3),
          "attn_q_norm_gamma": 1 + _rand(rng, dh, scale=0.2),
          "attn_k_norm_gamma": 1 + _rand(rng, dh, scale=0.2),
          "o_weight": _rand(rng, d, h * dh, scale=0.3)}
    y = _rand(rng, t, d)
    op = get_op("MultiHeadAttention")
    attrs = op.parse_attrs(dict(
        num_heads=h, num_kv_heads=hkv, causal=True, use_rope=True,
        rope_base=1e6, qk_norm=True, qk_norm_eps=1e-5, use_flash=False))
    assert op.get_arg_names(attrs)[3:] == ("q_norm_gamma", "k_norm_gamma")

    def f(y, *leaves):
        p = dict(zip(lp, leaves))
        q, k, v = (jnp.dot(y[None], p[n].T)
                   for n in ("q_weight", "k_weight", "v_weight"))
        (att,), _ = op.impl(attrs, (q, k, v, p["attn_q_norm_gamma"],
                                    p["attn_k_norm_gamma"]), (), None)
        return jnp.dot(att, p["o_weight"].T)[0]

    def ref(y, *leaves):
        return fam._attention_mixer(y, dict(zip(lp, leaves)), cfg, False)

    with jax.default_matmul_precision("highest"):
        _close(f, ref, (y,) + tuple(lp.values()), tol=3e-5)


def _plain_attention(q, k, v):
    b, h, t, d = q.shape
    k = jnp.repeat(k, h // k.shape[1], 1)
    v = jnp.repeat(v, h // v.shape[1], 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HP) / np.sqrt(d)
    keep = np.arange(t)[None, :] <= np.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=HP)


@pytest.mark.parametrize("t,regime", [(512, "fused"), (768, "fused"),
                                      (1024, "superblocked"),
                                      (1024, "split"), (1536, "split")])
def test_flash_kernels_at_head_size_64_interpreted(t, regime, monkeypatch):
    """Forward, the fused backward (whole, and in query superblocks of one
    tile), and the dq and dkv kernels at head size 64 (64-lane blocks: no
    padding) in interpret mode against the einsum path, 4 query heads a KV
    head as the benchmark cell has them: output and the gradients of q, k
    and v within the tolerance the tests hold a head of 128 to. The
    kernels' record says what head size, which backward and which query
    superblock were traced."""
    from mxnet_tpu.ops.pallas import flash_attention as fa
    from mxnet_tpu.ops.registry import built_layers

    monkeypatch.setattr(fa, "BLOCK_Q", 256)
    monkeypatch.setattr(fa, "BLOCK_K", 256)
    if regime == "split":
        monkeypatch.setattr(fa, "_RESIDENT_MAX", 256)
        monkeypatch.setattr(fa, "SUPER_TARGET", 512)
        monkeypatch.setattr(fa, "_SCOPED_VMEM", 0)
    if regime == "superblocked":
        monkeypatch.setattr(fa, "_SCOPED_VMEM",
                            fa._fused_bwd_vmem_bytes(t, t, 64, 4, 256))
    rng = np.random.RandomState(t)
    q, k, v = (_rand(rng, 1, n, t, 64) for n in (8, 2, 2))
    with built_layers() as built:
        _close(lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                                  interpret=True),
               _plain_attention, (q, k, v), tol=3e-5)
    assert {(r["backward"], r["q_super"]) for r in built.layers
            if "backward" in r} == {{"fused": ("fused", t),
                                     "superblocked": ("fused", 256),
                                     "split": ("split", None)}[regime]}
    assert {r["head_dim"] for r in built.layers if r.get("kernel")} == {64}


def test_flash_gate_takes_a_head_of_64_and_counts_its_vmem_at_128():
    """The compiled path's contract: whole lanes, or half a vreg's. A row
    of 64 takes a whole vreg row in VMEM, so the fused backward's byte
    count at a head of 64 is a head of 128's: 8192 tokens do not fit it
    whole (the chip's compiler asked 24.25 MiB where the count by 64 said
    15.5), and fit it at a query superblock of 2048 rows (15.25 MiB)."""
    from mxnet_tpu.ops.pallas import flash_attention as fa

    assert fa.kernel_qualifies(8192, 8192, 64, causal=True)
    assert fa.kernel_qualifies(8192, 8192, 128, causal=True)
    assert not fa.kernel_qualifies(8192, 8192, 96, causal=True)
    assert not fa.kernel_qualifies(8192, 8192, 32, causal=True)
    assert fa.kernel_qualifies(512, 512, 32, compiled=False)
    assert fa._fused_bwd_vmem_bytes(8192, 8192, 64, 2) \
        == fa._fused_bwd_vmem_bytes(8192, 8192, 128, 2) > fa._SCOPED_VMEM
    assert fa._fused_bwd_vmem_bytes(4096, 4096, 64, 2) <= fa._SCOPED_VMEM
    assert fa._fused_q_super(8192, 8192, 64, 2) == 2048
    assert fa._fused_bwd_vmem_bytes(8192, 8192, 64, 2, 2048) \
        == fa._fused_bwd_vmem_bytes(8192, 8192, 128, 2, 2048) \
        <= fa._SCOPED_VMEM


# --- the expert layer's sigmoid-and-bias route -----------------------------------

def _expert_args(e, held, d, f, tokens, seed, first=0):
    rng = np.random.RandomState(seed)
    return (_rand(rng, 1, tokens, d), _rand(rng, e, d, scale=0.5),
            _rand(rng, held, f, d, scale=0.3), _rand(rng, held, f, d,
                                                     scale=0.3),
            _rand(rng, held, d, f, scale=0.3), _rand(rng, e, scale=0.3))


def _bias_op(e, held, first, top_k):
    op = get_op("ExpertFFN")
    attrs = op.parse_attrs(dict(
        num_experts=e, experts_held=held, first_expert=first, top_k=top_k,
        act_type="silu", route="sigmoid_bias", norm_eps=1e-6))
    assert op.get_aux_names(attrs) == ("expert_bias",)

    def f(x, wr, wg, wu, wd, bias):
        (y, counts), aux_up = op.impl(attrs, (x, x, wr, wg, wu, wd), (bias,),
                                      None)
        assert aux_up == ()        # state: the op never writes it
        return y, counts

    return f


_CFG = {"num_experts_per_tok": 4, "norm_topk_prob": True,
        "routed_scaling_factor": 1}


@pytest.mark.parametrize("e,held,first,top_k", [
    (64, 8, 0, 4), (64, 8, 24, 4), (8, 8, 0, 2), (16, 4, 12, 3)])
def test_sigmoid_bias_expert_layer_against_the_reference(e, held, first,
                                                         top_k, fam):
    """Output and the gradients of the input, the router and the three
    expert matrices, float32, against the family's masked dense sum over
    the held experts; the bias gets no gradient."""
    cfg = dict(_CFG, num_experts_per_tok=top_k)
    x, wr, wg, wu, wd, bias = _expert_args(e, held, 16, 24, 40, e + first)

    def f(x, wr, wg, wu, wd):
        return _bias_op(e, held, first, top_k)(x, wr, wg, wu, wd, bias)[0]

    def ref(x, wr, wg, wu, wd):
        lp = {"router_weight": wr, "gate_weight": wg, "up_weight": wu,
              "down_weight": wd}
        return fam._experts(x[0], lp, bias, cfg, False, first)[None]

    with jax.default_matmul_precision("highest"):
        _close(f, ref, (x, wr, wg, wu, wd))
        g = jax.grad(lambda b: jnp.sum(_bias_op(e, held, first, top_k)(
            x, wr, wg, wu, wd, b)[0] ** 2))(bias)
    assert not np.asarray(g).any()


def test_the_eight_shares_add_up_to_the_uncut_layer(fam):
    """The eight shares ``first_expert`` 0, 8, .., 56 of a layer under the
    sigmoid-and-bias route, each with its own experts, the whole router
    and the whole bias, sum to what the reference gives a holder of all
    64: the router is counted once a share and nothing else is shared."""
    e, top_k, tokens = 64, 4, 32
    x, wr, wg, wu, wd, bias = _expert_args(e, e, 16, 24, tokens, seed=9)
    with jax.default_matmul_precision("highest"):
        whole, counts = _bias_op(e, e, 0, top_k)(x, wr, wg, wu, wd, bias)
        parts = [_bias_op(e, 8, first, top_k)(
            x, wr, wg[first:first + 8], wu[first:first + 8],
            wd[first:first + 8], bias) for first in range(0, e, 8)]
        lp = {"router_weight": wr, "gate_weight": wg, "up_weight": wu,
              "down_weight": wd}
        uncut = fam._experts(x[0], lp, bias, _CFG, False)[None]
    np.testing.assert_allclose(sum(y for y, _ in parts), whole, atol=1e-5)
    np.testing.assert_allclose(whole, uncut, atol=1e-5)
    np.testing.assert_array_equal(jnp.concatenate([c for _, c in parts]),
                                  counts)
    assert float(counts.sum()) == tokens * top_k


def test_the_bias_moves_the_choice_and_no_weight():
    """A bias large on experts the scores would not choose: they are
    chosen, and each chosen expert's weight is still its own sigmoid score
    over the chosen scores' sum + 1e-6, with no trace of the bias; without
    ``norm_topk`` the bare score times ``scale``."""
    rng = np.random.RandomState(2)
    x, wr = _rand(rng, 30, 16), _rand(rng, 8, 16, scale=0.5)
    scores = np.asarray(jax.nn.sigmoid(jnp.einsum(
        "nd,ed->ne", x, wr, precision=HP)))
    plain_w, plain_idx = moe.route(x, wr, 2, True, jnp.zeros(8), 1e-6)
    np.testing.assert_array_equal(np.sort(plain_idx, -1),
                                  np.sort(np.argsort(-scores, -1)[:, :2], -1))
    bias = jnp.asarray([0, 0, 0, 0, 0, 0, 5.0, 7.0])   # force experts 6, 7
    w, idx = moe.route(x, wr, 2, True, bias, 1e-6)
    assert (np.sort(idx, -1) == [6, 7]).all()
    assert (np.sort(plain_idx, -1) != [6, 7]).any()
    chosen = np.take_along_axis(scores, np.asarray(idx), 1)
    np.testing.assert_allclose(
        w, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    w, idx = moe.route(x, wr, 2, False, bias, 1e-6, 2.5)
    np.testing.assert_allclose(
        w, 2.5 * np.take_along_axis(scores, np.asarray(idx), 1), rtol=1e-6)
    # the softmax route is what it was
    w, idx = moe.route(x, wr, 2, True)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    with pytest.raises(ValueError, match="route"):
        op = get_op("ExpertFFN")
        op.impl(op.parse_attrs(dict(num_experts=8, route="tanh")),
                (x[None], x[None], wr) + (jnp.zeros((8, 4, 16)),) * 2
                + (jnp.zeros((8, 16, 4)),), (), None)


# --- the block builder -----------------------------------------------------------

def _toy_symbol(fam, toy, **kw):
    return fam.symbol(toy, kw.get("for_training", True))


def test_block_kinds_build_the_hybrid_model(fam, toy):
    """Kinds, names and shapes of the toy LFM2: a conv + dense-SwiGLU
    layer, then attention and conv layers with experts; a width a layer;
    one table for both ends; every norm at the model's eps; the bias an
    auxiliary state and no argument."""
    sym = fam.symbol(toy, True)
    args = sym.list_arguments()
    shapes = dict(zip(args, sym.infer_shape(data=(2, 8),
                                            softmax_label=(2, 8))[0]))
    assert {n: s for n, s in shapes.items()
            if n not in ("data", "softmax_label")} == fam.param_shapes(toy)
    assert "pred_weight" not in args and "pred_bias" not in args
    assert shapes["layer0_ffn1_weight"] == (160, 64)       # the dense width
    assert shapes["layer1_gate_weight"] == (4, 48, 64)     # an expert's
    assert shapes["layer0_conv_weight"] == (64, 3)
    assert shapes["layer1_attn_q_norm_gamma"] == (16,)
    assert sym.list_auxiliary_states() == [
        "layer%d_experts_expert_bias" % i for i in (1, 2, 3, 4)]
    assert sym.infer_shape(data=(2, 8), softmax_label=(2, 8))[2] \
        == [(8,)] * 4
    ops = {n.name: n for n in sym._nodes() if not n.is_var}
    assert ops["layer0_conv"].op.name == "ShortConv"
    assert "layer0_attn" not in ops and "layer1_conv" not in ops
    assert ops["layer1_attn"].attrs["qk_norm"]
    assert ops["layer1_attn"].attrs["qk_norm_eps"] == 1e-5
    assert ops["layer1_attn"].attrs["rope_base"] == 1e6
    assert ops["layer1_experts"].attrs["route"] == "sigmoid_bias"
    for name in ("layer0_ln1", "layer3_ln2", "lnf"):
        assert ops[name].attrs["eps"] == 1e-5
    # the experts' router reads what the experts read
    data, router_data = ops["layer2_experts"].inputs[:2]
    assert data[0] is router_data[0] and data[0].name == "layer2_ln2"
    # the head multiplies by the table
    assert ops["pred"].inputs[1][0] is ops["embed"].inputs[1][0]
    with pytest.raises(ValueError, match="mixer"):
        models.get_symbol("transformer-lm", num_layers=1,
                          layers=[{"mixer": "lstm"}])
    with pytest.raises(ValueError, match="ffn"):
        models.get_symbol("transformer-lm", num_layers=1,
                          layers=[{"ffn": "relu"}])
    with pytest.raises(ValueError, match="tie_head"):
        models.get_symbol("transformer-lm", num_layers=1, tie_head=True)


def test_smallthinkers_router_still_reads_the_mixers_input():
    """``router_input`` left out: the router reads the attention's normed
    input, as before the key was there."""
    sym = models.get_symbol(
        "transformer-lm", num_classes=50, num_layers=1, num_heads=4,
        head_dim=8, model_dim=16, ffn_dim=12, num_kv_heads=2,
        layers=[{"norm": "rms", "ffn": "experts"}],
        experts={"num_experts": 8, "experts_held": 4, "top_k": 2},
        final_norm="rms", head_bias=False)
    node = {n.name: n for n in sym._nodes() if not n.is_var}["layer0_experts"]
    assert node.inputs[0][0].name == "layer0_ln2"
    assert node.inputs[1][0].name == "layer0_ln1"
    assert node.attrs["route"] == "softmax"
    assert sym.list_auxiliary_states() == []
    for n in sym._nodes():
        if not n.is_var and n.op.name == "RMSNorm":
            assert n.attrs["eps"] == n.op.param_spec["eps"] == 1e-6


def _bound(sym, compute_dtype=None, batch=(2, 16)):
    inputs = {"data": batch, "softmax_label": batch}
    names = sym.list_arguments()
    return sym.simple_bind(
        mx.cpu(), grad_req={n: "null" if n in inputs else "write"
                            for n in names},
        type_dict=dict.fromkeys(inputs, "int32"),
        compute_dtype=compute_dtype, **inputs)


def test_model_gradients_are_the_references(fam, toy):
    """The whole toy model in float32 through ``simple_bind``, forward and
    backward: the loss and every leaf's gradient against the family's
    ``ref_seq_loss`` (tied table: the sum of both uses), the seeded bias in
    the executor's auxiliary states."""
    seed, batch = 21, (2, 16)
    exe = _bound(fam.symbol(toy, True), batch=batch)
    params = fam.init_params(toy, seed)     # seeds the executor's bias too
    state = fam.init_state(toy, seed)
    for n, b in state.items():
        np.testing.assert_array_equal(exe.aux_dict[n].asnumpy(), b)
        assert np.abs(np.asarray(b)).max() > 0
    traffic = {"batch": batch[0], "seq_len": batch[1]}
    (data, label), = fam.make_batches(toy, traffic, seed, 1)
    for n, a in params.items():
        exe.arg_dict[n]._data = a
    exe.arg_dict["data"]._data = data["data"]
    exe.arg_dict["softmax_label"]._data = label["softmax_label"]
    with jax.default_matmul_precision("highest"):
        loss = exe.forward(is_train=True)[0].asnumpy()
        exe.backward()
        want, grads = jax.value_and_grad(lambda p: sum(
            fam.ref_seq_loss(p, state, data["data"][i],
                             label["softmax_label"][i], toy)
            for i in range(batch[0])) / (batch[0] * batch[1]))(params)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for n, g in grads.items():
        np.testing.assert_allclose(exe.grad_dict[n].asnumpy(), g, atol=2e-6,
                                   rtol=2e-4, err_msg=n)


def test_fused_step_keeps_the_bias_float32_and_untouched(fam, toy):
    """``make_train_step`` under ``compute_dtype="bfloat16"``: the loss
    falls, ``expert_bias`` reaches the router in float32 (a bias that
    bfloat16 cannot hold still decides), is the same bits after the steps,
    and the step's span and the program's record say what was traced."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.executor import master_leaves

    sym = fam.symbol(toy, True)
    # the table is tied to the head: the states alone stay float32
    assert master_leaves(sym) == set(sym.list_auxiliary_states())
    exe = _bound(sym, compute_dtype="bfloat16")
    params = fam.init_params(toy, 3)
    bias = {n: a.asnumpy().copy() for n, a in exe.aux_dict.items()}
    seen = []
    real = moe.route

    def spy(router_data, router_weight, top_k, norm_topk, bias=None, *rest):
        seen.append(bias.dtype)
        return real(router_data, router_weight, top_k, norm_topk, bias,
                    *rest)

    moe.route = spy
    telemetry.reset()
    try:
        step = exe.make_train_step(lambda p, g, s: (
            {n: p[n] - 0.3 * g[n] for n in p}, s))
        (data, label), = fam.make_batches(
            toy, {"batch": 2, "seq_len": 16}, 3, 1)
        telemetry.drain_events()
        losses, states = [], {}
        for _ in range(6):
            outs, params, states = step(params, states, {**data, **label})
            losses.append(float(np.asarray(outs[0]).reshape(-1)[0]))
    finally:
        moe.route = real
    assert losses[-1] < losses[0]
    assert seen and all(d == jnp.float32 for d in seen)
    for n, b in bias.items():
        np.testing.assert_array_equal(exe.aux_dict[n].asnumpy(), b)
    (rec,) = telemetry.programs()
    by_op = {}
    for layer in rec["layers"]:
        by_op.setdefault(layer["op"], []).append(layer)
    assert [c["path"] for c in by_op["ShortConv"]] == ["xla"] * 4
    assert [a["head_dim"] for a in by_op["MultiHeadAttention"]] == [16]
    assert {e["route"] for e in by_op["ExpertFFN"]} == {"sigmoid_bias"}
    assert all(layer["node"] in rec["nodes"] for layer in rec["layers"])
    spans = [args for ph, name, _d, _t, _dur, args, *_ in
             telemetry.drain_events(clear=False)
             if name == "executor.train_step"]
    assert len(spans) == 6
    for args in spans:
        assert args["moe_layers"] == 4 and args["moe_experts_held"] == 4
        assert args["moe_buffer_rows"] == 32 * 2
        assert args["moe_expected_rows"] == 32 * 2 * 4 / 8


def test_float32_state_is_what_the_op_declares(fam, toy, monkeypatch):
    """The executor knows no op by name: the states a compute dtype leaves
    alone are those ``OpDef.master_inputs`` lists, by the names the graph
    gives them; BatchNorm's moving statistics, which no op lists, are
    cast as before."""
    from mxnet_tpu.executor import master_leaves

    op = get_op("ExpertFFN")
    assert op.master_inputs == ("expert_bias",)
    assert get_op("BatchNorm").master_inputs == ()
    sym = fam.symbol(toy, True)
    assert master_leaves(sym) == set(fam.state_shapes(toy))
    monkeypatch.setattr(op, "master_inputs", ())
    assert master_leaves(sym) == frozenset()
    bn = mx.sym.BatchNorm(mx.sym.Variable("data"), name="bn")
    assert bn.list_auxiliary_states() and master_leaves(bn) == frozenset()


def test_init_params_raises_where_the_bias_cannot_be_seeded(fam, toy):
    """The family seeds ``expert_bias`` into the executor last bound from
    its symbol, through ``copy_params_from``: with that executor gone, or
    with states under other names than the family's, it raises and does
    not leave a zero bias behind silently."""
    import gc

    exe = _bound(fam.symbol(toy, True))
    fam.init_params(toy, 5)
    for n, b in fam.init_state(toy, 5).items():
        np.testing.assert_array_equal(exe.aux_dict[n].asnumpy(), b)
    other = dict(toy, use_expert_bias=False)    # a symbol without the states
    assert fam.state_shapes(other) == {}
    with pytest.raises(RuntimeError, match="auxiliary states"):
        fam.init_params(other, 5)
    del exe
    gc.collect()
    with pytest.raises(RuntimeError, match="no live executor"):
        fam.init_params(toy, 5)


def test_dense_steps_span_names_the_head_size_and_no_more():
    """Today's dense model: the program's record names the head size, and
    the span gains none of the keys of layers it does not have."""
    from mxnet_tpu import telemetry

    sym = models.get_symbol("transformer-lm", num_classes=50, num_layers=1,
                            num_heads=4, model_dim=16, ffn_dim=32,
                            scalar_loss=True)
    exe = _bound(sym, batch=(2, 8))
    shapes = dict(zip(sym.list_arguments(), sym.infer_shape(
        data=(2, 8), softmax_label=(2, 8))[0]))
    rng = np.random.RandomState(0)
    params = {n: _rand(rng, *s, scale=0.1) for n, s in shapes.items()
              if n not in ("data", "softmax_label")}
    step = exe.make_train_step(lambda p, g, s: (p, s))
    ids = rng.randint(0, 50, (2, 9)).astype(np.int32)
    telemetry.reset()
    for _ in range(2):
        _, params, _ = step(params, {}, {"data": ids[:, :-1],
                                         "softmax_label": ids[:, 1:]})
    spans = [args for ph, name, _d, _t, _dur, args, *_ in
             telemetry.drain_events(clear=False)
             if name == "executor.train_step"]
    (rec,) = telemetry.programs()
    assert [(r["op"], r.get("head_dim"), r["node"]) for r in rec["layers"]] == [
        ("MultiHeadAttention", 4, "layer0_attn"), ("Embedding", None, "embed")]
    assert len(spans) == 2 and not {
        "conv_layers", "moe_layers", "moe_route", "attn_head_dim",
        "uncast_table_bytes", "loop_steps", "loop_layers",
        "loop_exits", "mtp_depth", "mtp_weight"} & set(spans[-1])
