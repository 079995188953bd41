"""What Qwen3-Next forced into the trainer: the gated delta rule
(``GatedDeltaRule``: its chunked plain path and the Pallas kernels,
interpreted here), the block builder's ``gdn`` mixer, a gate on attention's
output (``MultiHeadAttention``'s ``output_gate``) and a sigmoid-gated shared
expert. Each against the benchmark family's plain reference
(``benchmark/families/qwen3_next_lm.py``: the rule token by token) or a
formula written out here, on seeded weights, forward and gradients; and the
five accepted cells' toy models, whose graphs these kinds leave as they
were."""
import hashlib
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
from mxnet_tpu.ops import delta_rule
from mxnet_tpu.ops.registry import get_op

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


def _family(name):
    """A benchmark family file, loaded by path as ``run.py`` loads it."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "family_" + name, os.path.join(BENCH, "families", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _toy(name):
    with open(os.path.join(BENCH, "tests", "data", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def fam():
    return _family("qwen3_next_lm")


@pytest.fixture(scope="module")
def toy():
    return _toy("toy_qwen3next")


def _rand(rng, *shape, scale=1.0):
    return jnp.asarray(scale * rng.randn(*shape).astype(np.float32))


def _close(f, ref, args, tol=2e-5, scaled=False):
    """Output and the gradient of every argument, against ``ref``'s; with
    ``scaled``, each to ``tol`` of its own largest magnitude (for sums of
    many float32 products)."""
    out, vjp = jax.vjp(f, *args)
    want, vjp_ref = jax.vjp(ref, *args)
    w = _rand(np.random.RandomState(99), *out.shape)

    def check(a, b, msg):
        atol = tol * float(jnp.abs(b).max()) if scaled else tol
        np.testing.assert_allclose(a, b, atol=atol, rtol=tol, err_msg=msg)

    check(out, want, "output")
    for i, (a, b) in enumerate(zip(vjp(w), vjp_ref(w))):
        check(a, b, "argument %d" % i)


# --- the rule ----------------------------------------------------------------

_G_SCALE = {"g_near_0": 0.01, "g_very_negative": 40.0}
# g very negative: a chunk's cumulative sums reach -1300 and more, where a
# float32 holds 1e-4 absolute, so each decay e^(gam_i - gam_j) is exact to
# 1e-4 relative, whatever computes it from the sums
_TOL = {"g_near_0": 2e-5, "g_very_negative": 2e-4}


def _rule_inputs(regime, b=2, t=150, hk=2, hv=4, d=16, seed=0):
    """q, k unit a head (q over sqrt(d)), v, g <= 0 at the regime's scale,
    beta in (0, 1); (B, T, heads x d) and (B, T, hv)."""
    rng = np.random.RandomState(seed)

    def unit(x, h):
        x = x.reshape(b, t, h, d)
        return (x / np.linalg.norm(x, axis=-1, keepdims=True)).reshape(
            b, t, h * d)

    q = unit(rng.randn(b, t, hk * d), hk) / np.sqrt(d)
    k = unit(rng.randn(b, t, hk * d), hk)
    v = rng.randn(b, t, hv * d)
    g = -_G_SCALE[regime] * rng.rand(b, t, hv)
    beta = rng.rand(b, t, hv)
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


def _token_by_token(fam, hk, hv):
    """The family's reference recurrence, a sequence at a time, on the op's
    (B, T, heads x d) layout."""
    def rule(q, k, v, g, beta):
        b, t, _ = q.shape
        grp = hv // hk

        def one(q, k, v, g, beta):
            return fam.delta_rule(
                jnp.repeat(q.reshape(t, hk, -1), grp, 1),
                jnp.repeat(k.reshape(t, hk, -1), grp, 1),
                v.reshape(t, hv, -1), g, beta).reshape(t, -1)

        return jax.vmap(one)(q, k, v, g, beta)

    return rule


@pytest.mark.parametrize("regime", sorted(_G_SCALE))
def test_chunked_rule_is_the_token_by_token_recurrence(fam, regime):
    """The plain path (chunks of 64 in the WY form, a scan over chunks) at
    150 tokens, two chunks and a padded third, against the recurrence token
    by token: the output and the gradient of q, k, v, g and beta, with g
    near 0 (the state kept) and very negative (e^g underflows to 0 within a
    chunk: decays from differences of cumulative sums, nothing overflows)."""
    args = _rule_inputs(regime)
    with jax.default_matmul_precision("highest"):
        _close(lambda *a: delta_rule.gated_delta_rule(*a, 2, 4, "plain"),
               _token_by_token(fam, 2, 4), args, tol=_TOL[regime])


@pytest.mark.parametrize("heads", [1, 4])
@pytest.mark.parametrize("regime", sorted(_G_SCALE))
def test_pallas_kernels_are_the_plain_path(regime, heads, monkeypatch):
    """The kernels (the forward, which keeps the states, and the reverse
    walk of the backward) in interpret mode against the plain path at
    three chunks of two sequences, sixteen value heads over eight key
    heads, walked one value head a grid cell or four (two whole key heads)
    side by side, so that a sequence has four cells and a block index
    counted in the wrong unit lands on another head's block (interpret
    mode clamps one past the end back onto the right one): the output and
    the five gradients, the value heads' dq and dk summed onto
    their key head (by the backward kernel itself where a cell holds whole
    key heads)."""
    from mxnet_tpu.ops.pallas import gated_delta

    monkeypatch.setattr(gated_delta, "HEADS", heads)
    assert gated_delta._heads(16, 2) == heads
    args = _rule_inputs(regime, b=2, t=192, hk=8, hv=16, seed=1)

    def kernel(q, k, v, g, beta):
        b, t, h = g.shape
        gam = jnp.cumsum(g.reshape(b, t // 64, 64, h), 2).reshape(b, t, h)
        return gated_delta.gated_delta(q, k, v, gam, beta, 8, 16, True)

    with jax.default_matmul_precision("highest"):
        _close(kernel, lambda *a: delta_rule.gated_delta_rule(
            *a, 8, 16, "plain"), args, tol=2e-5)


def _kernel_inputs(hk=8, hv=16, t=192):
    """The rule's inputs as the kernels take them: two sequences of three
    chunks, g's cumulative sums within each chunk."""
    q, k, v, g, beta = _rule_inputs("g_near_0", b=2, t=t, hk=hk, hv=hv,
                                    seed=1)
    b, _, h = g.shape
    gam = jnp.cumsum(g.reshape(b, t // 64, 64, h), 2).reshape(b, t, h)
    return q, k, v, gam, beta


@pytest.mark.parametrize("heads", [1, 4])
def test_training_forward_keeps_the_state_before_every_chunk(heads,
                                                             monkeypatch):
    """The forward a gradient runs (the ``custom_vjp``'s forward rule)
    writes the o of the call without a gradient and, bit for bit, the state
    before every chunk that ``chunk_forward`` gives chunk by chunk: two
    sequences, sixteen value heads over eight key heads, one value head a
    grid cell or four."""
    from mxnet_tpu.ops.pallas import gated_delta

    monkeypatch.setattr(gated_delta, "HEADS", heads)
    assert gated_delta._heads(16, 2) == heads
    q, k, v, gam, beta = _kernel_inputs()
    o, res = gated_delta._gated_delta_fwd(q, k, v, gam, beta, 8, 16, True)
    st = res[-1]
    assert st.shape == (2 * 16, 3, 16, 16) and st.dtype == jnp.float32
    np.testing.assert_array_equal(
        o, gated_delta.gated_delta(q, k, v, gam, beta, 8, 16, True))
    chunk = jax.jit(delta_rule.chunk_forward)
    for b in range(2):
        for h in range(16):
            s = jnp.zeros((16, 16), jnp.float32)
            kc, vc = slice(h // 2 * 16, (h // 2 + 1) * 16), slice(
                h * 16, (h + 1) * 16)
            for c in range(3):
                np.testing.assert_array_equal(st[b * 16 + h, c], s)
                t = slice(c * 64, (c + 1) * 64)
                _, s = chunk(q[b, t, kc], k[b, t, kc], v[b, t, vc],
                             gam[b, None, t, h], beta[b, None, t, h], s)


def _traced_kernels(monkeypatch):
    """Each ``pallas_call`` traced from here on, as (name, outputs)."""
    from mxnet_tpu.ops.pallas import gated_delta

    calls, real = [], gated_delta.pl.pallas_call

    def traced(*args, **kw):
        calls.append((kw["name"], len(kw["out_shape"])))
        return real(*args, **kw)

    monkeypatch.setattr(gated_delta.pl, "pallas_call", traced)
    return calls


def test_a_gradient_runs_the_forward_and_the_reverse_walk_alone(
        monkeypatch):
    """Under ``jax.grad`` the rule runs two kernels: the forward, with o and
    the states among its two outputs, and the reverse walk; no second
    forward walk for the states."""
    from mxnet_tpu.ops.pallas import gated_delta

    calls = _traced_kernels(monkeypatch)
    args = _kernel_inputs()
    jax.grad(lambda *a: gated_delta.gated_delta(*a, 8, 16, True).sum(),
             argnums=tuple(range(5)))(*args)
    assert calls == [("gated_delta_fwd", 2), ("gated_delta_bwd", 5)]


def test_without_a_gradient_the_forward_writes_no_states(monkeypatch):
    """Inference and evaluation call the rule without a gradient: one
    kernel, whose one output is o."""
    from mxnet_tpu.ops.pallas import gated_delta

    calls = _traced_kernels(monkeypatch)
    args = _kernel_inputs()
    o = jax.jit(lambda *a: gated_delta.gated_delta(*a, 8, 16, True))(*args)
    assert o.shape == args[2].shape
    assert calls == [("gated_delta_fwd", 1)]


@pytest.mark.parametrize("path,states", [("kernel", "forward"),
                                         ("plain", "autodiff")])
def test_record_says_where_the_backward_reads_its_states(path, states,
                                                         monkeypatch):
    """``GatedDeltaRule``'s record says ``backward_states``: ``forward``
    where the kernels run (their forward keeps the states), ``autodiff``
    on the plain path; both paths give the op's output and gradients
    alike (the kernels interpreted)."""
    import functools

    from mxnet_tpu.ops.pallas import gated_delta
    from mxnet_tpu.ops.registry import built_layers

    hk, hv, d = 2, 4, 16
    op = get_op("GatedDeltaRule")
    attrs = op.parse_attrs({"num_key_heads": hk, "num_value_heads": hv,
                            "key_dim": d, "value_dim": d})
    rng = np.random.RandomState(5)
    width = 2 * hk * d + hv * d
    args = (_rand(rng, 1, 128, width), _rand(rng, 1, 128, 2 * hv),
            _rand(rng, width, 4, scale=0.5), _rand(rng, hv, scale=0.5),
            _rand(rng, hv, scale=0.5))

    def rule(*a):
        return op.impl(attrs, a, (), None)[0][0]

    with jax.default_matmul_precision("highest"):
        want, vjp_want = jax.vjp(rule, *args)
        monkeypatch.setattr(delta_rule, "delta_path", lambda *a: path)
        monkeypatch.setattr(gated_delta, "gated_delta", functools.partial(
            gated_delta.gated_delta, interpret=True))
        with built_layers() as built:
            out, vjp = jax.vjp(rule, *args)
        w = _rand(rng, *out.shape)
        grads, grads_want = vjp(w), vjp_want(w)
    assert [(r["op"], r["path"], r["backward_states"])
            for r in built.layers] == [("GatedDeltaRule", path, states)]
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for a, b in zip(grads, grads_want):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_the_kernels_take_whole_chunks_of_whole_lanes():
    """``fits``: whole chunks of 64 tokens and heads of whole lanes; off the
    TPU every shape takes the plain path."""
    from mxnet_tpu.ops.pallas import gated_delta

    assert gated_delta.fits(8192, 128, 128)
    assert not gated_delta.fits(8192 + 32, 128, 128)
    assert not gated_delta.fits(8192, 64, 128)
    assert delta_rule.delta_path(8192, 128, 128) == "plain"


def test_op_convolves_norms_and_gates_as_the_reference(fam, toy):
    """``GatedDeltaRule`` whole (the convolution and silu, q and k to unit
    length, beta and g from b, a, A_log and dt_bias, the rule) between the
    family's projections and its gated norm: the mixer against the family's
    ``gdn``, output and every gradient."""
    hk, hv, dk, dv = (toy["linear_num_key_heads"],
                      toy["linear_num_value_heads"],
                      toy["linear_key_head_dim"],
                      toy["linear_value_head_dim"])
    rng = np.random.RandomState(4)
    shapes = fam._mixer_shapes(toy, "", "gdn")
    lp = {n: (jnp.log(jnp.asarray(rng.uniform(0.05, 4, s), jnp.float32))
              if n.endswith("_A_log") else 1 + _rand(rng, *s, scale=0.2)
              if n.endswith(("_gamma", "_dt_bias"))
              else _rand(rng, *s, scale=0.1)) for n, s in shapes.items()}
    y = _rand(rng, 1, 70, toy["hidden_size"])
    names = list(lp)
    op = get_op("GatedDeltaRule")
    attrs = op.parse_attrs({"num_key_heads": hk, "num_value_heads": hv,
                            "key_dim": dk, "value_dim": dv})

    def program(y, *leaves):
        p = dict(zip(names, leaves))
        (o,), _ = op.impl(attrs, (y @ p["gdn_qkv_weight"].T,
                                  y @ p["gdn_ba_weight"].T,
                                  p["gdn_core_conv_weight"],
                                  p["gdn_core_A_log"],
                                  p["gdn_core_dt_bias"]), (), None)
        o = o.reshape(1, 70, hv, dv)
        o = (o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                               + toy["rms_norm_eps"])
             * p["gdn_norm_gamma"]).reshape(1, 70, -1)
        return (o * jax.nn.silu(y @ p["gdn_z_weight"].T)
                ) @ p["gdn_out_weight"].T

    def ref(y, *leaves):
        return fam.gdn(y[0], dict(zip(names, leaves)), toy)[None]

    with jax.default_matmul_precision("highest"):
        _close(program, ref, (y,) + tuple(lp.values()), scaled=True)


# --- the gated attention -----------------------------------------------------

def test_gated_attention_is_its_formula():
    """``MultiHeadAttention`` with ``output_gate`` beside q/k norms, a
    rotary part of 4 of 16 dims and two query heads a KV head, against the
    formula written out head by head in numpy: each head's output times
    sigmoid of its gate, element by element; the record says gated."""
    from mxnet_tpu.ops.registry import built_layers

    b, t, h, hkv, d, r, base = 1, 9, 4, 2, 16, 4, 1e7
    rng = np.random.RandomState(7)
    q, gate = (np.asarray(_rand(rng, b, t, h * d)) for _ in range(2))
    k, v = (np.asarray(_rand(rng, b, t, hkv * d)) for _ in range(2))
    gq, gk = (1 + 0.3 * rng.randn(d).astype(np.float32) for _ in range(2))
    op = get_op("MultiHeadAttention")
    attrs = op.parse_attrs(dict(num_heads=h, num_kv_heads=hkv, causal=True,
                                use_rope=True, rope_base=base, rope_dims=r,
                                qk_norm=True, output_gate=True,
                                use_flash=False))
    assert op.get_arg_names(attrs)[-1] == "gate"
    with built_layers() as built:
        (out,), _ = op.impl(attrs, tuple(jnp.asarray(x) for x in (
            q, k, v, gq, gk, gate)), (), None)
    assert [rec["output_gate"] for rec in built.layers] == [True]

    def norm(x, g):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-6) * g

    def rotate(x):
        half = r // 2
        ang = np.arange(t)[:, None] * base ** (-np.arange(half) / half)
        x1, x2 = x[:, :half], x[:, half:]
        return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                               x2 * np.cos(ang) + x1 * np.sin(ang)], -1)

    want = np.zeros((t, h * d))
    for i in range(h):
        j = i // (h // hkv)
        qh = norm(q[0, :, i * d:(i + 1) * d], gq)
        kh = norm(k[0, :, j * d:(j + 1) * d], gk)
        qh = np.concatenate([qh[:, :d - r], rotate(qh[:, d - r:])], -1)
        kh = np.concatenate([kh[:, :d - r], rotate(kh[:, d - r:])], -1)
        s = np.where(np.tril(np.ones((t, t), bool)), qh @ kh.T / np.sqrt(d),
                     -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        o = p / p.sum(-1, keepdims=True) @ v[0, :, j * d:(j + 1) * d]
        want[:, i * d:(i + 1) * d] = o / (1 + np.exp(
            -gate[0, :, i * d:(i + 1) * d]))
    np.testing.assert_allclose(np.asarray(out)[0], want, atol=2e-5)


# --- the expert layer with a gated shared expert -----------------------------

def test_the_shares_and_the_gated_shared_expert_add_up_to_the_uncut_layer(
        fam):
    """The four shares ``first_expert`` 0, 4, 8, 12 of a layer of 16 experts
    (softmax top 4, renormalised), each with its own experts and the whole
    router, plus the sigmoid-gated shared expert counted ONCE, sum to what
    the reference gives a holder of all 16: the router and the gated shared
    expert are what every chip computes alike."""
    e, tokens, d, f = 16, 32, 16, 24
    rng = np.random.RandomState(9)
    lp = {"router_weight": _rand(rng, e, d, scale=0.5),
          "gate_weight": _rand(rng, e, f, d, scale=0.3),
          "up_weight": _rand(rng, e, f, d, scale=0.3),
          "down_weight": _rand(rng, e, d, f, scale=0.3),
          "shared_ffn1_weight": _rand(rng, f, d, scale=0.3),
          "shared_ffn3_weight": _rand(rng, f, d, scale=0.3),
          "shared_ffn2_weight": _rand(rng, d, f, scale=0.3),
          "shared_gate_weight": _rand(rng, 1, d, scale=0.5)}
    x = _rand(rng, 1, tokens, d)
    cfg = {"num_experts_per_tok": 4, "norm_topk_prob": True}
    op = get_op("ExpertFFN")

    def share(first):
        attrs = op.parse_attrs(dict(num_experts=e, experts_held=4,
                                    first_expert=first, top_k=4,
                                    act_type="silu"))
        (y, _), _ = op.impl(attrs, (x, x, lp["router_weight"]) + tuple(
            lp[n][first:first + 4] for n in ("gate_weight", "up_weight",
                                             "down_weight")), (), None)
        return y

    with jax.default_matmul_precision("highest"):
        layer = sum(share(first) for first in range(0, e, 4)) \
            + fam.shared(x[0], lp)[None]
        uncut = fam.expert_layer(x[0], lp, cfg)[None]
        gate = jax.nn.sigmoid(x[0] @ lp["shared_gate_weight"].T)
    np.testing.assert_allclose(layer, uncut, atol=2e-5)
    # the gate is a number a token in (0, 1), and it weighs the shared
    # expert alone
    assert gate.shape == (tokens, 1) and 0 < gate.min() and gate.max() < 1
    with jax.default_matmul_precision("highest"):
        ungated = fam.shared(x[0], dict(lp, shared_gate_weight=jnp.zeros(
            (1, d))))
    np.testing.assert_allclose(fam.shared(x[0], lp), 2 * gate * ungated,
                               rtol=1e-5, atol=1e-6)


# --- the model through the trainer's path ------------------------------------

def _bound(sym, compute_dtype=None, batch=(2, 40)):
    inputs = {"data": batch, "softmax_label": batch}
    return sym.simple_bind(
        mx.cpu(), grad_req={n: "null" if n in inputs else "write"
                            for n in sym.list_arguments()},
        type_dict=dict.fromkeys(inputs, "int32"),
        compute_dtype=compute_dtype, **inputs)


def test_block_builder_makes_gdn_and_gated_attention_layers(fam, toy):
    """Kinds, names and wiring of the toy Qwen3-Next: layers 0-2 Gated
    DeltaNet (their nodes ``<layer>_gdn_*``, the core reading the q|k|v
    and b|a projections, z gating the per-head norm), layer 3 gated
    attention (the gate projected from the layer's normed input), every
    shared expert behind its sigmoid gate; the family's leaves are the
    symbol's, in its order and at its shapes."""
    sym = fam.symbol(toy, True)
    ops = {n.name: n for n in sym._nodes() if not n.is_var}
    assert ops["layer0_gdn_core"].op.name == "GatedDeltaRule"
    assert [c.name for c, _ in ops["layer0_gdn_core"].inputs][:2] == [
        "layer0_gdn_qkv", "layer0_gdn_ba"]
    assert ops["layer0_gdn_qkv"].inputs[0][0].name == "layer0_ln1"
    assert {c.name for c, _ in ops["layer0_gdn_gated"].inputs} == {
        "layer0_gdn_flat", "layer0_gdn_silu"}
    assert ops["layer0_gdn_silu"].inputs[0][0].name == "layer0_gdn_z"
    assert not {"layer0_q", "layer0_attn", "layer3_gdn_core"} & set(ops)
    attn = ops["layer3_attn"]
    assert attn.attrs["output_gate"] and attn.attrs["qk_norm"]
    assert attn.attrs["rope_dims"] == 4
    assert [c.name for c, _ in attn.inputs][-1] == "layer3_q_gate"
    assert ops["layer3_q_gate"].inputs[0][0].name == "layer3_ln1"
    for i in range(4):
        gated = ops["layer%d_shared_gated" % i]
        assert {c.name for c, _ in gated.inputs} == {
            "layer%d_shared_ffn2" % i, "layer%d_shared_sigmoid" % i}
    args = [n for n in sym.list_arguments()
            if n not in ("data", "softmax_label")]
    shapes, _, _ = sym.infer_shape(data=(1, 64), softmax_label=(1, 64))
    got = dict(zip(sym.list_arguments(), map(tuple, shapes)))
    assert args == list(fam.param_shapes(toy))
    assert {n: got[n] for n in args} == fam.param_shapes(toy)
    with pytest.raises(ValueError, match="gdn mixer takes"):
        models.get_symbol("transformer-lm", num_layers=1,
                          layers=[{"mixer": "gdn", "gdn_heads": (3, 4),
                                   "gdn_dims": (16, 16)}])
    with pytest.raises(ValueError, match="shared_gate"):
        models.get_symbol("transformer-lm", num_layers=1,
                          layers=[{"ffn": "experts", "shared_gate": True}],
                          experts={"num_experts": 4})


def test_model_gradients_are_the_references(fam, toy):
    """The whole toy model in float32 through ``simple_bind``, forward and
    backward, at 40 tokens (the rule's last chunk padded): the loss and
    every leaf's gradient against the family's reference, whose rule runs
    token by token."""
    seed, batch = 21, (2, 40)
    exe = _bound(fam.symbol(toy, True), batch=batch)
    params = fam.init_params(toy, seed)
    (data, label), = fam.make_batches(
        toy, {"batch": batch[0], "seq_len": batch[1]}, seed, 1)
    for n in exe.arg_dict:
        if n in params:
            exe.arg_dict[n]._data = params[n]
    exe.arg_dict["data"]._data = data["data"]
    exe.arg_dict["softmax_label"]._data = label["softmax_label"]
    with jax.default_matmul_precision("highest"):
        loss = exe.forward(is_train=True)[0].asnumpy()
        exe.backward()
        want, grads = jax.value_and_grad(lambda p: sum(
            fam.ref_seq_loss(p, data["data"][i], label["softmax_label"][i],
                             toy) for i in range(batch[0]))
            / (batch[0] * batch[1]))(params)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    assert set(exe.grad_dict) - {"data", "softmax_label"} == set(params)
    for n in params:
        np.testing.assert_allclose(exe.grad_dict[n].asnumpy(), grads[n],
                                   atol=3e-6, rtol=3e-4, err_msg=n)


def test_fused_steps_record_says_what_was_traced(fam, toy):
    """``make_train_step`` under ``compute_dtype="bfloat16"``: the loss
    falls, A_log and dt_bias stay float32 in the step, and the record names
    every delta-rule node's path, chunk, heads and state dtype, and the
    attention node's gate."""
    from mxnet_tpu import telemetry

    exe = _bound(fam.symbol(toy, True), compute_dtype="bfloat16",
                 batch=(1, 64))
    params = fam.init_params(toy, 3)
    telemetry.reset()
    step = exe.make_train_step(lambda p, g, s: (
        {n: p[n] - 0.3 * g[n] for n in p}, s))
    (data, label), = fam.make_batches(toy, {"batch": 1, "seq_len": 64}, 3,
                                      1)
    losses, states = [], {}
    for _ in range(4):
        outs, params, states = step(params, states, {**data, **label})
        losses.append(float(np.asarray(outs[0]).reshape(-1)[0]))
    assert losses[-1] < losses[0]
    (rec,) = telemetry.programs()
    gdn = [r for r in rec["layers"] if r["op"] == "GatedDeltaRule"]
    assert [(r["node"], r["path"], r["chunk"], r["key_heads"],
             r["value_heads"], r["state_dtype"]) for r in gdn] == [
        ("layer%d_gdn_core" % i, "plain", 64, 2, 4, "float32")
        for i in range(3)]
    attn = [r for r in rec["layers"] if r["op"] == "MultiHeadAttention"]
    assert [(r["node"], r["output_gate"]) for r in attn] == [
        ("layer3_attn", True)]
    from mxnet_tpu.executor import master_leaves
    kept = master_leaves(exe._symbol)
    assert {"layer0_gdn_core_A_log", "layer0_gdn_core_dt_bias"} <= kept
    assert not {n for n in kept if "gdn_qkv" in n}


# --- the accepted cells' toys build the graphs they built --------------------

# graph hashes of the five accepted cells' toy configurations (the families'
# symbols under a fresh NameManager), taken before the gdn mixer, the
# attention's output gate and the shared expert's gate: every attention
# node serializes the new ``output_gate`` attribute at its default, which
# the hash leaves out; nothing else may differ
ACCEPTED = {"toy_lm": ("transformer_lm", "425a9b9ed454cdde", 31),
            "toy_smallthinker": ("smallthinker_lm", "d695a9546e56f2af", 45),
            "toy_lfm2": ("lfm2_moe_lm", "fe158a4d4620cb04", 51),
            "toy_ouro": ("ouro_lm", "eb8429d2b244992d", 29),
            "toy_glm": ("glm_moe_lite_lm", "b1cacdf57cdc2785", 69)}


@pytest.mark.parametrize("toy_name", sorted(ACCEPTED))
def test_accepted_cells_toys_build_the_graphs_they_built(toy_name):
    family, digest, n_args = ACCEPTED[toy_name]
    with mx.name.NameManager():
        sym = _family(family).symbol(_toy(toy_name), True)
    graph = json.loads(sym.tojson())
    attention = [n["attrs"] for n in graph["nodes"]
                 if n["op"] == "MultiHeadAttention"]
    assert attention and all(a.pop("output_gate") == "False"
                             for a in attention)
    text = json.dumps(graph, indent=2)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
    assert len(sym.list_arguments()) == n_args
