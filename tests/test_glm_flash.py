"""What GLM-4.7-Flash forced into the trainer: latent attention (MLA: the
block builder's ``latent`` mixer, ``LatentKV``'s assembly of the key and
``MultiHeadAttention``'s ``rope_dims``), a shared expert beside the routed
ones, a multi-token-prediction module (``get_symbol(mtp=)``,
``MultiTokenLoss``) and the flash kernels at head size 256 (interpreted
here). Each against the benchmark family's plain reference
(``benchmark/families/glm_moe_lite_lm.py``) or a hand-written formula, on
seeded weights, forward and gradients."""
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
        "glm_family", os.path.join(BENCH, "families", "glm_moe_lite_lm.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def toy():
    with open(os.path.join(BENCH, "tests", "data", "toy_glm.json")) as f:
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


def _mla_leaves(fam, cfg, rng):
    shapes = fam._layer_shapes(cfg, "", "dense")
    return {n: (1 + _rand(rng, *s, scale=0.2) if n.endswith("_gamma")
                else _rand(rng, *s, scale=0.3))
            for n, s in shapes.items() if n.startswith(("mla_", "o_"))}


def _latent_nodes(attrs_mha, lp, y, cfg):
    """The program's nodes of a latent mixer, in float32: the projections
    (FullyConnected, here plain products), the two RMSNorm ops,
    ``slice_axis``, ``LatentKV`` and ``MultiHeadAttention``."""
    rms, mha, kvop = (get_op("RMSNorm"), get_op("MultiHeadAttention"),
                      get_op("LatentKV"))
    eps = {"eps": cfg["rms_norm_eps"]}

    def run(op, attrs, *inputs):
        outs, _ = op.impl(op.parse_attrs(attrs), inputs, (), None)
        return outs

    (c_q,) = run(rms, eps, y @ lp["mla_q_a_weight"].T, lp["mla_q_norm_gamma"])
    q = c_q @ lp["mla_q_b_weight"].T
    latent = y @ lp["mla_kv_a_weight"].T
    (c_kv,) = run(rms, eps, latent[..., :cfg["kv_lora_rank"]],
                  lp["mla_kv_norm_gamma"])
    k, v = run(kvop, {"num_heads": cfg["num_attention_heads"],
                      "head_dim": cfg["qk_nope_head_dim"]
                      + cfg["qk_rope_head_dim"],
                      "rope_dims": cfg["qk_rope_head_dim"]},
               latent, c_kv @ lp["mla_kv_b_weight"].T)
    (att,) = run(mha, attrs_mha, q, k, v)
    return att @ lp["o_weight"].T


def _mha_attrs(cfg, **kw):
    return dict(num_heads=cfg["num_attention_heads"], causal=True,
                use_rope=True, rope_base=float(cfg["rope_theta"]),
                rope_dims=cfg["qk_rope_head_dim"], use_flash=False, **kw)


# --- latent attention --------------------------------------------------------

def test_latent_attention_against_the_reference(fam, toy):
    """The program's latent mixer (the nodes the builder makes) against the
    family's ``_mla``: output and every gradient, float32."""
    rng = np.random.RandomState(3)
    lp = _mla_leaves(fam, toy, rng)
    y = _rand(rng, 2, 10, toy["hidden_size"])
    names = list(lp)

    def f(y, *leaves):
        return _latent_nodes(_mha_attrs(toy), dict(zip(names, leaves)), y,
                             toy)

    def ref(y, *leaves):
        p = dict(zip(names, leaves))
        return jnp.stack([fam._mla(y[i], p, toy, False) for i in range(2)])

    with jax.default_matmul_precision("highest"):
        _close(f, ref, (y,) + tuple(lp.values()), tol=3e-5)


def test_key_assembly_and_rope_dims_by_hand(toy):
    """``LatentKV`` and ``rope_dims`` against MLA written out head by head
    in numpy: the key is a head's unrotated part of the up-projection and
    the ONE rotary part of the down-projection, rotated at its position over
    its 4 dims alone; the query rotates its trailing 4 and nothing else; a
    ``rope_dims`` of 0 rotates the whole head as before."""
    h, nope, r, kvr = (toy["num_attention_heads"], toy["qk_nope_head_dim"],
                       toy["qk_rope_head_dim"], toy["kv_lora_rank"])
    dh, t = nope + r, 6
    rng = np.random.RandomState(5)
    latent = np.asarray(_rand(rng, 1, t, kvr + r))
    kv = np.asarray(_rand(rng, 1, t, h * (nope + dh)))
    op = get_op("LatentKV")
    (key, value), _ = op.impl(op.parse_attrs(
        {"num_heads": h, "head_dim": dh, "rope_dims": r}),
        (jnp.asarray(latent), jnp.asarray(kv)), (), None)
    key, value = np.asarray(key), np.asarray(value)
    for i in range(h):
        block = kv[0, :, i * (nope + dh):(i + 1) * (nope + dh)]
        np.testing.assert_array_equal(key[0, :, i * dh:i * dh + nope],
                                      block[:, :nope])
        np.testing.assert_array_equal(key[0, :, i * dh + nope:(i + 1) * dh],
                                      latent[0, :, kvr:])
        np.testing.assert_array_equal(value[0, :, i * dh:(i + 1) * dh],
                                      block[:, nope:])

    def rotate(x, base=1e6):     # (T, n): positions 0..T-1, halves
        half = x.shape[-1] // 2
        ang = np.arange(x.shape[0])[:, None] * base ** (
            -np.arange(half) / half)
        x1, x2 = x[:, :half], x[:, half:]
        return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                               x2 * np.cos(ang) + x1 * np.sin(ang)], -1)

    q = np.asarray(_rand(rng, 1, t, h * dh))
    mha = get_op("MultiHeadAttention")
    for dims in (r, 0):
        attrs = mha.parse_attrs(dict(_mha_attrs(toy), rope_dims=dims))
        (out,), _ = mha.impl(attrs, (jnp.asarray(q), jnp.asarray(key),
                                     jnp.asarray(value)), (), None)
        want = np.zeros((t, h * dh))
        for i in range(h):
            cols = slice(i * dh, (i + 1) * dh)
            qh, kh = q[0, :, cols], key[0, :, cols]
            if dims:
                qh = np.concatenate([qh[:, :-dims], rotate(qh[:, -dims:])], -1)
                kh = np.concatenate([kh[:, :-dims], rotate(kh[:, -dims:])], -1)
            else:
                qh, kh = rotate(qh), rotate(kh)
            s = qh @ kh.T / np.sqrt(dh)
            s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            want[:, i * dh:(i + 1) * dh] = (p / p.sum(-1, keepdims=True)
                                            @ value[0, :, i * dh:(i + 1) * dh])
        np.testing.assert_allclose(np.asarray(out)[0], want, atol=2e-5)
    with pytest.raises(ValueError, match="rope_dims"):
        mha.impl(mha.parse_attrs(dict(_mha_attrs(toy), rope_dims=3)),
                 (jnp.asarray(q), jnp.asarray(key), jnp.asarray(value)), (),
                 None)


# --- the expert layer with a shared expert -----------------------------------

def _expert_leaves(rng, e, held, d, f, fs):
    return {"router_weight": _rand(rng, e, d, scale=0.5),
            "gate_weight": _rand(rng, held, f, d, scale=0.3),
            "up_weight": _rand(rng, held, f, d, scale=0.3),
            "down_weight": _rand(rng, held, d, f, scale=0.3),
            "shared_ffn1_weight": _rand(rng, fs, d, scale=0.3),
            "shared_ffn3_weight": _rand(rng, fs, d, scale=0.3),
            "shared_ffn2_weight": _rand(rng, d, fs, scale=0.3)}


def _routed_op(e, held, first, top_k, scale):
    op = get_op("ExpertFFN")
    attrs = op.parse_attrs(dict(
        num_experts=e, experts_held=held, first_expert=first, top_k=top_k,
        act_type="silu", route="sigmoid_bias", norm_eps=1e-20, scale=scale))

    def f(x, lp, bias):
        (y, _), _ = op.impl(attrs, (x, x, lp["router_weight"],
                                    lp["gate_weight"], lp["up_weight"],
                                    lp["down_weight"]), (bias,), None)
        return y

    return f


def _shared(x, lp):
    return ((jax.nn.silu(x @ lp["shared_ffn1_weight"].T)
             * (x @ lp["shared_ffn3_weight"].T)) @ lp["shared_ffn2_weight"].T)


def test_the_eight_shares_and_the_shared_expert_add_up_to_the_uncut_layer(
        fam):
    """The eight shares ``first_expert`` 0, 8, .., 56 of a layer of 64
    experts (top 4, scale 1.8, the sigmoid-and-bias route), each with its
    own experts and the whole router and bias, plus the shared expert
    counted ONCE, sum to what the reference gives a holder of all 64: the
    router and the shared expert are what every chip computes alike."""
    e, tokens, d, f = 64, 32, 16, 24
    rng = np.random.RandomState(9)
    lp = _expert_leaves(rng, e, e, d, f, f)
    x, bias = _rand(rng, 1, tokens, d), _rand(rng, e, scale=0.3)
    cfg = {"num_experts_per_tok": 4, "norm_topk_prob": True,
           "routed_scaling_factor": 1.8}
    with jax.default_matmul_precision("highest"):
        parts = [_routed_op(e, 8, first, 4, 1.8)(
            x, {n: (a[first:first + 8] if n in ("gate_weight", "up_weight",
                                                 "down_weight") else a)
                for n, a in lp.items()}, bias) for first in range(0, e, 8)]
        layer = sum(parts) + _shared(x, lp)
        uncut = fam.expert_layer(x[0], lp, bias, cfg)[None]
    np.testing.assert_allclose(layer, uncut, atol=2e-5)
    # the routed part weighs by 1.8; the shared expert by nothing
    with jax.default_matmul_precision("highest"):
        routed = fam.routed(x[0], lp, bias, cfg, False)
        half = fam.routed(x[0], lp, bias,
                          dict(cfg, routed_scaling_factor=0.9), False)
    np.testing.assert_allclose(routed, 2 * half, rtol=1e-5, atol=1e-6)


# --- the multi-token-prediction objective ------------------------------------

def test_multi_token_loss_shifts_by_one_and_drops_the_last_position():
    """``MultiTokenLoss``: position i's logits against the label one later
    (the token two after i), the last position in no term, the mean over
    the T - 1 that have a target, times the weight; the last position's
    logits get no gradient."""
    b, t, v = 2, 7, 11
    rng = np.random.RandomState(1)
    logits = _rand(rng, b * t, v)
    label = jnp.asarray(rng.randint(0, v, (b, t)).astype(np.int32))
    op = get_op("MultiTokenLoss")
    attrs = op.parse_attrs({"weight": 0.3})

    def f(z):
        (out,), _ = op.impl(attrs, (z, label), (), None)
        return out

    z = np.asarray(logits).reshape(b, t, v)
    logp = z - np.log(np.exp(z).sum(-1, keepdims=True))
    want = 0.3 * -np.mean([logp[i, j, int(label[i, j + 1])]
                           for i in range(b) for j in range(t - 1)])
    np.testing.assert_allclose(f(logits), want, rtol=1e-6)
    grad = np.asarray(jax.grad(f)(logits)).reshape(b, t, v)
    assert not grad[:, -1].any() and grad[:, :-1].any()


def _bound(sym, compute_dtype=None, batch=(2, 16)):
    inputs = {"data": batch, "softmax_label": batch}
    return sym.simple_bind(
        mx.cpu(), grad_req={n: "null" if n in inputs else "write"
                            for n in sym.list_arguments()},
        type_dict=dict.fromkeys(inputs, "int32"),
        compute_dtype=compute_dtype, **inputs)


def test_block_builder_makes_latent_layers_a_shared_expert_and_the_module(
        fam, toy):
    """Kinds, names and wiring of the toy GLM: every mixer latent (its
    nodes ``<layer>_mla_*``, no plain q/k/v projection), the shared expert
    on the experts' own input, the module embedding the LABELS with the
    model's table, reading the stack's state BEFORE the final norm, and
    multiplying by the model's head."""
    sym = fam.symbol(toy, True)
    ops = {n.name: n for n in sym._nodes() if not n.is_var}
    assert not {"layer0_q", "layer0_k", "layer0_v"} & set(ops)
    assert ops["layer1_attn"].attrs["rope_dims"] == toy["qk_rope_head_dim"]
    assert ops["layer1_mla_kv"].op.name == "LatentKV"
    q, k, v = (c.name for c, _ in ops["layer1_attn"].inputs)
    assert (q, k, v) == ("layer1_mla_q_b", "layer1_mla_kv", "layer1_mla_kv")
    assert ops["layer1_shared_ffn1"].inputs[0][0].name == "layer1_ln2"
    assert ops["layer1_experts"].inputs[0][0].name == "layer1_ln2"
    emb = ops["mtp_embed"]
    assert emb.inputs[0][0].name == "softmax_label"
    assert emb.inputs[1][0] is ops["embed"].inputs[1][0]
    assert ops["mtp_hnorm"].inputs[0][0].name != "lnf"
    assert ops["lnf"].inputs[0][0] is ops["mtp_hnorm"].inputs[0][0]
    assert ops["mtp_pred"].inputs[1][0] is ops["pred"].inputs[1][0]
    assert ops["mtp_loss"].attrs["weight"] == toy["mtp_loss_weight"]
    assert sym.list_auxiliary_states() == [
        "layer1_experts_expert_bias", "layer2_experts_expert_bias",
        "mtp_layer_experts_expert_bias"]
    kind = fam.layer_kind(toy, "experts")
    with pytest.raises(ValueError, match="latent mixer takes"):
        models.get_symbol("transformer-lm", num_layers=1, head_dim=16,
                          layers=[dict(kind, q_rank=0)], num_heads=4,
                          model_dim=64, experts={"num_experts": 8})
    with pytest.raises(ValueError, match="shared_dim"):
        models.get_symbol("transformer-lm", num_layers=1,
                          layers=[{"shared_dim": 8}])
    with pytest.raises(ValueError, match="mtp"):
        models.get_symbol("transformer-lm", num_layers=1,
                          mtp={"layer": {}, "weight": 0.3})


@pytest.mark.parametrize("with_mtp", [True, False])
def test_model_gradients_are_the_references(fam, toy, with_mtp):
    """The whole toy model in float32 through ``simple_bind``, forward and
    backward, with and without the module: the loss and every leaf's
    gradient against the family's reference (without the module, its main
    term alone), the seeded bias in the executor's auxiliary states."""
    seed, batch = 21, (2, 16)
    sym = fam.symbol(toy, True)
    if not with_mtp:
        sym = models.get_symbol(
            "transformer-lm", num_classes=toy["vocab_size"],
            num_layers=toy["num_hidden_layers"],
            num_heads=toy["num_attention_heads"], head_dim=16,
            model_dim=toy["hidden_size"],
            ffn_dim=toy["moe_intermediate_size"],
            num_kv_heads=toy["num_key_value_heads"],
            layers=[fam.layer_kind(toy, f) for f in ("dense", "experts",
                                                     "experts")],
            experts={"num_experts": 8, "experts_held": 4, "top_k": 2,
                     "act_type": "silu", "route": "sigmoid_bias",
                     "norm_eps": 1e-20, "scale": 1.8},
            final_norm="rms", head_bias=False, norm_eps=1e-5,
            scalar_loss=True)
    exe = _bound(sym, batch=batch)
    state = fam.init_state(toy, seed)
    params = fam.ref_params(toy, seed)
    exe.copy_params_from({}, aux_params={n: a for n, a in state.items()
                                         if n in exe.aux_dict})
    traffic = {"batch": batch[0], "seq_len": batch[1]}
    (data, label), = fam.make_batches(toy, traffic, seed, 1)
    for n in exe.arg_dict:
        if n in params:
            exe.arg_dict[n]._data = params[n]
    exe.arg_dict["data"]._data = data["data"]
    exe.arg_dict["softmax_label"]._data = label["softmax_label"]

    def main_only(p, s, tokens, labels, cfg):
        h, _ = fam.ref_states(p, s, tokens, labels, cfg)
        return jnp.sum(fam._nll(h, p["pred_weight"], labels, False))

    loss_fn = fam.ref_seq_loss if with_mtp else main_only
    with jax.default_matmul_precision("highest"):
        loss = exe.forward(is_train=True)[0].asnumpy()
        exe.backward()
        want, grads = jax.value_and_grad(lambda p: sum(
            loss_fn(p, state, data["data"][i], label["softmax_label"][i],
                    toy) for i in range(batch[0])) / (batch[0] * batch[1]))(
            params)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    names = [n for n in exe.grad_dict if n in params]
    assert len(names) == (len(params) if with_mtp else len(
        [n for n in params if not n.startswith("mtp_")]))
    for n in names:
        np.testing.assert_allclose(exe.grad_dict[n].asnumpy(), grads[n],
                                   atol=3e-6, rtol=3e-4, err_msg=n)


def test_fused_steps_span_and_record_say_what_was_traced(fam, toy):
    """``make_train_step`` under ``compute_dtype="bfloat16"``: the loss
    falls, the span carries ``mtp_depth`` 1 and ``mtp_weight`` 0.3, and
    the record names every attention node's head size and rotary part."""
    from mxnet_tpu import telemetry

    exe = _bound(fam.symbol(toy, True), compute_dtype="bfloat16")
    params = fam.init_params(toy, 3)
    telemetry.reset()
    step = exe.make_train_step(lambda p, g, s: (
        {n: p[n] - 0.3 * g[n] for n in p}, s))
    (data, label), = fam.make_batches(toy, {"batch": 2, "seq_len": 16}, 3, 1)
    telemetry.drain_events()
    losses, states = [], {}
    for _ in range(5):
        outs, params, states = step(params, states, {**data, **label})
        losses.append(float(np.asarray(outs[0]).reshape(-1)[0]))
    assert losses[-1] < losses[0]
    (rec,) = telemetry.programs()
    attn = [r for r in rec["layers"] if r["op"] == "MultiHeadAttention"]
    assert [(r["node"], r["head_dim"], r["rope_dims"]) for r in attn] == [
        ("layer%d_attn" % i, 16, 4) for i in range(3)] + [
        ("mtp_layer_attn", 16, 4)]
    spans = [args for ph, name, _d, _t, _dur, args, *_ in
             telemetry.drain_events(clear=False)
             if name == "executor.train_step"]
    assert len(spans) == 5
    for args in spans:
        assert args["mtp_depth"] == 1 and args["mtp_weight"] == 0.3
        assert args["moe_layers"] == 3


# --- flash at head size 256 --------------------------------------------------

def _plain_attention(q, k, v):
    t, d = q.shape[2], q.shape[3]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HP) / np.sqrt(d)
    keep = np.arange(t)[None, :] <= np.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=HP)


@pytest.mark.parametrize("regime", ["fused", "superblocked"])
def test_flash_kernels_at_head_size_256_interpreted(regime, monkeypatch):
    """Forward and the fused backward (whole, and in query superblocks of
    one tile, as the cell's 4096 tokens take them at 512 rows) at head
    size 256 in interpret mode against the einsum path: output and the
    gradients of q, k and v. The record says head 256, the fused backward
    and its superblock."""
    from mxnet_tpu.ops.pallas import flash_attention as fa
    from mxnet_tpu.ops.registry import built_layers

    t = 512
    monkeypatch.setattr(fa, "BLOCK_Q", 256)
    monkeypatch.setattr(fa, "BLOCK_K", 256)
    if regime == "superblocked":
        monkeypatch.setattr(fa, "_SCOPED_VMEM",
                            fa._fused_bwd_vmem_bytes(t, t, 256, 4, 256))
    rng = np.random.RandomState(t)
    q, k, v = (_rand(rng, 1, 2, t, 256) for _ in range(3))
    with built_layers() as built:
        _close(lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                                  interpret=True),
               _plain_attention, (q, k, v), tol=3e-5)
    assert {(r["backward"], r["q_super"]) for r in built.layers
            if "backward" in r} == {("fused", {"fused": t,
                                              "superblocked": 256}[regime])}
    assert {r["head_dim"] for r in built.layers if r.get("kernel")} == {256}


def test_the_cells_head_of_256_takes_superblocks_of_512_rows():
    """At the cell's 4096 x 4096 in bfloat16 the fused backward's whole-key
    accumulators (2 x 4096 x 256 x 4 bytes) leave room for a query
    superblock of 512 rows and not of 1024 (16.91 MB against 16.78)."""
    from mxnet_tpu.ops.pallas import flash_attention as fa

    assert fa.kernel_qualifies(4096, 4096, 256, causal=True)
    assert fa._fused_q_super(4096, 4096, 256, 2) == 512
    assert fa._fused_bwd_vmem_bytes(4096, 4096, 256, 2, 1024) \
        > fa._SCOPED_VMEM >= fa._fused_bwd_vmem_bytes(4096, 4096, 256, 2, 512)
