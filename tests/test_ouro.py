"""What Ouro-2.6B forced into the trainer (ISSUE 39): a layer stack run
several times with one set of leaves (``get_symbol(loops=...)``), the
sandwich norm (``post_norm``), an exit after every pass and the
``LoopExitLoss`` objective over the exits, and the ``loop_*`` attributes of
the step's span. Each against the benchmark family's plain reference
(``benchmark/families/ouro_lm.py``) on seeded weights, at a small size: 2
layers, 3 passes, hidden 64, 4 heads of 16, vocabulary 256, 32 tokens."""
import hashlib
import importlib.util
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models, telemetry
from mxnet_tpu.ops import sequence
from mxnet_tpu.ops.registry import get_op

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEQ = 32


def _family(name):
    """A benchmark family file, loaded by path as ``run.py`` loads it."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        name + "_family", os.path.join(BENCH, "families", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _toy(name):
    with open(os.path.join(BENCH, "tests", "data", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def fam():
    return _family("ouro_lm")


@pytest.fixture(scope="module")
def toy():
    return _toy("toy_ouro")


def _bound(sym, compute_dtype=None, batch=(1, SEQ)):
    inputs = {"data": batch, "softmax_label": batch}
    return sym.simple_bind(
        mx.cpu(), grad_req={n: "null" if n in inputs else "write"
                            for n in sym.list_arguments()},
        type_dict=dict.fromkeys(inputs, "int32"),
        compute_dtype=compute_dtype, **inputs)


def _batch(fam, toy, seed):
    (data, label), = fam.make_batches(toy, {"batch": 1, "seq_len": SEQ},
                                      seed, 1)
    return data, label


def _spans():
    return [args for _ph, name, _d, _t, _dur, args, *_ in
            telemetry.drain_events(clear=False)
            if name == "executor.train_step"]


# --- the objective over the exits ---------------------------------------------

def _exit_inputs(seed, exits=3, rows=6, vocab=9):
    rng = np.random.RandomState(seed)
    logits = tuple(jnp.asarray(rng.randn(rows, vocab).astype(np.float32))
                   for _ in range(exits))
    gates = tuple(jnp.asarray(2 * rng.randn(rows).astype(np.float32))
                  for _ in range(exits - 1))
    return logits, gates, jnp.asarray(rng.randint(0, vocab, rows))


@pytest.mark.parametrize("exits", [2, 3, 4])
def test_exit_loss_is_the_formula(exits, fam):
    """The op's scalar against the formula written out in numpy (float64),
    and its closed-form gradients of every exit's logits and every gate
    against ``jax.grad`` of the family's reference formulation."""
    logits, gates, label = _exit_inputs(exits, exits)
    op = get_op("LoopExitLoss")
    attrs = op.parse_attrs({"num_exits": exits, "beta": 0.1})

    def f(logits, gates):
        (out,), _ = op.impl(attrs, (*logits, *[g[:, None] for g in gates],
                                    label.astype(jnp.float32)), (), None)
        return out

    z = [np.asarray(a, np.float64) for a in logits]
    lam = [1 / (1 + np.exp(-np.asarray(g, np.float64))) for g in gates]
    p, stay = [], 1.0
    for la in lam:
        p.append(la * stay)
        stay = stay * (1 - la)
    p.append(stay)
    rows = np.arange(len(label))
    want = 0.0
    for zt, pt in zip(z, p):
        lse = np.log(np.exp(zt).sum(-1))
        want += np.sum(pt * (lse - zt[rows, np.asarray(label)])
                       + 0.1 * pt * np.log(pt))
    np.testing.assert_allclose(float(f(logits, gates)), want, rtol=1e-6)

    def ref(logits, gates):
        ps = fam.exit_distribution([jax.nn.sigmoid(g) for g in gates])
        return sum(jnp.sum(pt * -jnp.take_along_axis(
            jax.nn.log_softmax(zt), label[:, None], 1)[:, 0]
            + 0.1 * pt * jnp.log(pt)) for zt, pt in zip(logits, ps))

    got, want = jax.grad(f, (0, 1))(logits, gates), \
        jax.grad(ref, (0, 1))(logits, gates)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)


def test_exit_loss_keeps_the_logits_dtype_and_builds_no_one_hot():
    """bfloat16 logits: the scalar is float32, each exit's gradient comes
    back bfloat16, the gates' in their own dtype; the backward's jaxpr
    holds no one-hot of the label; a loop of one pass is refused."""
    logits, gates, label = _exit_inputs(5)
    low = tuple(z.astype(jnp.bfloat16) for z in logits)
    loss, vjp = jax.vjp(lambda z, g: sequence._exit_loss(z, g, label, 0.1),
                        low, gates)
    assert loss.dtype == jnp.float32
    dz, dg = vjp(jnp.float32(1.0))
    assert [d.dtype for d in dz] == [jnp.bfloat16] * 3
    assert [d.dtype for d in dg] == [jnp.float32] * 2
    text = str(jax.make_jaxpr(lambda z, g: jax.grad(
        lambda z: sequence._exit_loss(z, g, label, 0.1))(z))(low, gates))
    assert "one_hot" not in text
    with pytest.raises(ValueError, match="num_exits"):
        get_op("LoopExitLoss").impl(
            get_op("LoopExitLoss").parse_attrs({"num_exits": 1}),
            (logits[0], label), (), None)


# --- the model builder ---------------------------------------------------------

def test_loop_builds_one_set_of_leaves_and_names_the_passes(fam, toy):
    """Every looped parameter is ONE argument, named for its layer; the
    nodes are named for their pass; each layer's four norms; the exits
    share the final norm, the head and the gate, and the last exit has no
    gate."""
    sym = fam.symbol(toy, True)
    args = sym.list_arguments()
    assert len(args) == len(set(args))
    assert not [a for a in args if a.startswith("ut")]
    assert [a for a in args if a not in ("data", "softmax_label")] \
        == list(fam.param_shapes(toy))
    ops = {n.name: n for n in sym._nodes() if not n.is_var}
    for t in range(3):
        for i in range(2):
            for part in ("ln1", "post1", "ln2", "post2"):
                node = ops["ut%d_layer%d_%s" % (t, i, part)]
                assert node.op.name == "RMSNorm"
                assert node.inputs[1][0].name == "layer%d_%s_gamma" % (i,
                                                                     part)
            assert ops["ut%d_layer%d_q" % (t, i)].inputs[1][0].name \
                == "layer%d_q_weight" % i
        assert ops["ut%d_pred" % t].inputs[1][0].name == "pred_weight"
    assert ops["ut0_exit_gate"].inputs[1][0].name == "exit_gate_weight"
    assert "ut2_exit_gate" not in ops
    # the next pass starts from the normed state the exit reads
    assert ops["ut1_layer0_ln1"].inputs[0][0].name == "ut0_lnf"
    loss = ops["exit_loss"]
    assert loss.op.name == "LoopExitLoss" and loss.attrs["num_exits"] == 3
    assert [c.name for c, _ in loss.inputs[:5]] == [
        "ut0_pred", "ut1_pred", "ut2_pred", "ut0_exit_gate", "ut1_exit_gate"]
    assert sym.list_outputs() == ["loss_output"]
    for bad in ({"loops": 2}, {"exit_loss": {"beta": 0.1}}):
        with pytest.raises(ValueError, match="exit_loss"):
            models.get_symbol("transformer-lm", num_layers=1, **bad)
    with pytest.raises(ValueError, match="expert_bias"):
        models.get_symbol(
            "transformer-lm", num_layers=1, loops=2,
            exit_loss={"beta": 0.1}, layers=[{"ffn": "experts"}],
            experts={"num_experts": 4, "top_k": 1, "route": "sigmoid_bias"})


def test_post_norm_alone_is_the_sandwich():
    """``post_norm`` without a loop: the mixer's and the feed-forward's
    outputs normed before each add, with scales of their own."""
    sym = models.get_symbol(
        "transformer-lm", num_classes=50, num_layers=1, num_heads=4,
        model_dim=16, ffn_dim=12, final_norm="rms", head_bias=False,
        layers=[{"norm": "rms", "ffn": "swiglu", "post_norm": True}])
    ops = {n.name: n for n in sym._nodes() if not n.is_var}
    assert ops["layer0_post1"].inputs[0][0].name == "layer0_o"
    assert ops["layer0_post2"].inputs[0][0].name == "layer0_ffn2"
    assert {"layer0_post1_gamma", "layer0_post2_gamma"} <= set(
        sym.list_arguments())


# graph hashes of the three cells' toy configurations (the families'
# symbols under a fresh NameManager), taken at the parent of ISSUE 39: a loop
# of one pass and no post_norm build exactly the graph the builder built.
# Since ``MultiHeadAttention`` has ``rope_dims`` and ``output_gate`` every
# attention node also serializes those attributes' defaults, which the hash
# leaves out: nothing else may differ
TODAYS = {"toy_lm": ("transformer_lm", "db00ad483be9ac8d", 31),
          "toy_smallthinker": ("smallthinker_lm", "581884492ef5d781", 45),
          "toy_lfm2": ("lfm2_moe_lm", "3834b4a502980c58", 51)}


@pytest.mark.parametrize("toy_name", sorted(TODAYS))
def test_one_pass_builds_todays_graph(toy_name):
    family, digest, n_args = TODAYS[toy_name]
    cfg = _toy(toy_name)
    with mx.name.NameManager():
        sym = _family(family).symbol(cfg, True)
    graph = json.loads(sym.tojson())
    attention = [n["attrs"] for n in graph["nodes"]
                 if n["op"] == "MultiHeadAttention"]
    assert attention and all(a.pop("rope_dims") == "0"
                             and a.pop("output_gate") == "False"
                             for a in attention)
    text = json.dumps(graph, indent=2)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
    assert len(sym.list_arguments()) == n_args
    assert not [n for n in sym._nodes() if n.name.startswith("ut")
                or not n.is_var and n.op.name == "LoopExitLoss"]


# --- the system against the reference -------------------------------------------

def _copy(params):
    """The step consumes what it is passed (donation)."""
    return {n: jnp.array(a, copy=True) for n, a in params.items()}


def _sgd(lr):
    """An update that also hands the gradients back as the new state."""
    return lambda p, g, s: ({n: p[n] - lr * g[n] for n in p}, dict(g))


def test_train_step_is_the_references(fam, toy):
    """The toy through ``simple_bind`` + ``make_train_step`` in float32 at
    ``highest``: the loss, the gradient of every leaf and the parameters
    after one SGD step against the family's ``ref_seq_loss``."""
    seed, lr = 21, 0.05
    exe = _bound(fam.symbol(toy, True))
    params = fam.init_params(toy, seed)
    data, label = _batch(fam, toy, seed)
    with jax.default_matmul_precision("highest"):
        want, grads = jax.value_and_grad(lambda p: fam.ref_seq_loss(
            p, data["data"][0], label["softmax_label"][0], toy) / SEQ)(params)
        step = exe.make_train_step(_sgd(lr))
        outs, new, got = step(_copy(params), {}, {**data, **label})
    np.testing.assert_allclose(np.asarray(outs[0]).reshape(-1)[0], want,
                               rtol=1e-5)
    assert set(got) == set(grads)
    for n, g in grads.items():
        np.testing.assert_allclose(got[n], g, atol=2e-6, rtol=2e-4,
                                   err_msg=n)
        np.testing.assert_allclose(new[n], params[n] - lr * g, atol=1e-6,
                                   rtol=1e-5, err_msg=n)


def test_a_looped_leaf_gets_the_sum_over_its_uses(fam, toy):
    """The reference with every pass given its OWN copy of the layers'
    leaves: the program's gradient of a looped leaf is the sum of the
    copies' gradients, and no single copy's."""
    seed = 4
    exe = _bound(fam.symbol(toy, True))
    params = fam.init_params(toy, seed)
    data, label = _batch(fam, toy, seed)
    tokens, labels = data["data"][0], label["softmax_label"][0]
    loops, n_layers = toy["total_ut_steps"], toy["num_hidden_layers"]

    def per_pass(copies, rest):
        x = rest["embed_weight"][tokens]
        exits = []
        for t in range(loops):
            for i in range(n_layers):
                x = fam._block(x, fam._layer(copies[t], i), toy, False)
            x = fam._rms(x, rest["lnf_gamma"], toy["rms_norm_eps"])
            exits.append(x)
        lams = [jax.nn.sigmoid(fam._mm(h, rest["exit_gate_weight"], False)
                               [:, 0] + rest["exit_gate_bias"][0])
                for h in exits[:-1]]
        return sum(jnp.sum(p * fam._exit_nll(h, rest["pred_weight"], labels,
                                             False)
                           + 0.1 * p * jnp.log(p))
                   for h, p in zip(exits, fam.exit_distribution(lams))) / SEQ

    looped = [n for n in params if n.startswith("layer")]
    rest = {n: a for n, a in params.items() if n not in looped}
    with jax.default_matmul_precision("highest"):
        copies = [{n: params[n] for n in looped} for _ in range(loops)]
        each = jax.grad(per_pass)(copies, rest)
        _, _, got = exe.make_train_step(_sgd(0.0))(
            _copy(params), {}, {**data, **label})
    for n in looped:
        total = sum(np.asarray(c[n]) for c in each)
        np.testing.assert_allclose(got[n], total, atol=2e-6, rtol=2e-4,
                                   err_msg=n)
        assert np.abs(np.asarray(each[0][n]) - total).max() > 1e-4 * \
            np.abs(total).max(), n


def test_bf16_step_casts_each_leaf_once_and_says_it_loops(fam, toy):
    """Under ``compute_dtype="bfloat16"`` the lowered step converts every
    float32 argument once however many nodes read it (the table, which
    only a gather reads, not at all); the loss falls over steps; the span
    carries the loop's attributes and the program's record the exits'."""
    exe = _bound(fam.symbol(toy, True), compute_dtype="bfloat16")
    params = fam.init_params(toy, 3)
    data, label = _batch(fam, toy, 3)
    step = exe.make_train_step(lambda p, g, s: (
        {n: p[n] - 0.5 * g[n] for n in p}, s))
    text = step.lower(params, {}, {**data, **label}).as_text()
    converts = re.findall(r"stablehlo\.convert (%arg\d+) : \(tensor<[^>]*f32>"
                          r"\) -> tensor<[^>]*bf16>", text)
    assert len(converts) == len(set(converts)) == len(params) - 1
    telemetry.reset()
    losses = []
    for _ in range(4):
        outs, params, _ = step(params, {}, {**data, **label})
        losses.append(float(np.asarray(outs[0]).reshape(-1)[0]))
    assert losses[-1] < losses[0]
    spans = _spans()
    assert len(spans) == 4
    for args in spans:
        assert (args["loop_steps"], args["loop_layers"],
                args["loop_exits"]) == (3.0, 2, 3)
        assert "moe_layers" not in args
    (rec,) = telemetry.programs()
    (exits,) = [r for r in rec["layers"] if r["op"] == "LoopExitLoss"]
    assert (exits["exits"], exits["rows"], exits["node"]) == (3, SEQ,
                                                              "exit_loss")
    assert len([r for r in rec["layers"]
                if r["op"] == "MultiHeadAttention"]) == 6
