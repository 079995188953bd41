"""The step's vocabulary-sized passes (ISSUE 30).

(a) ``softmax_cross_entropy`` in closed form: value and gradient against
``-sum(log_softmax[label])`` and ``jax.grad`` of it; (b) ``transformer-lm``
``scalar_loss=True`` against the ``log_softmax`` x ``one_hot`` composition
it replaced, built here; (c) ``make_train_step(compute_dtype="bfloat16")``
leaves a table that only ``Embedding`` gathers from in its master dtype,
casts everything else as before, and its lowered text holds neither a
one-hot nor a log_softmax array; (d) ``uncast_table_bytes`` on the
``executor.train_step`` record; (e) the backward of ``Embedding`` (ISSUE
38): the table's cotangent from a compact table of the ids' runs, against
``jax.grad`` of ``jnp.take``, in both branches of the operator, and the
``layers`` record that names the path.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models, telemetry
from mxnet_tpu import symbol as sym
from mxnet_tpu.executor import master_leaves
from mxnet_tpu.models import transformer
from mxnet_tpu.ops import matrix
from mxnet_tpu.ops.registry import OpContext, built_layers, get_op

INPUTS = ("data", "softmax_label")


@pytest.fixture(autouse=True)
def _clean_ring():
    telemetry.reset()
    yield
    telemetry.reset()


def _step_records():
    return [args for _ph, name, _dom, _ts, _dur, args, _tid, _tn
            in telemetry.drain_events(clear=False)
            if name == "executor.train_step"]


# --- (a) the operator ---------------------------------------------------------

def _op(data, label):
    op = get_op("softmax_cross_entropy")
    (out,), _ = op.impl(op.parse_attrs({}), (data, label), (), OpContext())
    return out


def _plain(data, label):
    logp = jax.nn.log_softmax(data.astype(jnp.float32), axis=-1)
    return -jnp.sum(jnp.take_along_axis(
        logp, label.astype(jnp.int32)[:, None], axis=1))


_LABELS = {
    "distinct": [0, 299, 17, 128, 5, 64, 255],
    "repeated": [5, 5, 5, 299, 17, 5, 17],
}


@pytest.mark.parametrize("label_dtype", ["int32", "float32"])
@pytest.mark.parametrize("labels", sorted(_LABELS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_softmax_cross_entropy_closed_form(dtype, labels, label_dtype):
    """A vocabulary of 300 (no multiple of 128), float labels as ``Module``
    sends them, a label that several rows share."""
    dtype = jnp.dtype(dtype)
    data = (3.0 * jax.random.normal(jax.random.PRNGKey(3), (7, 300),
                                    jnp.float32)).astype(dtype)
    label = jnp.asarray(_LABELS[labels], label_dtype)
    value, grad = jax.value_and_grad(_op)(data, label)
    want, want_grad = jax.value_and_grad(_plain)(data, label)
    # the sum leaves in float32 whatever the logits are stored in; the
    # gradient in their dtype, each entry rounded once
    assert value.dtype == jnp.float32 and grad.dtype == dtype
    assert value.shape == () and grad.shape == data.shape
    np.testing.assert_allclose(value, want, rtol=1e-6)
    tol = 1e-6 if dtype == jnp.float32 else 2.0 ** -8
    np.testing.assert_allclose(np.asarray(grad, np.float32),
                               np.asarray(want_grad, np.float32),
                               rtol=tol, atol=tol * 1e-2)


def test_softmax_cross_entropy_accumulates_in_float32():
    """The logsumexp of bfloat16 logits is summed in float32, and so is the
    loss over the rows: 4096 equal logits in each of 300 rows give 300
    log(4096) to float32's rounding, where a bfloat16 sum of ones stops at
    256 and a bfloat16 loss of this size steps by 16."""
    data = jnp.zeros((300, 4096), jnp.bfloat16)
    label = jnp.zeros(300, jnp.int32)
    value, grad = jax.jit(jax.value_and_grad(_op))(data, label)
    np.testing.assert_allclose(value, 300 * np.log(4096.0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(grad[:, 1:], np.float32),
                               1.0 / 4096, rtol=2.0 ** -8)


# --- (b) the model's head -------------------------------------------------------

_LM = dict(num_layers=2, num_heads=2, model_dim=16, ffn_dim=32, num_kv_heads=1,
           use_flash=False)


def _old_head(vocab):
    """``get_symbol(scalar_loss=True)`` as it was before ISSUE 30."""
    (pred, _), = transformer._backbone(
        vocab, _LM["num_layers"], _LM["num_heads"], _LM["model_dim"],
        _LM["ffn_dim"], _LM["num_kv_heads"], _LM["use_flash"])
    label = sym.Reshape(data=sym.Variable("softmax_label"), shape=(-1,))
    logp = sym.log_softmax(pred, axis=-1)
    onehot = sym.one_hot(label, depth=vocab)
    nll = sym._mul_scalar(
        sym.mean(sym.sum(sym._mul(logp, onehot), axis=1)), scalar=-1.0)
    return sym.MakeLoss(nll, name="loss")


def _loss_and_grads(net, vocab, batch, seq):
    exe = net.simple_bind(mx.cpu(), data=(batch, seq),
                          softmax_label=(batch, seq))
    rng = np.random.RandomState(1)
    for n, a in exe.arg_dict.items():
        a[:] = (rng.randint(0, vocab, a.shape) if n in INPUTS
                else rng.normal(0, 0.3, a.shape))
    loss = exe.forward(is_train=True)[0].asnumpy()
    exe.backward()
    return loss, {n: g.asnumpy() for n, g in exe.grad_dict.items()
                  if n not in INPUTS}


def test_lm_scalar_loss_head_equals_the_composition_it_replaced():
    vocab, batch, seq = 50, 3, 8
    new = models.get_symbol("transformer-lm", num_classes=vocab,
                            scalar_loss=True, **_LM)
    assert new.list_outputs() == ["loss_output"]
    assert new.list_arguments() == _old_head(vocab).list_arguments()
    loss, grads = _loss_and_grads(new, vocab, batch, seq)
    want, want_grads = _loss_and_grads(_old_head(vocab), vocab, batch, seq)
    assert loss.shape == want.shape == ()
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    assert sorted(grads) == sorted(want_grads) and len(grads) == 29
    for n in grads:
        np.testing.assert_allclose(grads[n], want_grads[n], rtol=1e-6,
                                   atol=1e-6, err_msg=n)


# --- (c) the fused step under a compute dtype -----------------------------------

def _fused(net, shapes, compute_dtype="bfloat16", types=None):
    """(executor, step, params, states, feed) of ``net``'s fused SGD step."""
    exe = net.simple_bind(
        mx.cpu(), grad_req={n: "null" if n in INPUTS else "write"
                            for n in net.list_arguments()},
        type_dict=types, compute_dtype=compute_dtype, **shapes)
    rng = np.random.RandomState(0)
    params = {n: jnp.asarray(rng.normal(0, 0.1, a.shape), jnp.float32)
              for n, a in exe.arg_dict.items() if n not in INPUTS}
    feed = {n: jnp.asarray(rng.randint(0, 8, shapes[n]), exe.arg_dict[n].dtype)
            for n in INPUTS}

    def update(p, g, s):
        return {n: p[n] - 0.1 * g[n] for n in p}, s

    states = {n: jnp.zeros_like(a) for n, a in params.items()}
    return exe, exe.make_train_step(update), params, states, feed


def _converts(text, shape, src="f32", dst="bf16"):
    dims = "x".join(str(d) for d in shape)
    return len(re.findall(
        r"stablehlo\.convert[^\n]*tensor<%sx%s>\) -> tensor<%sx%s>"
        % (dims, src, dims, dst), text))


def _scatter_results(text):
    """The result type of every ``stablehlo.scatter`` of a lowered text
    (the operation spans lines: its region comes before its type)."""
    return re.findall(r"stablehlo\.scatter.*?\}\) : \([^\n]*\) -> tensor<(\w+)>",
                      text, flags=re.S)


@pytest.fixture
def toy_vmem(monkeypatch):
    """VMEM scaled down to the toy LM's shapes, so that its table (56 rows
    of 32 bytes, 24 ids) stands where the benchmark cells' tables do: too
    large beside its rows, the compact table of 25 rows not."""
    monkeypatch.setattr(matrix, "_SCATTER_VMEM_BYTES", 2048)
    assert matrix.cotangent_path(56, 24, 16, 2) == "compact"


_LM_SHAPES = {"data": (3, 8), "softmax_label": (3, 8)}
_INT = {"data": "int32", "softmax_label": "int32"}


def _toy_lm(vocab=56):
    return models.get_symbol("transformer-lm", num_classes=vocab,
                             scalar_loss=True, **_LM)


def test_fused_lm_step_gathers_from_the_master_table(toy_vmem):
    vocab, dm, rows = 56, _LM["model_dim"], 24
    net = _toy_lm(vocab)
    assert master_leaves(net) == {"embed_weight"}
    exe, step, params, states, feed = _fused(net, _LM_SHAPES, types=_INT)
    text = step.lower(params, states, feed).as_text()
    # the head's weight is cast whole, for the MXU; the table no longer
    assert _converts(text, (vocab, dm)) == 1
    # its rows are cast after the gather, and their cotangent is summed in
    # the compute dtype (since ISSUE 38 in a compact table of 24 + 1 rows,
    # which one gather reads the table's gradient out of) and widens for
    # the update, as before: the table's and the head's
    assert _converts(text, (3, 8, dm)) >= 1
    assert _converts(text, (vocab, dm), "bf16", "f32") == 2
    scattered = set(_scatter_results(text))
    assert "25x%dxbf16" % dm in scattered
    assert not [r for r in scattered if r.startswith("%dx%dx" % (vocab, dm))]
    # over (rows, vocabulary): the row maximum and the sum of exp, and no
    # other reduction of a row; one column index, the backward's; no log
    over_rows = [line for line in text.splitlines()
                 if re.search(r"\(tensor<%dx%dx\w+>" % (rows, vocab), line)]
    reduced = [line for line in over_rows
               if "stablehlo.reduce" in line and "dimensions = [1]" in line]
    assert len(reduced) == 2, reduced
    assert len(re.findall(r"stablehlo\.iota[^\n]*tensor<%dx%dx"
                          % (rows, vocab), text)) == 1
    assert not re.search(r"stablehlo\.log [^\n]*tensor<%dx%dx"
                         % (rows, vocab), text)
    outs, params, states = step(params, states, feed)
    assert np.isfinite(np.asarray(outs[0], np.float32)).all()
    assert all(a.dtype == jnp.float32 for a in params.values())


def test_fused_lm_step_is_unchanged_without_a_compute_dtype():
    net = _toy_lm()
    exe, step, params, states, feed = _fused(net, _LM_SHAPES,
                                             compute_dtype=None, types=_INT)
    text = step.lower(params, states, feed).as_text()
    assert "bf16" not in text
    step(params, states, feed)
    (rec,) = telemetry.programs()
    assert rec["memory"]["uncast_table_bytes"] == 0


def _conv_net():
    data = sym.Variable("data")
    net = sym.Convolution(data, num_filter=4, kernel=(3, 3), name="conv")
    net = sym.BatchNorm(net, name="bn")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(sym.Flatten(net), num_hidden=8, name="fc")
    return sym.SoftmaxOutput(net, name="softmax")


def test_fused_conv_step_casts_every_float32_leaf_as_before():
    net = _conv_net()
    assert master_leaves(net) == frozenset()
    shapes = {"data": (2, 3, 8, 8), "softmax_label": (2,)}
    exe, step, params, states, feed = _fused(net, shapes)
    text = step.lower(params, states, feed).as_text()
    for name, a in params.items():
        assert _converts(text, a.shape) >= 1, name
    step(params, states, feed)
    (rec,) = telemetry.programs()
    assert rec["memory"]["uncast_table_bytes"] == 0


def _tied_lm(vocab=56, dm=16):
    """One matrix is the embedding's table and the head's weight."""
    table = sym.Variable("table_weight")
    x = sym.Embedding(data=sym.Variable("data"), weight=table,
                      input_dim=vocab, output_dim=dm, name="embed")
    x = sym.FullyConnected(data=sym.Reshape(x, shape=(-1, dm)), weight=table,
                           num_hidden=vocab, no_bias=True, name="pred")
    label = sym.Reshape(data=sym.Variable("softmax_label"), shape=(-1,))
    return sym.MakeLoss(sym.softmax_cross_entropy(x, label), name="loss")


def test_a_table_tied_to_the_head_is_still_cast():
    net = _tied_lm()
    assert master_leaves(net) == frozenset()
    exe, step, params, states, feed = _fused(net, _LM_SHAPES, types=_INT)
    text = step.lower(params, states, feed).as_text()
    assert _converts(text, (56, 16)) == 1
    outs, params, states = step(params, states, feed)
    assert np.isfinite(np.asarray(outs[0], np.float32)).all()


@pytest.mark.parametrize("case", ["output", "frozen", "two_tables"])
def test_gathered_only_reads_the_graph(case):
    data = sym.Variable("data")
    a = sym.Embedding(data=data, input_dim=9, output_dim=4, name="a")
    if case == "output":
        # a table that is also a head of the graph is read by the caller
        net = sym.Group([a, sym.Variable("a_weight")])
        assert master_leaves(net) == frozenset()
    elif case == "frozen":
        # the rule reads consumers, not grad_req: a frozen table qualifies
        assert master_leaves(sym.sum(a)) == {"a_weight"}
    else:
        b = sym.Embedding(data=data, input_dim=9, output_dim=4, name="b")
        scaled = sym._mul_scalar(sym.Variable("b_weight"), scalar=2.0)
        net = sym.Group([a + b, sym.sum(scaled)])
        assert master_leaves(net) == {"a_weight"}


def _bound_toy_lm():
    """(executor, params, feed) of the toy LM bound under bfloat16."""
    net = _toy_lm()
    exe = net.simple_bind(
        mx.cpu(), grad_req={n: "null" if n in INPUTS else "write"
                            for n in net.list_arguments()},
        type_dict=_INT, compute_dtype="bfloat16", **_LM_SHAPES)
    rng = np.random.RandomState(0)
    params = {n: jnp.asarray(rng.normal(0, 0.1, a.shape), jnp.float32)
              for n, a in exe.arg_dict.items() if n not in INPUTS}
    feed = {n: jnp.asarray(rng.randint(0, 8, _LM_SHAPES[n]), jnp.int32)
            for n in INPUTS}
    return exe, params, feed


@pytest.mark.parametrize("path", ["forward", "forward_backward",
                                  "fused_step"])
def test_every_compiled_path_gathers_from_the_master_table(path,
                                                           monkeypatch):
    """Under bfloat16 each of the executor's three programs hands
    ``Embedding`` its table in float32, and gives bitwise the outputs and
    gradients it gives where the table is cast in advance (``Embedding``
    declaring nothing): a cast commutes with a gather, and the rows'
    cotangent is summed in bfloat16 either way."""
    op = get_op("Embedding")
    impl, seen = op.impl, []

    def spy(attrs, inputs, aux, ctx):
        if ctx.compute_dtype is not None:  # the executor's, not inference's
            seen.append(jnp.dtype(inputs[1].dtype))
        return impl(attrs, inputs, aux, ctx)

    monkeypatch.setattr(op, "impl", spy)

    def run():
        del seen[:]
        exe, params, feed = _bound_toy_lm()
        if path == "fused_step":
            step = exe.make_train_step(lambda p, g, s: (g, s))
            outs, grads, _ = step(params, {}, feed)
        else:
            for n, a in {**params, **feed}.items():
                exe.arg_dict[n]._data = a
            if path == "forward":
                outs, grads = exe.forward(is_train=True), {}
            else:
                outs = exe.forward_backward()
                grads = {n: exe.grad_dict[n]._data for n in params}
        return (set(seen), [np.asarray(getattr(o, "_data", o)) for o in outs],
                {n: np.asarray(g) for n, g in grads.items()})

    kept, outs, grads = run()
    monkeypatch.setattr(op, "master_inputs", ())
    cast, want_outs, want_grads = run()
    assert kept == {jnp.dtype("float32")} and cast == {jnp.dtype("bfloat16")}
    assert len(outs) == len(want_outs) == 1
    np.testing.assert_array_equal(outs[0], want_outs[0])
    assert sorted(grads) == sorted(want_grads)
    assert bool(grads) == (path != "forward")
    for n in grads:
        assert grads[n].dtype == np.float32, n
        np.testing.assert_array_equal(grads[n], want_grads[n], err_msg=n)


def test_an_op_that_declares_a_master_input_needs_no_executor_edit(
        monkeypatch):
    """An op registered here declares its ``scale`` argument and its
    ``shift`` state: under bfloat16 the executor hands it both in float32
    and the data in bfloat16, with the compute dtype in its context. Once
    another op reads ``scale`` too, the executor casts it."""
    from mxnet_tpu.ops.registry import OP_REGISTRY, OpDef

    seen = {}

    def impl(attrs, inputs, aux, ctx):
        (data, scale), (shift,) = inputs, aux
        seen.update(data=data.dtype, scale=scale.dtype, shift=shift.dtype,
                    compute=ctx.compute_dtype)
        out = data * scale.astype(data.dtype) + shift.astype(data.dtype)
        return (out,), (shift + 1,)

    op = OpDef("_ScaleShiftForTest", impl, arg_names=("data", "scale"),
               aux_names=("shift",), master_inputs=("scale", "shift"))
    monkeypatch.setitem(OP_REGISTRY, op.name, op)
    scale_shift = sym._make_sym_function(op)
    scale = sym.Variable("scale")
    alone = sym.sum(scale_shift(sym.Variable("data"), scale, name="ss"))
    shared = alone + sym.sum(scale)
    assert master_leaves(alone) == {"scale", "ss_shift"}
    assert master_leaves(shared) == {"ss_shift"}
    for net, scale_dtype in ((alone, jnp.float32), (shared, jnp.bfloat16)):
        seen.clear()
        exe = net.bind(mx.cpu(), args={"data": mx.nd.ones((2, 3)),
                                       "scale": mx.nd.ones((3,))},
                       aux_states={"ss_shift": mx.nd.zeros((3,))},
                       compute_dtype="bfloat16")
        (out,) = exe.forward(is_train=True)
        assert seen == {"data": jnp.bfloat16, "scale": scale_dtype,
                        "shift": jnp.float32, "compute": jnp.bfloat16}
        assert out.dtype == np.float32 and float(out.asnumpy()) == (
            6.0 if net is alone else 9.0)
        np.testing.assert_array_equal(exe.aux_dict["ss_shift"].asnumpy(),
                                      np.ones(3, np.float32))


# --- (d) the counter ---------------------------------------------------------------

def test_uncast_table_bytes_on_the_train_step_record():
    vocab = 56
    exe, step, params, states, feed = _fused(_toy_lm(vocab), _LM_SHAPES,
                                             types=_INT)
    for _ in range(2):
        outs, params, states = step(params, states, feed)
    (rec,) = telemetry.programs()  # static: the program's, not a step's
    assert rec["memory"]["uncast_table_bytes"] == 4 * vocab * _LM["model_dim"]
    assert len(_step_records()) == 2


# --- (e) the table's cotangent -------------------------------------------------------

_VOCAB, _DM = 37, 8
_IDS = {
    "distinct": lambda rng: rng.permutation(_VOCAB)[:11],
    "uniform_with_repeats": lambda rng: rng.randint(0, _VOCAB, 29),
    "all_equal": lambda rng: np.full(13, 5),
    "more_ids_than_rows": lambda rng: rng.randint(0, _VOCAB, 4 * _VOCAB),
    "two_dimensional": lambda rng: rng.randint(0, _VOCAB, (3, 9)),
    "three_dimensional": lambda rng: rng.randint(0, _VOCAB, (2, 3, 5)),
    # jnp.take reads -1 as the last row and -37 as the first, and drops
    # what lies outside the table after that: -38, 37, 100
    "outside_the_table": lambda rng: np.array(
        [-1, -_VOCAB, -_VOCAB - 1, _VOCAB - 1, _VOCAB, 100, 0, 0, 5, 5]),
}


@pytest.fixture
def always_compact(monkeypatch):
    """The operator's backward through the compact table at any shape
    (the rule sends these toy tables down the direct path)."""
    monkeypatch.setattr(matrix, "cotangent_path", lambda *shape: "compact")


def _embed(weight, ids, rows_as=None):
    """The operator itself, in a context that computes in ``rows_as``
    where asked."""
    op = get_op("Embedding")
    attrs = op.parse_attrs({"input_dim": weight.shape[0],
                            "output_dim": weight.shape[1]})
    (out,), _ = op.impl(attrs, (ids, weight), (),
                        OpContext(compute_dtype=rows_as))
    return out


def _table_grads(ids, dtype, branch):
    """(the operator's table gradient, ``jnp.take``'s own in ``dtype``, and
    in float32 from the same rounded cotangent): ``branch`` ``"master"`` is
    a float32 table in a context that computes in ``dtype``, ``"cast"`` a
    table that reaches the operator in ``dtype``."""
    rng = np.random.RandomState(3)
    ids = jnp.asarray(ids, jnp.int32)
    w = jnp.asarray(rng.normal(0, 1, (_VOCAB, _DM)), jnp.float32)
    g = jnp.asarray(rng.normal(0, 1, ids.shape + (_DM,)), dtype)
    if branch == "master":
        table, rows_as = w, dtype
    else:
        table, rows_as = w.astype(dtype), None
    with built_layers() as built:
        out, vjp = jax.vjp(lambda t: _embed(t, ids, rows_as), table)
        (got,) = vjp(g)
    assert [la["backward"] for la in built.layers] == ["compact"]
    assert out.dtype == jnp.dtype(dtype)
    assert got.dtype == table.dtype and got.shape == table.shape

    def plain(t, g):
        return jax.vjp(lambda t: jnp.take(t, ids, axis=0), t)[1](g)[0]

    return (np.asarray(got, np.float32),
            np.asarray(plain(w.astype(dtype), g), np.float32),
            np.asarray(plain(w, g.astype(jnp.float32))))


@pytest.mark.parametrize("branch", ["master", "cast"])
@pytest.mark.parametrize("ids", sorted(_IDS))
def test_embedding_backward_equals_takes_in_float32(ids, branch,
                                                    always_compact):
    got, _, want = _table_grads(_IDS[ids](np.random.RandomState(0)),
                                "float32", branch)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("branch", ["master", "cast"])
@pytest.mark.parametrize("ids", sorted(_IDS))
def test_embedding_backward_in_bfloat16_is_no_further_from_float32(
        ids, branch, always_compact):
    """Equal ids' rows are added in bfloat16, in id order and then in the
    order they came, as the scatter-add it replaces added them: no further
    from the float32 sum than that one, and equal to it where no id
    repeats."""
    got, today, exact = _table_grads(_IDS[ids](np.random.RandomState(0)),
                                     "bfloat16", branch)
    assert np.abs(got - exact).max() <= np.abs(today - exact).max() + 1e-6
    if ids == "distinct":
        np.testing.assert_array_equal(got, today)


def test_compact_cotangent_under_jit_on_every_case():
    rng = np.random.RandomState(1)
    for name in sorted(_IDS):
        ids = jnp.asarray(_IDS[name](rng), jnp.int32)
        g = jnp.asarray(rng.normal(0, 1, ids.shape + (_DM,)), jnp.float32)
        np.testing.assert_allclose(
            jax.jit(matrix._compact_cotangent, static_argnums=2)(
                g, ids, _VOCAB),
            matrix._direct_cotangent(g, ids, _VOCAB),
            rtol=1e-6, atol=1e-6, err_msg=name)


@pytest.mark.parametrize("rows,ids,d,itemsize,path", [
    (37984, 8192, 2560, 2, "compact"),    # smallthinker_train_8k
    (49152, 8192, 3072, 2, "compact"),    # lm_train_4k
    # lfm2_train_8k: the table itself stands in VMEM beside its 16384 rows
    (8192, 16384, 2048, 2, "direct"),
    (10000, 1120, 256, 4, "direct"),      # models/lstm_lm.py on PTB
    (56, 24, 16, 2, "direct"),            # the toy step above
    # neither the table nor the compact table stands in VMEM
    (65536, 16384, 4096, 2, "direct"),
    (37984, 8192, 2560, 4, "direct"),     # SmallThinker's in float32
    # the largest compact table the described v5e's compiler held, and
    # the first it did not (55.8 MiB of 3072 bfloat16 numbers a row); rows
    # of 2560 are padded to 3072 for the adds and count as such
    (10 ** 6, 9552, 3072, 2, "compact"),
    (10 ** 6, 9600, 3072, 2, "direct"),
    (10 ** 6, 9552, 2560, 2, "compact"),
    (10 ** 6, 9600, 2560, 2, "direct"),
])
def test_cotangent_path_reads_shapes_alone(rows, ids, d, itemsize, path):
    assert matrix.cotangent_path(rows, ids, d, itemsize) == path


def test_rows_are_padded_to_whole_pieces_for_the_adds():
    """A width over 1024 that is no multiple of it is padded for the
    compact table's scatter-add and sliced back: the sums are the same."""
    assert [matrix._scatter_width(d) for d in (16, 1024, 1536, 2560, 3072)
            ] == [16, 1024, 2048, 3072, 3072]
    rng = np.random.RandomState(2)
    ids = jnp.asarray(rng.randint(0, _VOCAB, (3, 9)), jnp.int32)
    g = jnp.asarray(rng.normal(0, 1, (3, 9, 1536)), jnp.float32)
    compact, _, _ = matrix._compact_table(g, ids, _VOCAB)
    assert compact.shape == (28, 1536)
    np.testing.assert_allclose(
        matrix._compact_cotangent(g, ids, _VOCAB),
        matrix._direct_cotangent(g, ids, _VOCAB), rtol=1e-6, atol=1e-6)


def test_embedding_records_the_path_its_backward_took(monkeypatch):
    # 37 rows of 32 bytes: beside 9 rows the compact table fits, the table
    # does not; beside 111 neither
    monkeypatch.setattr(matrix, "_SCATTER_VMEM_BYTES", 1024)
    w = jnp.zeros((_VOCAB, _DM), jnp.float32)
    for n, path in ((9, "compact"), (3 * _VOCAB, "direct")):
        ids = jnp.arange(n, dtype=jnp.int32) % _VOCAB
        with built_layers() as built:
            jax.grad(lambda t: jnp.sum(_embed(t, ids)))(w)
        assert built.layers == [{"op": "Embedding", "rows": _VOCAB, "ids": n,
                                 "backward": path, "node": ""}]


@pytest.mark.parametrize("path", ["compact", "direct"])
def test_fused_steps_name_the_embeddings_path_in_layers(path, request):
    """The toy LM's step (a master table, 24 ids into 56 rows) and the tied
    one's (a cast table): each program's record holds one ``Embedding``
    layer under its node, with the path the rule gave it."""
    if path == "compact":
        request.getfixturevalue("toy_vmem")
    for net in (_toy_lm(), _tied_lm()):
        telemetry.reset()
        exe, step, params, states, feed = _fused(net, _LM_SHAPES, types=_INT)
        step(params, states, feed)
        (rec,) = telemetry.programs()
        (layer,) = [la for la in rec["layers"] if la["op"] == "Embedding"]
        assert layer == {"op": "Embedding", "node": "embed", "rows": 56,
                         "ids": 24, "backward": path}


@pytest.mark.parametrize("net", ["master_table", "tied_table"])
@pytest.mark.parametrize("compute_dtype", [None, "bfloat16"])
def test_fused_step_equals_the_scatter_adds_arithmetic(net, compute_dtype,
                                                      toy_vmem, monkeypatch):
    """One fused step of the toy LM with the compact backward and with the
    scatter-add it replaced (the parent's arithmetic): every parameter
    after the step is equal. In float32 the sums are the same numbers in
    the same order; in bfloat16 too, here."""
    def one_step():
        telemetry.reset()
        sym_ = _toy_lm() if net == "master_table" else _tied_lm()
        exe, step, params, states, feed = _fused(
            sym_, _LM_SHAPES, compute_dtype=compute_dtype, types=_INT)
        # eight ids out of 56 rows: every id repeats
        outs, params, states = step(params, states, feed)
        return {n: np.asarray(a) for n, a in params.items()}

    new = one_step()
    monkeypatch.setattr(matrix, "_compact_cotangent",
                        matrix._direct_cotangent)
    old = one_step()
    assert sorted(new) == sorted(old)
    for n in new:
        np.testing.assert_allclose(new[n], old[n], rtol=1e-6, atol=1e-7,
                                   err_msg=n)
