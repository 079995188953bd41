"""The step's vocabulary-sized passes (ISSUE 30).

(a) ``softmax_cross_entropy`` in closed form: value and gradient against
``-sum(log_softmax[label])`` and ``jax.grad`` of it; (b) ``transformer-lm``
``scalar_loss=True`` against the ``log_softmax`` x ``one_hot`` composition
it replaced, built here; (c) ``make_train_step(compute_dtype="bfloat16")``
leaves a table that only ``Embedding`` gathers from in its master dtype,
casts everything else as before, and its lowered text holds neither a
one-hot nor a log_softmax array; (d) ``uncast_table_bytes`` on the
``executor.train_step`` record.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models, telemetry
from mxnet_tpu import symbol as sym
from mxnet_tpu.executor import _gathered_only
from mxnet_tpu.models import transformer
from mxnet_tpu.ops.registry import OpContext, get_op

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
    pred = transformer._backbone(vocab, _LM["num_layers"], _LM["num_heads"],
                                 _LM["model_dim"], _LM["ffn_dim"],
                                 _LM["num_kv_heads"], _LM["use_flash"])
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


_LM_SHAPES = {"data": (3, 8), "softmax_label": (3, 8)}
_INT = {"data": "int32", "softmax_label": "int32"}


def _toy_lm(vocab=56):
    return models.get_symbol("transformer-lm", num_classes=vocab,
                             scalar_loss=True, **_LM)


def test_fused_lm_step_gathers_from_the_master_table():
    vocab, dm, rows = 56, _LM["model_dim"], 24
    net = _toy_lm(vocab)
    assert _gathered_only(net) == {"embed_weight"}
    exe, step, params, states, feed = _fused(net, _LM_SHAPES, types=_INT)
    text = step.lower(params, states, feed).as_text()
    # the head's weight is cast whole, for the MXU; the table no longer
    assert _converts(text, (vocab, dm)) == 1
    # its rows are cast after the gather, and their cotangent scatters into
    # a table of the compute dtype that widens for the update, as before
    assert _converts(text, (3, 8, dm)) >= 1
    assert _converts(text, (vocab, dm), "bf16", "f32") == 2
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
    assert _gathered_only(net) == frozenset()
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
    assert _gathered_only(net) == frozenset()
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
        assert _gathered_only(net) == frozenset()
    elif case == "frozen":
        # the rule reads consumers, not grad_req: a frozen table qualifies
        assert _gathered_only(sym.sum(a)) == {"a_weight"}
    else:
        b = sym.Embedding(data=data, input_dim=9, output_dim=4, name="b")
        scaled = sym._mul_scalar(sym.Variable("b_weight"), scalar=2.0)
        net = sym.Group([a + b, sym.sum(scaled)])
        assert _gathered_only(net) == {"a_weight"}


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
