"""Sequence operators + binary loss.

TPU-native equivalents of src/operator/sequence_{mask,last,reverse}.cc and
src/operator/tensor/loss_binary_op.cc (softmax_cross_entropy), the
objective over the exits of a stack run several times (LoopExitLoss), and a
multi-token-prediction module's shifted one (MultiTokenLoss). Layout
follows the reference: time-major (max_len, batch, ...) unless axis says
otherwise; sequence_length is a (batch,) vector of valid lengths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .registry import REQUIRED, defop, note_built


def _len_mask(seq_len, max_len, batch, dtype):
    steps = jnp.arange(max_len, dtype=jnp.float32).reshape(max_len, 1)
    return (steps < seq_len.astype(jnp.float32).reshape(1, batch)).astype(dtype)


@defop(
    "SequenceMask",
    arg_names=lambda attrs: ("data", "sequence_length") if attrs.get("use_sequence_length") else ("data",),
    param_spec={"use_sequence_length": False, "value": 0.0, "axis": 0},
    no_grad_inputs=("sequence_length",),
)
def _sequence_mask(attrs, data, sequence_length=None):
    """Mask positions past each sequence's length with `value`
    (reference sequence_mask-inl.h)."""
    if sequence_length is None:
        return data
    ax = int(attrs["axis"])
    x = jnp.moveaxis(data, ax, 0) if ax != 0 else data
    t, b = x.shape[0], x.shape[1]
    mask = _len_mask(sequence_length, t, b, x.dtype).reshape((t, b) + (1,) * (x.ndim - 2))
    out = x * mask + attrs["value"] * (1 - mask)
    return jnp.moveaxis(out, 0, ax) if ax != 0 else out


@defop(
    "SequenceLast",
    arg_names=lambda attrs: ("data", "sequence_length") if attrs.get("use_sequence_length") else ("data",),
    param_spec={"use_sequence_length": False, "axis": 0},
    no_grad_inputs=("sequence_length",),
)
def _sequence_last(attrs, data, sequence_length=None):
    """Select the last valid timestep per sequence (reference
    sequence_last-inl.h)."""
    ax = int(attrs["axis"])
    x = jnp.moveaxis(data, ax, 0) if ax != 0 else data
    if sequence_length is None:
        return x[-1]
    idx = jnp.maximum(sequence_length.astype(jnp.int32) - 1, 0)  # (batch,)
    return jax.vmap(lambda col, i: col[i], in_axes=(1, 0))(x, idx)


@defop(
    "SequenceReverse",
    arg_names=lambda attrs: ("data", "sequence_length") if attrs.get("use_sequence_length") else ("data",),
    param_spec={"use_sequence_length": False, "axis": 0},
    no_grad_inputs=("sequence_length",),
)
def _sequence_reverse(attrs, data, sequence_length=None):
    """Reverse the valid prefix of each sequence (reference
    sequence_reverse-inl.h)."""
    if sequence_length is None:
        return jnp.flip(data, axis=0)
    t = data.shape[0]
    steps = jnp.arange(t)

    def rev_one(col, length):  # col: (t, ...), length: scalar
        src = jnp.where(steps < length, length - 1 - steps, steps)
        return col[src]

    return jax.vmap(rev_one, in_axes=(1, 0), out_axes=1)(
        data, sequence_length.astype(jnp.int32)
    )


@jax.custom_vjp
def _summed_nll(logits, label):
    """sum over rows of logsumexp(row) - row[label], in closed form: the
    vocabulary is read for the float32 logsumexp and for nothing else (the
    label's logit is a gather of one number a row), and the backward is
    written out, so that neither a one-hot array nor a log_softmax array
    exists and the cotangent is never reduced over the vocabulary: the
    gradient is the one array the backward writes."""
    return _summed_nll_fwd(logits, label)[0]


def _summed_nll_fwd(logits, label):
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logits, label[:, None], axis=1)[:, 0]
    # the scalar leaves in float32, as it was summed: a bfloat16 sum over
    # 8192 rows would step by 512
    nll = jnp.sum(lse - picked.astype(jnp.float32))
    return nll, (logits, label, lse)


def _summed_nll_bwd(res, g):
    logits, label, lse = res
    p = jnp.exp(logits.astype(jnp.float32) - lse[:, None])
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    d = g * (p - (col == label[:, None]))
    # written once: a head multiplies it twice and sums it once, and XLA
    # would put the exp above into the prologue of each. On the v5e that
    # held the two matmuls at 16.9 and 14.4 ms a step where they take 13.5
    # on the stored array, which costs 2.4 ms to write (PERF.md, PR 30)
    return jax.lax.optimization_barrier(d.astype(logits.dtype)), None


_summed_nll.defvjp(_summed_nll_fwd, _summed_nll_bwd)


@defop(
    "softmax_cross_entropy",
    arg_names=("data", "label"),
    param_spec={},
    no_grad_inputs=("label",),
)
def _softmax_cross_entropy(attrs, data, label):
    """Scalar summed cross-entropy (reference loss_binary_op.cc), float32
    whatever the logits' dtype."""
    return _summed_nll(data, label.astype(jnp.int32).reshape(-1))


@defop(
    "MultiTokenLoss",
    arg_names=("data", "label"),
    param_spec={"weight": 1.0},
    no_grad_inputs=("label",),
)
def _multi_token_loss(attrs, data, label):
    """A multi-token-prediction module's objective: ``weight`` times the
    mean over positions of the closed-form cross-entropy of the logits at
    position i against the label one position later, over the T - 1
    positions that have one. ``data`` (B * T, vocab), the rows in
    ``label``'s order; ``label`` (B, T) the next-token labels, so that
    position i's target is the token two after it. Float32 whatever the
    logits' dtype."""
    weight = float(attrs["weight"])
    b, t = label.shape
    note_built({"op": "MultiTokenLoss", "weight": weight})
    logits = data.reshape(b, t, -1)[:, :t - 1].reshape(b * (t - 1), -1)
    target = label.astype(jnp.int32)[:, 1:].reshape(-1)
    return _summed_nll(logits, target) * (weight / (b * (t - 1)))


def _exit_distribution(gates):
    """``(log p, p)``, each (exits, rows) float32, from the gate logits
    (exits - 1, rows) float32: ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``
    with ``lambda = sigmoid(gate)``, and the last exit what the others
    leave. In log space, where a product of many small factors stays
    finite."""
    log_exit = jax.nn.log_sigmoid(gates)
    log_stay = jax.nn.log_sigmoid(-gates)
    before = jnp.cumsum(log_stay, axis=0) - log_stay  # sum over j < t
    logp = jnp.concatenate([log_exit + before,
                            jnp.sum(log_stay, axis=0, keepdims=True)], 0)
    return logp, jnp.exp(logp)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _exit_loss(logits, gates, label, beta):
    """sum over rows of ``sum_t p_t CE_t + beta sum_t p_t log p_t``: the
    exits' expected cross-entropy less ``beta`` times the exit
    distribution's entropy. ``logits``: one (rows, vocab) array an exit;
    ``gates``: one (rows,) gate logit an exit but the last. Each exit's
    cross-entropy is ``_summed_nll``'s closed form a row (its own float32
    logsumexp, the label's logit gathered); the weights and the entropy are
    float32; the backward is written out and builds no one-hot."""
    return _exit_loss_fwd(logits, gates, label, beta)[0]


def _exit_loss_fwd(logits, gates, label, beta):
    lses, ce = [], []
    for z in logits:  # one exit's float32 logsumexp at a time
        lse = jax.nn.logsumexp(z.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(z, label[:, None], axis=1)[:, 0]
        lses.append(lse)
        ce.append(lse - picked.astype(jnp.float32))
    ce = jnp.stack(ce)
    a = jnp.stack([g.astype(jnp.float32) for g in gates])
    logp, p = _exit_distribution(a)
    loss = jnp.sum(p * (ce + beta * logp))
    return loss, (logits, gates, label, lses, ce, a, logp, p)


def _exit_loss_bwd(beta, res, g):
    logits, gates, label, lses, ce, a, logp, p = res
    # d loss / d p_t is CE_t + beta (log p_t + 1); the constant beta drops
    # out, the p's summing to 1 whatever the gates
    cp = p * (ce + beta * logp)
    after = jnp.cumsum(cp[::-1], axis=0)[::-1] - cp  # sum over t > j
    lam = jax.nn.sigmoid(a)
    # d p_t / d gate_j = p_t (1 - lambda_j) at t = j, -p_t lambda_j at t > j
    d_gates = g * (cp[:-1] * (1.0 - lam) - lam * after[:-1])
    d_logits = []
    for t, (z, lse) in enumerate(zip(logits, lses)):
        prob = jnp.exp(z.astype(jnp.float32) - lse[:, None])
        col = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
        d = (g * p[t])[:, None] * (prob - (col == label[:, None]))
        # stored once, as _summed_nll's: the head multiplies it twice
        d_logits.append(jax.lax.optimization_barrier(d.astype(z.dtype)))
    return (tuple(d_logits),
            tuple(d.astype(x.dtype) for d, x in zip(d_gates, gates)),
            None)


_exit_loss.defvjp(_exit_loss_fwd, _exit_loss_bwd)


def _exit_args(attrs):
    n = int(attrs["num_exits"])
    return (tuple("logits%d" % t for t in range(n))
            + tuple("gate%d" % t for t in range(n - 1)) + ("label",))


@defop(
    "LoopExitLoss",
    arg_names=_exit_args,
    param_spec={"num_exits": REQUIRED, "beta": 0.1},
    no_grad_inputs=("label",),
)
def _loop_exit_loss(attrs, *inputs):
    """Scalar summed objective over the exits of a layer stack run
    ``num_exits`` times (each pass's exit reads the state it leaves):
    ``logits0..`` (rows, vocab) an exit, ``gate0..`` the gate logit of
    every exit but the last, (rows,) or (rows, 1), whose sigmoid is the
    chance of leaving there, and ``label`` (rows,). Per row, the exits'
    cross-entropies weighed by the exit distribution, plus ``beta`` times
    ``sum_t p_t log p_t`` (the distribution's entropy, negated), float32
    whatever the logits' dtype."""
    n = int(attrs["num_exits"])
    if n < 2:
        raise ValueError("LoopExitLoss: num_exits %d; a loop has 2 or more"
                         % n)
    logits, gates, label = inputs[:n], inputs[n:2 * n - 1], inputs[-1]
    label = label.astype(jnp.int32).reshape(-1)
    note_built({"op": "LoopExitLoss", "exits": n, "rows": label.shape[0]})
    return _exit_loss(tuple(logits), tuple(g.reshape(-1) for g in gates),
                      label, float(attrs["beta"]))
