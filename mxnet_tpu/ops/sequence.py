"""Sequence operators + binary loss.

TPU-native equivalents of src/operator/sequence_{mask,last,reverse}.cc and
src/operator/tensor/loss_binary_op.cc (softmax_cross_entropy). Layout
follows the reference: time-major (max_len, batch, ...) unless axis says
otherwise; sequence_length is a (batch,) vector of valid lengths.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import defop


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
