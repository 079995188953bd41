"""Gated delta rule — Pallas TPU kernels for the core of ``GatedDeltaRule``
(``ops/delta_rule.py``): the rule over whole sequences, chunk after chunk,
the state held in VMEM in float32 between them.

A grid cell is one chunk of ``CHUNK`` tokens of ``HEADS`` value heads (the
whole value heads of two key heads); the grid runs a cell's chunks in
order (the states are carried in a scratch buffer, so that axis is
sequential) and the cells in parallel. A cell's heads are independent
chains of small products, and the compiler interleaves them. q, k and v
are read in place from the projection's (B, T, heads x size) layout in
column blocks: value head j reads key head j // (value heads / key heads),
with no copy of the shared key heads. The cumulative sums of g and the
write strengths come a head at a time, (chunks, CHUNK) float32, and each
cell takes its row. The mathematics of a chunk is
``delta_rule.chunk_forward`` and ``chunk_backward``, the same functions the
plain path runs:

- ``gated_delta_fwd``: reads q, k, v, g, beta once, writes o; where it is
  differentiated, also the state before every chunk (B x value heads x
  chunks x dk x dv float32, held from the layer's forward to its
  backward);
- ``gated_delta_bwd``: walks the chunks in REVERSE, reading those states,
  the gradient of the state after the chunk in VMEM, and writes the
  gradients of q and k a key head (the value heads that share it summed
  in the cell; a value head each, summed after, where a cell holds one),
  of v, of g's cumulative sums and of beta.

Joined by a ``custom_vjp``: the forward keeps its inputs and the states it
walked, so the backward walks the chunks once, in reverse. A call without
a gradient (inference, evaluation) writes o alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..delta_rule import CHUNK, chunk_backward, chunk_forward

_LANES = 128
_F32 = jnp.float32


# value heads a grid cell walks side by side: their chains of small
# products interleave (the compiler's bundles a head and chunk for a
# described v5e at 4 heads: 1542 forward with the states, 1534 without,
# 2337 backward, where one head alone takes 2071, 2014 and 2965; 8 heads
# save 5% more)
HEADS = 4


def _heads(hv, grp):
    """Value heads a grid cell walks: ``HEADS`` where they are whole key
    heads' (a cell's key block is then whole key heads), else one."""
    return HEADS if hv % HEADS == 0 and HEADS % grp == 0 else 1


def fits(t, key_dim, value_dim):
    """Whether the kernels take a sequence of ``t`` tokens at these head
    sizes: whole chunks, heads of whole lanes."""
    return t % CHUNK == 0 and key_dim % _LANES == 0 \
        and value_dim % _LANES == 0


def _params(interpret):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))}


def _rows(x):
    """(B, T, H) -> (B H, chunks, CHUNK): a head's numbers, a chunk a
    row."""
    b, t, h = x.shape
    return x.transpose(0, 2, 1).reshape(b * h, t // CHUNK, CHUNK)


def _unrows(x, b):
    bh, nc, c = x.shape
    return x.reshape(b, bh // b, nc * c).transpose(0, 2, 1)


def _specs(hv, grp, nc, dk, dv, heads, reverse=False):
    """BlockSpecs over the grid (B x value heads / ``heads``, chunks): a
    chunk of the key heads' q or k that ``heads`` value heads read, of
    those value heads' v (or o, dq ...), their rows, their states before
    the chunk."""
    per = hv // heads                  # grid cells a sequence
    kh = max(heads // grp, 1)          # key heads a key block

    def chunk(i):
        return nc - 1 - i if reverse else i

    # a block index counts blocks: the cell's first key head over kh
    key = pl.BlockSpec((1, CHUNK, kh * dk), lambda h, i: (
        h // per, chunk(i), (h % per) * heads // grp // kh))
    val = pl.BlockSpec((1, CHUNK, heads * dv), lambda h, i: (
        h // per, chunk(i), h % per))
    per_value_key = pl.BlockSpec((1, CHUNK, heads * dk), lambda h, i: (
        h // per, chunk(i), h % per))
    rows = pl.BlockSpec((heads, nc, CHUNK), lambda h, i: (h, 0, 0))
    state = pl.BlockSpec((heads, 1, dk, dv), lambda h, i: (h, chunk(i), 0,
                                                            0))
    return key, val, per_value_key, rows, state


def _key_cols(j, grp, heads, dk):
    """Columns of value head j (of the cell's ``heads``) in the cell's key
    block."""
    if heads >= grp:
        return slice(j // grp * dk, (j // grp + 1) * dk)
    return slice(0, dk)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *refs, grp):
    """A chunk of the cell's heads: o, and where ``refs`` holds an output
    before the state's scratch, the state before the chunk."""
    i = pl.program_id(1)
    s_ref = refs[-1]
    heads, dk, dv = s_ref.shape

    @pl.when(i == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    for j in range(heads):   # independent heads: their chains interleave
        kc, vc = _key_cols(j, grp, heads, dk), slice(j * dv, (j + 1) * dv)
        s = s_ref[j]
        if len(refs) == 2:
            refs[0][j, 0] = s
        o, s_ref[j] = chunk_forward(
            q_ref[0, :, kc], k_ref[0, :, kc], v_ref[0, :, vc],
            g_ref[j, pl.ds(i, 1), :], b_ref[j, pl.ds(i, 1), :], s)
        o_ref[0, :, vc] = o.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, st_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, db_ref, ds_ref, *, grp):
    i = pl.program_id(1)
    c = pl.num_programs(1) - 1 - i
    heads, dk, dv = ds_ref.shape

    @pl.when(i == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    sums = {}   # a key head's dq and dk, summed over its value heads
    for j in range(heads):
        kc, vc = _key_cols(j, grp, heads, dk), slice(j * dv, (j + 1) * dv)
        dq, dk_, dv_, dgam, dbeta, ds_ref[j] = chunk_backward(
            q_ref[0, :, kc], k_ref[0, :, kc], v_ref[0, :, vc],
            g_ref[j, pl.ds(c, 1), :], b_ref[j, pl.ds(c, 1), :], st_ref[j, 0],
            do_ref[0, :, vc], ds_ref[j])
        col = kc.start if heads >= grp else j * dk
        if col in sums:
            dq, dk_ = dq + sums[col][0], dk_ + sums[col][1]
        sums[col] = dq, dk_
        dv_ref[0, :, vc] = dv_.astype(dv_ref.dtype)
        dg_ref[j, pl.ds(c, 1), :] = dgam
        db_ref[j, pl.ds(c, 1), :] = dbeta
    for col, (dq, dk_) in sums.items():
        dq_ref[0, :, col:col + dk] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, col:col + dk] = dk_.astype(dk_ref.dtype)


def _sizes(q, v, hk, hv):
    b, t, _ = q.shape
    return b, t, q.shape[-1] // hk, v.shape[-1] // hv, t // CHUNK, hv // hk


def _walk(q, k, v, gam, beta, hk, hv, interpret, states):
    """The forward walk: o (B, T, hv dv); with ``states`` (o, the state
    before every chunk (B hv, chunks, dk, dv) float32)."""
    b, t, dk, dv, nc, grp = _sizes(q, v, hk, hv)
    heads = _heads(hv, grp)
    key, val, _, rows, state = _specs(hv, grp, nc, dk, dv, heads)
    flops = b * hv * t * 2 * (5 * CHUNK * dk + 3 * dk * dv + CHUNK * CHUNK)
    out_specs = [val]
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    if states:
        out_specs.append(state)
        out_shape.append(jax.ShapeDtypeStruct((b * hv, nc, dk, dv), _F32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, grp=grp),
        grid=(b * hv // heads, nc),
        in_specs=[key, key, val, rows, rows],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), _F32)],
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=b * hv * t * (CHUNK + 2),
            bytes_accessed=b * t * (grp * 2 * hk * dk + 2 * hv * dv)
            * q.dtype.itemsize + (b * hv * nc * dk * dv * 4 if states
                                  else 0)),
        name="gated_delta_fwd",
        interpret=interpret,
        **_params(interpret),
    )(q, k, v, _rows(gam), _rows(beta))
    return tuple(out) if states else out[0]


def _backward(q, k, v, gam, beta, st, do, hk, hv, interpret):
    """The reverse walk over the states ``st`` the forward kept."""
    b, t, dk, dv, nc, grp = _sizes(q, v, hk, hv)
    heads = _heads(hv, grp)
    key, val, per_value_key, rows, state = _specs(hv, grp, nc, dk, dv, heads,
                                                  reverse=True)
    # where a cell holds whole key heads it sums their value heads' dq and
    # dk itself; else each value head's are written and summed after
    whole = heads >= grp
    dq_spec = key if whole else per_value_key
    dq_shape = jax.ShapeDtypeStruct((b, t, (hk if whole else hv) * dk),
                                    q.dtype)
    dq, dk_, dv_, dgam, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, grp=grp),
        grid=(b * hv // heads, nc),
        in_specs=[key, key, val, rows, rows, state, val],
        out_specs=[dq_spec, dq_spec, val, rows, rows],
        out_shape=[dq_shape, dq_shape,
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b * hv, nc, CHUNK), _F32),
                   jax.ShapeDtypeStruct((b * hv, nc, CHUNK), _F32)],
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), _F32)],
        cost_estimate=pl.CostEstimate(
            flops=b * hv * t * 4 * (5 * CHUNK * dk + 3 * dk * dv
                                    + CHUNK * CHUNK),
            transcendentals=b * hv * t * (CHUNK + 2),
            bytes_accessed=b * t * hv * (4 * dk + 4 * dv) * q.dtype.itemsize
            + b * hv * nc * dk * dv * 4),
        name="gated_delta_bwd",
        interpret=interpret,
        **_params(interpret),
    )(q, k, v, _rows(gam), _rows(beta), st, do)

    def by_key_head(x):   # the value heads of a key head summed, float32
        if whole:
            return x
        return x.reshape(b, t, hk, grp, dk).astype(_F32).sum(3).reshape(
            b, t, hk * dk).astype(q.dtype)

    return (by_key_head(dq), by_key_head(dk_), dv_, _unrows(dgam, b),
            _unrows(dbeta, b))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def gated_delta(q, k, v, gam, beta, key_heads, value_heads, interpret=False):
    """The rule over whole chunks: q, k (B, T, key_heads dk), v (B, T,
    value_heads dv), ``gam`` (B, T, value_heads) the cumulative sums of g
    within each chunk of ``CHUNK`` tokens and beta, float32 -> o (B, T,
    value_heads dv) in v's dtype."""
    return _walk(q, k, v, gam, beta, key_heads, value_heads, interpret,
                 False)


def _gated_delta_fwd(q, k, v, gam, beta, key_heads, value_heads, interpret):
    o, st = _walk(q, k, v, gam, beta, key_heads, value_heads, interpret,
                  True)
    return o, (q, k, v, gam, beta, st)


def _gated_delta_bwd(key_heads, value_heads, interpret, res, do):
    return _backward(*res, do, key_heads, value_heads, interpret)


gated_delta.defvjp(_gated_delta_fwd, _gated_delta_bwd)
