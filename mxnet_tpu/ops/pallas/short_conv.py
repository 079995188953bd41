"""Gated short convolution — Pallas TPU kernels for the elementwise part of
``ShortConv`` (``ops/shortconv.py``): between the op's two projections,

    y = C * conv(B * X),      conv_t = sum_j k[:, j] * a_(t - (L-1) + j)

over ``u = (B | C | X)`` (batch, T, 3d) and taps ``k`` (d, L). XLA builds
this as two passes forward and three backward, because what has L shifted
readers (``a = B * X``; in the backward ``dy * C`` too) is stored and read
back (PERF.md, Findings, PR 35). Here each direction is ONE pass: a grid
cell owns ``ROWS`` tokens of one sequence at the full width, computes
``a`` in VMEM, and takes the L - 1 rows its taps reach outside the tile
from a HALO block, the sublane tile of 8 rows before the tile (forward, and
the backward's ``a``) or after it (the backward's look-ahead over ``dy *
C``), fetched through a second BlockSpec over the same array. Tiles are
independent of each other, so the grid is parallel and nothing is carried:

- ``short_conv_fwd``: reads u once, writes y.
- ``short_conv_bwd``: reads u and dy once, writes du (the three gradients
  side by side, as the in projection's backward wants them) and the taps'
  gradient as one partial row a tile, which the caller sums.

Everything widens to float32 inside; the taps are the last on the token
itself, zeros before a sequence's start (the first tile masks its halo) and
after its end. A shift along the sequence is a sublane rotation of the tile
(``pltpu.roll``) whose wrapped rows are replaced from the halo, eight rows
at a time, the only rows it can reach.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# tokens a grid step; columns worked on at a time inside it (what keeps the
# float32 temporaries of a step at 256 KB each); the halo's rows
ROWS = 128
CHUNK = 512
HALO = 8
_LANES = 128
_F32 = jnp.float32


def fits(t, d, taps):
    """Whether the kernels take a sequence of ``t`` tokens at width ``d``
    with ``taps`` taps: whole row tiles, whole lanes, no tap reaching past
    the halo, and the taps' gradient a row each of one sublane tile."""
    return (t % ROWS == 0 and d % _LANES == 0 and 1 <= taps <= HALO)


def _chunk(d):
    return next(c for c in (CHUNK, 256, _LANES) if d % c == 0)


def _parts(ref, c0, chunk, d, rows=slice(None)):
    """(B, C, X) columns [c0, c0 + chunk) of a (1, rows, 3d) block,
    float32."""
    return tuple(ref[0, rows, p * d + c0:p * d + c0 + chunk].astype(_F32)
                 for p in range(3))


def _back(a, before, k_ref, c0, chunk, taps, row8, weigh=None):
    """The causal taps over a tile ``a`` (rows, chunk) whose eight
    preceding rows are ``before``: (conv, its first eight rows done right).
    ``weigh`` (rows, chunk): also each tap's sum over the rows of ``weigh``
    times that tap's shifted ``a``, a list of (1, chunk) rows."""
    cols = slice(c0, c0 + chunk)
    last = k_ref[taps - 1:taps, cols]
    conv = last * a
    top = conv[0:HALO]
    sums = [None] * taps
    if weigh is not None:
        sums[taps - 1] = jnp.sum(weigh * a, axis=0, keepdims=True)
    for j in range(taps - 1):
        s = taps - 1 - j                         # this tap looks s rows back
        kj = k_ref[j:j + 1, cols]
        r = pltpu.roll(a, s, 0)                  # r[i] = a[i - s], wrapped
        rt = jnp.where(row8 < s, pltpu.roll(before, s, 0), r[0:HALO])
        conv = conv + kj * r
        top = top + kj * rt
        if weigh is not None:
            sums[j] = (jnp.sum(weigh * r, axis=0, keepdims=True)
                       + jnp.sum(weigh[0:HALO] * (rt - r[0:HALO]), axis=0,
                                 keepdims=True))
    return conv, top, sums


def _fwd_kernel(u_ref, before_ref, k_ref, y_ref, *, d, chunk, taps):
    first = pl.program_id(1) == 0
    row8 = jax.lax.broadcasted_iota(jnp.int32, (HALO, chunk), 0)
    for c0 in range(0, d, chunk):
        cols = slice(c0, c0 + chunk)
        b, c, x = _parts(u_ref, c0, chunk, d)
        bh, _, xh = _parts(before_ref, c0, chunk, d)
        before = jnp.where(first, 0.0, bh * xh)
        conv, top, _ = _back(b * x, before, k_ref, c0, chunk, taps, row8)
        y_ref[0, :, cols] = (c * conv).astype(y_ref.dtype)
        y_ref[0, 0:HALO, cols] = (c[0:HALO] * top).astype(y_ref.dtype)


def _bwd_kernel(u_ref, before_ref, after_ref, dy_ref, dy_after_ref, k_ref,
                du_ref, dk_ref, *, d, chunk, taps):
    t = pl.program_id(1)
    first, last = t == 0, t == pl.num_programs(1) - 1
    rows = dy_ref.shape[1]
    tail = slice(rows - HALO, rows)
    row8 = jax.lax.broadcasted_iota(jnp.int32, (HALO, chunk), 0)
    dk_ref[...] = jnp.zeros_like(dk_ref)
    for c0 in range(0, d, chunk):
        cols = slice(c0, c0 + chunk)
        b, c, x = _parts(u_ref, c0, chunk, d)
        bh, _, xh = _parts(before_ref, c0, chunk, d)
        before = jnp.where(first, 0.0, bh * xh)
        dy = dy_ref[0, :, cols].astype(_F32)
        dconv = dy * c                             # gradient of the conv
        after = jnp.where(
            last, 0.0, dy_after_ref[0, :, cols].astype(_F32)
            * after_ref[0, :, cols].astype(_F32))  # the next eight rows'
        conv, top, sums = _back(b * x, before, k_ref, c0, chunk, taps, row8,
                                weigh=dconv)
        for j in range(taps):
            dk_ref[0, 0, j:j + 1, cols] = sums[j]
        # the transposed taps look AHEAD: da_t = sum_j k_j dconv_(t + s_j)
        da = k_ref[taps - 1:taps, cols] * dconv
        bottom = da[tail]
        for j in range(taps - 1):
            s = taps - 1 - j
            kj = k_ref[j:j + 1, cols]
            f = pltpu.roll(dconv, rows - s, 0)     # f[i] = dconv[i + s]
            ft = jnp.where(row8 >= HALO - s, pltpu.roll(after, HALO - s, 0),
                           f[tail])
            da = da + kj * f
            bottom = bottom + kj * ft
        out = du_ref.dtype
        du_ref[0, :, cols] = (da * x).astype(out)
        du_ref[0, tail, cols] = (bottom * x[tail]).astype(out)
        mid = slice(d + c0, d + c0 + chunk)
        du_ref[0, :, mid] = (dy * conv).astype(out)
        du_ref[0, 0:HALO, mid] = (dy[0:HALO] * top).astype(out)
        end = slice(2 * d + c0, 2 * d + c0 + chunk)
        du_ref[0, :, end] = (da * b).astype(out)
        du_ref[0, tail, end] = (bottom * b[tail]).astype(out)


def _params(interpret):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"))}


def _specs(t, d):
    """(tile, the eight rows before it, the eight after it) for a (batch,
    T, width) array, by the width in blocks of ``d`` and the column block:
    ``make(width, column)``."""
    per = ROWS // HALO

    def make(width, column):
        return (
            pl.BlockSpec((1, ROWS, width), lambda b, i: (b, i, column)),
            pl.BlockSpec((1, HALO, width), lambda b, i: (
                b, jnp.maximum(i * per - 1, 0), column)),
            pl.BlockSpec((1, HALO, width), lambda b, i: (
                b, jnp.minimum((i + 1) * per, t // HALO - 1), column)))

    return make


def _forward(u, k, interpret):
    batch, t, d3 = u.shape
    d, taps = d3 // 3, k.shape[1]
    tile, before, _ = _specs(t, d)(d3, 0)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, chunk=_chunk(d), taps=taps),
        grid=(batch, t // ROWS),
        in_specs=[tile, before, pl.BlockSpec((taps, d), lambda b, i: (0, 0))],
        out_specs=pl.BlockSpec((1, ROWS, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, t, d), u.dtype),
        cost_estimate=pl.CostEstimate(
            flops=(2 * taps + 2) * batch * t * d, transcendentals=0,
            bytes_accessed=4 * batch * t * d * u.dtype.itemsize),
        name="short_conv_fwd",
        interpret=interpret,
        **_params(interpret),
    )(u, u, k.T.astype(_F32))


def _backward(u, k, dy, interpret):
    batch, t, d3 = u.shape
    d, taps = d3 // 3, k.shape[1]
    make = _specs(t, d)
    tile, before, _ = make(d3, 0)
    _, _, after_c = make(d, 1)                     # the C columns alone
    dy_tile, _, dy_after = make(d, 0)
    tiles = t // ROWS
    du, dk = pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, chunk=_chunk(d), taps=taps),
        grid=(batch, tiles),
        in_specs=[tile, before, after_c, dy_tile, dy_after,
                  pl.BlockSpec((taps, d), lambda b, i: (0, 0))],
        out_specs=[tile, pl.BlockSpec((1, 1, HALO, d),
                                      lambda b, i: (b, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct((batch, tiles, HALO, d), _F32)],
        cost_estimate=pl.CostEstimate(
            flops=(6 * taps + 6) * batch * t * d, transcendentals=0,
            bytes_accessed=7 * batch * t * d * u.dtype.itemsize),
        name="short_conv_bwd",
        interpret=interpret,
        **_params(interpret),
    )(u, u, u, dy, dy, k.T.astype(_F32))
    return du, jnp.sum(dk[:, :, :taps], axis=(0, 1)).T.astype(k.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def gated_conv(u, k, interpret=False):
    """u (batch, T, 3d) = (B | C | X) and k (d, L) -> C * conv(B * X),
    (batch, T, d) in u's dtype, with its one-pass backward."""
    return _forward(u, k, interpret)


def _gated_fwd(u, k, interpret):
    return _forward(u, k, interpret), (u, k)


def _gated_bwd(interpret, res, dy):
    u, k = res
    return _backward(u, k, dy, interpret)


gated_conv.defvjp(_gated_fwd, _gated_bwd)
