"""Grouped matrix products — Pallas TPU kernels for the expert layer.

Rows ``(R, k)`` sorted into ``E`` groups, each group multiplied by its own
matrix: the three products of an expert feed-forward (``ops/moe.py``) and
their transposes. The TPU compiler builds ``jax.lax.ragged_dot`` as a
Mosaic kernel over 512 x 512 x 256 tiles, which fetches a group's matrix
again for every row tile and the rows again for every column tile: 170
FLOP a byte where the v5e's ridge is 240, 45-52% of the MXU at the expert
cell's shape, where these take 88% (gmm) and 79% (tgmm) (PERF.md,
Findings, PR 34). An expert's whole matrix is a few MB, so these kernels
keep it in VMEM while the group's row tiles pass:

- ``gmm``: a grid over VISITS. A visit is one (row tile, group) pair; a
  row tile that a group boundary cuts is visited once for each group it
  holds, and each visit stores only its group's rows. The matrix block's
  index is the visit's group, so Pallas fetches it when the group changes
  and not before: E fetches a product. The matrix is read in the layout
  the caller holds it: ``(E, n, k)`` (contracted over its last dim, the
  forward of a weight stored (out, in)) or ``(E, k, n)`` (``transposed``
  False: the same weight read for dX).
- ``tgmm``: ``a[rows of g]^T b[rows of g]`` for every group -> ``(E, ka,
  nb)``, the weight's gradient: a float32 block accumulates over its
  group's visits and is written when the group ends. The output is tiled
  so the accumulator fits; the operand on the narrower side is read again
  for each tile of the wider.

The grid is STATIC: ``R / tm + E - 1`` visits whatever the sizes (the most
that E groups can cut R / tm tiles into, every empty group visited once so
that its matrix is fetched and its gradient block zeroed like any other).
Visits that the sizes leave over repeat the last tile with no row of
theirs: they multiply and store nothing. Every visit does the same work, so
a product's time does not follow its group sizes, which is what the expert
cell asks of its step (PERF.md, Findings, PR 33).

``sizes`` must add up to R: the caller stretches its last group over rows
no assignment took (they are zeros). Rows past the sum would be left
unwritten.

Everything lives in the 16 MiB of VMEM a kernel gets unasked
(``flash_attention._SCOPED_VMEM``: a ``vmem_limit_bytes`` on a Mosaic call
changes how XLA builds other fusions of the step, PR 32). A matrix that
does not stand there twice beside the tiles (2048 x 1536 in bfloat16: 6.3
MB, 18.5 MiB in all) is taken in column blocks: ``gmm`` computes its
result's columns in two halves where both fit (``gmm_column_blocks``; a
second walk over the rows), ``tgmm`` halves its output block
(``tgmm_wide``). Shapes that fit whole are built as PR 34 built them.
``fits`` counts the bytes from the shapes and the caller takes
``jax.lax.ragged_dot`` where neither fits: no other blocking has been
timed against it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _SCOPED_VMEM

# Rows of a visit (both kernels: one set of visits serves a layer's nine
# products), the columns of gmm's result computed at a time, and the most
# columns of tgmm's output block. PR-34 sweep on a v5e at the expert cell's
# shapes (49152 rows, 16 groups, 2560 x 768, bfloat16; ms a call alone, the
# visits' arithmetic included, which a step shares between nine products:
# the compiler's ragged_dot 1.97-2.18 forward and dX, 2.29 dW): gmm is flat,
# 128 rows 1.24-1.26, 256 1.21-1.24, 512 1.27 where it fits (more rows a
# weight tile and fewer grid steps, but E - 1 cut tiles are a larger share
# of fewer tiles); columns at a time 128, 256, 768 within 0.3%; a plain
# store on tiles no boundary cuts 0.3% SLOWER; the matrix in one buffer
# (pl.Buffered(1), which would fit 512 rows) 1.29-1.35: the fetch of the
# next group's matrix then waits for the last visit of this one. tgmm: 256
# rows 1.35-1.37, 512 1.37-1.38, output blocks 640 wide 1.49, 2560 (whole)
# does not fit.
ROWS = 256
GMM_CHUNK = 256
TGMM_WIDE = 1280
# the most column blocks `gmm` takes a matrix in, and the narrowest output
# block of `tgmm`: what the chip has timed against the compiler's own kernel
# (2048 x 1536 in bfloat16: 2.34-2.36 and 2.64 ms against 2.94-3.26,
# PERF.md, PR 35). Past them nothing is measured, and the caller takes
# `ragged_dot` as before
MAX_COLUMN_BLOCKS = 2
TGMM_NARROW = 640
# what the kernels' own temporaries took beside the pipeline's buffers as
# the compiler counted them for a described v5e (gmm 2.97-3.08 MiB at 512
# rows; tgmm 1.5-1.9 MiB and the copies counted in `tgmm_vmem_bytes`)
_TEMPORARIES = 3 * 2 ** 20
_LANES = 128
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def group_visits(sizes, rows, tm):
    """The visits of ``rows`` (a multiple of ``tm``) sorted rows cut into
    groups of ``sizes`` (E,) int32, which add up to ``rows``: int32 arrays
    ``(group, tile, lo, hi)``, each of the static length ``rows / tm + E -
    1``. Visit v works on row tile ``tile[v]`` for group ``group[v]``,
    whose rows are ``lo[v] <= r < hi[v]``; both run upwards. An empty
    group has one visit (no rows); visits left over are the last tile's
    and the last group's, with no rows."""
    e = sizes.shape[0]
    tiles = rows // tm
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tm, tiles - 1)
    count = jnp.where(sizes > 0, (ends + tm - 1) // tm - starts // tm, 1)
    upto = jnp.cumsum(count)
    v = jnp.arange(tiles + e - 1, dtype=jnp.int32)
    g = jnp.minimum(jnp.searchsorted(upto, v, side="right"), e - 1)
    g = g.astype(jnp.int32)
    real = v < upto[-1]
    tile = jnp.where(real, first[g] + v - (upto[g] - count[g]), tiles - 1)
    lo = jnp.where(real, starts[g], 0)
    hi = jnp.where(real, ends[g], 0)
    return g, tile.astype(jnp.int32), lo.astype(jnp.int32), \
        hi.astype(jnp.int32)


def _mine(tile_ref, lo_ref, hi_ref, v, tm):
    """(tm, 1) mask: the rows of visit v's tile that are its group's."""
    r = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (r >= lo_ref[v]) & (r < hi_ref[v])


def _gmm_kernel(group_ref, tile_ref, lo_ref, hi_ref, x_ref, w_ref, o_ref, *,
                chunk, transposed, visit_axis=0):
    del group_ref  # the index maps' only
    tm, n = o_ref.shape
    mine = _mine(tile_ref, lo_ref, hi_ref, pl.program_id(visit_axis), tm)
    for j in range(0, n, chunk):
        w = w_ref[j:j + chunk, :] if transposed else w_ref[:, j:j + chunk]
        y = jax.lax.dot_general(x_ref[...], w, _NT if transposed else _NN,
                                preferred_element_type=jnp.float32)
        # the rows of other groups keep what their own visit stored, or
        # will store: every row is some visit's
        o_ref[:, j:j + chunk] = jnp.where(
            mine, y, o_ref[:, j:j + chunk].astype(jnp.float32)
        ).astype(o_ref.dtype)


def gmm_vmem_bytes(tm, k, n, itemsize):
    """What ``gmm`` holds in VMEM, from the shapes alone: the matrix, a
    row tile and a result tile twice each (the pipeline's two buffers),
    and the kernel's temporaries. ``n``: the result's columns computed a
    grid step, the whole width or a column block of it."""
    return 2 * (k * n + tm * k + tm * n) * itemsize + _TEMPORARIES


def gmm_column_blocks(tm, k, n, itemsize):
    """In how many column blocks ``gmm`` computes a result of ``n``
    columns so that a block of the matrix fits VMEM twice beside the
    tiles: 1 wherever the whole matrix does (the kernel PR 34 measured),
    else ``MAX_COLUMN_BLOCKS`` = 2 halves of whole lanes (PR 35 measured)
    where those do; 0 where they do not. A block more is one more pass
    over the rows, for a matrix that still crosses HBM once a group and
    block."""
    for parts in range(1, MAX_COLUMN_BLOCKS + 1):
        if n % (parts * _LANES) == 0 and gmm_vmem_bytes(
                tm, k, n // parts, itemsize) <= _SCOPED_VMEM:
            return parts
    return 0


def _params(interpret, semantics):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics)}


@functools.partial(jax.jit, static_argnames=("transposed", "tm", "chunk",
                                             "interpret"))
def gmm(x, w, sizes, transposed=True, tm=ROWS, chunk=GMM_CHUNK,
        interpret=False):
    """x (R, k), rows grouped by ``sizes`` (E,), by w (E, n, k)
    (``transposed``, the default: contracted over its last dim) or (E, k,
    n): (R, n) in x's dtype, accumulated in float32."""
    rows, k = x.shape
    n = w.shape[1] if transposed else w.shape[2]
    if w.shape[2 if transposed else 1] != k or rows % tm:
        raise ValueError("gmm: x %s by w %s (transposed=%s), row tile %d"
                         % (x.shape, w.shape, transposed, tm))
    visits = group_visits(sizes, rows, tm)
    e = w.shape[0]
    # a matrix too large to stand in VMEM twice goes in `parts` column
    # blocks, an outer grid dimension, each a walk over all the visits; one
    # block is the grid of visits alone, as PR 34 built it
    parts = gmm_column_blocks(tm, k, n, x.dtype.itemsize) or 1
    blocked = parts > 1
    nc = n // parts
    chunk = min(chunk, nc)

    def at(ids):
        """(column block, visit, group of, tile of) from an index map's
        arguments: the grid's indices, then the four prefetched arrays."""
        return (ids[0] if blocked else 0), ids[-5], ids[-4], ids[-3]

    def rows_of(*ids):
        _, v, _, t = at(ids)
        return t[v], 0

    def matrix_of(*ids):
        c, v, g, _ = at(ids)
        return (g[v], c, 0) if transposed else (g[v], 0, c)

    def result_of(*ids):
        c, v, _, t = at(ids)
        return t[v], c

    visit_grid = (rows // tm + e - 1,)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, chunk=chunk, transposed=transposed,
                          visit_axis=int(blocked)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(parts,) + visit_grid if blocked else visit_grid,
            in_specs=[
                pl.BlockSpec((tm, k), rows_of),
                pl.BlockSpec((None, nc, k) if transposed else (None, k, nc),
                             matrix_of),
            ],
            out_specs=pl.BlockSpec((tm, nc), result_of),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=(x.size * parts + w.size + rows * n)
            * x.dtype.itemsize),
        name="expert_gmm",
        metadata={"ragged_dot_tiling": "%d,%d,%d" % (tm, k, chunk)},
        interpret=interpret,
        **_params(interpret, ("arbitrary",) * (1 + blocked)),
    )(*visits, x, w)


def _tgmm_kernel(group_ref, tile_ref, lo_ref, hi_ref, a_ref, b_ref, o_ref,
                 acc_ref, *, mask_a):
    v = pl.program_id(2)
    last = pl.num_programs(2) - 1
    g = group_ref[v]

    @pl.when((v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != g))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    mine = _mine(tile_ref, lo_ref, hi_ref, v, a_ref.shape[0])
    a, b = a_ref[...], b_ref[...]
    # the narrower side is masked: fewer selects for the same product
    if mask_a:
        a = jnp.where(mine, a, jnp.zeros_like(a))
    else:
        b = jnp.where(mine, b, jnp.zeros_like(b))
    acc_ref[...] += jax.lax.dot_general(a, b, _TN,
                                        preferred_element_type=jnp.float32)

    @pl.when((v == last) | (group_ref[jnp.minimum(v + 1, last)] != g))
    def _write():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _tgmm_blocks(ka, nb, wide=TGMM_WIDE):
    """The output block of ``tgmm``: the wider of the two sides is cut to
    at most ``wide`` columns (a divisor that is a multiple of 128 lanes),
    the narrower stays whole."""
    def cut(width):
        if width <= wide:
            return width
        for parts in range(2, width // _LANES + 1):
            if width % (parts * _LANES) == 0 and width // parts <= wide:
                return width // parts
        return _LANES

    return (cut(ka), nb) if ka > nb else (ka, cut(nb))


def tgmm_wide(tm, ka, nb, itemsize, out_itemsize):
    """The most columns of ``tgmm``'s output block: ``TGMM_WIDE`` wherever
    that fits VMEM (the kernel PR 34 measured), else its half,
    ``TGMM_NARROW`` (PR 35 measured); 0 where that does not."""
    wide = TGMM_WIDE
    while wide >= TGMM_NARROW:
        if tgmm_vmem_bytes(tm, ka, nb, itemsize, out_itemsize,
                           wide) <= _SCOPED_VMEM:
            return wide
        wide //= 2
    return 0


def tgmm_vmem_bytes(tm, ka, nb, itemsize, out_itemsize, wide=TGMM_WIDE):
    """What ``tgmm`` holds in VMEM: both row tiles and the output block
    twice, the float32 accumulator, a's tile transposed (masked on the
    way where a is the narrower side), b's tile masked where b is, and
    the kernel's temporaries."""
    ta, tb = _tgmm_blocks(ka, nb, wide)
    return (2 * tm * (ta + tb) * itemsize + 2 * ta * tb * out_itemsize
            + ta * tb * 4 + tm * ta * itemsize
            + (tm * tb * itemsize if tb < ta else 0) + _TEMPORARIES)


@functools.partial(jax.jit, static_argnames=("tm", "wide", "out_dtype",
                                             "interpret"))
def tgmm(a, b, sizes, tm=ROWS, wide=None, out_dtype=None,
         interpret=False):
    """a (R, ka) and b (R, nb), rows grouped by ``sizes`` (E,): (E, ka,
    nb), group g's block ``a[its rows]^T b[its rows]`` (zeros for an empty
    group), accumulated in float32, in ``out_dtype`` (a's)."""
    rows, ka = a.shape
    nb = b.shape[1]
    if b.shape[0] != rows or rows % tm:
        raise ValueError("tgmm: a %s, b %s, row tile %d"
                         % (a.shape, b.shape, tm))
    e = sizes.shape[0]
    out_dtype = jnp.dtype(out_dtype or a.dtype)
    if wide is None:
        wide = tgmm_wide(tm, ka, nb, a.dtype.itemsize,
                         out_dtype.itemsize) or TGMM_WIDE
    ta, tb = _tgmm_blocks(ka, nb, wide)
    visits = group_visits(sizes, rows, tm)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, mask_a=ta <= tb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(ka // ta, nb // tb, rows // tm + e - 1),
            in_specs=[
                pl.BlockSpec((tm, ta),
                             lambda i, j, v, g, t, lo, hi: (t[v], i)),
                pl.BlockSpec((tm, tb),
                             lambda i, j, v, g, t, lo, hi: (t[v], j)),
            ],
            out_specs=pl.BlockSpec(
                (None, ta, tb), lambda i, j, v, g, t, lo, hi: (g[v], i, j)),
            scratch_shapes=[pltpu.VMEM((ta, tb), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((e, ka, nb), out_dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * ka * nb, transcendentals=0,
            bytes_accessed=(a.size * (nb // tb) + b.size * (ka // ta))
            * a.dtype.itemsize + e * ka * nb * out_dtype.itemsize),
        name="expert_tgmm",
        metadata={"ragged_dot_tiling": "%d,%d,%d" % (tm, ta, tb)},
        interpret=interpret,
        **_params(interpret, ("parallel", "parallel", "arbitrary")),
    )(*visits, a, b)


def fits(rows, k, n, itemsize):
    """Whether the kernels of a product of (rows, k) rows by (E, n, k)
    matrices (forward, dX, dW) take their tiles inside a kernel's VMEM:
    the shape half of the gate (``ops/moe.py`` asks ``on_tpu`` for the
    other). Widths are whole lanes, rows whole tiles."""
    return (k % _LANES == 0 and n % _LANES == 0 and rows % ROWS == 0
            and gmm_column_blocks(ROWS, k, n, itemsize) > 0    # forward
            and gmm_column_blocks(ROWS, n, k, itemsize) > 0    # dX
            and tgmm_wide(ROWS, n, k, itemsize, itemsize) > 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(x, w, sizes, interpret=False):
    """x (R, k), rows grouped by ``sizes``, by w (E, n, k) -> (R, n), with
    its own backward: dX is ``gmm`` over the same matrices read the other
    way, dW is ``tgmm``."""
    return gmm(x, w, sizes, interpret=interpret)


def _grouped_fwd(x, w, sizes, interpret):
    return gmm(x, w, sizes, interpret=interpret), (x, w, sizes)


def _grouped_bwd(interpret, res, dy):
    x, w, sizes = res
    dx = gmm(dy, w, sizes, transposed=False, interpret=interpret)
    dw = tgmm(dy, x, sizes, out_dtype=w.dtype, interpret=interpret)
    return dx, dw, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)
