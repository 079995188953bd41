"""Gated short convolution: the sequence mixer of a hybrid conv + attention
decoder (LiquidAI's LFM2 family, ``model_type`` ``lfm2_moe``).

    (B, C, X) = split3(W_in u)          W_in: d -> 3d, in that order
    a = B * X
    c_t = sum_j k[:, j] * a_(t - (L-1) + j)    depthwise, causal, zeros
                                               before the sequence's start
    y = W_out (C * c)                   W_out: d -> d

No bias, no activation, no position encoding; ``k`` is (d, L), L taps a
channel (``conv_L_cache``; 3 in the published models), the LAST tap on the
token itself.

Between the two projections everything is elementwise but for the shifts
along the sequence. The mathematics is written ONCE, plainly, in
``_gated_conv`` below (XLA's fusions, autodiff's backward): the CPU's
tests, odd shapes and a program under a mesh run it. The shifts are pads of
the sequence axis with a negative edge, which XLA fuses into the pass that
reads them. On the TPU it is NOT one pass each way as XLA builds it: what
has three shifted readers (``a``; in the backward ``dy * C`` too) is stored
once and read back, so the forward is two passes and the backward three,
1.8 ms a layer at the benchmark cell's size where the bytes that must move
take 0.90 (PERF.md, Findings, PR 35). So where the sequence is whole row
tiles and the width whole lanes the TPU takes the repo's own Pallas kernels
(``ops/pallas/short_conv.py``, tested against ``_gated_conv``):
``short_conv_fwd`` reads the projection's result once and writes the gated
result, ``short_conv_bwd`` reads it and the incoming gradient once and
writes the three gradients, 1.13 ms a layer, and keeps 0.27 GB less of the
step's temporaries. Which of the two follows from what the code can observe
(``conv_path``; the layer's ``note_built`` record says it), with no option
to choose it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import defop, get_op, note_built


_F32 = jnp.float32


def _shift(x, n):
    """x (B, T, d) moved ``n`` tokens later (n > 0) or earlier (n < 0)
    along the sequence, zeros coming in: one ``pad`` with a negative edge,
    which XLA fuses into the pass that reads it."""
    if n == 0:
        return x
    edges = [(0, 0, 0), (n, -n, 0), (0, 0, 0)]
    return jax.lax.pad(x, jnp.zeros((), x.dtype), edges)


def _taps(a, k, sign):
    """sum_j k[:, j] * a shifted by ``sign * (L - 1 - j)``: the causal
    convolution (sign +1) or its transpose, which looks ahead (-1). ``a``
    in its stored dtype, the sum float32."""
    taps = k.shape[1]
    out = None
    for j in range(taps):
        term = (_shift(a, sign * (taps - 1 - j)).astype(_F32)
                * k[:, j].astype(_F32))
        out = term if out is None else out + term
    return out


def _gate(b, x):
    """a = B * X, multiplied in float32 and stored in the projection's
    dtype: XLA keeps ``a`` as an array of its own whatever is written here
    (three shifted readers; it will not compute it three times), so the
    one rounding is where it is stored. Everything else widens INSIDE the
    pass that reads it: widened before the split, the projection's whole
    result was written in float32 (0.4 GB a layer at the benchmark cell's
    size, and 0.13 GB for ``a``; the compiler's listing for a described
    v5e, PERF.md, Findings, PR 35)."""
    return (b.astype(_F32) * x.astype(_F32)).astype(b.dtype)


def _gated_conv(u, k):
    """u (B, T, 3d) = (B | C | X) and k (d, L) -> C * conv(B * X), (B, T,
    d) in u's dtype."""
    b, c, x = jnp.split(u, 3, axis=-1)
    return (c.astype(_F32) * _taps(_gate(b, x), k, 1)).astype(u.dtype)


def gated_conv(u, k, path):
    """``_gated_conv`` by the implementation ``conv_path`` gave."""
    if path == "pallas":
        from .pallas import short_conv
        return short_conv.gated_conv(u, k)
    return _gated_conv(u, k)


def conv_path(shape, taps):
    """Which implementation the elementwise part of a layer over data of
    ``shape`` (B, T, d) takes: the repo's own kernels (``"pallas"``) on the
    TPU where the sequence is whole row tiles, the width whole lanes and
    no mesh partitions the program (the partitioner cannot split a Mosaic
    call); XLA's fusions (``"xla"``) elsewhere. One algorithm, chosen from
    what the code can observe, as ``flash_attention`` and ``ExpertFFN``
    choose."""
    from ..parallel.mesh import partition_mesh
    from .pallas import on_tpu, short_conv
    if (on_tpu() and len(shape) == 3 and partition_mesh() is None
            and short_conv.fits(shape[1], shape[2], taps)):
        return "pallas"
    return "xla"


@defop(
    "ShortConv",
    arg_names=("data", "in_weight", "conv_weight", "out_weight"),
    param_spec={"kernel": 3},
)
def _short_conv(attrs, data, in_weight, conv_weight, out_weight):
    """Gated short convolution on (B, T, d): ``out_weight (C * conv(B *
    X))`` with ``(B, C, X) = split3(in_weight data)``; ``in_weight`` (3d,
    d), ``conv_weight`` (d, ``kernel``) with its last tap on the token
    itself, ``out_weight`` (d, d). Causal within each sequence of the
    batch, no bias."""
    d = data.shape[-1]
    taps = int(attrs["kernel"])
    if in_weight.shape != (3 * d, d) or conv_weight.shape != (d, taps) \
            or out_weight.shape != (d, d):
        raise ValueError(
            "ShortConv: data of width %d wants in_weight (%d, %d), "
            "conv_weight (%d, %d), out_weight (%d, %d); got %s, %s, %s"
            % (d, 3 * d, d, d, taps, d, d, in_weight.shape,
               conv_weight.shape, out_weight.shape))
    path = conv_path(data.shape, taps)
    note_built({"op": "ShortConv", "kernel": taps, "path": path})
    u = jnp.dot(data, in_weight.T)
    return jnp.dot(gated_conv(u, conv_weight, path), out_weight.T)


def _short_conv_infer(attrs, shapes):
    data = shapes[0]
    if data is None:
        return shapes
    d = data[-1]
    shapes[1] = shapes[1] or (3 * d, d)
    shapes[2] = shapes[2] or (d, int(attrs["kernel"]))
    shapes[3] = shapes[3] or (d, d)
    return shapes


get_op("ShortConv").infer_params = _short_conv_infer
