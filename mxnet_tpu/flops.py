"""Analytic FLOP accounting for symbolic graphs.

The reference publishes throughput (img/s) only; the north-star target for
this repo is stated as MFU (BASELINE.md), which needs a *defensible* FLOP
model. This module implements the standard accounting used by the scaling
literature:

- 1 MAC = 2 FLOPs,
- forward cost = sum over matmul-bearing ops (Convolution, Deconvolution,
  FullyConnected, dot, batch_dot, RNN); elementwise/norm/pool ops are
  excluded (they are bandwidth- not FLOP-bound and conventionally omitted
  — the same convention under which ResNet-50 is quoted at ~4.1 GFLOPs
  forward per 224x224 image),
- training step cost = 3x forward (backward does ~2x the forward matmul
  work: grad wrt inputs + grad wrt weights).

`count_flops(sym, **shapes)` walks the graph with inferred shapes
(symbol.get_internals + infer_shape, the nnvm InferShape analogue) and
returns forward FLOPs. MFU = achieved FLOP/s / nominal peak FLOP/s of the
chip at the compute precision (chip_peak_flops).
"""
from __future__ import annotations

from typing import Dict, Tuple


def _prod(shape) -> int:
    out = 1
    for s in shape:
        out *= int(s)
    return out


# Nominal peak dense bf16 FLOP/s per chip, by jax device_kind. Public
# figures from the TPU product tables (per chip, not per core).
CHIP_PEAK_BF16 = {
    "TPU v2": 46e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,        # v5p
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # Trillium
    "TPU v6e": 918e12,
    "TPU7x": 2307e12,
}


#: peak multiplier vs bf16 by compute precision: the MXU runs 8-bit
#: operands (int8, fp8) at double rate, so an MFU quoted against the
#: bf16 peak would flatter quantized kernels by 2x.
PRECISION_PEAK_MULT = {"bf16": 1.0, "float32": 1.0, "f32": 1.0,
                       "int8": 2.0, "fp8": 2.0, "fp8_e4m3": 2.0}


def chip_peak_flops(device=None, precision: str = "bf16"
                    ) -> Tuple[float, str]:
    """(nominal peak FLOP/s at ``precision``, device_kind) for a jax
    device. ``precision`` int8/fp8 doubles the bf16 figure (the MXU's
    double-rate 8-bit path) — quantized-matmul MFU must be quoted
    against THIS peak, not the bf16 one, to stay honest.

    Raises ValueError for a device_kind the table does not hold: a
    utilization against a guessed peak is worse than none. A caller that
    runs on the CPU on purpose (a CI smoke) reports throughput and does
    not ask.
    """
    import jax

    mult = PRECISION_PEAK_MULT.get(str(precision).lower())
    if mult is None:
        raise ValueError("unknown compute precision %r (have %s)"
                         % (precision, sorted(PRECISION_PEAK_MULT)))
    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", str(device))
    if kind in CHIP_PEAK_BF16:
        return CHIP_PEAK_BF16[kind] * mult, kind
    # longest-prefix match on the device kind only ("TPU v5 lite core"
    # -> "TPU v5 lite", never "TPU v5 lite" -> the v5p "TPU v5" entry)
    best = ""
    for key in CHIP_PEAK_BF16:
        if kind.startswith(key) and len(key) > len(best):
            best = key
    if best:
        return CHIP_PEAK_BF16[best] * mult, kind
    raise ValueError(
        "no nominal peak for device_kind %r (platform %r); add it to "
        "flops.CHIP_PEAK_BF16 with its source (have %s)"
        % (kind, getattr(device, "platform", "?"), sorted(CHIP_PEAK_BF16)))


def count_flops(sym, **known_shapes) -> Dict[str, float]:
    """Forward-pass FLOPs of `sym` at the given input shapes.

    Returns {"total": fwd_flops, "<op_type>": flops_by_op_type...}.
    Counts 2*MACs for Convolution/Deconvolution/FullyConnected/dot/
    batch_dot/RNN; everything else contributes 0 (stated convention, see
    module docstring).
    """
    internals = sym.get_internals()
    _, out_shapes, _ = internals.infer_shape(**known_shapes)
    shape_of = {}
    for (node, idx), shp in zip(internals._entries, out_shapes):
        if shp is not None:
            shape_of[(id(node), idx)] = tuple(shp)

    by_type: Dict[str, float] = {}
    total = 0.0
    for node in sym._nodes():
        if node.is_var:
            continue
        opname = node.op.name
        in_shapes = [shape_of.get((id(c), i)) for c, i in node.inputs]
        out0 = shape_of.get((id(node), 0))
        f = _node_flops(opname, node.attrs, in_shapes, out0)
        if f:
            by_type[opname] = by_type.get(opname, 0.0) + f
            total += f
    by_type["total"] = total
    # low-precision share, separated so MFU can be quoted per precision:
    # 8-bit matmuls against the double-rate peak, the rest against bf16
    by_type["total_lowbit"] = by_type.get("QuantizedFullyConnected", 0.0)
    return by_type


def _node_flops(opname, attrs, in_shapes, out_shape) -> float:
    if out_shape is None:
        return 0.0
    if opname == "Convolution":
        # weight: (num_filter, C/groups, *kernel); every output element
        # accumulates prod(weight.shape[1:]) MACs.
        w = in_shapes[1]
        if w is None:
            return 0.0
        macs = _prod(out_shape) * _prod(w[1:])
        bias = 0 if str(attrs.get("no_bias", False)) in ("True", "true", "1") \
            else _prod(out_shape)
        return 2.0 * macs + bias
    if opname == "Deconvolution":
        # gradient-of-conv: every *input* element is multiplied into
        # prod(weight.shape[1:]) output taps.
        data, w = in_shapes[0], in_shapes[1]
        if data is None or w is None:
            return 0.0
        return 2.0 * _prod(data) * _prod(w[1:])
    if opname in ("FullyConnected", "QuantizedFullyConnected"):
        # QuantizedFullyConnected: identical MAC count at 8-bit operand
        # width — it lands in its own by_type bucket, and MFU for that
        # share must be quoted against chip_peak_flops(precision="int8")
        # (the double-rate peak), keeping quantized MFU honest.
        w = in_shapes[1]
        if w is None:
            return 0.0
        k = int(w[-1])
        macs = _prod(out_shape) * k
        bias = 0 if str(attrs.get("no_bias", False)) in ("True", "true", "1") \
            else _prod(out_shape)
        return 2.0 * macs + bias
    if opname in ("dot", "batch_dot"):
        a = in_shapes[0]
        if a is None:
            return 0.0
        ta = str(attrs.get("transpose_a", False)) in ("True", "true", "1")
        ka = int(a[-2]) if ta else int(a[-1])
        return 2.0 * _prod(out_shape) * ka
    if opname == "MultiHeadAttention":
        # two matmuls per head — scores (Tq·Tk·Dh) and weighted values —
        # = 4·N·H·Tq·Tk·Dh; causal counts the USEFUL (unmasked) half,
        # matching how the flash kernels skip it and how docs/perf.md
        # credits attention micros. Projections are separate FC nodes.
        q = in_shapes[0]
        k = in_shapes[1] if len(in_shapes) > 1 else None
        if q is None or k is None:
            return 0.0
        n, tq, dmq = int(q[0]), int(q[1]), int(q[2])
        tk = int(k[1])
        causal = str(attrs.get("causal", False)) in ("True", "true", "1")
        f = 4.0 * n * tq * tk * dmq  # H·Dh == dmq (query width)
        if causal:
            # useful (unmasked) count: query row i sees keys
            # [0, i + tk - tq], i.e. max(0, tk - tq + 1 + i) of them.
            # tq <= tk: every row sees >= 1 key, closed form
            # tq*(tk - (tq-1)/2); tq > tk: the first tq-tk rows see
            # nothing and the rest see 1..tk (clamping matters — the
            # unclamped form goes NEGATIVE). ~f/2 at tq == tk; > f/2
            # for cross-length causal (tq < tk with key offset).
            if tq <= tk:
                rows = tq * (tk - (tq - 1) / 2.0)
            else:
                rows = tk * (tk + 1) / 2.0
            return f * rows / (tq * tk)
        return f
    if opname == "RNN":
        # fused multi-layer RNN: dominated by 8 gate matmuls per LSTM step
        # (4 gates x {input, hidden}). Use weight blob size as MAC count
        # per timestep per batch row: total = 2 * T * N * prod(weights).
        data = in_shapes[0]
        w = in_shapes[1]
        if data is None or w is None:
            return 0.0
        t, n = int(data[0]), int(data[1])
        return 2.0 * t * n * _prod(w)
    return 0.0


def training_flops(fwd_flops: float) -> float:
    """Standard training-step accounting: backward = 2x forward matmul
    work, so one optimizer step = 3x forward FLOPs."""
    return 3.0 * fwd_flops
