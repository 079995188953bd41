"""Matrix / shape-manipulation / indexing / ordering operators.

TPU-native equivalents of src/operator/tensor/{matrix_op,dot,indexing_op,
ordering_op}.{cc,h} (SURVEY §2.1 #17). All static-shape by construction so
XLA can tile them onto the MXU/VPU; `dot` maps to lax.dot_general which is
the MXU primitive.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
from .registry import alias, current_node, defop, note_built


@defop("dot", arg_names=("lhs", "rhs"), param_spec={"transpose_a": False, "transpose_b": False})
def _dot(attrs, lhs, rhs):
    """Matrix product (reference: src/operator/tensor/dot.cc). For ndim>2 the
    reference contracts the last axis of lhs with the first of rhs; matmuls
    land on the MXU via lax.dot_general."""
    if attrs["transpose_a"]:
        lhs = jnp.moveaxis(lhs, 0, -1) if lhs.ndim > 2 else lhs.T
    if attrs["transpose_b"]:
        rhs = jnp.moveaxis(rhs, -1, 0) if rhs.ndim > 2 else rhs.T
    if lhs.ndim == 1 and rhs.ndim == 1:
        return jnp.dot(lhs, rhs)
    return jnp.tensordot(lhs, rhs, axes=([lhs.ndim - 1], [0]))


@defop(
    "batch_dot",
    arg_names=("lhs", "rhs"),
    param_spec={"transpose_a": False, "transpose_b": False},
)
def _batch_dot(attrs, lhs, rhs):
    """Batched matmul over leading axis (reference dot.cc batch_dot)."""
    if attrs["transpose_a"]:
        lhs = jnp.swapaxes(lhs, -1, -2)
    if attrs["transpose_b"]:
        rhs = jnp.swapaxes(rhs, -1, -2)
    return jnp.matmul(lhs, rhs)


def quantized_matmul(x, w, scale, act_dtype="int8"):
    """``x @ dequant(w).T`` with the dequantization fused into the GEMM.

    ``w``: (O, I) int8 or fp8-e4m3 per-channel-quantized weight,
    ``scale``: (O,) or (O, 1) f32 output-channel scales. Two execution
    strategies, picked by ``act_dtype``:

    - ``"int8"`` (int8 weights only): dynamic per-row symmetric
      activation quantization, then a native int8×int8 ``dot_general``
      with i32 accumulation — the MXU's double-rate int8 path (and the
      measured fast path on CPU VNNI); the two scales rescale the i32
      accumulator back to f32.
    - ``"bf16"`` / ``"float32"``: dequant-on-load — the weight is widened
      and scaled right at the GEMM input so XLA fuses the multiply into
      the matmul read; weight bytes in HBM stay 1/4 (or 1/2) of f32.
      fp8 weights always take this path.

    Returns f32, shape ``x.shape[:-1] + (O,)``.
    """
    scale = scale.reshape(-1)                     # (O,)
    out_shape = x.shape[:-1] + (w.shape[0],)
    x2 = x.reshape(-1, x.shape[-1])
    if w.dtype == jnp.int8 and act_dtype == "int8":
        amax = jnp.max(jnp.abs(x2), axis=1, keepdims=True)
        xs = jnp.maximum(amax, 1e-12).astype(jnp.float32) / 127.0
        xq = jnp.round(x2 / xs).astype(jnp.int8)
        acc = jax.lax.dot_general(
            xq, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)
        out = acc.astype(jnp.float32) * xs * scale[None, :]
    else:
        ct = jnp.bfloat16 if act_dtype == "bf16" else jnp.float32
        wf = w.astype(ct) * scale[:, None].astype(ct)
        out = jax.lax.dot_general(
            x2.astype(ct), wf, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    return out.reshape(out_shape)


@defop("transpose", arg_names=("data",), param_spec={"axes": ()})
def _transpose(attrs, data):
    axes = tuple(attrs["axes"]) or None
    return jnp.transpose(data, axes)


@defop("SwapAxis", arg_names=("data",), param_spec={"dim1": 0, "dim2": 0})
def _swapaxis(attrs, data):
    """Swap two axes (reference src/operator/swapaxis.cc)."""
    return jnp.swapaxes(data, int(attrs["dim1"]), int(attrs["dim2"]))


alias("SwapAxis", "swapaxes")


def _infer_reshape(data_shape, target):
    """Reference reshape semantics incl. special codes 0,-1,-2,-3,-4
    (src/operator/tensor/matrix_op.cc ReshapeShape)."""
    out = []
    src = list(data_shape)
    i = 0  # index into src
    t = list(target)
    j = 0
    while j < len(t):
        k = t[j]
        if k == 0:
            out.append(src[i]); i += 1
        elif k == -1:
            out.append(-1); i = min(i + 1, len(src))  # placeholder
        elif k == -2:
            out.extend(src[i:]); i = len(src)
        elif k == -3:
            out.append(src[i] * src[i + 1]); i += 2
        elif k == -4:
            a, b = t[j + 1], t[j + 2]
            if a == -1:
                a = src[i] // b
            if b == -1:
                b = src[i] // a
            out.extend([a, b]); i += 1; j += 2
        else:
            # an explicit dim consumes one source dim too (reference
            # ReshapeInferShape ++src_idx on positive dims) — without
            # this, a following -4/-3/0 splits the WRONG source dim
            out.append(int(k)); i = min(i + 1, len(src))
        j += 1
    if -1 in out:
        known = int(np.prod([d for d in out if d != -1])) or 1
        total = int(np.prod(data_shape)) if data_shape else 1
        out[out.index(-1)] = total // known
    return tuple(out)


@defop("Reshape", arg_names=("data",), param_spec={"shape": (), "reverse": False, "target_shape": (), "keep_highest": False})
def _reshape(attrs, data):
    """Reshape with the reference's 0/-1/-2/-3/-4 codes (matrix_op.cc).
    ``reverse=True`` matches the special codes from the RIGHT (reference
    ReshapeInferShape reverses src dims and target, then un-reverses)."""
    shape = tuple(attrs["shape"]) if attrs["shape"] else tuple(attrs["target_shape"])
    if attrs.get("reverse"):
        inferred = _infer_reshape(data.shape[::-1], shape[::-1])[::-1]
    else:
        inferred = _infer_reshape(data.shape, shape)
    return jnp.reshape(data, inferred)


alias("Reshape", "reshape")


@defop("Flatten", arg_names=("data",), param_spec={})
def _flatten(attrs, data):
    """Collapse all but the leading axis (reference matrix_op.cc Flatten)."""
    return jnp.reshape(data, (data.shape[0], -1))


alias("Flatten", "flatten")


@defop("expand_dims", arg_names=("data",), param_spec={"axis": 0})
def _expand_dims(attrs, data):
    return jnp.expand_dims(data, int(attrs["axis"]))


@defop("slice", arg_names=("data",), param_spec={"begin": (), "end": ()})
def _slice(attrs, data):
    """Static slice (reference matrix_op.cc slice / crop)."""
    begin, end = attrs["begin"], attrs["end"]
    idx = tuple(
        slice(None if b is None else int(b), None if e is None else int(e))
        for b, e in zip(begin, end)
    )
    return data[idx]


alias("slice", "crop")


@defop("slice_axis", arg_names=("data",), param_spec={"axis": 0, "begin": 0, "end": None})
def _slice_axis(attrs, data):
    ax = int(attrs["axis"]) % data.ndim
    begin = int(attrs["begin"])
    end = attrs["end"]
    end = data.shape[ax] if end is None else int(end)
    if end < 0:
        end += data.shape[ax]
    idx = [slice(None)] * data.ndim
    idx[ax] = slice(begin, end)
    return data[tuple(idx)]


@defop(
    "Concat",
    arg_names=(),
    variadic=True,
    param_spec={"num_args": 0, "dim": 1},
    py_name="concat",
)
def _concat(attrs, *inputs):
    """Concatenate along an axis (reference src/operator/concat.cc)."""
    return jnp.concatenate(inputs, axis=int(attrs["dim"]))


alias("Concat", "concat")


@defop(
    "SliceChannel",
    arg_names=("data",),
    param_spec={"num_outputs": 1, "axis": 1, "squeeze_axis": False},
    num_outputs=lambda attrs: int(attrs["num_outputs"]),
    py_name="split",
)
def _slice_channel(attrs, data):
    """Split along an axis into num_outputs parts (reference
    src/operator/slice_channel.cc)."""
    n = int(attrs["num_outputs"])
    ax = int(attrs["axis"])
    parts = jnp.split(data, n, axis=ax)
    if attrs["squeeze_axis"]:
        parts = [jnp.squeeze(p, axis=ax) for p in parts]
    return tuple(parts)


alias("SliceChannel", "split")


@defop("repeat", arg_names=("data",), param_spec={"repeats": 1, "axis": None})
def _repeat(attrs, data):
    ax = attrs["axis"]
    return jnp.repeat(data, int(attrs["repeats"]), axis=None if ax is None else int(ax))


@defop("tile", arg_names=("data",), param_spec={"reps": ()})
def _tile(attrs, data):
    return jnp.tile(data, tuple(attrs["reps"]))


@defop("reverse", arg_names=("data",), param_spec={"axis": ()})
def _reverse(attrs, data):
    axes = attrs["axis"]
    if isinstance(axes, (int, np.integer)):
        axes = (axes,)
    return jnp.flip(data, axis=tuple(int(a) for a in axes))


alias("reverse", "flip")


@defop(
    "Pad",
    arg_names=("data",),
    param_spec={"mode": "constant", "pad_width": (), "constant_value": 0.0},
)
def _pad(attrs, data):
    """N-d padding, constant/edge/reflect (reference src/operator/pad.cc)."""
    pw = attrs["pad_width"]
    pairs = [(int(pw[2 * i]), int(pw[2 * i + 1])) for i in range(len(pw) // 2)]
    mode = attrs["mode"]
    if mode == "constant":
        return jnp.pad(data, pairs, constant_values=attrs["constant_value"])
    return jnp.pad(data, pairs, mode="edge" if mode == "edge" else "reflect")


alias("Pad", "pad")


# --- indexing (reference indexing_op.cc) ------------------------------------
# What XLA's memory-space assignment gives a scatter on the v5e: the table
# stands in VMEM (``S(1)`` in the compiled text) where it and the rows added
# into it fit 112 MiB together, the chip's 128 less the 16 a kernel may
# scope. Read from compiles for a described v5e (PERF.md, Findings, PR 38):
# with 8192 rows of 40 / 48 / 64 / 16 MiB added, the largest table held was
# 71.8 / 63.9 / 47.8 / 96.0 MiB. Into a table there a sorted scatter-add
# costs 0.09-0.15 us a row; into one in HBM every add is a read-modify-write
# of a row there, 0.5-1.9 us.
_SCATTER_VMEM_BYTES = 112 * 2 ** 20
# XLA's scatter adds a row in whole (8, 128) registers where its width is
# whole pieces of 1024 numbers; a row that is not costs four times as much,
# bfloat16 or float32, in VMEM as in HBM (8192 rows into a table of 37984:
# 2.99 ms at 2048 wide, 4.44 at 3072, 16.04 at 2560). Wider rows than one
# piece are padded to whole pieces for the adds.
_SCATTER_ROW_LANES = 1024


def _scatter_width(d):
    return d + -d % _SCATTER_ROW_LANES if d > _SCATTER_ROW_LANES else d


def _direct_cotangent(g, ids, rows):
    """``jnp.take``'s own transpose: a scatter-add of the ids' rows into
    the ``(rows, d)`` table. XLA sorts the ids and adds row by row."""
    table = jax.ShapeDtypeStruct((rows,) + g.shape[ids.ndim:], g.dtype)
    (dw,) = jax.linear_transpose(
        lambda w: jnp.take(w, ids, axis=0), table)(g)
    return dw


def _compact_plan(ids, rows):
    """(order, slot, at) of the N ids: their stable order by id (ids that
    ``jnp.take`` reads as another row, the negative ones, as that row);
    the slot of each sorted id, one a run of equal ids, in id order; and
    where a run's first id names its slot in an ``int32[rows]`` array:
    the id itself, and past the end (dropped) at every other position and
    for an id outside the table, so that no two are equal."""
    flat = ids.reshape(-1)
    flat = jnp.where(flat < 0, flat + rows, flat)
    order = jnp.argsort(flat, stable=True)
    key = flat[order]
    first = jnp.concatenate([jnp.ones((1,), bool), key[1:] != key[:-1]])
    slot = jnp.cumsum(first, dtype=jnp.int32) - 1
    at = jnp.where(first & (key >= 0) & (key < rows), key,
                   rows + jnp.arange(flat.size, dtype=key.dtype))
    return order, slot, at


def _compact_table(g, ids, rows):
    """(compact, slot, at): equal ids' rows of ``g`` summed, in id order
    and then in the order they came, into a table of N + 1 rows (N the
    number of ids), one slot a distinct id, row N left zero; and the plan's
    ``slot`` and ``at``. The rows are fetched in id order by one gather
    and added by one sorted scatter-add into a table small enough for
    VMEM, today's arithmetic add for add (``_scatter_width``: over
    padded rows where the width asks for it)."""
    n, d = ids.size, g.shape[-1]
    order, slot, at = _compact_plan(ids, rows)
    got = g.reshape(n, d).at[order].get(mode="promise_in_bounds")
    got = jnp.pad(got, ((0, 0), (0, _scatter_width(d) - d)))
    compact = jnp.zeros((n + 1, got.shape[1]), g.dtype).at[slot].add(
        got, indices_are_sorted=True, mode="promise_in_bounds")
    return compact[:, :d], slot, at


def _compact_cotangent(g, ids, rows):
    """The direct form's sums with no row of the ``(rows, d)`` result
    written twice and nothing added into an array of that height: the
    compact table, an ``int32[rows]`` array that says which slot each row
    of the result reads (N: none, the zero row), and ONE gather told its
    indices are in bounds that writes the result once. Every piece has a
    static shape and walks all N rows whatever the ids hold. Ids outside
    the table are dropped, as ``jnp.take``'s transpose drops them."""
    compact, slot, at = _compact_table(g, ids, rows)
    slot_of = jnp.full((rows,), ids.size, jnp.int32).at[at].set(
        slot, unique_indices=True, mode="drop")
    return compact.at[slot_of].get(mode="promise_in_bounds")


def cotangent_path(rows, n, d, itemsize):
    """Which way the table's cotangent is built, from the shapes alone:
    the table's height, the ids' count, a row's width and the bytes of a
    number in the gradient's dtype. ``"direct"`` where the table itself
    stands in VMEM beside the n rows added into it: the compact form does
    the same adds and more (``lfm2_train_8k``'s 16384 ids into 8192 rows:
    1.53 ms against 2.03). ``"compact"`` where only the table of n + 1
    rows does (``smallthinker_train_8k`` and ``lm_train_4k``: 8192 ids
    into 37984 and 49152 rows). ``"direct"`` again where neither does:
    both add into HBM then, and the compact form's gathers come on top
    (16384 ids, 8 KB a row: 7.3 ms against 9.8, in four column pieces
    10.8). The readings: PERF.md, Findings, PR 38."""
    def in_vmem(table_rows, width):
        return (table_rows + n) * width * itemsize <= _SCATTER_VMEM_BYTES

    compact = (n and in_vmem(n + 1, _scatter_width(d))
               and not in_vmem(rows, d))
    return "compact" if compact else "direct"


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rows_as(weight, ids, dtype, node):
    return jnp.take(weight, ids, axis=0).astype(dtype)


def _rows_as_fwd(weight, ids, dtype, node):
    # the table itself is no residual: an empty slice carries its height
    return _rows_as(weight, ids, dtype, node), (ids, weight[:, :0])


def _rows_as_bwd(dtype, node, res, g):
    # the rows' cotangent is summed into a table of the compute dtype, as
    # it was when the table itself was cast, and widens where the update
    # reads it
    ids, height = res
    rows = height.shape[0]
    path = cotangent_path(rows, ids.size, g.shape[-1], g.dtype.itemsize)
    note_built({"op": "Embedding", "rows": rows, "ids": ids.size,
                "backward": path}, node=node)
    build = _compact_cotangent if path == "compact" else _direct_cotangent
    return build(g, ids, rows).astype(height.dtype), None


_rows_as.defvjp(_rows_as_fwd, _rows_as_bwd)


@defop(
    "Embedding",
    arg_names=("data", "weight"),
    param_spec={"input_dim": 0, "output_dim": 0, "dtype": "float32"},
    no_grad_inputs=("data",),
    # casting the table is a pass over the whole of it for the few rows a
    # step gathers: the rows are cast instead
    master_inputs=("weight",),
    simple=False,
)
def _embedding(attrs, inputs, aux, ctx):
    """Table lookup (reference indexing_op.cc Embedding +
    EmbeddingOpBackward). A float32 table the executor left in its master
    dtype gives its rows in the compute dtype (``ctx.compute_dtype``); the
    backward of either is ``_rows_as_bwd``."""
    data, weight = inputs
    ids = data.astype(jnp.int32)
    dtype = ctx.compute_dtype
    if dtype is None or weight.dtype != jnp.float32:
        dtype = weight.dtype
    if not jnp.issubdtype(weight.dtype, jnp.floating):
        return (jnp.take(weight, ids, axis=0),), ()  # no cotangent to build
    return (_rows_as(weight, ids, jnp.dtype(dtype), current_node()),), ()


@defop("take", arg_names=("a", "indices"), param_spec={"axis": 0, "mode": "clip"}, no_grad_inputs=("indices",))
def _take(attrs, a, indices):
    mode = attrs["mode"]
    return jnp.take(a, indices.astype(jnp.int32), axis=int(attrs["axis"]),
                    mode="wrap" if mode == "wrap" else "clip")


@defop("batch_take", arg_names=("a", "indices"), param_spec={}, no_grad_inputs=("indices",))
def _batch_take(attrs, a, indices):
    """Per-row gather: out[i] = a[i, indices[i]] (reference batch_take)."""
    return jnp.take_along_axis(
        a, indices.astype(jnp.int32).reshape(-1, 1), axis=1
    ).reshape(indices.shape)


@defop(
    "one_hot",
    arg_names=("indices",),
    param_spec={"depth": 0, "on_value": 1.0, "off_value": 0.0, "dtype": "float32"},
    no_grad_inputs=("indices",),
)
def _one_hot(attrs, indices):
    depth = int(attrs["depth"])
    oh = jax.nn.one_hot(indices.astype(jnp.int32), depth, dtype=jnp.dtype(attrs["dtype"]))
    return oh * (attrs["on_value"] - attrs["off_value"]) + attrs["off_value"]


# --- ordering (reference ordering_op.cc) ------------------------------------
@defop("sort", arg_names=("data",), param_spec={"axis": -1, "is_ascend": True})
def _sort(attrs, data):
    ax = attrs["axis"]
    out = jnp.sort(data, axis=None if ax is None else int(ax))
    if not attrs["is_ascend"]:
        out = jnp.flip(out, axis=-1 if ax is None else int(ax))
    return out


@defop("argsort", arg_names=("data",), param_spec={"axis": -1, "is_ascend": True, "dtype": "float32"})
def _argsort(attrs, data):
    ax = attrs["axis"]
    if not attrs["is_ascend"]:
        data = -data
    return jnp.argsort(data, axis=None if ax is None else int(ax)).astype(data.dtype)


@defop(
    "topk",
    arg_names=("data",),
    param_spec={"axis": -1, "k": 1, "ret_typ": "indices", "is_ascend": False, "dtype": "float32"},
    num_outputs=lambda attrs: 2 if attrs.get("ret_typ") == "both" else 1,
)
def _topk(attrs, data):
    """Top-k along an axis (reference ordering_op.cc). ret_typ selects
    value/indices/both/mask."""
    ax = int(attrs["axis"]) % data.ndim
    k = int(attrs["k"])
    moved = jnp.moveaxis(data, ax, -1)
    if attrs["is_ascend"]:
        vals, idx = jax.lax.top_k(-moved, k)
        vals = -vals
    else:
        vals, idx = jax.lax.top_k(moved, k)
    vals = jnp.moveaxis(vals, -1, ax)
    idxf = jnp.moveaxis(idx, -1, ax).astype(data.dtype)
    rt = attrs["ret_typ"]
    if rt == "value":
        return vals
    if rt == "both":
        return vals, idxf
    if rt == "mask":
        oh = jax.nn.one_hot(idx, moved.shape[-1], dtype=data.dtype).sum(-2)
        return jnp.moveaxis(oh, -1, ax)
    return idxf
