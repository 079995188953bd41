#!/usr/bin/env python3
"""A builder's probe, no verdict: the backward of ``Embedding`` alone under
``jax.jit``, at the shapes of the three benchmark cells (the rows' cotangent
``g`` in bfloat16, the ids as the cell feeds them) and at two shapes no cell
has (a float32 table of an LSTM language model; a compact table too large
for VMEM).

    chiprun -- python tools/bench_embedding_backward.py

What it separates. Today's backward is a scatter-add of the N ids' rows
into the ``(V, d)`` table: where that table fits VMEM XLA keeps it there
(``S(1)`` in the compiled text) and an add costs 0.09 us; where it lies in
HBM every add is a read-modify-write of a row there. The compact form
(``ops/matrix.py`` ``_compact_cotangent``) sums equal ids' rows into a table
of N + 1 rows and reads the ``(V, d)`` result out of it; its last step is
timed as the gather the operator uses and as a scatter told its indices
are unique and sorted (which writes N rows after a pass of zeros), and
where the operator pads the rows for the adds (``_scatter_width``: a row
that is not whole pieces of 1024 numbers costs XLA's scatter four times as
much; the ``rows37984_*`` shapes show it) also without the padding. The
pieces of the compact form are timed alone too, and the direct form on the
ids flattened (the cells' ids are ``(1, 8192)`` and ``(2, 4096)``). Ids
uniform, from a Zipf law of exponent 1, and all equal: every piece has a
static shape, so the three should agree. One line of JSON a reading: ms a
call, the HBM floor of the bytes it must move (g read once, the result
written once in float32, as the step's update reads it), whether
each scatter's and each gather's table lies in VMEM.
"""
import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench_expert_moves import tables_in_vmem as gathers_in_vmem  # noqa: E402
from bench_grouped_matmul import timed  # noqa: E402

HBM_BYTES_PER_S = 819e9  # one v5e (benchmark/lib/peaks.json)

# (ids' shape, table rows V, width d, the gradient's dtype)
SHAPES = {"smallthinker_train_8k": ((1, 8192), 37984, 2560, "bfloat16"),
          "lm_train_4k": ((2, 4096), 49152, 3072, "bfloat16"),
          "lfm2_train_8k": ((2, 8192), 8192, 2048, "bfloat16"),
          # models/lstm_lm.py on PTB: 32 x 35 ids, float32
          "lstm_ptb": ((32, 35), 10000, 256, "float32"),
          # the compact table (134 MB) does not fit VMEM
          "wide_16k": ((2, 8192), 65536, 4096, "bfloat16"),
          # SmallThinker's table at other widths: what a row's add costs
          "rows37984_d2048": ((1, 8192), 37984, 2048, "bfloat16"),
          "rows37984_d3072": ((1, 8192), 37984, 3072, "bfloat16"),
          "rows37984_d2560_f32": ((1, 8192), 37984, 2560, "float32"),
          "tiny": ((2, 24), 56, 128, "float32")}  # the CPU's rehearsal
LAWS = ("uniform", "zipf", "equal")


def draw(law, shape, rows, rng):
    import numpy as np
    if law == "uniform":
        ids = rng.integers(0, rows, shape)
    elif law == "zipf":
        p = 1.0 / np.arange(1, rows + 1)
        ids = rng.choice(rows, size=shape, p=p / p.sum())
    else:
        ids = np.full(shape, rows // 3)
    return ids.astype(np.int32)


def forms(rows, n, d, item):
    """{name: f(g, ids)} of the whole backward."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import matrix

    def compact_scatter_last(g, ids):
        compact, _, at = matrix._compact_table(g, ids, rows)
        # the runs' firsts in id order, as their slots are; what is past
        # the end is dropped (slots past the last run hold zeros)
        return jnp.zeros((rows,) + compact.shape[1:], g.dtype).at[
            jnp.sort(at)].set(compact[:n], unique_indices=True,
                              indices_are_sorted=True, mode="drop")

    def compact_in_columns(g, ids, pieces):
        # each column piece a compact table of its own that fits VMEM
        w = g.shape[-1] // pieces
        return jnp.concatenate([
            matrix._compact_cotangent(g[..., c * w:(c + 1) * w], ids, rows)
            for c in range(pieces)], axis=1)

    def compact_unpadded(g, ids):
        # the operator's form without `_scatter_width`'s padding
        n, d = ids.size, g.shape[-1]
        order, slot, at = matrix._compact_plan(ids, rows)
        compact = jnp.zeros((n + 1, d), g.dtype).at[slot].add(
            g.reshape(n, d).at[order].get(mode="promise_in_bounds"),
            indices_are_sorted=True, mode="promise_in_bounds")
        slot_of = jnp.full((rows,), n, jnp.int32).at[at].set(
            slot, unique_indices=True, mode="drop")
        return compact.at[slot_of].get(mode="promise_in_bounds")

    whole = {
        "direct, the cell's ids": lambda g, ids:
            matrix._direct_cotangent(g, ids, rows),
        "direct, flat ids": lambda g, ids: matrix._direct_cotangent(
            g.reshape((-1,) + g.shape[ids.ndim:]), ids.reshape(-1), rows),
        "compact, a gather last": lambda g, ids:
            matrix._compact_cotangent(g, ids, rows),
        "compact, a scatter last": compact_scatter_last,
    }
    if matrix._scatter_width(d) != d:
        whole["compact, its rows unpadded"] = compact_unpadded
    cuts = 1
    while (2 * n + 1) * d * item > cuts * matrix._SCATTER_VMEM_BYTES:
        cuts *= 2
    if cuts > 1:
        whole["compact, in %d column pieces" % cuts] = (
            lambda g, ids: compact_in_columns(g, ids, cuts))
    # read as the step's update reads it, widened and scaled: a program's
    # result lies in HBM, so without a reader no table of the result's
    # height could stand in VMEM
    return {what: (lambda g, ids, f=f: 0.5 * f(g, ids).astype(jnp.float32))
            for what, f in whole.items()}


def pieces(rows):
    """{name: (f, which of (g, ids) it takes)}: the compact form's steps,
    each with what comes before it."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import matrix

    def rows_in_order(g, ids):
        order, _, _ = matrix._compact_plan(ids, rows)
        return g.reshape((ids.size,) + g.shape[ids.ndim:]).at[order].get(
            mode="promise_in_bounds")

    def slot_of(ids):
        _, slot, at = matrix._compact_plan(ids, rows)
        return jnp.full((rows,), ids.size, jnp.int32).at[at].set(
            slot, unique_indices=True, mode="drop")

    return {"piece: the plan (sort, runs)":
            (lambda ids: matrix._compact_plan(ids, rows), (1,)),
            "piece: plan + the rows in id order": (rows_in_order, (0, 1)),
            "piece: ... + the compact table":
            (lambda g, ids: matrix._compact_table(g, ids, rows)[0], (0, 1)),
            "piece: plan + the slot of every table row": (slot_of, (1,))}


def tables_in_vmem(text):
    """For each scatter and gather of a compiled program, whether the table
    it adds into or reads lies in VMEM (memory space ``S(1)`` in its
    layout) and not in HBM: ``{"scatter": [...], "gather": [...]}``. A
    scatter works in place: its result is its table."""
    return {"scatter": ["S(1)" in result for result in
                        re.findall(r" = (\S+) scatter\(", text)],
            "gather": gathers_in_vmem(text)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="smallthinker_train_8k,lm_train_4k,"
                    "lfm2_train_8k,lstm_ptb,wide_16k",
                    help="comma-separated, of: " + ", ".join(SHAPES))
    ap.add_argument("--laws", default=",".join(LAWS))
    ap.add_argument("--forms", default="",
                    help="only the forms and pieces whose name holds this")
    ap.add_argument("--out", default="",
                    help="also append every line to this file")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops import matrix

    def say(line):
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")

    say({"device": jax.devices()[0].device_kind})
    rng = np.random.default_rng(args.seed)
    for shape in args.shapes.split(","):
        ids_shape, rows, d, dtype = SHAPES[shape]
        n = int(np.prod(ids_shape))
        item = jnp.dtype(dtype).itemsize
        nbytes = n * d * item + rows * d * 4
        g = jnp.asarray(rng.standard_normal(ids_shape + (d,), np.float32),
                        dtype)
        base = {"shape": shape, "ids": list(ids_shape), "V": rows, "d": d,
                "dtype": dtype,
                "rule": matrix.cotangent_path(rows, n, d, item),
                "hbm_floor_ms": round(1e3 * nbytes / HBM_BYTES_PER_S, 4)}
        for li, law in enumerate(args.laws.split(",")):
            ids = jnp.asarray(draw(law, ids_shape, rows, rng))
            todo = [(what, f, (g, ids)) for what, f in forms(rows, n, d, item).items()]
            if li == 0:
                todo += [(what, f, tuple((g, ids)[i] for i in takes))
                         for what, (f, takes) in pieces(rows).items()]
            want = None
            for what, f, f_args in todo:
                if args.forms not in what:
                    continue
                f = jax.jit(f).lower(*f_args).compile()
                ms, out = timed(f, f_args, args.reps)
                line = dict(base, law=law, what=what, ms=round(ms, 4),
                            us_per_id=round(1e3 * ms / n, 4))
                if not what.startswith("piece"):
                    out = np.asarray(out, np.float32)
                    want = out if want is None else want
                    line["differs_from_direct_by"] = float(
                        np.abs(out - want).max())
                line["in_vmem"] = tables_in_vmem(f.as_text())
                say(line)


if __name__ == "__main__":
    main()
