#!/usr/bin/env python3
"""A builder's probe, no verdict: the expert layer's moves between token
order and sorted-row order, each alone under ``jax.jit``, at the shapes of
the two sparse benchmark cells (``smallthinker_train_8k``: N = 8192 tokens,
k = 6, d = 2560, R = 49152 rows; ``lfm2_train_8k``: N = 16384, k = 4,
d = 2048, R = 65536), bfloat16.

    chiprun -- python tools/bench_expert_moves.py

What it separates: the layer's fast gather (``_spread``: a table of N rows,
a one-dimensional index, a two-dimensional result) and its slow one (the
combine as it stood until PR 36: a table of R rows, an ``(N, k)`` index, a
result with k on the second-minor dimension) differ in BOTH the index's
shape and the table's size. So the four gathers {N rows, R rows} x {index
``(R,)``, index ``(N, k)``}, then what followed the three-dimensional one
(the relayout ``(R, d) -> (N, k, d)`` and the masked sum over k), the sum
over k slabs ``(k, N, d)`` that follows the flat one, both whole combines
and both forms of ``_spread``. The indices are the layer's own plan for a
uniform routing (``plan_of``); the two SHUFFLED readings (a random
permutation) show that where a row lies decides nothing. What decides is
where the TABLE lies: every line says whether the gathers of its compiled
program read a table in VMEM (``gathers_from_vmem``), where XLA puts one
of up to 64 MiB. Hence the last experiment, the combine with its R-row
table cut into eight column pieces that each fit: slower than the one
gather from HBM (PERF.md, Findings, PR 36). A gather alone reads 0.8 ms
longer than the same gather inside the step. One line of JSON a reading:
ms a call, GB/s over the bytes it must move, the HBM floor of those bytes.
"""
import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench_grouped_matmul import timed  # noqa: E402

HBM_BYTES_PER_S = 819e9  # one v5e (benchmark/lib/peaks.json)

# (tokens N, top_k, d, experts held, experts)
SHAPES = {"smallthinker_train_8k": (8192, 6, 2560, 16, 64),
          "lfm2_train_8k": (16384, 4, 2048, 8, 64),
          "tiny": (64, 3, 128, 4, 8)}  # a rehearsal on the CPU: no time


def plan_of(n, k, held, experts, rng):
    """The layer's own plan for a routing drawn uniformly: k distinct
    experts a token, sorted as ``ExpertFFN`` sorts them. The indices of a
    step are NOT random: a stable sort by expert leaves every group's rows
    in token order, so each gather reads ``held + 1`` ascending streams."""
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops import moe

    idx = jnp.asarray(np.argsort(rng.random((n, experts)), axis=1)[:, :k]
                      .astype(np.int32))
    order, inv, sizes = moe._sort_assignments(idx, 0, held)
    return moe._plan(order, inv, jnp.sum(sizes), n * k, k)


def cases(n, k, d, held, experts, rng):
    """[(name, function, arguments, bytes it must move)] at one shape."""
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops import moe

    r = n * k
    f32 = jnp.float32

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32),
                           jnp.bfloat16)

    tokens, rows = rand(n, d), rand(r, d)
    plan = plan_of(n, k, held, experts, rng)
    tok, slot, ok, valid = (plan[key] for key in
                            ("tok", "slot", "ok", "valid"))
    flat = slot.T.reshape(-1)
    shuffled = jnp.asarray(rng.permutation(r).astype(np.int32))
    slabs, padded = rows.reshape(k, n, d), rows.reshape(n, k, d)
    row = 2 * d  # bytes

    def take(table, index):
        return jnp.take(table, index, axis=0)

    def made_here(table, index):
        return take(table + table, index)

    def masked_sum(got, ok):  # (N, k, d): the form until PR 36
        return jnp.sum(jnp.where(ok[:, :, None], got, 0), axis=1,
                       dtype=f32).astype(got.dtype)

    def slab_sum(got, ok):  # (k, N, d), ok (N, k): PR 36's
        return jnp.sum(jnp.where(ok.T[:, :, None], got, 0), axis=0,
                       dtype=f32).astype(got.dtype)

    def combine_old(rows, slot, ok):
        return masked_sum(take(rows, slot), ok)

    def combine_new(rows, slot, ok):  # the layer's own
        return moe._collect(rows, {"slot": slot, "ok": ok})

    def combine_columns(rows, slot, ok, pieces):
        # each column piece a table of its own, small enough for VMEM
        w, index = d // pieces, slot.T.reshape(-1)
        return jnp.concatenate([
            slab_sum(take(rows[:, c * w:(c + 1) * w], index)
                     .reshape(k, n, w), ok) for c in range(pieces)], axis=1)

    def spread_masked(x, tok, valid):
        return jnp.where(valid[:, None], take(x, tok), 0)

    return [
        ("gather: table (N, d), index (R,)", take, (tokens, tok), 2 * r * row),
        ("gather: table (N, d), index (N, k)", take,
         (tokens, tok.reshape(n, k)), 2 * r * row),
        ("gather: table (R, d), index (R,)", take, (rows, flat), 2 * r * row),
        ("gather: table (R, d), index (N, k)", take, (rows, slot),
         2 * r * row),
        ("gather: table (N, d), index (R,) SHUFFLED", take,
         (tokens, shuffled // k), 2 * r * row),
        ("gather: table (R, d), index (R,) SHUFFLED", take, (rows, shuffled),
         2 * r * row),
        # as in the step, where an earlier operation of the program writes
        # the table: XLA may then keep it in VMEM (one pass more to make it)
        ("gather: table (N, d) made in the program, index (R,)", made_here,
         (tokens, tok), 2 * (r + n) * row),
        ("gather: table (R, d) made in the program, index (R,)", made_here,
         (rows, flat), 4 * r * row),
        ("relayout (R, d) -> (N, k, d)", lambda x: x.reshape(n, k, d),
         (rows,), 2 * r * row),
        ("masked sum over k of (N, k, d)", masked_sum, (padded, ok),
         (r + n) * row),
        ("masked sum of k slabs (k, N, d)", slab_sum, (slabs, ok),
         (r + n) * row),
        ("combine until PR 36 (3-D gather, sum over axis 1)", combine_old,
         (rows, slot, ok), (r + n) * row),
        ("combine since PR 36 (flat gather, sum of k slabs)", combine_new,
         (rows, slot, ok), (r + n) * row),
        ("combine, the rows in 8 column pieces (each table fits VMEM)",
         lambda *a: combine_columns(*a, 8), (rows, slot, ok), (r + n) * row),
        ("spread with its mask", spread_masked, (tokens, tok, valid),
         2 * r * row),
        ("spread without", take, (tokens, tok), 2 * r * row),
    ]


def tables_in_vmem(text):
    """For each gather of a compiled program, whether the table it reads
    lies in VMEM (memory space ``S(1)`` in its layout) and not in HBM."""
    found = []
    for body in text.split("\n\n"):
        gather = re.search(r" gather\(%?([\w.]+),", body)
        if gather:
            table = re.search(r"%%?%s = \S+" % re.escape(gather.group(1)),
                              body)
            found.append(bool(table) and "S(1)" in table.group(0))
    return found


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="smallthinker_train_8k,lfm2_train_8k",
                    help="comma-separated, of: " + ", ".join(SHAPES))
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    import jax
    import numpy as np

    print(json.dumps({"device": jax.devices()[0].device_kind}))
    for shape in args.shapes.split(","):
        n, k, d, held, experts = SHAPES[shape]
        results = {}
        for what, f, f_args, nbytes in cases(
                n, k, d, held, experts, np.random.default_rng(args.seed)):
            f = jax.jit(f)
            ms, out = timed(f, f_args, args.reps)
            if what.startswith("combine"):
                results[what] = np.asarray(out, np.float32)
            print(json.dumps({
                "shape": shape, "N": n, "k": k, "d": d, "what": what,
                "ms": round(ms, 4),
                "GB_per_s": round(nbytes / ms / 1e6, 1),
                "hbm_floor_ms": round(1e3 * nbytes / HBM_BYTES_PER_S, 4),
                "gathers_from_vmem": tables_in_vmem(
                    f.lower(*f_args).compile().as_text())}), flush=True)
        old, new = list(results.values())[:2]
        print(json.dumps({"shape": shape, "combines_differ_by": float(
            np.abs(old - new).max())}), flush=True)


if __name__ == "__main__":
    main()
