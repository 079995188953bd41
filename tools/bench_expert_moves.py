#!/usr/bin/env python3
"""A builder's probe, no verdict: the expert layer's moves between token
order and sorted-row order, each alone under ``jax.jit``, at the shapes of
the sparse benchmark cells (``smallthinker_train_8k``: N = 8192 tokens,
k = 6, d = 2560, R = 49152 rows; ``lfm2_train_8k``: N = 16384, k = 4,
d = 2048, R = 65536; ``glm_flash_train_4k``: N = 4096, k = 4, d = 2048,
R = 16384), bfloat16.

    python tools/bench_expert_moves.py [--shapes ...] \
        [--cases bookkeeping]  # on one v5e chip

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

The two ``bookkeeping`` readings time what moves ONE number a row, forward
and backward, alone: the router (the cell's route), the sort by expert,
the group sizes and the row weights, in the layer's form (sorts and
selects) and in the gather-based form it replaced (the reference kept in
``tests/test_expert_bookkeeping.py``), with how many gathers and scatters
each compiled program holds and how far the two forms' results lie apart.

The two ``dx_pair`` readings time the sum of the gate and the up product's
dX alone (``ops/moe.py`` ``_gate_up``'s backward, the Pallas kernels:
interpreted off the TPU): two ``gmm`` calls and an add over (R, d), and the
second ``gmm`` with the first's result as its ``addend``, with the HBM and
the MXU floors of each and how far the two sums lie apart relative to the
largest (bfloat16 rounds the first sum twice, the second once).
"""
import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench_grouped_matmul import timed  # noqa: E402

HBM_BYTES_PER_S = 819e9  # one v5e (benchmark/lib/peaks.json)
MXU_FLOP_PER_S = 197e12

# (tokens N, top_k, d, experts held, experts)
SHAPES = {"smallthinker_train_8k": (8192, 6, 2560, 16, 64),
          "lfm2_train_8k": (16384, 4, 2048, 8, 64),
          "glm_flash_train_4k": (4096, 4, 2048, 8, 64),
          "tiny": (64, 4, 128, 4, 8)}  # a rehearsal on the CPU: no time
# each cell's expert width f
EXPERT_WIDTH = {"smallthinker_train_8k": 768, "lfm2_train_8k": 1536,
                "glm_flash_train_4k": 1536, "tiny": 256}
# each cell's router: moe.route's keywords, and whether it takes a bias
ROUTES = {"smallthinker_train_8k": ({"norm_topk": True}, False),
          "lfm2_train_8k": ({"norm_topk": True, "norm_eps": 1e-6}, True),
          "glm_flash_train_4k": ({"norm_topk": True, "norm_eps": 1e-20,
                                  "scale": 1.8}, True),
          "tiny": ({"norm_topk": True, "norm_eps": 1e-6}, True)}


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


def bookkeeping(forms, k, held, rows, route_kw):
    """Router, sort, group sizes and row weights, forward and backward, in
    ``forms`` (``moe`` or the gather-based reference): a function of the
    router's input and matrix, the selection bias and a cotangent of the
    row weights."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import moe

    def f(router_data, router_weight, bias, ct):
        def loss(router_data, router_weight):
            w, idx = forms.route(router_data, router_weight, k, bias=bias,
                                 **route_kw)
            order, inv, sizes = forms._sort_assignments(idx, 0, held)
            plan = moe._plan(order, inv, jnp.sum(sizes), rows, k)
            return jnp.sum(forms._row_weights(w, plan) * ct), sizes
        return jax.value_and_grad(loss, (0, 1), has_aux=True)(
            router_data, router_weight)
    return f


def bookkeeping_cases(n, k, d, held, experts, rng, route):
    """The bookkeeping in the layer's form and in the reference's."""
    import types

    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops import moe

    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import test_expert_bookkeeping as reference

    gathered = types.SimpleNamespace(
        route=reference.route_gathered,
        _sort_assignments=reference.sort_gathered,
        _row_weights=reference.row_weights_gathered)
    route_kw, biased = route
    rows = n * min(k, held)
    args = (jnp.asarray(rng.standard_normal((n, d), np.float32),
                        jnp.bfloat16),
            jnp.asarray(0.02 * rng.standard_normal((experts, d), np.float32)),
            jnp.asarray(0.02 * rng.standard_normal(experts, np.float32))
            if biased else None,
            jnp.asarray(rng.standard_normal(rows, np.float32)))
    return [("bookkeeping, gathers and scatters (the reference)",
             bookkeeping(gathered, k, held, rows, route_kw), args, None),
            ("bookkeeping, sorts and selects (the layer's)",
             bookkeeping(moe, k, held, rows, route_kw), args, None)]


def dx_pair_cases(n, k, d, held, experts, f, rng):
    """The two dX of the gate and up products summed: after both kernels,
    or in the second as its addend. The group sizes are the layer's own
    for a uniform routing, the last stretched over the empty rows."""
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops import moe
    from mxnet_tpu.ops.pallas import grouped_matmul as gm
    from mxnet_tpu.ops.pallas import on_tpu

    rows = n * min(k, held)
    idx = np.argsort(rng.random((n, experts)), axis=1)[:, :k]
    _, _, sizes = moe._sort_assignments(jnp.asarray(idx, jnp.int32), 0, held)
    walked = sizes.at[-1].add(rows - jnp.sum(sizes))
    interpret = not on_tpu()

    def rand(*shape, scale=1.0):
        return jnp.asarray(scale * rng.standard_normal(shape, np.float32),
                           jnp.bfloat16)

    def gmm(dy, w, addend=None):
        return gm.gmm(dy, w, walked, transposed=False, addend=addend,
                      interpret=interpret)

    def added(dg, du, wg, wu):
        return gmm(dg, wg) + gmm(du, wu)

    def in_kernel(dg, du, wg, wu):
        return gmm(du, wu, addend=gmm(dg, wg))

    args = (rand(rows, f), rand(rows, f), rand(held, f, d, scale=0.02),
            rand(held, f, d, scale=0.02))
    blocks = (gm.gmm_column_blocks(gm.ROWS, f, d, 2),
              gm.gmm_column_blocks(gm.ROWS, f, d, 2, addend=True))
    row_f, row_d, matrices = 2 * f, 2 * d, 2 * 2 * held * f * d
    flops = 2 * 2 * rows * f * d
    return [("dx_pair, two products and an add (%d+%d column blocks)"
             % (blocks[0], blocks[0]), added, args,
             rows * (2 * blocks[0] * row_f + 5 * row_d) + matrices, flops),
            ("dx_pair, the second with the first as its addend "
             "(%d+%d column blocks)" % (blocks[0], blocks[1]), in_kernel,
             args, rows * ((blocks[0] + blocks[1]) * row_f + 3 * row_d)
             + matrices, flops)]


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
    ap.add_argument("--shapes", default="smallthinker_train_8k,lfm2_train_8k,"
                    "glm_flash_train_4k",
                    help="comma-separated, of: " + ", ".join(SHAPES))
    ap.add_argument("--cases", default="",
                    help="only the readings whose name holds this")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    import jax
    import numpy as np

    print(json.dumps({"device": jax.devices()[0].device_kind}))
    for shape in args.shapes.split(","):
        n, k, d, held, experts = SHAPES[shape]
        results = {"combine": [], "bookkeeping": [], "dx_pair": []}
        rng = np.random.default_rng(args.seed)
        for what, f, f_args, nbytes, *flops in (
                cases(n, k, d, held, experts, rng)
                + bookkeeping_cases(n, k, d, held, experts, rng,
                                    ROUTES[shape])
                + dx_pair_cases(n, k, d, held, experts, EXPERT_WIDTH[shape],
                                rng)):
            if args.cases not in what:
                continue
            f = jax.jit(f)
            ms, out = timed(f, f_args, args.reps)
            for kind, got in results.items():
                if what.startswith(kind):
                    got.append([np.asarray(a, np.float32) for a in
                                jax.tree_util.tree_leaves(out)])
            text = f.lower(*f_args).compile().as_text()
            line = {"shape": shape, "N": n, "k": k, "d": d, "what": what,
                    "ms": round(ms, 4), "gathers_from_vmem":
                    tables_in_vmem(text)}
            if nbytes is None:
                line["gathers_and_scatters"] = len(re.findall(
                    r" (?:gather|scatter)\(", text))
            else:
                line.update({"GB_per_s": round(nbytes / ms / 1e6, 1),
                             "hbm_floor_ms": round(
                                 1e3 * nbytes / HBM_BYTES_PER_S, 4)})
            if flops:
                line["mxu_floor_ms"] = round(1e3 * flops[0] / MXU_FLOP_PER_S,
                                             4)
            print(json.dumps(line), flush=True)
        for kind, got in results.items():
            if len(got) >= 2:
                differ = [float(np.abs(a - b).max())
                          for a, b in zip(got[0], got[1])]
                line = {"shape": shape, kind + "_forms_differ_by": differ}
                if kind == "dx_pair":
                    # one rounding of the sum against two: within a
                    # bfloat16 step of the largest element
                    line["relative"] = differ[0] / float(
                        np.abs(got[0][0]).max())
                    line["within_bfloat16_rounding"] = (
                        line["relative"] <= 2.0 ** -7)
                print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
