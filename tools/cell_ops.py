#!/usr/bin/env python3
"""A builder's probe, no verdict: where a benchmark cell's step spends its
device time, operation by operation. Runs the cell traced, exactly as
``benchmark/run.py --trace 1`` does (the result line is printed as it
prints it), keeps every device operation's seconds that the reduction saw
(``trace_reduce``'s ``op_seconds``; the result line keeps the largest few),
and groups them as the ``step.ms.*`` metrics do: by the graph node the
program's own record (``mxnet_tpu.telemetry.programs()``) gives each
operation, through ``benchmark/lib/programs.py`` and ``lib/groups.py``.
PERF.md's section 5 is this table; it needs no dump and no cold cache.

    chiprun -- python tools/cell_ops.py --workload smallthinker_train_8k \\
        --seed 2147481701 [--root chipcheck] [--out chiprun_out/ops.json]

``--root``: another checkout's benchmark and program (a ``git archive``;
one from before the record prints the result line and no table). Also
printed: what the record's first read cost, and the bytes the compiled
program wants beside what the device gives.
"""
import argparse
import collections
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="smallthinker_train_8k")
    ap.add_argument("--seed", type=int, default=2147481701)
    ap.add_argument("--seconds", type=float, default=40)
    ap.add_argument("--root", default=os.path.dirname(HERE))
    ap.add_argument("--out", default=None)
    ap.add_argument("--top", type=int, default=6,
                    help="operations listed a group")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    sys.path.insert(0, os.path.join(root, "benchmark"))
    import run as bench
    from lib import trace_reduce

    kept = {}
    reduce_ = trace_reduce.reduce

    def keeping(trace, **kw):
        got = reduce_(trace, **kw)
        kept.update(got)
        return got

    trace_reduce.reduce = keeping
    bench.main(["--workload", args.workload, "--seed", str(args.seed),
                "--seconds", str(args.seconds), "--trace", "1"])
    steps = kept.get("programs", {}).get("step", {}).get("runs")
    if not steps:
        raise SystemExit("cell_ops: the trace holds no run of the step "
                         "on a device")
    try:
        from lib import own_names, programs
    except ImportError:
        raise SystemExit("cell_ops: %s has no lib/programs.py" % root)
    rec = programs.record()
    if rec is None or rec["ops"] is None:
        raise SystemExit("cell_ops: the program kept no record of its step")
    import jax

    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    print("noting the record %.4f s; its first read: text %.3f s, parse %.3f "
          "s; memory %s; the device's bytes_limit %s" % (
              rec["note_s"], rec["read_s"]["text"], rec["read_s"]["parse"],
              json.dumps(rec["memory"]), limit))
    print("layers: %s" % json.dumps(rec["layers"]))
    total, where = programs.group_seconds(kept["op_seconds"], rec)
    node_of = {op["name"]: op["node"] for op in rec["ops"]}
    ms = {own_names.own_name(line): 1e3 * s / steps
          for line, s in kept["op_seconds"].items()}
    grouped = collections.defaultdict(list)
    for name, group in where.items():
        grouped[group].append((ms[name], name))
    table = {g: {"ms_a_step": 1e3 * total[g] / steps, "ops": len(ops),
                 "largest": [[n, v] for v, n in
                             sorted(ops, reverse=True)[:args.top]]}
             for g, ops in grouped.items()}
    print("steps traced %d; all groups %.3f ms a step" % (
        steps, sum(row["ms_a_step"] for row in table.values())))
    for g, row in sorted(table.items(), key=lambda kv: -kv[1]["ms_a_step"]):
        print("%-18s %8.3f ms a step  %4d ops   %s" % (
            g, row["ms_a_step"], row["ops"], "  ".join(
                "%s %.3f" % (n[:28], v) for n, v in row["largest"])))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"steps": steps, "groups": table,
                       "memory": rec["memory"], "read_s": rec["read_s"],
                       "note_s": rec["note_s"],
                       "layers": rec["layers"], "bytes_limit": limit,
                       # a few operations' names as the trace gives them
                       # (whole lines), the longest-running first: what a
                       # traffic file's `kernels` patterns are matched with
                       "lines": [line[:1500] for line, _ in sorted(
                           kept["op_seconds"].items(),
                           key=lambda kv: -kv[1])[:40]],
                       "ops": {n: [v, where[n], node_of.get(n)]
                               for n, v in ms.items()}}, f)


if __name__ == "__main__":
    main()
