#!/usr/bin/env python3
"""A builder's probe, no verdict: what an expert cell's routing does to its
step. For each seed it drives the cell's own fused step (the benchmark's
``Trainer``: ``simple_bind`` + ``make_train_step``) over the cell's batches
and reads, step by step, the milliseconds beside the assignments each
``ExpertFFN`` layer's held experts drew (the op's second output, added to
the bound symbol's outputs here and nowhere else): whether a step's time
follows its routing, and how far from the expected load untrained weights
route.

    chiprun -- python tools/probe_expert_step.py --seeds 11,12,13 --steps 24

One line of JSON a seed (the first says which grouped-product path the
layers were traced with: the program record's ``layers``), a summary
last (the spread of the seeds' median milliseconds, and their slope over the
held rows); with ``--out`` the steps too.
"""
import argparse
import gc
import json
import os
import statistics
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


def built_paths():
    """{path: layers traced with it}, from the newest step's record."""
    import collections

    from mxnet_tpu import telemetry
    return dict(collections.Counter(
        layer["path"] for layer in telemetry.programs()[-1]["layers"]
        if layer["op"] == "ExpertFFN"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="smallthinker_train_8k")
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--seeds", default="2147481601,2147481602,2147481603")
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--any-device", action="store_true",
                    help="run where there is no TPU (a toy manifest)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH)
    import jax
    import numpy as np
    import run as bench
    import mxnet_tpu as mx
    from mxnet_tpu.ops import moe

    manifest = bench.load_json(ROOT, args.manifest)
    cell = {w["name"]: w for w in manifest["workloads"]}[args.workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cfg = bench.load_json(ROOT, entry["file"])
    tr = bench.load_json(BENCH, *(("traffic",) if "/" not in cell["traffic"]
                                  else ()), cell["traffic"] + ".json")
    if args.any_device:
        devices, peaks = jax.devices()[:1], {}
    else:
        devices, peaks = bench.find_chips(cell["chips"])
    bench.setup_cache()
    fam = bench.load_module("families", cfg["family"])
    driver = bench.load_module("drivers", tr["driver"])

    def symbol(cfg_, for_training):
        loss = fam.symbol(cfg_, for_training)
        inner = loss.get_internals()
        counts = [inner[n] for n in inner.list_outputs()
                  if n.endswith("_expert_tokens")]
        return mx.sym.Group([loss] + counts)

    probed = types.SimpleNamespace(**vars(fam))
    probed.symbol = symbol
    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = bench.Context(cfg=cfg, traffic=tr, family=probed, seed=seeds[0],
                        seconds=0, trace=False, devices=devices, peaks=peaks,
                        root=ROOT, memory_peak=bench.memory_peak)
    trainer = driver.Trainer(ctx)
    tokens = tr["batch"] * tr["seq_len"]
    layers = 0
    formats = None
    out = open(args.out, "w") if args.out else None
    lines = []
    for seed in seeds:
        if formats is not None:
            for a in jax.tree_util.tree_leaves((trainer.params,
                                                trainer.states)):
                a.delete()
            gc.collect()
            trainer.params = jax.device_put(fam.init_params(cfg, seed),
                                            formats[0])
            trainer.states = jax.device_put(
                {n: jax.numpy.zeros_like(a)
                 for n, a in trainer.params.items()}, formats[1])
        feeds = [{**d, **l} for d, l in fam.make_batches(
            cfg, tr, seed, tr["feed_batches"])]
        ms, held = [], []
        for i in range(args.steps + 1):
            t0 = time.perf_counter()
            outs = trainer.step(feeds[i % len(feeds)])
            jax.block_until_ready(outs)
            dt = 1e3 * (time.perf_counter() - t0)
            rows = [float(np.asarray(o, np.float64).sum()) for o in outs[1:]]
            layers = len(rows)
            if i == 0:
                if formats is None:  # the first step compiled, and relaid
                    formats = jax.tree_util.tree_map(
                        lambda a: a.format,
                        (trainer.params, trainer.states))
                continue
            ms.append(dt)
            held.append(rows)
            if out:
                out.write(json.dumps({"seed": seed, "step": i, "ms": dt,
                                      "held_rows": rows}) + "\n")
        buffer, expected = moe.buffer_rows(
            tokens, cfg["moe_num_active_primary_experts"],
            len(np.asarray(outs[1])), cfg["moe_num_primary_experts"])
        flat = [r for rows in held for r in rows]
        line = {"seed": seed, "steps": len(ms),
                "ms_median": statistics.median(ms), "ms_min": min(ms),
                "ms_max": max(ms),
                "held_rows_a_layer_over_expected": {
                    "mean": statistics.mean(flat) / expected,
                    "min": min(flat) / expected, "max": max(flat) / expected},
                "held_rows_a_step": statistics.mean(sum(r) for r in held),
                "buffer_rows": buffer, "expected_rows": expected,
                "layer_steps": len(flat),
                "layer_steps_over_twice_expected": sum(
                    r > 2 * expected for r in flat)}
        if not lines:
            line["product_paths"] = built_paths()
        lines.append(line)
        print(json.dumps(line), flush=True)
    if len(lines) > 2:
        xs = [l["held_rows_a_step"] for l in lines]
        ys = [l["ms_median"] for l in lines]
        mx_, my = statistics.mean(xs), statistics.mean(ys)
        sxx = sum((x - mx_) ** 2 for x in xs)
        slope = sum((x - mx_) * (y - my) for x, y in zip(xs, ys)) / sxx \
            if sxx else 0.0
        q = statistics.quantiles(ys, n=4)
        print(json.dumps({
            "layers": layers,
            "ms_median_over_seeds": statistics.median(ys),
            "ms_spread_pct": 100 * (q[2] - q[0]) / statistics.median(ys),
            "ms_per_1000_held_rows": 1e3 * slope,
            "corr": (slope * (sxx / sum((y - my) ** 2 for y in ys)) ** 0.5
                     if sxx and len(set(ys)) > 1 else None)}), flush=True)
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
