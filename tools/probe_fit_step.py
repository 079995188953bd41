#!/usr/bin/env python3
"""Probe ``Module.fit`` on the chip: where the host holds the device back.

    chiprun -- env MXNET_STEP_AUTO_LAYOUT=0 python tools/probe_fit_step.py \
        --model resnet50 --batch 256 --steps 40 --feed device

Runs the package's own training loop (``BaseModule.fit``: ``next`` of the
iterator, ``fit_step``, ``update_metric``) for two epochs on synthetic
batches: the first warms up and compiles, the second is traced by a
profiler session that a batch-end callback opens and closes. The trace is
reduced with ``benchmark/lib/trace_reduce.py``; the device's idle gaps are
named twice, by the shortest host event over each gap's middle (the
benchmark's rule) and by the innermost *program* span there (the step-path
spans of ``mxnet_tpu.telemetry``, which are ``TraceAnnotation``s and so lie
in the trace on its clock). The ring gives each span's self time per step.

A builder's probe, not a benchmark cell: it compares nothing and claims
nothing. It refuses to run without a TPU. One JSON object is printed last
and written to ``chiprun_out/probe_fit_step.<model>.<feed>.json``.
"""
import argparse
import json
import os
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import numpy as np  # noqa: E402

# StarCoder2-3B's widths (benchmark/configs/starcoder2-3b.train.json)
LM = dict(num_classes=49152, num_heads=24, model_dim=3072, ffn_dim=12288,
          num_kv_heads=2)


def build(args, mx):
    """(symbol, data shape, label shape, a function making one host batch,
    the metric)."""
    if args.model == "resnet50":
        from mxnet_tpu.models import resnet

        sym = resnet.get_symbol(num_classes=1000, num_layers=50,
                                image_shape=(3, 224, 224))
        dshape, lshape = (args.batch, 3, 224, 224), (args.batch,)

        def batch(rng):
            return (rng.standard_normal(dshape, np.float32),
                    rng.integers(0, 1000, lshape).astype(np.float32))

        return sym, dshape, lshape, batch, mx.metric.Accuracy()
    from mxnet_tpu import models

    sym = models.get_symbol("transformer-lm", num_layers=args.depth,
                            scalar_loss=True, **LM)
    dshape = lshape = (args.batch, args.seq)

    def batch(rng):
        ids = rng.integers(0, LM["num_classes"], (args.batch, args.seq + 1))
        return (ids[:, :-1].astype(np.float32), ids[:, 1:].astype(np.float32))

    return sym, dshape, lshape, batch, mx.metric.Loss()


def span_table(records, t0, t1):
    """Per span name over the records that began in [t0, t1): how many,
    median duration and median self time (duration less the direct
    children's), in microseconds."""
    inside = [r for r in records if t0 <= r["start"] < t1]
    kids = {}
    for r in inside:
        kids.setdefault(r["parent"], []).append(r["dur"])
    table = {}
    for r in inside:
        row = table.setdefault(r["name"], {"dur": [], "self": []})
        row["dur"].append(r["dur"])
        row["self"].append(r["dur"] - sum(kids.get(r["id"], ())))
    return {name: {"n": len(v["dur"]),
                   "median_us": statistics.median(v["dur"]) / 1e3,
                   "median_self_us": statistics.median(v["self"]) / 1e3,
                   "total_self_ms": sum(v["self"]) / 1e6}
            for name, v in sorted(table.items())}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=("resnet50", "lm"), required=True)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--steps", type=int, default=40, help="traced steps")
    ap.add_argument("--warm", type=int, default=8)
    ap.add_argument("--feed", choices=("device", "host"), default="device")
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("probe_fit_step: needs a TPU, found %s" % dev.platform)
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.base import init_compile_cache
    from lib import trace_reduce

    init_compile_cache()
    ctx = mx.Context("tpu", dev.id)
    sym, dshape, lshape, make, metric = build(args, mx)
    rng = np.random.default_rng(args.seed)
    host = [make(rng) for _ in range(2)]
    if args.feed == "device":
        feed = [(mx.nd.array(x, ctx=ctx), mx.nd.array(y, ctx=ctx))
                for x, y in host]
    else:  # what NDArrayIter hands over: arrays the step has to bring in
        feed = host

    class Batches(mx.io.DataIter):
        provide_data = [mx.io.DataDesc("data", dshape)]
        provide_label = [mx.io.DataDesc("softmax_label", lshape)]

        def __init__(self):
            super().__init__(args.batch)
            self.epoch, self.at = 0, 0

        def reset(self):
            self.epoch, self.at = self.epoch + 1, 0

        def next(self):
            n = args.warm if self.epoch == 0 else args.steps + 3
            if self.at >= n:
                raise StopIteration
            x, y = feed[self.at % len(feed)]
            self.at += 1
            return mx.io.DataBatch(data=[x], label=[y], pad=0)

    trace_dir = os.path.join(ROOT, ".bench_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    mod = mx.mod.Module(sym, context=ctx, compute_dtype="bfloat16")
    marks = {}

    def batch_end(param):
        if param.epoch != 1:
            return
        if param.nbatch == 1:  # past the step that re-snapshots
            jax.block_until_ready(mod.get_outputs()[0]._data)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            marks["t0"] = (telemetry.clock_ns(), time.perf_counter())
        elif param.nbatch == args.steps + 1:
            jax.block_until_ready(mod.get_outputs()[0]._data)
            marks["t1"] = (telemetry.clock_ns(), time.perf_counter())
            jax.profiler.stop_trace()

    t_start = time.perf_counter()
    mod.fit(Batches(), num_epoch=2, kvstore=None, eval_metric=metric,
            initializer=mx.initializer.Xavier(factor_type="in",
                                              magnitude=2.0),
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.01, "momentum": 0.9},
            batch_end_callback=batch_end)
    wall_s = time.perf_counter() - t_start
    assert mod.fit_step_path == "fused", mod.fit_step_path

    records = [{"name": n, "start": ts, "dur": dur,
                "id": (a or {}).get("id"), "parent": (a or {}).get("parent"),
                "args": a or {}}
               for ph, n, _d, ts, dur, a, _tid, _tn
               in telemetry.drain_events(clear=False) if ph == "X"]
    setup = [{"name": r["name"], "ms": r["dur"] / 1e6,
              **{k: v for k, v in r["args"].items()
                 if k not in ("id", "parent")}}
             for r in sorted(records, key=lambda r: r["start"])
             if r["start"] < marks["t0"][0] and r["dur"] > 20e6]

    trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    red = trace_reduce.reduce(trace, program_patterns={
        "step": ["jit_one_step"]}, align="step", top=40)
    # the same gaps, named by the innermost span of the program alone
    plane = trace_reduce.device_planes(trace)[0]
    ops = [(s, s + d) for n, s, d in trace_reduce._line(
        plane, trace_reduce.OPS_LINE)
        if d > 0 and not trace_reduce._is_umbrella(n)]
    mods = sorted((s, s + d) for n, s, d in trace_reduce._line(
        plane, trace_reduce.MODULES_LINE) if d > 0 and "jit_one_step" in n)
    lo, hi = mods[0][0], mods[-1][1]
    busy = trace_reduce.clip(trace_reduce.union(
        [[s, e] for s, e in ops]), lo, hi)
    hosts = trace_reduce.host_events(trace)
    program = trace_reduce.index_hosts(
        [h for h in hosts if h[0] in telemetry.STEP_PATH])
    by_span, long_total, short_total = {}, 0.0, 0.0
    for gap in trace_reduce.gaps(busy, lo, hi):
        length = gap[1] - gap[0]
        if length < 50e3:
            short_total += length
            continue
        long_total += length
        key = trace_reduce.name_gap(gap, program)
        by_span[key] = by_span.get(key, 0.0) + length
    named = sum(v for k, v in by_span.items() if k != "no_host_span")
    # the program's spans in the xplane, against the step program's runs
    in_trace = {}
    for name, s, e in hosts:
        if name in telemetry.STEP_PATH and lo <= s <= hi:
            in_trace[name] = in_trace.get(name, 0) + 1

    step = red["programs"]["step"]
    out = {
        "probe": "fit_step", "model": args.model, "feed": args.feed,
        "batch": args.batch, "depth": args.depth if args.model == "lm"
        else None, "device": {"platform": dev.platform,
                              "kind": dev.device_kind},
        "auto_layout": os.environ.get("MXNET_STEP_AUTO_LAYOUT", "1"),
        "wall_s": wall_s,
        "traced_steps_by_host_clock_ms": 1e3 * (
            marks["t1"][1] - marks["t0"][1]) / args.steps,
        "window_s": red["window_s"], "busy_s": red["busy_s"],
        "idle_pct": 100.0 * (1 - red["busy_s"] / red["window_s"]),
        "step_runs": step["runs"],
        "step_device_ms": 1e3 * statistics.fmean(step["busy_s"]),
        "step_gap_after_ms": 1e3 * statistics.median(step["gap_after_s"]),
        "idle_gaps_by_any_host_event": red["breakdown"]["idle_gaps"],
        "idle_long_gaps_s": long_total / 1e9,
        "idle_short_gaps_s": short_total / 1e9,
        "idle_by_program_span_s": {k: v / 1e9 for k, v in sorted(
            by_span.items(), key=lambda kv: -kv[1])},
        "idle_named_by_program_span_pct":
            100.0 * named / long_total if long_total else None,
        "program_spans_in_trace": in_trace,
        "spans_per_step": span_table(records, marks["t0"][0],
                                     marks["t1"][0]),
        "setup_spans_over_20ms": setup,
        "memory_peak_bytes": (dev.memory_stats() or {}).get(
            "peak_bytes_in_use"),
    }
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", "probe_fit_step.%s.%s.json"
                        % (args.model, args.feed))
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
