#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run, from the root of a checkout. It looks the cell up in
``BENCHMARK.json``, loads the cell's configuration, traffic mix, family and
driver by name from the files beside this one, refuses to run without the
TPU the cell asks for, lets the driver set up, measure and compare, and
prints one JSON object as the last line of standard output. With
``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, each read by ``metrics/<name>.py``.

Nothing here knows a cell, a model or a metric by name: see README.md.
"""
import time

T_START = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py`` by file, so that a name with dots
    (a metric's) is a file name and not a package path."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit("benchmark: no %s file %s" % (kind, path))
    spec = importlib.util.spec_from_file_location(
        "bench_%s_%s" % (kind, name.replace(".", "_").replace("-", "_")),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def applies(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


class Context:
    """What a driver gets: the cell's files, the run's arguments, the
    devices and their peaks."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def elapsed(self):
        return time.perf_counter() - T_START


def find_chips(chips):
    """The TPU devices of this machine, or exit 3 with no result."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print("benchmark: no accelerator: %s" % e, file=sys.stderr)
        raise SystemExit(3)
    if devices[0].platform != "tpu" or len(devices) < chips:
        print("benchmark: the cell needs %d TPU chip(s); JAX found %d %s "
              "device(s) of kind %r" % (chips, len(devices),
                                        devices[0].platform,
                                        devices[0].device_kind),
              file=sys.stderr)
        raise SystemExit(3)
    peaks = load_json(BENCH, "lib", "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks:
        print("benchmark: device kind %r is not in lib/peaks.json" % kind,
              file=sys.stderr)
        raise SystemExit(3)
    return devices[:chips], peaks[kind]


def setup_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where JAX_COMPILATION_CACHE_DIR says), every program kept."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def memory_peak(devices):
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def main(argv=None, find=find_chips):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default="BENCHMARK.json",
                    help="for the tests: another manifest, relative to the "
                         "checkout")
    args = ap.parse_args(argv)

    manifest = load_json(ROOT, args.manifest)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        raise SystemExit("benchmark: no workload %r in BENCHMARK.json"
                         % args.workload)
    cell = cells[args.workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cfg = load_json(ROOT, entry["file"])
    # a cell's traffic is a name under traffic/; the tests' toy manifest
    # gives a path under benchmark/ instead
    traffic = load_json(BENCH, *(("traffic",) if "/" not in cell["traffic"]
                                 else ()), cell["traffic"] + ".json")

    sys.path.insert(0, ROOT)   # the system under test: mxnet_tpu
    sys.path.insert(0, BENCH)  # lib, families
    devices, peaks = find(cell["chips"])
    setup_cache()
    family = load_module("families", cfg["family"])
    driver = load_module("drivers", traffic["driver"])
    ctx = Context(cfg=cfg, traffic=traffic, family=family, seed=args.seed,
                  seconds=args.seconds, trace=bool(args.trace),
                  devices=devices, peaks=peaks, root=ROOT,
                  memory_peak=memory_peak)
    run = driver.run(ctx)

    if args.trace:
        wanted = [m for m in manifest["per_layer"]
                  if applies(m, cell["name"])]
        values = {}
        for m in wanted:
            v = load_module("metrics", m["name"]).read(run)
            if v is not None:
                values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        wanted = [m for m in manifest["end_to_end"]
                  if applies(m, cell["name"])]
        values = {m["name"]: {"value": float(run["end_to_end"][m["name"]]),
                              "unit": m["unit"]} for m in wanted}

    checks = {name: {"value": value, "limit": limit}
              for name, value, limit in run["checks"]}
    correct = bool(checks) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": run["attempted"],
              "failed": run["failed"], "metrics": values, "device": device}
    if args.trace and run.get("trace"):
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = run["trace"]["breakdown"]
    result["facts"] = run.get("facts", {})
    result["compared"] = checks  # last: each number beside its limit
    for name, c in checks.items():
        print("compared %s = %s (limit %s)" % (name, c["value"], c["limit"]),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
