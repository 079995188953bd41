#!/usr/bin/env python3
"""Not a test: the two upper readings of an expert cell's limits, taken on
the chip by hand at the cell's own size (PERF.md, section 2).

    python3 benchmark/tests/fault_expert_clip.py --workload smallthinker_train_8k --seed N --what fault|control

``fault`` runs the cell (``run.py``, 10 s window) with the PROGRAM's expert
layer broken underneath: every held expert is clipped at its expected load
(tokens x top_k / experts), the assignments past it dropped, which is what a
capacity-factor-1 dispatch does. ``control`` puts the family's reference in
fp8 in the program's place and compares it with the float32 reference. Both
have to come out not ``correct``. The last line of output is a JSON object
with every number the comparison knows. ``tests/test_smallthinker.py`` runs
the same fault at a toy size on the CPU.
"""
import argparse
import contextlib
import io
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@contextlib.contextmanager
def clipped_experts(num_experts):
    """``mxnet_tpu.ops.moe`` with every held expert clipped at
    tokens x top_k / ``num_experts`` assignments while the block runs."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import moe

    real = moe._sort_assignments

    def clipped(idx, first, held):
        order, inv, sizes = real(idx, first, held)
        cap = idx.size // num_experts
        local = idx.reshape(-1) - first
        mine = (local >= 0) & (local < held)
        starts = jnp.cumsum(sizes) - sizes
        rank = inv - starts[jnp.clip(local, 0, held - 1)]
        dropped = mine & (rank >= cap)
        return real(jnp.where(dropped, -1, idx.reshape(-1)).reshape(idx.shape),
                    first, held)

    moe._sort_assignments = clipped
    try:
        yield
    finally:
        moe._sort_assignments = real


def main(argv=None, find=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="smallthinker_train_8k")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--what", choices=("fault", "control"), required=True)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH)
    import run as bench

    with open(os.path.join(ROOT, args.manifest)) as f:
        manifest = json.load(f)
    cell = {w["name"]: w for w in manifest["workloads"]}[args.workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cfg = bench.load_json(ROOT, entry["file"])
    if args.what == "fault":
        out = io.StringIO()
        with clipped_experts(cfg["moe_num_primary_experts"]), \
                contextlib.redirect_stdout(out):
            bench.main(["--manifest", args.manifest, "--workload",
                        args.workload, "--seed", str(args.seed), "--seconds",
                        "10", "--trace", "0"],
                       **({"find": find} if find else {}))
        result = json.loads(out.getvalue().strip().splitlines()[-1])
        print(json.dumps({"what": "fault", "correct": result["correct"],
                          "compared": result["compared"],
                          "numbers": result["facts"]["numbers"]}))
        return 0
    traffic = bench.load_json(BENCH, *(
        ("traffic",) if "/" not in cell["traffic"] else ()),
        cell["traffic"] + ".json")
    if find is None:
        bench.find_chips(cell["chips"])
    bench.setup_cache()
    fam = bench.load_module("families", cfg["family"])
    drv = bench.load_module("drivers", traffic["driver"])
    ref = fam.ref_train(cfg, traffic, args.seed, traffic["ref_steps"])
    low = fam.ref_train(cfg, traffic, args.seed, traffic["ref_steps"],
                        low=True)
    checks, facts = drv.compare(low, ref, traffic["limits"])
    print(json.dumps({
        "what": "control",
        "correct": all(v <= limit for _, v, limit in checks),
        "compared": {n: {"value": v, "limit": limit}
                     for n, v, limit in checks},
        "numbers": facts["numbers"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
