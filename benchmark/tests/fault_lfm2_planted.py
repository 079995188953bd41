#!/usr/bin/env python3
"""Not a test: the upper readings of the ``lfm2_moe_lm`` cell's limits, taken
on the chip by hand at the cell's own size (PERF.md, section 2).

    python3 benchmark/tests/fault_lfm2_planted.py --workload lfm2_train_8k --seed N --what control|taps|bias|half|still

The faults run the cell (``run.py``, 10 s window) with the PROGRAM broken
underneath. Two are of its new mathematics. ``taps``: every short
convolution reads its taps in reverse, the first on the token itself.
``bias``: the experts are chosen by the sigmoid score WITHOUT the
``expert_bias`` (the weights, the score's either way, stay), which is what a
router that forgot its selection bias does, and shows that the seeded bias
reached the program and decides. Two are the contract's of any training
cell. ``half``: every step trains on the first of its two sequences twice.
``still``: the state a step returns is thrown away (reads 1 on both change
numbers by construction: the toy's test only). ``control`` puts the
family's reference in fp8 in the program's place and compares it with the
float32 reference. All have to come out not ``correct``. The last line of
output is a JSON object with every number the comparison knows.
``benchmark/tests/test_lfm2_cell.py`` runs them at a toy size on the CPU.
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
def bias_forgotten():
    """``mxnet_tpu.ops.moe.route`` choosing by the score alone while the
    block runs: the bias arrives and is set to nought."""
    from mxnet_tpu.ops import moe

    real = moe.route

    def forgetful(router_data, router_weight, top_k, norm_topk, bias=None,
                  *rest):
        if bias is not None:
            bias = bias * 0.0
        return real(router_data, router_weight, top_k, norm_topk, bias,
                    *rest)

    moe.route = forgetful
    try:
        yield
    finally:
        moe.route = real


@contextlib.contextmanager
def taps_reversed():
    """The short convolution's elementwise part (``ops/shortconv.py``
    ``gated_conv``, whichever implementation it picks when the step is
    traced) given its taps last first."""
    from mxnet_tpu.ops import shortconv

    real = shortconv.gated_conv
    shortconv.gated_conv = lambda u, k, path: real(u, k[:, ::-1], path)
    try:
        yield
    finally:
        shortconv.gated_conv = real


@contextlib.contextmanager
def broken_step(breaks):
    """``run.py`` handing out the ``train_steps`` driver with
    ``Trainer.step`` replaced by ``breaks(real_step)``, as
    ``test_benchmark.py`` plants the contract's two faults of any training
    cell on the dense toy cell."""
    import run as bench

    real_load = bench.load_module

    def load_module(kind, name):
        mod = real_load(kind, name)
        if (kind, name) == ("drivers", "train_steps"):
            mod.Trainer.step = breaks(mod.Trainer.step)
        return mod

    bench.load_module = load_module
    try:
        yield
    finally:
        bench.load_module = real_load


def half_batch():
    """Every step trains on the first half of its batch, fed twice (the
    mean is the first sequence's)."""
    def breaks(real):
        def step(self, feed):
            import jax.numpy as jnp

            return real(self, {n: jnp.concatenate([a[:a.shape[0] // 2]] * 2)
                               for n, a in feed.items()})
        return step
    return broken_step(breaks)


def state_unchanged():
    """Every step runs and its new parameters and momentum are thrown
    away."""
    def breaks(real):
        def step(self, feed):
            keep = ({n: a + 0 for n, a in self.params.items()},
                    {n: a + 0 for n, a in self.states.items()})
            outs = real(self, feed)
            self.params, self.states = keep
            return outs
        return step
    return broken_step(breaks)


FAULTS = {"taps": taps_reversed, "bias": bias_forgotten,
          "half": half_batch, "still": state_unchanged}


def main(argv=None, find=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="lfm2_train_8k")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--what", choices=("control",) + tuple(FAULTS),
                    required=True)
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
    if args.what in FAULTS:
        out = io.StringIO()
        with FAULTS[args.what](), contextlib.redirect_stdout(out):
            bench.main(["--manifest", args.manifest, "--workload",
                        args.workload, "--seed", str(args.seed), "--seconds",
                        "10", "--trace", "0"],
                       **({"find": find} if find else {}))
        result = json.loads(out.getvalue().strip().splitlines()[-1])
        print(json.dumps({"what": args.what, "correct": result["correct"],
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
