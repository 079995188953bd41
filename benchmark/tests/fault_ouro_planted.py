#!/usr/bin/env python3
"""Not a test: the upper readings of the ``ouro_lm`` cell's limits, taken on
the chip by hand at the cell's own size (PERF.md, section 2).

    python3 benchmark/tests/fault_ouro_planted.py --workload ouro_train_4k --seed N --what control|passes|carry|entropy|still

The faults run the cell (``run.py``, a 10 s window by default) with the PROGRAM broken
underneath; the reference is left whole. Three are of the loop's own
mathematics. ``passes``: the stack runs one pass fewer, with one exit
fewer (three instead of Ouro's four). ``carry``: every pass after the
first reads the state the previous pass left BEFORE the final norm, while
the exits still read it normed. ``entropy``: the exits' objective without
its entropy term (beta 0). One is the contract's of any training cell.
``still``: the state a step returns is thrown away (reads 1 on both change
numbers by construction: the toy's test only). ``control`` puts the
family's reference in fp8 in the program's place and compares it with the
float32 reference. All have to come out not ``correct``. The last line of
output is a JSON object with every number the comparison knows.
``benchmark/tests/test_ouro_cell.py`` runs them at a toy size on the CPU.
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
def patched(module, name, make):
    """``module.name`` replaced by ``make(the real one)`` meanwhile."""
    real = getattr(module, name)
    setattr(module, name, make(real))
    try:
        yield
    finally:
        setattr(module, name, real)


@contextlib.contextmanager
def one_pass_fewer():
    """The program's symbol built with ``loops`` one less (its leaves are
    the same: one set a layer)."""
    from mxnet_tpu import models

    real = models._BUILDERS["transformer-lm"]

    def get_symbol(*args, loops=1, exit_loss=None, **kw):
        return real(*args, loops=loops - 1 if loops > 2 else loops,
                    exit_loss=exit_loss, **kw)

    models._BUILDERS["transformer-lm"] = get_symbol
    try:
        yield
    finally:
        models._BUILDERS["transformer-lm"] = real


@contextlib.contextmanager
def carry_unnormed():
    """Every pass after the first starts from the input of the previous
    pass's final norm: the block builder handed, for the first layer of
    pass t > 0, what ``ut<t-1>_lnf`` reads instead of what it gives."""
    from mxnet_tpu.models import transformer
    from mxnet_tpu.symbol import Symbol

    def make(real):
        def block(x, *args, **kw):
            names = args[3]
            if names.leaf == "layer0" and not names.node.startswith(
                    ("layer", "ut0_")):
                norm = x._entries[0][0]
                assert norm.name.endswith("_lnf"), norm.name
                x = Symbol([norm.inputs[0]])
            return real(x, *args, **kw)
        return block
    with patched(transformer, "_block", make):
        yield


@contextlib.contextmanager
def entropy_left_out():
    """``LoopExitLoss`` traced with beta 0: the expected cross-entropy
    alone."""
    from mxnet_tpu.ops import sequence

    def make(real):
        return lambda logits, gates, label, beta: real(logits, gates, label,
                                                       0.0)
    with patched(sequence, "_exit_loss", make):
        yield


@contextlib.contextmanager
def state_unchanged():
    """Every step runs and its new parameters and momentum are thrown
    away (``run.py`` handing out the ``train_steps`` driver with
    ``Trainer.step`` wrapped)."""
    import run as bench

    def make(real_load):
        def load_module(kind, name):
            mod = real_load(kind, name)
            if (kind, name) == ("drivers", "train_steps"):
                real = mod.Trainer.step

                def step(self, feed):
                    keep = ({n: a + 0 for n, a in self.params.items()},
                            {n: a + 0 for n, a in self.states.items()})
                    outs = real(self, feed)
                    self.params, self.states = keep
                    return outs
                mod.Trainer.step = step
            return mod
        return load_module
    with patched(bench, "load_module", make):
        yield


FAULTS = {"passes": one_pass_fewer, "carry": carry_unnormed,
          "entropy": entropy_left_out, "still": state_unchanged}


def main(argv=None, find=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="ouro_train_4k")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--what", choices=("control",) + tuple(FAULTS),
                    required=True)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--seconds", default="10", help="a fault's window")
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
                        args.seconds, "--trace", "0"],
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
