#!/usr/bin/env python3
"""Not a test: the upper readings of the ``qwen3_next_lm`` cell's limits,
taken on the chip by hand at the cell's own size (PERF.md, section 2).

    python3 benchmark/tests/fault_qwen3next_planted.py --workload qwen3next_train_8k --seed N --what control|no_decay|no_beta|chunk_reset|qk_unnormed|attn_ungated|shared_ungated|still[,...]

The faults run the cell (``run.py``, a 10 s window by default) with the
PROGRAM broken underneath; the reference is left whole. Six are of the new
mathematics. ``no_decay``: the delta rule without its decay (g = 0).
``no_beta``: without its write strength (beta = 1). ``chunk_reset``: the
state reset to zero at the start of every chunk of the rule (and no
gradient carried back across one). ``qk_unnormed``: q and k reach the rule
without their unit-length norm (q still over sqrt(key size)).
``attn_ungated``: the attention layer's output without its sigmoid gate.
``shared_ungated``: the shared expert added without its sigmoid gate. One
is the contract's of any training cell. ``still``: the state a step returns
is thrown away (reads 1 on both change numbers by construction: the toy's
test only). ``control`` puts the family's reference in fp8 in the
program's place and compares it with the float32 reference. All have to
come out not ``correct``. Each prints a line, a JSON object with every
number the comparison knows. Several, comma-separated, run in one process
one after the other and share the family's float32 reference of the seed,
which is computed once (a fault breaks the program and never the
reference). ``benchmark/tests/test_qwen3next_cell.py`` runs them at a toy
size on the CPU.
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


def _rule(change):
    """``GatedDeltaRule``'s rule called with (g, beta) changed by
    ``change``."""
    from mxnet_tpu.ops import delta_rule

    def make(real):
        def rule(q, k, v, g, beta, *rest):
            return real(q, k, v, *change(g, beta), *rest)
        return rule
    return patched(delta_rule, "gated_delta_rule", make)


def no_decay():
    return _rule(lambda g, beta: (g * 0, beta))


def no_beta():
    return _rule(lambda g, beta: (g, beta * 0 + 1))


@contextlib.contextmanager
def chunk_reset():
    """Every chunk of the rule starts from a zero state, on the plain path
    and in the kernels alike."""
    from mxnet_tpu.ops import delta_rule
    from mxnet_tpu.ops.pallas import gated_delta

    def fwd(real):
        def chunk(q, k, v, gam, beta, s, want_o=True):
            return real(q, k, v, gam, beta, s * 0, want_o)
        return chunk

    def bwd(real):
        def chunk(q, k, v, gam, beta, s, do, ds):
            *grads, ds0 = real(q, k, v, gam, beta, s * 0, do, ds)
            return (*grads, ds0 * 0)
        return chunk

    with patched(delta_rule, "chunk_forward", fwd), \
            patched(gated_delta, "chunk_forward", fwd), \
            patched(gated_delta, "chunk_backward", bwd):
        yield


def qk_unnormed():
    """q and k reach the rule as the convolution gives them."""
    from mxnet_tpu.ops import delta_rule

    return patched(delta_rule, "_unit_heads",
                   lambda real: lambda x, heads, eps: x)


def attn_ungated():
    """``MultiHeadAttention`` leaves its gate input unread."""
    from mxnet_tpu.ops.registry import get_op

    op = get_op("MultiHeadAttention")

    def make(real):
        def impl(attrs, inputs, aux, ctx):
            if not attrs.get("output_gate"):
                return real(attrs, inputs, aux, ctx)
            return real(dict(attrs, output_gate=False), inputs[:-1], aux,
                        ctx)
        return impl
    return patched(op, "impl", make)


def shared_ungated():
    """The block builder's shared-expert gate is a one where sigmoid(u .
    w_sg) should be (the gate's projection is still there, and reads
    nothing back)."""
    from mxnet_tpu import symbol

    def make(real):
        def activation(*args, **kw):
            if kw.get("name", "").endswith("_shared_sigmoid"):
                return symbol.ones_like(kw["data"], name=kw["name"])
            return real(*args, **kw)
        return activation
    return patched(symbol, "Activation", make)


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


FAULTS = {"no_decay": no_decay, "no_beta": no_beta,
          "chunk_reset": chunk_reset, "qk_unnormed": qk_unnormed,
          "attn_ungated": attn_ungated, "shared_ungated": shared_ungated,
          "still": state_unchanged}


def _shared_family(bench):
    """``bench.load_module`` handing out one family module for the whole
    process, whose ``ref_train`` is computed once a (seed, precision)."""
    real, families, refs = bench.load_module, {}, {}

    def load_module(kind, name):
        if kind != "families":
            return real(kind, name)
        if name not in families:
            fam = families[name] = real(kind, name)
            ref_train = fam.ref_train

            def memo(cfg, traffic, seed, steps, low=False):
                key = (seed, steps, low)
                if key not in refs:
                    refs[key] = ref_train(cfg, traffic, seed, steps, low)
                return refs[key]
            fam.ref_train = memo
        return families[name]
    return patched(bench, "load_module", lambda _: load_module)


def _fault(bench, args, what, find):
    out = io.StringIO()
    with FAULTS[what](), contextlib.redirect_stdout(out):
        bench.main(["--manifest", args.manifest, "--workload",
                    args.workload, "--seed", str(args.seed), "--seconds",
                    args.seconds, "--trace", "0"],
                   **({"find": find} if find else {}))
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    return {"what": what, "correct": result["correct"],
            "compared": result["compared"],
            "numbers": result["facts"]["numbers"]}


def _control(bench, args, cfg, cell, find):
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
    return {"what": "control",
            "correct": all(v <= limit for _, v, limit in checks),
            "compared": {n: {"value": v, "limit": limit}
                         for n, v, limit in checks},
            "numbers": facts["numbers"]}


def main(argv=None, find=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="qwen3next_train_8k")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--what", required=True,
                    help="comma-separated: control, " + ", ".join(FAULTS))
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--seconds", default="10", help="a fault's window")
    args = ap.parse_args(argv)
    wanted = args.what.split(",")
    unknown = set(wanted) - {"control", *FAULTS}
    if unknown:
        ap.error("--what: unknown %s" % ", ".join(sorted(unknown)))
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH)
    import run as bench

    with open(os.path.join(ROOT, args.manifest)) as f:
        manifest = json.load(f)
    cell = {w["name"]: w for w in manifest["workloads"]}[args.workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cfg = bench.load_json(ROOT, entry["file"])
    with _shared_family(bench):
        for what in wanted:
            if what == "control":
                line = _control(bench, args, cfg, cell, find)
            else:
                line = _fault(bench, args, what, find)
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
