#!/usr/bin/env python3
"""Not a test: the upper readings of the ``glm_moe_lite_lm`` cell's limits,
taken on the chip by hand at the cell's own size (PERF.md, section 2).

    python3 benchmark/tests/fault_glm_planted.py --workload glm_flash_train_4k --seed N --what control|rope_all|kv_unnormed|mtp_unshifted|scale1|still

The faults run the cell (``run.py``, a 10 s window by default) with the
PROGRAM broken underneath; the reference is left whole. Four are of the new
mathematics. ``rope_all``: RoPE over the whole head of 256 where only its
trailing 64 rotate. ``kv_unnormed``: the kv latent scaled by its RMSNorm's
scale without being normed. ``mtp_unshifted``: the multi-token-prediction
module embeds the token at i (t_i) where it should embed the next one
(t_{i+1}), its target unchanged. ``scale1``: the routed experts weighed with
``routed_scaling_factor`` 1.0 where the model says 1.8. One is the
contract's of any training cell. ``still``: the state a step returns is
thrown away (reads 1 on both change numbers by construction: the toy's test
only). ``control`` puts the family's reference in fp8 in the program's place
and compares it with the float32 reference. All have to come out not
``correct``. The last line of output is a JSON object with every number the
comparison knows. ``benchmark/tests/test_glm_cell.py`` runs them at a toy
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


@contextlib.contextmanager
def rope_all():
    """``MultiHeadAttention`` rotating every dim of a head whatever its
    ``rope_dims``."""
    from mxnet_tpu.ops import attention

    with patched(attention, "_rope_tail",
                 lambda real: lambda x, dims, base: real(x, 0, base)):
        yield


@contextlib.contextmanager
def kv_unnormed():
    """The block builder makes the kv latent's norm a plain product with its
    scale (the same leaf), so the latent reaches its up-projection
    un-normed."""
    from mxnet_tpu import symbol
    from mxnet_tpu.models import transformer

    def make(real):
        def norm(x, kind, dm, names, suffix, eps=None):
            if suffix != "_mla_kv_norm":
                return real(x, kind, dm, names, suffix, eps)
            return symbol.broadcast_mul(x, names.var(suffix + "_gamma", (dm,)),
                                        name=names.node + suffix)
        return norm
    with patched(transformer, "_norm", make):
        yield


@contextlib.contextmanager
def mtp_unshifted():
    """The module's embedding reads the model's input ids (t_i) where it
    should read the labels (t_{i+1}); its target stays the label one
    later."""
    from mxnet_tpu import symbol

    def make(real):
        seen = {}

        def embedding(*args, **kw):
            if kw.get("name") == "embed":
                seen["data"] = kw["data"]
            elif kw.get("name") == "mtp_embed":
                kw["data"] = seen["data"]
            return real(*args, **kw)
        return embedding
    with patched(symbol, "Embedding", make):
        yield


@contextlib.contextmanager
def scale_one():
    """Every expert layer's routing weights times 1.0 where the model says
    ``routed_scaling_factor``."""
    from mxnet_tpu.ops import moe

    with patched(moe, "route", lambda real: lambda *a: real(*a[:6], 1.0)):
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


FAULTS = {"rope_all": rope_all, "kv_unnormed": kv_unnormed,
          "mtp_unshifted": mtp_unshifted, "scale1": scale_one,
          "still": state_unchanged}


def main(argv=None, find=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="glm_flash_train_4k")
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
