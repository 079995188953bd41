#!/usr/bin/env python
"""Whether a change left a benchmark configuration's fused training step the
program it was: the step's LOWERED text (as ``tools/step_ops.py`` builds it,
for a described v5e, nothing compiled), hashed.

    JAX_PLATFORMS=cpu python tools/step_text.py [--root <checkout>]
        [--config benchmark/configs/starcoder2-3b.train.json]
        [--traffic benchmark/traffic/train_seq4096.json] [--out <file>]

Run it on two checkouts (``git archive <commit> | tar -x -C <dir>``) and
compare the last line. A Mosaic kernel sits in the text as serialized MLIR
WITH its source locations, so any edit above a kernel in its file changes
the text though the kernel is the same: each kernel body is therefore
parsed and printed without locations before hashing (``raw`` is the hash of
the text as lowered).
"""
import argparse
import base64
import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def lowered_text(root, cfg, traffic):
    """The text ``step_ops.compile_step`` lowers, caught before it
    compiles."""
    sys.path.insert(0, root)
    sys.path.insert(0, os.path.join(root, "tools"))
    import jax
    import step_ops

    caught = []

    def stop(lowered, *args, **kwargs):
        caught.append(lowered.as_text())
        raise StopIteration

    real, jax.stages.Lowered.compile = jax.stages.Lowered.compile, stop
    try:
        step_ops.compile_step(cfg, traffic)
    except StopIteration:
        pass
    finally:
        jax.stages.Lowered.compile = real
    return caught[0]


def without_locations(text):
    """``text`` with every Mosaic kernel body replaced by the hash of its
    MLIR printed without debug locations."""
    import jax._src.interpreters.mlir as jmlir
    from jax._src.lib.mlir import ir

    def body(match):
        ctx = jmlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            asm = module.operation.get_asm(enable_debug_info=False)
        return "BODY<%s>" % hashlib.sha256(asm.encode()).hexdigest()

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)


def main():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=here, help="the checkout to lower")
    ap.add_argument("--config",
                    default="benchmark/configs/starcoder2-3b.train.json")
    ap.add_argument("--traffic",
                    default="benchmark/traffic/train_seq4096.json")
    ap.add_argument("--out", help="also write the stripped text here")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    with open(os.path.join(root, args.config)) as f:
        cfg = json.load(f)
    with open(os.path.join(root, args.traffic)) as f:
        traffic = json.load(f)
    text = lowered_text(root, cfg, traffic)
    stripped = without_locations(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(stripped)
    print("%s raw %s program %s" % (
        root, hashlib.sha256(text.encode()).hexdigest()[:16],
        hashlib.sha256(stripped.encode()).hexdigest()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
