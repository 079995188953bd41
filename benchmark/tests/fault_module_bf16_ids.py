#!/usr/bin/env python3
"""Not a test: a witness, run by hand, of the fault that keeps
``Module.fit_step`` out of the cells (PERF.md, Open questions 1).

    python3 benchmark/tests/fault_module_bf16_ids.py [--config <file>]
        [--layers n] [--batch b] [--seq t] [--seed s]

One step on the same token ids through two entries of the program, both at
``compute_dtype="bfloat16"``, momentum SGD: the benchmark's own
(``simple_bind(type_dict=int32)`` + ``make_train_step``, the driver's
``Trainer``) and ``Module.fit_step``. After one step the embedding's
momentum is non-zero in exactly the rows that were looked up. It prints,
for each entry, how many rows moved, whether they are the ids that were
fed, and whether they are those ids rounded to bfloat16's eight bits (the
largest round up to the vocabulary's size, a row that is not there: the
loss is then NaN); one JSON line last. Defaults: the cell's widths, vocabulary, batch and sequence
at depth 1 (``Module`` cannot hold depth 4 in 16 GB: Open questions 2).
"""
import argparse
import gc
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)


def rows_moved(momentum):
    import jax.numpy as jnp
    import numpy as np

    return np.nonzero(np.asarray(jnp.abs(momentum).sum(axis=1)))[0]


def verdict(rows, ids, vocab):
    import jax.numpy as jnp
    import numpy as np

    fed = np.unique(ids)
    rounded = np.unique(np.asarray(
        jnp.asarray(ids, jnp.float32).astype(jnp.bfloat16)
        .astype(jnp.int32)))
    inside = rounded[rounded < vocab]  # some round up to the vocabulary size
    return {"rows_moved": int(len(rows)), "distinct_ids_fed": int(len(fed)),
            "are_the_ids_fed": bool(np.array_equal(rows, fed)),
            "distinct_ids_rounded_to_bf16": int(len(rounded)),
            "of_them_outside_the_vocabulary": int(len(rounded) - len(inside)),
            "are_the_ids_rounded_to_bf16": bool(np.array_equal(rows,
                                                               inside))}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=os.path.join(
        BENCH, "configs", "starcoder2-3b.train.json"))
    ap.add_argument("--layers", type=int, default=1)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    import jax
    import numpy as np
    import mxnet_tpu as mx
    import run as bench

    cfg = dict(json.load(open(args.config)), num_hidden_layers=args.layers)
    tr = {"batch": args.batch, "seq_len": args.seq,
          "compute_dtype": "bfloat16",
          "optimizer": {"learning_rate": 0.01, "momentum": 0.9}}
    fam = bench.load_module("families", cfg["family"])
    drv = bench.load_module("drivers", "train_steps")
    dev = jax.devices()[0]
    ctx = bench.Context(cfg=cfg, traffic=tr, family=fam, seed=args.seed,
                        devices=[dev])
    data, label = fam.make_batches(cfg, tr, args.seed, 1)[0]
    ids = np.asarray(data["data"])
    out = {"device": dev.device_kind, "layers": args.layers,
           "batch": args.batch, "seq_len": args.seq,
           "vocab_size": cfg["vocab_size"],
           "ids_above_256": int((ids > 256).sum()), "ids": int(ids.size)}

    trainer = drv.Trainer(ctx)
    outs = trainer.step({**data, **label})
    out["make_train_step"] = dict(
        verdict(rows_moved(trainer.states["embed_weight"]), ids,
                cfg["vocab_size"]),
        loss=fam.loss_from_outputs(outs, label))
    print("make_train_step:", out["make_train_step"], flush=True)
    del trainer, outs
    gc.collect()

    dev_ctx = mx.Context(dev.platform, dev.id)
    shape = (args.batch, args.seq)
    mod = mx.mod.Module(fam.symbol(cfg, True), context=dev_ctx,
                        compute_dtype="bfloat16")
    mod.bind(data_shapes=[("data", shape)],
             label_shapes=[("softmax_label", shape)], for_training=True)
    mod.init_params(arg_params={
        n: mx.nd.NDArray(a, ctx=dev_ctx)
        for n, a in fam.init_params(cfg, args.seed).items()},
        aux_params={}, allow_missing=False)
    mod.init_optimizer(kvstore=None, optimizer="sgd", optimizer_params={
        "learning_rate": 0.01, "momentum": 0.9, "rescale_grad": 1.0})
    mod.fit_step(mx.io.DataBatch(
        [mx.nd.NDArray(data["data"], ctx=dev_ctx)],
        [mx.nd.NDArray(label["softmax_label"], ctx=dev_ctx)]))
    assert mod.fit_step_path == "fused", mod.fit_step_path
    _, states = mod.fit_step_arrays()
    momentum = states["embed_weight"]
    momentum = momentum[0] if isinstance(momentum, (tuple, list)) \
        else momentum
    out["module_fit_step"] = dict(
        verdict(rows_moved(momentum), ids, cfg["vocab_size"]),
        loss=float(np.asarray(mod.get_outputs()[0].asnumpy(),
                              np.float32).reshape(-1)[0]))
    print("Module.fit_step:", out["module_fit_step"], flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
