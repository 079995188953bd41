#!/usr/bin/env python
"""Every device operation of a benchmark configuration's fused training
step, as the TPU's compiler builds it for a DESCRIBED v5e: no chip, here on
the CPU sandbox, a quarter of a minute.

    JAX_PLATFORMS=cpu python tools/step_ops.py [--layers N] [--group G]
        [--config benchmark/configs/starcoder2-3b.train.json]
        [--traffic benchmark/traffic/train_seq4096.json]

For each operation of the optimized program: its result, the graph node it
was traced from (the evaluator puts each node's name on what it traces:
``jvp(layer0_ffn1)`` is that node's forward, ``transpose(jvp(..))`` its
backward), XLA's own ``estimated_cycles`` as milliseconds at 1.5 GHz, and
the milliseconds its operands and result take to cross HBM once (a floor;
too high where only some rows of an operand are read, as in a gather).
Grouped by what the node is for. The times are the compiler's guesses: they
were off by up to 50% either way against the chip (PERF.md, PR 30), so they
rank nothing alone; they say what the program DOES, and where to look in a
trace. The symbol is the one the configuration's family file builds
(``benchmark/families/<family>.py``).
"""
import argparse
import importlib.util
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))  # lib.*, the families

CLOCK_HZ, HBM_BYTES_PER_S = 1.5e9, 819e9
ITEM = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1, "u8": 1,
        "pred": 1, "f64": 8, "s64": 8, "u64": 8}


def family_symbol(cfg):
    """The training symbol as the configuration's family file builds it."""
    spec = importlib.util.spec_from_file_location(
        "family", os.path.join(ROOT, "benchmark", "families",
                               cfg.get("family", "transformer_lm") + ".py"))
    family = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(family)
    return family.symbol(cfg, True)


def compile_step(cfg, traffic, sym=None):
    """The compiled fused step of ``cfg``'s model (of ``sym``, where one is
    given) at its sizes, and the symbol: lowered from described arrays, so
    nothing is allocated."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import mxnet_tpu as mx
    import mxnet_tpu.ops.pallas as pallas
    from mxnet_tpu import random as mxrandom
    from mxnet_tpu.executor import Executor
    from mxnet_tpu.ndarray import NDArray

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    sym = family_symbol(cfg) if sym is None else sym
    inputs = {n: (traffic["batch"], traffic["seq_len"])
              for n in ("data", "softmax_label")}
    shapes, _, aux_shapes = sym.infer_shape(**inputs)
    names = sym.list_arguments()

    def described(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=chip)

    args = {n: NDArray(described(s, "int32" if n in inputs else "float32"))
            for n, s in zip(names, shapes)}
    aux = {n: NDArray(described(s, "float32"))
           for n, s in zip(sym.list_auxiliary_states(), aux_shapes)}
    exe = Executor(sym, mx.cpu(), args, dict(args),
                   {n: "null" if n in inputs else "write" for n in names},
                   aux, compute_dtype=traffic["compute_dtype"])
    leaves = [n for n in names if n not in inputs]
    o = traffic["optimizer"]
    rule = mx.optimizer.create(
        "sgd", learning_rate=o["learning_rate"], momentum=o["momentum"],
        wd=0.0, rescale_grad=1.0,
        param_idx2name=dict(enumerate(leaves))).pure_rule()

    def update(params, grads, states, lr, wd):
        new = {n: rule(params[n], grads[n], states[n], lr[i], wd[i])
               for i, n in enumerate(leaves)}
        return ({n: p for n, (p, _) in new.items()},
                {n: s for n, (_, s) in new.items()})

    step = exe.make_train_step(update).step
    params = {n: args[n]._data for n in leaves}
    key = mxrandom.next_key()
    per_leaf = described((len(leaves),), "float32")
    on_tpu = pallas.on_tpu
    pallas.on_tpu = lambda: True  # the flash gate, answered for the chip
    try:
        lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
            params, dict(params), {n: a._data for n, a in aux.items()},
            described(key.shape, key.dtype),
            {n: args[n]._data for n in inputs}, per_leaf, per_leaf)
    finally:
        pallas.on_tpu = on_tpu
    return lowered.compile(), sym


_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def _bytes(type_text):
    total = 0
    for dtype, dims in _SHAPE.findall(type_text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * ITEM.get(dtype, 4)
    return total


def device_ops(text, nodes):
    """One dict for each operation of the program that does work, as the
    program's own record lists them (``mxnet_tpu/telemetry/programs.py``:
    name, opcode, kernel, node) and as the benchmark groups them
    (``benchmark/lib/groups.py``), with its result and operands (types
    without their layouts), est_ms and hbm_ms. ``nodes``: the record's
    ``nodes`` (``graph_nodes(symbol)``)."""
    from lib import groups
    from mxnet_tpu.telemetry.programs import INSTR, computations
    from mxnet_tpu.telemetry.programs import device_ops as record_ops

    lines, types = {}, {}
    for comp in computations(text)[0].values():
        for line in comp:
            m = INSTR.match(line)
            lines[m.group(1)] = line
            types[m.group(1)] = re.sub(r"\{[^}]*\}", "", m.group(2))
    by_node = groups.node_groups(nodes)
    ops = []
    for op in record_ops(text):
        line = lines[op["name"]]
        cycles = re.search(r'"estimated_cycles":"(\d+)"', line)
        operands = [types.get(o, "") for o in re.findall(
            r"%([\w.\-]+)", INSTR.match(line).group(4).split("), ")[0])]
        moved = sum(_bytes(t) for t in [types[op["name"]]] + operands)
        ops.append(dict(op, group=groups.group_of(nodes, by_node, op),
                        result=types[op["name"]], operands=operands,
                        est_ms=(1e3 * int(cycles.group(1)) / CLOCK_HZ
                                if cycles else None),
                        hbm_ms=1e3 * moved / HBM_BYTES_PER_S))
    return ops


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=os.path.join(
        ROOT, "benchmark", "configs", "starcoder2-3b.train.json"))
    ap.add_argument("--traffic", default=os.path.join(
        ROOT, "benchmark", "traffic", "train_seq4096.json"))
    ap.add_argument("--layers", type=int, help="another depth than the file's")
    ap.add_argument("--group", help="list this group's operations only")
    ap.add_argument("--min-ms", type=float, default=0.05,
                    help="list operations estimated at least this long")
    ap.add_argument("--text", help="also write the optimized program here")
    args = ap.parse_args()
    with open(args.config) as f:
        cfg = json.load(f)
    with open(args.traffic) as f:
        traffic = json.load(f)
    if args.layers:
        cfg["num_hidden_layers"] = args.layers
    compiled, sym = compile_step(cfg, traffic)
    text = compiled.as_text()
    if args.text:
        with open(args.text, "w") as f:
            f.write(text)
    from lib.groups import GROUPS
    from mxnet_tpu.telemetry.programs import graph_nodes

    ops = device_ops(text, graph_nodes(sym))
    mem = compiled.memory_analysis()
    print("the program's arguments %.2f GB, outputs %.2f GB (%.2f GB of them "
          "in the arguments' place), temporaries %.2f GB" % (
              mem.argument_size_in_bytes / 1e9, mem.output_size_in_bytes / 1e9,
              mem.alias_size_in_bytes / 1e9, mem.temp_size_in_bytes / 1e9))
    print("%d device operations; est = XLA's estimated_cycles at 1.5 GHz, "
          "hbm = operands and result once over 819 GB/s; compiler's "
          "guesses, not times" % len(ops))
    for group in GROUPS:
        mine = [o for o in ops if o["group"] == group]
        if not mine or (args.group and args.group != group):
            continue
        print("\n%-24s %4d ops  est %8.2f ms  hbm %8.2f ms" % (
            group, len(mine), sum(o["est_ms"] or 0.0 for o in mine),
            sum(o["hbm_ms"] for o in mine)))
        for o in sorted(mine, key=lambda o: -(o["est_ms"] or 0.0)):
            est = o["est_ms"]  # a kernel or a scatter carries no estimate
            if max(est or 0.0, o["hbm_ms"] if est is None else 0.0) \
                    >= args.min_ms:
                print("  %-34s %-48s %-22s est %7s  hbm %7.3f" % (
                    o["name"][:34], o["result"][:48], o["node"][:22],
                    "-" if est is None else "%.3f" % est, o["hbm_ms"]))
    print("\nall: est %.2f ms, hbm %.2f ms" % (
        sum(o["est_ms"] or 0.0 for o in ops), sum(o["hbm_ms"] for o in ops)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
