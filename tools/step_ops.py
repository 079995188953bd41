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
import collections
import importlib.util
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CLOCK_HZ, HBM_BYTES_PER_S = 1.5e9, 819e9
FREE = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")
ITEM = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1, "u8": 1,
        "pred": 1, "f64": 8, "s64": 8, "u64": 8}
GROUPS = ("head and loss", "embedding", "feed-forward", "expert products",
          "expert routing", "attention projections", "attention glue",
          "flash", "short conv", "norms", "residual adds",
          "updates and casts")


def family_symbol(cfg):
    """The training symbol as the configuration's family file builds it."""
    bench = os.path.join(ROOT, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)  # the family imports lib.*
    spec = importlib.util.spec_from_file_location(
        "family", os.path.join(bench, "families",
                               cfg.get("family", "transformer_lm") + ".py"))
    family = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(family)
    return family.symbol(cfg, True)


def compile_step(cfg, traffic):
    """The compiled fused step of ``cfg``'s model at its sizes, and the
    symbol: lowered from described arrays, so nothing is allocated."""
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
    sym = family_symbol(cfg)
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


def node_groups(sym):
    """Graph node name -> group, from each node's operator and, for a
    FullyConnected, from what it feeds or is fed by."""
    nodes = [n for n in sym._nodes() if not n.is_var]
    feeds = collections.defaultdict(set)  # node -> operators that read it
    for n in nodes:
        for child, _ in n.inputs:
            feeds[id(child)].add(n.op.name)
    by_op = {"Embedding": "embedding", "LayerNorm": "norms",
             "RMSNorm": "norms", "ExpertFFN": "expert routing",
             "MultiHeadAttention": "attention glue", "ShortConv": "short conv",
             "Activation": "feed-forward", "elemwise_add": "residual adds",
             "_plus": "residual adds"}
    out = {}
    for n in nodes:
        near = feeds[id(n)] | {c.op.name for c, _ in n.inputs if not c.is_var}
        if n.op.name == "FullyConnected" and "MultiHeadAttention" in near:
            out[n.name] = "attention projections"
        elif n.op.name == "FullyConnected" and near & {"Activation",
                                                       "broadcast_mul"}:
            out[n.name] = "feed-forward"  # a gated one's up and down too
        elif n.op.name == "broadcast_mul" and "Activation" in near:
            out[n.name] = "feed-forward"
        else:
            out[n.name] = by_op.get(n.op.name, "head and loss")
    return out


_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][a-z\-]*)\((.*)$")
_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_SCOPE = re.compile(r'op_name="[^"]*?jvp\(([^()]+)\)')


def _bytes(type_text):
    total = 0
    for dtype, dims in _SHAPE.findall(type_text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * ITEM.get(dtype, 4)
    return total


def instruction_lines(text):
    """The program's lines, each instruction whole on one: the printer
    breaks a Mosaic call's ``kernel_metadata`` (a JSON object among the
    frontend attributes) over lines of its own."""
    out = []
    for line in text.splitlines():
        if out and (line.startswith('"')
                    or line.startswith("}") and line.strip() != "}"):
            out[-1] += line
        else:
            out.append(line)
    return out


def group_of(groups, name, node, kernel):
    """The group of the operation ``name`` traced from graph node ``node``
    (``kernel``: a Mosaic call)."""
    if name.startswith(("ragged-dot", "expert_gmm", "expert_tgmm")):
        # the grouped-matmul kernels: the repo's own (a Mosaic call is
        # named for its kernel) or, where the layer's gate leaves the
        # products to it, the compiler's with its tile metadata, which
        # carries no graph node's name
        return "expert products"
    group = groups.get(node, "updates and casts")
    return "flash" if kernel and group == "attention glue" else group


def device_ops(text, groups):
    """One dict for each operation of the entry computation that does
    work: name, opcode, result and operands (types without their layouts),
    kernel (a Mosaic call), node, group, est_ms, hbm_ms."""
    comps, name = {}, None
    for line in instruction_lines(text):
        if line and not line[0].isspace() and "{" in line and "(" in line:
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            name = name.lstrip("%")
            comps[name] = []
            if line.startswith("ENTRY"):
                entry = name
        elif name and _INSTR.match(line):
            comps[name].append(line)

    def scopes(line, depth=0):
        """(weight, node) of the instruction and of what it calls: the node
        of a matmul or a kernel inside names the fusion."""
        opcode = _INSTR.match(line).group(3)
        weight = 2 if opcode in ("convolution", "custom-call") else 0
        found = [(weight, m) for m in _SCOPE.findall(line)]
        called = re.search(r"calls=%(\S+?)[,\s]", line)
        if called and depth < 4:
            for inner in comps.get(called.group(1), ()):
                found += scopes(inner, depth + 1)
        return found

    types = {}
    for line in comps[entry]:
        m = _INSTR.match(line)
        types[m.group(1)] = re.sub(r"\{[^}]*\}", "", m.group(2))
    ops = []
    for line in comps[entry]:
        name, _, opcode, rest = _INSTR.match(line).groups()
        if opcode in FREE or opcode.endswith(("-start", "-done")):
            continue  # the second: asynchronous copies, beside the work
        cycles = re.search(r'"estimated_cycles":"(\d+)"', line)
        operands = [types.get(o, "") for o in
                    re.findall(r"%([\w.\-]+)", rest.split("), ")[0])]
        moved = sum(_bytes(t) for t in [types[name]] + operands)
        found = sorted(scopes(line), key=lambda t: -t[0])
        node = found[0][1] if found else ""
        kernel = "tpu_custom_call" in line
        group = group_of(groups, name, node, kernel)
        ops.append({"name": name, "opcode": opcode, "result": types[name],
                    "operands": operands, "kernel": kernel,
                    "node": node, "group": group,
                    "est_ms": (1e3 * int(cycles.group(1)) / CLOCK_HZ
                               if cycles else None),
                    "hbm_ms": 1e3 * moved / HBM_BYTES_PER_S})
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
    ops = device_ops(text, node_groups(sym))
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
