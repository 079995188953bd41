"""What each step program the executor built IS: one record a program.

``Executor.make_train_step`` calls :func:`note` once a call has built a
step program, with the compiled step that RUNS: the one jit built, asked
back from jit after the dispatch at the arguments it ran at (jit keeps its
lowering and its executable, so asking compiles nothing). On the
AUTO-layout path that is the program jit compiled for the learned layouts
(with ``chain`` > 1 the learning compile's own executable); on the plain
``jax.jit`` path the program the dispatch built, the first call's or a new
shape's. The executor's span attributes that summarise the layers come
from here too (:func:`layer_summary`).
:func:`programs` (``telemetry.programs()``) returns the records, oldest
first, each a dict:

- ``program`` (``"train_step"``), ``step`` (the ``executor.train_step``
  span's ``step`` that built it), ``build`` (the ``id`` of the
  ``executor.train_step.build`` record its building began under
  (``auto_layout_learn``); None on the plain path, which has none);
- ``ops``: every instruction of the optimized program that does work,
  ``{"name", "opcode", "kernel", "node"}``: its own name (what stands before
  `` = `` on its line, and on a profiler trace's), whether it is a Mosaic
  call, and the graph node it was traced from (the evaluator puts each
  node's name on what it traces: ``jvp(layer0_ffn1)`` is that node's
  forward, ``transpose(jvp(..))`` its backward). A fusion's node is that of
  the heaviest instruction inside it: a convolution or a custom call names
  it, else the fusion's own metadata. The body of a loop or a branch is
  listed in the loop's place. ``node`` is ``""`` for what is traced outside
  every node (the optimizer's rule, the executor's casts): not guessed. The
  asynchronous ``-start`` / ``-done`` pairs run beside the work and are
  left out. None where no compiled object was in hand (jit did not hand
  it back; a new shape on the AUTO-layout path);
- ``nodes``: ``{node: {"op", "inputs"}}``, each graph node's operator and
  the names of what it reads: enough to tell an attention projection from
  a feed-forward product;
- ``layers``: what the layers told of themselves as the step was traced
  (``ops/registry.py`` ``note_built``), each with its ``node``;
- ``memory``: ``argument``, ``output``, ``alias``, ``temp`` and
  ``generated_code`` bytes of the compiled program's ``memory_analysis()``
  (each None where ``ops`` is) and ``uncast_table_bytes`` (static: the
  arguments the step leaves in their master dtype, ``OpDef.master_inputs``);
- ``note_s``: what noting it cost the step that built it (fetching the
  HLO modules and the memory analysis), seconds; ``read_s``: what the first
  read cost, ``{"text", "parse"}`` seconds (None where ``ops`` is).

Noting costs next to nothing: it keeps the program's HLO modules (host
objects; never the compiled step, whose device memory the executor frees)
and the memory analysis's five numbers. The text is printed and parsed at
the FIRST :func:`programs` after it, once. ``telemetry.reset()`` empties the
list; ``MXNET_TELEMETRY=0`` records none; the newest ``KEEP`` are kept.
"""
from __future__ import annotations

import logging
import re
import threading
import time
from collections import deque

from .tracer import _master_enabled

KEEP = 16
FREE = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")
UMBRELLAS = ("while", "conditional", "call")  # their bodies do the work
INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][a-z\-]*)\((.*)$")
SCOPE = re.compile(r'op_name="[^"]*?jvp\(([^()]+)\)')
_BODIES = re.compile(r"(?:body|condition|to_apply|true_computation|"
                     r"false_computation)=%([\w.\-]+)"
                     r"|branch_computations=\{([^}]*)\}")
_MEMORY = ("argument", "output", "alias", "temp", "generated_code")

_records: deque = deque(maxlen=KEEP)
_lock = threading.Lock()


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


def computations(text):
    """``({computation: [instruction line, ...]}, the entry's name)``."""
    comps, entry, name = {}, None, None
    for line in instruction_lines(text):
        if line and not line[0].isspace() and "{" in line and "(" in line:
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            name = name.lstrip("%")
            comps[name] = []
            if line.startswith("ENTRY"):
                entry = name
        elif name and INSTR.match(line):
            comps[name].append(line)
    return comps, entry


def working_lines(comps, entry):
    """``(line, node)`` for every instruction that does work: the entry's,
    and in a loop's or a branch's place its body's."""
    def scopes(line, depth=0):
        # (weight, node) of the instruction and of what it calls: the node
        # of a matmul or a kernel inside names the fusion
        opcode = INSTR.match(line).group(3)
        weight = 2 if opcode in ("convolution", "custom-call") else 0
        found = [(weight, m) for m in SCOPE.findall(line)]
        called = re.search(r"calls=%(\S+?)[,\s]", line)
        if called and depth < 4:
            for inner in comps.get(called.group(1), ()):
                found += scopes(inner, depth + 1)
        return found

    out, seen = [], set()

    def walk(comp):
        if comp in seen:
            return
        seen.add(comp)
        for line in comps.get(comp, ()):
            opcode = INSTR.match(line).group(3)
            if opcode in UMBRELLAS:
                for one, many in _BODIES.findall(line):
                    for body in [one] if one else re.findall(r"%([\w.\-]+)",
                                                             many):
                        walk(body)
            elif opcode not in FREE and not opcode.endswith(("-start",
                                                             "-done")):
                found = sorted(scopes(line), key=lambda t: -t[0])
                out.append((line, found[0][1] if found else ""))

    walk(entry)
    return out


def device_ops(text):
    """``ops`` of a record, from the optimized program's text."""
    ops = []
    for line, node in working_lines(*computations(text)):
        name, _, opcode, _ = INSTR.match(line).groups()
        ops.append({"name": name, "opcode": opcode,
                    "kernel": "tpu_custom_call" in line, "node": node})
    return ops


def graph_nodes(symbol):
    """``nodes`` of a record: every operator node of ``symbol``."""
    return {n.name: {"op": n.op.name, "inputs": [c.name for c, _ in n.inputs]}
            for n in symbol._nodes() if not n.is_var}


def layer_summary(layers, symbol):
    """The ``executor.train_step`` span's static attributes, from what the
    step's layers told of themselves as it was traced (``layers``, the
    record's): where a ``LoopExitLoss`` was traced, ``loop_exits``, its
    exits; ``loop_layers``, the layers held, told apart by the leaves their
    mixer reads (its own and its projections'); ``loop_steps``, the mixers
    traced over the layers held: the times the step runs each layer. Where
    a multi-token-prediction module was traced, ``mtp_depth`` (its
    ``MultiTokenLoss`` nodes: the modules) and ``mtp_weight`` (the first
    one's weight in the loss). For the ``ExpertFFN`` layers, how many, and
    of one layer the experts held, the choices a token, the rows of its
    sorted-assignment buffer as allocated and the assignments expected
    under uniform routing."""
    def of(*ops):
        return [layer for layer in layers if layer["op"] in ops]

    attrs = {}
    exits = of("LoopExitLoss")
    if exits:
        nodes = {n.name: n for n in symbol._nodes() if not n.is_var}

        def leaves(node, depth=2):
            out = set()
            for child, _ in node.inputs:
                if child.is_var:
                    out.add(child.name)
                elif depth > 1:
                    out |= leaves(child, depth - 1)
            return frozenset(out)

        runs = [leaves(nodes[layer["node"]])
                for layer in of("MultiHeadAttention", "ShortConv")
                if layer["node"] in nodes]
        held = len(set(runs))
        attrs.update(loop_exits=exits[0]["exits"], loop_layers=held,
                     loop_steps=len(runs) / held if held else 0.0)
    mtp = of("MultiTokenLoss")
    if mtp:
        attrs.update(mtp_depth=len(mtp), mtp_weight=mtp[0]["weight"])
    moe = of("ExpertFFN")
    if moe:
        one = moe[0]
        attrs.update(moe_layers=len(moe),
                     moe_experts_held=one["experts_held"],
                     moe_top_k=one["top_k"],
                     moe_buffer_rows=one["buffer_rows"],
                     moe_expected_rows=one["expected_rows"])
    return attrs


class _Record:
    def __init__(self, fields, modules):
        self.fields = fields
        self.modules = modules  # until the first read; then None

    def read(self):
        if self.modules is not None:
            t0 = time.perf_counter()
            text = "\n\n".join(m.to_string() for m in self.modules)
            t1 = time.perf_counter()
            self.fields["ops"] = device_ops(text)
            self.fields["read_s"] = {"text": t1 - t0,
                                     "parse": time.perf_counter() - t1}
            self.modules = None
        return self.fields


def note(program, step, build, layers, nodes, compiled=None,
         uncast_table_bytes=0):
    """Keep a record of a program just built. ``compiled``: the
    ``jax.stages.Compiled`` in hand, if any; it is not kept."""
    if not _master_enabled():
        return
    t0 = time.perf_counter()
    memory, modules = dict.fromkeys(_MEMORY), None
    if compiled is not None:
        try:
            stats = compiled.memory_analysis()
            memory = {k: int(getattr(stats, k + "_size_in_bytes"))
                      for k in _MEMORY}
            modules = compiled.runtime_executable().hlo_modules()
        except Exception:  # a backend that says neither: nothing to read
            logging.getLogger("mxnet_tpu").debug(
                "program record: no text or memory analysis", exc_info=True)
            memory, modules = dict.fromkeys(_MEMORY), None
    memory["uncast_table_bytes"] = int(uncast_table_bytes)
    fields = {"program": program, "step": step, "build": build, "ops": None,
              "nodes": nodes, "layers": [dict(r) for r in layers],
              "memory": memory, "note_s": time.perf_counter() - t0,
              "read_s": None}
    with _lock:
        _records.append(_Record(fields, modules))


def programs():
    """The records, oldest first; the first call after a record was noted
    prints and parses its program."""
    with _lock:
        return [r.read() for r in _records]


def clear():
    with _lock:
        _records.clear()
