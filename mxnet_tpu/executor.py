"""Graph executor.

TPU-native analogue of src/executor/graph_executor.{h,cc} +
include/mxnet/executor.h:34-104. Where the reference builds per-node engine
ops with memory planning and bulk segments, this executor compiles the WHOLE
symbolic graph into:

- one jitted forward computation  (Forward,  graph_executor.cc:32), and
- one jitted forward+backward computation (Backward, graph_executor.cc:45),
  derived with jax.vjp — the analogue of nnvm::pass::Gradient
  (graph_executor.cc:233) — with grad_req write/add/null semantics
  (OpReqType, operator.h:24-37) applied in-graph. `add` accumulation donates
  the old gradient buffer so XLA updates it in place (kAddTo ≡ donation).

Memory planning, inplace reuse, and op fusion are XLA's buffer assignment —
the PlanMemory/DetectInplaceAddTo passes have no hand-written counterpart
here by design (SURVEY §7 translation table).

The optional `shared_exec` reuses argument/grad buffers across executors
(bucketing support, graph_executor.cc:452-564 shared pools).
"""
from __future__ import annotations

import contextlib
import logging
import os
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import progcache as _progcache
from . import random as _random
from . import telemetry as _telemetry
from .analysis import compile_witness as _witness
from .base import MXNetError
from .context import Context, default_context
from .ndarray import NDArray
from .ops.matrix import gathered_rows_as
from .ops.registry import built_layers
from .telemetry.programs import graph_nodes as _graph_nodes
from .telemetry.programs import note as _note_program


def _cast_floats(tree, dtype, src=None, skip=()):
    """Cast float leaves of a list/dict tree to dtype (inside jit, so XLA
    fuses the converts into neighbouring ops). Only leaves of dtype `src`
    (default float32) are touched, so integer/bool leaves pass through, and
    so do the entries of a dict tree that `skip` names."""
    src = jnp.float32 if src is None else jnp.dtype(src)

    def cast(v):
        if hasattr(v, "dtype") and v.dtype == src:
            return v.astype(dtype)
        return v
    if skip:
        return {k: v if k in skip else cast(v) for k, v in tree.items()}
    return jax.tree_util.tree_map(cast, tree)


def _gathered_only(symbol):
    """The argument leaves of ``symbol`` that nothing reads but the
    ``weight`` input of an ``Embedding``. Casting such a table is a pass
    over the whole of it for the sake of the few rows a step gathers; the
    fused step leaves it in its master dtype and ``Embedding`` casts the
    rows (ops/matrix.py ``gathered_rows_as``)."""
    only = {}
    for node in symbol._nodes():
        if node.is_var:
            continue
        for pos, (child, _) in enumerate(node.inputs):
            if child.is_var and not child.is_aux:
                gathered = (node.op.name == "Embedding" and
                            node.op.get_arg_names(node.attrs)[pos] == "weight")
                only[child.name] = only.get(child.name, True) and gathered
    heads = {node.name for node, _ in symbol._entries if node.is_var}
    return frozenset(n for n, ok in only.items() if ok and n not in heads)


def _float32_state(symbol):
    """The auxiliary states that stay float32 under a compute dtype: those
    their op declares (``OpDef.float32_aux``; ``ExpertFFN``'s
    ``expert_bias`` chooses experts, and rounded to bfloat16 it chooses
    others)."""
    keep = set()
    for node in symbol._nodes():
        if node.is_var or not node.op.float32_aux:
            continue
        aux = node.op.get_aux_names(node.attrs)
        states = node.inputs[len(node.inputs) - len(aux):]
        keep.update(child.name for name, (child, _) in zip(aux, states)
                    if name in node.op.float32_aux)
    return frozenset(keep)


def _layer_attrs(layers, symbol):
    """The ``executor.train_step`` span's static attributes for the
    ``ExpertFFN`` layers among what the step's layers told of themselves as
    it was traced (``ops/registry.py`` ``built_layers``): how many, and of
    one layer the experts held, the choices a token, the rows of its
    sorted-assignment buffer as allocated and the assignments expected
    under uniform routing; for a stack run several times (``_loop_attrs``);
    and where a multi-token-prediction module was traced, ``mtp_depth``
    (its ``MultiTokenLoss`` nodes: the modules) and ``mtp_weight`` (the
    first one's weight in the loss). The rest of what the layers say is the
    program record's (``telemetry.programs()``: ``layers``)."""
    attrs = _loop_attrs(layers, symbol)
    mtp = [layer for layer in layers if layer["op"] == "MultiTokenLoss"]
    if mtp:
        attrs.update(mtp_depth=len(mtp), mtp_weight=mtp[0]["weight"])
    moe = [layer for layer in layers if layer["op"] == "ExpertFFN"]
    if not moe:
        return attrs
    one = moe[0]
    return dict(attrs, moe_layers=len(moe),
                moe_experts_held=one["experts_held"], moe_top_k=one["top_k"],
                moe_buffer_rows=one["buffer_rows"],
                moe_expected_rows=one["expected_rows"])


def _loop_attrs(layers, symbol):
    """Where a ``LoopExitLoss`` was traced: ``loop_exits``, its exits;
    ``loop_layers``, the layers held, told apart by the leaves their mixer
    reads (its own and its projections'); ``loop_steps``, the mixers traced
    over the layers held: the times the step runs each layer."""
    exits = [layer for layer in layers if layer["op"] == "LoopExitLoss"]
    if not exits:
        return {}
    nodes = {n.name: n for n in symbol._nodes() if not n.is_var}

    def leaves(node, depth=2):
        out = set()
        for child, _ in node.inputs:
            if child.is_var:
                out.add(child.name)
            elif depth > 1:
                out |= leaves(child, depth - 1)
        return frozenset(out)

    runs = [leaves(nodes[layer["node"]]) for layer in layers
            if layer["op"] in ("MultiHeadAttention", "ShortConv")
            and layer["node"] in nodes]
    held = len(set(runs))
    return dict(loop_exits=exits[0]["exits"], loop_layers=held,
                loop_steps=len(runs) / held if held else 0.0)


def _relaid(tree, formats):
    """``tree`` put into ``formats``, the arrays it was copied from freed:
    (the tree, the formats its leaves hold, whether every leaf took the
    format asked for). The step consumes what it is passed (the donation
    contract), but a relayout copies, and the caller's originals would
    otherwise live beside the copies until the first step returns: 2.1 GB
    that the chip did not have for SmallThinker's expert matrices (PERF.md,
    PR 33). A copy that does not REPORT the layout asked for is not used:
    that leaf stays the caller's array in the layout it has, and the step
    is then jitted for that (a float32[16,2560,768] leaf came back so, and
    a step jitted for what the copy reported read it permuted)."""
    flat, treedef = jax.tree_util.tree_flatten(tree)
    out, had, whole = [], [], True
    for old, fmt in zip(flat, treedef.flatten_up_to(formats)):
        new = jax.device_put(old, fmt)
        want = getattr(fmt, "layout", None)
        if want is not None and new.format.layout != want:
            logging.getLogger("mxnet_tpu").warning(
                "train step: a %s%s leaf did not take its learned layout; "
                "it keeps the layout it has", old.dtype, list(old.shape))
            new, fmt, whole = old, old.format, False
        elif new is not old and isinstance(old, jax.Array) and \
                old.is_fully_addressable and not old.is_deleted() and \
                {s.data.unsafe_buffer_pointer()
                 for s in old.addressable_shards}.isdisjoint(
                     s.data.unsafe_buffer_pointer()
                     for s in new.addressable_shards):
            old.delete()
        out.append(new)
        had.append(fmt)
    return treedef.unflatten(out), treedef.unflatten(had), whole


def _under_mesh(eval_fn, mesh):
    """``eval_fn`` traced with ``mesh`` announced to the ops
    (parallel.mesh.partitioned_over); ``eval_fn`` itself without a mesh."""
    if mesh is None:
        return eval_fn
    from .parallel.mesh import partitioned_over

    def under_mesh(*args):
        with partitioned_over(mesh):
            return eval_fn(*args)

    return under_mesh


def bind_span():
    """The ``executor.bind`` span: the one ``Symbol.simple_bind`` opened
    around its allocations when the constructor runs inside it, else a
    new one."""
    open_ = _telemetry.open_spans()
    if open_ and open_[-1].name == "executor.bind":
        return contextlib.nullcontext(open_[-1])
    return _telemetry.span("executor.bind", domain="executor")


class Executor:
    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None, shared_exec=None,
                 compute_dtype=None, mesh=None):
        with bind_span() as sp:
            self._init(symbol, ctx, args, args_grad, grad_req, aux_states,
                       group2ctx, shared_exec, compute_dtype, mesh)
            sp.annotate(n_args=len(self.arg_dict), arg_bytes=sum(
                a.size * np.dtype(a.dtype).itemsize
                for a in self.arg_dict.values()))

    def _init(self, symbol, ctx, args, args_grad, grad_req, aux_states,
              group2ctx, shared_exec, compute_dtype, mesh):
        """mesh: the jax Mesh the bound arrays are sharded over, when the
        graph is partitioned by XLA's SPMD pass (the executor group's data
        mesh); ops that must split themselves read it at trace time.

        compute_dtype: optional low-precision compute dtype ("bfloat16").
        Mixed precision the TPU-native way: parameters, gradients, and
        optimizer state stay float32 (master weights); inside the single
        jitted graph all float32 leaves are cast to compute_dtype so matmuls
        and convs hit the MXU's bf16 path, and outputs/gradients are cast
        back to float32. This is the analogue of the reference's fp16
        training path (Cast ops + float16 data, tests/python/train/
        test_dtype.py) — bf16 needs no loss scaling, unlike fp16.
        Default from MXNET_COMPUTE_DTYPE env var."""
        self._symbol = symbol
        if compute_dtype is None:
            compute_dtype = os.environ.get("MXNET_COMPUTE_DTYPE") or None
        self._compute_dtype = (jnp.dtype(compute_dtype)
                               if compute_dtype not in (None, "", "float32")
                               else None)
        # auxiliary states the compute dtype leaves alone
        self._state32 = (_float32_state(symbol)
                         if self._compute_dtype is not None else ())
        self._ctx = ctx if isinstance(ctx, Context) else (ctx[0] if ctx else default_context())
        self._group2ctx = group2ctx
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()

        # ---- normalize args into name->NDArray dicts
        self.arg_dict: Dict[str, NDArray] = self._to_dict(args, arg_names, "args")
        if shared_exec is not None:
            # share buffers with the master executor (bucketing)
            for n in arg_names:
                if n in shared_exec.arg_dict and shared_exec.arg_dict[n].shape == self.arg_dict[n].shape:
                    self.arg_dict[n] = shared_exec.arg_dict[n]
        self.aux_dict: Dict[str, NDArray] = self._to_dict(aux_states or {}, aux_names, "aux")

        # ---- grad_req per-arg
        if isinstance(grad_req, str):
            self.grad_req = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self.grad_req = dict(zip(arg_names, grad_req))
        else:
            self.grad_req = {n: grad_req.get(n, "null") for n in arg_names}

        if args_grad is None:
            self.grad_dict: Dict[str, NDArray] = {}
        else:
            self.grad_dict = self._to_dict(args_grad, arg_names, "args_grad", allow_missing=True)
        if shared_exec is not None:
            for n, g in shared_exec.grad_dict.items():
                if n in self.grad_dict and g.shape == self.grad_dict[n].shape:
                    self.grad_dict[n] = g
        for n in arg_names:
            if self.grad_req.get(n, "null") != "null" and n not in self.grad_dict:
                self.grad_req[n] = "null"

        self._arg_names = arg_names
        self._aux_names = aux_names
        self._eval_fn = _under_mesh(symbol.build_eval(), mesh)
        self._fwd_cache: Dict[bool, Any] = {}
        self._bwd_fn = None
        self._fwd_bwd_fn = None
        self.outputs: List[NDArray] = []
        self._monitor_cb = None
        self._monitored_rng = None
        self._rng_counter = 0
        self._last_rng = None
        self._graph_needs_rng = None  # computed lazily on first use
        self._train_steps = 0  # calls into make_train_step's step: span attr

    @staticmethod
    def _to_dict(values, names, what, allow_missing=False):
        if values is None:
            values = {}
        if isinstance(values, dict):
            out = dict(values)
        else:
            values = list(values)
            if len(values) != len(names) and not allow_missing:
                raise MXNetError(
                    "%s: expected %d entries, got %d" % (what, len(names), len(values))
                )
            out = {n: v for n, v in zip(names, values) if v is not None}
        missing = [n for n in names if n not in out]
        if missing and not allow_missing and what != "args_grad":
            raise MXNetError("%s missing entries for %s" % (what, missing))
        return out

    # --- compiled paths ---------------------------------------------------
    def _get_fwd(self, is_train: bool):
        fn = self._fwd_cache.get(is_train)
        if fn is None:
            eval_fn = self._eval_fn
            cd, state32 = self._compute_dtype, self._state32

            def fwd(arg_values, aux_values, rng):
                if cd is not None:
                    arg_values = _cast_floats(arg_values, cd)
                    aux_values = _cast_floats(aux_values, cd, skip=state32)
                outs, aux_up = eval_fn(arg_values, aux_values, is_train, rng)
                if cd is not None:
                    outs = _cast_floats(outs, jnp.float32, src=cd)
                    aux_up = _cast_floats(aux_up, jnp.float32, src=cd)
                return outs, aux_up

            fn = jax.jit(fwd)
            self._fwd_cache[is_train] = fn
        return fn

    def _get_fwd_bwd(self):
        """Fused forward+backward — ONE XLA computation for the whole
        training step graph (north-star: single HLO per symbolic subgraph)."""
        if self._fwd_bwd_fn is None:
            eval_fn = self._eval_fn
            grad_names = [n for n in self._arg_names if self.grad_req.get(n) != "null"]
            reqs = tuple(self.grad_req[n] for n in grad_names)

            cd, state32 = self._compute_dtype, self._state32

            def fwd_bwd(arg_values, aux_values, rng, head_grads, old_grads):
                grad_vals = [arg_values[n] for n in grad_names]

                def f(*gvals):
                    av = dict(arg_values)
                    for n, v in zip(grad_names, gvals):
                        av[n] = v
                    auxv = aux_values
                    if cd is not None:
                        # bf16 compute; vjp of the cast returns f32 grads
                        # (transpose of convert_element_type casts back).
                        av = _cast_floats(av, cd)
                        auxv = _cast_floats(auxv, cd, skip=state32)
                    outs, aux_up = eval_fn(av, auxv, True, rng)
                    if cd is not None:
                        outs = _cast_floats(outs, jnp.float32, src=cd)
                        aux_up = _cast_floats(aux_up, jnp.float32, src=cd)
                    return outs, aux_up

                (outs, aux_up), vjp = jax.vjp(lambda *g: f(*g), *grad_vals, has_aux=False)
                if head_grads is None:
                    head_grads = [jnp.ones_like(o) for o in outs]
                grads = vjp((list(head_grads), {k: jnp.zeros_like(v) for k, v in aux_up.items()}))
                new_grads = []
                for n, g, req in zip(grad_names, grads, reqs):
                    # old_grads holds ONLY add-req buffers; write-req grads
                    # need no host-side zeros (kAddTo vs kWriteTo)
                    new_grads.append(old_grads[n] + g if req == "add" else g)
                return outs, aux_up, new_grads

            self._fwd_bwd_fn = jax.jit(fwd_bwd, donate_argnums=(4,))
            self._grad_names = grad_names
        return self._fwd_bwd_fn

    def make_train_step(self, update_fn, chain=1, mesh=None, shard_axis="data"):
        """Build ONE jitted computation for a whole training step:
        forward + backward + optimizer update, with parameter and
        optimizer-state buffers donated so XLA updates them in place.

        This is the full-fusion analogue of the reference's bulk segment
        execution (graph_executor.cc:681-759 batches ops into one engine op;
        here the step — including the update the reference runs as separate
        fused optimizer kernels, optimizer_op.cc — is a single XLA program,
        so per-step host work is one dispatch and one pytree flatten).

        update_fn(params, grads, states, *extra) -> (new_params, new_states)
        must be pure/traceable (e.g. built from optimizer.create's update
        rule); extra positional args to step() are forwarded to it as traced
        values (dynamic lr/wd arrays and the like).
        Returns step(params, states, data_values: dict) ->
        (outputs, new_params, new_states). `params` covers the grad-bearing
        args; `data_values` the rest (data/label). Aux states (BN stats) are
        threaded internally and updated in place on self.aux_dict.

        DONATION CONTRACT: the params/states passed to step() are consumed
        (their device buffers are reused for the outputs — kWriteInplace).
        Do not alias them with live NDArrays; thread the returned values
        into the next call.

        ``chain`` > 1 runs that many optimizer steps (same feed) inside
        ONE device program via lax.scan — the bulk-execution analogue
        for dispatch-bound loops (one host dispatch per chain).
        Aux states (BN stats) thread through the scan carry.

        On TPU the step additionally compiles with AUTO input/output
        layouts for params/states (jax.experimental.layout): without
        this, XLA keeps the f32 master weights in the row-major entry
        layout and inserts per-step layout copies around every conv
        weight's use and update (~1 ms/step at bs128 for ResNet-50 in a
        device trace: the 214 anonymous data-formatting copies). The
        first call relayouts the caller's arrays once; returned params
        stay in the chosen layouts thereafter.
        MXNET_STEP_AUTO_LAYOUT=0 disables.

        ``mesh``: a jax Mesh with a data-parallel axis ``shard_axis``.
        When its size is > 1, MXNET_SHARDED_UPDATE picks the ZeRO stage
        (Xu et al., PAPERS.md; docs/parallelism.md "ZeRO-2/3"). Stage 1
        (default): f32 master weights and optimizer state live
        1/N-sharded across the data axis, gradients are reduce-scattered
        onto the shards, each replica updates only its shard, and the
        new weights are all-gathered for the next forward. Stage 2
        additionally scatters each gradient bucket at its producer site
        as backward emits it (zero2_grad_scatter — full gradients never
        materialize). Stage 3 additionally keeps the parameters sharded
        THROUGH the step: leaves all-gather on demand in forward and
        re-gather in backward (zero3_gather + zero3_remat), so
        param+grad+opt bytes/chip are all ~1/N. Every stage is expressed
        as sharding constraints inside the ONE donated program, so XLA's
        SPMD partitioner places (and overlaps) the collectives; 0 opts
        out. The first call commits params/states to the sharded layout;
        returned values stay sharded, so thread them back in as usual.
        """
        eval_fn = _under_mesh(self._eval_fn, mesh)
        grad_names = list(self._grad_names_list())
        data_names = [n for n in self._arg_names if n not in set(grad_names)]
        cd = self._compute_dtype
        tables = _gathered_only(self._symbol) if cd is not None else ()
        chain = max(1, int(chain))
        state32 = self._state32
        built = built_layers()  # what the layers tell of themselves, as traced
        from .parallel import collectives as _coll
        stage = _coll.sharded_stage(mesh, shard_axis)
        sharded = stage >= 1

        def one_step(params, states, aux_values, rng, data_values, *extra):
            # a (re)trace lists the layers again, and the backward's
            # choices with them: a custom VJP's backward is traced when
            # the VJP is applied, after the forward's evaluation
            del built.layers[:]
            with built:
                return _one_step(params, states, aux_values, rng,
                                 data_values, *extra)

        def _one_step(params, states, aux_values, rng, data_values, *extra):
            # Stage 1/2: params arrive 1/N-sharded; gather the whole tree
            # replicated up front for forward/backward (vjp's transpose of
            # the gather, fused with the data-parallel psum, is exactly
            # reduce_scatter). Stage 3 differentiates the SHARDED tree
            # directly: each leaf is gathered on demand inside `f` and
            # re-gathered in backward (zero3_remat drops the gathered
            # copies from the residuals), so full weights are transient.
            arg = (_coll.replicate_constrain(params, mesh)
                   if sharded and stage < 3 else params)

            def f(p):
                full = (_coll.zero3_gather(p, mesh, shard_axis)
                        if stage >= 3 else p)
                if stage >= 2:
                    # ZeRO-2: backward emits reduce-scattered gradient
                    # shards bucket-by-bucket as it runs (overlapping the
                    # remaining backward compute) instead of materializing
                    # the full gradient tree first
                    full = _coll.zero2_grad_scatter(full, mesh, shard_axis)
                av = dict(data_values)
                av.update(full)
                auxv = aux_values
                if cd is not None:
                    av = _cast_floats(av, cd, skip=tables)
                    auxv = _cast_floats(auxv, cd, skip=state32)
                with gathered_rows_as(cd):
                    outs, aux_up = eval_fn(av, auxv, True, rng)
                if cd is not None:
                    outs = _cast_floats(outs, jnp.float32, src=cd)
                    aux_up = _cast_floats(aux_up, jnp.float32, src=cd)
                return outs, aux_up

            fd = _coll.zero3_remat(f) if stage >= 3 else f
            (outs, aux_up), vjp = jax.vjp(fd, arg)
            (grads,) = vjp(([jnp.ones_like(o) for o in outs],
                            {k: jnp.zeros_like(v) for k, v in aux_up.items()}))
            if sharded:
                grads = _coll.zero1_constrain(grads, mesh, shard_axis)
            new_params, new_states = update_fn(params, grads, states, *extra)
            if sharded:
                new_params = _coll.zero1_constrain(new_params, mesh,
                                                   shard_axis)
                new_states = _coll.zero1_constrain(new_states, mesh,
                                                   shard_axis)
            return outs, new_params, new_states, aux_up

        if chain == 1:
            step = one_step
        else:
            def step(params, states, aux_values, rng, data_values, *extra):
                def body(carry, sub_rng):
                    p, s, aux = carry
                    outs, p, s, aux = one_step(p, s, aux, sub_rng,
                                               data_values, *extra)
                    return (p, s, aux), outs

                keys = jax.random.split(rng, chain)
                (p, s, aux), outs_seq = jax.lax.scan(
                    body, (params, states, aux_values), keys)
                outs = [o[-1] for o in outs_seq]  # last sub-step's outputs
                return outs, p, s, aux

        # gate on THIS executor's device, not the process default backend:
        # a cpu-context Module in a tpu-default process (mixed setups,
        # CPU data workers next to a chip) must not route cpu arrays
        # through the TPU-only AUTO-layout compile
        use_auto = (self._ctx.device_type in ("tpu", "gpu")
                    and jax.default_backend() == "tpu"
                    and os.environ.get(
                        "MXNET_STEP_AUTO_LAYOUT", "1") != "0")
        jitted = None if use_auto else jax.jit(step, donate_argnums=(0, 1))
        aot = {}  # compiled, in_formats, placed (built on first call)

        def _avals(tree, sharding=True):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype,
                    sharding=a.sharding if sharding else None), tree)

        def _inputs(data_values):
            aux_values = {n: a._data for n, a in self.aux_dict.items()}
            dv = {n: (v._data if isinstance(v, NDArray) else jnp.asarray(v))
                  for n, v in data_values.items()}
            for n in data_names:
                if n not in dv and n in self.arg_dict:
                    dv[n] = self.arg_dict[n]._data
            return aux_values, dv

        def lower(params, states, data_values, *extra):
            """``jax.stages.Lowered`` of the step at these arguments'
            shapes and shardings, for inspection (``as_text()`` shows
            which kernels the program holds). Traces and lowers; compiles
            and donates nothing."""
            aux_values, dv = _inputs(data_values)
            return jax.jit(step).lower(_avals(params), _avals(states),
                                       aux_values, self._next_rng(), dv,
                                       *extra)

        def _build(phase):
            return _telemetry.span("executor.train_step.build",
                                   domain="executor", phase=phase)

        def _dispatch():
            return _telemetry.span("executor.train_step.dispatch",
                                   domain="executor")

        def _built_one(sp):
            # the compile listener marks the spans a program was built
            # (compiled, or read from the compilation cache) under
            return bool((getattr(sp, "args", None) or {}).get("compiled"))

        def _note(compiled=None, under=None):
            """The program just built says what it is
            (``telemetry.programs()``); the compiled step is not kept."""
            _note_program(
                "train_step", step=self._train_steps,
                build=getattr(under, "id", None), layers=built.layers,
                nodes=_graph_nodes(self._symbol), compiled=compiled,
                uncast_table_bytes=aot.get("uncast_table_bytes", 0))

        def _run_impl(params, states, data_values, *extra):
            rng = self._next_rng()
            aux_values, dv = _inputs(data_values)
            if sharded and not aot.get("placed"):
                # first bind: materialize master weights + optimizer state
                # directly in the 1/N ZeRO layout (never
                # replicated-then-sliced); returned values keep it, so
                # this runs once
                with _build("place"):
                    params = _coll.zero1_place(params, mesh, shard_axis)
                    states = _coll.zero1_place(states, mesh, shard_axis)
                aot["placed"] = True
            elif mesh is None and not aot.get("placed"):
                # commit the caller's snapshot to this executor's device.
                # The step's outputs come back committed whenever any
                # argument is (a device_put batch), and jit keys its cache
                # on that: a first call on uncommitted params compiles a
                # program the second call cannot reuse
                dev = self._ctx.jax_device()
                with _build("place"):
                    params = jax.device_put(params, dev)
                    states = jax.device_put(states, dev)
                aot["placed"] = True
            if not aot.get("gauges"):
                # per-chip byte gauges, one series per ZeRO stage:
                # param/grad from the stage's layout contract
                # (collectives.stage_train_bytes — gradients are
                # in-program transients XLA never exposes), opt measured
                # from the live optimizer-state tree
                n_sh = (int(dict(mesh.shape).get(shard_axis, 1))
                        if mesh is not None else 1)
                pb, gb = _coll.stage_train_bytes(
                    params, stage, max(1, n_sh), shard_axis)
                lbl = {"stage": str(stage)}
                _telemetry.registry.gauge(
                    "train_param_bytes", labels=lbl,
                    help="per-chip parameter bytes held through one train "
                         "step (layout-implied)").set(pb)
                _telemetry.registry.gauge(
                    "train_grad_bytes", labels=lbl,
                    help="per-chip gradient bytes at the reduction "
                         "boundary (layout-implied)").set(gb)
                _telemetry.registry.gauge(
                    "train_opt_bytes", labels=lbl,
                    help="per-chip optimizer-state bytes at rest "
                         "(measured)").set(_coll.per_device_bytes(states))
                aot["gather_bytes"] = sum(
                    int(a.size * jnp.dtype(a.dtype).itemsize)
                    for a in jax.tree_util.tree_leaves(params))
                # what _cast_floats would have cast and, being a table
                # that only gathers read, is left in its master dtype
                aot["uncast_table_bytes"] = sum(
                    4 * int(a.size) for n, a in {**dv, **params}.items()
                    if n in tables and a.dtype == jnp.float32)
                aot["gauges"] = True
            if use_auto:
                if not aot.get("informats"):
                    from jax.experimental.layout import Format, Layout

                    def spec(tree):
                        # AUTO only for matrices (2-D leaves: where the
                        # LM's per-step layout copies live); vectors keep
                        # the default layout, and so do leaves of more
                        # dimensions: a learned layout does not survive
                        # `device_put` for them. A float32[16,2560,768]
                        # expert matrix came back REPORTING the default
                        # layout, which jit refused, and, jitted for the
                        # layout it reported, read permuted (PERF.md,
                        # Findings, PR 33); 1x1 conv weights are refused
                        # on a warm cache (section 7, item 3). Under the
                        # ZeRO-1 sharded update the Format also pins each
                        # leaf's NamedSharding so the learned layouts
                        # apply to the 1/N shards.
                        def one(a):
                            if sharded:
                                sh = _coll.zero1_sharding(
                                    mesh, a.shape, shard_axis)
                                return (Format(Layout.AUTO, sh)
                                        if a.ndim == 2 else sh)
                            return Format(Layout.AUTO) if a.ndim == 2 else None
                        return jax.tree_util.tree_map(one, tree)

                    nextra = (None,) * len(extra)
                    pspec, sspec = spec(params), spec(states)
                    jf = jax.jit(
                        step, donate_argnums=(0, 1),
                        in_shardings=(pspec, sspec, None, None, None)
                        + nextra,
                        out_shardings=(None, pspec, sspec, None))
                    # phase 1: compile once with AUTO to LEARN the
                    # copy-free layouts. For UNchained steps
                    # (dispatch-per-step) phase 2 re-jits with the
                    # CONCRETE learned formats, so every step goes
                    # through jit's cached dispatch and not the AOT
                    # Compiled object's Python argument processing; with
                    # chain > 1 the learned executable is called directly
                    # and the second (scan-of-steps) compile is skipped.
                    # AUTO arguments are lowered from bare avals (the
                    # Format above carries the sharding): a concrete
                    # jax.Array has a layout of its own, which jit
                    # refuses next to Layout.AUTO
                    with _build("auto_layout_learn") as learn:
                        learned = jf.lower(_avals(params, sharding=False),
                                           _avals(states, sharding=False),
                                           aux_values, rng, dv,
                                           *extra).compile()
                        _witness.record_compile("train_step",
                                                key="auto_layout")
                    pf, sf = (learned.input_formats[0][0],
                              learned.input_formats[0][1])
                    aot["informats"] = (pf, sf)
                    aot["learned"] = learned, learn
                # relayout to the learned formats; only needed until the
                # caller threads returned (already-relaid) arrays back
                # in — re-issuing device_put on matching arrays is
                # avoided entirely after the first call
                learn = None
                if not aot.get("relaid"):
                    pf, sf = aot["informats"]
                    with _build("relayout"):
                        params, pf, took_p = _relaid(params, pf)
                        states, sf, took_s = _relaid(states, sf)
                    learned, learn = aot.pop("learned")
                    if chain == 1 or not (took_p and took_s):
                        # for the formats the arrays HAVE: the learned
                        # ones, but for a leaf that did not take its own
                        aot["jit"] = jax.jit(
                            step, donate_argnums=(0, 1),
                            in_shardings=(pf, sf, None, None, None)
                            + (None,) * len(extra),
                            out_shardings=(None, pf, sf, None))
                    else:
                        aot["jit"] = learned
                        _note(learned, learn)
                        learn = None  # noted: nothing is re-jitted
                    aot["relaid"] = True
                with _dispatch() as sp:
                    outs, new_params, new_states, aux_up = aot["jit"](
                        params, states, aux_values, rng, dv, *extra)
                if learn is not None:
                    # the program that RUNS is the one jit just compiled
                    # for the learned formats, and it names its
                    # instructions otherwise than `learned` does (PERF.md,
                    # Findings, PR 37). jit keeps its lowering and its
                    # executable: asked again at the same arguments'
                    # shapes and formats it lowers and compiles nothing
                    try:
                        running = aot["jit"].lower(
                            new_params, new_states, aux_values, rng, dv,
                            *extra).compile()
                    except Exception:
                        logging.getLogger("mxnet_tpu").warning(
                            "train step: jit did not hand its program back; "
                            "the program record has no ops", exc_info=True)
                        running = None
                    _note(running, learn)
                elif _built_one(sp):  # another program (new shapes), with
                    _note()           # no compiled object in hand to read
            else:
                if _progcache.enabled() and "exec" not in aot:
                    # Persistent program cache for the fused step: key by
                    # the LOWERED text — update_fn is arbitrary Python, so
                    # only lowering captures the actual program (a metadata
                    # key could collide across optimizer rules). Donation
                    # is part of the key and survives serialization. Any
                    # failure pins the plain-jit path for this step fn.
                    with _build("progcache") as sp:
                        try:
                            lowered = jitted.lower(params, states, aux_values,
                                                   rng, dv, *extra)
                            key = _progcache.lowered_key(
                                lowered.as_text(), donate=(0, 1),
                                extra="train_step")
                            exe = _progcache.load(key, kind="train_step")
                            if exe is None:
                                exe = lowered.compile()
                                _witness.record_compile("train_step",
                                                        key=key[:16])
                                _progcache.store(key, exe, note="train_step",
                                                 kind="train_step")
                            aot["exec"] = exe
                            _note(exe, sp)
                        except Exception:
                            logging.getLogger("mxnet_tpu").warning(
                                "progcache: train-step AOT path failed; "
                                "using plain jit", exc_info=True)
                            aot["exec"] = None
                with _dispatch() as sp:
                    if aot.get("exec") is not None:
                        try:
                            outs, new_params, new_states, aux_up = \
                                aot["exec"](params, states, aux_values, rng,
                                            dv, *extra)
                        except Exception:
                            # a stale/incompatible loaded executable must
                            # never fail the step: recompile via the jit
                            # path (inputs are intact — argument processing
                            # precedes any donation) and stop using the
                            # cached program
                            logging.getLogger("mxnet_tpu").warning(
                                "progcache: cached train step unusable; "
                                "recompiling", exc_info=True)
                            aot["exec"] = None
                            outs, new_params, new_states, aux_up = jitted(
                                params, states, aux_values, rng, dv, *extra)
                    else:
                        outs, new_params, new_states, aux_up = jitted(
                            params, states, aux_values, rng, dv, *extra)
                if _built_one(sp):  # jit built it: no compiled object
                    _note()         # in hand to read it from
            for n, v in aux_up.items():
                self.aux_dict[n]._data = v
            self.outputs = [NDArray(o) for o in outs]
            return outs, new_params, new_states

        def run(params, states, data_values, *extra):
            # jit dispatch is async: the span is the HOST side of the step.
            # What the call does by its own hand before the dispatch
            # (placing the snapshot, the AUTO-layout learning compile, the
            # relayout, the program cache) is a `build` child each; the
            # call into the program is the `dispatch` child, and the
            # compile listener says on both whether, and for how long, jit
            # traced, lowered, compiled or read its cache inside them
            self._train_steps += 1
            # the bytes are reckoned on the first call, and the expert
            # layers are listed as that call traces the step
            known = "gather_bytes" in aot
            with _telemetry.span("executor.train_step", domain="executor",
                                 step=self._train_steps, chain=chain,
                                 stage=stage,
                                 gather_bytes=aot.get("gather_bytes", 0),
                                 **(_layer_attrs(built.layers, self._symbol)
                                    if known else {})) as sp:
                out = _run_impl(params, states, data_values, *extra)
                if not known:
                    sp.add("gather_bytes", aot.get("gather_bytes", 0))
                    sp.annotate(**_layer_attrs(built.layers, self._symbol))
                return out

        run.lower = lower
        # the pure function the program is jitted from, for tools that
        # compile it themselves (tools/step_ops.py, for a described chip)
        run.step = step
        return run

    def _next_rng(self):
        if self._graph_needs_rng is None:
            self._graph_needs_rng = any(
                (not n.is_var) and n.op.needs_rng
                for n in self._symbol._nodes())
        if not self._graph_needs_rng and self._monitor_cb is None:
            # no stochastic op consumes the key: reuse one key instead of
            # paying jax.random.split's eager host cost (~2 ms) on EVERY
            # forward/step — the dominant Python overhead of the fused
            # fit step for deterministic graphs (docs/perf.md fit row).
            # With a monitor installed the key must stay per-step fresh:
            # _monitor_should_run dedupes fwd/bwd taps of one step by
            # comparing key bytes, and a constant key would silence every
            # tap after the first.
            if self._last_rng is None:
                self._last_rng = _random.next_key()
            return self._last_rng
        self._last_rng = _random.next_key()
        return self._last_rng

    # --- public API (reference Executor::Forward/Backward) ----------------
    def forward(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k in self.arg_dict:
                self.arg_dict[k]._data = v._data if isinstance(v, NDArray) else jnp.asarray(v)
        arg_values = {n: a._data for n, a in self.arg_dict.items()}
        aux_values = {n: a._data for n, a in self.aux_dict.items()}
        rng = self._next_rng()
        if self._monitor_should_run(rng):
            self._run_monitored(arg_values, aux_values, is_train, rng)
        fn = self._get_fwd(bool(is_train))
        with _telemetry.span("executor.forward", domain="executor",
                             is_train=bool(is_train)):
            outs, aux_up = fn(arg_values, aux_values, rng)
        if is_train:
            for n, v in aux_up.items():
                self.aux_dict[n]._data = v
        self.outputs = [NDArray(o) for o in outs]
        return self.outputs

    def backward(self, out_grads=None, is_train=True):
        """Runs the fused forward+backward computation (the separate-call API
        is preserved; the fused path keeps a single XLA executable — forward
        activations are recomputed inside, XLA CSEs what it can)."""
        if out_grads is not None and not isinstance(out_grads, (list, tuple)):
            out_grads = [out_grads]
        fn = self._get_fwd_bwd()
        arg_values = {n: a._data for n, a in self.arg_dict.items()}
        aux_values = {n: a._data for n, a in self.aux_dict.items()}
        rng = self._last_rng if self._last_rng is not None else self._next_rng()
        if self._monitor_should_run(rng):
            # tap every intermediate output for Monitor, exactly as the
            # reference taps during the training forward
            # (graph_executor.cc:761-781)
            self._run_monitored(arg_values, aux_values, True, rng)
        heads = None if out_grads is None else [g._data for g in out_grads]
        old = {n: self.grad_dict[n]._data for n in self._grad_names_list()
               if self.grad_req[n] == "add"}
        with _telemetry.span("executor.backward", domain="executor"):
            outs, aux_up, new_grads = fn(arg_values, aux_values, rng,
                                         heads, old)
        for n, g in zip(self._grad_names_list(), new_grads):
            self.grad_dict[n]._data = g
        for n, v in aux_up.items():
            self.aux_dict[n]._data = v
        self.outputs = [NDArray(o) for o in outs]
        return self.outputs

    def forward_backward(self, out_grads=None, **kwargs):
        """One fused train step: forward + backward in a single jitted call."""
        for k, v in kwargs.items():
            if k in self.arg_dict:
                self.arg_dict[k]._data = v._data if isinstance(v, NDArray) else jnp.asarray(v)
        self._next_rng()
        return self.backward(out_grads)

    def _grad_names_list(self):
        self._get_fwd_bwd()
        return self._grad_names

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self._arg_names]

    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self._arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self._aux_names]

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    def copy_params_from(self, arg_params, aux_params=None, allow_extra_params=False):
        for n, v in (arg_params or {}).items():
            if n in self.arg_dict:
                self.arg_dict[n]._data = jnp.asarray(v.asnumpy() if isinstance(v, NDArray) else v, self.arg_dict[n]._data.dtype)
            elif not allow_extra_params:
                raise MXNetError("unknown argument %s" % n)
        for n, v in (aux_params or {}).items():
            if n in self.aux_dict:
                self.aux_dict[n]._data = jnp.asarray(v.asnumpy() if isinstance(v, NDArray) else v, self.aux_dict[n]._data.dtype)
            elif not allow_extra_params:
                raise MXNetError("unknown aux state %s" % n)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Re-bind with new shapes (compile cache keyed by shape ⇒ cheap)."""
        from . import ndarray as nd

        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args = {}
        for n, shp in zip(self._arg_names, arg_shapes):
            cur = self.arg_dict[n]
            if tuple(cur.shape) == tuple(shp):
                new_args[n] = cur
            else:
                new_args[n] = nd.zeros(shp, dtype=str(cur._data.dtype))
        new_aux = {}
        for n, shp in zip(self._aux_names, aux_shapes):
            cur = self.aux_dict[n]
            new_aux[n] = cur if tuple(cur.shape) == tuple(shp) else nd.zeros(shp, dtype=str(cur._data.dtype))
        grads = None
        if self.grad_dict:
            grads = {n: nd.zeros(a.shape, dtype=str(a._data.dtype)) for n, a in new_args.items() if n in self.grad_dict}
        return Executor(self._symbol, self._ctx, new_args, grads, self.grad_req,
                        new_aux, compute_dtype=self._compute_dtype)

    # --- monitor (reference graph_executor.cc:761-781 monitor callback) ---
    def _monitor_should_run(self, rng):
        """Tap once per step: skip when the callback reports itself idle
        (Monitor between intervals) and dedupe forward+backward of the
        same step (same rng key)."""
        cb = self._monitor_cb
        if cb is None:
            return False
        is_active = getattr(cb, "is_active", None)
        if is_active is not None and not is_active():
            return False
        key = None if rng is None else np.asarray(rng).tobytes()
        if key is not None and key == self._monitored_rng:
            return False
        self._monitored_rng = key
        return True

    def set_monitor_callback(self, callback):
        self._monitor_cb = callback

    def _run_monitored(self, arg_values, aux_values, is_train, rng):
        """Eager re-evaluation reporting every intermediate output to the
        monitor callback (Monitor support, python/mxnet/monitor.py)."""
        sym = self._symbol
        internals = sym.get_internals()
        eval_fn = internals.build_eval()
        outs, _ = eval_fn(arg_values, aux_values, is_train, rng)
        for name, val in zip(internals.list_outputs(), outs):
            self._monitor_cb(name, NDArray(val))

    def print_summary(self):
        return self._symbol.debug_str()
