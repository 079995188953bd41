"""Symbolic graph API.

TPU-native analogue of nnvm::Symbol + python/mxnet/symbol.py. A Symbol is a
list of output entries over a DAG of nodes; composing symbols builds the
graph; ``bind``/``simple_bind`` compile it — here to ONE jitted XLA
computation for forward and one for backward (the north-star "single HLO per
symbolic subgraph"), instead of the reference's per-node engine ops
(graph_executor.cc:567-679). Shape inference: forward shapes via
jax.eval_shape; parameter shapes via per-op rules (ops/shape_rules.py),
replacing nnvm InferShape (SURVEY §2.1 #35).

Graph JSON save/load keeps the reference's format family
(nnvm::pass::SaveJSON: nodes/arg_nodes/heads) so checkpoints remain
inspectable by the same tooling.
"""
from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import attribute, name as _name_mod
from .base import MXNetError
from .ops import OP_REGISTRY, OpContext, OpDef, get_op
from .ops.registry import at_node

# Monotonic id for ephemeral Symbol.grad ops (never reused, unlike id()).
_GRAD_OP_COUNTER = 0


class _Node:
    __slots__ = ("op", "name", "attrs", "inputs", "is_aux", "misc_attrs")

    def __init__(self, op: Optional[OpDef], name: str, attrs: Dict[str, Any],
                 inputs: List[Tuple["_Node", int]], is_aux: bool = False,
                 misc_attrs: Optional[Dict[str, str]] = None):
        self.op = op
        self.name = name
        self.attrs = attrs
        self.inputs = inputs
        self.is_aux = is_aux  # variable node holding auxiliary (non-grad) state
        self.misc_attrs = misc_attrs or {}

    @property
    def is_var(self):
        return self.op is None


def _topo_order(out_entries) -> List[_Node]:
    order: List[_Node] = []
    visited = set()

    def visit(node):
        if id(node) in visited:
            return
        visited.add(id(node))
        for child, _ in node.inputs:
            visit(child)
        order.append(node)

    for node, _ in out_entries:
        visit(node)
    return order


class Symbol:
    def __init__(self, entries: List[Tuple[_Node, int]]):
        self._entries = list(entries)

    # --- introspection ----------------------------------------------------
    @property
    def name(self):
        if len(self._entries) == 1:
            return self._entries[0][0].name
        return None

    def _nodes(self) -> List[_Node]:
        return _topo_order(self._entries)

    def list_arguments(self) -> List[str]:
        return [n.name for n in self._nodes() if n.is_var and not n.is_aux]

    def list_auxiliary_states(self) -> List[str]:
        return [n.name for n in self._nodes() if n.is_var and n.is_aux]

    def list_outputs(self) -> List[str]:
        outs = []
        for node, idx in self._entries:
            if node.is_var:
                outs.append(node.name)
            else:
                onames = node.op.get_output_names(node.attrs)
                outs.append("%s_%s" % (node.name, onames[idx]))
        return outs

    def list_inputs(self):
        return [n.name for n in self._nodes() if n.is_var]

    def get_internals(self) -> "Symbol":
        entries = []
        for node in self._nodes():
            if node.is_var:
                entries.append((node, 0))
            else:
                for i in range(node.op.get_num_outputs(node.attrs)):
                    entries.append((node, i))
        return Symbol(entries)

    def get_children(self) -> Optional["Symbol"]:
        """Grouped symbol of the output nodes' immediate inputs, in
        order; None for a pure-variable symbol (reference
        python/mxnet/symbol.py get_children / test_symbol.py
        test_symbol_children semantics). A multi-output node contributes
        its inputs ONCE, not per selected output."""
        entries = []
        seen = set()
        for node, _ in self._entries:
            if id(node) in seen:
                continue
            seen.add(id(node))
            entries.extend(node.inputs)
        if not entries:
            return None
        return Symbol(entries)

    def __reduce__(self):
        # op impls are closures (unpicklable); the versioned JSON schema
        # is the durable form, so pickle round-trips THROUGH it
        # (reference test_symbol.py test_symbol_pickle capability).
        # Ephemeral ops (grad()'s synthesized backward nodes) are not in
        # the registry, so their JSON could never load back — fail at
        # DUMP time, not in some later process with a corrupt blob.
        from .ops.registry import OP_REGISTRY

        for n in self._nodes():
            if not n.is_var and n.op.name not in OP_REGISTRY:
                raise MXNetError(
                    "cannot pickle symbol: op %r is not in the registry "
                    "(ephemeral gradient/internal node)" % n.op.name)
        return (load_json, (self.tojson(),))

    def __deepcopy__(self, memo):
        # without this, copy.deepcopy would fall back to __reduce_ex__
        # and route through the JSON schema (breaking ephemeral-op
        # symbols that the structural __copy__ handles fine)
        return self.__copy__()

    def __getitem__(self, index):
        if isinstance(index, str):
            outs = self.list_outputs()
            if index not in outs:
                raise MXNetError("cannot find output %r in %s" % (index, outs))
            index = outs.index(index)
        return Symbol([self._entries[index]])

    def __len__(self):
        return len(self._entries)

    def __iter__(self):
        return (self[i] for i in range(len(self._entries)))

    def attr(self, key):
        if len(self._entries) == 1:
            return self._entries[0][0].misc_attrs.get(key)
        return None

    def attr_dict(self):
        ret = {}
        for node in self._nodes():
            if node.misc_attrs:
                ret[node.name] = dict(node.misc_attrs)
        return ret

    def _set_attr(self, **kwargs):
        for node, _ in self._entries:
            node.misc_attrs.update(kwargs)

    # --- composition ------------------------------------------------------
    def __call__(self, *args, **kwargs):
        """Compose: substitute variable nodes (reference Symbol compose)."""
        s = self.__copy__()
        s._compose(*args, **kwargs)
        return s

    def _compose(self, *args, **kwargs):
        mapping = {}
        if args:
            vars_in = [n for n in self._nodes() if n.is_var and not n.is_aux]
            for var, rep in zip(vars_in, args):
                mapping[id(var)] = rep._entries[0]
        for k, v in kwargs.items():
            for n in self._nodes():
                if n.is_var and n.name == k:
                    mapping[id(n)] = v._entries[0]
        for node in self._nodes():
            node.inputs = [
                mapping.get(id(child), (child, idx)) if child.is_var else (child, idx)
                for child, idx in node.inputs
            ]

    def __copy__(self):
        # deep copy of node graph
        memo: Dict[int, _Node] = {}

        def cp(node):
            if id(node) in memo:
                return memo[id(node)]
            nn = _Node(node.op, node.name, dict(node.attrs),
                       [], node.is_aux, dict(node.misc_attrs))
            memo[id(node)] = nn
            nn.inputs = [(cp(c), i) for c, i in node.inputs]
            return nn

        return Symbol([(cp(n), i) for n, i in self._entries])

    # --- arithmetic (creates broadcast graph nodes) -----------------------
    def _binop(self, other, op_name, scalar_op, reverse=False):
        if isinstance(other, Symbol):
            a, b = (other, self) if reverse else (self, other)
            return _create_symbol(get_op(op_name), [a, b], {}, None)
        attrs = {"scalar": float(other)}
        name = scalar_op if not reverse else scalar_op.replace("_", "_r", 1)
        return _create_symbol(get_op(name), [self], attrs, None)

    def __add__(self, other):
        return self._binop(other, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binop(other, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, other):
        return self._binop(other, "broadcast_sub", "_minus_scalar", reverse=True)

    def __mul__(self, other):
        return self._binop(other, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binop(other, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, other):
        return self._binop(other, "broadcast_div", "_div_scalar", reverse=True)

    def __pow__(self, other):
        return self._binop(other, "broadcast_power", "_power_scalar")

    def __neg__(self):
        return self._binop(-1.0, "broadcast_mul", "_mul_scalar")

    def grad(self, wrt):
        """Gradient symbol wrt the named arguments (reference symbol.py:
        1374-1397 documents this API but its C implementation is a stub —
        'currently not implemented'; jax.vjp makes it real here).

        Returns a Symbol with one output per name in ``wrt``: the gradient
        of the SUM of this symbol's outputs with respect to that argument.
        The gradient symbol takes the same arguments (and aux states) as
        ``self``."""
        from .ops.registry import OpDef

        wrt = [wrt] if isinstance(wrt, str) else list(wrt)
        base = self.__copy__()
        arg_names = base.list_arguments()
        aux_names = base.list_auxiliary_states()
        missing = [w for w in wrt if w not in arg_names]
        if missing:
            raise MXNetError("grad: unknown arguments %s (have %s)"
                             % (missing, arg_names))
        eval_fn = base.build_eval()
        n_args = len(arg_names)

        def impl(attrs, inputs, aux, ctx):
            arg_values = dict(zip(arg_names, inputs))
            aux_values = dict(zip(aux_names, aux))

            import builtins

            def f(g_values):
                av = dict(arg_values)
                av.update(g_values)
                outs, _ = eval_fn(av, aux_values, ctx.is_train, ctx.rng)
                # builtins.sum: `sum` is a generated op in this namespace
                return builtins.sum(jnp.sum(o) for o in outs)

            grads = jax.grad(f)({w: arg_values[w] for w in wrt})
            return tuple(grads[w] for w in wrt), ()

        gname = _name_mod.current().get(None, "grad")
        # Ephemeral op: NOT registered in the global OP_REGISTRY (symbol
        # nodes hold the OpDef object directly; registering would grow the
        # registry unboundedly and id()-based names can collide after GC).
        # Consequence: grad symbols cannot round-trip through tojson/load.
        global _GRAD_OP_COUNTER
        _GRAD_OP_COUNTER += 1
        opdef = OpDef(
            name="_grad_%s_%d" % (gname, _GRAD_OP_COUNTER),
            impl=impl,
            arg_names=tuple(arg_names),
            aux_names=tuple(aux_names),
            num_outputs=len(wrt),
            output_names=tuple("%s_grad" % w for w in wrt),
            needs_rng=True,
            uses_train=True,
            doc="Gradient of %r wrt %s (Symbol.grad; ephemeral op, "
                "not serializable via tojson/load)" % (gname, wrt),
        )
        inputs = [Variable(n) for n in arg_names]
        for n in aux_names:  # aux slots need is_aux variable nodes
            inputs.append(Symbol([(_Node(None, n, {}, [], is_aux=True), 0)]))
        return _create_symbol(opdef, inputs, {}, gname,
                              input_names=arg_names + aux_names)

    # --- inference --------------------------------------------------------
    def infer_shape(self, *args, **kwargs):
        res = self._infer(kwargs, partial=False)
        return res

    def infer_shape_partial(self, *args, **kwargs):
        return self._infer(kwargs, partial=True)

    def infer_type(self, *args, **kwargs):
        """Dtype-only propagation (reference nnvm InferType): parameter
        dtypes follow the first known input dtype; Cast/creation ops set
        their own."""
        known = {k: np.dtype(v) for k, v in kwargs.items()}
        nodes = self._nodes()
        dt: Dict[Tuple[int, int], Any] = {}
        var_dt: Dict[str, Any] = {}
        for node in nodes:
            if not node.is_var:
                continue
            d = known.get(node.name)
            if d is None and "__dtype__" in node.misc_attrs:
                d = np.dtype(node.misc_attrs["__dtype__"])
            if d is not None:
                dt[(id(node), 0)] = d
                var_dt[node.name] = d
        for node in nodes:
            if node.is_var:
                continue
            in_dts = [dt.get((id(c), i)) for c, i in node.inputs]
            ref = next((d for d in in_dts if d is not None), None)
            own = node.attrs.get("dtype") if "dtype" in (node.attrs or {}) else None
            if ref is None and own is None:
                continue
            for (c, i), d in zip(node.inputs, in_dts):
                if d is None and ref is not None:
                    dt[(id(c), i)] = ref
                    if c.is_var:
                        var_dt[c.name] = ref
            out_d = np.dtype(own) if own else ref
            for i in range(node.op.get_num_outputs(node.attrs)):
                dt[(id(node), i)] = out_d
        arg_ts = [var_dt.get(n) for n in self.list_arguments()]
        aux_ts = [var_dt.get(n) for n in self.list_auxiliary_states()]
        out_ts = [dt.get((id(n), i)) for n, i in self._entries]
        return (arg_ts, out_ts, aux_ts)

    def _infer(self, known_shapes, partial):
        args_s, outs_s, aux_s, _ = self._infer_structs(known_shapes, {}, partial)
        return args_s, outs_s, aux_s

    def _infer_structs(self, known_shapes: Dict[str, tuple], known_dtypes: Dict[str, Any], partial: bool):
        """Propagate ShapeDtypeStructs through the graph."""
        known_shapes = {
            k: tuple(v) for k, v in known_shapes.items() if v is not None
        }
        env: Dict[Tuple[int, int], Any] = {}  # (node id, out idx) -> ShapeDtypeStruct
        var_struct: Dict[str, Any] = {}
        default_dtype = jnp.float32
        nodes = self._nodes()
        # seed variables with known shapes
        for node in nodes:
            if not node.is_var:
                continue
            shape = known_shapes.get(node.name)
            if shape is None and "__shape__" in node.misc_attrs:
                shape = tuple(json.loads(node.misc_attrs["__shape__"]))
            dtype = known_dtypes.get(node.name)
            if dtype is None and "__dtype__" in node.misc_attrs:
                dtype = np.dtype(node.misc_attrs["__dtype__"])
            if shape is not None:
                st = jax.ShapeDtypeStruct(shape, dtype or default_dtype)
                env[(id(node), 0)] = st
                var_struct[node.name] = st
            elif dtype is not None:
                var_struct[node.name] = jax.ShapeDtypeStruct((), dtype)

        for node in nodes:
            if node.is_var:
                continue
            op = node.op
            attrs = node.attrs
            in_structs = [env.get((id(c), i)) for c, i in node.inputs]
            n_aux = len(op.get_aux_names(attrs)) if not op.variadic else 0
            n_args = len(node.inputs) - n_aux
            # fill parameter shapes via the op's reverse rule
            rule = getattr(op, "infer_params", None)
            if rule is not None:
                shapes = [None if s is None else tuple(s.shape) for s in in_structs]
                shapes = rule(attrs, shapes)
                ref_dtype = next((s.dtype for s in in_structs if s is not None), default_dtype)
                for i, (s, st) in enumerate(zip(shapes, in_structs)):
                    if st is None and s is not None:
                        child, cidx = node.inputs[i]
                        new_st = jax.ShapeDtypeStruct(tuple(s), ref_dtype)
                        env[(id(child), cidx)] = new_st
                        if child.is_var:
                            var_struct[child.name] = new_st
                in_structs = [env.get((id(c), i)) for c, i in node.inputs]
            if any(s is None for s in in_structs):
                if partial:
                    continue
                missing = [
                    node.inputs[i][0].name for i, s in enumerate(in_structs) if s is None
                ]
                raise MXNetError(
                    "infer_shape: cannot infer inputs %s of node %s; provide their shapes"
                    % (missing, node.name)
                )
            ins = in_structs[:n_args]
            auxs = in_structs[n_args:]

            def fn(*flat):
                i_ = flat[: len(ins)]
                a_ = flat[len(ins):]
                outs, _ = op.impl(attrs, i_, a_, OpContext(False, jax.random.PRNGKey(0)))
                return outs

            try:
                out_structs = jax.eval_shape(fn, *(list(ins) + list(auxs)))
            except Exception as e:  # surface with node context
                raise MXNetError(
                    "shape inference failed at node %s (%s): %s" % (node.name, op.name, e)
                ) from e
            for i, st in enumerate(out_structs):
                env[(id(node), i)] = jax.ShapeDtypeStruct(tuple(st.shape), st.dtype)

        arg_names = self.list_arguments()
        aux_names = self.list_auxiliary_states()
        args_shapes = [
            (tuple(var_struct[n].shape) if n in var_struct else None) for n in arg_names
        ]
        aux_shapes = [
            (tuple(var_struct[n].shape) if n in var_struct else None) for n in aux_names
        ]
        out_shapes = []
        out_structs_list = []
        for node, idx in self._entries:
            st = env.get((id(node), idx))
            out_shapes.append(None if st is None else tuple(st.shape))
            out_structs_list.append(st)
        structs = {
            "args": {n: var_struct.get(n) for n in arg_names},
            "aux": {n: var_struct.get(n) for n in aux_names},
            "outs": out_structs_list,
        }
        if not partial and any(s is None for s in args_shapes + out_shapes + aux_shapes):
            raise MXNetError("infer_shape: incomplete inference; missing shapes")
        return args_shapes, out_shapes, aux_shapes, structs

    # --- binding ----------------------------------------------------------
    def bind(self, ctx, args, args_grad=None, grad_req="write", aux_states=None,
             group2ctx=None, shared_exec=None, compute_dtype=None):
        from .executor import Executor

        return Executor(self, ctx, args, args_grad, grad_req, aux_states,
                        group2ctx=group2ctx, shared_exec=shared_exec,
                        compute_dtype=compute_dtype)

    def simple_bind(self, ctx, grad_req="write", type_dict=None, group2ctx=None,
                    shared_exec=None, compute_dtype=None, **kwargs):
        """Infer shapes from kwargs, allocate arrays, bind (reference
        python/mxnet/symbol.py:1117)."""
        from . import ndarray as nd
        from .executor import Executor, bind_span

        with bind_span():
            type_dict = type_dict or {}
            arg_shapes, _, aux_shapes = self.infer_shape(**kwargs)
            arg_names = self.list_arguments()
            aux_names = self.list_auxiliary_states()
            _, _, _, structs = self._infer_structs(kwargs, {k: np.dtype(v) for k, v in type_dict.items()}, partial=False)
            args = {}
            for n, shp in zip(arg_names, arg_shapes):
                st = structs["args"][n]
                args[n] = nd.zeros(shp, ctx=ctx, dtype=str(st.dtype))
            args_grad = None
            if grad_req != "null":
                args_grad = {
                    n: nd.zeros(a.shape, ctx=ctx, dtype=str(structs["args"][n].dtype))
                    for n, a in args.items()
                }
            aux_states = {
                n: nd.zeros(shp, ctx=ctx, dtype=str(structs["aux"][n].dtype))
                for n, shp in zip(aux_names, aux_shapes)
            }
            return Executor(self, ctx, args, args_grad, grad_req, aux_states,
                            group2ctx=group2ctx, shared_exec=shared_exec,
                            compute_dtype=compute_dtype)

    # --- evaluation helper used by Executor -------------------------------
    def build_eval(self, remat_segments=None):
        """Return fn(arg_values: dict, aux_values: dict, is_train, rng)
        -> (outputs list, aux_updates dict). Pure; jit-able.

        remat_segments > 1 partitions the graph into that many contiguous
        topological segments, each wrapped in ``jax.checkpoint``: backward
        keeps only segment-boundary activations and recomputes segment
        interiors — the reference's MXNET_BACKWARD_DO_MIRROR /
        note_memory.md "memonger" memory-for-FLOPs trade
        (graph_executor.cc:213-226), realized the TPU way. ``None`` reads
        the MXNET_BACKWARD_DO_MIRROR env var (1 = auto ≈ sqrt(#ops),
        k>1 = exactly k segments).

        MXNET_CONV_LAYOUT=nhwc (default; read here, like the mirror
        flag) additionally runs the conv backbone as NHWC layout islands
        (ops/layout.py): convs seed islands, layout-agnostic neighbours
        propagate them, anything else transposes back — so the rewrite
        is local to this evaluator and the graph/API stay NCHW."""
        from .ops import layout as _oplayout

        nhwc = _oplayout.enabled()
        nodes = self._nodes()
        entries = self._entries
        if remat_segments is None:
            import builtins
            import math
            import os as _os

            flag = int(_os.environ.get("MXNET_BACKWARD_DO_MIRROR", "0") or 0)
            # `sum`/`max` here are generated op functions, not builtins
            n_ops = builtins.sum(1 for n in nodes if not n.is_var)
            remat_segments = (builtins.max(2, int(math.sqrt(n_ops)))
                              if flag == 1 else flag)
        if remat_segments and remat_segments > 1:
            return self._build_eval_segmented(nodes, entries,
                                              int(remat_segments))

        def eval_fn(arg_values, aux_values, is_train, rng):
            env: Dict[Tuple[int, int], Any] = {}
            tags = set()  # env keys whose value is resident NHWC
            aux_updates: Dict[str, Any] = {}
            for ni, node in enumerate(nodes):
                if node.is_var:
                    src = aux_values if node.is_aux else arg_values
                    if node.name not in src:
                        raise MXNetError("missing value for %s" % node.name)
                    env[(id(node), 0)] = src[node.name]
                    continue
                op = node.op
                attrs = node.attrs
                vals = [env[(id(c), i)] for c, i in node.inputs]
                n_aux = len(op.get_aux_names(attrs)) if not op.variadic else 0
                n_args = len(vals) - n_aux
                tagged_out = ()
                if nhwc:
                    attrs, vals, tagged_out = _oplayout.adapt(
                        op.name, attrs, vals,
                        [(id(c), i) in tags for c, i in node.inputs])
                node_rng = None
                if op.needs_rng:
                    node_rng = jax.random.fold_in(rng, ni)
                # the node's name on every device operation traced from it:
                # the profiler and the compiled text say whose a fusion is
                with jax.named_scope(node.name), at_node(node.name):
                    outs, aux_out = op.impl(
                        attrs, tuple(vals[:n_args]), tuple(vals[n_args:]),
                        OpContext(is_train, node_rng),
                    )
                for i, o in enumerate(outs):
                    env[(id(node), i)] = o
                    if i in tagged_out:
                        tags.add((id(node), i))
                for (child, _), new in zip(node.inputs[n_args:], aux_out):
                    if child.is_var:
                        aux_updates[child.name] = new
            outputs = [(_oplayout.to_nchw(env[(id(n), i)])
                        if (id(n), i) in tags else env[(id(n), i)])
                       for n, i in entries]
            return outputs, aux_updates

        return eval_fn

    def _build_eval_segmented(self, nodes, entries, n_segments):
        """Segmented evaluator: contiguous topo chunks, each under
        jax.checkpoint; only chunk-boundary values are saved for backward.

        NHWC layout islands (MXNET_CONV_LAYOUT, ops/layout.py) span
        chunk boundaries: the tag set lives in the evaluator scope, so a
        value that leaves one chunk resident-NHWC enters the next one
        tagged — the per-conv layouts (and therefore the numerics) match
        the unsegmented evaluator exactly, and jax.checkpoint simply
        stores the NHWC boundary value. The retrace during backward
        re-derives the same tags (the pass is deterministic)."""
        import math

        import builtins

        from .ops import layout as _oplayout

        nhwc = _oplayout.enabled()

        op_nodes = [(ni, n) for ni, n in enumerate(nodes) if not n.is_var]
        # `min`/`max`/`sum` are generated op functions in this namespace
        k = builtins.max(1, builtins.min(n_segments, len(op_nodes)))
        per = math.ceil(len(op_nodes) / k)
        chunks = [op_nodes[i * per:(i + 1) * per]
                  for i in range(k) if op_nodes[i * per:(i + 1) * per]]
        final_keys = {(id(n), i) for n, i in entries}
        # per-chunk: which produced entries must leave the chunk (consumed
        # by a LATER chunk or part of the final outputs)
        out_keys = []
        for ci, chunk in enumerate(chunks):
            produced = {(id(n), i) for _, n in chunk
                        for i in range(n.op.get_num_outputs(n.attrs))}
            needed = set()
            for cj in range(ci + 1, len(chunks)):
                for _, n in chunks[cj]:
                    for c, i in n.inputs:
                        if (id(c), i) in produced:
                            needed.add((id(c), i))
            needed |= produced & final_keys
            out_keys.append(sorted(needed, key=lambda t: (t[0], t[1])))
        in_keys = []
        for ci, chunk in enumerate(chunks):
            produced = {(id(n), i) for _, n in chunk
                        for i in range(n.op.get_num_outputs(n.attrs))}
            needed = {(id(c), i) for _, n in chunk for c, i in n.inputs
                      if (id(c), i) not in produced}
            in_keys.append(sorted(needed, key=lambda t: (t[0], t[1])))

        def eval_fn(arg_values, aux_values, is_train, rng):
            env: Dict[Tuple[int, int], Any] = {}
            tags = set()  # NHWC-resident keys, shared across chunks
            aux_updates: Dict[str, Any] = {}
            for node in nodes:
                if node.is_var:
                    src = aux_values if node.is_aux else arg_values
                    if node.name not in src:
                        raise MXNetError("missing value for %s" % node.name)
                    env[(id(node), 0)] = src[node.name]

            for ci, chunk in enumerate(chunks):
                ikeys, okeys = in_keys[ci], out_keys[ci]

                def chunk_fn(in_vals, c_rng, _chunk=chunk, _ik=ikeys,
                             _ok=okeys):
                    local = dict(zip(_ik, in_vals))
                    aux_out_items = []
                    for ni, node in _chunk:
                        op, attrs = node.op, node.attrs
                        vals = [local[(id(c), i)] for c, i in node.inputs]
                        n_aux = (len(op.get_aux_names(attrs))
                                 if not op.variadic else 0)
                        n_args = len(vals) - n_aux
                        tagged_out = ()
                        if nhwc:
                            attrs, vals, tagged_out = _oplayout.adapt(
                                op.name, attrs, vals,
                                [(id(c), i) in tags for c, i in node.inputs])
                        node_rng = (jax.random.fold_in(c_rng, ni)
                                    if op.needs_rng else None)
                        with jax.named_scope(node.name), \
                                at_node(node.name):
                            outs, aux_out = op.impl(
                                attrs, tuple(vals[:n_args]),
                                tuple(vals[n_args:]),
                                OpContext(is_train, node_rng))
                        for i, o in enumerate(outs):
                            local[(id(node), i)] = o
                            if i in tagged_out:
                                tags.add((id(node), i))
                        for (child, _), new in zip(node.inputs[n_args:],
                                                   aux_out):
                            if child.is_var:
                                aux_out_items.append((child.name, new))
                    return (tuple(local[kk] for kk in _ok),
                            tuple(v for _, v in aux_out_items))

                aux_names_chunk = []
                for ni, node in chunk:
                    op, attrs = node.op, node.attrs
                    n_aux = (len(op.get_aux_names(attrs))
                             if not op.variadic else 0)
                    if n_aux:
                        for child, _ in node.inputs[-n_aux:]:
                            if child.is_var:
                                aux_names_chunk.append(child.name)
                # last chunk needs no checkpoint: its residuals are the
                # final outputs anyway
                fn = (jax.checkpoint(chunk_fn)
                      if ci < len(chunks) - 1 else chunk_fn)
                in_vals = tuple(env[kk] for kk in ikeys)
                out_vals, aux_vals = fn(in_vals, rng)
                env.update(zip(okeys, out_vals))
                aux_updates.update(zip(aux_names_chunk, aux_vals))

            outputs = [env[(id(n), i)] for n, i in entries]
            return outputs, aux_updates

        return eval_fn

    # --- save / load ------------------------------------------------------
    def tojson(self, format: str = "native") -> str:
        """Serialize the graph. format="native" (default) is this
        repo's schema; format="reference" emits the reference
        framework's nodes/arg_nodes/heads symbol JSON
        (interop.save_symbol_json — readable by the reference era and
        by this repo's own reader, the write-side complement of the
        read interop)."""
        if format == "reference":
            from . import interop

            return interop.save_symbol_json(self)
        if format != "native":
            raise ValueError("unknown symbol JSON format %r" % (format,))
        nodes = self._nodes()
        idx = {id(n): i for i, n in enumerate(nodes)}
        jnodes = []
        for n in nodes:
            jnodes.append(
                {
                    "op": "null" if n.is_var else n.op.name,
                    "name": n.name,
                    # None serializes as "null" (the enum spelling the
                    # loader's coerce_attr maps back to None), so
                    # save->load->save is byte-stable
                    "attrs": {k: ("null" if v is None
                                  else repr(v) if not isinstance(v, str)
                                  else v)
                              for k, v in n.attrs.items()},
                    "inputs": [[idx[id(c)], i, 0] for c, i in n.inputs],
                    "is_aux": bool(n.is_aux),
                    "misc_attrs": n.misc_attrs,
                }
            )
        heads = [[idx[id(n)], i, 0] for n, i in self._entries]
        return json.dumps(
            {
                "nodes": jnodes,
                "arg_nodes": [i for i, n in enumerate(nodes) if n.is_var],
                "heads": heads,
                "attrs": {"mxnet_tpu_version": 1},
            },
            indent=2,
        )

    def save(self, fname: str, format: str = "native"):
        with open(fname, "w") as f:
            f.write(self.tojson(format=format))

    def debug_str(self):
        lines = []
        for n in self._nodes():
            if n.is_var:
                lines.append("Variable:%s" % n.name)
            else:
                ins = ", ".join("%s[%d]" % (c.name, i) for c, i in n.inputs)
                lines.append("%s(%s) name=%s attrs=%s" % (n.op.name, ins, n.name, n.attrs))
        return "\n".join(lines)


def load_json(json_str: str) -> Symbol:
    from .base import coerce_attr

    data = json.loads(json_str)
    from . import interop
    if interop.is_reference_symbol_json(data):
        # a reference-ecosystem symbol dump (any legacy version):
        # interop.py applies the upgrade semantics of the reference's
        # legacy_json_util.cc
        return interop.load_symbol_json(data)
    nodes: List[_Node] = []
    for jn in data["nodes"]:
        if jn["op"] == "null":
            node = _Node(None, jn["name"], {}, [], jn.get("is_aux", False), jn.get("misc_attrs", {}))
        else:
            op = get_op(jn["op"])
            attrs = {k: coerce_attr(v) for k, v in jn.get("attrs", {}).items()}
            attrs = op.parse_attrs(attrs)
            inputs = [(nodes[i], oi) for i, oi, _ in jn["inputs"]]
            node = _Node(op, jn["name"], attrs, inputs, False, jn.get("misc_attrs", {}))
        nodes.append(node)
    entries = [(nodes[i], oi) for i, oi, _ in data["heads"]]
    return Symbol(entries)


def load(fname: str) -> Symbol:
    with open(fname) as f:
        return load_json(f.read())


def Variable(name: str, attr=None, shape=None, lr_mult=None, wd_mult=None,
             dtype=None, init=None, **kwargs) -> Symbol:
    """Create a variable symbol (reference symbol.py Variable)."""
    if not isinstance(name, str):
        raise TypeError("Expect a string for variable name")
    misc = attribute.current().get(attr or {})
    if shape is not None:
        misc["__shape__"] = json.dumps(list(shape))
    if dtype is not None:
        misc["__dtype__"] = str(np.dtype(dtype))
    if lr_mult is not None:
        misc["__lr_mult__"] = str(lr_mult)
    if wd_mult is not None:
        misc["__wd_mult__"] = str(wd_mult)
    if init is not None:
        misc["__init__"] = init if isinstance(init, str) else init.dumps()
    for k, v in kwargs.items():
        misc[k] = str(v)
    node = _Node(None, name, {}, [], False, misc)
    return Symbol([(node, 0)])


var = Variable


def Group(symbols: Sequence[Symbol]) -> Symbol:
    entries = []
    for s in symbols:
        entries.extend(s._entries)
    return Symbol(entries)


def zeros(shape, dtype="float32", **kwargs):
    return _create_symbol(get_op("_zeros"), [], {"shape": shape, "dtype": dtype}, kwargs.get("name"))


def ones(shape, dtype="float32", **kwargs):
    return _create_symbol(get_op("_ones"), [], {"shape": shape, "dtype": dtype}, kwargs.get("name"))


def arange(start, stop=None, step=1.0, repeat=1, dtype="float32", **kwargs):
    return _create_symbol(
        get_op("_arange"),
        [],
        {"start": start, "stop": stop, "step": step, "repeat": repeat, "dtype": dtype},
        kwargs.get("name"),
    )


def _create_symbol(op: OpDef, input_syms: List[Symbol], attrs: Dict[str, Any],
                   name: Optional[str], input_names: Optional[List[str]] = None) -> Symbol:
    parsed = op.parse_attrs(attrs)
    hint = (op.py_name or op.name).lower().lstrip("_")
    node_name = _name_mod.current().get(name, hint)
    arg_names = list(op.get_arg_names(parsed))
    aux_names = list(op.get_aux_names(parsed))
    entries: List[Tuple[_Node, int]] = []
    if op.variadic:
        for s in input_syms:
            entries.append(s._entries[0])
    else:
        given = {}
        if input_names:
            for n, s in zip(input_names, input_syms):
                given[n] = s
        else:
            for n, s in zip(arg_names + aux_names, input_syms):
                given[n] = s
        for n in arg_names + aux_names:
            if n in given and given[n] is not None:
                entries.append(given[n]._entries[0])
            else:
                # auto-create the parameter variable (reference: NNVM compose
                # creates missing inputs named <node>_<arg>)
                vnode = _Node(None, "%s_%s" % (node_name, n), {}, [],
                              is_aux=(n in aux_names),
                              misc_attrs=attribute.current().get({}))
                entries.append((vnode, 0))
    # mark aux variables
    node = _Node(op, node_name, parsed, entries, False, attribute.current().get({}))
    nout = op.get_num_outputs(parsed)
    return Symbol([(node, i) for i in range(nout)])


def _make_sym_function(op: OpDef):
    def fn(*args, **kwargs):
        name = kwargs.pop("name", None)
        attr = kwargs.pop("attr", None)
        sym_kwargs = {}
        attrs = {}
        for k, v in kwargs.items():
            if isinstance(v, Symbol):
                sym_kwargs[k] = v
            else:
                attrs[k] = v
        parsed = op.parse_attrs(attrs)
        if op.variadic:
            inputs = list(args) + [sym_kwargs[k] for k in sorted(sym_kwargs)]
            s = _create_symbol(op, inputs, attrs, name)
        else:
            names = list(op.get_arg_names(parsed)) + list(op.get_aux_names(parsed))
            ordered: List[Optional[Symbol]] = [None] * len(names)
            for i, a in enumerate(args):
                ordered[i] = a
            for k, v in sym_kwargs.items():
                if k not in names:
                    raise MXNetError("%s: unexpected input %r" % (op.name, k))
                ordered[names.index(k)] = v
            s = _create_symbol(op, ordered, attrs, name, input_names=names)
        if attr:
            s._set_attr(**attr)
        return s

    fn.__name__ = op.py_name or op.name
    fn.__doc__ = op.build_doc()
    return fn


def _populate_namespace():
    g = globals()
    seen = {}
    for rname, op in OP_REGISTRY.items():
        if id(op) in seen:
            target = seen[id(op)]
        else:
            target = _make_sym_function(op)
            seen[id(op)] = target
        if rname not in g:
            g[rname] = target
        pub = op.py_name or rname
        if pub not in g:
            g[pub] = target


_populate_namespace()
