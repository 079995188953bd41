"""Single operator registry — the TPU-native replacement for the reference's
THREE registration generations (SURVEY §2.3: legacy ``OperatorProperty`` via
``MXNET_REGISTER_OP_PROPERTY``, NNVM ``FCompute`` ops, and the deprecated
SimpleOp registry — src/operator/, include/mxnet/op_attr_types.h).

One ``OpDef`` per operator carries everything the reference spread across
attribute maps:

- ``impl``          — a pure JAX function (the FCompute / mshadow kernel);
  autodiff comes from ``jax.vjp`` over the composed graph (the reference's
  nnvm::pass::Gradient, graph_executor.cc:233), so no per-op backward is
  registered unless the op *overrides* the mathematical gradient
  (SoftmaxOutput & friends use ``jax.custom_vjp`` inside ``impl``).
- ``arg_names``     — differentiable inputs (ListArguments).
- ``aux_names``     — mutable non-differentiated state (BN moving stats;
  the reference's ListAuxiliaryStates, operator.h:166-480).
- ``param_spec``    — typed attrs with defaults (DMLC_DECLARE_PARAMETER).
- shape/dtype inference is *derived* via ``jax.eval_shape`` instead of
  hand-written InferShape/InferType.

Both user-facing APIs — imperative ``mxnet_tpu.ndarray`` and symbolic
``mxnet_tpu.symbol`` — are *generated* from this registry at import, exactly
as the reference generates its Python API from the C op registry
(python/mxnet/ndarray.py:28-39, OpWrapperGenerator.py).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

from ..base import MXNetError, coerce_attr

OP_REGISTRY: Dict[str, "OpDef"] = {}

# A required parameter (no default) in a param_spec.
REQUIRED = object()

_tracing = threading.local()


class built_layers:
    """Entered around the trace of a graph: an op that tells what it built
    (``note_built``: ``ExpertFFN`` its buffer and its products' path,
    ``ShortConv`` its path, ``MultiHeadAttention`` its head size, band and
    kernels, ``Embedding`` how its backward sums the table's gradient)
    leaves a dict in ``self.layers``, one a graph node. Trace-time
    Python state only: nothing here reaches the program."""

    def __init__(self):
        self.layers = []

    def __enter__(self):
        self._prev = getattr(_tracing, "into", None)
        _tracing.into = self.layers
        return self

    def __exit__(self, *exc):
        _tracing.into = self._prev
        return False


@contextlib.contextmanager
def at_node(name):
    """Entered by the graph's evaluator around one node's ``impl``: an op
    does not know its node, so ``note_built`` reads the name from here."""
    prev, _tracing.node = current_node(), name
    try:
        yield
    finally:
        _tracing.node = prev


def current_node():
    """The graph node whose ``impl`` is being traced, or ``""``: what a
    custom VJP hands its backward, which is traced outside the node."""
    return getattr(_tracing, "node", "")


def note_built(record, node=None):
    """Leave ``record`` (a dict with the op's name under ``"op"``) in the
    ``built_layers`` this trace runs under, if any, under ``"node"`` the
    graph node being traced (``node``: another's, a backward's). What a
    node's op says in several places (the attention op and its kernels'
    gate; a backward) is one record."""
    into = getattr(_tracing, "into", None)
    if into is None:
        return
    node = current_node() if node is None else node
    for seen in into:
        if node and seen["node"] == node and seen["op"] == record["op"]:
            seen.update(record)
            return
    into.append(dict(record, node=node))


@dataclasses.dataclass
class OpContext:
    """Per-call execution context (reference OpContext, operator.h:42-62)."""

    is_train: bool = False
    rng: Any = None  # jax PRNG key, present iff opdef.needs_rng
    # the dtype the executor's graph computes in (None: as stored); an op
    # reads it for a leaf it declared in ``OpDef.master_inputs``
    compute_dtype: Any = None


@dataclasses.dataclass
class OpDef:
    name: str
    # full signature: impl(attrs, inputs: tuple, aux: tuple, ctx: OpContext)
    #   -> (outputs: tuple, aux_updates: tuple)
    impl: Callable
    arg_names: Any = ("data",)  # list, or fn(attrs)->list
    aux_names: Any = ()
    num_outputs: Any = 1  # int, or fn(attrs)->int
    param_spec: Optional[Dict[str, Any]] = None  # name -> default / REQUIRED
    needs_rng: bool = False
    uses_train: bool = False
    variadic: bool = False  # takes arbitrary list of inputs (Concat, add_n)
    no_grad_inputs: Sequence[str] = ()  # e.g. labels
    # arguments and auxiliary states, by name, that the op takes in their
    # master dtype under a compute dtype: the executor leaves such a leaf
    # uncast where every op that reads it says so
    master_inputs: Sequence[str] = ()
    doc: str = ""
    py_name: Optional[str] = None  # name exposed in nd/sym namespaces
    output_names: Any = None  # list or fn(attrs)->list; default [name_output]
    param_docs: Optional[Dict[str, str]] = None  # per-param doc text

    def get_arg_names(self, attrs) -> Tuple[str, ...]:
        a = self.arg_names
        return tuple(a(attrs) if callable(a) else a)

    def get_aux_names(self, attrs) -> Tuple[str, ...]:
        a = self.aux_names
        return tuple(a(attrs) if callable(a) else a)

    def get_num_outputs(self, attrs) -> int:
        n = self.num_outputs
        return n(attrs) if callable(n) else n

    def get_output_names(self, attrs):
        o = self.output_names
        if o is None:
            return ["output"] if self.get_num_outputs(attrs) == 1 else [
                "output%d" % i for i in range(self.get_num_outputs(attrs))
            ]
        return list(o(attrs) if callable(o) else o)

    def parse_attrs(self, kwargs: Dict[str, Any]) -> Dict[str, Any]:
        """Validate & coerce kwargs against param_spec (the DMLC parameter
        string-parse step). Unknown keys raise, like dmlc::Parameter::Init."""
        attrs = {}
        if self.param_spec is None:
            return {k: coerce_attr(v) for k, v in kwargs.items()}
        for key, val in kwargs.items():
            if key not in self.param_spec:
                raise MXNetError(
                    "%s got unknown parameter %r (known: %s)"
                    % (self.name, key, sorted(self.param_spec))
                )
            attrs[key] = coerce_attr(val)
        for key, default in self.param_spec.items():
            if key in attrs:
                continue
            if default is REQUIRED:
                raise MXNetError("%s requires parameter %r" % (self.name, key))
            attrs[key] = default
        return attrs

    def build_doc(self) -> str:
        """Generate the full user-facing docstring from the registry entry
        — summary, tensor inputs, and one entry per parameter with
        type/required-or-default (+ doc text when registered). This is the
        analogue of the reference generating Python docstrings from each
        param struct's __FIELDS__ (src/operator/convolution.cc:158,
        cpp-package/scripts/OpWrapperGenerator.py)."""
        lines = [(self.doc or "%s operator." % self.name).strip(), ""]
        defaults = {k: v for k, v in (self.param_spec or {}).items()
                    if v is not REQUIRED}
        if self.variadic:
            inputs = ["*data : NDArray/Symbol (variable number of inputs)"]
        else:
            try:
                inputs = ["%s : NDArray/Symbol" % n
                          for n in self.get_arg_names(defaults)]
                inputs += ["%s : NDArray/Symbol (auxiliary state)" % n
                           for n in self.get_aux_names(defaults)]
            except Exception:
                inputs = ["data : NDArray/Symbol"]
        lines.append("Inputs")
        lines.append("------")
        lines.extend(inputs)
        if self.param_spec:
            lines.append("")
            lines.append("Parameters")
            lines.append("----------")
            pdocs = self.param_docs or {}
            for key, default in self.param_spec.items():
                if default is REQUIRED:
                    head = "%s : required" % key
                else:
                    tname = type(default).__name__ if default is not None else "any"
                    head = "%s : %s, optional, default=%r" % (key, tname, default)
                lines.append(head)
                if key in pdocs:
                    lines.append("    " + pdocs[key])
        lines.append("")
        lines.append("Returns")
        lines.append("-------")
        n_out = self.num_outputs
        lines.append("%s output(s)" % ("variable" if callable(n_out) else n_out))
        return "\n".join(lines)


def register_op(opdef: OpDef) -> OpDef:
    if opdef.name in OP_REGISTRY:
        raise MXNetError("operator %s already registered" % opdef.name)
    OP_REGISTRY[opdef.name] = opdef
    return opdef


def get_op(name: str) -> OpDef:
    try:
        return OP_REGISTRY[name]
    except KeyError:
        raise MXNetError("unknown operator %r" % name) from None


def defop(
    name: str,
    arg_names=("data",),
    aux_names=(),
    num_outputs=1,
    param_spec=None,
    needs_rng=False,
    uses_train=False,
    variadic=False,
    no_grad_inputs=(),
    py_name=None,
    output_names=None,
    simple=True,
    param_docs=None,
    master_inputs=(),
):
    """Decorator registering an operator implementation.

    ``simple=True``  — fn(attrs, *inputs) -> out | tuple(outs)
    ``simple=False`` — fn(attrs, inputs, aux, ctx) -> (outs, aux_updates)
    """

    def dec(fn):
        if simple:

            def impl(attrs, inputs, aux, ctx, _fn=fn):
                out = _fn(attrs, *inputs)
                return (out if isinstance(out, tuple) else (out,)), ()

        else:
            impl = fn
        opdef = OpDef(
            name=name,
            impl=impl,
            arg_names=arg_names,
            aux_names=aux_names,
            num_outputs=num_outputs,
            param_spec=param_spec,
            needs_rng=needs_rng,
            uses_train=uses_train,
            variadic=variadic,
            no_grad_inputs=no_grad_inputs,
            master_inputs=master_inputs,
            doc=fn.__doc__ or "",
            py_name=py_name or name,
            output_names=output_names,
            param_docs=param_docs,
        )
        register_op(opdef)
        return fn

    return dec


def alias(opdef_name: str, *names: str):
    """Register alternative registry names for an op (reference add_alias)."""
    op = get_op(opdef_name)
    for n in names:
        if n not in OP_REGISTRY:
            OP_REGISTRY[n] = op
