"""Compiles charged to the span that caused them.

One always-on listener on ``jax.monitoring``'s duration events. JAX
reports how long each jaxpr trace, each lowering to MLIR, each backend
compile and each persistent-compilation-cache read took, on the thread
that did it. The listener adds each duration to every span open on that
thread, innermost to outermost:

=============================================  ==================
event                                          span attribute
=============================================  ==================
``/jax/core/compile/jaxpr_trace_duration``     ``trace_s``
``.../jaxpr_to_mlir_module_duration``          ``lower_s``
``.../backend_compile_duration``, cache miss   ``compile_s``
``.../backend_compile_duration``, cache hit    ``cache_read_s``
=============================================  ==================

``backend_compile_duration`` wraps ``compile_or_get_cached``, which on
a hit fires ``/jax/compilation_cache/cache_retrieval_time_sec`` just
before: that event is what tells a read from a compile, and the whole of
the backend duration (the read, the deserialization, the load) is then
the read's. Either way the span gets ``compiled=True`` and one more in
``programs``: a step that built a program says so itself.

This sees every path through jit, the plain ``jitted(...)`` call that
``analysis.compile_witness`` cannot see among them, with no opt-in; the
witness keeps its stack-capturing role under its own flag.

Registry counters: ``compile_seconds_total``,
``compile_cache_read_seconds_total`` and ``compiles_total{span=...}``
by the innermost open span (``span="none"`` with no program span open:
a caller's own jit, no layer's).
"""
from __future__ import annotations

from . import tracer
from .metrics import registry

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_NESTING = {_TRACE: "trace_s", _LOWER: "lower_s"}

# an inner jit is traced inside its caller's trace and fires its own
# event first: an event that began after the one now ending is inside it
_SLACK_NS = 1_000_000

_compile_s = registry.counter(
    "compile_seconds_total", help="seconds in XLA backend compiles")
_cache_read_s = registry.counter(
    "compile_cache_read_seconds_total",
    help="seconds reading executables from the persistent compilation "
         "cache")


def _self_time(key, seconds):
    """``seconds`` less the events of the same kind nested inside this
    one (they ended, and were counted, before it did)."""
    local = tracer._local
    seen = getattr(local, "nesting", None)
    if seen is None:
        seen = local.nesting = {"trace_s": [], "lower_s": []}
    lst = seen[key]
    dur = int(seconds * 1e9)
    start = tracer.clock_ns() - dur
    inside = 0
    while lst and lst[-1][0] >= start - _SLACK_NS:
        inside += lst.pop()[1]
    if len(lst) > 64:
        del lst[:32]
    lst.append((start, dur))
    return max(0, dur - inside) / 1e9


def _on_duration(event, duration_secs, **_kw):
    if not tracer._master_enabled():
        return
    stack = tracer.open_spans()
    if event in _NESTING:
        key = _NESTING[event]
        seconds = _self_time(key, duration_secs)
        for sp in reversed(stack):
            sp.add(key, seconds)
    elif event == _CACHE_READ:
        tracer._local.cache_hit = True
    elif event == _BACKEND:
        hit = getattr(tracer._local, "cache_hit", False)
        tracer._local.cache_hit = False
        key = "cache_read_s" if hit else "compile_s"
        for sp in reversed(stack):
            sp.add(key, duration_secs)
            sp.add("programs", 1)
            sp.args["compiled"] = True
        (_cache_read_s if hit else _compile_s).inc(duration_secs)
        registry.counter(
            "compiles_total",
            labels={"span": stack[-1].name if stack else "none"},
            help="executables built (compiled or read from the "
                 "compilation cache), by the innermost open span").inc()


def install():
    """Register the listener once per process."""
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)


install()
