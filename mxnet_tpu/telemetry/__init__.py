"""mxnet_tpu.telemetry — process-wide tracing + metrics (ISSUE 4).

Five pieces, all with branch-and-return disabled paths:

- **tracing** (:mod:`.tracer`): per-thread ring-buffer span recorder.
  Domains are OFF by default; enable them with
  ``MXNET_PROFILER=engine,serving,kvstore`` (or ``all``), or
  programmatically via :func:`enable_spans`. The step-path spans
  (``tracer.STEP_PATH``) record regardless. Every span is also a
  ``jax.profiler.TraceAnnotation``, so a profiler session holds it on
  the device trace's clock; :mod:`.compiles` charges traces, lowerings,
  compiles and cache reads to the spans open when they happen.
  ``profiler.dump_profile()`` writes a chrome://tracing JSON.
- **metrics** (:mod:`.metrics`): the central :data:`registry` of
  counters/gauges/histograms plus adopted metric groups (ServingMetrics
  et al.), with ``get_name_value()`` and Prometheus ``exposition()``
  (histograms carry OpenMetrics exemplars linking buckets to traces).
  Counters are ON by default; ``MXNET_TELEMETRY=0`` kills everything.
- **trace context** (:mod:`.context`): W3C ``traceparent`` parse/mint
  at the HTTP edge, thread-local + object carry through serving and
  the PS plane, ``trace_id``/``span_id``/``parent_id`` span stamps.
- **flight recorder** (:mod:`.flight`): always-on bounded ring of
  completed request timelines; SLO anomalies (deadline miss, shed,
  compile-after-steady, drain, ``MXNET_SLOW_REQUEST_MS``) write
  diagnostic bundles to ``MXNET_FLIGHT_DIR``.

- **program records** (:mod:`.programs`): what each step program the
  executor built is (its device operations by graph node, the kernels
  its layers built, the bytes it wants), read through :func:`programs`.

See docs/observability.md. Instrumentation must live OUTSIDE
jitted/shard_mapped functions — enforced by
``mxnet_tpu.analysis.trace_purity`` (rule ``telemetry-in-jit``), which
also flags ``current_context()`` reads inside jitted code.
"""
from .tracer import (STEP_PATH, begin, chrome_events, clock_ns, complete,
                     disable_spans, drain_events, dump_ring, enable_spans,
                     enabled, enabled_domains, end, instant, open_spans,
                     set_span_sink, span)
from .tracer import reset as _reset_ring
from .metrics import (CONTENT_TYPE_LATEST, Counter, Gauge, Histogram,
                      Registry, registry)
# the name is the function from here on: the module's other names are
# imported from it (``from mxnet_tpu.telemetry.programs import note``)
from .programs import clear as _clear_programs, programs
from . import compiles
from . import context
from . import flight
from .context import TraceContext, current_context



def reset():
    """Drop every buffered event and every program record."""
    _reset_ring()
    _clear_programs()


__all__ = [
    "span", "begin", "end", "complete", "instant", "open_spans",
    "STEP_PATH", "enabled", "enable_spans", "disable_spans", "enabled_domains",
    "drain_events", "chrome_events", "clock_ns", "reset", "dump_ring",
    "set_span_sink", "programs",
    "registry", "Registry", "Counter", "Gauge", "Histogram",
    "CONTENT_TYPE_LATEST",
    "context", "flight", "TraceContext", "current_context",
]
