"""mxnet_tpu.telemetry — process-wide tracing + metrics (ISSUE 4).

Four pieces, all with branch-and-return disabled paths:

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

See docs/observability.md. Instrumentation must live OUTSIDE
jitted/shard_mapped functions — enforced by
``mxnet_tpu.analysis.trace_purity`` (rule ``telemetry-in-jit``), which
also flags ``current_context()`` reads inside jitted code.
"""
from .tracer import (STEP_PATH, begin, chrome_events, clock_ns, complete,
                     disable_spans, drain_events, dump_ring, enable_spans,
                     enabled, enabled_domains, end, instant, open_spans,
                     reset, set_span_sink, span)
from .metrics import (CONTENT_TYPE_LATEST, Counter, Gauge, Histogram,
                      Registry, registry)
from . import compiles
from . import context
from . import flight
from .context import TraceContext, current_context

__all__ = [
    "span", "begin", "end", "complete", "instant", "open_spans",
    "STEP_PATH", "enabled", "enable_spans", "disable_spans", "enabled_domains",
    "drain_events", "chrome_events", "clock_ns", "reset", "dump_ring",
    "set_span_sink",
    "registry", "Registry", "Counter", "Gauge", "Histogram",
    "CONTENT_TYPE_LATEST",
    "context", "flight", "TraceContext", "current_context",
]
