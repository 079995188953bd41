"""Profiler controls.

TPU-native analogue of python/mxnet/profiler.py + src/engine/profiler.cc
(SURVEY §5.1). The reference stamps per-op begin/end in engine workers
and dumps chrome://tracing JSON (MXDumpProfile). Here the host half of
that picture comes from :mod:`mxnet_tpu.telemetry` (per-thread span ring
buffers instrumenting the engine, serving, kvstore and executor layers)
plus the engine's own per-op events; the device half is a jax.profiler
trace, which holds the host spans too (every span is a
``TraceAnnotation``). ``dump_profile()`` writes ONE
chrome://tracing-loadable JSON file on ONE clock: the profiler's when
the window took a jax trace, the ring's monotonic clock when it did not.
It ALWAYS writes that file at the configured ``filename`` (logging the
path), even when the jax trace was never started and even with zero
host events, so a CPU-only run has real output (docs/observability.md).
"""
from __future__ import annotations

import glob
import json
import logging
import os

from . import telemetry

_log = logging.getLogger("mxnet_tpu")

_state = {"running": False, "dir": None, "filename": "profile.json",
          "jax": False, "jax_taken": False, "engine_prof": False,
          "prev_domains": None}


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """(reference profiler.py profiler_set_config / MXSetProfilerConfig)."""
    _state["filename"] = filename
    _state["dir"] = os.path.dirname(os.path.abspath(filename)) or "."


def profiler_set_state(state="stop"):
    """(reference profiler.py profiler_set_state / MXSetProfilerState).

    ``'run'`` enables host telemetry spans (every domain unless
    ``MXNET_PROFILER`` names a subset), turns on the engine's per-op
    profiling, and — unless ``MXNET_PROFILER_JAX=0`` — starts a
    jax.profiler trace under ``<dir>/jax_trace``. ``'stop'`` ends the
    window; ``dump_profile()`` flushes everything to one JSON file."""
    if state == "run" and not _state["running"]:
        _state["prev_domains"] = (telemetry.enabled_domains()
                                  if telemetry.enabled_domains() else None)
        telemetry.enable_spans(os.environ.get("MXNET_PROFILER") or "all")
        try:
            from . import engine

            engine.get().set_profiling(True)
            _state["engine_prof"] = True
        except Exception:
            _state["engine_prof"] = False
        if os.environ.get("MXNET_PROFILER_JAX", "1") != "0":
            try:
                import jax

                trace_dir = (_state["dir"] or ".") + "/jax_trace"
                os.makedirs(trace_dir, exist_ok=True)
                jax.profiler.start_trace(trace_dir)
                _state["jax"] = _state["jax_taken"] = True
            except Exception:
                _log.exception("jax.profiler trace failed to start; "
                               "host-span profiling continues")
                _state["jax"] = False
        _state["running"] = True
    elif state == "stop" and _state["running"]:
        if _state["jax"]:
            import jax

            jax.profiler.stop_trace()
            _state["jax"] = False
            _log.info("profiler trace written under %s/jax_trace",
                      _state["dir"] or ".")
        if _state["engine_prof"]:
            try:
                from . import engine

                engine.get().set_profiling(False)
            except Exception:
                pass
        if _state["prev_domains"]:
            telemetry.enable_spans(_state["prev_domains"])
        else:
            telemetry.disable_spans()
        _state["running"] = False


def _ring_file_events(dirs):
    """Merge span ring files dumped by OTHER processes
    (``telemetry.dump_ring()`` — PS servers, launcher-spawned workers
    write ``telemetry_ring_<pid>.json``). Each file's events are already
    chrome-format; pid tags keep their rows separate in the viewer, and
    trace-stamped spans join the same trace_id across processes. Files
    are consumed (removed) so a second dump only sees newer rings."""
    events = []
    seen = set()
    for d in dirs:
        if not d or d in seen:
            continue
        seen.add(d)
        for path in sorted(glob.glob(
                os.path.join(d, "telemetry_ring_*.json"))):
            try:
                with open(path) as f:
                    data = json.load(f)
                evs = (data if isinstance(data, list)
                       else data.get("traceEvents", [])
                       if isinstance(data, dict) else [])
                events.extend(e for e in evs if isinstance(e, dict))
                os.remove(path)
            except (OSError, ValueError):
                continue
    return events


def _xplane_events(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir`` as chrome events,
    every plane (host threads and devices) on the profiler's clock: one
    pid per plane, one tid per line. Empty when there is none."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return []
    from jax.profiler import ProfileData

    events = []
    for pid, plane in enumerate(ProfileData.from_file(files[-1]).planes, 1):
        rows = []
        for tid, line in enumerate(plane.lines, 1):
            evs = [{"name": ev.name, "ph": "X", "pid": pid, "tid": tid,
                    "ts": ev.start_ns / 1000.0,
                    "dur": ev.duration_ns / 1000.0} for ev in line.events]
            if evs:
                rows.append({"name": "thread_name", "ph": "M", "pid": pid,
                             "tid": tid, "args": {"name": line.name}})
                rows.extend(evs)
        if rows:
            events.append({"name": "process_name", "ph": "M", "pid": pid,
                           "args": {"name": plane.name}})
            events.extend(rows)
    return events


def dump_profile() -> str:
    """(reference MXDumpProfile) — stop the window if running and write
    the chrome://tracing JSON at the configured ``filename``.

    Always writes (zero events included) and returns the absolute path.
    ``traceEvents`` is on one clock. When the window took a jax trace it
    is that trace, device lines and host rows alike: the host spans are
    in it as annotations. The ring's events (host spans with their
    attributes, the engine's per-op events, other processes' ring
    files), which are on ``time.monotonic_ns``, then go beside it under
    ``ringEvents``, never into the same list. Without a jax trace
    ``traceEvents`` is the ring. The ring is drained either way: a
    second dump only contains newer events."""
    if _state["running"]:
        profiler_set_state("stop")
    path = os.path.abspath(_state["filename"])
    ring = telemetry.chrome_events(clear=True)
    n_host = len(ring)
    if _state["engine_prof"]:
        try:
            from . import engine

            ring.extend(engine.get().dump_profile().get("traceEvents", []))
        except Exception:
            pass
        _state["engine_prof"] = False
    ring.extend(_ring_file_events(
        [_state["dir"], os.environ.get("MXNET_TELEMETRY_RING_DIR")]))
    doc = {"traceEvents": ring, "displayTimeUnit": "ms",
           "clock": "monotonic_ns"}
    if _state["jax_taken"]:
        _state["jax_taken"] = False
        try:
            traced = _xplane_events((_state["dir"] or ".") + "/jax_trace")
        except Exception:
            _log.exception("the jax trace could not be read; the profile "
                           "holds the host ring alone")
            traced = []
        if traced:
            doc = {"traceEvents": traced, "displayTimeUnit": "ms",
                   "clock": "profiler", "ringEvents": ring}
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f)
    _log.info("profile dumped to %s (%d events on the %s clock, %d host "
              "spans)", path, len(doc["traceEvents"]), doc["clock"], n_host)
    return path


class TraceAnnotation:
    """Named region annotation visible in the trace (reference per-op
    OprExecStat naming; here jax.profiler.TraceAnnotation)."""

    def __init__(self, name, **kwargs):
        import jax

        self._ctx = jax.profiler.TraceAnnotation(name, **kwargs)

    def __enter__(self):
        return self._ctx.__enter__()

    def __exit__(self, *a):
        return self._ctx.__exit__(*a)
