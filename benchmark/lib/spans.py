"""The program's own spans, read from its ring in the driver's process.

``mxnet_tpu.telemetry`` keeps the step-path spans it records (PERF.md,
section 3) in a ring per thread; ``drain_events(clear=False)`` reads it
and leaves it. The last ``run["steps"]`` records named
``executor.train_step`` are the window's; whatever ended before the first
of them is set-up's. A program that records no such span (a parent commit
from before the spans, ``MXNET_TELEMETRY=0``) gives None everywhere, and
the harness leaves the metric out."""
import statistics

STEP = "executor.train_step"
SETUP_ROOTS = ("executor.bind", STEP)


def records():
    """Every record in the ring as a dict, by start time."""
    try:
        from mxnet_tpu import telemetry
        events = telemetry.drain_events(clear=False)
    except Exception:
        return []
    out = [{"name": name, "start_ns": ts, "dur_ns": dur, "args": args or {}}
           for ph, name, _domain, ts, dur, args, _tid, _thread in events
           if ph == "X"]
    out.sort(key=lambda r: r["start_ns"])
    return out


def split(run, recs=None):
    """``(set-up's records, the window's step records)``, or None when the
    ring holds fewer step records than the window ran steps."""
    recs = records() if recs is None else recs
    steps = [r for r in recs if r["name"] == STEP]
    n = int(run.get("steps") or 0)
    if n <= 0 or len(steps) < n:
        return None
    window = steps[-n:]
    opens = window[0]["start_ns"]
    return [r for r in recs if r["start_ns"] < opens], window


def setup_sum(run, keys, recs=None):
    """The sum of the attributes ``keys`` over set-up's ``executor.bind``
    and ``executor.train_step`` records: each holds what its children
    hold, so the children are not added again."""
    parts = split(run, recs)
    if parts is None:
        return None
    return sum(float(r["args"].get(k, 0.0)) for r in parts[0]
               if r["name"] in SETUP_ROOTS for k in keys)


def setup_progcache_hits(run, recs=None):
    """Set-up's ``progcache.load`` records that loaded a program."""
    parts = split(run, recs)
    if parts is None:
        return None
    return [r for r in parts[0]
            if r["name"] == "progcache.load" and r["args"].get("hit")]


def setup_seconds(run, name, recs=None):
    parts = split(run, recs)
    if parts is None:
        return None
    hit = [r["dur_ns"] for r in parts[0] if r["name"] == name]
    return sum(hit) / 1e9 if hit else None


def window_median_ms(run, recs=None):
    parts = split(run, recs)
    if parts is None:
        return None
    return statistics.median(r["dur_ns"] for r in parts[1]) / 1e6


def window_compiles(run, recs=None):
    """How many of the window's steps built a program, and which."""
    parts = split(run, recs)
    if parts is None:
        return None
    return [r["args"].get("step") for r in parts[1]
            if r["args"].get("compiled")]
