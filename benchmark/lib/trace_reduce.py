"""From a profiler trace to numbers: device busy and idle time, time per
program, time per device operation, idle gaps named by what the host was
doing. Starts from the reduction in ``tools/profile_step.py`` (the device
plane's "XLA Ops" line, control-flow umbrellas skipped), reads the trace
with ``jax.profiler.ProfileData`` instead of the TensorFlow proto, and adds
the interval arithmetic.

Two halves, so that the arithmetic is tested without a chip:
``load(path)`` turns an ``.xplane.pb`` into a plain dict
``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
dur_ns], ...]}]}]}`` (the form ``tests/data/trace_small.json`` keeps), and
``reduce(trace, ...)`` does the rest on that dict alone.
"""
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# events that envelop other device events: counting them would fill gaps
UMBRELLAS = ("while", "conditional", "call")
# host events that only say the profiler or an idle thread pool is there
HOST_NOISE = ("ThreadpoolListener", "$profiler", "start_trace", "stop_trace",
              "ProfilerSession", "CollectData")


def find_xplane(logdir):
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError("no .xplane.pb under %r" % logdir)
    return files[-1]


def load(path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                      for ev in line.events]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals):
    return sum(e - s for s, e in intervals)


def gaps(busy, lo, hi):
    """The idle intervals of [lo, hi) that ``busy`` (merged) leaves."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append([at, s])
        at = max(at, e)
    if hi > at:
        out.append([at, hi])
    return out


def _is_umbrella(name):
    head = name.lstrip("%").split(".")[0].split("(")[0].strip().lower()
    return head in UMBRELLAS


def short_name(name, width=64):
    keep = "".join(c if (c.isalnum() or c in "._-") else "_" for c in name)
    return keep[:width]


def device_planes(trace):
    return [p for p in trace["planes"] if p["name"].startswith(DEVICE_PREFIX)]


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def host_events(trace):
    """Every host-thread event as (name, start, end), profiler noise out."""
    out = []
    for plane in trace["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                if d <= 0 or any(n in name for n in HOST_NOISE):
                    continue
                out.append((name, s, s + d))
    return out


LONG_HOST_NS = 10e6


def index_hosts(hosts):
    """Split host events into the long ones (scanned whole for every gap)
    and the short ones sorted by start (scanned only near the gap)."""
    long_ = [h for h in hosts if h[2] - h[1] >= LONG_HOST_NS]
    short = sorted((h for h in hosts if h[2] - h[1] < LONG_HOST_NS),
                   key=lambda h: h[1])
    return long_, short, [h[1] for h in short]


def name_gap(gap, index):
    """The shortest host event that covers the gap's middle: what the host
    was doing while the device waited."""
    import bisect

    long_, short, starts = index
    mid = 0.5 * (gap[0] + gap[1])
    best, best_len = None, None
    i = bisect.bisect_right(starts, mid) - 1
    while i >= 0 and starts[i] > mid - LONG_HOST_NS:
        name, s, e = short[i]
        if e > mid and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
        i -= 1
    if best is None:
        for name, s, e in long_:
            if s <= mid < e and (best_len is None or e - s < best_len):
                best, best_len = name, e - s
    return best or "no_host_span"


def reduce(trace, program_patterns=None, long_gap_ns=50e3, top=10,
           align=None):
    """The window is the span of the device events.
    ``program_patterns``: {label: [substring,
    ...]} matched against the names on the "XLA Modules" line, so that a
    cell's traffic file, not this code, says which programs it drives.

    ``align``: a label of ``program_patterns``; the window is then cut to
    run from the start of that program's first whole run to the end of its
    last, so that it holds whole steps only.

    Returns seconds throughout. Per device plane the busy time is the union
    of the "XLA Ops" intervals (umbrellas out); ``busy_s`` averages the
    planes that ran anything."""
    planes = device_planes(trace)
    if not planes:
        raise ValueError("no %s* plane in the trace: not taken on a TPU"
                         % DEVICE_PREFIX)
    per_plane = []
    for plane in planes:
        ops = [(n, s, s + d) for n, s, d in _line(plane, OPS_LINE)
               if d > 0 and not _is_umbrella(n)]
        if ops:
            per_plane.append((plane, ops))
    if not per_plane:
        raise ValueError("no operation ran on a device in the trace")
    lo = min(s for _, ops in per_plane for _, s, _ in ops)
    hi = max(e for _, ops in per_plane for _, _, e in ops)
    if align and program_patterns and align in program_patterns:
        runs = sorted((s, s + d)
                      for n, s, d in _line(per_plane[0][0], MODULES_LINE)
                      if d > 0 and s >= lo and s + d <= hi
                      and any(p in n for p in program_patterns[align]))
        if len(runs) >= 2:
            lo, hi = runs[0][0], runs[-1][1]
    busy_each = []
    for _, ops in per_plane:
        busy_each.append(clip(union([[s, e] for _, s, e in ops]), lo, hi))
    busy_s = sum(total(b) for b in busy_each) / len(busy_each) / 1e9

    # the first plane carries the breakdown (one chip, or one of four alike)
    plane, ops = per_plane[0]
    busy = busy_each[0]
    by_op = {}
    for name, s, e in ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            by_op[name] = by_op.get(name, 0.0) + (e - s)
    device_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]

    programs = {}
    modules = [(n, s, s + d) for n, s, d in _line(plane, MODULES_LINE)
               if d > 0 and s >= lo and s + d <= hi]
    for label, pats in (program_patterns or {}).items():
        runs = sorted((s, e) for n, s, e in modules
                      if any(p in n for p in pats))
        if not runs:
            continue
        busy_in = [total(clip(busy, s, e)) for s, e in runs]
        between = [runs[i + 1][0] - runs[i][1] for i in range(len(runs) - 1)]
        programs[label] = {
            "runs": len(runs),
            "busy_s": [b / 1e9 for b in busy_in],
            "gap_after_s": [g / 1e9 for g in between],
        }

    hosts = index_hosts(host_events(trace))
    named, short = {}, 0.0
    for gap in gaps(busy, lo, hi):
        length = gap[1] - gap[0]
        if length < long_gap_ns:
            short += length
            continue
        key = name_gap(gap, hosts)
        named[key] = named.get(key, 0.0) + length
    idle = sorted(named.items(), key=lambda kv: -kv[1])[:top - 1]
    idle.append(("shorter_gaps", short))
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_s,
        "planes": len(per_plane),
        "programs": programs,
        "op_seconds": {n: v / 1e9 for n, v in by_op.items()},
        "breakdown": {
            "device_ops": [[short_name(n), v / 1e9] for n, v in device_ops],
            "idle_gaps": [[short_name(n), v / 1e9] for n, v in idle],
        },
    }
