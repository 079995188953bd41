"""Small helpers the per-layer metric readers share. A reader that finds
nothing to read returns None, and the harness leaves the metric out."""
import statistics


def program(run, label):
    trace = run.get("trace")
    if not trace:
        return None
    return trace["programs"].get(label)


def mean_ms(values):
    return 1e3 * statistics.fmean(values) if values else None


def op_seconds(run, patterns):
    """Device seconds of the operations whose name holds one of
    ``patterns``, and how many such names there were."""
    trace = run.get("trace")
    if not trace or not patterns:
        return 0.0, 0
    hit = [v for n, v in trace["op_seconds"].items()
           if any(p in n for p in patterns)]
    return sum(hit), len(hit)


def idle_pct(run):
    trace = run.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
