"""Seconds in `executor.bind` before the window: shapes inferred, the
argument and gradient buffers allocated, the executor built. Program span."""
from lib import spans


def read(run):
    return spans.setup_seconds(run, "executor.bind")
