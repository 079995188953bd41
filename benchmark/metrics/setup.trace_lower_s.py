"""Seconds JAX spent tracing jaxprs and lowering them to MLIR under
`executor.bind` and the `executor.train_step` spans before the window
(`trace_s` + `lower_s`, which the compile listener charges to the spans open
when they happen). Program span."""
from lib import spans


def read(run):
    return spans.setup_sum(run, ("trace_s", "lower_s"))
