"""Device busy time inside one run of the fused-step program. Device trace."""
from lib import readers


def read(run):
    step = readers.program(run, "step")
    return readers.mean_ms(step["busy_s"]) if step else None
