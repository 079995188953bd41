"""Seconds in XLA backend compiles under `executor.bind` and the
`executor.train_step` spans before the window (`compile_s`): 0 when the
persistent compilation cache answered every one. Program span."""
from lib import spans


def read(run):
    return spans.setup_sum(run, ("compile_s",))
