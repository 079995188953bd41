"""Median duration of the window's `executor.train_step` spans: the host's
side of one step, argument preparation and the dispatch into the program.
Program span."""
from lib import spans


def read(run):
    return spans.window_median_ms(run)
