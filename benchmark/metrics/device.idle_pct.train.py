"""1 - busy/window over the traced steps of a training cell. Device trace."""
from lib import readers


def read(run):
    return readers.idle_pct(run)
