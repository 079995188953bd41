"""Executables compiled or read back before the window under
`executor.bind` and the set-up `executor.train_step` spans (`programs`), plus
the program-cache loads that hit. The AUTO-layout path builds the step twice.
Program span."""
from lib import spans


def read(run):
    built = spans.setup_sum(run, ("programs",))
    hits = spans.setup_progcache_hits(run)
    if built is None or hits is None:
        return None
    return built + len(hits)
