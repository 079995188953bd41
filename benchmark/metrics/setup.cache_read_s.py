"""Seconds reading executables back before the window: `cache_read_s` of
`executor.bind` and the set-up `executor.train_step` spans (JAX's persistent
compilation cache) plus the `progcache.load` spans that loaded a program
(the package's own program cache). Program span."""
from lib import spans


def read(run):
    jax_cache = spans.setup_sum(run, ("cache_read_s",))
    hits = spans.setup_progcache_hits(run)
    if jax_cache is None or hits is None:
        return None
    return jax_cache + sum(r["dur_ns"] for r in hits) / 1e9
