"""Of the persistent compile cache's answers before the window opened, the
share that were hits, in percent: 100 x hits / (hits + misses), as
``jax.monitoring`` reports them. ``None`` where no compile request used
the cache (a rehearsal, which has none). A run after a checkout's first
finds every program there and reads 100; a program that misses is compiled
again in every run."""

from benchmarks import setup_log


def read(run, observed, reduced):
    seen = setup_log.cache_before_window(run)
    if seen is None or not sum(seen):
        return None
    hits, misses = seen
    return 100.0 * hits / (hits + misses)
