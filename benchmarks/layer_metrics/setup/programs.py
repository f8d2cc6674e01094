"""Executables built or loaded before the window opened: the
``backend_compile`` events JAX reports (``jax.monitoring``) that end before
the window's start, eager operations' small programs included, as
``harness.Compiles.count`` counts them. Every program a cell warms up adds
one, and each costs set-up whether it is compiled or loaded. An earlier
line of the output gives the set-up table: by program and the driver's
phase, the executables, trace + lower and compile/load seconds, and the
persistent cache's hits and misses."""

from benchmarks import setup_log


def read(run, observed, reduced):
    events = setup_log.events_before_window(run, setup_log.COMPILE_LOAD)
    if not events:
        return None
    setup_log.print_table(run)
    return len(events)
