"""Seconds of set-up in the backend's compile: the union of the
``backend_compile_duration`` spans that end before the window opens,
which hold XLA compiling a program on a persistent cache miss and the
cache loading it on a hit."""

from benchmarks import setup_log


def read(run, observed, reduced):
    events = setup_log.events_before_window(run, setup_log.COMPILE_LOAD)
    if not events:
        return None
    return setup_log.union_seconds(events)
