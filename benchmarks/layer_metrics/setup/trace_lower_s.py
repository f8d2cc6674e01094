"""Seconds of set-up in which JAX traced a function to a jaxpr or lowered
one to an MLIR module: the UNION of the ``jaxpr_trace_duration`` and
``jaxpr_to_mlir_module_duration`` spans that end before the window opens
(an inner ``jit`` is traced inside its caller's trace, so the spans'
sum would count it twice). A persistent cache hit saves the compile and
not this: it is paid in every run."""

from benchmarks import setup_log


def read(run, observed, reduced):
    events = setup_log.events_before_window(run, setup_log.TRACE_LOWER)
    if not events:
        return None
    return setup_log.union_seconds(events)
