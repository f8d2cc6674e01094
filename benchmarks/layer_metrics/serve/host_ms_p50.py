"""Median host time of a ``ServeEngine.step()``, in milliseconds: the
``ds.serve_step`` span less its ``prefill`` and decode children, which is
the scheduler, the block tables and the bookkeeping. An earlier line says
where the device's idle seconds of the stretch fall, by the innermost
program span open at the time (what ``idle_gaps`` files under ``step``)."""

from benchmarks import program_trace as pt
from benchmarks.generate import percentile
from benchmarks.harness import say

DEVICE_WORK = ("prefill",) + pt.DECODE_SPANS


def read(run, observed, reduced):
    trace = pt.of_run(run)
    steps = pt.spans_in_window(trace, "serve_step")
    if not steps:
        return None
    if reduced is not None:
        idle = pt.idle_by_span(trace, reduced.window)
        say("device idle seconds by innermost program span: " + ", ".join(
            f"{name} {sec:.4f}" for name, sec in sorted(
                idle.items(), key=lambda kv: -kv[1])))
    return percentile([pt.self_seconds(trace, s, less=DEVICE_WORK) * 1e3
                       for s in steps], 50)
