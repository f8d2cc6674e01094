"""Share of the device's busy time, in percent, spent in the backward
pass: the ops whose scope JAX marked ``transpose(...)``, which under remat
holds the recomputed forward too, and the add into the gradient
accumulators (``ds.accumulate``)."""

from benchmarks import program_trace as pt


def read(run, observed, reduced):
    return pt.share_of_busy(
        pt.of_run(run), reduced,
        lambda op: pt.is_backward(op) or pt.in_scope(op, "ds.accumulate"))
