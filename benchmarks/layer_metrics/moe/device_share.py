"""Share of the device's busy time, in percent, spent in the expert
layers, forward and backward: the ops under ``ds.moe_route``,
``ds.moe_dispatch``, ``ds.moe_experts``, ``ds.moe_shared`` and
``ds.moe_combine``, and XLA's grouped-matmul kernels, which bear no scope
(``moe_trace.py``). An earlier line gives the device seconds of each
part."""

from benchmarks import moe_trace as mt
from benchmarks import program_trace as pt
from benchmarks.harness import say


def part_of(op):
    if mt.is_grouped_matmul(op):
        return mt.EXPERTS + " (grouped matmul)"
    named = [p for p in pt.scope_parts(op.scope) if p.startswith("ds.moe_")]
    return named[-1] if named else None


def read(run, observed, reduced):
    trace = pt.of_run(run)
    share = pt.share_of_busy(trace, reduced, mt.is_moe)
    if share is not None:
        parts = {k: v for k, v in pt.seconds_by(trace, reduced,
                                                part_of).items() if k}
        say("device seconds in the expert layers: " + ", ".join(
            f"{k} {v:.4f}" for k, v in sorted(parts.items(),
                                              key=lambda kv: -kv[1])))
    return share
