"""Share of the device's busy time, in percent, spent under ``ds.ssm``: the
Mamba-2 mixers whole (projections, convolution, the recurrence over a
prompt or its one step on the slots' state, gate and group norm), in the
decode and the prefill programs alike. An earlier line gives the device
seconds of its inner scopes."""

from benchmarks import program_trace as pt
from benchmarks.harness import say

INNER = ("ds.ssm_conv", "ds.ssm_scan", "ds.ssm_step")


def read(run, observed, reduced):
    trace = pt.of_run(run)
    share = pt.share_of_busy(trace, reduced,
                             lambda op: pt.in_scope(op, "ds.ssm"))
    if share is not None:
        def part(op):
            if not pt.in_scope(op, "ds.ssm"):
                return None
            inner = [p for p in pt.scope_parts(op.scope) if p in INNER]
            return inner[-1] if inner else "ds.ssm (the rest of the mixer)"
        say("device seconds in the Mamba-2 mixers: " + ", ".join(
            f"{k} {v:.4f}" for k, v in sorted(
                pt.seconds_by(trace, reduced, part).items(),
                key=lambda kv: -kv[1]) if k))
    return share
