"""Share of the device's busy time, in percent, spent under ``ds.mtp``,
forward and backward: the multi-token-prediction module's second embedding
lookup, its two norms and ``eh_proj``, its block and its pass through the
head. Its block's grouped expert matmuls are NOT in it: the compiler's
kernel bears no scope (``moe_trace.py``), so they are counted with the
other layers' under ``moe.device_share`` alone."""

from benchmarks import program_trace as pt


def read(run, observed, reduced):
    return pt.share_of_busy(pt.of_run(run), reduced,
                            lambda op: pt.in_scope(op, "ds.mtp"))
