"""Share of the device's busy time, in percent, spent in the expert layers
of the serving programs: the ops under the five ``ds.moe_*`` scopes plus
XLA's grouped-matmul kernels, which bear no scope (``moe_trace.py``'s
rule, as ``moe.device_share`` reads it in the training cell). An earlier
line gives the device seconds of each part."""

from benchmarks.harness import load_module


def read(run, observed, reduced):
    return load_module("layer_metrics", "moe.device_share").read(
        run, observed, reduced)
