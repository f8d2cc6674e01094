"""Share of the device's busy time, in percent, spent in operations the
profiler files under the MXU (convolution fusions: every dot of the model,
the attention dispatch's XLA path and the CE head)."""


def read(run, observed, reduced):
    return None if reduced is None else 100.0 * reduced.share_of_busy("matmul")
