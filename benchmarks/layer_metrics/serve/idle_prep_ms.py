"""Device idle ms a decode step while the serving engine's innermost span
is ``admit`` or ``batch_inputs``: the scheduler and the host arrays of a
step (``benchmarks/host_idle.py``)."""

from benchmarks import host_idle


def read(run, observed, reduced):
    return host_idle.ms_per_decode_step(run, reduced, "prep")
