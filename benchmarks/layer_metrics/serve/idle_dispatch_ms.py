"""Device idle ms a decode step while the serving engine's innermost span
is ``dispatch``: a jitted call, from the call to its return
(``benchmarks/host_idle.py``)."""

from benchmarks import host_idle


def read(run, observed, reduced):
    return host_idle.ms_per_decode_step(run, reduced, "dispatch")
