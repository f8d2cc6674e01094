"""Device idle ms a decode step while the serving engine's innermost span
is ``put``: a step's or a prompt's host-to-device transfers and its key
(``benchmarks/host_idle.py``)."""

from benchmarks import host_idle


def read(run, observed, reduced):
    return host_idle.ms_per_decode_step(run, reduced, "put")
