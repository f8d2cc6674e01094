"""Device idle ms a decode step while the serving engine's innermost span
is ``fetch``: while the host waits for a token the chip is busy, so what
is idle here is the program's launch and the token's way back
(``benchmarks/host_idle.py``)."""

from benchmarks import host_idle


def read(run, observed, reduced):
    return host_idle.ms_per_decode_step(run, reduced, "fetch")
