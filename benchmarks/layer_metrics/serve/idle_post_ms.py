"""Device idle ms a decode step while the serving engine's innermost span
is ``post_step``: tokens appended, finished requests released, the step's
metrics (``benchmarks/host_idle.py``)."""

from benchmarks import host_idle


def read(run, observed, reduced):
    return host_idle.ms_per_decode_step(run, reduced, "post")
