"""Peak bytes in use on the fullest chip, in GiB, as the runtime's
``memory_stats()`` counts them up to the end of the system's own work
(what the reference needs afterwards is not counted)."""

from benchmarks.harness import device_info


def read(run, observed, reduced):
    peak = device_info(run)["memory_peak_bytes"]
    return peak / 2 ** 30 if peak else None
