"""How late the benchmark's own client submitted requests (submitted -
due), 99th percentile in milliseconds. The client has one thread, so a
request that falls due during a step waits for it: at most one step, the
step ``itl_ms_p95`` reads, and that wait is inside the time to first
token (``serve.ttft_ms_p50``). Large against that time, it would mean a
starved generator, not a slow server."""

from benchmarks.generate import percentile


def read(run, observed, reduced):
    return percentile(observed["late_ms"], 99)
