"""The 90th percentile of the time to first token (from when a request
was due) over the requests of the untraced window. With the 60 requests a
window holds at this cell's rate only six lie beyond it, and it swings by
6% from seed to seed (PERF.md, Findings, PR 22): so it is recorded here,
beside the median (``serve.ttft_ms_p50``), and guards nothing. Left out
below twenty requests, where it would be a maximum."""

from benchmarks.generate import percentile


def read(run, observed, reduced):
    ttft = observed["ttft_ms"]
    return percentile(ttft, 90) if len(ttft) >= 20 else None
