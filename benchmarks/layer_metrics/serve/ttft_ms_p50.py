"""The median time to first token (from when a request was due) over the
requests of the untraced window. A request falls due somewhere inside the
step that is running, waits for it to return, and gets its first token
from the next step, which prefills it: so this is about half a step plus
the step that carries a prefill, the step whose length ``itl_ms_p95``
reads. It was an end-to-end metric until the driver's check of PR 22 read
a spread of 4.8% and 12.5% over two sets of six runs of 60 requests each,
too wide for any bound the contract allows (PERF.md, Findings, PR 22):
so it is recorded here and guards nothing."""

from benchmarks.generate import percentile


def read(run, observed, reduced):
    return percentile(observed["ttft_ms"], 50) if observed["ttft_ms"] else None
