"""Median duration, in milliseconds, of the program's ``ds.prefill`` spans
in the traced stretch: a prompt's dispatch, the pack of its cache into
pool blocks and the fetch of its first token. A step that admits a
request is longer than a decode-only step by this much."""

from benchmarks import program_trace as pt
from benchmarks.generate import percentile


def read(run, observed, reduced):
    spans = pt.spans_in_window(pt.of_run(run), "prefill")
    return percentile([s.duration * 1e3 for s in spans], 50) if spans \
        else None
