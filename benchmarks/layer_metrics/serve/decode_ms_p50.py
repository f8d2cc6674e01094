"""Median duration, in milliseconds, of the program's ``ds.decode_step``
spans in the traced stretch (``mixed_step`` and ``spec_step`` alike):
dispatch of the decode program to its tokens on the host."""

from benchmarks import program_trace as pt
from benchmarks.generate import percentile


def read(run, observed, reduced):
    spans = pt.decode_spans(pt.of_run(run))
    return percentile([s.duration * 1e3 for s in spans], 50) if spans \
        else None
