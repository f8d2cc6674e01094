"""Host milliseconds per ``ServeEngine.step()``, median over the steps of
the untraced window: admission, at most one prefill, one decode of every
active row, and the fetch of its tokens."""

from benchmarks.generate import percentile


def read(run, observed, reduced):
    return percentile(observed["step_ms"], 50) if observed["step_ms"] else None
