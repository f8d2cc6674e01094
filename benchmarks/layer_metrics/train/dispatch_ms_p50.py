"""Host milliseconds inside ``engine.train_batch()`` until it returns,
median over the untraced window. It matters once it nears the step time:
until then the device has the next step queued before it finishes one."""

from benchmarks.generate import percentile


def read(run, observed, reduced):
    return percentile(observed["dispatch_ms"], 50)
