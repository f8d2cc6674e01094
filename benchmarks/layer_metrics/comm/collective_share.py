"""Time with a collective running or in flight, over the traced window, in
percent: how much communication there is, hidden or not. Read on the
chips whose trace holds the collectives' spans in flight (the profiler
records them for the first chip of a host only; without them a hidden
collective cannot be seen), else on all. Left out on one chip, where
there is none."""


def read(run, observed, reduced):
    if reduced is None or run.chips < 2:
        return None
    seen = reduced.by_class["collective"]
    where = reduced.spans_in_flight or list(seen)
    return (100.0 * reduced.mean({d: seen[d] for d in where})
            / reduced.window_s)
