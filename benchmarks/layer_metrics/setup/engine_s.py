"""Seconds of the driver's ``engine construction`` phase of set-up: both
drivers mark it round the program's own entry, ``initialize()`` in
training and ``init_serving()`` in serving, up to its state on the
device. ``None`` where the driver marks no such phase."""

PHASE = "engine construction"


def read(run, observed, reduced):
    return dict(getattr(run, "phases", ())).get(PHASE)
