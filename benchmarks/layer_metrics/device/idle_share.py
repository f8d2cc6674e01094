"""Share of the traced window, in percent, in which no operation ran on
the device (mean over the devices): 1 - busy / window."""


def read(run, observed, reduced):
    if reduced is None:
        return None
    return 100.0 * (1.0 - reduced.busy_s / reduced.window_s)
