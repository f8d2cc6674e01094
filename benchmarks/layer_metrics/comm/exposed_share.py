"""Collective time during which no compute ran on that device, over the
traced window, in percent, mean over the devices: the part of
``comm.collective_share`` that overlap could win back."""


def read(run, observed, reduced):
    if reduced is None or run.chips < 2:
        return None
    return (100.0 * reduced.mean(reduced.exposed_collective)
            / reduced.window_s)
