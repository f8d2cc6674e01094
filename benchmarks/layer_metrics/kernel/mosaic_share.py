"""Share of the device's busy time, in percent, spent in Mosaic (Pallas)
custom calls: the flash-attention kernels in a training cell. 0 where the
attention dispatch takes the XLA path."""


def read(run, observed, reduced):
    return None if reduced is None else 100.0 * reduced.share_of_busy("mosaic")
