"""Share of the device's busy time, in percent, spent in the Pallas kernel
named ``flash_fwd`` (flash attention's forward). With
``kernel.flash_bwd_share`` it adds up to ``kernel.mosaic_share`` where no
other Mosaic kernel runs."""

from benchmarks import program_trace as pt


def read(run, observed, reduced):
    return pt.share_of_busy(pt.of_run(run), reduced,
                            lambda op: op.kernel == "flash_fwd")
