"""Share of the device's busy time, in percent, spent in the Pallas kernel
named ``flash_bwd`` (flash attention's backward: dQ, dK and dV from one
pass over the causal triangle, since PR 32). With
``kernel.flash_fwd_share`` it adds up to ``kernel.mosaic_share`` where no
other Mosaic kernel runs."""

from benchmarks import program_trace as pt


def read(run, observed, reduced):
    return pt.share_of_busy(pt.of_run(run), reduced,
                            lambda op: op.kernel == "flash_bwd")
