"""Share of the device's busy time, in percent, spent under ``ds.mla``,
forward and backward: latent attention's two low-rank paths with their
inner norms, RoPE, the attention itself (the flash kernels where the
dispatch takes them) and the output projection, in every block, the MTP
module's among them."""

from benchmarks import program_trace as pt


def read(run, observed, reduced):
    return pt.share_of_busy(pt.of_run(run), reduced,
                            lambda op: pt.in_scope(op, "ds.mla"))
