"""Share of the device's busy time, in percent, spent once per optimizer
step whatever the number of micro-batches: the update itself
(``ds.optimizer``: unscale, norm, clip, update, zeroing) and the cast of
the master weights to the compute dtype (``ds.cast_params``)."""

from benchmarks import program_trace as pt


def read(run, observed, reduced):
    return pt.share_of_busy(
        pt.of_run(run), reduced,
        lambda op: pt.in_scope(op, "ds.optimizer", "ds.cast_params"))
