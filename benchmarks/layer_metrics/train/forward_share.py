"""Share of the device's busy time, in percent, spent in the forward pass:
the ops whose scope JAX marked ``jvp(...)`` and not ``transpose(...)``.
With ``train.backward_share`` and ``train.optimizer_share`` it splits a
step; what the three leave is the part of the step no scope names."""

from benchmarks import program_trace


def read(run, observed, reduced):
    return program_trace.share_of_busy(program_trace.of_run(run), reduced,
                                       program_trace.is_forward)
