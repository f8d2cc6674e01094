"""Executables built or loaded inside the measured window (jax.monitoring).
It should be 0: a compile in the window is a stall that the run charges to
throughput, and it makes the run not ``correct``."""


def read(run, observed, reduced):
    return observed["compiles_in_window"]
