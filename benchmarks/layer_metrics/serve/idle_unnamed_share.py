"""Share, in percent, of the device's idle seconds in the traced stretch
that no ``serve.idle_*_ms`` metric takes: a span's own time outside the
host path's spans, ``submit``, no span at all. Host work added outside a
named span shows here (``benchmarks/host_idle.py``)."""

from benchmarks import host_idle


def read(run, observed, reduced):
    return host_idle.unnamed_share(run, reduced)
