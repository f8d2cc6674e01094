"""Share of the device's busy time, in percent, that the expert layers
spend on anything but an expert's matmul: the router (``ds.moe_route``),
the sort by held expert, the group sizes and the gather into the sorted
buffer (``ds.moe_dispatch``), and the rows' way back with the weighted sum
(``ds.moe_combine``), forward and backward. The buffer has
``experts_per_token x tokens`` rows whatever share of them is held, so
this is what static shapes cost."""

from benchmarks import moe_trace as mt
from benchmarks import program_trace as pt


def read(run, observed, reduced):
    return pt.share_of_busy(pt.of_run(run), reduced, mt.is_dispatch)
