"""Share of the device's busy time, in percent, spent in the prefill and
pack programs: every op of an executable that holds the scope
``ds.prefill`` or ``ds.pack``, the compiler's own copies of its parameters
and results included (they bear no scope, and in the pack program they are
nearly all of its time). What admitting requests costs the rows that are
decoding."""

from benchmarks import program_trace as pt


def read(run, observed, reduced):
    trace = pt.of_run(run)
    if trace is None:
        return None
    programs = pt.programs_under(trace, "ds.prefill", "ds.pack")
    return pt.share_of_busy(trace, reduced,
                            lambda op: op.program_id in programs)
