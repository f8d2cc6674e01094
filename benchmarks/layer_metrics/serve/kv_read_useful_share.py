"""Of the KV positions the decode programs read in the traced stretch,
the share that was live, in percent: the sum of ``live_positions`` over
the sum of ``read_positions`` of the stretch's decode spans (the engine
counts both per dispatch: ``ServeEngine.stats``). The default decode reads
every slot's whole reserved window, so this is occupancy times fill."""

from benchmarks import program_trace as pt


def read(run, observed, reduced):
    spans = pt.decode_spans(pt.of_run(run))
    read_positions = sum(s.stats.get("read_positions", 0) for s in spans)
    if not read_positions:
        return None
    return 100.0 * sum(s.stats.get("live_positions", 0)
                       for s in spans) / read_positions
