"""Of the rows the latent experts' grouped matmul computes at decode, the
share that are real, in percent: 100 x the sum of ``moe_held_assignments``
over the sum of ``moe_rows_computed`` (row tiles visited x the kernel's row
tile) of the stretch's ``ds.decode_step`` spans. ``None`` where that sum
is 0 (XLA's kernel, which counts no tiles) or the counter is absent."""

from benchmarks import program_trace as pt


def read(run, observed, reduced):
    spans = [s for s in pt.decode_spans(pt.of_run(run))
             if "moe_rows_computed" in s.stats
             and "moe_held_assignments" in s.stats]
    computed = sum(s.stats["moe_rows_computed"] for s in spans)
    if not computed:
        return None
    return 100.0 * sum(s.stats["moe_held_assignments"]
                       for s in spans) / computed
