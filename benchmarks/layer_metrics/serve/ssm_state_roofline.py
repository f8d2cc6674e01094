"""The recurrent state's share of its roofline, in percent: the least time
the chip could take to read and write the state of the rows that were
ALIVE (``state_slots_live`` of the stretch's ``ds.decode_step`` spans, in
every Mamba-2 layer, at the published HBM bandwidth;
``flops_nemotron_h.ssm_state_step_bytes``) over the device time under
``ds.ssm_step`` in the same stretch. A program that also moves the state
of dead slots reads low."""

from benchmarks import flops
from benchmarks import flops_nemotron_h as count
from benchmarks import program_trace as pt
from benchmarks.harness import say


def read(run, observed, reduced):
    if reduced is None or run.peaks is None:
        return None
    trace = pt.of_run(run)
    spans = [s for s in pt.decode_spans(trace)
             if "state_slots_live" in s.stats]
    taken = pt.seconds_by(trace, reduced, lambda op: pt.in_scope(
        op, "ds.ssm_step")).get(True, 0.0)
    if not spans or taken <= 0.0:
        return None
    rows = sum(s.stats["state_slots_live"] for s in spans)
    least, _ = flops.roofline_seconds(
        0.0, count.ssm_state_step_bytes(run.config, rows), run.peaks)
    say(f"recurrent state roofline: {len(spans)} decode steps, "
        f"{rows / len(spans):.1f} rows alive a step: {least:.4f}s needed "
        f"against {taken:.4f}s under ds.ssm_step")
    return 100.0 * least / taken
