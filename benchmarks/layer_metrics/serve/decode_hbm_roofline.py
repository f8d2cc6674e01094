"""The decode step's share of its HBM roofline, in percent: the least time
for ALL the stretch's decode steps must read and write (every weight
outside the routed experts, the experts touched, the live rows' recurrent
state both ways, the live K/V; ``flops_nemotron_h.decode_step_bytes``, from
the ``ds.decode_step`` spans' own counts) at the published HBM bandwidth,
over the device time of the decode program in the same stretch."""

from benchmarks import flops
from benchmarks import flops_nemotron_h as count
from benchmarks import program_trace as pt
from benchmarks.harness import say

NEEDS = ("state_slots_live", "moe_experts_touched", "moe_held_assignments",
         "live_positions")


def read(run, observed, reduced):
    if reduced is None or run.peaks is None:
        return None
    trace = pt.of_run(run)
    spans = [s for s in pt.decode_spans(trace)
             if all(k in s.stats for k in NEEDS)]
    decode = pt.programs_under(trace, "ds.decode") if trace else {}
    taken = pt.seconds_by(trace, reduced, lambda op: (
        op.program_id in decode)).get(True, 0.0)
    if not spans or taken <= 0.0:
        return None
    nbytes = sum(count.decode_step_bytes(
        run.config, live_rows=s.stats["state_slots_live"],
        experts_touched=s.stats["moe_experts_touched"],
        held_assignments=s.stats["moe_held_assignments"],
        live_positions=s.stats["live_positions"]) for s in spans)
    least, _ = flops.roofline_seconds(0.0, nbytes, run.peaks)
    say(f"decode step HBM roofline: {len(spans)} steps have to move "
        f"{nbytes / len(spans) / 1e9:.3f} GB each: {least:.4f}s needed "
        f"against {taken:.4f}s of the decode program")
    return 100.0 * least / taken
