"""The held experts' share of their roofline at decode, in percent: the
least time for their two matmuls over the stretch's decode steps (the
weights of every expert that some row chose, ``moe_experts_touched``, read
once, plus the assignments' latents, at the published HBM bandwidth; or
the matmuls' FLOPs over ``moe_held_assignments`` at the bf16 peak,
whichever is larger; ``flops_nemotron_h``) over the device time under
``ds.moe_experts`` and XLA's grouped-matmul kernels in the decode program
over the same stretch. Counts are the program's (the ``ds.decode_step``
spans' stats), so a router that loads the held experts twice as much
needs twice the time."""

from benchmarks import flops
from benchmarks import flops_nemotron_h as count
from benchmarks import moe_trace as mt
from benchmarks import program_trace as pt
from benchmarks.harness import say


def read(run, observed, reduced):
    if reduced is None or run.peaks is None:
        return None
    trace = pt.of_run(run)
    spans = [s for s in pt.decode_spans(trace)
             if "moe_experts_touched" in s.stats]
    decode = pt.programs_under(trace, "ds.decode") if trace else {}
    taken = pt.seconds_by(trace, reduced, lambda op: (
        op.program_id in decode and mt.is_experts(op))).get(True, 0.0)
    if not spans or taken <= 0.0:
        return None
    touched = sum(s.stats["moe_experts_touched"] for s in spans)
    rows = sum(s.stats["moe_held_assignments"] for s in spans)
    least, bound = flops.roofline_seconds(
        count.experts_step_flops(run.config, rows),
        count.experts_step_bytes(run.config, touched, rows), run.peaks)
    say(f"experts roofline at decode: {len(spans)} steps, "
        f"{touched / len(spans):.1f} experts touched and "
        f"{rows / len(spans):.1f} held assignments a step over the expert "
        f"layers: {least:.4f}s needed ({bound}-bound) against {taken:.4f}s "
        f"under {mt.EXPERTS} in the decode program")
    return 100.0 * least / taken
