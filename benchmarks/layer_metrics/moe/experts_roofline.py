"""The held experts' share of their roofline, in percent: the least time
the chip could take for the gate, up and down matmuls of the held
assignments, forward and backward (``flops_glm4_moe_lite``: operations
over peak FLOP/s against bytes over peak bytes/s, the held experts'
weights moved three times a layer pass plus the rows), over the device
time of the expert computation: the ops under ``ds.moe_experts`` and
XLA's grouped-matmul kernels (``moe_trace.py``). The same work whatever
implements it.

The assignments are COUNTED by the program, not expected
(``held_assignments_per_token`` of a ``ds.step_counters`` span: the mean
over the expert layers and micro-batches of the step it speaks of): a
router that sends the held experts twice their share does twice the work.
Counts and device time are of the SAME optimizer steps: those that ran
inside the window and whose counters reached it too, two steps later
(``moe_trace.counted_steps``), so the cell traces four steps and two of
them are read. A window that holds no such step gives nothing to read."""

from benchmarks import flops
from benchmarks import flops_glm4_moe_lite as count
from benchmarks import moe_trace as mt
from benchmarks import program_trace as pt
from benchmarks.harness import say


def read(run, observed, reduced):
    if reduced is None or run.peaks is None:
        return None
    trace = pt.of_run(run)
    steps = [(counted, stretches)
             for counted, stretches in mt.counted_steps(run, trace, reduced)
             if "moe_held_assignments_per_token" in counted]
    taken = sum(mt.seconds_in(trace, stretches, mt.is_experts)
                for _, stretches in steps)
    if taken <= 0.0:
        return None
    t, c = run.traffic, run.config
    passes = (len(steps) * t["gradient_accumulation_steps"]
              * run.family.expert_layers(c))
    per_token = sum(counted["moe_held_assignments_per_token"]
                    for counted, _ in steps) / len(steps)
    rows = per_token * t["micro_batch_per_chip"] * t["seq_len"] * passes
    shape = dict(hidden=c["hidden_size"],
                 intermediate=c["moe_intermediate_size"])
    least, bound = flops.roofline_seconds(
        count.experts_train_flops(rows=rows, **shape),
        count.experts_train_bytes(rows=rows, held=c["n_routed_experts"],
                                  passes=passes, **shape), run.peaks)
    say(f"experts roofline: steps "
        f"{[counted['of_step'] for counted, _ in steps]}: {rows:.0f} held "
        f"assignments "
        f"({per_token:.4f} a token) over {passes} layer passes need "
        f"{least:.4f}s ({bound}-bound) against {taken:.4f}s under "
        f"{mt.EXPERTS}")
    return 100.0 * least / taken
