"""The fullest held expert's rows over the mean held expert's, as the
program counted them (``held_rows_max`` and ``held_rows_mean`` of the
``ds.step_counters`` spans in the traced window: each the mean over the
expert layers and micro-batches of a step). 1 is a router that loads the
held experts evenly; the grouped matmul's time follows the total, a
deployment's slowest chip the fullest."""

from benchmarks import moe_trace as mt
from benchmarks import program_trace as pt
from benchmarks.harness import say


def read(run, observed, reduced):
    trace = pt.of_run(run)
    fullest = mt.mean_counter(trace, "moe_held_rows_max")
    mean = mt.mean_counter(trace, "moe_held_rows_mean")
    if not fullest or not mean:
        return None
    say("step counters in the traced window: " + "; ".join(
        ", ".join(f"{k} {v:.4g}" if isinstance(v, float) else f"{k} {v}"
                  for k, v in sorted(c.items()))
        for c in mt.step_counters(trace)))
    return fullest / mean
