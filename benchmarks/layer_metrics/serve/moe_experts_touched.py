"""Of the held experts, the share that at least one row of a decode step
chose, in percent: ``moe_experts_touched`` of the stretch's
``ds.decode_step`` spans (summed over the expert layers by the program)
over held experts x expert layers, mean over the steps. What the grouped
matmul has to read follows it."""

from benchmarks import flops_nemotron_h as count
from benchmarks import program_trace as pt


def read(run, observed, reduced):
    spans = [s for s in pt.decode_spans(pt.of_run(run))
             if "moe_experts_touched" in s.stats]
    if not spans:
        return None
    held = run.config["n_routed_experts"] * count.kinds(run.config)["E"]
    return 100.0 * sum(s.stats["moe_experts_touched"]
                       for s in spans) / len(spans) / held
