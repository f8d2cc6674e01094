"""Model FLOP/s utilization, in percent: the matmul operations forward and
backward REQUIRE per token (``flops.py``; causal attention counted as
half, nothing for recomputation) times the tokens per second per chip of
the untraced window, over the chip's published bf16 peak."""

from benchmarks import flops


def read(run, observed, reduced):
    if run.peaks is None:
        return None
    per_token = flops.TRAIN_OVER_FORWARD * run.family.forward_flops_per_token(
        run.config, run.traffic)
    return 100.0 * flops.mfu(per_token, observed["tokens_per_s_per_chip"],
                             run.peaks["bf16_tflops"])
