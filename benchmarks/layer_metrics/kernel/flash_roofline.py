"""The flash-attention kernels' share of their roofline, in percent: the
least time the chip could take for the attention of the traced steps
(``flops.flash_train_flops`` / ``flash_train_bytes``: forward and backward
of every layer of every micro-batch, the larger of operations over peak
FLOP/s and bytes over peak bytes/s) over the time the Mosaic calls took.
The earlier line says which bound sets the least time."""

from benchmarks import flops
from benchmarks.harness import say


def read(run, observed, reduced):
    if reduced is None or run.peaks is None:
        return None
    mosaic_s = reduced.mean(reduced.by_class["mosaic"])
    if mosaic_s <= 0.0:
        return None
    hidden, layers, _ = run.family.hidden_layers_heads(run.config)
    t = run.traffic
    work = dict(batch=t["micro_batch_per_chip"], seq=t["seq_len"],
                hidden=hidden)
    least, bound = flops.roofline_seconds(
        flops.flash_train_flops(causal=run.family.CAUSAL, **work),
        flops.flash_train_bytes(**work), run.peaks)
    calls = t["trace_steps"] * t["gradient_accumulation_steps"] * layers
    say(f"flash roofline: {calls} layer passes x {least * 1e6:.1f} us "
        f"({bound}-bound) against {mosaic_s:.4f}s of Mosaic time")
    return 100.0 * calls * least / mosaic_s
