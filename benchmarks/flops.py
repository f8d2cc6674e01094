"""Operations and bytes that the work REQUIRES, from shapes alone.

The yardstick's arithmetic, kept with the benchmark so that no PR that
claims a gain can move it. Every function counts matrix-multiply FLOPs
(2 per multiply-add) of one forward pass per token; training needs three
times that (forward, and a backward that costs two forwards). Nothing is
counted for recomputation (remat, flash's recomputed scores beyond the one
the algorithm needs), for layer norms, softmax, GELU or the optimizer: a
system that recomputes does more work for the same required operations,
and its utilization reads lower, as it should.

``bench.py::train_flops_per_step`` (superseded) counted attention as
``12*L*h*s`` per token for causal models too, i.e. the full square; here a
causal mask halves it, because half the score matrix is never needed.
"""

import json
import os
from typing import Dict

TRAIN_OVER_FORWARD = 3.0     # forward + backward (2x forward)


def load_peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind`` from ``peaks.json``. A device
    that is not in the table is an error, never a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} has no entry in {path}; known: "
            f"{sorted(table)}. Add its published peaks with their source.")
    return table[device_kind]


def attention_flops_per_token(hidden: int, seq: int, causal: bool) -> float:
    """QK^T and PV for one token of one layer, forward. A query at
    position i needs i+1 keys under a causal mask (mean (seq+1)/2), all
    ``seq`` keys without one; each key costs 2*hidden FLOPs in QK^T and
    the same in PV."""
    keys = (seq + 1) / 2.0 if causal else float(seq)
    return 4.0 * hidden * keys


def block_flops_per_token(hidden: int, intermediate: int, seq: int,
                          causal: bool) -> float:
    """One transformer block, forward, one token: QKV (3 h^2), output
    projection (h^2), the two MLP matmuls (2 h*intermediate), attention."""
    dense = 2.0 * (4.0 * hidden * hidden + 2.0 * hidden * intermediate)
    return dense + attention_flops_per_token(hidden, seq, causal)


def gpt_forward_flops_per_token(*, hidden: int, layers: int, vocab: int,
                                seq: int, intermediate: int = 0) -> float:
    """Causal LM with a tied (or untied) full-vocabulary head on every
    position. The embedding lookup is a gather, not a matmul."""
    intermediate = intermediate or 4 * hidden
    return (layers * block_flops_per_token(hidden, intermediate, seq, True)
            + 2.0 * hidden * vocab)


def bert_forward_flops_per_token(*, hidden: int, layers: int, vocab: int,
                                 seq: int, intermediate: int,
                                 mask_rate: float) -> float:
    """Bidirectional encoder with the MLM head (transform h^2 + decoder
    h*vocab). The loss needs the head only at the masked positions, so the
    head is counted for ``mask_rate`` of the tokens — a system that runs it
    on every position does more than is required."""
    head = 2.0 * hidden * hidden + 2.0 * hidden * vocab
    return (layers * block_flops_per_token(hidden, intermediate, seq, False)
            + mask_rate * head)


def mfu(flops_per_token: float, tokens_per_s_per_chip: float,
        peak_tflops: float) -> float:
    """Model FLOP/s utilization of one chip, as a fraction."""
    return flops_per_token * tokens_per_s_per_chip / (peak_tflops * 1e12)


# ---------------------------------------------------------------------------
# The flash-attention kernels (forward, dq, dkv) of one training micro-step
# ---------------------------------------------------------------------------

def flash_train_flops(*, batch: int, seq: int, hidden: int,
                      causal: bool) -> float:
    """Matmul FLOPs flash attention needs for one layer's forward and
    backward over ``batch`` sequences: forward S=QK^T and O=PV (2
    matmuls), backward one recomputation of S plus dP, dV, dK, dQ (5): 7
    matmuls of 2*seq*keys*head_dim per head. A backward split into a dq
    and a dkv kernel recomputes S and dP twice (9); the extra two are the
    implementation's, not the algorithm's."""
    keys = (seq + 1) / 2.0 if causal else float(seq)
    return 7.0 * 2.0 * batch * seq * keys * hidden


def flash_train_bytes(*, batch: int, seq: int, hidden: int,
                      itemsize: int = 2) -> float:
    """HBM bytes the same work has to move at least: forward reads q, k, v
    and writes o (4 tensors of batch*seq*hidden); backward reads q, k, v,
    o, do and writes dq, dk, dv (8). Row statistics (lse, delta) are
    seq/head_dim times smaller and left out."""
    return 12.0 * batch * seq * hidden * itemsize


def roofline_seconds(flops: float, nbytes: float, peaks: Dict[str, float]):
    """The least time the chip could take and which bound sets it."""
    t_compute = flops / (peaks["bf16_tflops"] * 1e12)
    t_memory = nbytes / (peaks["hbm_gbps"] * 1e9)
    return ((t_compute, "compute") if t_compute >= t_memory
            else (t_memory, "memory"))
