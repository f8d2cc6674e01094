"""Ragged chunked-prefill attention — Pallas TPU kernel, kernel tier
round 2 for the serving hot loop (Sarathi-style chunked prefill,
arXiv 2308.16369).

The bucketed serving path compiles one prefill program per bucket (plus
tail variants) beside its decode program — a family of programs whose cold compiles land inside TTFT under bursty traffic. This
kernel collapses all of it into ONE program per engine step: the batch is
a flat **ragged token batch** ``[T]`` mixing decode tokens (one per
running sequence) with prefill *chunks* of admitted prompts, bounded by a
per-step token budget. Each token carries its own position and its own
row of the block table, so segments of any length coexist in one launch
and the program never retraces as traffic shifts (one compile ever —
recompile-detector-proven in tests).

The attention itself is the ``paged_attention.py`` kernel with one query
row: a ragged token is a one-token "sequence" with its own block-table
row and position, so the grid is ``(tokens, heads, table_width)`` with
the table walk innermost — each ``(t, h)`` pair streams its sequence's
pool blocks through VMEM under the same online-softmax recurrence. The
per-token table (the sequence's block-table row, gathered host-side by
slot) and positions ride as scalar prefetch so the DMA engine chases the
block ids. One kernel body serves decode, speculative verify and the
ragged mixed step, so there is one thing to keep lowering on the chip.

Masking is per ragged segment: key position ``j`` is visible to token
``t`` iff ``j <= pos[t]`` — within a prefill chunk every token sees the
prompt prefix up to itself (causal), decode tokens see their whole
written past, and cross-sequence isolation is by construction (a token's
walk only ever touches its own sequence's blocks). Int8 pools dequantize
in-kernel with the PR 15 whole-heads ``[BS, H]`` scale-block layout.

``interpret=True`` (automatic off-TPU) runs the same kernel through the
Pallas interpreter so CPU tier-1 parity tests cover the real kernel
arithmetic. The compiled kernel's geometry gate is the kernel's own:
``paged_attention.paged_decode_ok``.
"""

from typing import Optional

import jax

from deepspeed_tpu.ops.transformer.paged_attention import \
    paged_decode_attention

__all__ = ["chunked_prefill_attention"]


def chunked_prefill_attention(q: jax.Array, k_pool: jax.Array,
                              v_pool: jax.Array,
                              k_scale: Optional[jax.Array],
                              v_scale: Optional[jax.Array],
                              table: jax.Array, pos: jax.Array, *,
                              block_size: int,
                              softmax_scale: Optional[float] = None,
                              interpret: Optional[bool] = None) -> jax.Array:
    """Attention of a ragged token batch ``q`` [T, H, D] over the paged
    pool through **per-token** block tables.

    ``k_pool``/``v_pool``: [N, BS, H*D], the pool as it is stored (fp, or
    int8 with ``k_scale``/``v_scale`` [N, BS, H] fp32 per-(token, head)
    scales). ``table``:
    [T, WB] int32 — row ``t`` is the block-table row of the sequence that
    token ``t`` belongs to (the caller gathers ``block_table[slots]``;
    pad tokens carry an all-scratch row). ``pos``: [T] int32 — token
    ``t``'s own cache position; it attends to key positions ``<= pos[t]``.
    Returns [T, H, D] in ``q.dtype``. The batch's K/V must already be
    written into the pools (``ChunkedLayerCache.update_attend`` does
    both).
    """
    # Token t == a paged "sequence" with one query at position pos[t]:
    # the decode kernel's visibility rule j <= pos + i at i = 0.
    out = paged_decode_attention(q[:, None], k_pool, v_pool, k_scale,
                                 v_scale, table, pos, block_size=block_size,
                                 softmax_scale=softmax_scale,
                                 interpret=interpret)
    return out[:, 0]
