"""Paged decode attention — Pallas TPU kernel, the second instantiation of
the flash-attention family (``flash_attention.py``) aimed at the serving
tier's hot loop: one (or a handful of) query token(s) per sequence
attending over a **block table** into the paged KV pool
(``serving/kv_cache.py``).

Why a kernel: the fallback decode path gathers the whole padded KV window
— ``pool[block_table]`` materializes ``[B, MB·BS, H, D]`` *per decode
step*, in the compute dtype, before a dense masked attention reads it
again. That is HBM traffic proportional to the table width, paid twice
(gather write + attention read), and with int8 pools it also materializes
the dequantized fp copy. Here the K/V blocks stream **directly from the
pool through VMEM** (the block table rides as scalar prefetch so the DMA
engine chases it), online softmax runs in fp32 scratch, and int8 pools
are dequantized **in-kernel** with their per-(token, head) fp32 scales —
the fp copy of the cache is never materialized anywhere.

Grid: ``(batch, heads, table_width)`` with the table dimension innermost
— each ``(b, h)`` pair walks its row of the block table accumulating
running max / normaliser / fp32 accumulator in VMEM scratch (the same
online-softmax recurrence as the flash forward kernel). Inactive table
entries point at the reserved scratch block 0, so a short sequence's walk
re-reads one hot block instead of streaming cold pool memory — HBM
traffic scales with the *sequence*, not the window.

Masking matches ``PagedLayerCache.update`` exactly: key position ``j``
(table-slot order) is visible to query ``i`` iff ``j <= pos + i`` — the
cached past plus the chunk's causal prefix. The multi-query form
(``num_q > 1``) is what speculative decoding's verification step uses to
score ``k+1`` positions in one dispatch.

``interpret=True`` (automatic off-TPU) runs the same kernel through the
Pallas interpreter so the CPU tier-1 parity suite covers the real kernel
arithmetic — the ``tests/unit/test_cuda_forward.py`` strategy, like the
flash kernel.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import LANES, NEG_INF
from deepspeed_tpu.utils.platform import on_tpu

__all__ = ["paged_decode_attention", "paged_decode_ok"]


def paged_decode_ok(head_dim: int, block_size: int) -> bool:
    """Auto-dispatch gate (``attention.py`` style): can the compiled
    kernel tile this cache geometry on the MXU/VPU? Each streamed K/V
    block is a ``[block_size, head_dim]`` tile cut out of the pool's
    ``[BS, H*D]`` rows: the lane dim is the head_dim (128-multiple, so a
    head's columns start on a lane-tile boundary) and the sublane dim
    the block size (8-multiple). Shapes that fail fall back to the
    default decode — and the interpret path used by CPU tier-1
    takes any shape, so parity tests force ``impl="kernel"`` instead of
    relying on this gate. ``tests/test_tpu_lowering.py`` compiles the
    kernel for a v5e at the smallest geometry this gate admits."""
    return head_dim % 128 == 0 and block_size % 8 == 0


def _head_scale_column(scale_ref, hi):
    """This head's ``[BS, 1]`` column of a whole-heads ``[BS, H]`` scale
    block. A masked lane reduction, not a dynamic lane slice: Mosaic
    refuses ``dynamic_slice`` at a traced offset on the lane axis."""
    sc = scale_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    return jnp.sum(jnp.where(lane == hi, sc, 0.0), axis=1, keepdims=True)


def _online_softmax_step(s, v, m_scr, l_scr, acc):
    """One block of the online-softmax recurrence on masked scores ``s``
    [S, BS] and values ``v`` [BS, D], updating the running max /
    normaliser ([S, LANES], lane-broadcast) and the fp32 accumulator
    [S, D] in VMEM scratch. Everything stays 2-D (keepdims) so a
    single-row query (decode, ``S == 1``) tiles like any other."""
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new[:, :1])
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc[...] = acc[...] * alpha[:, :1] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def _decode_kernel(bt_ref, pos_ref, *refs, scale: float, block_size: int,
                   num_q: int, int8: bool):
    if int8:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_scr, l_scr, acc = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc = refs
        ks_ref = vs_ref = None
    bi = pl.program_id(0)
    wi = pl.program_id(2)
    num_w = pl.num_programs(2)

    @pl.when(wi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc[...] = jnp.zeros_like(acc)

    q = q_ref[...].astype(jnp.float32) * scale               # [S, D]
    k = k_ref[...].astype(jnp.float32)                       # [BS, D]
    v = v_ref[...].astype(jnp.float32)
    if int8:
        # In-kernel dequant: the pool's per-(token, head) RTNE scales
        # ride as whole-heads [BS, H] blocks (trailing dim equals the
        # array's — mosaic tiling). Scale traffic stays proportional to
        # the streamed blocks; the fp K/V copy exists only as this VMEM
        # block.
        hi = pl.program_id(1)
        k = k * _head_scale_column(ks_ref, hi)               # [BS, 1]
        v = v * _head_scale_column(vs_ref, hi)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [S, BS]
    # Visibility matches PagedLayerCache.update: key j (table-slot
    # order) visible to query i iff j <= pos + i. Table slots past the
    # written region point at scratch garbage — masked here exactly like
    # the gather path's kpos <= qpos mask.
    kpos = wi * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (num_q, block_size), 1)
    qpos = pos_ref[bi] + jax.lax.broadcasted_iota(
        jnp.int32, (num_q, block_size), 0)
    s = jnp.where(kpos <= qpos, s, NEG_INF)
    _online_softmax_step(s, v, m_scr, l_scr, acc)

    @pl.when(wi == num_w - 1)
    def _finish():
        l_safe = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[...] = (acc[...] / l_safe).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array,
                           k_scale: Optional[jax.Array],
                           v_scale: Optional[jax.Array],
                           block_table: jax.Array, pos: jax.Array, *,
                           block_size: int,
                           softmax_scale: Optional[float] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Attention of ``q`` [B, S, H, D] over the paged pool through each
    row's block table.

    ``k_pool``/``v_pool``: [N, BS, H*D], the pool as it is stored
    (``serving/kv_cache.py``: heads folded into the lane axis; fp, or
    int8 with ``k_scale``/``v_scale`` [N, BS, H] fp32 per-(token, head)
    scales). ``block_table``:
    [B, WB] int32 pool-block ids (the caller may pass a column-sliced
    window — all positions indexed are table-relative). ``pos``: [B]
    int32, the first query's position (queries sit at ``pos..pos+S-1``).
    Returns [B, S, H, D] in ``q.dtype``. The chunk's K/V must already be
    written into the pools (``PagedLayerCache.update_attend`` does both).
    """
    b, s, h, d = q.shape
    wb = block_table.shape[1]
    bs = int(block_size)
    if k_pool.shape[1:] != (bs, h * d):
        raise ValueError(f"pool blocks are {k_pool.shape[1:]}, not the "
                         f"stored form [block_size, heads * head_dim] = "
                         f"{(bs, h * d)}")
    scale = softmax_scale if softmax_scale is not None else 1.0 / (d ** 0.5)
    interpret = not on_tpu() if interpret is None else interpret
    int8 = k_scale is not None

    kernel = functools.partial(_decode_kernel, scale=float(scale),
                               block_size=bs, num_q=s, int8=int8)
    # Heads sit folded in the lane axis ([.., H*D]: the pool is stored
    # so, and q is a small activation to fold): a block is then one
    # head's D columns, (rows, D) on the last two dims, which is what
    # Mosaic tiles. A (.., 1, D) block over [.., H, D] puts 1 on the
    # sublane axis and is refused at lowering.
    q_spec = pl.BlockSpec((None, s, d), lambda bi, hi, wi, bt, p: (bi, 0, hi))
    kv_spec = pl.BlockSpec((None, bs, d),
                           lambda bi, hi, wi, bt, p: (bt[bi, wi], 0, hi))
    in_specs = [q_spec, kv_spec, kv_spec]
    inputs = [q.reshape(b, s, h * d), k_pool, v_pool]
    if int8:
        # Whole-heads (BS, H) scale blocks straight from the pool
        # layout; the kernel picks its head's column. H extra lanes per
        # block is noise next to the [BS, D] K/V stream.
        sc_spec = pl.BlockSpec((None, bs, h),
                               lambda bi, hi, wi, bt, p: (bt[bi, wi], 0, 0))
        in_specs += [sc_spec, sc_spec]
        inputs += [k_scale, v_scale]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,        # block table + positions
            grid=(b, h, wb),              # table walk innermost: scratch
                                          # accumulates per (seq, head)
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((s, LANES), jnp.float32),   # running max
                pltpu.VMEM((s, LANES), jnp.float32),   # normaliser
                pltpu.VMEM((s, d), jnp.float32),       # fp32 accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((b, s, h * d), q.dtype),
        name="paged_attention",
        interpret=interpret,
    )(block_table.astype(jnp.int32), pos.astype(jnp.int32), *inputs)
    return out.reshape(b, s, h, d)
