"""Fused flash attention — Pallas TPU kernel, the framework's answer to the
reference's fused attention CUDA path (``csrc/transformer/softmax_kernels.cu``
+ the strided-batch attention GEMMs in ``ds_transformer_cuda.cpp:147``) with
O(seq) memory instead of materialising the [S, S] score matrix.

Forward: one kernel per (batch·head, q-block): that head's K/V sit whole in
VMEM and are walked in kv-blocks while running max / normaliser / fp32
accumulator are carried (online softmax). Saves the per-row logsumexp for
the backward pass. Under ``causal=True`` the walk covers the causal triangle
only (``causal_walk``).

Backward: custom VJP with ONE kernel per (batch·head, kv-block), named
``flash_bwd``: that head's Q, dO, lse and delta sit whole in VMEM, the
q-blocks are walked, and S, the mask, P, dP and dS = P ⊙ (dP − delta), delta
= rowsum(dO ⊙ O), are built once per visited block for all three gradients
(5 matmuls a block). dK and dV are summed over the walk; dQ over the kv axis
of the grid in a float32 VMEM scratch that leaves once a head.

All matmuls accumulate in fp32 on the MXU (preferred_element_type); block
sizes are 128-aligned for MXU/VPU tiling. ``interpret=True`` runs the same
kernels through the Pallas interpreter for CPU tests (the kernel-parity
strategy of reference tests/unit/test_cuda_forward.py).
"""

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.utils.platform import on_tpu

# Blocks. K and V (backward: Q, dO, lse, delta) stay whole in VMEM per (b, h),
# so a smaller block on the looped side adds loop trips, not DMAs; a smaller
# block on the grid side adds grid steps. VMEM at 512 x 1024 (fp32): q bq·d
# + k/v 2·bk·d + score block bq·bk = 2.7 / 3.4 / 4.7 MB at d = 64 / 128 /
# 256, inside the 16 MB default; `_vmem_params` raises the cap for the long
# backward.
#
# causal=False runs 512 x 1024 over the whole rectangle, as it always has
# (HISTORY, 2026-07-30, not re-measured: 128 x 128 ran at ~1 TFLOP/s, 512 x
# 1024 at ~31, fwd+bwd at seq 4096, d=64). Its forward's lowered text is
# pinned.
#
# causal=True visits only the blocks at or under the diagonal and masks only
# those it crosses (`causal_walk`), in CAUSAL_BLOCK x CAUSAL_BLOCK blocks.
# Measured on a v5e (tools/probe_flash_blocks.py; median device us of one
# call, bf16), at the two training cells' shapes. PR 32, one backward kernel
# (fwd / bwd), beside its parent's two (fwd / dq + dkv):
#
#   block_q x block_k            bh 64, seq 1024, d 64    bh 20, seq 4096, d 256
#   parent, 512 x 512            236 / 251 + 340 = 591    1393 / 1720 + 2534 = 4254
#   512 x 512  (visits 3/4, 36/64)   236 / 424            1392 / 3134
#   1024 x 1024 (1/1, 10/16)         201 / 489            1446 / 3094
#   256 x 512  (6/8, 72/128)         239 / 546            1385 / 3529
#   512 x 256  (6/8, 72/128)         451 / 501            1793 / 3139
#   512 x 1024 (2/2, 20/32)          266 / 546            1420 / 3372
#   256 x 256  (10/16, 136/256)      367 / 566            (not timed)
#
# PR 29 (fwd / dq / dkv), what the causal walk was chosen from:
#
#   before PR 29: 512 x 1024, every block masked and in a
#   loop                             342 / 317 / 444      1526 / 1841 / 2663
#   256 x 256                        367 / 336 / 480      1656 / 1841 / 2794
#   1024 x 1024                      201 / 273 / 374      1446 / 1797 / 2493
#   512 x 512, every run of the walk a loop (the first form tried)
#                                    324 / 268 / 387      1558 / 1785 / 2628
#
# What the tables say: (1) the time does not follow the score elements
# alone. The same block inside a `fori_loop` of one trip (or under a `cond`:
# tried, no better) costs 1.3-1.7 times what it costs written out, and a
# grid step about 1 us: so the one diagonal block of a program is written
# out (`CausalWalk`: a Python 1 for its count), which is worth as much as
# the blocks not visited, and 256-wide blocks lose although they visit
# least. (2) 512 x 512 is the best pair for the forward and the backward
# together at both head sizes, so the rule does not read head_dim; the
# forward alone prefers 1024 x 1024 at seq 1024. (3) One backward kernel
# costs what the dkv kernel cost plus a quarter (424 v 340, 3134 v 2534):
# the dq kernel's whole second pass over S, P and dP (251, 1720) went.
# (4) A length with no 128-multiple divisor from 256 to 512 (640, 896) runs
# as one block (128 x 128 blocks were 2-3 times slower, PR 29).
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
CAUSAL_BLOCK = 512
LANES = 128   # TPU lane width: per-row scalars (lse/delta) are broadcast
              # across the lane dim so their blocks satisfy (8,128) tiling
NEG_INF = -1e30


def fit_block(block: int, seq: int) -> int:
    """Largest 128-multiple <= `block` that divides `seq` (the kernels
    require whole blocks); used by the auto-dispatch gate too — degraded
    blocks lose to XLA (see attention.py crossover notes)."""
    block = min(block, seq)
    if seq % 128 == 0:
        while seq % block:
            block -= 128
    return block


def default_blocks(causal: bool, seq_q: int, seq_k: int,
                   head_dim: int) -> Tuple[int, int]:
    """``(block_q, block_k)`` when the caller names none: a function of
    what the call can see (the header has the measurements behind it).
    ``head_dim`` is seen and not read: 64 and 256 chose the same blocks."""
    if not causal:
        return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K

    def side(seq):
        # a length with no 128-multiple divisor from 256 to 512 (640, 896)
        # runs as one block, as it did: 128-wide blocks are 2-3 times slower
        block = fit_block(CAUSAL_BLOCK, seq)
        return block if block >= 256 else fit_block(DEFAULT_BLOCK_K, seq)

    return side(seq_q), side(seq_k)


def fitted_blocks(causal: bool, seq_q: int, seq_k: int, head_dim: int,
                  block_q: Optional[int] = None,
                  block_k: Optional[int] = None) -> Tuple[int, int]:
    """The blocks a call runs with: the caller's or the default, each fitted
    to its sequence length."""
    auto_q, auto_k = default_blocks(causal, seq_q, seq_k, head_dim)
    return (fit_block(auto_q if block_q is None else block_q, seq_q),
            fit_block(auto_k if block_k is None else block_k, seq_k))


class CausalWalk(NamedTuple):
    """Which score blocks the kernels visit under ``causal=True``.

    ``kv_runs(qi)`` is a q-block's walk over the kv-blocks (forward),
    ``q_runs(ki)`` a kv-block's walk over the q-blocks (backward): each a tuple
    of ``(first, count, masked)`` runs in ascending order, ``qi`` / ``ki`` a
    Python int or the kernel's traced ``program_id``. Where the diagonal
    crosses exactly one block of every block of a side (``block_q ==
    block_k`` on aligned lengths: every default), the masked run's count is
    the Python int 1, and the kernels write that block out with no loop
    round it. ``visited`` / ``crossed`` / ``total`` count the blocks of the
    whole walk (the same from either side)."""
    kv_runs: Callable
    q_runs: Callable
    visited: int
    crossed: int
    total: int


def _blocks_below(n, block: int, num_blocks: int):
    """``floor(n / block)`` clipped to ``[0, num_blocks]``. A negative ``n``
    clips to 0 whichever way the division rounds, so Python's ``//`` and the
    kernel's truncating ``lax.div`` agree."""
    if isinstance(n, int):
        return min(max(n // block, 0), num_blocks)
    return jnp.clip(jax.lax.div(n, block), 0, num_blocks)


def causal_walk(seq_q: int, seq_k: int, block_q: int,
                block_k: int) -> CausalWalk:
    """The causal triangle in blocks. Causality is bottom-right aligned
    (``xla_attention``'s ``tril`` with ``k = seq_k - seq_q``): query row i
    attends keys ``j <= i + offset``. A block with no such pair is not
    visited; one where every pair is such needs no mask (``masked`` False);
    the diagonal crosses the rest."""
    num_q, num_kv = seq_q // block_q, seq_k // block_k
    offset = seq_k - seq_q

    def kv_edges(qi):       # [0, full) wholly under, [full, end) crossed
        # wholly under: the block's last key <= its first row + offset
        full = _blocks_below(qi * block_q + offset + 1, block_k, num_kv)
        # visited: the block's first key <= its last row + offset
        end = _blocks_below((qi + 1) * block_q + offset + block_k - 1,
                            block_k, num_kv)
        return full, end

    def q_edges(ki):        # [start, full) crossed, [full, num_q) wholly under
        start = _blocks_below(ki * block_k - offset, block_q, num_q)
        full = _blocks_below((ki + 1) * block_k - offset + block_q - 2,
                             block_q, num_q)
        return start, full

    kv = [kv_edges(qi) for qi in range(num_q)]
    kv_one = all(end - full == 1 for full, end in kv)
    q_one = all(full - start == 1
                for start, full in map(q_edges, range(num_kv)))

    def kv_runs(qi):
        full, end = kv_edges(qi)
        return (0, full, False), (full, 1 if kv_one else end - full, True)

    def q_runs(ki):
        start, full = q_edges(ki)
        return ((start, 1 if q_one else full - start, True),
                (full, num_q - full, False))

    return CausalWalk(kv_runs, q_runs,
                      visited=sum(end for _, end in kv),
                      crossed=sum(end - full for full, end in kv),
                      total=num_q * num_kv)


def _walk(body, carry, runs):
    """``body(i, carry, masked)`` over each ``(first, count, masked)`` run,
    in order: a loop, or straight-line code where the count is a Python int
    (the header has what a loop round one block costs)."""
    for first, count, masked in runs:
        step = functools.partial(body, masked=masked)
        if isinstance(count, int):
            for i in range(count):
                carry = step(first + i, carry)
        else:
            carry = jax.lax.fori_loop(first, first + count, step, carry)
    return carry


def _needs_coords(causal: bool, masked: bool, dropout_rate: float) -> bool:
    """Whether a block's body builds its elements' coordinates: for the
    causal mask and for the dropout hash. The non-causal walk builds them
    too and, without dropout, reads them nowhere (the compiler drops them):
    its lowered text stays what it was before the causal walk changed."""
    return masked or dropout_rate > 0.0 or not causal


def _block_coords(qi, ki, block_q: int, block_k: int):
    """Absolute (row, column) of every element of score block (qi, ki)."""
    shape = (block_q, block_k)
    return (qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1))


# ---------------------------------------------------------------------------
# Attention dropout — counter-based hash PRNG
# ---------------------------------------------------------------------------
# The reference applies attention dropout inside its fused kernels
# (csrc/transformer/dropout_kernels.cu, ds_transformer_cuda.cpp:168-190).
# Here the keep-mask is a pure function of (seed, batch·head, absolute row,
# absolute col) via a murmur3-style integer hash — vector int ops that run
# identically inside the Mosaic kernel, in the Pallas interpreter, and in
# plain jnp (`dropout_keep_mask` is the oracle the parity tests use). The
# backward kernel regenerates exactly the forward's mask because the hash
# depends only on absolute element coordinates, not the block walk order.

def _hash_u32(x):
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0x27D4EB2F)
    x = x ^ (x >> 16)
    return x


def _dropout_bits(seed, bh, rows, cols):
    """uint32 hash bits for absolute element coordinates. rows/cols are
    int32 arrays broadcastable to the score-block shape."""
    x = (rows.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
         + cols.astype(jnp.uint32) * jnp.uint32(0x7FEB352D))
    x = x ^ (jnp.asarray(seed).astype(jnp.uint32) + jnp.uint32(0x165667B1))
    x = x ^ (jnp.asarray(bh).astype(jnp.uint32) * jnp.uint32(0x58F633B5)
             + jnp.uint32(1))
    return _hash_u32(x)


def dropout_keep_mask(seed, bh, rows, cols, rate: float):
    """Boolean keep-mask for attention dropout — the single source of truth
    shared by the kernels and the jnp oracle (tests/test_flash_attention).
    seed: int32 scalar; bh: batch·head index; rows/cols: absolute score
    coordinates (broadcastable int32 arrays)."""
    bits = _dropout_bits(seed, bh, rows, cols)
    # top 24 bits vs an integer threshold — Mosaic has no uint32->float
    # cast, and the int32 compare is cheaper anyway (>>8 keeps it positive).
    thresh = int(float(rate) * (1 << 24))
    return (bits >> 8).astype(jnp.int32) >= thresh


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(seed_ref, *refs, causal: bool, scale: float, block_k: int,
                seq_q: int, seq_k: int, has_mask: bool, dropout_rate: float):
    if has_mask:
        q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        mask_ref = None
    bh_idx = pl.program_id(0)
    qi = pl.program_id(1)
    block_q = q_ref.shape[1]
    d = q_ref.shape[2]
    q = q_ref[0].astype(jnp.float32) * scale          # [bq, d]

    def body(ki, carry, masked):
        m_prev, l_prev, acc = carry
        k = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bq, bk]
        if _needs_coords(causal, masked, dropout_rate):
            q_idx, k_idx = _block_coords(qi, ki, block_q, block_k)
        if masked:
            s = jnp.where(q_idx + (seq_k - seq_q) >= k_idx, s, NEG_INF)
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        if mask_ref is not None:
            # Key-padding mask (float 0/1, [1, bk]): multiplying p keeps the
            # masked keys out of BOTH the normaliser and the accumulator —
            # exact, and robust even for fully-masked rows (p -> 0, l -> 0).
            km = mask_ref[0, :, pl.ds(ki * block_k, block_k)]
            p = p * km
        alpha = jnp.exp(m_prev - m_new)
        # Dropout applies to the accumulated probabilities only — the
        # normaliser keeps the full softmax mass, matching post-softmax
        # dropout semantics (reference dropout_kernels.cu applies it to the
        # normalised probs; here l normalises first, then D p v sums).
        if dropout_rate > 0.0:
            keep = dropout_keep_mask(seed_ref[0], bh_idx, q_idx, k_idx,
                                     dropout_rate)
            p_acc = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
        else:
            p_acc = p
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jnp.dot(
            p_acc, v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    init = (jnp.full((block_q,), NEG_INF, jnp.float32),
            jnp.zeros((block_q,), jnp.float32),
            jnp.zeros((block_q, d), jnp.float32))
    if causal:
        m, l, acc = _walk(body, init, causal_walk(
            seq_q, seq_k, block_q, block_k).kv_runs(qi))
    else:
        m, l, acc = jax.lax.fori_loop(
            0, seq_k // block_k, functools.partial(body, masked=False), init)
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse = m + jnp.log(l_safe)
    lse_ref[0] = jnp.broadcast_to(lse[:, None], (block_q, LANES))


def _flash_forward(q, k, v, kv_mask, causal, scale, block_q, block_k,
                   interpret, nheads=1, dropout_rate=0.0, seed=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    has_mask = kv_mask is not None
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                               block_k=block_k, seq_q=sq, seq_k=sk,
                               has_mask=has_mask, dropout_rate=dropout_rate)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, s: (b, i, 0)),
        pl.BlockSpec((1, sk, d), lambda b, i, s: (b, 0, 0)),
        pl.BlockSpec((1, sk, d), lambda b, i, s: (b, 0, 0)),
    ]
    inputs = [q, k, v]
    if has_mask:
        # Mask rides as [B, 1, Sk] so the (1, 1, Sk) block's trailing dims
        # equal the array's (TPU mosaic tiling constraint for sub-8 rows).
        in_specs.append(
            pl.BlockSpec((1, 1, sk), lambda b, i, s: (b // nheads, 0, 0)))
        inputs.append(kv_mask)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,     # dropout seed rides in SMEM
            grid=(bh, sq // block_q),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, s: (b, i, 0)),
                pl.BlockSpec((1, block_q, LANES), lambda b, i, s: (b, i, 0)),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, LANES), jnp.float32),
        ],
        name="flash_fwd",
        interpret=interpret,
        compiler_params=_vmem_params(
            (2 * sk * d + 2 * block_q * d) * q.dtype.itemsize
            + block_q * LANES * 4 + (4 * sk if has_mask else 0)),
    )(seed, *inputs)
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernel
# ---------------------------------------------------------------------------

def _bwd_kernel(seed_ref, *refs, causal: bool, scale: float, block_q: int,
                seq_q: int, seq_k: int, has_mask: bool, dropout_rate: float):
    """dQ, dK and dV of one (batch·head, kv-block) program: S, the mask, P, dP
    and dS are built once per visited block. dK and dV are summed over the
    q-blocks in registers; dQ over the kv-blocks (the grid's second axis, in
    ascending order) in the float32 scratch ``dq_acc``, which leaves for the
    head's dQ block at its last kv-block."""
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         dq_ref, dk_ref, dv_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dq_acc) = refs
        mask_ref = None
    bh_idx = pl.program_id(0)
    ki = pl.program_id(1)
    block_k = k_ref.shape[1]
    d = k_ref.shape[2]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)

    @pl.when(ki == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def body(qi, carry, masked):
        dk, dv = carry
        rows = pl.ds(qi * block_q, block_q)
        q = q_ref[0, rows, :].astype(jnp.float32) * scale
        do = do_ref[0, rows, :].astype(jnp.float32)
        lse = lse_ref[0, rows, 0]
        delta = delta_ref[0, rows, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if _needs_coords(causal, masked, dropout_rate):
            q_idx, k_idx = _block_coords(qi, ki, block_q, block_k)
        if masked:
            s = jnp.where(q_idx + (seq_k - seq_q) >= k_idx, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                       # [bq, bk]
        if mask_ref is not None:
            p = p * mask_ref[0]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        # With dropout D: o = Σ D p̂ v / l, and Σ_j p̂_j D_j dp_j = do·o =
        # delta still holds, so ds = p (D∘dp − delta) — regenerate the
        # forward's exact keep-mask from the hash.
        if dropout_rate > 0.0:
            keep = dropout_keep_mask(seed_ref[0], bh_idx, q_idx, k_idx,
                                     dropout_rate)
            inv = 1.0 / (1.0 - dropout_rate)
            p_acc = jnp.where(keep, p * inv, 0.0)   # dropped probs for dv
            dp = jnp.where(keep, dp * inv, 0.0)
        else:
            p_acc = p
        dv = dv + jax.lax.dot_general(p_acc, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dq_acc[rows, :] += jnp.dot(ds, k, preferred_element_type=jnp.float32)
        return dk, dv

    init = (jnp.zeros((block_k, d), jnp.float32),
            jnp.zeros((block_k, d), jnp.float32))
    if causal:
        dk, dv = _walk(body, init, causal_walk(
            seq_q, seq_k, block_q, block_k).q_runs(ki))
    else:
        dk, dv = jax.lax.fori_loop(
            0, seq_q // block_q, functools.partial(body, masked=False), init)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _vmem_params(est_bytes: int, dimension_semantics=None):
    """Raise Mosaic's scoped-VMEM cap (default 16 MiB) when a kernel
    instance's double-buffered working set won't fit — the long-sequence
    backward keeps whole-sequence q/do/lse/delta/dq refs per instance, which
    at seq 4096 overflows the default (v5e has 128 MiB VMEM).
    ``est_bytes`` is the single-buffered per-instance sum; ×4 + 16 MiB
    covers double buffering plus the compiler's own stack slack (measured:
    Mosaic asked for ~2% above a bare ×4 at seq 16384)."""
    limit = None
    if est_bytes * 4 > 16 * 2**20:
        limit = int(min(100 * 2**20, est_bytes * 4 + 16 * 2**20))
    if limit is None and dimension_semantics is None:
        return None
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=limit)


def _flash_backward(res, g, causal, scale, block_q, block_k, interpret,
                    nheads=1, dropout_rate=0.0):
    q, k, v, kv_mask, out, lse, seed = res
    bh, sq, d = q.shape
    sk = k.shape[1]
    do = g
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (LANES,))
    has_mask = kv_mask is not None

    def whole(width):       # the head's rows whole: fetched once a head
        return pl.BlockSpec((1, sq, width), lambda b, i, s: (b, 0, 0))

    kv_block = pl.BlockSpec((1, block_k, d), lambda b, i, s: (b, i, 0))
    in_specs = [whole(d), kv_block, kv_block, whole(d), whole(LANES),
                whole(LANES)]
    inputs = [q, k, v, do, lse, delta]
    if has_mask:
        in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b, i, s: (b // nheads, 0, i)))
        inputs.append(kv_mask)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, causal=causal, scale=scale,
                          block_q=block_q, seq_q=sq, seq_k=sk,
                          has_mask=has_mask, dropout_rate=dropout_rate),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, sk // block_k),
            in_specs=in_specs,
            # dQ's block does not follow the kv axis: it leaves VMEM once a head
            out_specs=[whole(d), kv_block, kv_block],
            scratch_shapes=[pltpu.VMEM((sq, d), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        name="flash_bwd",
        interpret=interpret,
        compiler_params=_vmem_params(
            (3 * sq * d + 4 * block_k * d) * q.dtype.itemsize
            + 2 * sq * LANES * 4 + sq * d * 4,
            dimension_semantics=("parallel", "arbitrary")),
    )(seed, *inputs)


# ---------------------------------------------------------------------------
# Public entry — [B, S, H, D] layout, custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_bhsd(q, k, v, seed, causal, scale, block_q, block_k, interpret,
                dropout_rate):
    out, _ = _flash_forward(q, k, v, None, causal, scale, block_q, block_k,
                            interpret, dropout_rate=dropout_rate, seed=seed)
    return out


def _flash_fwd_rule(q, k, v, seed, causal, scale, block_q, block_k,
                    interpret, dropout_rate):
    out, lse = _flash_forward(q, k, v, None, causal, scale, block_q, block_k,
                              interpret, dropout_rate=dropout_rate, seed=seed)
    return out, (q, k, v, None, out, lse, seed)


def _flash_bwd_rule(causal, scale, block_q, block_k, interpret, dropout_rate,
                    res, g):
    dq, dk, dv = _flash_backward(res, g, causal, scale, block_q, block_k,
                                 interpret, dropout_rate=dropout_rate)
    import numpy as _np
    return dq, dk, dv, _np.zeros(res[6].shape, jax.dtypes.float0)


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_bhsd_masked(q, k, v, kv_mask, seed, causal, scale, block_q,
                       block_k, interpret, nheads, dropout_rate):
    out, _ = _flash_forward(q, k, v, kv_mask, causal, scale, block_q,
                            block_k, interpret, nheads,
                            dropout_rate=dropout_rate, seed=seed)
    return out


def _flash_fwd_rule_masked(q, k, v, kv_mask, seed, causal, scale, block_q,
                           block_k, interpret, nheads, dropout_rate):
    out, lse = _flash_forward(q, k, v, kv_mask, causal, scale, block_q,
                              block_k, interpret, nheads,
                              dropout_rate=dropout_rate, seed=seed)
    return out, (q, k, v, kv_mask, out, lse, seed)


def _flash_bwd_rule_masked(causal, scale, block_q, block_k, interpret, nheads,
                           dropout_rate, res, g):
    dq, dk, dv = _flash_backward(res, g, causal, scale, block_q, block_k,
                                 interpret, nheads,
                                 dropout_rate=dropout_rate)
    import numpy as _np
    return (dq, dk, dv, jnp.zeros_like(res[3]),
            _np.zeros(res[6].shape, jax.dtypes.float0))


_flash_bhsd_masked.defvjp(_flash_fwd_rule_masked, _flash_bwd_rule_masked)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False,
                    kv_mask: Optional[jax.Array] = None,
                    softmax_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    dropout_rate: float = 0.0,
                    dropout_rng: Optional[jax.Array] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Flash attention over [batch, seq, heads, head_dim] tensors.

    ``kv_mask``: optional key-padding mask [batch, seq_k], 1/True = attend —
    the fused-kernel answer to the reference's attention-mask input
    (csrc/transformer/softmax_kernels.cu applies it inside attn_softmax).

    ``dropout_rate`` + ``dropout_rng``: in-kernel attention dropout
    (reference dropout_kernels.cu): the keep-mask is regenerated in the
    backward kernel from a counter-based hash (see ``dropout_keep_mask``),
    so no [S, S] mask is ever materialized.

    ``block_q`` / ``block_k``: ``None`` takes ``default_blocks`` for this
    call's ``causal``, lengths and head size; either way the block is fitted
    to its length (``fit_block``).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]

    block_q, block_k = fitted_blocks(causal, sq, sk, d, block_q, block_k)
    if sq % block_q or sk % block_k:
        raise ValueError(f"seq lengths ({sq},{sk}) must divide blocks "
                         f"({block_q},{block_k})")
    scale = softmax_scale if softmax_scale is not None else 1.0 / (d ** 0.5)
    interpret = not on_tpu() if interpret is None else interpret
    dropout_rate = float(dropout_rate)
    if dropout_rate > 0.0:
        if dropout_rng is None:
            raise ValueError("dropout_rate > 0 requires dropout_rng")
        kd = jax.random.key_data(dropout_rng).astype(jnp.uint32).reshape(-1)
        seed = (kd[0] ^ (kd[-1] << 1)).astype(jnp.int32)[None]
    else:
        seed = jnp.zeros((1,), jnp.int32)
    # [B,S,H,D] -> [B*H, S, D]
    def to_bhsd(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    if kv_mask is not None:
        if kv_mask.shape != (b, sk):
            raise ValueError(f"kv_mask shape {kv_mask.shape} != {(b, sk)}")
        out = _flash_bhsd_masked(
            to_bhsd(q), to_bhsd(k), to_bhsd(v),
            kv_mask.astype(jnp.float32)[:, None, :], seed,
            causal, scale, block_q, block_k, interpret, h, dropout_rate)
    else:
        out = _flash_bhsd(to_bhsd(q), to_bhsd(k), to_bhsd(v), seed,
                          causal, scale, block_q, block_k, interpret,
                          dropout_rate)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
