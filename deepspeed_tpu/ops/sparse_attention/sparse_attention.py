"""Block-sparse attention executors.

The reference executes block-sparse attention with Triton SDD/DSD/DDS
matmuls + block softmax (``ops/sparse_attention/matmul.py``,
``softmax.py``); here the same layouts run through:

- ``impl="xla"`` — dense attention under the layout-expanded mask (the
  numerics oracle, and perfectly fine for modest sequence lengths);
- ``impl="pallas"`` — a flash-style Pallas kernel that, per (head,
  q-block), loops ONLY over that row's active kv-blocks. The active-index
  list is precomputed on the host from the (static) layout, so compute and
  HBM traffic scale with layout density — the O(s·√s) long-sequence story
  of the reference (docs/index.md:142), TPU-style. Training goes through a
  custom VJP whose dq / dk+dv kernels walk the layout (and its transpose)
  exactly like the reference's Triton SDD/DSD/DDS backward modes
  (matmul.py:749, trsrc/softmax_bwd.tr) — peak memory stays density-
  scaled in backward too.

Layouts come from ``sparsity_config.py`` as [H, B, B] int32.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import _vmem_params
from deepspeed_tpu.utils.platform import on_tpu

NEG_INF = -1e30


def layout_to_dense_mask(layout: np.ndarray, block: int) -> np.ndarray:
    """[H, B, B] block layout -> [H, S, S] bool element mask."""
    return np.kron(np.asarray(layout), np.ones((block, block))).astype(bool)


def layout_kv_indices(layout: np.ndarray):
    """Per (head, q-block) active kv-block ids, padded with -1:
    -> int32 [H, B, max_active]."""
    layout = np.asarray(layout)
    h, b, _ = layout.shape
    max_active = int(layout.sum(-1).max())
    idx = np.full((h, b, max_active), -1, np.int32)
    for hi in range(h):
        for qi in range(b):
            cols = np.nonzero(layout[hi, qi])[0]
            idx[hi, qi, :len(cols)] = cols
    return idx, max_active


def layout_q_indices(layout: np.ndarray):
    """Transpose layout: per (head, kv-block) active Q-block ids, padded
    with -1 — the dk/dv backward iteration order (the reference runs its
    Triton matmuls with a transposed layout for the same purpose,
    ops/sparse_attention/matmul.py:749 ``mode`` dsd/dds)."""
    layout = np.asarray(layout)
    return layout_kv_indices(layout.transpose(0, 2, 1))


def _xla_sparse(q, k, v, layout, block, causal, scale, key_mask=None):
    mask = jnp.asarray(layout_to_dense_mask(layout, block))   # [H, S, S]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask[None], logits, NEG_INF)
    if key_mask is not None:
        # [B, S] key-padding mask (the reference's key_padding_mask,
        # sparse_self_attention.py:58) — masked keys drop out of every row.
        logits = jnp.where(key_mask[:, None, None, :].astype(jnp.bool_),
                           logits, NEG_INF)
    if causal:
        s = q.shape[1]
        cm = jnp.tril(jnp.ones((s, s), jnp.bool_))
        logits = jnp.where(cm[None, None], logits, NEG_INF)
    # guard fully-masked rows (no allowed keys) against NaN
    rowmax = jnp.max(logits, axis=-1, keepdims=True)
    probs = jnp.where(rowmax > NEG_INF / 2,
                      jax.nn.softmax(logits, axis=-1), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v)


LANES = 128  # per-row lse/delta broadcast across lanes for (8,128) tiling


def _sparse_kernel(kv_idx_ref, cnt_ref, *refs, causal: bool, scale: float,
                   block: int, num_heads: int, has_mask: bool):
    """grid: (B*H, q_blocks). Refs: q [1, block, D]; k/v [1, S, D];
    optional key-padding mask [1, 1, S] (1 = keep, reference
    sparse_self_attention.py:58 key_padding_mask); kv_idx [H, qb, max]
    + per-row counts [H, qb] in SMEM (scalar-prefetched — SMEM supports
    the arbitrary dynamic indexing a layout lookup needs). The loop runs
    this ROW's actual active count (dynamic trip count), not the global
    max — rows touched by a few global columns don't pay for the densest
    row. Saves per-row logsumexp for the backward recomputation."""
    if has_mask:
        q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        mask_ref = None
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    h = jax.lax.rem(bh, num_heads)
    d = q_ref.shape[2]
    q = q_ref[0].astype(jnp.float32) * scale

    def body(j, carry):
        m_prev, l_prev, acc = carry
        ki = kv_idx_ref[h, qi, j]
        kblk = k_ref[0, pl.ds(ki * block, block), :].astype(jnp.float32)
        vblk = v_ref[0, pl.ds(ki * block, block), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if has_mask:
            mblk = mask_ref[0, 0, pl.ds(ki * block, block)]
            s = jnp.where(mblk[None, :] > 0, s, NEG_INF)
        if causal:
            q_pos = qi * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 0)
            k_pos = ki * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        # rows that have seen nothing yet keep NEG_INF; exp underflows to 0
        p = jnp.exp(s - jnp.maximum(m_new, NEG_INF / 2)[:, None])
        alpha = jnp.exp(m_prev - jnp.maximum(m_new, NEG_INF / 2))
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jnp.dot(
            p, vblk, preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    init = (jnp.full((block,), NEG_INF, jnp.float32),
            jnp.zeros((block,), jnp.float32),
            jnp.zeros((block, d), jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, cnt_ref[h, qi], body, init)
    out = jnp.where((l > 0)[:, None], acc / jnp.maximum(l, 1e-30)[:, None], 0.0)
    o_ref[0] = out.astype(o_ref.dtype)
    # Fully-masked rows keep lse ~ NEG_INF; the backward guards on it.
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)
    lse_ref[0] = jnp.broadcast_to(lse[:, None], (block, LANES))


def _sparse_bwd_dq_kernel(kv_idx_ref, cnt_ref, *refs, causal: bool,
                          scale: float, block: int, num_heads: int,
                          has_mask: bool):
    """dq over (B*H, q_blocks): loop this row's active kv-blocks, recompute
    p from the saved lse, ds = p (dp - delta), dq += ds @ k. Mirrors the
    flash _bwd_dq_kernel but walks the layout's active list."""
    if has_mask:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, dq_ref \
            = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref = refs
        mask_ref = None
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    h = jax.lax.rem(bh, num_heads)
    d = q_ref.shape[2]
    q = q_ref[0].astype(jnp.float32) * scale
    do = do_ref[0].astype(jnp.float32)
    lse = jnp.maximum(lse_ref[0, :, 0], NEG_INF / 2)   # guard empty rows
    delta = delta_ref[0, :, 0]

    def body(j, dq):
        ki = kv_idx_ref[h, qi, j]
        kblk = k_ref[0, pl.ds(ki * block, block), :].astype(jnp.float32)
        vblk = v_ref[0, pl.ds(ki * block, block), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if has_mask:
            mblk = mask_ref[0, 0, pl.ds(ki * block, block)]
            s = jnp.where(mblk[None, :] > 0, s, NEG_INF)
        if causal:
            q_pos = qi * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 0)
            k_pos = ki * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, vblk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        return dq + jnp.dot(ds, kblk, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, cnt_ref[h, qi], body,
                           jnp.zeros((block, d), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _sparse_bwd_dkv_kernel(q_idx_ref, cnt_ref, *refs, causal: bool,
                           scale: float, block: int, num_heads: int,
                           has_mask: bool):
    """dk/dv over (B*H, kv_blocks): loop this column's active q-blocks via
    the TRANSPOSE layout (layout_q_indices); dv += pᵀ @ dO,
    dk += dsᵀ @ q. The dynamic per-COLUMN trip count matters most here:
    global columns are touched by every q-block while window columns see
    ~3 — padding every column to the densest one made the backward
    effectively dense (measured 2x dense flash at seq 16k before)."""
    if has_mask:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, \
            dk_ref, dv_ref = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref \
            = refs
        mask_ref = None
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    h = jax.lax.rem(bh, num_heads)
    d = k_ref.shape[2]
    kblk = k_ref[0].astype(jnp.float32)
    vblk = v_ref[0].astype(jnp.float32)
    kmask = mask_ref[0, 0] if has_mask else None   # [block], this kv block

    def body(j, carry):
        dk, dv = carry
        qi = q_idx_ref[h, ki, j]
        q = q_ref[0, pl.ds(qi * block, block), :].astype(
            jnp.float32) * scale
        do = do_ref[0, pl.ds(qi * block, block), :].astype(jnp.float32)
        lse = jnp.maximum(lse_ref[0, pl.ds(qi * block, block), 0],
                          NEG_INF / 2)
        delta = delta_ref[0, pl.ds(qi * block, block), 0]
        s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if has_mask:
            s = jnp.where(kmask[None, :] > 0, s, NEG_INF)
        if causal:
            q_pos = qi * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 0)
            k_pos = ki * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                        # [bq, bk]
        dv = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, vblk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        0, cnt_ref[h, ki], body,
        (jnp.zeros((block, d), jnp.float32),
         jnp.zeros((block, d), jnp.float32)))
    # q rides pre-scaled into ds, so dk = dsᵀ @ (q·scale) already carries
    # the softmax scale — no extra factor (unlike dq, whose ds @ k product
    # has no scale in it).
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _sparse_forward(qf, kf, vf, kv_mask, kv_idx, kv_cnt, block, causal,
                    scale, num_heads, interpret):
    bh, s, d = qf.shape
    qb = s // block
    has_mask = kv_mask is not None
    esz = qf.dtype.itemsize
    kernel = functools.partial(_sparse_kernel, causal=causal, scale=scale,
                               block=block, num_heads=num_heads,
                               has_mask=has_mask)
    in_specs = [
        pl.BlockSpec((1, block, d), lambda b, i, idx, cnt: (b, i, 0)),
        pl.BlockSpec((1, s, d), lambda b, i, idx, cnt: (b, 0, 0)),
        pl.BlockSpec((1, s, d), lambda b, i, idx, cnt: (b, 0, 0)),
    ]
    inputs = [qf, kf, vf]
    if has_mask:
        in_specs.append(pl.BlockSpec(
            (1, 1, s), lambda b, i, idx, cnt: (b // num_heads, 0, 0)))
        inputs.append(kv_mask)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,       # kv_idx + per-row counts ride in SMEM
        grid=(bh, qb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block, d), lambda b, i, idx, cnt: (b, i, 0)),
            pl.BlockSpec((1, block, LANES),
                         lambda b, i, idx, cnt: (b, i, 0)),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), qf.dtype),
            jax.ShapeDtypeStruct((bh, s, LANES), jnp.float32),
        ],
        name="sparse_attn_fwd",
        interpret=interpret,
        compiler_params=_vmem_params(
            2 * s * d * esz + 2 * block * d * esz + block * LANES * 4
            + (4 * s if has_mask else 0)),
    )(kv_idx, kv_cnt, *inputs)
    return out, lse


def _sparse_backward(qf, kf, vf, kv_mask, do, out, lse, kv_idx, kv_cnt,
                     q_idx, q_cnt, block, causal, scale, num_heads,
                     interpret):
    bh, s, d = qf.shape
    qb = s // block
    has_mask = kv_mask is not None
    esz = qf.dtype.itemsize
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (LANES,))

    dq_specs = [
        pl.BlockSpec((1, block, d), lambda b, i, idx, cnt: (b, i, 0)),
        pl.BlockSpec((1, s, d), lambda b, i, idx, cnt: (b, 0, 0)),
        pl.BlockSpec((1, s, d), lambda b, i, idx, cnt: (b, 0, 0)),
        pl.BlockSpec((1, block, d), lambda b, i, idx, cnt: (b, i, 0)),
        pl.BlockSpec((1, block, LANES), lambda b, i, idx, cnt: (b, i, 0)),
        pl.BlockSpec((1, block, LANES), lambda b, i, idx, cnt: (b, i, 0)),
    ]
    dq_inputs = [qf, kf, vf, do, lse, delta]
    if has_mask:
        dq_specs.append(pl.BlockSpec(
            (1, 1, s), lambda b, i, idx, cnt: (b // num_heads, 0, 0)))
        dq_inputs.append(kv_mask)
    dq = pl.pallas_call(
        functools.partial(_sparse_bwd_dq_kernel, causal=causal, scale=scale,
                          block=block, num_heads=num_heads,
                          has_mask=has_mask),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, qb),
            in_specs=dq_specs,
            out_specs=pl.BlockSpec((1, block, d),
                                   lambda b, i, idx, cnt: (b, i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), qf.dtype),
        name="sparse_attn_bwd_dq",
        interpret=interpret,
        compiler_params=_vmem_params(
            2 * s * d * esz + 4 * block * d * esz + 2 * block * LANES * 4
            + (4 * s if has_mask else 0)),
    )(kv_idx, kv_cnt, *dq_inputs)

    dkv_specs = [
        pl.BlockSpec((1, s, d), lambda b, i, idx, cnt: (b, 0, 0)),
        pl.BlockSpec((1, block, d), lambda b, i, idx, cnt: (b, i, 0)),
        pl.BlockSpec((1, block, d), lambda b, i, idx, cnt: (b, i, 0)),
        pl.BlockSpec((1, s, d), lambda b, i, idx, cnt: (b, 0, 0)),
        pl.BlockSpec((1, s, LANES), lambda b, i, idx, cnt: (b, 0, 0)),
        pl.BlockSpec((1, s, LANES), lambda b, i, idx, cnt: (b, 0, 0)),
    ]
    dkv_inputs = [qf, kf, vf, do, lse, delta]
    if has_mask:
        # This kv block's mask slice rides blocked like k/v.
        dkv_specs.append(pl.BlockSpec(
            (1, 1, block), lambda b, i, idx, cnt: (b // num_heads, 0, i)))
        dkv_inputs.append(kv_mask)
    dk, dv = pl.pallas_call(
        functools.partial(_sparse_bwd_dkv_kernel, causal=causal, scale=scale,
                          block=block, num_heads=num_heads,
                          has_mask=has_mask),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, qb),
            in_specs=dkv_specs,
            out_specs=[
                pl.BlockSpec((1, block, d),
                             lambda b, i, idx, cnt: (b, i, 0)),
                pl.BlockSpec((1, block, d),
                             lambda b, i, idx, cnt: (b, i, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), kf.dtype),
            jax.ShapeDtypeStruct((bh, s, d), vf.dtype),
        ],
        name="sparse_attn_bwd_dkv",
        interpret=interpret,
        compiler_params=_vmem_params(
            2 * s * d * esz + 2 * s * LANES * 4 + 4 * block * d * esz
            + (4 * s if has_mask else 0)),
    )(q_idx, q_cnt, *dkv_inputs)
    return dq, dk, dv


@functools.lru_cache(maxsize=64)
def _sparse_vjp_fn(layout_key, block, causal, scale, interpret,
                   has_mask=False):
    """Build (and cache) a differentiable [B*H, S, D]-layout sparse
    attention closure for one static layout. The layout rides in the cache
    key as bytes (custom_vjp nondiff args must be hashable). With
    ``has_mask`` the closure takes a [B, 1, S] fp32 key-padding mask as a
    fourth (zero-cotangent) argument."""
    layout_bytes, h, nb = layout_key
    layout = np.frombuffer(layout_bytes, np.int8).reshape(h, nb, nb)
    kv_idx_np, _ = layout_kv_indices(layout)
    q_idx_np, _ = layout_q_indices(layout)
    kv_idx = jnp.asarray(kv_idx_np)
    q_idx = jnp.asarray(q_idx_np)
    kv_cnt = jnp.asarray(layout.sum(-1).astype(np.int32))         # [H, B]
    q_cnt = jnp.asarray(layout.sum(-2).astype(np.int32))          # [H, B]

    if has_mask:
        @jax.custom_vjp
        def fn(qf, kf, vf, mf):
            out, _ = _sparse_forward(qf, kf, vf, mf, kv_idx, kv_cnt, block,
                                     causal, scale, h, interpret)
            return out

        def fwd(qf, kf, vf, mf):
            out, lse = _sparse_forward(qf, kf, vf, mf, kv_idx, kv_cnt,
                                       block, causal, scale, h, interpret)
            return out, (qf, kf, vf, mf, out, lse)

        def bwd(res, g):
            qf, kf, vf, mf, out, lse = res
            dq, dk, dv = _sparse_backward(
                qf, kf, vf, mf, g, out, lse, kv_idx, kv_cnt, q_idx, q_cnt,
                block, causal, scale, h, interpret)
            return dq, dk, dv, jnp.zeros_like(mf)
    else:
        @jax.custom_vjp
        def fn(qf, kf, vf):
            out, _ = _sparse_forward(qf, kf, vf, None, kv_idx, kv_cnt,
                                     block, causal, scale, h, interpret)
            return out

        def fwd(qf, kf, vf):
            out, lse = _sparse_forward(qf, kf, vf, None, kv_idx, kv_cnt,
                                       block, causal, scale, h, interpret)
            return out, (qf, kf, vf, out, lse)

        def bwd(res, g):
            qf, kf, vf, out, lse = res
            return _sparse_backward(qf, kf, vf, None, g, out, lse, kv_idx,
                                    kv_cnt, q_idx, q_cnt, block, causal,
                                    scale, h, interpret)

    fn.defvjp(fwd, bwd)
    return fn


def _pallas_sparse(q, k, v, layout, block, causal, scale, interpret,
                   key_mask=None):
    b, s, h, d = q.shape
    layout = np.asarray(layout).astype(np.int8)

    def to_bhsd(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    key = (layout.tobytes(), layout.shape[0], layout.shape[1])
    fn = _sparse_vjp_fn(key, int(block), bool(causal), float(scale),
                        bool(interpret), key_mask is not None)
    if key_mask is not None:
        mf = key_mask.astype(jnp.float32)[:, None, :]       # [B, 1, S]
        out = fn(to_bhsd(q), to_bhsd(k), to_bhsd(v), mf)
    else:
        out = fn(to_bhsd(q), to_bhsd(k), to_bhsd(v))
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     layout, block: int, *,
                     causal: bool = False,
                     softmax_scale: Optional[float] = None,
                     impl: str = "xla",
                     key_mask: Optional[jax.Array] = None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Block-sparse attention over [B, S, H, D] with an [H, B, B] layout.

    ``key_mask``: optional [B, S] key-padding mask (1 = keep) — masked
    keys drop out of every row (reference sparse_self_attention.py:58
    key_padding_mask); supported by BOTH executors."""
    s = q.shape[1]
    if s % block:
        raise ValueError(f"seq {s} not divisible by block {block}")
    if np.asarray(layout).shape[1] != s // block:
        raise ValueError(f"layout has {np.asarray(layout).shape[1]} blocks, "
                         f"sequence needs {s // block}")
    scale = softmax_scale if softmax_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    tpu = on_tpu()
    # Mosaic lane-alignment constraint: the masked kernels slice the
    # [B, 1, S] mask on its LANE dim at the dynamic per-row column offset
    # (col*block), which TPU lowering only admits when it is provably a
    # multiple of 128 — i.e. block % 128 == 0 (the long-sequence configs;
    # the K/V slices are sublane-dim and only need block % 8). Interpret
    # mode (CPU) has no such constraint.
    masked_pallas_ok = key_mask is None or block % 128 == 0
    if impl == "auto":
        impl = ("pallas" if tpu and masked_pallas_ok else "xla")
    if impl == "xla":
        return _xla_sparse(q, k, v, layout, block, causal, scale, key_mask)
    if impl == "pallas":
        if interpret is None:
            interpret = not tpu
        if not interpret and not masked_pallas_ok:
            raise ValueError(
                f"key_mask with block={block} cannot lower to Mosaic "
                "(mask lane-slices need block % 128 == 0 on TPU) — use "
                "block >= 128, impl='xla', or drop the mask")
        return _pallas_sparse(q, k, v, layout, block, causal, scale,
                              interpret, key_mask=key_mask)
    raise ValueError(f"unknown sparse attention impl '{impl}'")


class SparseSelfAttention:
    """Layout-bound attention callable (reference
    ops/sparse_attention/sparse_self_attention.py:14): construct once with a
    SparsityConfig, call with q/k/v [B, S, H, D]."""

    def __init__(self, sparsity_config, max_seq_length: int = 2048,
                 attn_mask_mode: str = "mul", impl: str = "xla"):
        self.sparsity_config = sparsity_config
        self.max_seq_length = max_seq_length
        self.impl = impl
        self._layouts = {}

    def layout(self, seq_len: int):
        if seq_len not in self._layouts:
            self._layouts[seq_len] = self.sparsity_config.make_layout(seq_len)
        return self._layouts[seq_len]

    def __call__(self, q, k, v, *, causal: Optional[bool] = None,
                 key_mask: Optional[jax.Array] = None,
                 softmax_scale: Optional[float] = None):
        if causal is None:
            causal = getattr(self.sparsity_config, "attention",
                             "bidirectional") == "unidirectional"
        return sparse_attention(q, k, v, self.layout(q.shape[1]),
                                self.sparsity_config.block, causal=causal,
                                softmax_scale=softmax_scale,
                                key_mask=key_mask, impl=self.impl)


def pad_to_block_size(x: jax.Array, block: int, axis: int = 1):
    """SparseAttentionUtils.pad_to_block_size analogue: right-pad the seq
    axis to a block multiple; returns (padded, pad_len)."""
    s = x.shape[axis]
    pad = (-s) % block
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad
