"""Sequence/context parallelism — ring attention and Ulysses all-to-all.

The reference snapshot has NO sequence parallelism (its `slice_parallel` is
just an alias of the model axis, pipe/topology.py:446; long sequences are
served by block-sparse attention only). This module adds the real
capability the way TPUs want it:

- **Ring attention**: q/k/v stay sharded over the ``sequence`` mesh axis;
  K/V chunks rotate around the ring with ``ppermute`` over ICI while each
  device accumulates flash-style online-softmax partials for its local Q
  chunk. Memory per device is O(S/n); the K/V rotation overlaps with the
  per-chunk attention compute under XLA's scheduler.
- **Ulysses all-to-all**: ``all_to_all`` reshards [B, S/n, H, D] ->
  [B, S, H/n, D] so each device runs FULL-sequence attention for H/n heads
  (the Pallas flash kernel applies directly), then reshards back. Two
  all-to-alls per call; requires heads % n == 0.

Both run inside a shard_map that is manual over ``sequence`` ONLY, so data
parallel batch sharding and ZeRO placement continue to compose via GSPMD.
Softmax statistics and cross-chunk merges are fp32.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.parallel.mesh import SEQUENCE_AXIS

NEG_INF = -1e30


def _chunk_attention_partial(q, k, v, scale, mask):
    """Unnormalised attention of one (q-chunk, kv-chunk) pair.

    q: [B, Sq, H, D]; k,v: [B, Sk, H, D]; mask: [Sq, Sk] bool or None.
    Returns (acc [B,Sq,H,D] fp32, m [B,H,Sq] fp32 rowmax, l [B,H,Sq] fp32
    rowsum) — the flash-attention partial statistics for later merging.
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)                      # [B,H,Sq]
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return acc, m, l


def _merge_partials(carry, update):
    """Online-softmax merge of two partial results."""
    acc0, m0, l0 = carry
    acc1, m1, l1 = update
    m = jnp.maximum(m0, m1)
    a0 = jnp.exp(m0 - m)
    a1 = jnp.exp(m1 - m)
    acc = (acc0 * a0.transpose(0, 2, 1)[..., None] +
           acc1 * a1.transpose(0, 2, 1)[..., None])
    return acc, m, l0 * a0 + l1 * a1


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   mesh: Mesh,
                   causal: bool = False,
                   softmax_scale: Optional[float] = None,
                   axis: str = SEQUENCE_AXIS) -> jax.Array:
    """Ring attention over the ``sequence`` axis.

    q/k/v: [B, S, H, D] GLOBAL shapes (jit-level); under the hood each
    sequence rank holds S/n. Returns [B, S, H, D].
    """
    n = mesh.shape.get(axis, 1)
    scale = softmax_scale if softmax_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if n == 1:
        from deepspeed_tpu.ops.transformer.attention import xla_attention

        return xla_attention(q, k, v, causal=causal, softmax_scale=scale)
    s_global = q.shape[1]
    if s_global % n:
        raise ValueError(f"seq {s_global} not divisible by sequence axis {n}")
    chunk = s_global // n
    orig_dtype = q.dtype

    def ring_fn(q_c, k_c, v_c):
        rank = jax.lax.axis_index(axis)
        shift = [(i, (i + 1) % n) for i in range(n)]
        q32 = q_c.astype(jnp.float32)
        q_pos = rank * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (chunk, chunk), 0)

        def hop(carry, r):
            acc_m_l, kc, vc = carry
            src = (rank - r) % n
            if causal:
                k_pos = src * chunk + jax.lax.broadcasted_iota(
                    jnp.int32, (chunk, chunk), 1)
                mask = q_pos >= k_pos
            else:
                mask = None
            part = _chunk_attention_partial(q32, kc.astype(jnp.float32),
                                            vc.astype(jnp.float32),
                                            scale, mask)
            acc_m_l = _merge_partials(acc_m_l, part)
            kc = jax.lax.ppermute(kc, axis, shift)
            vc = jax.lax.ppermute(vc, axis, shift)
            return (acc_m_l, kc, vc), None

        b, _, h, d = q_c.shape
        init = ((jnp.zeros((b, chunk, h, d), jnp.float32),
                 jnp.full((b, h, chunk), NEG_INF, jnp.float32),
                 jnp.zeros((b, h, chunk), jnp.float32)), k_c, v_c)
        (final, _, _), _ = jax.lax.scan(hop, init, jnp.arange(n))
        acc, _, l = final
        l_safe = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        return (acc / l_safe).astype(orig_dtype)

    seq_spec = P(None, SEQUENCE_AXIS, None, None)
    mapped = shard_map(
        ring_fn, mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec),
        out_specs=seq_spec,
        axis_names={axis},
        check_vma=False,
    )
    # Partial-manual shard_map only traces under jit; the wrapper inlines
    # when an outer jit is active and compiles standalone in eager use.
    return jax.jit(mapped)(q, k, v)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      mesh: Mesh,
                      causal: bool = False,
                      softmax_scale: Optional[float] = None,
                      attention_impl: str = "xla",
                      axis: str = SEQUENCE_AXIS) -> jax.Array:
    """Ulysses-style all-to-all sequence parallelism.

    Reshards seq-sharded q/k/v to head-sharded, runs full-sequence attention
    per head group (optionally with the Pallas flash kernel), reshards back.
    """
    n = mesh.shape.get(axis, 1)
    scale = softmax_scale if softmax_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    from deepspeed_tpu.ops.transformer.attention import attention as attn

    if n == 1:
        return attn(q, k, v, causal=causal, softmax_scale=scale,
                    impl=attention_impl)
    h = q.shape[2]
    if h % n:
        raise ValueError(f"{h} heads not divisible by sequence axis {n}")

    def ulysses_fn(q_c, k_c, v_c):
        # [B, S/n, H, D] -> [B, S, H/n, D]: gather seq, scatter heads.
        def seq_to_head(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        def head_to_seq(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        qh, kh, vh = seq_to_head(q_c), seq_to_head(k_c), seq_to_head(v_c)
        out = attn(qh, kh, vh, causal=causal, softmax_scale=scale,
                   impl=attention_impl)
        return head_to_seq(out)

    seq_spec = P(None, SEQUENCE_AXIS, None, None)
    mapped = shard_map(
        ulysses_fn, mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec),
        out_specs=seq_spec,
        axis_names={axis},
        check_vma=False,
    )
    return jax.jit(mapped)(q, k, v)
