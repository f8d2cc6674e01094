"""Multi-head attention — the framework's central attention dispatch.

TPU-native equivalent of the reference's fused attention kernels
(``csrc/transformer/softmax_kernels.cu``, ``transform_kernels.cu``, and the
strided-batch GEMMs inside ``ds_transformer_cuda.cpp:147``): on TPU the hot
path is a Pallas flash-attention kernel (``deepspeed_tpu/ops/transformer/
flash_attention.py``); the ``xla`` implementation is the always-correct
reference that XLA fuses on its own and the numerics oracle for kernel-parity
tests (the reference's ``tests/unit/test_cuda_forward.py`` methodology).

All implementations share one signature over ``[batch, seq, heads, head_dim]``
tensors. ``impl``:

- ``"xla"``    — pure jnp einsum attention (softmax in fp32).
- ``"pallas"`` — fused flash attention Pallas kernel (O(S) memory).
- ``"auto"``   — pallas on TPU when shapes are tileable, else xla.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from deepspeed_tpu.utils.logging import logger
from deepspeed_tpu.utils.platform import on_tpu


def xla_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = False,
                  bias: Optional[jax.Array] = None,
                  mask: Optional[jax.Array] = None,
                  dropout_rate: float = 0.0,
                  dropout_rng: Optional[jax.Array] = None,
                  deterministic: bool = True,
                  softmax_scale: Optional[float] = None) -> jax.Array:
    """Reference attention. q,k,v: [B, S, H, D] (k/v seq may differ from q's).

    Softmax is computed in fp32 regardless of input dtype — the same
    numerical-stability choice as the reference's ``attn_softmax`` kernel
    (csrc/transformer/softmax_kernels.cu).
    """
    orig_dtype = q.dtype
    head_dim = q.shape[-1]
    scale = softmax_scale if softmax_scale is not None else 1.0 / (head_dim ** 0.5)
    # [B, H, Sq, Sk]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    neg = jnp.finfo(jnp.float32).min
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        logits = jnp.where(causal_mask[None, None], logits, neg)
    if mask is not None:
        # mask: [B, Sk] key-padding, or broadcastable to [B, H, Sq, Sk];
        # True/1 = attend.
        if mask.ndim == 2:
            mask = mask[:, None, None, :]
        elif mask.ndim == 3:
            mask = mask[:, None]
        logits = jnp.where(mask.astype(jnp.bool_), logits, neg)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_rate > 0.0 and not deterministic:
        if dropout_rng is None:
            raise ValueError("dropout_rate>0 requires dropout_rng")
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(orig_dtype), v)
    return out


def _as_kv_mask(mask, batch, sk):
    """Extract a key-padding mask [B, Sk] from the common mask forms, or
    None if the mask is a general [B, H, Sq, Sk] pattern the flash kernel
    cannot take."""
    if mask is None:
        return None
    if mask.ndim == 2 and mask.shape == (batch, sk):
        return mask
    if (mask.ndim == 4 and mask.shape[0] == batch and mask.shape[1] == 1
            and mask.shape[2] == 1 and mask.shape[3] == sk):
        return mask[:, 0, 0, :]
    if (mask.ndim == 3 and mask.shape[0] == batch and mask.shape[1] == 1
            and mask.shape[2] == sk):
        return mask[:, 0, :]
    return None


# Auto-mode crossover. HISTORY, not re-measured on current code: taken on a
# v5e on 2026-07-30/31 (8-layer BERT-large-shaped stacks, fwd+bwd, NON-causal,
# the flash kernels at their 512x1024 blocks), before this round's benchmark
# existed and through another harness:
#   seq 128:  XLA  97 vs pallas 86 TFLOP/s  -> XLA
#   seq 512:  XLA  79 vs pallas 87          -> pallas
#   seq 1024: XLA  64 vs pallas 96          -> pallas
#   seq 2048: XLA  50 vs pallas 85          -> pallas
#   seq 4096: XLA  37 vs pallas 78          -> pallas
# Short sequences stay on XLA's fused materialized attention (tiny score
# tensors, better fusion with the surrounding matmuls); from 512 keys up
# the O(S) streaming kernel wins on both time and memory. With attention
# dropout ON the gap widens further (the xla path adds bernoulli + an
# [S,S] mask; in-kernel hash dropout costs ~2%): measured r3, fwd+bwd
# 8-layer stacks — seq 512: 19.7 vs 32.8 ms; 1024: 23.8 vs 56.1;
# 2048: 25.9 vs 101.3 (PROFILE.md, history too). The non-causal kernels are
# what they were then; the CAUSAL walk and its blocks changed since
# (flash_attention.py's header has what was measured for them), which can
# only have moved a causal crossover DOWN: 512 stays the gate until a cell
# below it says otherwise. What the benchmark holds today: at 128 keys the
# XLA path (bert-large-train-s128), at 1024 and 4096 causal keys the kernels
# (PERF.md section 5). Overridable with impl="pallas"/"xla".
PALLAS_MIN_SEQ_K = 512


def _pallas_ok(q, k, bias, mask, dropout_active: bool = False):
    if bias is not None:
        return False
    if mask is not None and _as_kv_mask(mask, q.shape[0], k.shape[1]) is None:
        return False
    sq, sk = q.shape[1], k.shape[1]
    if not (sq % 128 == 0 and sk % 128 == 0 and q.shape[-1] in
            (64, 128, 256)):
        return False
    if sk < PALLAS_MIN_SEQ_K:
        return False
    # Odd 128-multiple self-attention lengths (640/768/896/1152) collapse
    # the Q blocks; round 3 measured XLA ahead there and dispatched away.
    # Re-measured in round 4 against the SAME kernels with the explicit
    # padded-flash alternative (tools/probe_pad_dispatch.py, fwd+bwd
    # 8-layer stacks, in-run A/B, ms):
    #   seq   640 off: xla 29.4  pallas 19.2  padded 26.0  -> pallas
    #   seq   768 off: xla 32.7  pallas 18.3  padded 26.1  -> pallas
    #   seq   896 off: xla 45.4  pallas 28.4  padded 26.4  -> ~tie
    #   seq  1152 off: xla 68.5  pallas 38.4  padded 50.6  -> pallas
    #   (dropout ON widens every pallas win by ~2x: xla pays bernoulli +
    #    an [S,S] mask.)
    # The degraded-block kernel now wins every cell (the r3 xla numbers
    # did not survive the round-4 VMEM/compiler-params changes), so the
    # gate admits all 128-multiple lengths; impl="pallas_pad" remains the
    # explicit 512-padded route (marginal winner at 896 only).
    return True


def _flash_per_shard(flash, mesh, q, k, v, kv_mask, dropout_rng):
    """``flash(q, k, v, kv_mask, dropout_rng)`` — once per device shard on
    a multi-device mesh.

    GSPMD cannot partition a Mosaic custom call (jax refuses the lowering:
    "Mosaic kernels cannot be automatically partitioned"), so under a
    multi-device ``jit`` the kernel must sit in a region that is manual
    over EVERY mesh axis. Attention is independent over batch and heads:
    the batch splits over the data-like axes, the heads over the model
    axis, and nothing else is split (a sequence-sharded input is gathered;
    ring/Ulysses are the sequence-parallel implementations).

    The mesh is the manual region's own when tracing inside one (pipeline
    stages, the hierarchical grad sync), else ``mesh`` — the caller's, or
    the one the tracing engine pinned. With no mesh known the kernel is
    called as is: right on one device, and jax's own loud error on many.
    """
    from deepspeed_tpu.parallel.mesh import (DATA_AXIS, DCN_AXIS, MODEL_AXIS,
                                             get_pinned_mesh)

    ctx = jax.sharding.get_abstract_mesh()
    if not ctx.empty:
        mesh, manual = ctx, frozenset(ctx.manual_axes)
    else:
        mesh = mesh if mesh is not None else get_pinned_mesh()
        manual = frozenset()
    free = (frozenset(mesh.axis_names) - manual
            if mesh is not None and mesh.size > 1 else frozenset())
    if not free:        # one device, or already per-shard on every axis
        return flash(q, k, v, kv_mask, dropout_rng)

    def split_over(dim, names):
        # the free axes among `names`, if together they divide `dim`
        names = tuple(a for a in names if a in free and mesh.shape[a] > 1)
        size = math.prod(mesh.shape[a] for a in names)
        return names if names and dim % size == 0 else None

    batch = split_over(q.shape[0], (DCN_AXIS, DATA_AXIS))
    heads = split_over(q.shape[2], (MODEL_AXIS,))
    qkv = PartitionSpec(batch, None, heads, None)
    # kv_mask / dropout_rng may be None: an empty pytree, spec ignored
    in_specs = (qkv, qkv, qkv, PartitionSpec(batch, None), PartitionSpec())

    def shard(q, k, v, kv_mask, rng):
        if rng is not None:
            # decorrelate the in-kernel dropout masks across shards
            for axis in (batch or ()) + (heads or ()):
                rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
        return flash(q, k, v, kv_mask, rng)

    return jax.shard_map(shard, mesh=mesh, in_specs=in_specs, out_specs=qkv,
                         axis_names=free, check_vma=False)(
                             q, k, v, kv_mask, dropout_rng)


def _padded_flash(q, k, v, *, causal, kv_mask, softmax_scale, dropout_rate,
                  dropout_rng, pad_to: int = 512):
    """Run the flash kernel on sequences padded up to a full-block multiple,
    masking the pad keys and slicing the pad queries off — recovers the
    tuned 512-wide blocks for lengths like 640/768/896/1152 whose own
    divisors collapse the block size (round-3 VERDICT weak #3)."""
    import jax.numpy as jnp

    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    b, sq = q.shape[0], q.shape[1]
    sk = k.shape[1]
    tq = -(-sq // pad_to) * pad_to
    tk = -(-sk // pad_to) * pad_to

    def pad_seq(x, t):
        s = x.shape[1]
        if s == t:
            return x
        w = [(0, 0)] * x.ndim
        w[1] = (0, t - s)
        return jnp.pad(x, w)

    if kv_mask is None:
        kv_mask = jnp.ones((b, sk), jnp.float32)
    out = flash_attention(pad_seq(q, tq), pad_seq(k, tk), pad_seq(v, tk),
                          causal=causal, kv_mask=pad_seq(kv_mask, tk),
                          softmax_scale=softmax_scale,
                          dropout_rate=dropout_rate, dropout_rng=dropout_rng)
    return out[:, :sq]


@functools.lru_cache(maxsize=None)
def _log_auto_choice(impl: str, causal: bool, sq: int, sk: int,
                     head_dim: int) -> None:
    """Say once per shape which implementation ``impl="auto"`` resolved to
    (the cache is the once) — a run that was meant to use the flash kernel
    and landed on the XLA path must be visible in its log. For the causal
    flash kernels also how much of the score square their walk visits, how
    much of that it masks, and that the backward is one kernel."""
    walk = ""
    if impl == "pallas" and causal:
        from deepspeed_tpu.ops.transformer.flash_attention import (
            causal_walk, fitted_blocks)

        bq, bk = fitted_blocks(causal, sq, sk, head_dim)
        w = causal_walk(sq, sk, bq, bk)
        walk = (f"; causal walk in {bq}x{bk}: blocks visited {w.visited} of "
                f"{w.total}, {w.crossed} masked; backward: one kernel")
    logger.info(f"attention impl=auto -> {impl} (seq_q={sq}, seq_k={sk}, "
                f"head_dim={head_dim}{walk})")


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = False,
              bias: Optional[jax.Array] = None,
              mask: Optional[jax.Array] = None,
              dropout_rate: float = 0.0,
              dropout_rng: Optional[jax.Array] = None,
              deterministic: bool = True,
              softmax_scale: Optional[float] = None,
              mesh=None,
              impl: str = "auto") -> jax.Array:
    """Dispatching attention entry point used by every model family."""
    dropout_active = dropout_rate > 0.0 and not deterministic
    if impl == "auto":
        impl = ("pallas" if on_tpu() and _pallas_ok(
            q, k, bias, mask, dropout_active) else "xla")
        _log_auto_choice(impl, causal, q.shape[1], k.shape[1], q.shape[-1])
    if impl == "pallas_pad":
        kv_mask = _as_kv_mask(mask, q.shape[0], k.shape[1])
        if bias is not None or (mask is not None and kv_mask is None):
            raise ValueError("impl='pallas_pad' takes only key-padding "
                             "masks, like impl='pallas'")
        rate = dropout_rate if dropout_active else 0.0
        return _flash_per_shard(
            lambda q, k, v, kv_mask, rng: _padded_flash(
                q, k, v, causal=causal, kv_mask=kv_mask,
                softmax_scale=softmax_scale, dropout_rate=rate,
                dropout_rng=rng),
            mesh, q, k, v, kv_mask, dropout_rng)
    if impl == "pallas":
        kv_mask = _as_kv_mask(mask, q.shape[0], k.shape[1])
        if bias is not None or (mask is not None and kv_mask is None):
            raise ValueError("impl='pallas' flash attention takes only "
                             "key-padding masks ([B, Sk] / [B,1,1,Sk]) — "
                             "use impl='xla' for general masks/bias (or "
                             "sparse attention for layout masks)")
        from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

        rate = dropout_rate if dropout_active else 0.0
        return _flash_per_shard(
            lambda q, k, v, kv_mask, rng: flash_attention(
                q, k, v, causal=causal, kv_mask=kv_mask,
                softmax_scale=softmax_scale, dropout_rate=rate,
                dropout_rng=rng),
            mesh, q, k, v, kv_mask, dropout_rng)
    if impl == "xla":
        return xla_attention(q, k, v, causal=causal, bias=bias, mask=mask,
                             dropout_rate=dropout_rate, dropout_rng=dropout_rng,
                             deterministic=deterministic,
                             softmax_scale=softmax_scale)
    if impl in ("ring", "ulysses"):
        if bias is not None or mask is not None or (
                dropout_rate > 0.0 and not deterministic):
            raise ValueError(f"impl='{impl}' does not take mask/bias/dropout")
        if mesh is None:
            from deepspeed_tpu.parallel.mesh import get_default_mesh

            mesh = get_default_mesh()
        if mesh is None:
            raise ValueError(f"impl='{impl}' needs a mesh (pass mesh= or "
                             "build the engine first, which registers one)")
        from deepspeed_tpu.parallel.sequence import (ring_attention,
                                                     ulysses_attention)

        if impl == "ring":
            return ring_attention(q, k, v, mesh=mesh, causal=causal,
                                  softmax_scale=softmax_scale)
        return ulysses_attention(q, k, v, mesh=mesh, causal=causal,
                                 softmax_scale=softmax_scale)
    raise ValueError(f"unknown attention impl '{impl}'")
