"""Every ``pallas_call`` in the tree, each at the smallest shape its
dispatch gate admits, with its ``jax.numpy`` reference — ONE table for the
two checks that see the compiler:

- ``tests/test_tpu_lowering.py`` compiles each case with
  ``interpret=False`` for a described v5e topology (no chip needed) — the
  Pallas interpreter accepts block shapes Mosaic refuses, so interpret-mode
  parity tests alone cannot tell whether a kernel can run on a TPU;
- ``chip_smoke.py`` runs each case on the chip and compares it with its
  reference at the bf16 floor (``utils/parity.py``).

A kernel added to the tree gets a case here. ``run(interpret, *args)`` and
``reference(*args)`` return the same pytree; backward kernels are reached
through ``jax.vjp`` with a random cotangent (the last argument).
"""

from dataclasses import dataclass
from typing import Any, Callable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["KernelCase", "kernel_cases"]


@dataclass(frozen=True)
class KernelCase:
    name: str
    make_args: Callable[[np.random.Generator], Tuple]
    run: Callable[..., Any]         # run(interpret, *args)
    reference: Callable[..., Any]   # reference(*args)


def _normal(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _fwd_bwd(f: Callable, n_diff: int) -> Callable:
    """``f(*args[:-1])`` plus its VJP w.r.t. the first ``n_diff`` args at
    cotangent ``args[-1]`` — so a case covers the backward kernels too."""
    def g(*args):
        *xs, ct = args
        out, vjp = jax.vjp(lambda *d: f(*d, *xs[n_diff:]), *xs[:n_diff])
        return (out,) + vjp(ct.astype(out.dtype))
    return g


# -- flash attention (ops/transformer/flash_attention.py) ------------------
def _flash_case(head_dim: int, dtype, seq: int = 512,
                heads: int = 2) -> KernelCase:
    from deepspeed_tpu.ops.transformer.attention import xla_attention
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    # seq 512 is the auto-dispatch crossover (attention.PALLAS_MIN_SEQ_K):
    # one block. The longer cases walk the causal triangle in several.
    shape = (1, seq, heads, head_dim)

    def make_args(rng):
        return tuple(_normal(rng, shape, dtype) for _ in range(4))

    def run(interpret, *args):
        return _fwd_bwd(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=interpret), 3)(*args)

    return KernelCase(
        f"flash_attention fwd+bwd d{head_dim} {jnp.dtype(dtype).name}"
        + ("" if seq == 512 else f" seq {seq}"),
        make_args, run,
        _fwd_bwd(lambda q, k, v: xla_attention(q, k, v, causal=True), 3))


# -- block-sparse attention (ops/sparse_attention/sparse_attention.py) -----
def _sparse_case(block: int, masked: bool) -> KernelCase:
    from deepspeed_tpu.ops.sparse_attention.sparse_attention import \
        sparse_attention
    from deepspeed_tpu.ops.sparse_attention.sparsity_config import \
        FixedSparsityConfig

    heads, seq = 2, 4 * block
    layout = FixedSparsityConfig(heads, block, num_local_blocks=2,
                                 num_global_blocks=1).make_layout(seq)
    shape = (1, seq, heads, 64)

    def make_args(rng):
        args = [_normal(rng, shape) for _ in range(3)]
        if masked:
            # key-padding mask: the last quarter of the keys are pad
            mask = np.ones((1, seq), np.float32)
            mask[:, -seq // 4:] = 0.0
            args.append(jnp.asarray(mask))
        return tuple(args) + (_normal(rng, shape),)

    def attend(impl, interpret):
        def f(q, k, v, *mask):
            return sparse_attention(q, k, v, layout, block, causal=True,
                                    impl=impl, interpret=interpret,
                                    key_mask=mask[0] if mask else None)
        return _fwd_bwd(f, 3)

    return KernelCase(
        f"sparse_attention fwd+bwd block {block}"
        + (" key-masked" if masked else ""),
        make_args,
        lambda interpret, *a: attend("pallas", interpret)(*a),
        attend("xla", None))


# -- fused blockwise Adam (ops/adam/fused_update.py) -----------------------
def _fused_adam_case(cast: bool) -> KernelCase:
    from deepspeed_tpu.ops.adam.fused_adam import AdamState, FusedAdam
    from deepspeed_tpu.ops.adam.fused_update import (fused_adam_leaf,
                                                     scalar_tile)

    opt = FusedAdam(lr=1e-3, weight_decay=0.01, adamw_mode=True)
    shape = (300, 129)          # not a lane multiple: exercises the padding
    cast_dtype = jnp.bfloat16 if cast else None
    step = jnp.int32(3)

    def make_args(rng):
        return (_normal(rng, shape), 0.01 * _normal(rng, shape, jnp.bfloat16),
                0.01 * _normal(rng, shape), jnp.abs(_normal(rng, shape)) * 1e-4)

    def run(interpret, p, g, m, v):
        t = (step + 1).astype(jnp.float32)
        sc = scalar_tile(opt.lr, 1.0 - opt.beta1 ** t, 1.0 - opt.beta2 ** t)
        return fused_adam_leaf(p, g, m, v, sc, b1=opt.beta1, b2=opt.beta2,
                               eps=opt.eps, weight_decay=opt.weight_decay,
                               adamw_mode=opt.adamw_mode,
                               cast_dtype=cast_dtype, interpret=interpret)

    def reference(p, g, m, v):
        new_p, st = opt.update(g, AdamState(step=step, exp_avg=m,
                                            exp_avg_sq=v), p)
        out = (new_p, st.exp_avg, st.exp_avg_sq)
        return out + ((new_p.astype(cast_dtype),) if cast else ())

    return KernelCase("fused_adam_leaf" + (" + bf16 cast" if cast else ""),
                      make_args, run, reference)


# -- paged decode / ragged prefill (ops/transformer/paged_attention.py) ----
def _paged_reference(q, k_pool, v_pool, k_scale, v_scale, table, pos,
                     block_size):
    """Gather each row's table window, dequantize, masked attention: key j
    (table-slot order) is visible to query i iff j <= pos + i."""
    from deepspeed_tpu.ops.transformer.attention import xla_attention

    heads = q.shape[2]

    def gathered(pool, scale):
        # unfold the stored [N, BS, H*D] form
        x = pool.astype(jnp.float32).reshape(*pool.shape[:2], heads, -1)
        if scale is not None:
            x = x * scale[..., None]
        x = x[table]                               # [B, WB, BS, H, D]
        return x.reshape(x.shape[0], -1, *x.shape[3:])

    s = q.shape[1]
    kk, vv = gathered(k_pool, k_scale), gathered(v_pool, v_scale)
    qpos = pos[:, None] + jnp.arange(s)[None, :]
    kpos = jnp.arange(table.shape[1] * block_size)
    mask = kpos[None, None, :] <= qpos[:, :, None]             # [B, S, K]
    out = xla_attention(q.astype(jnp.float32), kk, vv, mask=mask[:, None])
    return out.astype(q.dtype)


def _paged_args(rng, q_shape, int8: bool):
    """head_dim 128 / block 16 pools (paged_decode_ok's smallest geometry),
    scrambled non-contiguous per-row tables, positions mid-block."""
    from deepspeed_tpu.serving.kv_cache import _quant_tokens

    rows, heads, d = q_shape[0], q_shape[-2], q_shape[-1]
    n_blocks, bs, wb = 8, 16, 3
    pools = [_normal(rng, (n_blocks, bs, heads, d)) for _ in range(2)]
    table = np.stack([rng.permutation(np.arange(1, n_blocks))[:wb]
                      for _ in range(rows)]).astype(np.int32)
    pos = rng.integers(0, (wb - 1) * bs, (rows,)).astype(np.int32)
    q = _normal(rng, q_shape, jnp.bfloat16)
    if int8:
        (k, ks), (v, vs) = (_quant_tokens(p) for p in pools)
    else:
        # fp pools carry no scales: None is an empty pytree to jit
        (k, ks), (v, vs) = ((p.astype(jnp.bfloat16), None) for p in pools)
    # the stored form: heads folded into the lane axis
    k, v = (p.reshape(n_blocks, bs, heads * d) for p in (k, v))
    return q, k, v, ks, vs, jnp.asarray(table), jnp.asarray(pos)


def _paged_case(num_q: int, int8: bool) -> KernelCase:
    from deepspeed_tpu.ops.transformer.paged_attention import \
        paged_decode_attention

    what = "decode" if num_q == 1 else f"speculative verify (s={num_q})"
    return KernelCase(
        f"paged_decode_attention {what} {'int8' if int8 else 'bf16'} pools",
        lambda rng: _paged_args(rng, (2, num_q, 2, 128), int8),
        lambda interpret, *args: paged_decode_attention(
            *args, block_size=16, interpret=interpret),
        lambda *args: _paged_reference(*args, block_size=16))


def _chunked_case(int8: bool) -> KernelCase:
    from deepspeed_tpu.ops.transformer.chunked_prefill import \
        chunked_prefill_attention

    def reference(q, *rest):
        # a ragged token is a one-query sequence at its own position
        return _paged_reference(q[:, None], *rest, block_size=16)[:, 0]

    return KernelCase(
        f"chunked_prefill_attention {'int8' if int8 else 'bf16'} pools",
        lambda rng: _paged_args(rng, (8, 2, 128), int8),
        lambda interpret, *args: chunked_prefill_attention(
            *args, block_size=16, interpret=interpret),
        reference)


def kernel_cases() -> List[KernelCase]:
    return [
        _flash_case(64, jnp.float32), _flash_case(128, jnp.float32),
        _flash_case(64, jnp.bfloat16),       # the trainer's dtype
        _flash_case(256, jnp.bfloat16),      # latent attention's head size
        # the two training cells' shapes in miniature batch, default blocks
        _flash_case(64, jnp.bfloat16, seq=1024),
        _flash_case(256, jnp.bfloat16, seq=4096, heads=1),
        _sparse_case(64, masked=False), _sparse_case(128, masked=False),
        _sparse_case(128, masked=True),      # masks need block % 128 == 0
        _fused_adam_case(cast=False), _fused_adam_case(cast=True),
        _paged_case(1, int8=False), _paged_case(1, int8=True),
        _paged_case(5, int8=False), _paged_case(5, int8=True),
        _chunked_case(int8=False), _chunked_case(int8=True),
    ]
