"""Fused LayerNorm + projection — the TPU half of the reference's fused
transformer block.

The reference's defining kernel is one fused body per layer: LN, QKV
projection, attention, bias/GELU/dropout all execute without HBM
round-trips between them (``csrc/transformer/ds_transformer_cuda.cpp:147``
forward, ``:295`` backward, with ``normalize_kernels.cu`` and
``gelu_kernels.cu`` folded in). XLA already fuses elementwise epilogues
into matmuls, but it cannot fuse a row *reduction* (the LayerNorm
mean/variance) into a matmul operand — so every pre-LN site pays a
[tokens, hidden] round-trip to HBM for the normalized activations in the
forward AND for their gradient in the backward. At GPT-2 bench shapes
that is ~25 MB × 2 sites × 12 layers × fwd+bwd per microbatch.

``ln_matmul`` fuses ``y = act(LN(x) @ W + b)`` into one Pallas kernel:
the normalized rows live only in VMEM. The backward is a second kernel
that recomputes the (cheap, VPU) LayerNorm from ``x`` and produces all
five gradients in a single sweep over the row blocks, accumulating
``dW``/``db``/``dgamma``/``dbeta`` in VMEM-resident fp32 blocks across
the sequential TPU grid.

Matmul dtype discipline matches the unfused flax path so the fused op is
trajectory-compatible: LN in fp32, normalized output cast to the weight
dtype for the MXU dot, fp32 accumulation (``preferred_element_type``),
output cast back to the activation dtype.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.transformer.flash_attention import _vmem_params
from deepspeed_tpu.utils.platform import on_tpu

DEFAULT_BLOCK_ROWS = 512
_SQRT_2_OVER_PI = 0.7978845608028654


def _gelu_tanh(x):
    """tanh-approximate GELU, fp32 — bit-matches ``nn.gelu(approximate=
    True)`` evaluated in fp32."""
    return 0.5 * x * (1.0 + jnp.tanh(_SQRT_2_OVER_PI
                                     * (x + 0.044715 * x * x * x)))


def _gelu_tanh_grad(x):
    """d/dx of the tanh-approximate GELU."""
    u = _SQRT_2_OVER_PI * (x + 0.044715 * x * x * x)
    t = jnp.tanh(u)
    du = _SQRT_2_OVER_PI * (1.0 + 3.0 * 0.044715 * x * x)
    return 0.5 * (1.0 + t) + 0.5 * x * (1.0 - t * t) * du


def _layernorm_rows(xf, gamma, beta, eps):
    """fp32 LayerNorm over the last dim; returns (ln, xhat, rstd)."""
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    return xhat * gamma + beta, xhat, rstd


def _fwd_kernel(x_ref, g_ref, b_ref, w_ref, bias_ref, o_ref, *,
                eps: float, activation: Optional[str]):
    xf = x_ref[...].astype(jnp.float32)
    ln, _, _ = _layernorm_rows(xf, g_ref[0].astype(jnp.float32),
                               b_ref[0].astype(jnp.float32), eps)
    y = jnp.dot(ln.astype(w_ref.dtype), w_ref[...],
                preferred_element_type=jnp.float32)
    y = y + bias_ref[0].astype(jnp.float32)
    if activation == "gelu":
        y = _gelu_tanh(y)
    o_ref[...] = y.astype(o_ref.dtype)


def _bwd_kernel(x_ref, g_ref, b_ref, w_ref, bias_ref, dy_ref,
                dx_ref, dw_ref, dbias_ref, dg_ref, db_ref, *,
                eps: float, activation: Optional[str]):
    step = pl.program_id(0)
    xf = x_ref[...].astype(jnp.float32)
    gamma = g_ref[0].astype(jnp.float32)
    ln, xhat, rstd = _layernorm_rows(xf, gamma,
                                     b_ref[0].astype(jnp.float32), eps)
    ln_c = ln.astype(w_ref.dtype)
    dy = dy_ref[...].astype(jnp.float32)
    if activation == "gelu":
        pre = jnp.dot(ln_c, w_ref[...], preferred_element_type=jnp.float32)
        pre = pre + bias_ref[0].astype(jnp.float32)
        dy = dy * _gelu_tanh_grad(pre)
    dy_c = dy.astype(w_ref.dtype)

    dw = jax.lax.dot_general(ln_c, dy_c, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dbias = jnp.sum(dy, axis=0, keepdims=True)
    dln = jax.lax.dot_general(dy_c, w_ref[...], (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    dg = jnp.sum(dln * xhat, axis=0, keepdims=True)
    db = jnp.sum(dln, axis=0, keepdims=True)

    dxhat = dln * gamma
    m1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (rstd * (dxhat - m1 - xhat * m2)).astype(dx_ref.dtype)

    @pl.when(step == 0)
    def _init():
        dw_ref[...] = dw
        dbias_ref[...] = dbias
        dg_ref[...] = dg
        db_ref[...] = db

    @pl.when(step != 0)
    def _acc():
        dw_ref[...] += dw
        dbias_ref[...] += dbias
        dg_ref[...] += dg
        db_ref[...] += db


def _fit_rows(block: int, n: int) -> int:
    """Largest multiple-of-8 row count <= block dividing n (sublane
    granularity); 0 if none exists."""
    block = min(block, n)
    while block >= 8 and (n % block or block % 8):
        block -= 8
    return block if block >= 8 and n % block == 0 else 0


def _run_fwd(x, gamma, beta, w, bias, eps, activation, block_rows,
             interpret):
    n, d = x.shape
    f = w.shape[1]
    bn = _fit_rows(block_rows, n)
    kernel = functools.partial(_fwd_kernel, eps=eps, activation=activation)
    return pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((d, f), lambda i: (0, 0)),
            pl.BlockSpec((1, f), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, f), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, f), x.dtype),
        name="fused_ln_fwd",
        interpret=interpret,
        compiler_params=_vmem_params(
            d * f * w.dtype.itemsize + bn * d * x.dtype.itemsize
            + 2 * bn * f * 4 + bn * d * 4),
    )(x, gamma[None], beta[None], w, bias[None])


def _run_bwd(x, gamma, beta, w, bias, dy, eps, activation, block_rows,
             interpret):
    n, d = x.shape
    f = w.shape[1]
    bn = _fit_rows(block_rows, n)
    kernel = functools.partial(_bwd_kernel, eps=eps, activation=activation)
    dx, dw, dbias, dg, db = pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((d, f), lambda i: (0, 0)),
            pl.BlockSpec((1, f), lambda i: (0, 0)),
            pl.BlockSpec((bn, f), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((d, f), lambda i: (0, 0)),
            pl.BlockSpec((1, f), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x.dtype),
            jax.ShapeDtypeStruct((d, f), jnp.float32),
            jax.ShapeDtypeStruct((1, f), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        name="fused_ln_bwd",
        interpret=interpret,
        compiler_params=_vmem_params(
            2 * d * f * 4 + 2 * bn * (d + f) * 4 + 2 * (d + f) * 4),
    )(x, gamma[None], beta[None], w, bias[None], dy)
    return dx, dw, dbias[0], dg[0], db[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _ln_matmul(x, gamma, beta, w, bias, eps, activation, block_rows,
               interpret):
    return _run_fwd(x, gamma, beta, w, bias, eps, activation, block_rows,
                    interpret)


def _ln_matmul_fwd(x, gamma, beta, w, bias, eps, activation, block_rows,
                   interpret):
    out = _run_fwd(x, gamma, beta, w, bias, eps, activation, block_rows,
                   interpret)
    return out, (x, gamma, beta, w, bias)


def _ln_matmul_bwd(eps, activation, block_rows, interpret, res, dy):
    x, gamma, beta, w, bias = res
    dx, dw, dbias, dg, db = _run_bwd(x, gamma, beta, w, bias, dy, eps,
                                     activation, block_rows, interpret)
    return (dx, dg.astype(gamma.dtype), db.astype(beta.dtype),
            dw.astype(w.dtype), dbias.astype(bias.dtype))


_ln_matmul.defvjp(_ln_matmul_fwd, _ln_matmul_bwd)


def ln_matmul_reference(x, gamma, beta, w, bias, *, eps: float = 1e-5,
                        activation: Optional[str] = None):
    """jnp oracle with the exact dtype discipline of the kernel (and of the
    unfused flax path): fp32 LN, weight-dtype MXU dot, fp32 accumulate."""
    xf = x.astype(jnp.float32)
    ln, _, _ = _layernorm_rows(xf, gamma.astype(jnp.float32),
                               beta.astype(jnp.float32), eps)
    y = jnp.dot(ln.astype(w.dtype), w, preferred_element_type=jnp.float32)
    y = y + bias.astype(jnp.float32)
    if activation == "gelu":
        y = _gelu_tanh(y)
    return y.astype(x.dtype)


def ln_matmul_ok(n: int, d: int, f: int,
                 block_rows: int = DEFAULT_BLOCK_ROWS) -> bool:
    """Shape gate for the fused path: lane-aligned hidden/output dims and a
    viable row block (mirrors the flash kernel's dispatch gating)."""
    return (d % 128 == 0 and f % 128 == 0
            and _fit_rows(block_rows, n) >= 128)


def ln_matmul(x: jax.Array, gamma: jax.Array, beta: jax.Array,
              w: jax.Array, bias: jax.Array, *, eps: float = 1e-5,
              activation: Optional[str] = None,
              block_rows: int = DEFAULT_BLOCK_ROWS,
              interpret: Optional[bool] = None) -> jax.Array:
    """``act(LayerNorm(x; gamma, beta) @ w + bias)`` without the LN
    round-trip. ``x``: [..., D] (leading dims flattened internally);
    ``w``: [D, F]; ``activation``: None or "gelu".

    Reference: csrc/transformer/ds_transformer_cuda.cpp:147 (the fused
    LN→QKV prologue) and gelu_kernels.cu (the fused bias+GELU epilogue).
    """
    if activation not in (None, "gelu"):
        raise ValueError(f"unknown activation {activation!r}")
    lead = x.shape[:-1]
    d = x.shape[-1]
    f = w.shape[1]
    n = 1
    for s in lead:
        n *= s
    if d % 128 or f % 128 or _fit_rows(block_rows, n) == 0:
        raise ValueError(f"shapes (n={n}, d={d}, f={f}) not tileable with "
                         f"block_rows={block_rows} — gate with "
                         "ln_matmul_ok()")
    interpret = not on_tpu() if interpret is None else interpret
    out = _ln_matmul(x.reshape(n, d), gamma, beta, w, bias, float(eps),
                     activation, block_rows, interpret)
    return out.reshape(*lead, f)


# ---------------------------------------------------------------------------
# Shadow parameter modules
# ---------------------------------------------------------------------------
# Declare parameters with the exact names/shapes/initializers of
# ``nn.LayerNorm`` / ``nn.Dense`` WITHOUT applying the op, so a model can
# route through :func:`ln_matmul` while keeping its checkpointed parameter
# tree (and TP partition-rule regexes) byte-identical to the unfused
# build. flax folds param RNG over the module path, not declaration
# order, so initial values are bit-identical too.

import flax.linen as nn  # noqa: E402  (kernels above stay flax-free)


class LNParams(nn.Module):
    """``nn.LayerNorm``'s parameter tree ({scale, bias}), params only."""

    features: int

    @nn.compact
    def __call__(self):
        scale = self.param("scale", nn.initializers.ones,
                           (self.features,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros,
                          (self.features,), jnp.float32)
        return scale, bias


class DenseParams(nn.Module):
    """``nn.Dense``'s parameter tree ({kernel, bias}), params only."""

    in_features: int
    features: int

    @nn.compact
    def __call__(self):
        kernel = self.param("kernel", nn.linear.default_kernel_init,
                            (self.in_features, self.features), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros,
                          (self.features,), jnp.float32)
        return kernel, bias
