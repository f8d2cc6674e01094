"""Fused blockwise Adam(W) update — Pallas TPU kernel, kernel tier
round 2 for the training hot loop.

``FusedAdam.update`` is a whole-tree elementwise chain that XLA lowers to
~10 HBM-bound ops per leaf: each of master params, grads and both moments
is read and written across several fused loops, so the optimizer step
pays the parameter bytes multiple times. This kernel is the reference's
``multi_tensor_adam.cu`` capability TPU-native (SURVEY §2.9): one Pallas
pass per flat block reads ``(p, g, m, v)`` once, runs the full Adam(W)
recurrence in fp32 registers, and writes ``(p', m', v')`` — and
optionally the compute-dtype (bf16) cast of ``p'`` — in a single HBM
round-trip.

The math is **bit-for-bit the ``FusedAdam.update`` leaf chain** (same op
order, fp32 throughout), so the XLA chain stays the parity oracle; the
traced scalars (lr and the two bias corrections, functions of the traced
step counter) ride as a tiny broadcast VMEM tile. Leaves are flattened,
padded to lane tiles and processed as ``(rows, 128)`` blocks — the
blockwise layout, not the tree structure, is what the kernel sees, so
every ZeRO tier's (possibly sharded) master partition goes through the
same program.

``interpret=True`` (automatic off-TPU) runs the same kernel through the
Pallas interpreter so CPU tier-1 parity tests cover the real kernel
arithmetic.
"""

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deepspeed_tpu.ops.adam.fused_adam import AdamState, FusedAdam
from deepspeed_tpu.utils.platform import on_tpu

__all__ = ["fused_adam_leaf", "fused_adam_apply", "fused_update_cost"]

_LANE = 128
# Max block rows per grid step; multiple of 16 so an optional bf16 cast
# output tiles on the sublane dim too (f32 needs 8, bf16 needs 16).
_MAX_ROWS = 256


def fused_adam_update_kernel(sc_ref, p_ref, g_ref, m_ref, v_ref, *out_refs,
                             b1: float, b2: float, eps: float, wd: float,
                             adamw: bool, cast: bool):
    if cast:
        p_out, m_out, v_out, c_out = out_refs
    else:
        p_out, m_out, v_out = out_refs
        c_out = None
    lr = sc_ref[0, 0]
    bc1 = sc_ref[0, 1]
    bc2 = sc_ref[0, 2]
    p = p_ref[...]
    g = g_ref[...].astype(jnp.float32)
    # Same op order as FusedAdam.update's leaf chain — the XLA chain is
    # the parity oracle and the test bound is ulp-level, not atol-level.
    if wd != 0.0 and not adamw:
        g = g + wd * p
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * jnp.square(g)
    denom = jnp.sqrt(v / bc2) + eps
    update = (m / bc1) / denom
    if wd != 0.0 and adamw:
        update = update + wd * p
    pn = p - lr * update
    p_out[...] = pn
    m_out[...] = m
    v_out[...] = v
    if cast:
        c_out[...] = pn.astype(c_out.dtype)


def fused_adam_leaf(p: jax.Array, g: jax.Array, m: jax.Array, v: jax.Array,
                    scalars: jax.Array, *, b1: float, b2: float, eps: float,
                    weight_decay: float, adamw_mode: bool,
                    cast_dtype: Optional[Any] = None,
                    interpret: Optional[bool] = None):
    """One leaf's fused update. ``p``/``m``/``v`` fp32, ``g`` any float
    dtype (cast in kernel, like the XLA chain). ``scalars``: [8, 128]
    fp32 broadcast tile with ``(lr, bc1, bc2)`` at ``[0, :3]``. Returns
    ``(p', m', v')`` in the leaf's shape — plus ``p'.astype(cast_dtype)``
    when ``cast_dtype`` is set (the compute-param cast rides the same
    HBM round-trip)."""
    interpret = not on_tpu() if interpret is None else interpret
    shape = p.shape
    n = int(p.size)
    if n == 0:
        outs = (p, m, v)
        if cast_dtype is not None:
            outs += (p.astype(cast_dtype),)
        return outs

    rows = -(-n // _LANE)
    rows = -(-rows // 16) * 16              # sublane tile (bf16-safe)
    br = min(_MAX_ROWS, rows)
    rows = -(-rows // br) * br              # grid covers exactly
    padded = rows * _LANE

    def flat(x, dtype):
        x = x.reshape(-1).astype(dtype)
        return jnp.pad(x, (0, padded - n)).reshape(rows, _LANE)

    pf = flat(p, jnp.float32)
    gf = flat(g, g.dtype)
    mf = flat(m, jnp.float32)
    vf = flat(v, jnp.float32)

    cast = cast_dtype is not None
    kernel = functools.partial(fused_adam_update_kernel, b1=float(b1),
                               b2=float(b2), eps=float(eps),
                               wd=float(weight_decay),
                               adamw=bool(adamw_mode), cast=cast)
    blk = lambda i: (i, 0)
    out_shape = [jax.ShapeDtypeStruct((rows, _LANE), jnp.float32)] * 3
    out_specs = [pl.BlockSpec((br, _LANE), blk)] * 3
    if cast:
        out_shape.append(jax.ShapeDtypeStruct((rows, _LANE), cast_dtype))
        out_specs.append(pl.BlockSpec((br, _LANE), blk))
    outs = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((8, _LANE), lambda i: (0, 0)),   # scalar tile
            pl.BlockSpec((br, _LANE), blk),
            pl.BlockSpec((br, _LANE), blk),
            pl.BlockSpec((br, _LANE), blk),
            pl.BlockSpec((br, _LANE), blk),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        name="fused_adam",
        interpret=interpret,
    )(scalars, pf, gf, mf, vf)
    return tuple(o.reshape(-1)[:n].reshape(shape) for o in outs)


def scalar_tile(lr, bc1, bc2) -> jax.Array:
    """Pack the traced step scalars into the kernel's [8, 128] fp32
    broadcast tile (one VMEM tile; re-read per grid step, negligible
    next to the parameter stream)."""
    vals = jnp.stack([jnp.asarray(lr, jnp.float32),
                      jnp.asarray(bc1, jnp.float32),
                      jnp.asarray(bc2, jnp.float32)])
    return jnp.zeros((8, _LANE), jnp.float32).at[0, :3].set(vals)


def fused_adam_apply(optimizer: FusedAdam, grads: Any, state: AdamState,
                     params: Any, lr=None,
                     cast_dtype: Optional[Any] = None):
    """Drop-in for ``FusedAdam.update`` over the whole tree, one fused
    kernel launch per leaf. Returns ``(new_params, new_state)`` — or
    ``(new_params, new_state, compute_params)`` when ``cast_dtype`` is
    set. Signature/semantics mirror ``FusedAdam.update`` so
    ``_make_apply_step`` can substitute it at the single computation
    site."""
    lr = optimizer.lr if lr is None else lr
    step = state.step + 1
    b1, b2 = optimizer.beta1, optimizer.beta2
    if optimizer.bias_correction:
        bc1 = 1.0 - b1 ** step.astype(jnp.float32)
        bc2 = 1.0 - b2 ** step.astype(jnp.float32)
    else:
        bc1 = jnp.float32(1.0)
        bc2 = jnp.float32(1.0)
    sc = scalar_tile(lr, bc1, bc2)

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.exp_avg)
    flat_v = treedef.flatten_up_to(state.exp_avg_sq)
    outs = [fused_adam_leaf(p, g, m, v, sc, b1=b1, b2=b2, eps=optimizer.eps,
                            weight_decay=optimizer.weight_decay,
                            adamw_mode=optimizer.adamw_mode,
                            cast_dtype=cast_dtype)
            for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([o[0] for o in outs])
    new_m = treedef.unflatten([o[1] for o in outs])
    new_v = treedef.unflatten([o[2] for o in outs])
    new_state = AdamState(step=step, exp_avg=new_m, exp_avg_sq=new_v)
    if cast_dtype is not None:
        return new_p, new_state, treedef.unflatten([o[3] for o in outs])
    return new_p, new_state


def fused_update_cost(params: Any) -> Tuple[float, float]:
    """Analytic ``(flops, bytes)`` of one fused update over ``params`` —
    XLA's ``cost_analysis`` cannot see inside a Pallas custom call, so
    the engine books these at its goodput ``set_flops`` site to keep the
    roofline verdict and ``devicetime/mfu_measured`` honest under the
    fused path. Per element: ~12 flops (the Adam recurrence) and 28
    bytes (read p/g/m/v + write p'/m'/v', fp32)."""
    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    return 12.0 * n, 28.0 * n
