"""Dropless expert layer that is told which experts it holds.

The layer routes every token over ALL published experts (sigmoid scores,
a selection-only correction bias, top-k, renormalised and scaled: the
``noaux_tc`` router of DeepSeek-V3 and GLM-4.x), and computes the part of
the result that the experts held HERE give: experts ``[first, first +
held)`` of the ``n_routed`` the router scores. Weights are normalised
over all ``k`` chosen experts, held or not, so that the parts of all the
shares add up to the uncut layer. A shared expert, which every chip
computes alike, is added whole. On one chip there is no exchange and
nothing stands in for one: what the absent experts would have added is
absent.

No capacity, no dropped assignment, no auxiliary loss. Shapes are static
although the number of held assignments depends on the data:

- the ``k * T`` assignments are sorted by held expert (assignments to an
  expert that is not held sort last), and the token rows are gathered in
  that order into ONE buffer of ``k * T`` rows. That is the smallest
  buffer that can never drop: every token may choose ``k`` held experts.
  A buffer bounded per expert (``[held, C, D]``) would need ``C = T`` to
  be as safe, ``held / k`` times the rows;
- the held experts' SwiGLU is three ``jax.lax.ragged_dot`` calls over
  that buffer with the group sizes counted from the routing: on a TPU
  XLA lowers each to its own grouped-matmul kernel, which visits the row
  tiles of the groups and not the unused tail, so the work follows the
  held assignments and not the buffer;
- the buffer's rows go back by the inverse permutation, and each token
  sums its ``k`` rows with its weights (zero for an expert not held).
  Dispatch and combine are gathers in both directions (``_sorted_rows``,
  ``_unsorted_rows``: the backward of one is the other's forward), never
  a scatter-add, which a TPU serialises.

``DroplessMoE.__call__(x)`` returns ``(y, counters)``; the counters are
float32 scalars a model hands to the engine under ``step_counters``.

Two more shapes of the same layer, for Nemotron-H's latent experts
(``models/nemotron_h.py``), which is also where the layer is called at
decode and prefill of the serving engine:

- ``latent_size > 0``: the routed experts read and write a latent of that
  width, ``x W_in`` (``latent_in [D, L]``), and their summed result goes
  back through ``latent_out [L, D]``. The router still reads the full
  width ``x``, and so does the shared expert. ``latent_out`` is linear, so
  the parts of the chips still add up.
- ``activation="relu2"``: an expert is ``W_down relu(W_up x)^2`` with no
  gate (``experts_gate`` and ``shared_gate`` do not exist).
- ``__call__(x, live=...)``: rows that are not ``live`` (a dead slot of
  the decode batch, the padding of a prompt's bucket) are routed nowhere:
  they take no row of any group, so the grouped matmul's work and the
  counters follow the rows that are there. With ``live`` the counters
  are the int32 ``SERVING_COUNTERS`` the serving programs return beside
  the token.
"""

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.parallel.mesh import EXPERT_AXIS
from deepspeed_tpu.telemetry.tracer import device_scope


@dataclass(frozen=True)
class DroplessMoEConfig:
    hidden_size: int
    expert_intermediate: int
    n_routed_experts: int               # the router's width: all of them
    n_held_experts: int                 # whose weights live on this chip
    first_held_expert: int = 0
    experts_per_token: int = 4
    shared_intermediate: int = 0        # 0 -> no shared expert
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    dtype: Any = jnp.bfloat16
    latent_size: int = 0                # > 0: routed experts on a latent
    activation: str = "swiglu"          # or "relu2": no gate
    # float32: the latent's way out and the shared expert's last matmul
    # hand over their float32 accumulators and are summed unrounded, for a
    # caller that adds the result to its residual before rounding once
    out_dtype: Any = None
    # the shared expert's two matmuls read their float32 inputs (``x`` as
    # it is handed in, then ``relu(.)^2``) as TWO bfloat16 halves, hi + lo:
    # 16 bits of mantissa where one rounding keeps 8 (``_in_two_halves``)
    shared_two_pass: bool = False

    def __post_init__(self):
        if self.activation not in ("swiglu", "relu2"):
            raise ValueError(f"activation {self.activation!r}: 'swiglu' or "
                             f"'relu2'")
        if self.shared_two_pass and (self.activation != "relu2"
                                     or self.out_dtype is None):
            raise ValueError("shared_two_pass is for the relu2 shared expert "
                             "with a float32 out_dtype")
        last = self.first_held_expert + self.n_held_experts
        if not 0 <= self.first_held_expert < last <= self.n_routed_experts:
            raise ValueError(
                f"held experts [{self.first_held_expert}, {last}) are not "
                f"among the {self.n_routed_experts} the router scores")
        if not 1 <= self.experts_per_token <= self.n_routed_experts:
            raise ValueError(f"experts_per_token {self.experts_per_token} "
                             f"of {self.n_routed_experts} experts")


def route(x: jax.Array, router_kernel: jax.Array, correction_bias: jax.Array,
          *, k: int, scaling_factor: float = 1.0, norm_topk_prob: bool = True
          ) -> Tuple[jax.Array, jax.Array]:
    """``(chosen [T, k] int32, weights [T, k] float32)``. Scores are
    ``sigmoid(x W)`` in float32; the bias moves the CHOICE (top-k of
    ``s + b``) and never the weight, which is the score itself,
    renormalised over the k chosen and scaled."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_kernel.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + correction_bias.astype(jnp.float32), k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), weights * scaling_factor


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _sorted_rows(x, order, inverse, k):
    """``x[order // k]``: the token row of every assignment, in sorted
    order. Backward: a gather by the inverse permutation and a sum over
    each token's k assignments."""
    return x[order // k]


def _sorted_rows_fwd(x, order, inverse, k):
    return x[order // k], (inverse,)


def _sorted_rows_bwd(k, res, g):
    (inverse,) = res
    return g[inverse].reshape(-1, k, g.shape[-1]).sum(1), None, None


_sorted_rows.defvjp(_sorted_rows_fwd, _sorted_rows_bwd)


@jax.custom_vjp
def _unsorted_rows(rows, order, inverse):
    """The sorted buffer's rows back in assignment order."""
    return rows[inverse]


def _unsorted_rows_fwd(rows, order, inverse):
    return rows[inverse], (order,)


def _unsorted_rows_bwd(res, g):
    (order,) = res
    return g[order], None, None


_unsorted_rows.defvjp(_unsorted_rows_fwd, _unsorted_rows_bwd)


SERVING_COUNTERS = ("held_assignments", "experts_touched", "held_rows_max")


def held_expert_part(x, chosen, weights, w_gate, w_up, w_down, *,
                     first_held: int, live=None
                     ) -> Tuple[jax.Array, Dict[str, Any]]:
    """The held experts' part of the layer's result for ``x [T, D]``:
    ``sum over chosen e in [first, first + held) of w_e expert_e(x)``,
    and the counters of this call. ``w_gate`` / ``w_up`` are
    ``[held, D, F]``, ``w_down`` is ``[held, F, D]``; an expert is SwiGLU,
    or with ``w_gate`` None ``w_down relu(w_up x)^2``. Rows that are not
    ``live`` (``[T]`` bool) choose no expert; with ``live`` the counters
    are the int32 ``SERVING_COUNTERS``."""
    t, d = x.shape
    k = chosen.shape[1]
    held = w_up.shape[0]
    with device_scope("moe_dispatch"):
        local = chosen.reshape(-1) - first_held
        is_held = (local >= 0) & (local < held)
        if live is not None:
            is_held = is_held & jnp.repeat(live, k)
        local = jnp.where(is_held, local, held)     # not held: sorts last
        order = jnp.argsort(local, stable=True).astype(jnp.int32)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=jnp.int32))
        sizes = (local[:, None] == jnp.arange(held)[None, :]).sum(
            0, dtype=jnp.int32)
        in_a_group = (jnp.arange(order.shape[0]) < sizes.sum())[:, None]
        rows = jnp.where(in_a_group, _sorted_rows(x, order, inverse, k), 0)
    with device_scope("moe_experts"):
        # Rows past the last group are no expert's: the kernel leaves
        # them as it finds them, so they are zeroed on the way in and out.
        dot = partial(jax.lax.ragged_dot, group_sizes=sizes)
        if w_gate is None:
            up = jnp.where(in_a_group, dot(rows, w_up), 0)
            mid = jnp.square(jax.nn.relu(up))
        else:
            gate = jnp.where(in_a_group, dot(rows, w_gate), 0)
            up = jnp.where(in_a_group, dot(rows, w_up), 0)
            mid = jax.nn.silu(gate) * up
        out = jnp.where(in_a_group, dot(mid, w_down), 0)
    with device_scope("moe_combine"):
        per_choice = _unsorted_rows(out, order, inverse).reshape(t, k, d)
        is_held = is_held.reshape(t, k)
        held_weights = jnp.where(is_held, weights, 0.0)
        y = jnp.einsum("tkd,tk->td", per_choice,
                       held_weights.astype(per_choice.dtype),
                       preferred_element_type=jnp.float32).astype(x.dtype)
    if live is not None:
        return y, {"held_assignments": sizes.sum(),
                   "experts_touched": (sizes > 0).sum(dtype=jnp.int32),
                   "held_rows_max": sizes.max()}
    f32 = jnp.float32
    counters = {
        "held_assignments_per_token": sizes.sum().astype(f32) / t,
        "held_rows_max": sizes.max().astype(f32),
        "held_rows_mean": sizes.astype(f32).mean(),
        "no_held_expert_share":
            1.0 - is_held.any(-1).astype(f32).mean(),
    }
    return y, counters


def _in_two_halves(dense: nn.Module, x: jax.Array, dtype) -> jax.Array:
    """``dense(x)`` for a float32 ``x`` and a ``dense`` that takes
    bfloat16 and hands back float32: ``x`` goes in as ``hi`` (its upper 16
    bits: sign, exponent, 7 bits of mantissa, which IS a bfloat16) and
    ``lo = round(x - hi)``, stacked as ``2 T`` rows of ONE matmul (the
    weights are read once; at decode that is what the time is), and the two
    halves of the result are added. What is lost of ``x`` is under
    ``2^-16`` of it where ``x.astype(bfloat16)`` alone loses up to
    ``2^-9``. ``hi`` is cut by a mask on the bits and not by
    ``x.astype(bfloat16).astype(float32)``: the TPU's compiler drops such a
    round trip as excess precision it is allowed to keep, ``lo`` is then 0
    and the second pass reads nothing (my chip run, PR 35)."""
    if dtype != jnp.bfloat16:           # float32 weights: nothing to halve
        return dense(x.astype(dtype))
    x = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    both = dense(jnp.concatenate([hi, x - hi], axis=0).astype(dtype))
    return both[:x.shape[0]] + both[x.shape[0]:]


class DroplessMoE(nn.Module):
    """Input ``[B, S, D]`` -> ``([B, S, D], counters)``."""

    cfg: DroplessMoEConfig

    @nn.compact
    def __call__(self, x, live=None):
        cfg = self.cfg
        b, s, d = x.shape
        f, held, dt = cfg.expert_intermediate, cfg.n_held_experts, cfg.dtype
        init = nn.initializers.normal(0.02)
        flat = x.reshape(b * s, d)
        h = flat.astype(dt)
        gated = cfg.activation == "swiglu"
        dense = partial(nn.Dense, use_bias=False, dtype=dt, kernel_init=init)

        router = self.param("router", init, (d, cfg.n_routed_experts),
                            jnp.float32)
        # Selection-only. The published rule that moves it lies outside
        # the gradient (its gradient is exactly zero), so it stays where
        # it starts: at zero.
        bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                          (cfg.n_routed_experts,), jnp.float32)
        with device_scope("moe_route"):
            # the router reads x as it is handed in: a caller that keeps
            # its norm's float32 output spares the scores one rounding
            chosen, weights = route(
                flat, router, bias, k=cfg.experts_per_token,
                scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=cfg.norm_topk_prob)

        # what the routed experts read and write: x itself, or a latent
        wide = cfg.latent_size or d
        rows = h
        if cfg.latent_size:
            with device_scope("moe_dispatch"):
                rows = dense(wide, name="latent_in")(h)
        w_gate = (self.param("experts_gate", init, (held, wide, f),
                             jnp.float32).astype(dt) if gated else None)
        w_up = self.param("experts_up", init, (held, wide, f), jnp.float32)
        w_down = self.param("experts_down", init, (held, f, wide),
                            jnp.float32)
        y, counters = held_expert_part(
            rows, chosen, weights, w_gate, w_up.astype(dt),
            w_down.astype(dt), first_held=cfg.first_held_expert,
            live=None if live is None else live.reshape(b * s))
        last = dense if cfg.out_dtype is None else partial(
            dense, dot_general=partial(
                jax.lax.dot_general, preferred_element_type=cfg.out_dtype))
        if cfg.latent_size:
            with device_scope("moe_combine"):
                y = last(d, name="latent_out")(y)

        if cfg.shared_intermediate:
            with device_scope("moe_shared"):
                gate = (dense(cfg.shared_intermediate, name="shared_gate")(h)
                        if gated else None)
                if cfg.shared_two_pass:
                    # the shared expert is most of what this layer adds to
                    # the stream, and relu^2 doubles a relative error: its
                    # one-pass roundings are what flips most near ties of
                    # the routers after it (PERF.md section 6, PR 35)
                    up = _in_two_halves(
                        last(cfg.shared_intermediate, name="shared_up"),
                        flat, dt)
                    y = y + _in_two_halves(last(d, name="shared_down"),
                                           jnp.square(nn.relu(up)), dt)
                else:
                    up = dense(cfg.shared_intermediate, name="shared_up")(h)
                    mid = (nn.silu(gate) * up if gated
                           else jnp.square(nn.relu(up)))
                    y = y + last(d, name="shared_down")(mid)
        return y.reshape(b, s, d), counters


def dropless_partition_rules() -> Tuple[Tuple[str, Tuple], ...]:
    """The held experts' leading axis over the ``expert`` mesh axis; the
    router and the shared expert replicated."""
    return (
        (r".*experts_(gate|up|down)$", (EXPERT_AXIS, None, None)),
        (r".*(router|e_score_correction_bias)$", None),
        (r".*(shared_(gate|up|down)|latent_(in|out))/kernel$", None),
    )
