"""Mamba-2's selective state-space recurrence (Dao & Gu 2024, "SSD"), in
``jax.numpy`` / ``lax``: a chunked scan for a whole prompt and one step
for decode, plus the short causal convolution in front of it.

Per head ``h`` (of ``H``, each ``P`` channels wide) and position ``t``::

    state_t = exp(dt_t A) state_{t-1} + dt_t u_t (x) B_t     [P, N]
    y_t     = state_t C_t + D u_t                            [P]

``A < 0`` and ``D`` are per head, ``dt > 0`` per head and position, ``B``
and ``C`` ``[G, N]`` per position, a group serving ``H / G`` heads. The
state is float32 wherever it lives, and so is everything that goes into
it or comes out of it: ``u``, ``B`` and ``C`` are widened on entry and the
scan's matmuls run at full float32 precision (they are small: 13 GFLOP a
layer for a 2048-token prompt), so the state a prompt leaves in its slot
is the recurrence's own and not a bfloat16 rendering of it.

:func:`ssm_scan` never loops over positions. A sequence is cut into
chunks of ``chunk`` positions; inside a chunk every output is a masked
``[chunk, chunk]`` matmul (the decays between two positions of a chunk are
``exp`` of a difference of cumulative sums), each chunk's contribution to
the state is one more matmul, and only the ``S / chunk`` chunk states are
carried by a ``lax.scan``. A position whose ``dt`` is 0 leaves the state
as it is and adds nothing to it: that is how a right-padded prompt is
given (``dt`` zeroed past its length), and how a length that is no
multiple of ``chunk`` is padded here.
"""

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.telemetry.tracer import device_scope

F32 = jnp.float32


@device_scope("ssm_conv")
def causal_conv(x: jax.Array, kernel: jax.Array, bias: jax.Array,
                tail: Optional[jax.Array] = None,
                length: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution over the last ``K`` positions of every
    channel. ``x [B, S, C]``, ``kernel [K, C]`` (row ``K - 1`` weighs the
    current position), ``bias [C]``; ``tail [B, K - 1, C]`` the
    un-convolved rows before ``x`` (zeros when absent: a sequence's
    start). Returns ``(y [B, S, C]`` in float32, the new tail``)``: the
    last ``K - 1`` rows of the input, or with ``length`` (a traced scalar,
    a right-padded prompt's true length) the ``K - 1`` rows before
    position ``length``."""
    b, s, c = x.shape
    k = kernel.shape[0]
    if tail is None:
        tail = jnp.zeros((b, k - 1, c), x.dtype)
    padded = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    y = bias.astype(F32)
    for j in range(k):
        y = y + padded[:, j:j + s].astype(F32) * kernel[j].astype(F32)
    start = s if length is None else length
    return y, jax.lax.dynamic_slice_in_dim(padded, start, k - 1, axis=1)


def _heads_of_groups(x: jax.Array, heads: int) -> jax.Array:
    """``[..., G, N] -> [..., H, N]``: every head its group's row."""
    return jnp.repeat(x, heads // x.shape[-2], axis=-2)


@device_scope("ssm_scan")
def ssm_scan(u: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, d: jax.Array, *, chunk: int = 128,
             state: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over a whole sequence. ``u [B, S, H, P]``, ``dt
    [B, S, H]`` float32 (after softplus; 0 marks a position that is not
    there), ``a [H]``, ``b`` / ``c [B, S, G, N]``, ``d [H]``, ``state
    [B, H, P, N]`` float32 or None (zeros). Returns ``(y [B, S, H, P]``
    float32, the state after the last position``)``."""
    bsz, s, h, p = u.shape
    n = b.shape[-1]
    q = min(chunk, s)
    pad = -s % q
    if pad:
        widen = lambda x: jnp.pad(
            x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        u, dt, b, c = widen(u), widen(dt), widen(b), widen(c)
    nc = (s + pad) // q
    cut = lambda x: x.reshape((bsz, nc, q) + x.shape[2:])
    uc = cut(u.astype(F32))
    bc = cut(_heads_of_groups(b, h).astype(F32))
    cc = cut(_heads_of_groups(c, h).astype(F32))
    dtc = cut(dt.astype(F32))                                # [B, nc, Q, H]
    log_decay = jnp.cumsum(dtc * a.astype(F32), axis=2)      # <= 0, falling
    total = log_decay[:, :, -1]                              # [B, nc, H]
    dot = lambda spec, x, y: jnp.einsum(
        spec, x, y, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=F32)
    # dt_j u_j, what position j adds to the state before any decay
    du = dtc[..., None] * uc                                 # [B, nc, Q, H, P]

    # inside a chunk: y_i = sum_{j <= i} exp(l_i - l_j) (C_i . B_j) du_j
    cb = dot("bcihn,bcjhn->bchij", cc, bc)
    gap = log_decay.transpose(0, 1, 3, 2)                    # [B, nc, H, Q]
    gap = gap[..., :, None] - gap[..., None, :]              # l_i - l_j
    seen = jnp.tril(jnp.ones((q, q), bool))
    weights = jnp.where(seen, jnp.exp(jnp.where(seen, gap, 0.0)) * cb, 0.0)
    y = dot("bchij,bcjhp->bcihp", weights, du)

    # what a chunk adds to the state, decayed to the chunk's end
    to_end = jnp.exp(total[:, :, None] - log_decay)          # [B, nc, Q, H]
    added = dot("bcjhp,bcjhn->bchpn", to_end[..., None] * du, bc)

    def carry(h_prev, step):
        total_c, added_c = step
        return (jnp.exp(total_c)[..., None, None] * h_prev + added_c, h_prev)

    h0 = (jnp.zeros((bsz, h, p, n), F32) if state is None
          else state.astype(F32))
    last, before = jax.lax.scan(
        carry, h0, (total.transpose(1, 0, 2), added.transpose(1, 0, 2, 3, 4)))
    before = before.transpose(1, 0, 2, 3, 4)                 # [B, nc, H, P, N]

    # the state a chunk starts from, read at every position of the chunk
    y = y + jnp.exp(log_decay)[..., None] * dot(
        "bcihn,bchpn->bcihp", cc, before)
    y = y.reshape(bsz, nc * q, h, p)[:, :s]
    return y + d.astype(F32)[:, None] * u[:, :s].astype(F32), last


@device_scope("ssm_step")
def ssm_step(u: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, d: jax.Array, state: jax.Array,
             live: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """One position. ``u [B, H, P]``, ``dt [B, H]`` float32, ``b`` / ``c
    [B, G, N]``, ``state [B, H, P, N]`` float32. Returns ``(y [B, H, P]``
    float32, the new state``)``. A row that is not ``live`` (``[B]`` bool)
    keeps its state. Elementwise in float32 row by row, so a row's result
    is the same bits whatever the other rows hold."""
    h = u.shape[1]
    dt = dt.astype(F32)
    bh = _heads_of_groups(b, h).astype(F32)                  # [B, H, N]
    ch = _heads_of_groups(c, h).astype(F32)
    uf = u.astype(F32)
    decay = jnp.exp(dt * a.astype(F32))[..., None, None]
    new = decay * state + (dt[..., None] * uf)[..., None] * bh[:, :, None, :]
    y = (new * ch[:, :, None, :]).sum(-1) + d.astype(F32)[:, None] * uf
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, state)
    return y, new
