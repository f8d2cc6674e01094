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

:func:`ssm_step` is one position of every slot; on a TPU, with the slots'
live mask, it is :func:`ssm_step_live`, a Pallas kernel that reads and
writes the state of the live slots alone, in place.

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

from functools import lru_cache, partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.telemetry.tracer import device_scope
from deepspeed_tpu.utils.logging import logger
from deepspeed_tpu.utils.platform import on_tpu

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
    is the same bits whatever the other rows hold.

    With ``live`` on a TPU the step is :func:`ssm_step_live`, which moves
    the live rows' state alone (and gives a dead row's ``y`` as 0);
    elsewhere every row is computed and a dead one's new state dropped."""
    if live is not None and on_tpu():
        _log_live_step(state.shape)
        return ssm_step_live(u, dt, a, b, c, d, state, live)
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


# -- the decode step over the live slots: Pallas TPU kernel ``ssm_step_live``

# A block is one slot's whole state. Measured on a v5e
# (tools/probe_ssm_step.py; PERF.md section 6), device us of one layer's
# step at nemotron3s-serve-chat's shapes, 128 slots of 128 heads x 64 x
# 128 float32 (4 MiB a slot), all of the step's ops; "least" is the live
# slots' state read and written at 819 GB/s. "As here": a group's rows
# written out, the groups in a loop, with a block of 128 heads (the whole
# slot) and, in a form since dropped, of 64 and 32; "written out": all 64
# rows of a slot; "rolled": every row in a loop; "first form": the state
# in its own layout, a head's dt u broadcast across N from one lane, y
# summed across the lanes:
#
#   slots   least   XLA's   as here, heads a block  written   rolled   first
#   alive           step     128     64     32      out (128)  (128)    form
#     1      10.2   1666       63     72     98         62       76      53
#    16     163.9   1666      250    262    284        247      390     280
#    38     389.2   1666      530    539    556        527      850     614
#    80     819.4   1666     1062   1066   1077       1060     1728    1250
#   128    1311.0   1666     1672   1670   1672       1669     2731    1977
#
# The kernel alone takes 12.7 us a live slot (81% of the 10.2 its bytes
# need) and 33 us for the rest (the visit list, dt u and the decay laid
# out by lanes, y's mask); XLA's step moves all 128 slots at 79%. The
# first form took 15.2 us a slot, every row rolled 21. A whole slot a block
# is the fastest, or level, at every count.


def row_heads(p: int, per_group: int) -> int:
    """The heads of one row of a block: as many as fill 128 lanes (two at
    ``P`` 64) where they lie in one group, else a group's."""
    r = 128 // p if 128 % p == 0 else 0
    return r if r and per_group % r == 0 else per_group


@lru_cache(maxsize=None)
def _log_live_step(shape: Tuple[int, ...]) -> None:
    """Say once per shape that the step takes the kernel."""
    slots, heads, p, n = shape
    logger.info(f"ssm_step over live slots -> pallas (slots {slots}, heads "
                f"a block {heads}, bytes a slot {heads * p * n * 4})")


def slot_visits(live: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``(slots, n)``: the live slots in slot order, then the last of them
    again to the end (slot 0 where none is live), as int32 ``[B]``, and
    how many are live (int32 ``[1]``). A comparison against the running
    count, as ``grouped_matmul``'s visit list."""
    b = live.shape[0]
    upto = jnp.cumsum(live.astype(jnp.int32))          # live slots through s
    n = upto[-1]
    visit = jnp.minimum(jnp.arange(b, dtype=jnp.int32), n - 1)
    slots = (upto[None, :] <= visit[:, None]).sum(1, dtype=jnp.int32)
    return jnp.where(n > 0, slots, 0), n.reshape(1)


def _kernel(slots_ref, n_ref, decay_ref, x_ref, b_ref, c_ref, state_ref,
            y_ref, new_ref, *, per_group):
    # one live slot, a row of heads at a time: the row's state
    # [heads x P, N] turned to [N, heads x P], where each lane's decay and
    # dt u are a row to broadcast down and B and C a column to broadcast
    # across, y a sum down the sublanes; the new state turned back. A visit
    # past the live slots computes and writes nothing.
    n = n_ref[0]

    @pl.when(pl.program_id(0) < n)
    def _():
        rows, width = x_ref.shape
        heads, p, nn = state_ref.shape
        r_heads = heads // rows
        per_row = per_group // r_heads                  # rows of a group

        def group(k, carry):
            g = pl.ds(k, 1)
            b_col = jnp.broadcast_to(b_ref[g, :], (width, nn)).T
            c_col = jnp.broadcast_to(c_ref[g, :], (width, nn)).T

            def row(i, carry):
                r = k * per_row + i
                mine = pl.ds(r * r_heads, r_heads)
                s_t = state_ref[mine].reshape(width, nn).T      # [N, width]
                new_t = (s_t * decay_ref[pl.ds(r, 1), :]
                         + b_col * x_ref[pl.ds(r, 1), :])
                new_ref[mine] = new_t.T.reshape(r_heads, p, nn)
                y_ref[pl.ds(r, 1), :] = jnp.sum(new_t * c_col, axis=0,
                                                keepdims=True)
                return carry
            # a group's rows written out, the groups in a loop: as fast as
            # every row written out, and an eighth of its trace and lowering
            return jax.lax.fori_loop(0, per_row, row, carry, unroll=True)
        jax.lax.fori_loop(0, rows // per_row, group, 0)

    @pl.when(n == 0)
    def _():
        # no slot live: the one slot the grid holds goes back as it came
        new_ref[...] = state_ref[...]


@partial(jax.jit, static_argnames=("interpret",))
def ssm_step_live(u: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                  c: jax.Array, d: jax.Array, state: jax.Array,
                  live: jax.Array, *, interpret: bool = False
                  ) -> Tuple[jax.Array, jax.Array]:
    """:func:`ssm_step` with ``live``, reading and writing the state of the
    live slots alone: the new state is written into ``state``'s buffer
    (donated by the caller, it is updated in place) and a dead slot's is
    never read or written. ``y`` of a dead row is 0. The arithmetic is
    :func:`ssm_step`'s, in float32, a row's result its own. A grid step
    is one slot, its whole state a block."""
    bsz, h, p, n = state.shape
    g = b.shape[1]
    r = row_heads(p, h // g)
    rows = lambda x: x.reshape(bsz, h // r, r * p)     # r heads x P lanes
    uf = u.astype(F32)
    dt = dt.astype(F32)
    slots, count = slot_visits(live)
    # past the live slots a visit holds the last live one's slot: no bytes
    lanes = pl.BlockSpec((None, h // r, r * p),
                         lambda v, s, cnt: (s[v], 0, 0))
    groups = pl.BlockSpec((None, g, n), lambda v, s, cnt: (s[v], 0, 0))
    slab = pl.BlockSpec((None, h, p, n), lambda v, s, cnt: (s[v], 0, 0, 0))
    # a slot's state in and out, each twice (the next streams in, the last
    # streams out), and room for the kernel's own values
    vmem = 4 * h * p * n * 4 + 8 * 2**20
    y, new = pl.pallas_call(
        partial(_kernel, per_group=h // g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz,),
            in_specs=[lanes, lanes, groups, groups, slab],
            out_specs=[lanes, slab]),
        out_shape=[jax.ShapeDtypeStruct((bsz, h // r, r * p), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        # inputs counted with the two prefetched: the state is the 7th
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(16 * 2**20, vmem)),
        interpret=interpret,
        name="ssm_step_live",
    )(slots, count, rows(jnp.repeat(jnp.exp(dt * a.astype(F32)), p, axis=-1)),
      rows(dt[..., None] * uf), b.astype(F32), c.astype(F32), state)
    y = y.reshape(bsz, h, p) + d.astype(F32)[:, None] * uf
    return jnp.where(live[:, None, None], y, 0.0), new
