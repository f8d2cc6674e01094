"""Explicit all-to-all MoE dispatch/combine — expert parallelism as a
real collective, not an XLA resharding accident.

The einsum and scatter paths in moe/layer.py keep the dense GShard
formulation and leave the token<->expert layout change to XLA's SPMD
partitioner: whatever all-to-all (or worse, all-gather) it decides to
emit is invisible, unmeasurable and unsteerable. This module is the
explicit path: a ``shard_map`` manual region over the data-like + expert
axes (``parallel.mesh.moe_dispatch_axes``) in which every token shard
builds per-destination send buffers and exchanges them with a real
``jax.lax.all_to_all`` over the ``expert`` axis — the same manual-region
collective idiom as comm/grad_sync.py's DCN stage, and the layout the
reference implements with torch.distributed all_to_all over its expert
process groups.

Semantics are EXACTLY the oracle's (moe/layer.py einsum path): routing —
choice/prob/pos/keep — is computed globally outside the region, so the
capacity-drop regime, combine weights and load-balance loss are
bit-comparable across all three dispatch modes. Inside the region:

- Tokens are sharded over data-like x expert (the input arrives sharded
  over data-like only and replicated over ``expert``; the entry reshard
  is a free dynamic-slice). Each grid cell holds a distinct token block
  and ``e_local = E / n_expert_shards`` experts.
- Dispatch: each cell scatters its kept tokens into a flat
  ``[E*C + 1, D]`` buffer at global slot ``choice*C + pos`` (dropped
  tokens hit the sentinel row — built with zeros + scatter, never
  ``jnp.pad``, which partial-manual regions reject), reshapes
  destination-major to ``[n_shards, e_local*C, D]`` and all-to-alls it
  over ``expert``. Receivers SUM over sources: global queue positions
  are unique per (expert, pos), so source contributions land in disjoint
  rows and the sum is a union.
- Experts run on their local ``[e_local, C, D]`` block with the local
  weight slices (in_spec ``P(expert, None, None)``). The expert FFN has
  no biases, so the zero rows contributed by peer columns' tokens stay
  exactly zero through it — each data column combines only its own
  tokens and no cross-column reduction is needed.
- Combine: outputs ride back masked by an ownership map (a 0/1 buffer
  scattered at the same slots and exchanged alongside the payload), the
  source cell flattens the returns destination-major — which IS global
  expert order — and gathers ``prob*keep``-weighted rows per k-round.

The buffers span the GLOBAL capacity ``C`` (positions are global), so a
cell's working set is ``O(E*C*D)`` — the price of exact oracle parity;
a per-column capacity would shrink it but change the drop regime.
"""

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel.mesh import (EXPERT_AXIS, axes_size,
                                         get_default_mesh,
                                         moe_dispatch_axes)
from jax import shard_map


def _resolve_mesh(mesh):
    if mesh is None:
        mesh = get_default_mesh()
    if mesh is None:
        raise ValueError(
            "MoE alltoall dispatch needs a mesh: pass MoEConfig.mesh or "
            "register one (parallel.mesh.set_default_mesh — the engine "
            "does this at construction)")
    return mesh


def alltoall_dispatch(h, rounds, w_in, w_out, *, capacity: int, dtype,
                      mesh=None):
    """Dispatch ``h`` [T, D] through the stacked experts with an explicit
    all-to-all over the ``expert`` axis. ``rounds`` is moe.layer._route's
    output (global routing); ``w_in`` [E, D, F] / ``w_out`` [E, F, D] are
    the stacked fp32 expert params. Returns y [T, D] in ``dtype``,
    bit-comparable with the einsum oracle's combine."""
    mesh = _resolve_mesh(mesh)
    tokens, d = h.shape
    e = int(w_in.shape[0])
    n_shards = int(mesh.shape.get(EXPERT_AXIS, 1))
    if e % n_shards:
        raise ValueError(
            f"num_experts {e} must divide by the expert mesh axis "
            f"({n_shards})")
    e_local = e // n_shards
    axes = moe_dispatch_axes(mesh)
    cells = axes_size(mesh.shape, axes)
    if tokens % cells:
        raise ValueError(
            f"token count {tokens} must divide by the dispatch grid "
            f"({cells} = {axes} shards) for the manual region")

    # Global routing, stacked [k, T] so the region's in_specs stay flat.
    choice = jnp.stack([r.choice for r in rounds])
    prob = jnp.stack([r.prob for r in rounds])
    pos = jnp.stack([r.pos for r in rounds])
    keep = jnp.stack([r.keep for r in rounds])
    k = len(rounds)
    sentinel = e * capacity

    def body(h_loc, choice, prob, pos, keep, w_in_loc, w_out_loc):
        # [k, T_cell] routing for this cell's tokens; slots are GLOBAL
        # (choice is the global expert id, pos the global queue position).
        slot = jnp.where(keep, choice * capacity + pos, sentinel)
        buf_x = jnp.zeros((e * capacity + 1, d), dtype)
        buf_o = jnp.zeros((e * capacity + 1,), dtype)
        for i in range(k):
            buf_x = buf_x.at[slot[i]].add(h_loc)
            buf_o = buf_o.at[slot[i]].add(keep[i].astype(dtype))
        # Destination-major: row block j holds shard j's experts.
        send_x = buf_x[:-1].reshape(n_shards, e_local * capacity, d)
        send_o = buf_o[:-1].reshape(n_shards, e_local * capacity)
        recv_x = jax.lax.all_to_all(send_x, EXPERT_AXIS, split_axis=0,
                                    concat_axis=0, tiled=False)
        recv_o = jax.lax.all_to_all(send_o, EXPERT_AXIS, split_axis=0,
                                    concat_axis=0, tiled=False)
        # Sources occupy disjoint global queue positions: sum == union.
        xin = jnp.sum(recv_x, axis=0).reshape(e_local, capacity, d)
        hmid = jnp.einsum("ecd,edf->ecf", xin, w_in_loc.astype(dtype))
        hmid = jax.nn.gelu(hmid, approximate=True)
        xout = jnp.einsum("ecf,efd->ecd", hmid, w_out_loc.astype(dtype))
        # Return trip: each source gets back exactly the slots it owns.
        back = recv_o[..., None] * xout.reshape(1, e_local * capacity, d)
        ret = jax.lax.all_to_all(back, EXPERT_AXIS, split_axis=0,
                                 concat_axis=0, tiled=False)
        # Shard-major flatten IS global expert order: row choice*C+pos.
        flat = jnp.concatenate(
            [ret.reshape(e * capacity, d), jnp.zeros((1, d), dtype)],
            axis=0)
        y = jnp.zeros_like(h_loc)
        for i in range(k):
            w = (prob[i] * keep[i]).astype(dtype)
            y = y + w[:, None] * flat[slot[i]]
        return y

    route = P(None, axes)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(axes, None), route, route, route, route,
                  P(EXPERT_AXIS, None, None), P(EXPERT_AXIS, None, None)),
        out_specs=P(axes, None),
        axis_names=set(axes), check_vma=False)
    # jit so the eager path works too (old jax's partial-manual
    # shard_map only lowers under jit; inside an outer jit this inlines).
    return jax.jit(fn)(h, choice, prob, pos, keep, w_in, w_out)


def modeled_dispatch_bytes_ici(*, num_experts: int, capacity: int,
                               hidden: int, dtype, mesh=None,
                               k: int = 1) -> int:
    """Modeled per-layer ICI bytes of the explicit exchange: the payload
    buffer rides the wire twice (dispatch + combine) and the ownership
    map once, with remote fraction ``(n-1)/n`` per cell, summed over the
    whole dispatch grid. Static — the same number for every step, priced
    from shapes alone (the counterpart of grad_sync's modeled_bytes).
    Returns 0 when the expert axis is unsharded (the exchange is local)
    or no mesh is registered; the implicit einsum/scatter reshards are
    XLA's business and deliberately not modeled."""
    del k
    if mesh is None:
        mesh = get_default_mesh()
    if mesh is None:
        return 0
    n_shards = int(mesh.shape.get(EXPERT_AXIS, 1))
    if n_shards <= 1:
        return 0
    cells = axes_size(mesh.shape, moe_dispatch_axes(mesh))
    itemsize = jnp.dtype(dtype).itemsize
    ec = num_experts * capacity
    per_cell = (2 * ec * hidden + ec) * itemsize * (n_shards - 1) / n_shards
    return int(cells * per_cell)
