"""Pipelined execution over the ``pipe`` mesh axis — TPU-native.

The reference drives pipeline parallelism from the host: a Python scheduler
(`pipe/schedule.py`) dispatches per-tick instructions whose Send/Recv are
NCCL broadcasts between adjacent ranks (`pipe/engine.py:1209`,
`pipe/p2p.py:31`). On TPU that design would serialise dispatch; instead the
WHOLE pipelined step is one jitted program: a ``shard_map`` manual over the
``pipe`` axis ONLY (`axis_names={'pipe'}`) runs every stage in SPMD, a
``lax.scan`` over schedule ticks moves microbatch activations between
neighbouring stages with ``lax.ppermute`` over ICI, and reverse-mode AD of
that scan yields the backward pipeline automatically (ppermute transposes
to the reverse shift) — the moral equivalent of the 1F1B instruction tape,
scheduled by XLA. Because ``data``/``model``/``sequence`` stay AUTO axes,
ZeRO data-sharding and Megatron tensor parallelism inside each block keep
working through GSPMD — the pp × tp × dp composition of the reference's 3D
topology (pipe/topology.py:246) without hand-built process groups.

Model layout contract (the ``PipelineModule`` analogue, pipe/module.py:87):
embedding and loss head live OUTSIDE the pipelined segment (computed under
plain GSPMD, which also ties input/output embeddings for free — the
reference needs TiedLayerSpec + a dedicated allreduce group for this,
module.py:73); the pipelined body is a stack of L structurally identical
blocks, stacked on a leading dim that is sharded over ``pipe`` so each
stage owns L/S consecutive blocks. Per-microbatch side inputs (attention
masks) travel as ``aux``, indexed by the schedule so stage s at tick t sees
the aux of the microbatch it is actually processing (m = t − s).
"""

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.parallel.mesh import PIPE_AXIS
from deepspeed_tpu.utils.platform import on_tpu


def stack_blocks(block_params_list):
    """Stack per-block param pytrees into one pytree with leading dim L."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *block_params_list)


def pipeline_spec(blocks_params) -> Any:
    """PartitionSpec tree sharding the stacked block dim over ``pipe``."""
    return jax.tree_util.tree_map(
        lambda x: P(PIPE_AXIS, *([None] * (x.ndim - 1))), blocks_params)


def default_skip_bubble() -> bool:
    """Whether fill/drain ticks skip their compute (``lax.cond`` on the
    per-rank validity predicate — the reference's 1F1B executes no bubble
    instructions by construction, pipe/schedule.py:182; here the cond
    saves the (S−1)/(M+S−1) bubble energy). Resolved at trace time:
    ``DSTPU_SKIP_BUBBLE`` = ``1``/``0`` forces it; default = TPU only.
    On XLA:CPU the cond composes with ZeRO-1's data-axis apply
    collectives into a deterministic second-step rendezvous DEADLOCK
    (pinned round 5 — ``tools/repro_cond_ppermute_deadlock.py``; ZeRO-0
    + cond runs fine and is CI-exercised, docs/ISSUES.md #1)."""
    import os

    v = os.environ.get("DSTPU_SKIP_BUBBLE", "")
    if v in ("0", "1"):
        return v == "1"
    return on_tpu()


# Cache of jitted pipelined programs: rebuilding shard_map+jit per call would
# recompile on every eager invocation. Keyed by everything that changes the
# traced program except array shapes (jit handles shape retracing itself).
_PIPELINE_CACHE = {}


def pipeline_apply_manual(block_fn: Callable,
                          stage_blocks: Any,
                          x_all: jax.Array,
                          aux_all: Any,
                          keys: Optional[jax.Array],
                          *,
                          stages: int,
                          num_microbatches: int,
                          remat_blocks: bool = True,
                          broadcast_output: bool = True,
                          pass_layer_idx: bool = False,
                          block_aux: bool = False,
                          skip_bubble: Optional[bool] = None,
                          rank: Optional[jax.Array] = None):
    """The manual-region pipeline body: call INSIDE a shard_map already
    manual over ``pipe`` (``stage_blocks`` leaves carry the local
    ``[L/S, ...]`` shard; ``x_all`` ``[M, mb, ...]`` is pipe-replicated).

    With ``broadcast_output`` (default) the last stage's microbatch outputs
    are psum-broadcast to every pipe rank in fp32; with it off the raw
    last-stage slice is returned and ONLY rank ``stages-1`` holds valid
    data — callers that mask per-rank themselves (the 1-bit pipeline
    engine) use this to keep gradient provenance per stage.

    With ``stages == 1`` this degenerates to a scan over blocks per
    microbatch (no collectives emitted).

    ``pass_layer_idx``: call ``block_fn(p, h, a, k, global_layer_idx)``
    — the GLOBAL block index (stage offset + local scan index), which
    per-layer schedules like Progressive Layer Drop need (the flat
    families read it from the Python loop counter; the reference threads
    PLD kwargs through engine.forward into each layer,
    /root/reference/deepspeed/runtime/engine.py:1085).

    ``block_aux``: block_fn returns ``(h, aux_scalar)`` (e.g. a MoE
    load-balance loss). The return value grows a second element: the
    fp32 aux total summed over every (microbatch, layer) — bubble ticks
    masked out, psum'd over ``pipe`` — which the caller folds into the
    loss (divide by M for the per-microbatch mean). Reference analogue:
    DeepSpeed-MoE's aux losses ride the module outputs through the
    pipeline the same way."""
    M = num_microbatches
    if skip_bubble is None:
        skip_bubble = default_skip_bubble()
    fn = jax.checkpoint(block_fn) if remat_blocks else block_fn
    n_local = jax.tree_util.tree_leaves(stage_blocks)[0].shape[0]

    def stage_apply(h, a, key, base):
        # Apply this stage's L/S blocks in order (scan keeps the program
        # small; blocks are structurally identical by contract).
        def body(carry, xs):
            h, aux = carry
            p, i = xs
            k = None if key is None else jax.random.fold_in(key, i)
            args = (p, h, a, k) + ((base + i,) if pass_layer_idx else ())
            y = fn(*args)
            if block_aux:
                y, a_l = y
                aux = aux + a_l.astype(jnp.float32)
            return (y, aux), None

        (h, aux), _ = jax.lax.scan(body, (h, jnp.float32(0.0)),
                                   (stage_blocks, jnp.arange(n_local)))
        return h, aux

    def aux_at(idx):
        if aux_all is None:
            return None
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, idx, axis=0,
                                                   keepdims=False), aux_all)

    if stages == 1:
        def per_mb(mb, i):
            key = None if keys is None else jax.random.fold_in(keys, i)
            return stage_apply(mb, aux_at(i), key, 0)

        if aux_all is None:
            out, auxs = jax.vmap(per_mb)(x_all, jnp.arange(M))
        else:
            # aux indexing is data-dependent per microbatch — use scan
            def body(_, mi):
                mb, i = mi
                return None, per_mb(mb, i)

            _, (out, auxs) = jax.lax.scan(body, None, (x_all, jnp.arange(M)))
        return (out, jnp.sum(auxs)) if block_aux else out

    T = M + stages - 1
    if rank is None:
        # Fine under a fully-manual caller; the partial-manual
        # pipeline_apply path passes a sharded-iota rank instead because
        # axis_index there lowered to a PartitionId HLO the SPMD
        # partitioner rejected when this was written.
        rank = jax.lax.axis_index(PIPE_AXIS)
    shift = [(i, (i + 1) % stages) for i in range(stages)]

    def tick(carry, t):
        buf, aux_acc = carry
        inject = jax.lax.dynamic_index_in_dim(
            x_all, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
        h = jnp.where(rank == 0, inject, buf)
        # Stage `rank` processes microbatch m = t - rank at tick t;
        # fill/drain ticks (m outside [0, M)) carry garbage that no
        # valid tick ever consumes (producer (r-1, t-1) has the same m
        # as consumer (r, t)). Wall-clock is the critical-path bound
        # T·stage_time either way (the ppermute keeps ranks in lockstep;
        # tests/test_pipeline.py::test_step_time_approaches_bubble_
        # bound), so skip_bubble saves the (S-1)/(M+S-1) bubble ENERGY:
        # default on for TPU, off for XLA:CPU where the cond composes
        # with ZeRO-1 apply collectives into a second-step rendezvous
        # deadlock (pinned: tools/repro_cond_ppermute_deadlock.py,
        # docs/ISSUES.md #1; the ZeRO-0 cond path is CI-exercised by
        # TestBubbleSkip).
        m = t - rank
        a = aux_at(jnp.clip(m, 0, M - 1))
        k = (None if keys is None
             else jax.random.fold_in(jax.random.fold_in(keys, t), rank))
        valid = jnp.logical_and(m >= 0, m < M)
        if skip_bubble:
            # Fill/drain ticks carry garbage no valid tick consumes —
            # skip their compute entirely (the reference's 1F1B executes
            # no bubble instructions by construction, pipe/schedule.py).
            # Per-rank divergence is fine under the manual shard_map: the
            # ppermute below still runs on every rank in lockstep.
            y, aux_y = jax.lax.cond(
                valid,
                lambda: stage_apply(h, a, k, rank * n_local),
                lambda: (h, jnp.float32(0.0)))
        else:
            y, aux_y = stage_apply(h, a, k, rank * n_local)
        # Bubble ticks' aux contribution must not pollute the loss.
        aux_acc = aux_acc + jnp.where(valid, aux_y, 0.0)
        buf = jax.lax.ppermute(y, PIPE_AXIS, shift)
        return (buf, aux_acc), y

    (_, aux_total), ys = jax.lax.scan(
        tick, (jnp.zeros_like(x_all[0]), jnp.float32(0.0)), jnp.arange(T))
    # Last stage produced microbatch m at tick m + S - 1.
    out = jax.lax.dynamic_slice_in_dim(ys, stages - 1, M, axis=0)
    if block_aux:
        # Each rank accumulated its own blocks' aux; the psum yields the
        # total over every (microbatch, layer), identical on all ranks.
        aux_total = jax.lax.psum(aux_total, PIPE_AXIS)
    if not broadcast_output:
        return (out, aux_total) if block_aux else out
    # Hand the result to every pipe rank (the reference broadcasts the
    # final-stage loss similarly, pipe/engine.py:453); activations of
    # non-final stages are discarded by the where. The psum runs in fp32:
    # a bf16 all-reduce under a partial-manual shard_map crashes the XLA
    # CPU backend ("Invalid binary instruction opcode copy"), and fp32
    # summation is the numerically safer choice anyway.
    masked = jnp.where(rank == stages - 1, out,
                       jnp.zeros_like(out)).astype(jnp.float32)
    out = jax.lax.psum(masked, PIPE_AXIS).astype(out.dtype)
    return (out, aux_total) if block_aux else out


def pipeline_apply(block_fn: Callable,
                   blocks_params: Any,
                   x: jax.Array,
                   mesh: Mesh,
                   *,
                   aux: Any = None,
                   rng: Optional[jax.Array] = None,
                   num_microbatches: Optional[int] = None,
                   remat_blocks: bool = True,
                   pass_layer_idx: bool = False,
                   block_aux: bool = False,
                   skip_bubble: Optional[bool] = None):
    """Run the stacked-block pipeline over microbatches.

    block_fn(params_one_block, x, aux_or_None, rng_or_None) -> x
    blocks_params: pytree, leaves [L, ...] — L % pipe_size == 0
    x: [M, mb, ...] microbatched activations (M = num_microbatches)
    aux: optional pytree of per-microbatch side inputs, leaves [M, ...]
         (e.g. attention masks) — handed to every block of the stage
         processing that microbatch
    rng: PRNG key for per-block dropout (None ≡ deterministic)

    Returns [M, mb, ...] last-stage outputs. With pipe_size == 1 this
    degenerates to a scan over blocks (no collectives emitted). Only the
    ``pipe`` axis is manual in the shard_map — tensor-parallel specs on the
    block params and data sharding on the batch keep working via GSPMD.
    """
    stages = mesh.shape.get(PIPE_AXIS, 1)
    L = jax.tree_util.tree_leaves(blocks_params)[0].shape[0]
    if L % stages:
        raise ValueError(f"{L} blocks not divisible by {stages} pipeline stages")
    M = num_microbatches if num_microbatches is not None else x.shape[0]
    if x.shape[0] != M:
        raise ValueError(f"x has {x.shape[0]} microbatches, expected {M}")

    if skip_bubble is None:
        skip_bubble = default_skip_bubble()
    if stages == 1:
        return pipeline_apply_manual(block_fn, blocks_params, x, aux, rng,
                                     stages=1, num_microbatches=M,
                                     remat_blocks=remat_blocks,
                                     pass_layer_idx=pass_layer_idx,
                                     block_aux=block_aux,
                                     skip_bubble=skip_bubble)

    compute_dtype = x.dtype

    def pipelined(stage_blocks, x_all, aux_all, keys, rank_arr):
        # stage_blocks leaves: [L/S, ...] (pipe dim stripped; other axes
        # remain GSPMD-auto); x_all: [M, mb, ...] replicated across pipe.
        # x crosses the shard_map boundary in fp32 (see psum note in
        # pipeline_apply_manual: the cotangent of a pipe-replicated input
        # is a psum, which must not run in bf16 under a partial-manual
        # shard_map). rank_arr is a pipe-sharded iota, so its single local
        # element IS this shard's stage index — the axis_index equivalent
        # that survives old-jax partial-manual lowering.
        return pipeline_apply_manual(
            block_fn, stage_blocks, x_all.astype(compute_dtype), aux_all,
            keys, stages=stages, num_microbatches=M,
            remat_blocks=remat_blocks, broadcast_output=True,
            pass_layer_idx=pass_layer_idx, block_aux=block_aux,
            skip_bubble=skip_bubble, rank=rank_arr[0])

    blocks_treedef = jax.tree_util.tree_structure(blocks_params)
    blocks_ndims = tuple(l.ndim for l in jax.tree_util.tree_leaves(blocks_params))
    aux_treedef = (None if aux is None
                   else jax.tree_util.tree_structure(aux))
    key = (block_fn, mesh, stages, M, remat_blocks, rng is None,
           blocks_treedef, blocks_ndims, aux_treedef, compute_dtype,
           pass_layer_idx, block_aux, skip_bubble)
    if key not in _PIPELINE_CACHE:
        def entry(blocks_arg, x_arg, aux_arg, rng_arg):
            return shard_map(
                pipelined,
                mesh=mesh,
                in_specs=(pipeline_spec(blocks_arg), P(), P(), P(),
                          P(PIPE_AXIS)),
                out_specs=(P(), P()) if block_aux else P(),
                axis_names={PIPE_AXIS},
                check_vma=False,
            )(blocks_arg, x_arg, aux_arg, rng_arg,
              jnp.arange(stages, dtype=jnp.int32))

        # Partial-manual shard_map only traces under jit; the jit also makes
        # repeated eager calls hit the compile cache.
        _PIPELINE_CACHE[key] = jax.jit(entry)
    return _PIPELINE_CACHE[key](blocks_params, x.astype(jnp.float32), aux, rng)
