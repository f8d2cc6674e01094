"""Hierarchical quantized gradient sync — the explicit grad-sync strategy.

Today the engine leaves all gradient reduction to implicit pjit resharding
in full precision: ``micro_step_inner`` constrains the accumulator to the
ZeRO grad specs and XLA emits whatever collectives make the shardings
true. On a single slice that is optimal; on multi-slice topologies the
same lowering drags full-precision gradient traffic over the slow
inter-slice DCN axis every step. ZeRO++ (arXiv 2306.10209) and EQuARX
(arXiv 2506.17615) show the fix: make the hierarchy explicit and compress
only the slow hop.

The strategy here (``docs/PERFORMANCE.md``):

1. **Bucket**: each micro-step's grad tree is flattened into fixed-size
   flat buckets (``comm.bucket_mb``) so collective launches amortize and
   the DCN stage works on a handful of large transfers instead of one op
   per leaf.
2. **ICI stage**: every bucket is cast to the ICI reduction dtype
   (``communication_data_type``, default the accumulator's native dtype)
   and constrained to the intra-slice ``data`` axis — XLA lowers that to
   a reduce-scatter over fast ICI, and the gradient accumulator carries
   only the 1/data-size scattered shard (the reference's IPG-bucket
   memory shape, stage2.py:701).
3. **DCN stage** (once per optimizer step): the scattered shard is
   all-reduced across slices over the manual ``dcn`` axis with blockwise
   int8 symmetric quantization (``comm/quantize.py``) — all_to_all the
   codes+scales, dequantize-sum-requantize, all_gather back — or a
   bf16 / fp32 passthrough. Wire bytes drop ~4x (int8) vs fp32.
4. **Unbucket**: the reduced buckets are sliced back into the grad tree
   and handed to the unchanged optimizer apply.

Execution model: the fwd/bwd + ICI stage run inside a ``shard_map``
manual over *only* the ``dcn`` axis (every other axis stays GSPMD-auto,
so ZeRO placement and tensor-parallel specs keep composing); the DCN
stage runs in a second region manual over ``{dcn, data}`` — the same
partial-manual shape the 1-bit optimizers already use — because this
jax's partitioner only supports ``all_to_all`` when the data-like axes
are all manual. Leaves whose grad specs shard over non-data axes
(pipeline blocks, tensor-parallel weights) cannot join a flat bucket;
they fall back to a per-leaf fp32 ``psum`` over ``dcn`` (a bf16 all-
reduce under a partial-manual shard_map crashes this XLA CPU backend —
see the psum note in parallel/pipe/pipeline.py).

``hierarchical: off`` (the default) bypasses this module entirely: the
engine builds the exact pre-existing step functions, bit-identical to
main. ``on`` with fp32 passthrough tracks the implicit path to float
reduction-ordering (~1 ulp — an explicit slice-wise sum cannot reproduce
the implicit single-collective summation order bit-for-bit; the parity
rungs in tests/test_dcn.py pin the bound).

**Overlap mode** (``comm.overlap_grad_sync``, default ``auto`` ≡ on
whenever the hierarchical sync engages — ROADMAP item 1, T3 arXiv
2401.16677 / The Big Send-off arXiv 2504.18658): the same wire protocol
rescheduled so gradient communication overlaps compute instead of
serializing after it, along two axes (docs/PERFORMANCE.md "Overlapped
gradient sync"):

1. *Intra-backward ICI overlap* — buckets are leaf-granular and packed
   in reverse traversal order (the order gradients become ready during
   backward), so each bucket's reduce-scatter depends only on its own
   leaves and the latency-hiding scheduler can run bucket k's scatter
   concurrently with layer k-1's backward. In-tree models additionally
   plant :func:`comm.overlap.grad_sync_boundary` markers on their layer
   stacks: a custom_vjp hook per layer group whose backward rule emits
   the group's data-axis scatter constraint *between* the layer
   backwards in the traced program (not all trailing).
2. *Cross-microstep DCN overlap* — instead of one cross-slice
   all-reduce of the accumulated shard at the GAS boundary, microstep
   k's bucket contributions are quantized and dispatched over DCN
   immediately, double-buffered so exactly one reduce is in flight
   while microstep k+1's fwd/bwd runs; the reduced scattered shards
   accumulate at the jit level and only the final microstep's reduce is
   exposed. DCN wire bytes grow by the GAS factor — traded for hiding
   nearly all of them — and the modeled ``comm/exposed_frac`` accounts
   for the overlap (:meth:`GradSyncPlan.modeled_exposed_seconds`).

Overlap off keeps the PR-4 single-boundary schedule byte-for-byte.
"""

import math
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.quantize import (dequantize_blockwise,
                                         modeled_wire_bytes,
                                         quantize_blockwise, rel_from_parts,
                                         roundtrip_error_parts)
from deepspeed_tpu.parallel.mesh import (DATA_AXIS, DCN_AXIS,
                                         axes_size as mesh_axes_size)
from jax import shard_map
from deepspeed_tpu.telemetry.tracer import device_scope
from deepspeed_tpu.utils.logging import log_dist

_MB = 1 << 20

_COMM_DTYPES = {
    None: None,
    "fp32": jnp.float32, "float32": jnp.float32,
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
    "fp16": jnp.float16, "float16": jnp.float16,
}


def comm_dtype_from_config(name: Optional[str]):
    """Map the ``communication_data_type`` config string to a jnp dtype
    (None ≡ the accumulator's native dtype). Validation happens at config
    parse; this keeps one authoritative mapping."""
    if name is not None and name not in _COMM_DTYPES:
        raise ValueError(
            f"communication_data_type '{name}' not in "
            f"{sorted(k for k in _COMM_DTYPES if k)}")
    return _COMM_DTYPES.get(name)


def resolve_hierarchical(comm_cfg, mesh: Mesh, *,
                         needs_local_grads: bool = False,
                         sparse_gradients: bool = False,
                         pipe_stages: int = 1) -> Tuple[bool, str]:
    """Resolve the ``comm.hierarchical`` tri-state against the runtime
    shape. Returns (enabled, reason). ``on`` raises on genuinely
    incompatible configurations instead of silently degrading; ``auto``
    quietly resolves off for them."""
    from deepspeed_tpu.config.config import ConfigError

    mode = comm_cfg.hierarchical
    dcn = mesh.shape.get(DCN_AXIS, 1)
    blockers = []
    if needs_local_grads:
        blockers.append(
            "1-bit optimizers run their own error-compensated compressed "
            "collective over dcn — the hierarchical grad sync would "
            "double-compress the same hop")
    if sparse_gradients:
        blockers.append(
            "the sparse embedding-grad exchange reduces over the data-like "
            "axes inside its VJP, which cannot trace under the dcn-manual "
            "region the hierarchical sync needs")
    if pipe_stages > 1:
        blockers.append(
            "pipeline stages > 1 compile their own manual region "
            "(parallel/pipe/pipeline.py) and shard_map regions do not "
            "nest on this jax")
    if mode == "off":
        return False, "comm.hierarchical=off"
    if mode == "on":
        if blockers:
            raise ConfigError(
                f"comm.hierarchical=on is incompatible with this "
                f"configuration: {blockers[0]}")
        if dcn <= 1:
            log_dist("comm.hierarchical=on with a single slice (dcn=1): "
                     "the DCN stage is degenerate — quantization cost "
                     "without traffic savings", ranks=[0])
        return True, "comm.hierarchical=on"
    if mode != "auto":
        raise ConfigError(
            f"comm.hierarchical must be auto|on|off, got '{mode}'")
    if dcn <= 1:
        return False, "auto: single slice (no dcn axis to compress)"
    if blockers:
        return False, f"auto: {blockers[0]}"
    return True, f"auto: dcn={dcn} hierarchical mesh"


def resolve_overlap(comm_cfg) -> bool:
    """Resolve ``comm.overlap_grad_sync`` (auto|on|off, default auto) to
    a bool. Overlap is a property of the hierarchical sync's schedule,
    so it only ever takes effect when :func:`resolve_hierarchical`
    engaged the strategy — the incompatible configurations (1-bit,
    pipeline stages > 1, sparse embedding grads) are already excluded
    there and never reach a plan."""
    from deepspeed_tpu.config.config import ConfigError

    mode = str(getattr(comm_cfg, "overlap_grad_sync", "auto")).lower()
    if mode == "off":
        return False
    if mode in ("auto", "on"):
        return True
    raise ConfigError(
        f"comm.overlap_grad_sync must be auto|on|off, got '{mode}'")


def _spec_axes(spec) -> set:
    axes = set()
    for entry in tuple(spec):
        parts = entry if isinstance(entry, tuple) else (entry,)
        axes.update(a for a in parts if a is not None)
    return axes


class GradSyncPlan:
    """A compiled-shape plan binding the strategy to one grad tree.

    Built once per engine at step-construction time; every method that
    touches arrays is pure jnp and traces inside the jitted step. Methods
    marked *stage-1* must be called inside the ``manual={dcn}`` region;
    ``dcn_sync`` wraps its own ``manual={dcn, data}`` region and is
    called at the jit level, on the dcn-stacked buckets stage 1 returns.
    """

    def __init__(self, comm_cfg, mesh: Mesh, grad_template: Any,
                 grad_specs: Any, acc_dtype, ici_dtype=None, gas: int = 1,
                 measure_quant_error: bool = False, overlap: bool = False):
        self.mesh = mesh
        self.dcn_size = int(mesh.shape.get(DCN_AXIS, 1))
        self.data_size = int(mesh.shape.get(DATA_AXIS, 1))
        self.bits = int(comm_cfg.dcn_quant_bits)
        self.block = int(comm_cfg.quant_block_size)
        # Nominal link bandwidths for the modeled device-time attribution
        # (modeled_exposed_seconds / comm/exposed_frac). One source of
        # truth with the config defaults (getattr covers hand-built cfg
        # objects without the fields).
        from deepspeed_tpu.config import constants as _C
        self.ici_gbps = float(getattr(comm_cfg, "ici_gbps",
                                      _C.COMM_ICI_GBPS_DEFAULT))
        self.dcn_gbps = float(getattr(comm_cfg, "dcn_gbps",
                                      _C.COMM_DCN_GBPS_DEFAULT))
        self.acc_dtype = acc_dtype
        self.ici_dtype = ici_dtype if ici_dtype is not None else acc_dtype
        # Numerics observatory (telemetry/numerics.py): when on, the DCN
        # stage also returns per-bucket RTNE round-trip error of the wire
        # payload vs the fp32 shard. Only the lossy tiers measure — the
        # fp32 passthrough has nothing to attribute. Off (the default)
        # the shard_map body is byte-for-byte the pre-numerics one.
        self.measure_quant = (bool(measure_quant_error)
                              and int(comm_cfg.dcn_quant_bits) in (8, 16))
        # Micro-steps per optimizer step THIS plan's region runs: each one
        # reduce-scatters every bucket over ICI, so the modeled ICI bytes
        # scale with it (the pipe engine's single pipelined fwd/bwd is 1).
        self.gas = int(gas)

        leaves, self.treedef = jax.tree_util.tree_flatten(grad_template)
        spec_leaves = self.treedef.flatten_up_to(grad_specs)
        self.num_leaves = len(leaves)
        self.leaf_shapes = [tuple(l.shape) for l in leaves]
        # math.prod(()) == 1 covers scalars; a zero-dim leaf really does
        # contribute 0 elements (forcing it to 1 would desync the bucket
        # layout from the concatenated flat buffer).
        self.leaf_sizes = [int(math.prod(s)) for s in self.leaf_shapes]
        self.bucketed_idx: List[int] = []
        self.fallback_idx: List[int] = []
        for i, (leaf, spec) in enumerate(zip(leaves, spec_leaves)):
            # leaves may be jax arrays or ShapeDtypeStructs (the offload
            # tier plans against an abstract template).
            float_leaf = jnp.issubdtype(leaf.dtype, jnp.floating)
            # Axes of size 1 shard nothing — a pipe=1 block spec or a
            # model=1 TP spec must not exile the whole model to the
            # uncompressed fallback.
            real_axes = {a for a in _spec_axes(spec)
                         if mesh.shape.get(a, 1) > 1}
            if float_leaf and real_axes <= {DATA_AXIS}:
                self.bucketed_idx.append(i)
            else:
                self.fallback_idx.append(i)
        self.fallback_specs = [spec_leaves[i] for i in self.fallback_idx]
        # Constraint specs usable INSIDE the dcn-manual region: values
        # there are slice-local, so any (pathological) dcn entry in a
        # fallback spec must drop — naming a manual axis in an inner
        # constraint is an error.
        self.fallback_inner_specs = [
            self._strip_dcn(s) for s in self.fallback_specs]

        self.total_elems = sum(self.leaf_sizes[i] for i in self.bucketed_idx)
        self.fallback_elems = sum(self.leaf_sizes[i]
                                  for i in self.fallback_idx)
        # Every bucket is padded to a multiple of data*dcn*block so the
        # scattered shard splits evenly into dcn sub-chunks of whole
        # quantization blocks.
        self.overlap = bool(overlap)
        align = self.data_size * self.dcn_size * self.block
        itemsize = jnp.dtype(self.ici_dtype).itemsize
        if self.overlap:
            # Leaf-granular buckets packed in REVERSE traversal order —
            # the order gradients become ready during backward — so
            # bucket k's reduce-scatter depends only on its own leaves
            # (the readiness-ordered dispatch ROADMAP item 1 asks for).
            # A leaf never straddles buckets; an oversized leaf is its
            # own bucket.
            target = max(align, int(comm_cfg.bucket_mb * _MB / itemsize))
            self.bucket_leaf_idx: List[List[int]] = []
            cur: List[int] = []
            cur_sz = 0
            for i in reversed(self.bucketed_idx):
                sz = self.leaf_sizes[i]
                if cur and cur_sz and cur_sz + sz > target:
                    self.bucket_leaf_idx.append(cur)
                    cur, cur_sz = [], 0
                cur.append(i)
                cur_sz += sz
            if cur:
                self.bucket_leaf_idx.append(cur)
            self.bucket_padded = [
                max(align,
                    (sum(self.leaf_sizes[i] for i in b) + align - 1)
                    // align * align)
                for b in self.bucket_leaf_idx]
            self.num_buckets = len(self.bucket_leaf_idx)
            # Back-compat scalar (describe(), jaxpr size assertions):
            # the largest bucket.
            self.bucket_elems = max(self.bucket_padded, default=0)
            self.padded_elems = sum(self.bucket_padded)
        else:
            # PR-4 layout: fixed-size buckets split from one contiguous
            # flat buffer (leaves may straddle boundaries).
            raw = max(align, int(comm_cfg.bucket_mb * _MB / itemsize))
            self.bucket_elems = ((raw + align - 1) // align) * align
            if self.total_elems:
                self.num_buckets = max(
                    1, (self.total_elems + self.bucket_elems - 1)
                    // self.bucket_elems)
                # Shrink a single bucket to the (aligned) payload: tiny
                # models must not pad to a full bucket_mb of zeros.
                if self.num_buckets == 1:
                    self.bucket_elems = (
                        (self.total_elems + align - 1) // align) * align
            else:
                self.num_buckets = 0
            self.padded_elems = self.num_buckets * self.bucket_elems
            self.bucket_leaf_idx = []
            self.bucket_padded = [self.bucket_elems] * self.num_buckets
        # Top-level param group -> all-bucketed? — consulted by the
        # ICI overlap hook (comm/overlap.py): a group with any fallback
        # leaf (non-data sharding) cannot take a flat data constraint.
        self._group_bucketed = {}
        try:
            paths = jax.tree_util.tree_flatten_with_path(grad_template)[0]
        except Exception:  # noqa: BLE001 — exotic pytrees: hooks just no-op
            paths = []
        groups: dict = {}
        for idx, (path, _) in enumerate(paths):
            if not path:
                continue
            k = path[0]
            key = getattr(k, "key", None)
            if key is None:
                key = getattr(k, "name", None)
            if key is None:
                continue
            groups.setdefault(str(key), []).append(idx)
        bucketed_set = set(self.bucketed_idx)
        self._group_bucketed = {
            k: all(i in bucketed_set for i in v) for k, v in groups.items()}
        self._data_sharding = NamedSharding(mesh, P(DATA_AXIS))
        self._dcn_sync_fn = None
        self._dcn_overlap_fn = None

    @staticmethod
    def _strip_dcn(spec) -> P:
        entries = []
        for entry in tuple(spec):
            parts = entry if isinstance(entry, tuple) else (entry,)
            kept = tuple(a for a in parts
                         if a is not None and a != DCN_AXIS)
            entries.append(kept if len(kept) > 1
                           else (kept[0] if kept else None))
        return P(*entries)

    # ------------------------------------------------------------------
    # stage 1 (inside the manual={dcn} region)
    # ------------------------------------------------------------------
    def zero_fallback(self) -> List[jax.Array]:
        return [jnp.zeros(self.leaf_shapes[i], self.acc_dtype)
                for i in self.fallback_idx]

    def zero_buckets(self) -> Tuple[jax.Array, ...]:
        return tuple(
            jax.lax.with_sharding_constraint(
                jnp.zeros((self.bucket_elems,), self.acc_dtype),
                self._data_sharding)
            for _ in range(self.num_buckets))

    def microstep_buckets(self, grads_tree: Any) -> Tuple[jax.Array, ...]:
        """Flatten this micro-step's bucketed leaves into ICI-dtype flat
        buckets, each constrained to the ``data`` axis — the constraint
        is where XLA emits the per-bucket reduce-scatter over ICI."""
        if not self.num_buckets:
            return ()
        leaves = self.treedef.flatten_up_to(grads_tree)
        parts = [leaves[i].reshape(-1).astype(self.ici_dtype)
                 for i in self.bucketed_idx]
        pad = self.padded_elems - self.total_elems
        if pad:
            # Padding joins the concat instead of a jnp.pad: a `pad` HLO
            # inside this partial-manual region trips the old
            # partitioner's manual-subgroup check (fatal, not catchable).
            parts.append(jnp.zeros((pad,), self.ici_dtype))
        flat = jnp.concatenate(parts)
        return tuple(
            jax.lax.with_sharding_constraint(
                flat[b * self.bucket_elems:(b + 1) * self.bucket_elems],
                self._data_sharding)
            for b in range(self.num_buckets))

    def fallback_leaves(self, grads_tree: Any) -> List[jax.Array]:
        leaves = self.treedef.flatten_up_to(grads_tree)
        return [leaves[i] for i in self.fallback_idx]

    def fallback_sync(self, leaves: Sequence[jax.Array]) -> List[jax.Array]:
        """Per-leaf dcn mean for leaves that cannot join a flat bucket
        (non-data sharding). fp32 on the wire: a bf16 all-reduce under a
        partial-manual shard_map crashes this XLA CPU backend (see
        parallel/pipe/pipeline.py)."""
        inv = 1.0 / self.dcn_size
        return [
            (jax.lax.psum(l.astype(jnp.float32), DCN_AXIS) * inv).astype(
                self.acc_dtype)
            for l in leaves]

    # ------------------------------------------------------------------
    # stage 2 (jit level, manual={dcn, data})
    # ------------------------------------------------------------------
    def _dcn_allreduce_local(self, chunk: jax.Array, gather_ici: bool = True):
        """Body of the DCN stage for ONE bucket's local scattered shard
        ``chunk`` [bucket_elems / data_size]: all-reduce it across slices
        with the configured wire dtype, return ``(gathered_bucket
        [bucket_elems], err)`` where ``err`` — when
        ``measure_quant_error`` is on (None otherwise: the lowering is
        then unchanged) — is this shard's local round-trip-error
        accumulables for BOTH lossy hops the wire takes,
        ``(err_sq, ref_sq, max_abs)`` of the outbound payload followed
        by the same triple for the reduced bucket's re-quantization
        before the return all-gather. Measuring only the first hop
        would systematically underreport the end-to-end error (~sqrt(2)x
        for similar hops). Runs inside the manual={dcn, data} region."""
        n = self.dcn_size
        sub = chunk.shape[0] // n
        parts = chunk.reshape(n, sub)
        inv = 1.0 / n
        err1 = (roundtrip_error_parts(parts, self.bits, self.block)
                if self.measure_quant else None)
        err2 = None
        if self.bits == 8:
            q, s = quantize_blockwise(parts, self.block)
            rq = jax.lax.all_to_all(q, DCN_AXIS, split_axis=0,
                                    concat_axis=0, tiled=False)
            rs = jax.lax.all_to_all(s, DCN_AXIS, split_axis=0,
                                    concat_axis=0, tiled=False)
            red = jnp.sum(dequantize_blockwise(rq, rs, self.block),
                          axis=0) * inv
            if self.measure_quant:
                # Second hop: the reduced bucket is re-quantized for the
                # return all-gather — an independent RTNE stage.
                err2 = roundtrip_error_parts(red, self.bits, self.block)
            q2, s2 = quantize_blockwise(red, self.block)
            aq = jax.lax.all_gather(q2, DCN_AXIS, axis=0, tiled=False)
            a_s = jax.lax.all_gather(s2, DCN_AXIS, axis=0, tiled=False)
            mine = dequantize_blockwise(aq, a_s, self.block).reshape(-1)
        else:
            # bits=32 "passthrough" ships the ICI dtype, NOT whatever
            # dtype the caller accumulated in: the runtime engines
            # accumulate buckets in acc_dtype while the pipe engine hands
            # over raw ici_dtype buckets — without this cast the two
            # would put different wire dtypes on DCN for the same config
            # (and modeled_bytes would misreport one of them).
            wire = (jnp.bfloat16 if self.bits == 16
                    else jnp.dtype(self.ici_dtype))
            rp = jax.lax.all_to_all(parts.astype(wire), DCN_AXIS,
                                    split_axis=0, concat_axis=0,
                                    tiled=False)
            red = (jnp.sum(rp.astype(jnp.float32), axis=0) * inv)
            if self.measure_quant:
                # Second hop (bits=16 only measures): the reduced bucket
                # returns over DCN as bf16 — the same cast loss again.
                err2 = roundtrip_error_parts(red, self.bits, self.block)
            ag = jax.lax.all_gather(red.astype(wire), DCN_AXIS, axis=0,
                                    tiled=False)
            mine = ag.astype(jnp.float32).reshape(-1)
        err = (err1 + err2) if self.measure_quant else None
        if not gather_ici:
            # Overlap mode: keep the reduced chunk as this device's data
            # shard — the jit-level double-buffered accumulator stays at
            # 1/data memory and the one all-gather happens at unbucket
            # time, after the final microstep.
            return mine, err
        # All-gather the reduced chunk back over ICI: the bucket leaves
        # this region replicated and the engine's grad-spec constraint
        # re-shards it locally (no further traffic).
        return jax.lax.all_gather(mine, DATA_AXIS, axis=0, tiled=True), err

    def dcn_sync(self, stacked: Tuple[jax.Array, ...]):
        """DCN stage entry: ``stacked`` buckets are [dcn, bucket_elems]
        (stage 1 stacks each slice's partial on a leading dcn dim).
        Returns ``(buckets, qerr)``: fully-reduced fp32 buckets, one HLO
        collective chain per bucket so the scheduler can overlap them,
        plus — when ``measure_quant_error`` is on — a replicated
        ``[num_buckets, 2]`` fp32 array of (rel-L2, max-abs) round-trip
        error per bucket, psum'd/pmax'd over the whole manual region
        (None otherwise). rel-L2 is the root-sum-square of the two RTNE
        hops (outbound payload + reduced-bucket re-quantization) — the
        error-propagation estimate of the END-TO-END error vs an fp32
        all-reduce; max-abs is the two hops' worst-case sum, in
        accumulator units — under fp16 that includes the loss scale."""
        if not stacked:
            return (), None
        if self._dcn_sync_fn is None:
            measure = self.measure_quant

            def body(*bs):
                res = [self._dcn_allreduce_local(b[0]) for b in bs]
                bufs = tuple(r[0] for r in res)
                if not measure:
                    return bufs
                rows = []
                for _, (e1, r1, m1, e2, r2, m2) in res:
                    axes = (DCN_AXIS, DATA_AXIS)
                    rel1 = rel_from_parts(jax.lax.psum(e1, axes),
                                          jax.lax.psum(r1, axes))
                    rel2 = rel_from_parts(jax.lax.psum(e2, axes),
                                          jax.lax.psum(r2, axes))
                    mab = (jax.lax.pmax(m1, axes)
                           + jax.lax.pmax(m2, axes))
                    rows.append(jnp.stack(
                        [jnp.sqrt(rel1 * rel1 + rel2 * rel2), mab]))
                return bufs, jnp.stack(rows)

            out_specs = tuple(P() for _ in stacked)
            if measure:
                out_specs = (out_specs, P())
            self._dcn_sync_fn = shard_map(
                body, mesh=self.mesh,
                in_specs=tuple(P(DCN_AXIS, DATA_AXIS) for _ in stacked),
                out_specs=out_specs,
                axis_names={DCN_AXIS, DATA_AXIS},
                check_vma=False)
        out = self._dcn_sync_fn(*stacked)
        if self.measure_quant:
            return out[0], out[1]
        return out, None

    # ------------------------------------------------------------------
    # overlap mode (comm.overlap_grad_sync; docs/PERFORMANCE.md
    # "Overlapped gradient sync")
    # ------------------------------------------------------------------
    def microstep_buckets_overlap(self, grads_tree: Any
                                  ) -> Tuple[jax.Array, ...]:
        """Per-bucket flat buffers built from ONLY each bucket's own
        leaves (+ its own padding) — every bucket gets an independent
        dependency chain, so its data-axis reduce-scatter can start as
        soon as *its* gradients exist, not when the whole tree does.
        Runs inside the manual={dcn} region like
        :meth:`microstep_buckets`."""
        if not self.num_buckets:
            return ()
        leaves = self.treedef.flatten_up_to(grads_tree)
        out = []
        for lidx, padded in zip(self.bucket_leaf_idx, self.bucket_padded):
            parts = [leaves[i].reshape(-1).astype(self.ici_dtype)
                     for i in lidx if self.leaf_sizes[i]]
            have = sum(self.leaf_sizes[i] for i in lidx)
            if padded - have:
                # Padding joins the concat (jnp.pad trips the old
                # partitioner's manual-subgroup check — see
                # microstep_buckets).
                parts.append(jnp.zeros((padded - have,), self.ici_dtype))
            out.append(jax.lax.with_sharding_constraint(
                jnp.concatenate(parts) if len(parts) > 1 else parts[0],
                self._data_sharding))
        return tuple(out)

    def _dcn_sync_overlap(self, stacked: Tuple[jax.Array, ...]):
        """Overlap-mode DCN stage for ONE microstep's buckets: same wire
        protocol as :meth:`dcn_sync` but the reduced buckets come back
        as data-sharded shards (``gather_ici=False`` — the jit-level
        accumulator keeps the 1/data memory shape and the single
        all-gather happens at unbucket time), and the quantization-error
        accumulables come back raw (``[num_buckets, 6]`` of
        (err_sq, ref_sq, max_abs) x two hops, already psum/pmax'd over
        the region) so the caller can accumulate them across
        microsteps."""
        if not stacked:
            return (), None
        if self._dcn_overlap_fn is None:
            measure = self.measure_quant

            def body(*bs):
                res = [self._dcn_allreduce_local(b[0], gather_ici=False)
                       for b in bs]
                bufs = tuple(r[0] for r in res)
                if not measure:
                    return bufs
                axes = (DCN_AXIS, DATA_AXIS)
                rows = []
                for _, (e1, r1, m1, e2, r2, m2) in res:
                    rows.append(jnp.stack(
                        [jax.lax.psum(e1, axes), jax.lax.psum(r1, axes),
                         jax.lax.pmax(m1, axes),
                         jax.lax.psum(e2, axes), jax.lax.psum(r2, axes),
                         jax.lax.pmax(m2, axes)]))
                return bufs, jnp.stack(rows)

            out_specs = tuple(P(DATA_AXIS) for _ in stacked)
            if measure:
                out_specs = (out_specs, P())
            self._dcn_overlap_fn = shard_map(
                body, mesh=self.mesh,
                in_specs=tuple(P(DCN_AXIS, DATA_AXIS) for _ in stacked),
                out_specs=out_specs,
                axis_names={DCN_AXIS, DATA_AXIS},
                check_vma=False)
        out = self._dcn_overlap_fn(*stacked)
        if self.measure_quant:
            return out[0], out[1]
        return out, None

    def _qerr_from_parts(self, acc: jax.Array) -> jax.Array:
        """Fold microstep-accumulated error parts ``[num_buckets, 6]``
        into the ``[num_buckets, 2]`` (rel-L2, max-abs) rows
        :meth:`dcn_sync` emits: per-hop rel from the summed squares
        (error-propagation across microsteps), hops RSS-combined;
        max-abs sums hops AND microsteps (the worst-case errors of the
        summed contributions add)."""
        rel1 = rel_from_parts(acc[:, 0], acc[:, 1])
        rel2 = rel_from_parts(acc[:, 3], acc[:, 4])
        return jnp.stack(
            [jnp.sqrt(rel1 * rel1 + rel2 * rel2), acc[:, 2] + acc[:, 5]],
            axis=1)

    def _microstep_region(self, *, compute_params, sub, scale, batch,
                          batch_spec, grad_fn, microbatched: bool):
        """ONE microstep's manual={dcn} region: fwd/bwd with the ICI
        overlap hook installed (in-tree models' bucket-boundary markers
        reduce-scatter each layer group's grads mid-backward), per-bucket
        flat buffers with independent dependency chains, per-microstep
        fallback sync, dcn-pmean'd loss. Returns ``(stacked_buckets,
        fb_synced, loss)`` with the buckets dcn-stacked for
        :meth:`_dcn_sync_overlap`."""
        from deepspeed_tpu.comm import overlap as overlap_mod

        hook = overlap_mod.ici_scatter_hook(
            self._data_sharding, self.ici_dtype,
            lambda name: self._group_bucketed.get(name, False))

        def body(cp, sub_, scale_, batch_, slice_id):
            key = jax.random.fold_in(sub_, slice_id[0])
            with overlap_mod.install_ici_hook(hook):
                loss, grads = grad_fn(cp, batch_, key, scale_)
            with device_scope("grad_sync"):
                mb = self.microstep_buckets_overlap(grads)
                fb_synced = self.fallback_sync(self.fallback_leaves(grads))
            loss = jax.lax.pmean(loss, DCN_AXIS)
            return tuple(b[None] for b in mb), fb_synced, loss

        batch_specs = dcn_batch_leaf_specs(
            batch, batch_spec, self.mesh,
            leading_gas_dim=not microbatched)
        rep = P()
        mapped = shard_map(
            body, mesh=self.mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: rep,
                                             compute_params),
                      rep, rep, batch_specs, P(DCN_AXIS)),
            out_specs=(tuple(P(DCN_AXIS)
                             for _ in range(self.num_buckets)),
                       [rep] * len(self.fallback_idx), rep),
            axis_names={DCN_AXIS},
            check_vma=False)
        return mapped(compute_params, sub, scale, batch,
                      slice_index_operand(self.mesh))

    def _run_overlap_gas(self, *, batches: Any, batch_spec,
                         compute_params: Any, sub: jax.Array,
                         scale: jax.Array, grad_fn,
                         microbatched: bool = True):
        """The overlapped GAS schedule: microstep k's buckets are
        quantized and dispatched over DCN immediately after its
        backward, double-buffered so exactly ONE reduce is in flight
        while microstep k+1's fwd/bwd runs (its collective chain has no
        data dependency on k+1's compute — the latency-hiding scheduler
        overlaps them; in the traced program the dcn collectives of
        microstep k sit between microstep k's and k+1's compute, not all
        trailing). Only the final microstep's reduce is exposed.
        Returns ``(grads_tree, loss, qerr)``."""
        steps = self.gas if microbatched else 1
        keys = jax.random.split(sub, steps)
        total: Optional[List[jax.Array]] = None
        inflight: Optional[Tuple[jax.Array, ...]] = None
        fb_total: Optional[List[jax.Array]] = None
        err_acc = None
        losses = []
        for k in range(steps):
            batch_k = (jax.tree_util.tree_map(lambda x, k=k: x[k], batches)
                       if microbatched else batches)
            stacked_k, fb_k, loss_k = self._microstep_region(
                compute_params=compute_params, sub=keys[k], scale=scale,
                batch=batch_k, batch_spec=batch_spec, grad_fn=grad_fn,
                microbatched=microbatched)
            losses.append(loss_k)
            with device_scope("accumulate"):
                fb_total = (list(fb_k) if fb_total is None
                            else [a + b for a, b in zip(fb_total, fb_k)])
                if inflight is not None:
                    # Consume the previous microstep's reduce — by now its
                    # wire time has been hidden behind this microstep's
                    # fwd/bwd. The accumulator holds ONE total plus ONE
                    # in-flight buffer (double-buffered), never more.
                    total = (list(inflight) if total is None
                             else [t + f for t, f in zip(total, inflight)])
            with device_scope("grad_sync"):
                inflight, parts = self._dcn_sync_overlap(stacked_k)
            if parts is not None:
                err_acc = parts if err_acc is None else err_acc + parts
        if inflight is not None:
            with device_scope("accumulate"):
                total = (list(inflight) if total is None
                         else [t + f for t, f in zip(total, inflight)])
        with device_scope("grad_sync"):
            grads = self._unbucket_overlap(total or [], fb_total or [])
        loss = jnp.mean(jnp.stack(losses))
        qerr = (self._qerr_from_parts(err_acc)
                if err_acc is not None else None)
        return grads, loss, qerr

    def _unbucket_overlap(self, buckets: Sequence[jax.Array],
                          fb: Sequence[jax.Array]) -> Any:
        """Slice each bucket's (data-sharded) reduced buffer back into
        its own leaves — leaves never straddle buckets in overlap mode —
        and merge the fallback leaves. The accumulated buckets arrive
        data-sharded; GSPMD inserts the one all-gather where the grad
        specs need it (same total ICI bytes as the non-overlap return
        gather)."""
        out: List[Optional[jax.Array]] = [None] * self.num_leaves
        for lidx, flat in zip(self.bucket_leaf_idx, buckets):
            off = 0
            for i in lidx:
                size = self.leaf_sizes[i]
                out[i] = flat[off:off + size].reshape(
                    self.leaf_shapes[i]).astype(self.acc_dtype)
                off += size
        for i, leaf in zip(self.fallback_idx, fb):
            out[i] = leaf
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def gas_sync(self, *, batches: Any, batch_spec, compute_params: Any,
                 sub: jax.Array, scale: jax.Array, grad_fn,
                 microbatched: bool = True):
        """The ONE entry every hierarchical grad path calls: run the GAS
        fwd/bwd + full hierarchical sync under whichever schedule this
        plan resolved (overlapped or the PR-4 boundary sync) and return
        ``(grads_tree, loss, qerr)``."""
        if self.overlap:
            return self._run_overlap_gas(
                batches=batches, batch_spec=batch_spec,
                compute_params=compute_params, sub=sub, scale=scale,
                grad_fn=grad_fn, microbatched=microbatched)
        stacked, fb_synced, loss = self.run_manual_gas(
            batches=batches, batch_spec=batch_spec,
            compute_params=compute_params, sub=sub, scale=scale,
            grad_fn=grad_fn, microbatched=microbatched)
        grads, qerr = self.sync_grads(stacked, fb_synced)
        return grads, loss, qerr

    # ------------------------------------------------------------------
    # jit level
    # ------------------------------------------------------------------
    def run_manual_gas(self, *, batches: Any, batch_spec,
                       compute_params: Any, sub: jax.Array,
                       scale: jax.Array, grad_fn,
                       microbatched: bool = True):
        """The ONE manual={dcn} region every BOUNDARY-schedule (overlap
        off) hierarchical grad path runs — the overlapped schedule uses
        per-microstep regions (:meth:`_microstep_region`) instead:
        fold the slice id into the dropout key, run the (Python-unrolled)
        GAS loop of ``grad_fn(compute_params, batch, key, scale) ->
        (loss, grads)`` calls, bucket+accumulate each micro-step's grads
        (ICI reduce-scatter at the bucket constraints), sync the fallback
        leaves, and return ``(stacked_buckets, fallback_synced, loss)``
        ready for :meth:`dcn_sync` + :meth:`unbucket`.

        ``microbatched=False`` makes one grad_fn call over the whole
        ``batches`` tree (the pipe engine's single pipelined fwd/bwd over
        all microbatches).

        Shared by both engines' three step builders so the two
        old-partitioner landmines stay fixed in one place: the GAS loop
        unrolls in Python (a lax.scan feeding a dcn-sharded region output
        trips a fatal manual-subgroup check) and bucket padding joins the
        concat (``jnp.pad`` trips the same check).
        """
        fallback_inner = [NamedSharding(self.mesh, s)
                          for s in self.fallback_inner_specs]
        steps = self.gas if microbatched else 1

        def body(cp, sub_, scale_, batches_, slice_id):
            # Decorrelate dropout across slices (each slice sees its own
            # batch shard); slice_id is the iota-operand axis_index
            # stand-in (slice_index_operand).
            key = jax.random.fold_in(sub_, slice_id[0])
            buckets = self.zero_buckets()
            fb = self.zero_fallback()
            losses = []
            for i in range(steps):
                if microbatched:
                    batch = jax.tree_util.tree_map(lambda x, i=i: x[i],
                                                   batches_)
                    key, k = jax.random.split(key)
                else:
                    batch, k = batches_, key
                loss, grads = grad_fn(cp, batch, k, scale_)
                with device_scope("grad_sync"):
                    mb = self.microstep_buckets(grads)
                with device_scope("accumulate"):
                    buckets = tuple(b + m.astype(b.dtype)
                                    for b, m in zip(buckets, mb))
                    gf = self.fallback_leaves(grads)
                    fb = [jax.lax.with_sharding_constraint(
                            a + g.astype(a.dtype), s)
                          for a, g, s in zip(fb, gf, fallback_inner)]
                losses.append(loss)
            with device_scope("grad_sync"):
                fb_synced = self.fallback_sync(fb)
            loss = jax.lax.pmean(jnp.mean(jnp.stack(losses)), DCN_AXIS)
            return tuple(b[None] for b in buckets), fb_synced, loss

        batch_specs = dcn_batch_leaf_specs(batches, batch_spec, self.mesh,
                                           leading_gas_dim=True)
        rep = P()
        mapped = shard_map(
            body, mesh=self.mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: rep,
                                             compute_params),
                      rep, rep, batch_specs, P(DCN_AXIS)),
            out_specs=(tuple(P(DCN_AXIS)
                             for _ in range(self.num_buckets)),
                       [rep] * len(self.fallback_idx), rep),
            axis_names={DCN_AXIS},
            check_vma=False)
        return mapped(compute_params, sub, scale, batches,
                      slice_index_operand(self.mesh))

    def unbucket(self, synced_buckets: Sequence[jax.Array],
                 synced_fallback: Sequence[jax.Array]) -> Any:
        """Slice the reduced buckets back into the grad tree (accumulator
        dtype) and merge the fallback leaves."""
        out: List[Optional[jax.Array]] = [None] * self.num_leaves
        if synced_buckets:
            flat = jnp.concatenate(synced_buckets)
            off = 0
            for i in self.bucketed_idx:
                size = self.leaf_sizes[i]
                out[i] = flat[off:off + size].reshape(
                    self.leaf_shapes[i]).astype(self.acc_dtype)
                off += size
        for i, leaf in zip(self.fallback_idx, synced_fallback):
            out[i] = leaf
        return jax.tree_util.tree_unflatten(self.treedef, out)

    # ------------------------------------------------------------------
    # modeling / telemetry
    # ------------------------------------------------------------------
    def sync_grads(self, stacked: Tuple[jax.Array, ...],
                   synced_fallback: Sequence[jax.Array]
                   ) -> Tuple[Any, Optional[jax.Array]]:
        """DCN-sync the stage-1 buckets and slice them back into the grad
        tree — the one sequence every hierarchical step runs after
        :meth:`run_manual_gas`. Returns ``(grads_tree, qerr)``; ``qerr``
        is :meth:`dcn_sync`'s per-bucket error array (None unless
        ``measure_quant_error``)."""
        with device_scope("grad_sync"):
            buckets, qerr = self.dcn_sync(stacked)
            return self.unbucket(buckets, synced_fallback), qerr

    def _bucket_dcn_bytes(self, elems: int) -> int:
        """Modeled DCN wire bytes for one bucket of ``elems`` elements
        (both directions) — the ONE formula behind modeled_bytes and the
        per-bucket trace instants, so the gauge and the instants can
        never disagree."""
        shard = elems // self.data_size
        if self.bits == 32:
            # Passthrough ships the bucket's ICI dtype verbatim (bf16
            # communication_data_type also halves the fp32 passthrough).
            return 2 * shard * jnp.dtype(self.ici_dtype).itemsize
        return 2 * modeled_wire_bytes(shard, self.bits, self.block)

    def _per_bucket_dcn_bytes(self) -> int:
        return self._bucket_dcn_bytes(self.bucket_elems)

    def modeled_bytes(self) -> dict:
        """Per-device per-step wire bytes (modeled; self-shard included,
        so an upper bound — ratios between tiers are exact). Overlap
        mode reduces every microstep's contribution over DCN separately
        (that is what hides the wire time behind the next microstep's
        compute), so its DCN bytes — and the fp32 reference on the SAME
        schedule — carry the GAS factor; the compression ratio between
        tiers is schedule-invariant."""
        sync_rounds = self.gas if self.overlap else 1
        dcn_once = (sum(self._bucket_dcn_bytes(e)
                        for e in self.bucket_padded)
                    + 2 * 4 * self.fallback_elems)   # fp32 psum fallback
        bytes_dcn = sync_rounds * dcn_once
        ici_item = jnp.dtype(self.ici_dtype).itemsize
        # One reduce-scatter per MICRO-step (each gas iteration's bucket
        # constraint) in the ICI dtype, plus one fp32 all-gather of the
        # dequantized buckets out of the DCN stage per optimizer step
        # (overlap mode defers it to unbucket time — same bytes).
        bytes_ici = (self.gas * self.padded_elems * ici_item
                     + self.padded_elems * 4)
        fp32_dcn = sync_rounds * (
            sum(2 * 4 * (e // self.data_size) for e in self.bucket_padded)
            + 2 * 4 * self.fallback_elems)
        return {
            "bytes_dcn": int(bytes_dcn),
            "bytes_ici": int(bytes_ici),
            "bytes_dcn_fp32": int(fp32_dcn),
            "compression_ratio": (fp32_dcn / bytes_dcn if bytes_dcn else 1.0),
            "num_buckets": self.num_buckets,
            "bucket_elems": self.bucket_elems,
            "bucketed_elems": self.total_elems,
            "fallback_elems": self.fallback_elems,
            "overlap": int(self.overlap),
        }

    def modeled_wire_seconds(self) -> float:
        """Total modeled collective seconds per optimizer step at the
        nominal link bandwidths — the wire time that exists, overlapped
        or not."""
        m = self.modeled_bytes()
        return (m["bytes_dcn"] / (self.dcn_gbps * 1e9)
                + m["bytes_ici"] / (self.ici_gbps * 1e9))

    def modeled_exposed_seconds(self,
                                overlap_budget_seconds: Optional[float]
                                = None) -> float:
        """Modeled EXPOSED collective seconds per optimizer step — the
        numerator of ``comm/exposed_frac`` and the
        ``goodput/exposed_comm_sec`` sub-attribution.

        Non-overlap schedule: the sync fires at the GAS boundary,
        nothing overlaps it (ROADMAP item 1's premise) — every modeled
        wire byte is exposed.

        Overlap schedule (docs/OBSERVABILITY.md "Gradient-sync
        metrics"): the exposed floor is the final microstep's DCN
        reduce plus the post-sync all-gather (nothing runs behind
        them); everything else is hideable behind backward compute.
        ``overlap_budget_seconds`` is the modeled compute time available
        to hide behind (the engine passes measured step time minus total
        wire time); hidden time is capped by it, so a comm-dominated
        step still reports most of its wire time as exposed. ``None``
        (no step measured yet, tools) reports the optimistic floor.
        Replace with jax.profiler-measured collective time
        (``comm/measured_exposed_frac``) when a profile was captured."""
        total = self.modeled_wire_seconds()
        if not self.overlap:
            return total
        steps = max(1, self.gas)
        m = self.modeled_bytes()
        dcn_final = (m["bytes_dcn"] / steps) / (self.dcn_gbps * 1e9)
        ag_final = (self.padded_elems * 4) / (self.ici_gbps * 1e9)
        floor = min(total, dcn_final + ag_final)
        if overlap_budget_seconds is None:
            return floor
        hidden = min(total - floor, max(0.0, overlap_budget_seconds))
        return total - hidden

    def describe(self) -> str:
        m = self.modeled_bytes()
        if self.overlap:
            shape = "+".join(str(e) for e in self.bucket_padded) or "0"
            buckets = f"{self.num_buckets}[{shape}] overlap"
        else:
            buckets = f"{self.num_buckets}x{self.bucket_elems}"
        return (f"grad_sync: dcn={self.dcn_size} bits={self.bits} "
                f"block={self.block} buckets={buckets} ici_dtype="
                f"{jnp.dtype(self.ici_dtype).name} "
                f"fallback_elems={self.fallback_elems} "
                f"modeled dcn bytes/step {m['bytes_dcn']} "
                f"({m['compression_ratio']:.2f}x vs fp32)")

    def emit_telemetry(self, telemetry, step: int) -> None:
        """Per-step registry gauges + one-time per-bucket annotations.
        Values are modeled from the plan shape (the collectives run inside
        one XLA program — there is no host-observable per-bucket seam),
        so this costs no device sync."""
        if telemetry is None or not getattr(telemetry, "enabled", False):
            return
        m = self.modeled_bytes()
        reg = telemetry.registry
        reg.gauge("comm/bytes_dcn").set(m["bytes_dcn"], step=step)
        reg.gauge("comm/bytes_ici").set(m["bytes_ici"], step=step)
        reg.gauge("comm/compression_ratio").set(m["compression_ratio"],
                                                step=step)
        if not getattr(self, "_buckets_announced", False):
            self._buckets_announced = True
            for b, elems in enumerate(self.bucket_padded):
                telemetry.instant("grad_sync/bucket", index=b,
                                  elems=elems,
                                  bytes_dcn=self._bucket_dcn_bytes(elems),
                                  bits=self.bits,
                                  overlap=int(self.overlap))


# ---------------------------------------------------------------------------
# ZeRO++ weight path: the explicit quantized param all-gather (qwZ/hpZ)
# ---------------------------------------------------------------------------

# The param-hop comm gauges (emitted by ParamGatherPlan.emit_telemetry),
# pinned against docs/OBSERVABILITY.md in BOTH directions by
# tests/test_doc_lint.py so fleet/devicetime attribution can always tell
# parameter traffic from gradient traffic.
COMM_PARAM_METRIC_TAGS = frozenset({
    "comm/bytes_dcn_params",
    "comm/bytes_ici_params",
})


class ParamGatherPlan:
    """The ZeRO++ weight-path wire protocol (arXiv 2306.10209 qwZ/hpZ):
    one explicit blockwise-quantized all-gather replacing the implicit
    full-precision pjit param all-gather for ZeRO stage >= 2.

    Placement comes from the partitioner (runtime/zero/partition.py):
    with ``zeropp.hpz: off`` the primary param/optimizer partition spans
    the full (dcn, data) product and this gather crosses DCN with int8
    codes; with ``hpz: on`` the partition stays intra-slice (the
    hierarchical secondary partition) and the gather rides ICI only —
    zero dcn-axis param collectives, asserted by tests/test_zeropp.py.

    Wire protocol per gathered leaf, inside ONE ``shard_map`` manual over
    the gather axes (everything else — TP specs, the dcn axis under hpZ —
    stays GSPMD-auto):

    - **int8**: flatten the local fp32 master shard, pad to a block
      multiple (padding joins a concat — ``jnp.pad`` trips the old
      partitioner's manual-subgroup check, see ``microstep_buckets``),
      quantize with the ONE deterministic RTNE core
      (:func:`deepspeed_tpu.comm.quantize.quantize_blockwise`),
      all-gather the int8 codes + fp32 scales, dequantize and stitch the
      full leaf back together. ~4x fewer wire bytes than fp32.
    - **bf16**: cast the shard, gather, upcast — 2x.
    - **fp32 passthrough** (``quantized_weights: off`` with hpZ on): a
      tiled fp32 all-gather — *exact*: the gathered tree is elementwise
      equal to the replicated master, so the hpZ-only tier is an
      ulp-parity rung, not a lossy one.

    Leaves below the stage-3 persistence threshold stay replicated
    (never gathered, no wire traffic); leaves sharded over non-data
    axes (TP/pipe) keep the implicit path (XLA gathers them in full
    precision as before — counted as ``fallback_elems``).

    ``measure_quant_error`` (numerics observatory on + a lossy tier):
    the region additionally returns the RTNE round-trip error of the
    wire payload vs the fp32 master — one ``[2]`` (rel-L2, max-abs)
    array psum'd/pmax'd over the manual axes — which the engine routes
    into the step aux and :class:`~deepspeed_tpu.telemetry.numerics.
    NumericsObservatory` emits as ``numerics/param_quant_rel_err`` /
    ``numerics/param_quant_max_abs_err``. Off, the region body is
    byte-for-byte the measurement-free one.

    The fused step builders hoist the gather out of the GAS scan —
    parameters are loop-invariant until the apply — so the modeled
    bytes below are per optimizer step.
    """

    def __init__(self, zeropp_cfg, mesh: Mesh, param_template: Any,
                 param_specs: Any, measure_quant_error: bool = False):
        self.mesh = mesh
        self.bits = int(zeropp_cfg.wire_bits)
        self.block = int(zeropp_cfg.quant_block_size)
        self.hpz = zeropp_cfg.hpz == "on"
        self.dcn_size = int(mesh.shape.get(DCN_AXIS, 1))
        self.data_size = int(mesh.shape.get(DATA_AXIS, 1))
        self.measure_quant = (bool(measure_quant_error)
                              and self.bits in (8, 16))

        leaves, self.treedef = jax.tree_util.tree_flatten(param_template)
        spec_leaves = self.treedef.flatten_up_to(param_specs)
        self.num_leaves = len(leaves)
        # (leaf idx, sharded dim, axes tuple) per explicitly-gathered leaf.
        self.gathered: List[Tuple[int, int, Tuple[str, ...]]] = []
        self.persistent_idx: List[int] = []   # replicated, no wire traffic
        self.fallback_idx: List[int] = []     # non-data sharding: implicit
        self._leaf_shapes = [tuple(getattr(l, "shape", ())) for l in leaves]
        # (leaf idx, ALL sharded axes) per fallback leaf — the hpZ
        # secondary charge still counts them (fallback_leaves()).
        self.fallback_axes: List[Tuple[int, Tuple[str, ...]]] = []
        for i, (leaf, spec) in enumerate(zip(leaves, spec_leaves)):
            entries = tuple(spec) if spec is not None else ()
            float_leaf = jnp.issubdtype(leaf.dtype, jnp.floating)
            dim = None
            dim_axes: Tuple[str, ...] = ()
            all_axes: List[str] = []
            other = False
            for j, e in enumerate(entries):
                parts = e if isinstance(e, tuple) else ((e,) if e else ())
                parts = tuple(a for a in parts if a is not None)
                if not parts:
                    continue
                real = tuple(a for a in parts
                             if self.mesh.shape.get(a, 1) > 1)
                if not real:
                    continue
                all_axes.extend(real)
                if set(real) <= {DCN_AXIS, DATA_AXIS}:
                    dim, dim_axes = j, real
                else:
                    other = True
            if dim is None and not other:
                self.persistent_idx.append(i)   # truly replicated
            elif other or not float_leaf:
                # TP/mixed-axis leaves (the flat-block protocol cannot
                # stitch a second sharded dim back) and sharded
                # non-float leaves: implicit full-precision path — they
                # DO produce wire traffic, so they must count as
                # fallback, never as persistent.
                self.fallback_idx.append(i)
                self.fallback_axes.append((i, tuple(all_axes)))
            else:
                self.gathered.append((i, dim, dim_axes))
        # The region is manual over the data-like axes {dcn, data} even
        # when the gather itself only names `data` (hpZ): this jax's old
        # SPMD partitioner rejects a manual subgroup whose AUTO axes sit
        # OUTSIDE the manual ones in mesh order (manual={data} with dcn
        # auto is the fatal IsManualSubgroup check; manual={dcn, data} is
        # the dcn_sync shape that works). Under hpZ every dcn rank holds
        # the full data-shard (params are dcn-replicated), so the body
        # computes identical values per slice and emits ZERO dcn-axis
        # collectives — the property tests/test_zeropp.py asserts.
        self.manual_axes = sorted(
            {a for _, _, axes in self.gathered for a in axes}
            | ({DCN_AXIS} if self.dcn_size > 1 and self.gathered else set()))
        self.gathered_elems = sum(
            int(math.prod(self._leaf_shapes[i])) for i, _, _ in self.gathered)
        self.fallback_elems = sum(
            int(math.prod(self._leaf_shapes[i])) for i in self.fallback_idx)
        self.persistent_elems = sum(
            int(math.prod(self._leaf_shapes[i]) or 1)
            for i in self.persistent_idx)
        self._gather_fn = None

    # ------------------------------------------------------------------
    def _restricted_spec(self, i: int, dim: int,
                         axes: Tuple[str, ...]) -> P:
        """shard_map in_spec for one gathered leaf: only the manual
        (gather) axes; everything else stays GSPMD-auto."""
        ndim = len(self._leaf_shapes[i])
        entries: List[Any] = [None] * ndim
        entries[dim] = axes if len(axes) > 1 else axes[0]
        return P(*entries)

    def gather(self, params: Any):
        """The explicit gather, traced inside the jitted step: returns
        ``(full_params fp32 tree, qerr)`` where the gathered leaves are
        fully replicated over the gather axes (the engine's precision
        policy casts to the compute dtype afterwards — elementwise, so
        the fp32 passthrough stays exact) and ``qerr`` is the ``[2]``
        (rel-L2, max-abs) wire round-trip error (None unless
        ``measure_quant_error``)."""
        leaves = self.treedef.flatten_up_to(params)
        if not self.gathered:
            return params, None
        if self._gather_fn is None:
            self._gather_fn = self._build_gather_fn()
        out = self._gather_fn(tuple(leaves[i] for i, _, _ in self.gathered))
        if self.measure_quant:
            full, qerr = out
        else:
            full, qerr = out, None
        merged = list(leaves)
        for (i, _, _), f in zip(self.gathered, full):
            merged[i] = f
        return jax.tree_util.tree_unflatten(self.treedef, merged), qerr

    def _build_gather_fn(self):
        measure = self.measure_quant
        bits, block = self.bits, self.block
        mesh = self.mesh
        red_axes = tuple(self.manual_axes)

        def gather_leaf(x, dim, axes):
            name = axes if len(axes) > 1 else axes[0]
            n = mesh_axes_size(mesh.shape, axes)
            if bits == 32:
                # Exact passthrough: one tiled fp32 all-gather stitches
                # the full leaf along the sharded dim directly.
                return jax.lax.all_gather(x, name, axis=dim,
                                          tiled=True), None
            flat = x.reshape(-1).astype(jnp.float32)
            m = flat.shape[0]
            pad = (-m) % block
            if pad:
                # Padding joins the concat instead of jnp.pad (the old
                # partitioner's manual-subgroup check — see
                # microstep_buckets).
                flat = jnp.concatenate(
                    [flat, jnp.zeros((pad,), jnp.float32)])
            err = (roundtrip_error_parts(flat, bits, block)
                   if measure else None)
            if bits == 8:
                q, s = quantize_blockwise(flat, block)
                qg = jax.lax.all_gather(q, name, axis=0, tiled=False)
                sg = jax.lax.all_gather(s, name, axis=0, tiled=False)
                deq = dequantize_blockwise(qg, sg, block)
            else:       # bf16 wire
                wg = jax.lax.all_gather(flat.astype(jnp.bfloat16), name,
                                        axis=0, tiled=False)
                deq = wg.astype(jnp.float32)
            shards = deq[:, :m].reshape((n,) + x.shape)
            full = jnp.moveaxis(shards, 0, dim).reshape(
                x.shape[:dim] + (n * x.shape[dim],) + x.shape[dim + 1:])
            return full, err

        red_size = mesh_axes_size(mesh.shape, red_axes)

        def body(ls):
            outs = []
            err_sq = ref_sq = mab = jnp.float32(0.0)
            for (idx, dim, axes), x in zip(self.gathered, ls):
                full, err = gather_leaf(x, dim, axes)
                outs.append(full)
                if err is not None:
                    e, r, ma = err
                    # The psum below runs over ALL manual axes, but a
                    # leaf gathered over a subset (e.g. a (data,)-only
                    # fallback leaf under the hpz=off global primary, or
                    # every leaf under hpZ where the region is manual
                    # over dcn too) holds REPLICATED shards along the
                    # rest — pre-divide by the replication factor so
                    # each unique shard's error counts exactly once and
                    # mixed trees aren't skewed toward replicated leaves.
                    gather_size = mesh_axes_size(mesh.shape, axes)
                    w = jnp.float32(gather_size / red_size)
                    err_sq = err_sq + e * w
                    ref_sq = ref_sq + r * w
                    mab = jnp.maximum(mab, ma)
            if not measure:
                return tuple(outs)
            rel = rel_from_parts(jax.lax.psum(err_sq, red_axes),
                                 jax.lax.psum(ref_sq, red_axes))
            return tuple(outs), jnp.stack(
                [rel, jax.lax.pmax(mab, red_axes)])

        in_specs = (tuple(self._restricted_spec(i, dim, axes)
                          for i, dim, axes in self.gathered),)
        out_leaf_specs = tuple(
            P(*([None] * len(self._leaf_shapes[i])))
            for i, _, _ in self.gathered)
        out_specs = ((out_leaf_specs, P()) if measure else out_leaf_specs)
        return shard_map(body, mesh=self.mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         axis_names=set(self.manual_axes),
                         check_vma=False)

    # ------------------------------------------------------------------
    # modeling / telemetry
    # ------------------------------------------------------------------
    def gathered_leaves(self, tree: Any = None) -> List[Tuple[Tuple[int, ...], Tuple[str, ...], Any]]:
        """(global shape, gather axes, companion-tree leaf) per
        explicitly-gathered leaf — what the memory ledger sizes the
        gathered compute-tree footprint from (persistent leaves stay
        replicated; fallback leaves ride the implicit path, so neither
        is gathered in full here). ``tree`` is an optional companion
        pytree of the params structure (the engine's base partition
        specs); None yields None companions."""
        comp = ([None] * self.num_leaves if tree is None
                else self.treedef.flatten_up_to(tree))
        return [(self._leaf_shapes[i], axes, comp[i])
                for i, _, axes in self.gathered]

    def fallback_leaves(self, tree: Any = None) -> List[Tuple[Tuple[int, ...], Tuple[str, ...], Any]]:
        """Same triples for the implicit-path (TP/mixed-axis) leaves,
        with ALL their sharded mesh axes. They skip the explicit gather
        but still carry the partitioner's primary placement on their
        free dim — so the hpZ secondary charge must count them alongside
        the gathered leaves (a global (hpz off) primary would spread
        them over dcn too)."""
        comp = ([None] * self.num_leaves if tree is None
                else self.treedef.flatten_up_to(tree))
        return [(self._leaf_shapes[i], axes, comp[i])
                for i, axes in self.fallback_axes]

    def modeled_bytes(self) -> dict:
        """Per-device per-optimizer-step modeled wire bytes of the param
        gather, split by link direction (self-shard included — an upper
        bound; ratios between tiers are exact, the GradSyncPlan
        convention). ``bytes_params_fp32`` is the same gather at fp32
        wire — the compression denominator. Persistent (replicated)
        leaves never hit the wire; fallback (TP-sharded) leaves ride the
        implicit full-precision path and are excluded from the explicit
        totals (reported so the probe can see them)."""
        bytes_dcn = bytes_ici = fp32 = 0.0
        for i, _, axes in self.gathered:
            elems = int(math.prod(self._leaf_shapes[i]))
            wire = modeled_wire_bytes(elems, self.bits, self.block)
            ref = modeled_wire_bytes(elems, 32, self.block)
            dcn_frac = ((self.dcn_size - 1) / self.dcn_size
                        if DCN_AXIS in axes and self.dcn_size > 1 else 0.0)
            bytes_dcn += wire * dcn_frac
            bytes_ici += wire * (1.0 - dcn_frac)
            fp32 += ref
        wire_total = bytes_dcn + bytes_ici
        return {
            "bytes_dcn_params": int(bytes_dcn),
            "bytes_ici_params": int(bytes_ici),
            "bytes_params_fp32": int(fp32),
            "compression_ratio": (fp32 / wire_total if wire_total else 1.0),
            "gathered_elems": self.gathered_elems,
            "fallback_elems": self.fallback_elems,
            "persistent_elems": self.persistent_elems,
            "hpz": int(self.hpz),
            "bits": self.bits,
        }

    def modeled_wire_seconds(self, dcn_gbps: float,
                             ici_gbps: float) -> float:
        """Modeled collective seconds per optimizer step of the explicit
        param gather at the nominal link bandwidths (the engine passes
        the grad plan's comm.dcn_gbps/ici_gbps). The gather runs
        sequentially before the fused fwd/bwd — nothing is scheduled to
        hide it — so callers count ALL of it as exposed
        (``_emit_comm_attribution``: the modeled ``comm/exposed_frac``
        must include this hop or the PR-9 modeled-vs-measured divergence
        warning fires spuriously whenever zeropp rides with the
        hierarchical sync)."""
        m = self.modeled_bytes()
        return (m["bytes_dcn_params"] / (dcn_gbps * 1e9)
                + m["bytes_ici_params"] / (ici_gbps * 1e9))

    def describe(self) -> str:
        m = self.modeled_bytes()
        tier = {8: "int8", 16: "bf16", 32: "fp32"}[self.bits]
        return (f"zeropp: param gather {tier} block={self.block} "
                f"hpz={'on' if self.hpz else 'off'} "
                f"axes={self.manual_axes} leaves={len(self.gathered)} "
                f"({self.gathered_elems} elems; {self.persistent_elems} "
                f"persistent, {self.fallback_elems} fallback) modeled "
                f"dcn/ici bytes {m['bytes_dcn_params']}/"
                f"{m['bytes_ici_params']} "
                f"({m['compression_ratio']:.2f}x vs fp32)")

    def emit_telemetry(self, telemetry, step: int) -> None:
        """The param-hop direction of the comm byte attribution
        (comm/bytes_dcn_params, comm/bytes_ici_params) — modeled from
        the plan shape like the grad gauges, no device sync."""
        if telemetry is None or not getattr(telemetry, "enabled", False):
            return
        m = self.modeled_bytes()
        reg = telemetry.registry
        reg.gauge("comm/bytes_dcn_params").set(m["bytes_dcn_params"],
                                               step=step)
        reg.gauge("comm/bytes_ici_params").set(m["bytes_ici_params"],
                                               step=step)


# The ISSUE-facing name: the plan IS the strategy object the engines wire
# in (one per engine, bound to its grad tree at step-construction time).
GradSyncStrategy = GradSyncPlan


def dcn_batch_leaf_specs(batches: Any, batch_spec, mesh: Mesh,
                         leading_gas_dim: bool = True) -> Any:
    """Per-leaf shard_map in_specs for the manual={dcn} region: keep only
    the dcn entries of the engine's batch spec, truncated to each leaf's
    rank, replicating any leaf whose dims don't divide (mirroring
    ``put_batch``'s graceful degradation — same rule as the 1-bit
    builder's ``batch_leaf_spec``)."""
    def restrict(entry):
        parts = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(a for a in parts if a == DCN_AXIS)
        return kept if len(kept) > 1 else (kept[0] if kept else None)

    base = tuple(restrict(e) for e in tuple(batch_spec))
    if leading_gas_dim:
        base = (None,) + base

    def leaf_spec(x):
        entries = base[:x.ndim]
        for d, e in zip(x.shape, entries):
            parts = e if isinstance(e, tuple) else ((e,) if e else ())
            n = mesh_axes_size(mesh.shape, parts)
            if n > 1 and d % n:
                return P(*([None] * x.ndim))
        return P(*entries)

    return jax.tree_util.tree_map(leaf_spec, batches)


def slice_index_operand(mesh: Mesh) -> jax.Array:
    """A [dcn]-iota whose single local element inside a manual={dcn}
    region IS the slice id — the ``axis_index`` equivalent that survives
    this jax's partial-manual lowering (axis_index lowers to a
    PartitionId HLO the old SPMD partitioner rejects; same trick as the
    pipeline's rank_arr)."""
    return jnp.arange(mesh.shape.get(DCN_AXIS, 1), dtype=jnp.int32)
