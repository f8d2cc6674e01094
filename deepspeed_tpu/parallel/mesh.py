"""Device-mesh construction.

TPU-native replacement for the reference's process-group plumbing
(``deepspeed/utils/distributed.py:12`` ``init_distributed`` and the
``mpu``-supplied groups the engine consumes at ``runtime/engine.py:672-683``):
instead of NCCL groups we build one ``jax.sharding.Mesh`` with named axes and
let pjit/XLA lower collectives onto ICI/DCN.

Axis order is chosen so the *data* axis is innermost (fastest-varying over
physically adjacent chips) — gradient reduce-scatter/all-gather is the hot
collective and should ride ICI neighbours; pipe is outermost since stage p2p
traffic is the lightest.
"""

import contextlib
import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deepspeed_tpu.utils.logging import log_dist

# Canonical axis names used across the framework.
DATA_AXIS = "data"
MODEL_AXIS = "model"
PIPE_AXIS = "pipe"
SEQUENCE_AXIS = "sequence"
EXPERT_AXIS = "expert"
# Slice-outer data-parallel axis for multi-slice / multi-pod topologies:
# collectives over it ride DCN (slow inter-slice links), everything else
# rides ICI. The reference's analogue is its Ethernet-cluster NCCL/MPI
# backends (runtime/comm/nccl.py:47) — the 1-bit optimizers compress over
# exactly this axis, and ZeRO sharding deliberately stays on the ICI-inner
# `data` axis (SURVEY §2.5 TPU-native row).
DCN_AXIS = "dcn"

ALL_AXES = (DCN_AXIS, PIPE_AXIS, EXPERT_AXIS, DATA_AXIS, SEQUENCE_AXIS,
            MODEL_AXIS)


def init_distributed(dist_backend: str = "xla",
                     coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     timeout: Optional[int] = None) -> None:
    """Multi-host rendezvous — the ``init_distributed`` analogue.

    Single-process usage (one host, or tests) needs no call; multi-host pods
    call this once per host before building a mesh. Environment discovery
    mirrors the reference's env-var path (MASTER_ADDR/RANK/WORLD_SIZE,
    reference utils/distributed.py:54): our launcher exports
    DSTPU_COORDINATOR / DSTPU_NUM_PROCS / DSTPU_RANK.
    """
    # NB: must not touch jax.devices()/process_count() here — any backend
    # query initialises the local runtime and jax.distributed.initialize
    # would then be too late.
    if jax.distributed.is_initialized():
        return
    coordinator_address = coordinator_address or os.environ.get("DSTPU_COORDINATOR")
    if coordinator_address is None and "MASTER_ADDR" in os.environ:
        port = os.environ.get("MASTER_PORT", "29500")
        coordinator_address = f"{os.environ['MASTER_ADDR']}:{port}"
    if coordinator_address is None:
        return  # single-host
    num_processes = num_processes or int(
        os.environ.get("DSTPU_NUM_PROCS", os.environ.get("WORLD_SIZE", "1")))
    process_id = process_id if process_id is not None else int(
        os.environ.get("DSTPU_RANK", os.environ.get("RANK", "0")))
    # Multi-host rendezvous through the shared jittered-backoff helper
    # (guardrails/retry.py): on a pod restart the coordinator host may come
    # up seconds after the workers, and one flaky DNS answer should not
    # kill an otherwise healthy incarnation. DSTPU_INIT_RETRIES=0 restores
    # fail-fast.
    from deepspeed_tpu.guardrails.retry import retry_call

    def rendezvous():
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id)
        except Exception:
            # A failed connect leaves global_state.client/service assigned,
            # and re-entering initialize() would then raise "should only be
            # called once" — masking the real error and making every retry
            # dead. shutdown() resets that state (no-op when nothing
            # started), so the next attempt is a genuine re-rendezvous.
            try:
                jax.distributed.shutdown()
            except Exception:  # noqa: BLE001 — best-effort reset
                pass
            raise

    retry_call(rendezvous,
               max_retries=int(os.environ.get("DSTPU_INIT_RETRIES", "3")),
               base=1.0, max_delay=15.0,
               describe="jax.distributed.initialize")
    log_dist(f"jax.distributed initialised: {num_processes} processes "
             f"@ {coordinator_address}", ranks=[0])


@dataclass(frozen=True)
class MeshShape:
    dcn: int = 1
    pipe: int = 1
    expert: int = 1
    data: int = 1
    sequence: int = 1
    model: int = 1

    @property
    def world(self) -> int:
        return (self.dcn * self.pipe * self.expert * self.data *
                self.sequence * self.model)

    def dims(self) -> Dict[str, int]:
        return {DCN_AXIS: self.dcn, PIPE_AXIS: self.pipe,
                EXPERT_AXIS: self.expert, DATA_AXIS: self.data,
                SEQUENCE_AXIS: self.sequence, MODEL_AXIS: self.model}


def build_mesh(data: int = -1,
               model: int = 1,
               pipe: int = 1,
               sequence: int = 1,
               expert: int = 1,
               slices: int = 1,
               devices: Optional[Sequence] = None) -> Mesh:
    """Build the framework mesh. ``data=-1`` infers from the device count.

    All axes are always present (size-1 axes are free); downstream sharding
    specs can therefore reference any axis unconditionally.

    ``slices > 1`` builds a DCN-aware hierarchical mesh: the outermost
    ``dcn`` axis spans TPU slices/pods (slow links), every other axis stays
    inside a slice (ICI). On real multi-slice hardware the device order
    comes from ``mesh_utils.create_hybrid_device_mesh`` (slice-local
    ICI topology inside, slice id outside); elsewhere (virtual CPU meshes,
    single-slice) a plain slice-major reshape stands in.
    """
    devices = list(devices if devices is not None else jax.devices())
    ndev = len(devices)
    fixed = model * pipe * sequence * expert * slices
    if data == -1:
        if ndev % fixed != 0:
            raise ValueError(
                f"{ndev} devices not divisible by "
                f"slices×model×pipe×seq×expert={fixed}")
        data = ndev // fixed
    shape = MeshShape(dcn=slices, pipe=pipe, expert=expert, data=data,
                      sequence=sequence, model=model)
    if shape.world != ndev:
        raise ValueError(f"mesh {shape.dims()} needs {shape.world} devices, have {ndev}")
    dims = shape.dims()
    full = tuple(dims[a] for a in ALL_AXES)
    from jax.experimental import mesh_utils

    dev_array = None
    if slices > 1:
        try:
            ici = (1,) + full[1:]
            dcn = (slices,) + (1,) * (len(full) - 1)
            dev_array = mesh_utils.create_hybrid_device_mesh(
                ici, dcn, devices=devices)
        except Exception:
            dev_array = None    # no slice metadata (CPU / single slice)
    if dev_array is None:
        # Use hardware-aware device ordering when available so the
        # innermost mesh axes land on ICI-adjacent chips.
        try:
            dev_array = mesh_utils.create_device_mesh(full, devices=devices)
        except Exception:
            dev_array = np.array(devices).reshape(full)
    return Mesh(dev_array, ALL_AXES)


def single_device_mesh() -> Mesh:
    return build_mesh(data=1)


# Ambient mesh: ops that need mesh-aware collectives (ring/Ulysses
# attention selected by a model config string) read it when no mesh is
# passed explicitly. The engine registers its mesh at construction.
_DEFAULT_MESH: Optional[Mesh] = None


def set_default_mesh(mesh: Optional[Mesh]) -> None:
    global _DEFAULT_MESH
    _DEFAULT_MESH = mesh


def get_default_mesh() -> Optional[Mesh]:
    return _PINNED_MESH if _PINNED_MESH is not None else _DEFAULT_MESH


# Trace-scoped mesh: an engine pins ITS mesh while its model code is being
# traced, so mesh-needing ops bind to the engine that is tracing them and
# never to whichever engine registered the ambient default first (or to
# one that no longer exists). Unlike the ambient default it is never left
# behind: outside a pin there is none.
_PINNED_MESH: Optional[Mesh] = None


@contextlib.contextmanager
def pinned_mesh(mesh: Mesh):
    global _PINNED_MESH
    prev, _PINNED_MESH = _PINNED_MESH, mesh
    try:
        yield
    finally:
        _PINNED_MESH = prev


def get_pinned_mesh() -> Optional[Mesh]:
    return _PINNED_MESH


def data_sharding(mesh: Mesh, batch_axes: Sequence[str] = (DATA_AXIS,)) -> NamedSharding:
    """Sharding for input batches: leading dim split over data(-like) axes."""
    return NamedSharding(mesh, PartitionSpec(tuple(batch_axes)))


def axes_size(mesh_shape, axes) -> int:
    """Product of the named axes' sizes in a mesh-shape mapping (absent
    axes count 1). The ONE definition of how an axes tuple maps to a
    shard count — the ZeRO partitioner, ParamGatherPlan's wire model /
    qerr weighting, and the memory ledger must all agree on it (accepts
    both ``mesh.shape`` and plain dicts)."""
    n = 1
    for a in axes:
        n *= int(mesh_shape.get(a, 1))
    return n


def data_like_axes(mesh: Mesh) -> tuple:
    """The mesh's data-parallel axes with size > 1 (dcn-outer + ici
    data), falling back to ``(data,)`` on a trivial mesh — the ONE
    definition of "data-like" shared by the sparse-gradient exchange and
    the engine surgery."""
    axes = tuple(a for a in (DCN_AXIS, DATA_AXIS)
                 if mesh.shape.get(a, 1) > 1)
    return axes or (DATA_AXIS,)


def moe_dispatch_axes(mesh: Mesh) -> tuple:
    """Manual axes of the explicit MoE dispatch region (moe/dispatch.py):
    the data-like token axes plus ``expert``. Tokens are sharded over the
    full tuple inside the region — expert parallelism is carved out of
    the data-parallel world, exactly the reference's expert process
    groups — and the all-to-all runs over ``expert`` within each
    data-like column."""
    return data_like_axes(mesh) + (EXPERT_AXIS,)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape.get(axis, 1)


def local_batch_ranks(mesh: Mesh) -> List[int]:
    """Global data-parallel positions handled by this process (for samplers)."""
    # With jit + NamedSharding, each process feeds its addressable shards;
    # data loading uses process_index/process_count granularity.
    return [jax.process_index()]
