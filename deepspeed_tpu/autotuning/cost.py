"""The projected-speed ranking behind trial selection.

Trials are the ground truth — this model only decides WHICH top-K
candidates earn one, so it is built from the pieces the tree already
trusts rather than a new estimator: the compute floor divides the default
step's XLA ``cost_analysis`` flops/bytes by the per-chip peak tables in
``profiling/flops_profiler`` (the same denominators every MFU in the tree
uses), and the wire term instantiates the REAL
``comm/grad_sync.GradSyncPlan`` / ``ParamGatherPlan`` on shape-only
templates and asks for their modeled exposed/wire seconds — one modeled
wire formula in the tree, not a copy. Host arithmetic only: no device
work, no compilation per candidate.
"""

from typing import Any, Dict, Optional

import numpy as np

from deepspeed_tpu.utils.logging import logger


# Scale compute_floor_seconds ranks on when the device has no peak entry.
_RANKING_NOMINAL_KIND = "TPU v5 lite"


def step_flops_bytes(engine, batches, lr) -> Dict[str, float]:
    """flops / bytes-accessed of the engine's CURRENT fused step, from
    the compiled executable's cost analysis (the XLA compilation cache
    dedupes the binary against the step the engine runs anyway)."""
    lowered = engine._train_step.lower(engine.state, batches, lr)
    cost = lowered.compile().cost_analysis() or {}
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0))}


def compute_floor_seconds(flops: float, bytes_accessed: float,
                          n_chips: int, device_kind: Optional[str],
                          dtype: str) -> float:
    """Roofline floor of the whole global step: the slower of the
    compute and HBM ceilings at the chip-kind peaks (flops_profiler's
    tables — the one source every MFU divides by). A device kind with
    no table entry (the CPU test mesh) is ranked on the v5e scale: the
    result only orders candidates against each other and never leaves
    the ranking, so a nominal scale is enough there."""
    from deepspeed_tpu.profiling.flops_profiler import (peak_hbm_gbps,
                                                        peak_tflops)

    chips = max(int(n_chips), 1)
    tflops = (peak_tflops(device_kind, dtype)
              or peak_tflops(_RANKING_NOMINAL_KIND, dtype))
    gbps = peak_hbm_gbps(device_kind) or peak_hbm_gbps(_RANKING_NOMINAL_KIND)
    f = flops / (chips * tflops * 1e12) if flops > 0 else 0.0
    b = bytes_accessed / (chips * gbps * 1e9) if bytes_accessed > 0 else 0.0
    return max(f, b)


def modeled_wire_seconds(cand_cfg, mesh, param_shapes, base_specs,
                         acc_dtype, comm_dtype, gas: int) -> float:
    """Exposed wire seconds of the candidate's explicit collectives —
    the grad-sync hop (GradSyncPlan.modeled_exposed_seconds: overlap-
    aware) plus the ZeRO++ param gather (fully exposed by construction,
    ParamGatherPlan.modeled_wire_seconds). Shape-only templates; 0.0
    when neither strategy engages (the implicit pjit path is modeled
    inside the step's own bytes)."""
    import jax

    from deepspeed_tpu.comm.grad_sync import (GradSyncPlan, ParamGatherPlan,
                                              resolve_hierarchical,
                                              resolve_overlap)
    from deepspeed_tpu.runtime.zero.partition import ZeroPartitioner

    total = 0.0
    partitioner = ZeroPartitioner(mesh, cand_cfg.zero_config)

    def sds_tree(dtype):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(
                tuple(getattr(l, "shape", ()) or ()), dtype), param_shapes)

    try:
        on, _ = resolve_hierarchical(cand_cfg.comm, mesh,
                                     needs_local_grads=False,
                                     sparse_gradients=False, pipe_stages=1)
    except Exception:  # noqa: BLE001 — comm.hierarchical=on blockers
        on = False
    if on:
        try:
            template = sds_tree(acc_dtype)
            plan = GradSyncPlan(
                cand_cfg.comm, mesh, grad_template=template,
                grad_specs=partitioner.grad_specs(template, base_specs),
                acc_dtype=acc_dtype, ici_dtype=comm_dtype, gas=int(gas),
                overlap=resolve_overlap(cand_cfg.comm))
            total += float(plan.modeled_exposed_seconds())
        except Exception as e:  # noqa: BLE001 — ranking must never kill
            logger.warning("autotune cost model: grad-sync wire model "
                           "failed (%s) — candidate ranked compute-only", e)
    zpp = cand_cfg.zero_config.zeropp
    if getattr(zpp, "active", False) and cand_cfg.zero_config.stage >= 2:
        try:
            template = sds_tree(np.float32)
            plan = ParamGatherPlan(
                zpp, mesh, param_template=template,
                param_specs=partitioner.param_specs(template, base_specs))
            total += float(plan.modeled_wire_seconds(
                cand_cfg.comm.dcn_gbps, cand_cfg.comm.ici_gbps))
        except Exception as e:  # noqa: BLE001
            logger.warning("autotune cost model: param-gather wire model "
                           "failed (%s) — candidate ranked without it", e)
    return total


def modeled_candidate_cost(engine, cand_cfg, gas: int,
                           flops_bytes: Dict[str, float]) -> Dict[str, Any]:
    """Per-candidate modeled step seconds: shared compute floor + the
    candidate's own exposed wire term. Candidates that differ only in
    knobs the model cannot see (micro x gas on a one-chip mesh) tie and
    keep enumeration order — the measured trial breaks the tie."""
    import jax

    dev = jax.devices()[0]
    compute = compute_floor_seconds(
        flops_bytes.get("flops", 0.0),
        flops_bytes.get("bytes_accessed", 0.0),
        n_chips=engine.mesh.size,
        device_kind=getattr(dev, "device_kind", ""),
        dtype=engine.precision.name)
    wire = modeled_wire_seconds(
        cand_cfg, engine.mesh, engine.state.params, engine._base_specs,
        acc_dtype=engine.grad_accum_dtype,
        comm_dtype=engine._comm_dtype or engine.grad_accum_dtype,
        gas=gas)
    return {"compute_sec": compute, "wire_sec": wire,
            "modeled_sec": compute + wire}
