"""Top-level config system.

Parity with the reference ``DeepSpeedConfig`` (``deepspeed/runtime/config.py:655``):
one JSON document (path or dict) parsed into typed sub-configs, including the
three-way batch-size constraint solver
``train_batch_size = micro_batch_per_device × gradient_accumulation_steps × dp_world_size``
(reference ``config.py:822-893``).

TPU-first deltas:
- a ``bf16`` block is first-class and is the preferred precision (no loss
  scaling required); ``fp16`` is kept for config-compat and engages the
  dynamic loss scaler.
- a ``mesh`` block declares named parallel axes (data/model/pipe/sequence/
  expert) — the reference delegated TP shape to an external Megatron ``mpu``.
"""

import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Union

from deepspeed_tpu.config import constants as C
from deepspeed_tpu.runtime.zero.config import ZeroConfig


class ConfigError(ValueError):
    pass


def _get(d: Dict[str, Any], key: str, default: Any) -> Any:
    v = d.get(key, default)
    return default if v is None else v


@dataclass
class FP16Config:
    enabled: bool = False
    loss_scale: float = 0.0  # 0 => dynamic
    initial_scale_power: int = C.FP16_INITIAL_SCALE_POWER_DEFAULT
    loss_scale_window: int = C.FP16_LOSS_SCALE_WINDOW_DEFAULT
    hysteresis: int = C.FP16_HYSTERESIS_DEFAULT
    min_loss_scale: float = C.FP16_MIN_LOSS_SCALE_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "FP16Config":
        d = d or {}
        return cls(
            enabled=bool(_get(d, C.FP16_ENABLED, False)),
            loss_scale=float(_get(d, C.FP16_LOSS_SCALE, 0.0)),
            initial_scale_power=int(_get(d, C.FP16_INITIAL_SCALE_POWER,
                                         C.FP16_INITIAL_SCALE_POWER_DEFAULT)),
            loss_scale_window=int(_get(d, C.FP16_LOSS_SCALE_WINDOW,
                                       C.FP16_LOSS_SCALE_WINDOW_DEFAULT)),
            hysteresis=int(_get(d, C.FP16_HYSTERESIS, C.FP16_HYSTERESIS_DEFAULT)),
            min_loss_scale=float(_get(d, C.FP16_MIN_LOSS_SCALE,
                                      C.FP16_MIN_LOSS_SCALE_DEFAULT)),
        )

    @property
    def dynamic_loss_scale(self) -> bool:
        return self.loss_scale == 0.0


@dataclass
class ActivationCheckpointingConfig:
    partition_activations: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    cpu_checkpointing: bool = False

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "ActivationCheckpointingConfig":
        d = d or {}
        return cls(
            partition_activations=bool(_get(d, C.ACT_CHKPT_PARTITION_ACTIVATIONS, False)),
            contiguous_memory_optimization=bool(
                _get(d, C.ACT_CHKPT_CONTIGUOUS_MEMORY_OPTIMIZATION, False)),
            number_checkpoints=d.get(C.ACT_CHKPT_NUMBER_CHECKPOINTS),
            synchronize_checkpoint_boundary=bool(
                _get(d, C.ACT_CHKPT_SYNCHRONIZE_CHECKPOINT_BOUNDARY, False)),
            profile=bool(_get(d, C.ACT_CHKPT_PROFILE, False)),
            cpu_checkpointing=bool(_get(d, C.ACT_CHKPT_CPU_CHECKPOINTING, False)),
        )


@dataclass
class FlopsProfilerConfig:
    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "FlopsProfilerConfig":
        d = d or {}
        return cls(
            enabled=bool(_get(d, C.FLOPS_PROFILER_ENABLED, False)),
            profile_step=int(_get(d, C.FLOPS_PROFILER_PROFILE_STEP, 1)),
            module_depth=int(_get(d, C.FLOPS_PROFILER_MODULE_DEPTH, -1)),
            top_modules=int(_get(d, C.FLOPS_PROFILER_TOP_MODULES, 1)),
            detailed=bool(_get(d, C.FLOPS_PROFILER_DETAILED, True)),
            output_file=d.get(C.FLOPS_PROFILER_OUTPUT_FILE),
        )


@dataclass
class PLDConfig:
    enabled: bool = False
    theta: float = 1.0
    gamma: float = 0.001

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "PLDConfig":
        d = d or {}
        return cls(enabled=bool(_get(d, C.PLD_ENABLED, False)),
                   theta=float(_get(d, C.PLD_THETA, 1.0)),
                   gamma=float(_get(d, C.PLD_GAMMA, 0.001)))


@dataclass
class ResilienceCheckpointConfig:
    """The async-checkpoint knobs (resilience/checkpoint.py)."""

    dir: str = ""
    interval: int = C.RESILIENCE_CKPT_INTERVAL_DEFAULT
    keep_last: int = C.RESILIENCE_CKPT_KEEP_LAST_DEFAULT
    max_retries: int = C.RESILIENCE_CKPT_MAX_RETRIES_DEFAULT
    backoff_seconds: float = C.RESILIENCE_CKPT_BACKOFF_DEFAULT
    async_write: bool = C.RESILIENCE_CKPT_ASYNC_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "ResilienceCheckpointConfig":
        d = d or {}
        cfg = cls(
            dir=str(_get(d, C.RESILIENCE_CKPT_DIR, "")),
            interval=int(_get(d, C.RESILIENCE_CKPT_INTERVAL,
                              C.RESILIENCE_CKPT_INTERVAL_DEFAULT)),
            keep_last=int(_get(d, C.RESILIENCE_CKPT_KEEP_LAST,
                               C.RESILIENCE_CKPT_KEEP_LAST_DEFAULT)),
            max_retries=int(_get(d, C.RESILIENCE_CKPT_MAX_RETRIES,
                                 C.RESILIENCE_CKPT_MAX_RETRIES_DEFAULT)),
            backoff_seconds=float(_get(d, C.RESILIENCE_CKPT_BACKOFF,
                                       C.RESILIENCE_CKPT_BACKOFF_DEFAULT)),
            async_write=bool(_get(d, C.RESILIENCE_CKPT_ASYNC,
                                  C.RESILIENCE_CKPT_ASYNC_DEFAULT)),
        )
        if cfg.interval < 1:
            raise ConfigError("resilience.checkpoint.interval must be >= 1")
        if cfg.keep_last < 1:
            raise ConfigError("resilience.checkpoint.keep_last must be >= 1")
        if cfg.max_retries < 0:
            raise ConfigError("resilience.checkpoint.max_retries must be >= 0")
        return cfg


@dataclass
class ResilienceConfig:
    """Preemption-aware training (resilience/): auto checkpointing every
    ``checkpoint.interval`` steps off the step path, auto-resume from the
    newest complete manifest, and a deterministic fault-injection plan
    (``fault_injection`` keys = FaultPlan fields; ``DSTPU_FAULT_PLAN`` env
    JSON overrides them)."""

    enabled: bool = False
    checkpoint: ResilienceCheckpointConfig = field(
        default_factory=ResilienceCheckpointConfig)
    auto_resume: bool = C.RESILIENCE_AUTO_RESUME_DEFAULT
    fault_injection: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "ResilienceConfig":
        d = d or {}
        cfg = cls(
            enabled=bool(_get(d, C.RESILIENCE_ENABLED, False)),
            checkpoint=ResilienceCheckpointConfig.from_dict(
                d.get(C.RESILIENCE_CHECKPOINT)),
            auto_resume=bool(_get(d, C.RESILIENCE_AUTO_RESUME,
                                  C.RESILIENCE_AUTO_RESUME_DEFAULT)),
            fault_injection=dict(d.get(C.RESILIENCE_FAULT_INJECTION) or {}),
        )
        if cfg.enabled and not cfg.checkpoint.dir:
            raise ConfigError(
                "resilience.enabled requires resilience.checkpoint.dir "
                "(where manifests/shards are committed)")
        return cfg


@dataclass
class LiveEvictionConfig:
    """Straggler-eviction knobs of the live-elasticity loop
    (resilience/elastic.py): evict a fleet-flagged persistent straggler
    only when the goodput cost model says the projected throughput gain
    over ``horizon_steps`` beats ``min_gain_factor`` x the measured
    in-process reshard cost."""

    enabled: bool = C.ELASTICITY_LIVE_EVICTION_ENABLED_DEFAULT
    horizon_steps: int = C.ELASTICITY_LIVE_EVICTION_HORIZON_DEFAULT
    min_gain_factor: float = C.ELASTICITY_LIVE_EVICTION_MIN_GAIN_DEFAULT
    assumed_reshard_sec: float = \
        C.ELASTICITY_LIVE_EVICTION_ASSUMED_RESHARD_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "LiveEvictionConfig":
        d = d or {}
        cfg = cls(
            enabled=bool(_get(d, C.ELASTICITY_LIVE_EVICTION_ENABLED,
                              C.ELASTICITY_LIVE_EVICTION_ENABLED_DEFAULT)),
            horizon_steps=int(_get(d, C.ELASTICITY_LIVE_EVICTION_HORIZON,
                                   C.ELASTICITY_LIVE_EVICTION_HORIZON_DEFAULT)),
            min_gain_factor=float(_get(
                d, C.ELASTICITY_LIVE_EVICTION_MIN_GAIN,
                C.ELASTICITY_LIVE_EVICTION_MIN_GAIN_DEFAULT)),
            assumed_reshard_sec=float(_get(
                d, C.ELASTICITY_LIVE_EVICTION_ASSUMED_RESHARD,
                C.ELASTICITY_LIVE_EVICTION_ASSUMED_RESHARD_DEFAULT)),
        )
        if cfg.horizon_steps < 1:
            raise ConfigError(
                "elasticity.live.eviction.horizon_steps must be >= 1")
        if cfg.min_gain_factor <= 0:
            raise ConfigError(
                "elasticity.live.eviction.min_gain_factor must be > 0")
        if cfg.assumed_reshard_sec <= 0:
            raise ConfigError(
                "elasticity.live.eviction.assumed_reshard_sec must be > 0")
        return cfg


@dataclass
class LiveElasticityConfig:
    """``elasticity.live`` — in-process live elasticity
    (resilience/elastic.py; docs/RESILIENCE.md "Live elasticity"): catch
    the preemption advance warning (SIGTERM inside ``grace_seconds``),
    drain, reshard onto the surviving chips in the SAME process, re-admit
    a returning slice at the next snapshot boundary, and close the
    straggler-eviction loop. Disabled (the default) is provably free: no
    signal handler installed, zero extra syncs, lowered step unchanged."""

    enabled: bool = C.ELASTICITY_LIVE_ENABLED_DEFAULT
    grace_seconds: float = C.ELASTICITY_LIVE_GRACE_DEFAULT
    check_interval_steps: int = C.ELASTICITY_LIVE_CHECK_INTERVAL_DEFAULT
    exit_code: int = C.ELASTIC_PREEMPT_EXIT_CODE_DEFAULT
    eviction: LiveEvictionConfig = field(default_factory=LiveEvictionConfig)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "LiveElasticityConfig":
        d = d or {}
        cfg = cls(
            enabled=bool(_get(d, C.ELASTICITY_LIVE_ENABLED,
                              C.ELASTICITY_LIVE_ENABLED_DEFAULT)),
            grace_seconds=float(_get(d, C.ELASTICITY_LIVE_GRACE,
                                     C.ELASTICITY_LIVE_GRACE_DEFAULT)),
            check_interval_steps=int(_get(
                d, C.ELASTICITY_LIVE_CHECK_INTERVAL,
                C.ELASTICITY_LIVE_CHECK_INTERVAL_DEFAULT)),
            exit_code=int(_get(d, C.ELASTICITY_LIVE_EXIT_CODE,
                               C.ELASTIC_PREEMPT_EXIT_CODE_DEFAULT)),
            eviction=LiveEvictionConfig.from_dict(
                d.get(C.ELASTICITY_LIVE_EVICTION)),
        )
        if cfg.enabled and cfg.grace_seconds <= 0:
            raise ConfigError("elasticity.live.grace_seconds must be > 0")
        if cfg.check_interval_steps < 1:
            raise ConfigError(
                "elasticity.live.check_interval_steps must be >= 1")
        if not 0 < cfg.exit_code < 256:
            raise ConfigError(
                "elasticity.live.exit_code must be in 1..255")
        return cfg


@dataclass
class GuardrailsDetectorConfig:
    """Anomaly-detector knobs (guardrails/detector.py)."""

    zscore_threshold: float = C.GUARDRAILS_DET_ZSCORE_DEFAULT
    warmup_steps: int = C.GUARDRAILS_DET_WARMUP_DEFAULT
    ewma_alpha: float = C.GUARDRAILS_DET_EWMA_ALPHA_DEFAULT
    track_grad_norm: bool = C.GUARDRAILS_DET_TRACK_GRAD_NORM_DEFAULT
    # In-device skip-on-nonfinite-grads for bf16/fp32 runs (the fp16 path
    # already has the loss-scaler skip). Default OFF: the predicate rides
    # inside the jitted step, so the gate must be an explicit opt-in.
    check_nonfinite_grads: bool = C.GUARDRAILS_DET_NONFINITE_GRADS_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "GuardrailsDetectorConfig":
        d = d or {}
        cfg = cls(
            zscore_threshold=float(_get(d, C.GUARDRAILS_DET_ZSCORE,
                                        C.GUARDRAILS_DET_ZSCORE_DEFAULT)),
            warmup_steps=int(_get(d, C.GUARDRAILS_DET_WARMUP,
                                  C.GUARDRAILS_DET_WARMUP_DEFAULT)),
            ewma_alpha=float(_get(d, C.GUARDRAILS_DET_EWMA_ALPHA,
                                  C.GUARDRAILS_DET_EWMA_ALPHA_DEFAULT)),
            track_grad_norm=bool(_get(d, C.GUARDRAILS_DET_TRACK_GRAD_NORM,
                                      C.GUARDRAILS_DET_TRACK_GRAD_NORM_DEFAULT)),
            check_nonfinite_grads=bool(
                _get(d, C.GUARDRAILS_DET_NONFINITE_GRADS,
                     C.GUARDRAILS_DET_NONFINITE_GRADS_DEFAULT)),
        )
        if cfg.zscore_threshold <= 0:
            raise ConfigError("guardrails.detector.zscore_threshold must be > 0")
        if cfg.warmup_steps < 1:
            raise ConfigError("guardrails.detector.warmup_steps must be >= 1")
        if not 0.0 < cfg.ewma_alpha <= 1.0:
            raise ConfigError("guardrails.detector.ewma_alpha must be in (0, 1]")
        return cfg


@dataclass
class GuardrailsRollbackConfig:
    """In-memory rollback knobs (guardrails/rollback.py)."""

    enabled: bool = C.GUARDRAILS_RB_ENABLED_DEFAULT
    snapshot_interval: int = C.GUARDRAILS_RB_SNAPSHOT_INTERVAL_DEFAULT
    ring_size: int = C.GUARDRAILS_RB_RING_SIZE_DEFAULT
    consecutive_spikes: int = C.GUARDRAILS_RB_CONSECUTIVE_SPIKES_DEFAULT
    skip_batches: int = C.GUARDRAILS_RB_SKIP_BATCHES_DEFAULT
    lr_decay: float = C.GUARDRAILS_RB_LR_DECAY_DEFAULT
    max_rollbacks: int = C.GUARDRAILS_RB_MAX_ROLLBACKS_DEFAULT
    escalate_to_disk: bool = C.GUARDRAILS_RB_ESCALATE_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "GuardrailsRollbackConfig":
        d = d or {}
        cfg = cls(
            enabled=bool(_get(d, C.GUARDRAILS_RB_ENABLED,
                              C.GUARDRAILS_RB_ENABLED_DEFAULT)),
            snapshot_interval=int(_get(d, C.GUARDRAILS_RB_SNAPSHOT_INTERVAL,
                                       C.GUARDRAILS_RB_SNAPSHOT_INTERVAL_DEFAULT)),
            ring_size=int(_get(d, C.GUARDRAILS_RB_RING_SIZE,
                               C.GUARDRAILS_RB_RING_SIZE_DEFAULT)),
            consecutive_spikes=int(_get(d, C.GUARDRAILS_RB_CONSECUTIVE_SPIKES,
                                        C.GUARDRAILS_RB_CONSECUTIVE_SPIKES_DEFAULT)),
            skip_batches=int(_get(d, C.GUARDRAILS_RB_SKIP_BATCHES,
                                  C.GUARDRAILS_RB_SKIP_BATCHES_DEFAULT)),
            lr_decay=float(_get(d, C.GUARDRAILS_RB_LR_DECAY,
                                C.GUARDRAILS_RB_LR_DECAY_DEFAULT)),
            max_rollbacks=int(_get(d, C.GUARDRAILS_RB_MAX_ROLLBACKS,
                                   C.GUARDRAILS_RB_MAX_ROLLBACKS_DEFAULT)),
            escalate_to_disk=bool(_get(d, C.GUARDRAILS_RB_ESCALATE,
                                       C.GUARDRAILS_RB_ESCALATE_DEFAULT)),
        )
        if cfg.snapshot_interval < 1:
            raise ConfigError("guardrails.rollback.snapshot_interval must be >= 1")
        if cfg.ring_size < 1:
            raise ConfigError("guardrails.rollback.ring_size must be >= 1")
        if cfg.consecutive_spikes < 1:
            raise ConfigError("guardrails.rollback.consecutive_spikes must be >= 1")
        if cfg.skip_batches < 0:
            raise ConfigError("guardrails.rollback.skip_batches must be >= 0")
        if not 0.0 < cfg.lr_decay <= 1.0:
            raise ConfigError("guardrails.rollback.lr_decay must be in (0, 1]")
        if cfg.max_rollbacks < 1:
            raise ConfigError("guardrails.rollback.max_rollbacks must be >= 1")
        return cfg


@dataclass
class GuardrailsWatchdogConfig:
    """Step-deadline watchdog knobs (guardrails/watchdog.py)."""

    enabled: bool = C.GUARDRAILS_WD_ENABLED_DEFAULT
    step_timeout_seconds: float = C.GUARDRAILS_WD_TIMEOUT_DEFAULT
    poll_interval_seconds: Optional[float] = None
    crashdump_dir: str = C.GUARDRAILS_WD_CRASHDUMP_DIR_DEFAULT
    exit_code: int = C.GUARDRAILS_WATCHDOG_EXIT_CODE_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "GuardrailsWatchdogConfig":
        d = d or {}
        cfg = cls(
            enabled=bool(_get(d, C.GUARDRAILS_WD_ENABLED,
                              C.GUARDRAILS_WD_ENABLED_DEFAULT)),
            step_timeout_seconds=float(_get(d, C.GUARDRAILS_WD_TIMEOUT,
                                            C.GUARDRAILS_WD_TIMEOUT_DEFAULT)),
            poll_interval_seconds=(
                float(d[C.GUARDRAILS_WD_POLL])
                if d.get(C.GUARDRAILS_WD_POLL) is not None else None),
            crashdump_dir=str(_get(d, C.GUARDRAILS_WD_CRASHDUMP_DIR,
                                   C.GUARDRAILS_WD_CRASHDUMP_DIR_DEFAULT)),
            exit_code=int(_get(d, C.GUARDRAILS_WD_EXIT_CODE,
                               C.GUARDRAILS_WATCHDOG_EXIT_CODE_DEFAULT)),
        )
        if cfg.enabled and cfg.step_timeout_seconds <= 0:
            raise ConfigError(
                "guardrails.watchdog.step_timeout_seconds must be > 0")
        if (cfg.poll_interval_seconds is not None
                and float(cfg.poll_interval_seconds) <= 0):
            raise ConfigError(
                "guardrails.watchdog.poll_interval_seconds must be > 0 "
                "(a non-positive poll busy-spins the watchdog thread)")
        if not 0 < cfg.exit_code < 256:
            raise ConfigError("guardrails.watchdog.exit_code must be in 1..255")
        return cfg


@dataclass
class GuardrailsConfig:
    """Unattended-training guardrails (guardrails/; docs/RESILIENCE.md
    "Guardrails"): EWMA/z-score anomaly detection over loss + grad norm,
    in-memory rollback from a bounded snapshot ring, and a step-deadline
    watchdog. Disabled (the default) the engine takes the exact pre-
    guardrails step path: no host fetches, no device syncs, no snapshots."""

    enabled: bool = False
    detector: GuardrailsDetectorConfig = field(
        default_factory=GuardrailsDetectorConfig)
    rollback: GuardrailsRollbackConfig = field(
        default_factory=GuardrailsRollbackConfig)
    watchdog: GuardrailsWatchdogConfig = field(
        default_factory=GuardrailsWatchdogConfig)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "GuardrailsConfig":
        d = d or {}
        return cls(
            enabled=bool(_get(d, C.GUARDRAILS_ENABLED, False)),
            detector=GuardrailsDetectorConfig.from_dict(
                d.get(C.GUARDRAILS_DETECTOR)),
            rollback=GuardrailsRollbackConfig.from_dict(
                d.get(C.GUARDRAILS_ROLLBACK)),
            watchdog=GuardrailsWatchdogConfig.from_dict(
                d.get(C.GUARDRAILS_WATCHDOG)),
        )

    @property
    def nonfinite_grad_check(self) -> bool:
        """The jitted-step gate: bf16/fp32 skip-on-nonfinite is active only
        when guardrails are on AND the detector opted in."""
        return self.enabled and self.detector.check_nonfinite_grads


@dataclass
class MeshConfig:
    """Named parallel axes. Sizes of 1 mean the axis is unused.

    ``data`` may be -1 (infer: world_size // product(other axes)).
    """

    data: int = -1
    model: int = 1
    pipe: int = 1
    sequence: int = 1
    expert: int = 1
    slices: int = 1     # DCN-outer slice count (multi-slice/multi-pod)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "MeshConfig":
        d = d or {}
        cfg = cls(
            data=int(_get(d, C.MESH_DATA, -1)),
            model=int(_get(d, C.MESH_MODEL, 1)),
            pipe=int(_get(d, C.MESH_PIPE, 1)),
            sequence=int(_get(d, C.MESH_SEQUENCE, 1)),
            expert=int(_get(d, C.MESH_EXPERT, 1)),
            slices=int(_get(d, C.MESH_SLICES, 1)),
        )
        for name in ("model", "pipe", "sequence", "expert", "slices"):
            if getattr(cfg, name) < 1:
                raise ConfigError(f"mesh.{name} must be >= 1")
        return cfg

    def resolve_data(self, world_size: int) -> int:
        fixed = (self.model * self.pipe * self.sequence * self.expert *
                 self.slices)
        if world_size % fixed != 0:
            raise ConfigError(
                f"world size {world_size} not divisible by mesh axes product {fixed}")
        data = world_size // fixed
        if self.data not in (-1, data):
            raise ConfigError(
                f"mesh.data={self.data} inconsistent with world={world_size}, "
                f"slices×model×pipe×sequence×expert={fixed}")
        # The GLOBAL data-parallel degree spans both the ICI-inner `data`
        # axis and the DCN-outer `dcn` axis (batches shard over both).
        return data * self.slices


@dataclass
class CommConfig:
    """``comm`` block — the hierarchical quantized gradient-sync strategy
    (comm/grad_sync.py, docs/PERFORMANCE.md).

    ``hierarchical``: ``auto`` engages the explicit bucketed sync on
    multi-slice (dcn > 1) meshes when the step path supports it; ``on``
    forces it (raising on incompatible configurations); ``off`` keeps
    today's implicit pjit resharding, bit-identical.
    ``dcn_quant_bits``: the DCN wire dtype — 8 (blockwise int8 + per-block
    fp32 scales), 16 (bf16 passthrough) or 32 (fp32 passthrough).
    ``quant_block_size``: elements per quantization block (per-block
    absmax scale granularity).
    ``bucket_mb``: flat gradient bucket size in MiB (the unit of the ICI
    reduce-scatter and DCN all-reduce).
    ``overlap_grad_sync``: the overlapped schedule (docs/PERFORMANCE.md
    "Overlapped gradient sync") — readiness-ordered per-bucket ICI
    reduce-scatter during backward plus a double-buffered per-microstep
    DCN all-reduce. ``auto`` (default) engages whenever the hierarchical
    sync does; ``off`` keeps the GAS-boundary schedule; ``on`` is
    explicit opt-in (same effect as auto — the incompatible paths are
    already excluded at ``hierarchical`` resolution).
    ``ici_gbps`` / ``dcn_gbps``: nominal per-device link bandwidths behind
    the modeled ``comm/exposed_frac`` device-time attribution
    (docs/OBSERVABILITY.md "Fleet observability").
    """

    hierarchical: str = C.COMM_HIERARCHICAL_DEFAULT
    dcn_quant_bits: int = C.COMM_DCN_QUANT_BITS_DEFAULT
    quant_block_size: int = C.COMM_QUANT_BLOCK_SIZE_DEFAULT
    bucket_mb: float = C.COMM_BUCKET_MB_DEFAULT
    overlap_grad_sync: str = C.COMM_OVERLAP_GRAD_SYNC_DEFAULT
    ici_gbps: float = C.COMM_ICI_GBPS_DEFAULT
    dcn_gbps: float = C.COMM_DCN_GBPS_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "CommConfig":
        d = d or {}
        cfg = cls(
            hierarchical=str(_get(d, C.COMM_HIERARCHICAL,
                                  C.COMM_HIERARCHICAL_DEFAULT)).lower(),
            dcn_quant_bits=int(_get(d, C.COMM_DCN_QUANT_BITS,
                                    C.COMM_DCN_QUANT_BITS_DEFAULT)),
            quant_block_size=int(_get(d, C.COMM_QUANT_BLOCK_SIZE,
                                      C.COMM_QUANT_BLOCK_SIZE_DEFAULT)),
            bucket_mb=float(_get(d, C.COMM_BUCKET_MB,
                                 C.COMM_BUCKET_MB_DEFAULT)),
            overlap_grad_sync=str(_get(
                d, C.COMM_OVERLAP_GRAD_SYNC,
                C.COMM_OVERLAP_GRAD_SYNC_DEFAULT)).lower(),
            ici_gbps=float(_get(d, C.COMM_ICI_GBPS,
                                C.COMM_ICI_GBPS_DEFAULT)),
            dcn_gbps=float(_get(d, C.COMM_DCN_GBPS,
                                C.COMM_DCN_GBPS_DEFAULT)),
        )
        if cfg.hierarchical not in ("auto", "on", "off"):
            raise ConfigError(
                f"comm.hierarchical must be auto|on|off, got "
                f"'{cfg.hierarchical}'")
        if cfg.dcn_quant_bits not in (8, 16, 32):
            raise ConfigError(
                f"comm.dcn_quant_bits must be 8 (int8), 16 (bf16) or 32 "
                f"(fp32), got {cfg.dcn_quant_bits}")
        if cfg.quant_block_size <= 0:
            raise ConfigError(
                f"comm.quant_block_size must be positive, got "
                f"{cfg.quant_block_size}")
        if cfg.bucket_mb <= 0:
            raise ConfigError(
                f"comm.bucket_mb must be positive, got {cfg.bucket_mb}")
        if cfg.overlap_grad_sync not in ("auto", "on", "off"):
            raise ConfigError(
                f"comm.overlap_grad_sync must be auto|on|off, got "
                f"'{cfg.overlap_grad_sync}'")
        if cfg.ici_gbps <= 0 or cfg.dcn_gbps <= 0:
            raise ConfigError(
                f"comm.ici_gbps/dcn_gbps must be positive, got "
                f"{cfg.ici_gbps}/{cfg.dcn_gbps}")
        return cfg


@dataclass
class AIOConfig:
    block_size: int = C.AIO_BLOCK_SIZE_DEFAULT
    queue_depth: int = C.AIO_QUEUE_DEPTH_DEFAULT
    thread_count: int = C.AIO_THREAD_COUNT_DEFAULT
    single_submit: bool = C.AIO_SINGLE_SUBMIT_DEFAULT
    overlap_events: bool = C.AIO_OVERLAP_EVENTS_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "AIOConfig":
        d = d or {}
        return cls(
            block_size=int(_get(d, C.AIO_BLOCK_SIZE, C.AIO_BLOCK_SIZE_DEFAULT)),
            queue_depth=int(_get(d, C.AIO_QUEUE_DEPTH, C.AIO_QUEUE_DEPTH_DEFAULT)),
            thread_count=int(_get(d, C.AIO_THREAD_COUNT, C.AIO_THREAD_COUNT_DEFAULT)),
            single_submit=bool(_get(d, C.AIO_SINGLE_SUBMIT, C.AIO_SINGLE_SUBMIT_DEFAULT)),
            overlap_events=bool(_get(d, C.AIO_OVERLAP_EVENTS, C.AIO_OVERLAP_EVENTS_DEFAULT)),
        )


@dataclass
class TelemetryTraceConfig:
    """Step tracer knobs (telemetry/tracer.py)."""

    enabled: bool = C.TELEMETRY_TRACE_ENABLED_DEFAULT
    file: str = C.TELEMETRY_TRACE_FILE_DEFAULT
    sync_spans: bool = C.TELEMETRY_TRACE_SYNC_SPANS_DEFAULT
    jax_profiler_dir: Optional[str] = None

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "TelemetryTraceConfig":
        d = d or {}
        return cls(
            enabled=bool(_get(d, C.TELEMETRY_TRACE_ENABLED,
                              C.TELEMETRY_TRACE_ENABLED_DEFAULT)),
            file=str(_get(d, C.TELEMETRY_TRACE_FILE,
                          C.TELEMETRY_TRACE_FILE_DEFAULT)),
            sync_spans=bool(_get(d, C.TELEMETRY_TRACE_SYNC_SPANS,
                                 C.TELEMETRY_TRACE_SYNC_SPANS_DEFAULT)),
            jax_profiler_dir=d.get(C.TELEMETRY_TRACE_JAX_PROFILER_DIR),
        )


@dataclass
class TelemetryMetricsConfig:
    """Metrics registry sinks (telemetry/registry.py)."""

    sinks: tuple = C.TELEMETRY_METRICS_SINKS_DEFAULT
    file: str = C.TELEMETRY_METRICS_FILE_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "TelemetryMetricsConfig":
        d = d or {}
        sinks = tuple(_get(d, C.TELEMETRY_METRICS_SINKS,
                           C.TELEMETRY_METRICS_SINKS_DEFAULT))
        for s in sinks:
            if s not in C.TELEMETRY_METRICS_VALID_SINKS:
                raise ConfigError(
                    f"telemetry.metrics.sinks: unknown sink {s!r} (valid: "
                    f"{list(C.TELEMETRY_METRICS_VALID_SINKS)})")
        return cls(sinks=sinks,
                   file=str(_get(d, C.TELEMETRY_METRICS_FILE,
                                 C.TELEMETRY_METRICS_FILE_DEFAULT)))


@dataclass
class TelemetryFleetConfig:
    """Fleet observability knobs (telemetry/fleet.py): cross-host metric
    aggregation at flush boundaries + rolling-window straggler detection.
    Default off — enabled it adds one tiny jitted all-gather and one host
    fetch per flush (never on the step path)."""

    enabled: bool = C.TELEMETRY_FLEET_ENABLED_DEFAULT
    window: int = C.TELEMETRY_FLEET_WINDOW_DEFAULT
    min_window: int = C.TELEMETRY_FLEET_MIN_WINDOW_DEFAULT
    zscore: float = C.TELEMETRY_FLEET_ZSCORE_DEFAULT
    persist: int = C.TELEMETRY_FLEET_PERSIST_DEFAULT
    breakdown_file: str = C.TELEMETRY_FLEET_BREAKDOWN_FILE_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "TelemetryFleetConfig":
        d = d or {}
        cfg = cls(
            enabled=bool(_get(d, C.TELEMETRY_FLEET_ENABLED,
                              C.TELEMETRY_FLEET_ENABLED_DEFAULT)),
            window=int(_get(d, C.TELEMETRY_FLEET_WINDOW,
                            C.TELEMETRY_FLEET_WINDOW_DEFAULT)),
            min_window=int(_get(d, C.TELEMETRY_FLEET_MIN_WINDOW,
                                C.TELEMETRY_FLEET_MIN_WINDOW_DEFAULT)),
            zscore=float(_get(d, C.TELEMETRY_FLEET_ZSCORE,
                              C.TELEMETRY_FLEET_ZSCORE_DEFAULT)),
            persist=int(_get(d, C.TELEMETRY_FLEET_PERSIST,
                             C.TELEMETRY_FLEET_PERSIST_DEFAULT)),
            breakdown_file=str(_get(d, C.TELEMETRY_FLEET_BREAKDOWN_FILE,
                                    C.TELEMETRY_FLEET_BREAKDOWN_FILE_DEFAULT)),
        )
        if cfg.min_window < 1 or cfg.window < cfg.min_window:
            raise ConfigError(
                f"telemetry.fleet: need window >= min_window >= 1, got "
                f"window={cfg.window} min_window={cfg.min_window}")
        if cfg.zscore <= 0:
            raise ConfigError(
                f"telemetry.fleet.zscore must be positive, got {cfg.zscore}")
        if cfg.persist < 1:
            raise ConfigError(
                f"telemetry.fleet.persist must be >= 1, got {cfg.persist}")
        # The supervisor and the stdlib-only fleet_report discover the
        # breakdown by the fleet_breakdown*.json pattern (they cannot see
        # this config) — a name outside it would be written and then
        # silently never read.
        if not (cfg.breakdown_file.startswith("fleet_breakdown")
                and cfg.breakdown_file.endswith(".json")):
            raise ConfigError(
                "telemetry.fleet.breakdown_file must match "
                f"'fleet_breakdown*.json' (readers discover it by that "
                f"pattern), got '{cfg.breakdown_file}'")
        return cfg


@dataclass
class TelemetryMemoryConfig:
    """Memory observatory knobs (telemetry/memory.py): XLA memory
    attribution + model-state ledger + capacity planner + OOM forensics.
    Default off — enabled it adds one AOT lower+compile per step
    function and per-step headroom gauges (riding the HBM stats fetch
    the engine gauges already pay for); never any change to the step
    jaxpr."""

    enabled: bool = C.TELEMETRY_MEMORY_ENABLED_DEFAULT
    headroom_warn_frac: float = C.TELEMETRY_MEMORY_HEADROOM_WARN_FRAC_DEFAULT
    crashdump_dir: str = C.TELEMETRY_MEMORY_CRASHDUMP_DIR_DEFAULT
    oom_exit_code: int = C.MEMORY_OOM_EXIT_CODE_DEFAULT
    plan_at_init: bool = C.TELEMETRY_MEMORY_PLAN_AT_INIT_DEFAULT
    plan_file: str = C.TELEMETRY_MEMORY_PLAN_FILE_DEFAULT
    activation_bytes_per_sample: float = C.TELEMETRY_MEMORY_ACT_BYTES_DEFAULT
    hbm_limit_gb: Optional[float] = None

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> \
            "TelemetryMemoryConfig":
        d = d or {}
        cfg = cls(
            enabled=bool(_get(d, C.TELEMETRY_MEMORY_ENABLED,
                              C.TELEMETRY_MEMORY_ENABLED_DEFAULT)),
            headroom_warn_frac=float(_get(
                d, C.TELEMETRY_MEMORY_HEADROOM_WARN_FRAC,
                C.TELEMETRY_MEMORY_HEADROOM_WARN_FRAC_DEFAULT)),
            crashdump_dir=str(_get(d, C.TELEMETRY_MEMORY_CRASHDUMP_DIR,
                                   C.TELEMETRY_MEMORY_CRASHDUMP_DIR_DEFAULT)),
            oom_exit_code=int(_get(d, C.TELEMETRY_MEMORY_OOM_EXIT_CODE,
                                   C.MEMORY_OOM_EXIT_CODE_DEFAULT)),
            plan_at_init=bool(_get(d, C.TELEMETRY_MEMORY_PLAN_AT_INIT,
                                   C.TELEMETRY_MEMORY_PLAN_AT_INIT_DEFAULT)),
            plan_file=str(_get(d, C.TELEMETRY_MEMORY_PLAN_FILE,
                               C.TELEMETRY_MEMORY_PLAN_FILE_DEFAULT)),
            activation_bytes_per_sample=float(_get(
                d, C.TELEMETRY_MEMORY_ACT_BYTES,
                C.TELEMETRY_MEMORY_ACT_BYTES_DEFAULT)),
            hbm_limit_gb=(float(d[C.TELEMETRY_MEMORY_HBM_LIMIT_GB])
                          if d.get(C.TELEMETRY_MEMORY_HBM_LIMIT_GB)
                          is not None else None),
        )
        if not (0.0 <= cfg.headroom_warn_frac <= 1.0):
            raise ConfigError(
                f"telemetry.memory.headroom_warn_frac must be in [0, 1], "
                f"got {cfg.headroom_warn_frac}")
        if not (1 <= cfg.oom_exit_code <= 255):
            raise ConfigError(
                f"telemetry.memory.oom_exit_code must be in [1, 255], got "
                f"{cfg.oom_exit_code}")
        if cfg.hbm_limit_gb is not None and cfg.hbm_limit_gb <= 0:
            raise ConfigError(
                f"telemetry.memory.hbm_limit_gb must be positive, got "
                f"{cfg.hbm_limit_gb}")
        # The planner file is discovered by pattern by the stdlib-only
        # memory_report (same argument as fleet.breakdown_file).
        if not (cfg.plan_file.startswith("memory_plan")
                and cfg.plan_file.endswith(".json")):
            raise ConfigError(
                "telemetry.memory.plan_file must match 'memory_plan*.json' "
                f"(tools/memory_report.py discovers it by that pattern), "
                f"got '{cfg.plan_file}'")
        return cfg


@dataclass
class TelemetryDevicetimeConfig:
    """Device-time observatory knobs (telemetry/devicetime.py): scheduled
    ``jax.profiler`` captures (``capture_steps`` steps every
    ``every_steps``, host-scoped dirs, keep-last-``keep_last`` GC) parsed
    into measured ``devicetime/*`` attribution, roofline classification
    and ``comm/measured_exposed_frac``. Default off — enabled, all work
    happens at capture boundaries; the in-between step path pays two
    integer comparisons and the step jaxpr never changes."""

    enabled: bool = C.TELEMETRY_DEVICETIME_ENABLED_DEFAULT
    capture_steps: int = C.TELEMETRY_DEVICETIME_CAPTURE_STEPS_DEFAULT
    every_steps: int = C.TELEMETRY_DEVICETIME_EVERY_STEPS_DEFAULT
    keep_last: int = C.TELEMETRY_DEVICETIME_KEEP_LAST_DEFAULT
    dir: str = C.TELEMETRY_DEVICETIME_DIR_DEFAULT
    top_k: int = C.TELEMETRY_DEVICETIME_TOP_K_DEFAULT
    divergence_warn: float = C.TELEMETRY_DEVICETIME_DIVERGENCE_WARN_DEFAULT
    hbm_gbps: Optional[float] = None

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> \
            "TelemetryDevicetimeConfig":
        d = d or {}
        cfg = cls(
            enabled=bool(_get(d, C.TELEMETRY_DEVICETIME_ENABLED,
                              C.TELEMETRY_DEVICETIME_ENABLED_DEFAULT)),
            capture_steps=int(_get(
                d, C.TELEMETRY_DEVICETIME_CAPTURE_STEPS,
                C.TELEMETRY_DEVICETIME_CAPTURE_STEPS_DEFAULT)),
            every_steps=int(_get(
                d, C.TELEMETRY_DEVICETIME_EVERY_STEPS,
                C.TELEMETRY_DEVICETIME_EVERY_STEPS_DEFAULT)),
            keep_last=int(_get(d, C.TELEMETRY_DEVICETIME_KEEP_LAST,
                               C.TELEMETRY_DEVICETIME_KEEP_LAST_DEFAULT)),
            dir=str(_get(d, C.TELEMETRY_DEVICETIME_DIR,
                         C.TELEMETRY_DEVICETIME_DIR_DEFAULT)),
            top_k=int(_get(d, C.TELEMETRY_DEVICETIME_TOP_K,
                           C.TELEMETRY_DEVICETIME_TOP_K_DEFAULT)),
            divergence_warn=float(_get(
                d, C.TELEMETRY_DEVICETIME_DIVERGENCE_WARN,
                C.TELEMETRY_DEVICETIME_DIVERGENCE_WARN_DEFAULT)),
            hbm_gbps=(float(d[C.TELEMETRY_DEVICETIME_HBM_GBPS])
                      if d.get(C.TELEMETRY_DEVICETIME_HBM_GBPS) is not None
                      else None),
        )
        if cfg.capture_steps < 1:
            raise ConfigError(
                f"telemetry.devicetime.capture_steps must be >= 1, got "
                f"{cfg.capture_steps}")
        if cfg.every_steps <= cfg.capture_steps:
            raise ConfigError(
                f"telemetry.devicetime needs every_steps > capture_steps "
                f"(a capture must close before the next can open), got "
                f"every_steps={cfg.every_steps} "
                f"capture_steps={cfg.capture_steps}")
        if cfg.keep_last < 1:
            raise ConfigError(
                f"telemetry.devicetime.keep_last must be >= 1, got "
                f"{cfg.keep_last}")
        if cfg.top_k < 1:
            raise ConfigError(
                f"telemetry.devicetime.top_k must be >= 1, got {cfg.top_k}")
        if not (0.0 < cfg.divergence_warn <= 1.0):
            raise ConfigError(
                f"telemetry.devicetime.divergence_warn must be in (0, 1], "
                f"got {cfg.divergence_warn}")
        if cfg.hbm_gbps is not None and cfg.hbm_gbps <= 0:
            raise ConfigError(
                f"telemetry.devicetime.hbm_gbps must be positive, got "
                f"{cfg.hbm_gbps}")
        return cfg


@dataclass
class TelemetryNumericsConfig:
    """Numerics observatory knobs (telemetry/numerics.py): per-layer-group
    gradient/weight/update statistics + bf16/fp16 saturation and
    underflow-to-zero counters computed inside the jitted step as one
    small stacked aux array (fetched in a single transfer at flush
    boundaries), plus per-bucket DCN / KV-cache quantization-error
    gauges. Default off — the lowered step is then bit-identical to a
    numerics-less config; enabled, the stats ride the existing step
    program and the step path performs zero extra host fetches."""

    enabled: bool = C.TELEMETRY_NUMERICS_ENABLED_DEFAULT
    max_groups: int = C.TELEMETRY_NUMERICS_MAX_GROUPS_DEFAULT
    max_spike_dumps: int = C.TELEMETRY_NUMERICS_MAX_SPIKE_DUMPS_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> \
            "TelemetryNumericsConfig":
        d = d or {}
        cfg = cls(
            enabled=bool(_get(d, C.TELEMETRY_NUMERICS_ENABLED,
                              C.TELEMETRY_NUMERICS_ENABLED_DEFAULT)),
            max_groups=int(_get(d, C.TELEMETRY_NUMERICS_MAX_GROUPS,
                                C.TELEMETRY_NUMERICS_MAX_GROUPS_DEFAULT)),
            max_spike_dumps=int(_get(
                d, C.TELEMETRY_NUMERICS_MAX_SPIKE_DUMPS,
                C.TELEMETRY_NUMERICS_MAX_SPIKE_DUMPS_DEFAULT)),
        )
        if cfg.max_groups < 1:
            raise ConfigError(
                f"telemetry.numerics.max_groups must be >= 1, got "
                f"{cfg.max_groups}")
        if cfg.max_spike_dumps < 0:
            raise ConfigError(
                f"telemetry.numerics.max_spike_dumps must be >= 0, got "
                f"{cfg.max_spike_dumps}")
        return cfg


@dataclass
class TelemetryRequestsConfig:
    """Request observatory knobs (telemetry/requests.py): per-request SLO
    accounting for the serve engine — exact lifetime partition, TPOT/e2e
    histograms, host-scoped ``requests.<host>.jsonl`` records, the
    engine-side serving-time partition, and the rolling decode-throughput
    window behind ``serving/tokens_per_sec_window``. Default off — the
    engine then holds no accountant (``None``) and its emitted tag set is
    byte-identical; enabled, every hook is host ``time.monotonic``
    arithmetic (zero device syncs)."""

    enabled: bool = C.TELEMETRY_REQUESTS_ENABLED_DEFAULT
    file: str = C.TELEMETRY_REQUESTS_FILE_DEFAULT
    window_sec: float = C.TELEMETRY_REQUESTS_WINDOW_SEC_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> \
            "TelemetryRequestsConfig":
        d = d or {}
        cfg = cls(
            enabled=bool(_get(d, C.TELEMETRY_REQUESTS_ENABLED,
                              C.TELEMETRY_REQUESTS_ENABLED_DEFAULT)),
            file=str(_get(d, C.TELEMETRY_REQUESTS_FILE,
                          C.TELEMETRY_REQUESTS_FILE_DEFAULT)),
            window_sec=float(_get(
                d, C.TELEMETRY_REQUESTS_WINDOW_SEC,
                C.TELEMETRY_REQUESTS_WINDOW_SEC_DEFAULT)),
        )
        # Records are discovered by pattern by the stdlib-only slo_report
        # (same argument as memory.plan_file / fleet.breakdown_file).
        if not (cfg.file.startswith("requests")
                and cfg.file.endswith(".jsonl")):
            raise ConfigError(
                "telemetry.requests.file must match 'requests*.jsonl' "
                f"(tools/slo_report.py discovers records by that pattern), "
                f"got '{cfg.file}'")
        if cfg.window_sec <= 0:
            raise ConfigError(
                f"telemetry.requests.window_sec must be positive, got "
                f"{cfg.window_sec}")
        return cfg


@dataclass
class TelemetryConfig:
    """Unified observability (telemetry/; docs/OBSERVABILITY.md): metrics
    registry + Chrome-trace step tracer + recompilation detector. Disabled
    (the default) every hook is a no-op and the step path performs zero
    telemetry-originated device syncs."""

    enabled: bool = False
    dir: str = C.TELEMETRY_DIR_DEFAULT
    trace: TelemetryTraceConfig = field(default_factory=TelemetryTraceConfig)
    metrics: TelemetryMetricsConfig = field(
        default_factory=TelemetryMetricsConfig)
    recompile_detection: bool = C.TELEMETRY_RECOMPILE_DEFAULT
    # Goodput accounting (telemetry/goodput.py): wall-clock attribution,
    # engine/mfu and per-attempt run manifests. Pure host clock reads —
    # no device syncs even when on — so it defaults on with telemetry.
    goodput: bool = C.TELEMETRY_GOODPUT_DEFAULT
    # Fleet observability (telemetry/fleet.py): cross-host aggregation +
    # straggler detection. Opt-in (adds a per-flush collective).
    fleet: TelemetryFleetConfig = field(default_factory=TelemetryFleetConfig)
    # Memory observatory (telemetry/memory.py): XLA attribution, ledger,
    # capacity planner, OOM forensics. Opt-in (adds one AOT compile).
    memory: TelemetryMemoryConfig = field(
        default_factory=TelemetryMemoryConfig)
    # Device-time observatory (telemetry/devicetime.py): scheduled
    # jax.profiler captures -> measured op-level attribution, roofline,
    # measured exposed-comm. Opt-in (profiler work at capture boundaries).
    devicetime: TelemetryDevicetimeConfig = field(
        default_factory=TelemetryDevicetimeConfig)
    # Numerics observatory (telemetry/numerics.py): per-layer-group
    # grad/update stats + saturation counters + quantization-error
    # gauges. Opt-in (adds in-program stat reductions to the step).
    numerics: TelemetryNumericsConfig = field(
        default_factory=TelemetryNumericsConfig)
    # Request observatory (telemetry/requests.py): per-request SLO
    # accounting + serving-time partition for the serve engine. Opt-in
    # (host clock arithmetic per step + one record per finished request).
    requests: TelemetryRequestsConfig = field(
        default_factory=TelemetryRequestsConfig)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "TelemetryConfig":
        d = d or {}
        cfg = cls(
            enabled=bool(_get(d, C.TELEMETRY_ENABLED, False)),
            dir=str(_get(d, C.TELEMETRY_DIR, C.TELEMETRY_DIR_DEFAULT)),
            trace=TelemetryTraceConfig.from_dict(d.get(C.TELEMETRY_TRACE)),
            metrics=TelemetryMetricsConfig.from_dict(
                d.get(C.TELEMETRY_METRICS)),
            recompile_detection=bool(_get(d, C.TELEMETRY_RECOMPILE,
                                          C.TELEMETRY_RECOMPILE_DEFAULT)),
            goodput=bool(_get(d, C.TELEMETRY_GOODPUT,
                              C.TELEMETRY_GOODPUT_DEFAULT)),
            fleet=TelemetryFleetConfig.from_dict(d.get(C.TELEMETRY_FLEET)),
            memory=TelemetryMemoryConfig.from_dict(
                d.get(C.TELEMETRY_MEMORY)),
            devicetime=TelemetryDevicetimeConfig.from_dict(
                d.get(C.TELEMETRY_DEVICETIME)),
            numerics=TelemetryNumericsConfig.from_dict(
                d.get(C.TELEMETRY_NUMERICS)),
            requests=TelemetryRequestsConfig.from_dict(
                d.get(C.TELEMETRY_REQUESTS)),
        )
        if cfg.enabled and not cfg.dir:
            raise ConfigError(
                "telemetry.enabled requires telemetry.dir (where the trace "
                "file and metrics JSONL land)")
        if cfg.fleet.enabled and not cfg.goodput:
            raise ConfigError(
                "telemetry.fleet requires telemetry.goodput (fleet "
                "aggregation reads the goodput accountant's deltas)")
        if cfg.devicetime.enabled and cfg.trace.jax_profiler_dir:
            raise ConfigError(
                "telemetry.devicetime and telemetry.trace.jax_profiler_dir "
                "are mutually exclusive: the passthrough holds THE one "
                "jax.profiler session open for the whole run, so scheduled "
                "captures could never start")
        return cfg


@dataclass
class ServingConfig:
    """``serving`` block — the continuous-batching serving engine
    (serving/engine.py, docs/SERVING.md).

    ``max_batch_size``: decode slots (the static batch width of the one
    compiled decode program). ``kv_block_size`` / ``kv_num_blocks``: the
    paged KV pool geometry — capacity is ``(kv_num_blocks - 1) *
    kv_block_size`` cache positions (block 0 is reserved scratch).
    ``int8_kv_cache``: store KV as blockwise int8 + per-(token, head)
    fp32 scales (comm/quantize.py RTNE). ``max_model_len``: per-sequence
    prompt+output cap (defaults to the model's max_seq_len).
    ``max_prefills_per_step``: prefills admitted per decode boundary —
    bounds how long the decode batch waits on prompt processing.
    ``temperature``/``top_k``/``seed``: engine-wide sampling policy
    (0.0 = greedy, byte-reproducible).

    Decode fast path (docs/SERVING.md "Decode fast path" — all three
    off by default): ``decode_attention`` gather|auto|kernel: "gather"
    decodes over the flat list of the batch's live blocks, "kernel"
    selects the Pallas paged decode-attention kernel, "auto" the kernel
    on a TPU where it tiles and else the "gather" decode;
    ``prefix_cache`` turns on COW prompt-head block reuse;
    ``speculative`` configures draft-model speculative decoding
    (greedy-identical by construction — requires ``temperature == 0``).

    Resilience (docs/SERVING.md "Serving under failure" — off by
    default, zero-overhead): the ``resilience`` sub-block turns on
    per-request deadlines + ``cancel()``, the SLO-aware admission gate
    (``max_queue_wait_ms`` projected-wait shed, ``max_queue_depth``
    hard backstop), decode-dispatch retry/rebuild/replay recovery
    (``max_retries`` / ``retry_base_sec``) and the degradation ladder
    (``degrade_after`` anomalies per rung; ``slow_step_ms`` marks a
    decode step as an anomaly).

    Chunked prefill (docs/SERVING.md "Chunked prefill admission" — off
    by default, zero-overhead): the ``chunked_prefill`` sub-block
    switches admission to Sarathi-style mixed steps — decode tokens plus
    prefill chunks of admitted prompts share ONE ragged program, bounded
    by ``token_budget`` tokens per step (requires ``temperature == 0``).
    """

    max_batch_size: int = C.SERVING_MAX_BATCH_SIZE_DEFAULT
    kv_block_size: int = C.SERVING_KV_BLOCK_SIZE_DEFAULT
    kv_num_blocks: int = C.SERVING_KV_NUM_BLOCKS_DEFAULT
    int8_kv_cache: bool = C.SERVING_INT8_KV_CACHE_DEFAULT
    max_model_len: Optional[int] = None
    max_prefills_per_step: int = C.SERVING_MAX_PREFILLS_PER_STEP_DEFAULT
    eos_token_id: Optional[int] = None
    temperature: float = C.SERVING_TEMPERATURE_DEFAULT
    top_k: int = C.SERVING_TOP_K_DEFAULT
    seed: int = C.SERVING_SEED_DEFAULT
    decode_attention: str = C.SERVING_DECODE_ATTENTION_DEFAULT
    prefix_cache: bool = C.SERVING_PREFIX_CACHE_DEFAULT
    spec_decode: bool = C.SERVING_SPEC_ENABLED_DEFAULT
    spec_k: int = C.SERVING_SPEC_K_DEFAULT
    spec_draft_layers: Optional[int] = None
    resilience: bool = C.SERVING_RESIL_ENABLED_DEFAULT
    resil_max_queue_depth: Optional[int] = None
    resil_max_queue_wait_ms: Optional[float] = None
    resil_default_deadline_ms: Optional[float] = None
    resil_max_retries: int = C.SERVING_RESIL_MAX_RETRIES_DEFAULT
    resil_retry_base_sec: float = C.SERVING_RESIL_RETRY_BASE_SEC_DEFAULT
    resil_degrade_after: int = C.SERVING_RESIL_DEGRADE_AFTER_DEFAULT
    resil_slow_step_ms: Optional[float] = None
    chunked_prefill: bool = C.SERVING_CHUNKED_ENABLED_DEFAULT
    chunked_token_budget: int = C.SERVING_CHUNKED_TOKEN_BUDGET_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "ServingConfig":
        d = d or {}
        cfg = cls(
            max_batch_size=int(_get(d, C.SERVING_MAX_BATCH_SIZE,
                                    C.SERVING_MAX_BATCH_SIZE_DEFAULT)),
            kv_block_size=int(_get(d, C.SERVING_KV_BLOCK_SIZE,
                                   C.SERVING_KV_BLOCK_SIZE_DEFAULT)),
            kv_num_blocks=int(_get(d, C.SERVING_KV_NUM_BLOCKS,
                                   C.SERVING_KV_NUM_BLOCKS_DEFAULT)),
            int8_kv_cache=bool(_get(d, C.SERVING_INT8_KV_CACHE,
                                    C.SERVING_INT8_KV_CACHE_DEFAULT)),
            max_model_len=(int(d[C.SERVING_MAX_MODEL_LEN])
                           if d.get(C.SERVING_MAX_MODEL_LEN) is not None
                           else None),
            max_prefills_per_step=int(_get(
                d, C.SERVING_MAX_PREFILLS_PER_STEP,
                C.SERVING_MAX_PREFILLS_PER_STEP_DEFAULT)),
            eos_token_id=(int(d[C.SERVING_EOS_TOKEN_ID])
                          if d.get(C.SERVING_EOS_TOKEN_ID) is not None
                          else None),
            temperature=float(_get(d, C.SERVING_TEMPERATURE,
                                   C.SERVING_TEMPERATURE_DEFAULT)),
            top_k=int(_get(d, C.SERVING_TOP_K, C.SERVING_TOP_K_DEFAULT)),
            seed=int(_get(d, C.SERVING_SEED, C.SERVING_SEED_DEFAULT)),
            decode_attention=str(_get(
                d, C.SERVING_DECODE_ATTENTION,
                C.SERVING_DECODE_ATTENTION_DEFAULT)),
            prefix_cache=bool(_get(d, C.SERVING_PREFIX_CACHE,
                                   C.SERVING_PREFIX_CACHE_DEFAULT)),
        )
        spec = d.get(C.SERVING_SPECULATIVE) or {}
        if not isinstance(spec, dict):
            raise ConfigError("serving.speculative must be a dict")
        cfg.spec_decode = bool(spec.get(C.SERVING_SPEC_ENABLED,
                                        C.SERVING_SPEC_ENABLED_DEFAULT))
        cfg.spec_k = int(spec.get(C.SERVING_SPEC_K,
                                  C.SERVING_SPEC_K_DEFAULT))
        cfg.spec_draft_layers = (
            int(spec[C.SERVING_SPEC_DRAFT_LAYERS])
            if spec.get(C.SERVING_SPEC_DRAFT_LAYERS) is not None else None)
        resil = d.get(C.SERVING_RESILIENCE) or {}
        if not isinstance(resil, dict):
            raise ConfigError("serving.resilience must be a dict")
        # a present block defaults to enabled (like `moe`): writing
        # `resilience: {}` is an opt-in, `enabled: false` keeps it inert
        cfg.resilience = bool(resil.get(C.SERVING_RESIL_ENABLED,
                                        bool(resil) or
                                        C.SERVING_RESIL_ENABLED_DEFAULT))
        cfg.resil_max_queue_depth = (
            int(resil[C.SERVING_RESIL_MAX_QUEUE_DEPTH])
            if resil.get(C.SERVING_RESIL_MAX_QUEUE_DEPTH) is not None
            else None)
        cfg.resil_max_queue_wait_ms = (
            float(resil[C.SERVING_RESIL_MAX_QUEUE_WAIT_MS])
            if resil.get(C.SERVING_RESIL_MAX_QUEUE_WAIT_MS) is not None
            else None)
        cfg.resil_default_deadline_ms = (
            float(resil[C.SERVING_RESIL_DEFAULT_DEADLINE_MS])
            if resil.get(C.SERVING_RESIL_DEFAULT_DEADLINE_MS) is not None
            else None)
        cfg.resil_max_retries = int(resil.get(
            C.SERVING_RESIL_MAX_RETRIES,
            C.SERVING_RESIL_MAX_RETRIES_DEFAULT))
        cfg.resil_retry_base_sec = float(resil.get(
            C.SERVING_RESIL_RETRY_BASE_SEC,
            C.SERVING_RESIL_RETRY_BASE_SEC_DEFAULT))
        cfg.resil_degrade_after = int(resil.get(
            C.SERVING_RESIL_DEGRADE_AFTER,
            C.SERVING_RESIL_DEGRADE_AFTER_DEFAULT))
        cfg.resil_slow_step_ms = (
            float(resil[C.SERVING_RESIL_SLOW_STEP_MS])
            if resil.get(C.SERVING_RESIL_SLOW_STEP_MS) is not None
            else None)
        known_resil = {C.SERVING_RESIL_ENABLED,
                       C.SERVING_RESIL_MAX_QUEUE_DEPTH,
                       C.SERVING_RESIL_MAX_QUEUE_WAIT_MS,
                       C.SERVING_RESIL_DEFAULT_DEADLINE_MS,
                       C.SERVING_RESIL_MAX_RETRIES,
                       C.SERVING_RESIL_RETRY_BASE_SEC,
                       C.SERVING_RESIL_DEGRADE_AFTER,
                       C.SERVING_RESIL_SLOW_STEP_MS}
        unknown = set(resil) - known_resil
        if unknown:
            raise ConfigError(
                f"unknown serving.resilience keys {sorted(unknown)}; "
                f"expected a subset of {sorted(known_resil)}")
        chunked = d.get(C.SERVING_CHUNKED_PREFILL)
        has_chunked = chunked is not None
        chunked = chunked or {}
        if not isinstance(chunked, dict):
            raise ConfigError("serving.chunked_prefill must be a dict")
        # a present block defaults to enabled (like `resilience`)
        cfg.chunked_prefill = bool(chunked.get(
            C.SERVING_CHUNKED_ENABLED,
            has_chunked or C.SERVING_CHUNKED_ENABLED_DEFAULT))
        cfg.chunked_token_budget = int(chunked.get(
            C.SERVING_CHUNKED_TOKEN_BUDGET,
            C.SERVING_CHUNKED_TOKEN_BUDGET_DEFAULT))
        known_chunked = {C.SERVING_CHUNKED_ENABLED,
                         C.SERVING_CHUNKED_TOKEN_BUDGET}
        unknown = set(chunked) - known_chunked
        if unknown:
            raise ConfigError(
                f"unknown serving.chunked_prefill keys {sorted(unknown)}; "
                f"expected a subset of {sorted(known_chunked)}")
        if cfg.max_batch_size < 1:
            raise ConfigError("serving.max_batch_size must be >= 1")
        if cfg.kv_block_size < 1:
            raise ConfigError("serving.kv_block_size must be >= 1")
        if cfg.kv_num_blocks < 2:
            raise ConfigError(
                "serving.kv_num_blocks must be >= 2 (block 0 is reserved "
                "as the scratch block for inactive slots)")
        if cfg.max_model_len is not None and cfg.max_model_len < 1:
            raise ConfigError("serving.max_model_len must be >= 1")
        if cfg.max_prefills_per_step < 1:
            raise ConfigError("serving.max_prefills_per_step must be >= 1")
        if cfg.temperature < 0:
            raise ConfigError("serving.temperature must be >= 0")
        if cfg.top_k < 0:
            raise ConfigError("serving.top_k must be >= 0")
        if cfg.decode_attention not in C.SERVING_DECODE_ATTENTION_CHOICES:
            raise ConfigError(
                f"serving.decode_attention must be one of "
                f"{C.SERVING_DECODE_ATTENTION_CHOICES}, got "
                f"{cfg.decode_attention!r}")
        if cfg.spec_k < 1:
            raise ConfigError("serving.speculative.k must be >= 1")
        if cfg.spec_draft_layers is not None and cfg.spec_draft_layers < 1:
            raise ConfigError(
                "serving.speculative.draft_layers must be >= 1")
        if cfg.spec_decode and cfg.temperature != 0.0:
            raise ConfigError(
                "serving.speculative requires temperature == 0 (greedy): "
                "the accept/rollback contract is token-identity with "
                "greedy decode")
        if cfg.resil_max_queue_depth is not None \
                and cfg.resil_max_queue_depth < 1:
            raise ConfigError(
                "serving.resilience.max_queue_depth must be >= 1")
        if cfg.resil_max_queue_wait_ms is not None \
                and cfg.resil_max_queue_wait_ms <= 0:
            raise ConfigError(
                "serving.resilience.max_queue_wait_ms must be > 0")
        if cfg.resil_default_deadline_ms is not None \
                and cfg.resil_default_deadline_ms <= 0:
            raise ConfigError(
                "serving.resilience.default_deadline_ms must be > 0")
        if cfg.resil_max_retries < 0:
            raise ConfigError("serving.resilience.max_retries must be >= 0")
        if cfg.resil_retry_base_sec <= 0:
            raise ConfigError(
                "serving.resilience.retry_base_sec must be > 0")
        if cfg.resil_degrade_after < 1:
            raise ConfigError(
                "serving.resilience.degrade_after must be >= 1")
        if cfg.resil_slow_step_ms is not None and cfg.resil_slow_step_ms <= 0:
            raise ConfigError(
                "serving.resilience.slow_step_ms must be > 0")
        if cfg.chunked_prefill \
                and cfg.chunked_token_budget < cfg.max_batch_size:
            raise ConfigError(
                "serving.chunked_prefill.token_budget must be >= "
                "max_batch_size (every decoding slot needs a row in each "
                "mixed step)")
        if cfg.chunked_prefill and cfg.temperature != 0.0:
            raise ConfigError(
                "serving.chunked_prefill requires temperature == 0 "
                "(greedy): the mixed program samples every ragged row "
                "with one key, and the contract with the bucketed path "
                "is token identity")
        return cfg


def _int_tuple(v, name: str) -> tuple:
    if v is None:
        return ()
    if not isinstance(v, (list, tuple)):
        raise ConfigError(f"{name} must be a list, got {type(v).__name__}")
    return tuple(int(x) for x in v)


def _float_tuple(v, name: str) -> tuple:
    if v is None:
        return ()
    if not isinstance(v, (list, tuple)):
        raise ConfigError(f"{name} must be a list, got {type(v).__name__}")
    return tuple(float(x) for x in v)


def _str_tuple(v, name: str) -> tuple:
    if v is None:
        return ()
    if not isinstance(v, (list, tuple)):
        raise ConfigError(f"{name} must be a list, got {type(v).__name__}")
    return tuple(str(x).lower() for x in v)


@dataclass
class MoeConfig:
    """``moe`` block — expert-parallel MoE training (moe/; docs/MOE.md).

    When enabled, ``deepspeed_tpu.initialize(model=...)`` swaps the
    in-tree GPT family's FFN blocks for MoE layers (every
    ``layer_freq``-th block), pins the engine mesh into the layer so the
    ``alltoall`` dispatch path has its expert axis, and — with telemetry
    on — turns on the moe/* gauges and per-expert numerics groups. A
    present block defaults to enabled (set ``enabled: false`` to keep a
    block around inert). Absent/off is provably free: no surgery, no
    extra step outputs, bit-identical lowered train step
    (tests/test_moe.py pins it)."""

    enabled: bool = C.MOE_ENABLED_DEFAULT
    num_experts: int = C.MOE_NUM_EXPERTS_DEFAULT
    k: int = C.MOE_TOP_K_DEFAULT
    layer_freq: int = C.MOE_LAYER_FREQ_DEFAULT
    capacity_factor: float = C.MOE_CAPACITY_FACTOR_DEFAULT
    eval_capacity_factor: float = C.MOE_EVAL_CAPACITY_FACTOR_DEFAULT
    min_capacity: int = C.MOE_MIN_CAPACITY_DEFAULT
    aux_alpha: float = C.MOE_AUX_ALPHA_DEFAULT
    router_jitter: float = C.MOE_ROUTER_JITTER_DEFAULT
    dispatch: str = C.MOE_DISPATCH_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "MoeConfig":
        # an empty `moe: {}` block is still an opt-in (all defaults)
        present = d is not None
        d = d or {}
        cfg = cls(
            enabled=bool(_get(d, C.MOE_ENABLED, present)),
            num_experts=int(_get(d, C.MOE_NUM_EXPERTS,
                                 C.MOE_NUM_EXPERTS_DEFAULT)),
            k=int(_get(d, C.MOE_TOP_K, C.MOE_TOP_K_DEFAULT)),
            layer_freq=int(_get(d, C.MOE_LAYER_FREQ,
                                C.MOE_LAYER_FREQ_DEFAULT)),
            capacity_factor=float(_get(d, C.MOE_CAPACITY_FACTOR,
                                       C.MOE_CAPACITY_FACTOR_DEFAULT)),
            eval_capacity_factor=float(_get(
                d, C.MOE_EVAL_CAPACITY_FACTOR,
                C.MOE_EVAL_CAPACITY_FACTOR_DEFAULT)),
            min_capacity=int(_get(d, C.MOE_MIN_CAPACITY,
                                  C.MOE_MIN_CAPACITY_DEFAULT)),
            aux_alpha=float(_get(d, C.MOE_AUX_ALPHA,
                                 C.MOE_AUX_ALPHA_DEFAULT)),
            router_jitter=float(_get(d, C.MOE_ROUTER_JITTER,
                                     C.MOE_ROUTER_JITTER_DEFAULT)),
            dispatch=str(_get(d, C.MOE_DISPATCH,
                              C.MOE_DISPATCH_DEFAULT)).lower(),
        )
        if not cfg.enabled:
            return cfg
        if cfg.num_experts < 2:
            raise ConfigError(
                f"moe.num_experts must be >= 2, got {cfg.num_experts}")
        if cfg.k not in (1, 2):
            raise ConfigError(f"moe.k must be 1 or 2, got {cfg.k}")
        if cfg.layer_freq < 1:
            raise ConfigError(
                f"moe.layer_freq must be >= 1, got {cfg.layer_freq}")
        if cfg.capacity_factor <= 0 or cfg.eval_capacity_factor <= 0:
            raise ConfigError(
                f"moe capacity factors must be positive, got "
                f"{cfg.capacity_factor}/{cfg.eval_capacity_factor}")
        if cfg.min_capacity < 1:
            raise ConfigError(
                f"moe.min_capacity must be >= 1, got {cfg.min_capacity}")
        if cfg.aux_alpha < 0:
            raise ConfigError(
                f"moe.aux_alpha must be >= 0, got {cfg.aux_alpha}")
        if not (0.0 <= cfg.router_jitter < 1.0):
            raise ConfigError(
                f"moe.router_jitter must be in [0, 1), got "
                f"{cfg.router_jitter}")
        if cfg.dispatch not in C.MOE_DISPATCH_CHOICES:
            raise ConfigError(
                f"moe.dispatch must be drawn from "
                f"{'/'.join(C.MOE_DISPATCH_CHOICES)}, got "
                f"'{cfg.dispatch}'")
        return cfg


@dataclass
class AutotuningConfig:
    """``autotuning`` block — the startup config search
    (autotuning/; docs/PERFORMANCE.md "Autotuning").

    Three stages: enumerate the knob space (every list here overrides the
    derived default axis), prune candidates that fail the ConfigError
    walls at parse or project over ``headroom_frac`` x HBM through the
    engine-free capacity projection (telemetry/memory.py), then run
    short in-process measured trials of the ``top_k``
    projected-fastest survivors (compile + ``trial_steps`` timed steps
    each, successive-halving early stop at ``halving_factor``) and adopt
    the measured winner. ``enabled`` gates only the automatic run inside
    ``deepspeed_tpu.initialize`` (and the launcher's ``--autotune`` env
    handshake); an explicit ``deepspeed_tpu.autotune(engine, ...)`` call
    reads the knobs regardless. Default OFF is provably free: no
    autotuning import at engine init, zero extra syncs, bit-identical
    lowered step."""

    enabled: bool = C.AUTOTUNING_ENABLED_DEFAULT
    zero_stages: tuple = ()
    micro_gas: tuple = ()            # ((micro, gas), ...) overrides
    bucket_mbs: tuple = ()
    dcn_quant_bits: tuple = ()
    overlap: tuple = ()              # overlap_grad_sync values
    zeropp: tuple = ()               # quantized_weights tiers
    moe_experts: tuple = ()          # expert counts (prune-only axis)
    moe_capacity_factors: tuple = ()
    moe_dispatch: tuple = ()         # einsum | scatter | alltoall
    top_k: int = C.AUTOTUNING_TOP_K_DEFAULT
    trial_steps: int = C.AUTOTUNING_TRIAL_STEPS_DEFAULT
    trial_warmup: int = C.AUTOTUNING_TRIAL_WARMUP_DEFAULT
    halving_factor: float = C.AUTOTUNING_HALVING_FACTOR_DEFAULT
    headroom_frac: float = C.AUTOTUNING_HEADROOM_FRAC_DEFAULT
    activation_bytes_per_sample: float = C.AUTOTUNING_ACT_BYTES_DEFAULT
    hbm_limit_gb: Optional[float] = None
    max_candidates: int = C.AUTOTUNING_MAX_CANDIDATES_DEFAULT
    result_file: str = C.AUTOTUNING_RESULT_FILE_DEFAULT

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "AutotuningConfig":
        d = d or {}
        if C.AUTOTUNING_ENABLED in d and d[C.AUTOTUNING_ENABLED] is not None:
            # An explicit value always wins — the tuner's own candidate
            # configs carry `enabled: false` precisely so a candidate
            # (the adopted one included) can never recursively search.
            enabled = bool(d[C.AUTOTUNING_ENABLED])
        else:
            # Launcher handshake: `dstpu --autotune` exports the env so
            # unmodified scripts (no explicit key) enable the search
            # through their config parse.
            enabled = (C.AUTOTUNING_ENABLED_DEFAULT
                       or os.environ.get(C.AUTOTUNING_ENV, "")
                       not in ("", "0"))
        mg = d.get(C.AUTOTUNING_MICRO_GAS)
        micro_gas = ()
        if mg is not None:
            if not isinstance(mg, (list, tuple)) or not all(
                    isinstance(p, (list, tuple)) and len(p) == 2
                    for p in mg):
                raise ConfigError(
                    "autotuning.micro_gas must be a list of [micro, gas] "
                    f"pairs, got {mg!r}")
            micro_gas = tuple((int(m), int(g)) for m, g in mg)
        cfg = cls(
            enabled=enabled,
            zero_stages=_int_tuple(d.get(C.AUTOTUNING_ZERO_STAGES),
                                   "autotuning.zero_stages"),
            micro_gas=micro_gas,
            bucket_mbs=_float_tuple(d.get(C.AUTOTUNING_BUCKET_MBS),
                                    "autotuning.bucket_mbs"),
            dcn_quant_bits=_int_tuple(d.get(C.AUTOTUNING_DCN_QUANT_BITS),
                                      "autotuning.dcn_quant_bits"),
            overlap=_str_tuple(d.get(C.AUTOTUNING_OVERLAP),
                               "autotuning.overlap"),
            zeropp=_str_tuple(d.get(C.AUTOTUNING_ZEROPP),
                              "autotuning.zeropp"),
            moe_experts=_int_tuple(d.get(C.AUTOTUNING_MOE_EXPERTS),
                                   "autotuning.moe_experts"),
            moe_capacity_factors=_float_tuple(
                d.get(C.AUTOTUNING_MOE_CAPACITY_FACTORS),
                "autotuning.moe_capacity_factors"),
            moe_dispatch=_str_tuple(d.get(C.AUTOTUNING_MOE_DISPATCH),
                                    "autotuning.moe_dispatch"),
            top_k=int(_get(d, C.AUTOTUNING_TOP_K,
                           C.AUTOTUNING_TOP_K_DEFAULT)),
            trial_steps=int(_get(d, C.AUTOTUNING_TRIAL_STEPS,
                                 C.AUTOTUNING_TRIAL_STEPS_DEFAULT)),
            trial_warmup=int(_get(d, C.AUTOTUNING_TRIAL_WARMUP,
                                  C.AUTOTUNING_TRIAL_WARMUP_DEFAULT)),
            halving_factor=float(_get(d, C.AUTOTUNING_HALVING_FACTOR,
                                      C.AUTOTUNING_HALVING_FACTOR_DEFAULT)),
            headroom_frac=float(_get(d, C.AUTOTUNING_HEADROOM_FRAC,
                                     C.AUTOTUNING_HEADROOM_FRAC_DEFAULT)),
            activation_bytes_per_sample=float(_get(
                d, C.AUTOTUNING_ACT_BYTES, C.AUTOTUNING_ACT_BYTES_DEFAULT)),
            hbm_limit_gb=(float(d[C.AUTOTUNING_HBM_LIMIT_GB])
                          if d.get(C.AUTOTUNING_HBM_LIMIT_GB) is not None
                          else None),
            max_candidates=int(_get(d, C.AUTOTUNING_MAX_CANDIDATES,
                                    C.AUTOTUNING_MAX_CANDIDATES_DEFAULT)),
            result_file=str(_get(d, C.AUTOTUNING_RESULT_FILE,
                                 C.AUTOTUNING_RESULT_FILE_DEFAULT)),
        )
        if cfg.top_k < 1:
            raise ConfigError(
                f"autotuning.top_k must be >= 1, got {cfg.top_k}")
        if cfg.trial_steps < 1:
            raise ConfigError(
                f"autotuning.trial_steps must be >= 1, got "
                f"{cfg.trial_steps}")
        if cfg.trial_warmup < 0:
            raise ConfigError(
                f"autotuning.trial_warmup must be >= 0, got "
                f"{cfg.trial_warmup}")
        if cfg.halving_factor <= 1.0:
            raise ConfigError(
                f"autotuning.halving_factor must be > 1 (a factor <= 1 "
                f"would eliminate every candidate including the best), "
                f"got {cfg.halving_factor}")
        if not (0.0 < cfg.headroom_frac <= 1.0):
            raise ConfigError(
                f"autotuning.headroom_frac must be in (0, 1], got "
                f"{cfg.headroom_frac}")
        if cfg.hbm_limit_gb is not None and cfg.hbm_limit_gb <= 0:
            raise ConfigError(
                f"autotuning.hbm_limit_gb must be positive, got "
                f"{cfg.hbm_limit_gb}")
        if cfg.max_candidates < 1:
            raise ConfigError(
                f"autotuning.max_candidates must be >= 1, got "
                f"{cfg.max_candidates}")
        bad = [s for s in cfg.zero_stages if s not in (0, 1, 2, 3)]
        if bad:
            raise ConfigError(
                f"autotuning.zero_stages must be drawn from 0-3, got {bad}")
        bad = [b for b in cfg.dcn_quant_bits if b not in (8, 16, 32)]
        if bad:
            raise ConfigError(
                f"autotuning.dcn_quant_bits must be drawn from 8/16/32, "
                f"got {bad}")
        bad = [o for o in cfg.overlap if o not in ("auto", "on", "off")]
        if bad:
            raise ConfigError(
                f"autotuning.overlap must be drawn from auto/on/off, "
                f"got {bad}")
        bad = [z for z in cfg.zeropp if z not in ("off", "bf16", "int8")]
        if bad:
            raise ConfigError(
                f"autotuning.zeropp must be drawn from off/bf16/int8, "
                f"got {bad}")
        bad = [e for e in cfg.moe_experts if e < 2]
        if bad:
            raise ConfigError(
                f"autotuning.moe_experts must be >= 2, got {bad}")
        bad = [f for f in cfg.moe_capacity_factors if f <= 0]
        if bad:
            raise ConfigError(
                f"autotuning.moe_capacity_factors must be positive, "
                f"got {bad}")
        bad = [m for m in cfg.moe_dispatch
               if m not in C.MOE_DISPATCH_CHOICES]
        if bad:
            raise ConfigError(
                f"autotuning.moe_dispatch must be drawn from "
                f"{'/'.join(C.MOE_DISPATCH_CHOICES)}, got {bad}")
        if any(m < 1 or g < 1 for m, g in cfg.micro_gas):
            raise ConfigError(
                f"autotuning.micro_gas pairs must be positive, got "
                f"{cfg.micro_gas}")
        # The result file is discovered by pattern by the stdlib-only
        # autotune_report (same argument as memory.plan_file).
        if not (cfg.result_file.startswith("autotune_result")
                and cfg.result_file.endswith(".json")):
            raise ConfigError(
                "autotuning.result_file must match 'autotune_result*.json' "
                f"(tools/autotune_report.py discovers it by that pattern), "
                f"got '{cfg.result_file}'")
        return cfg


@dataclass
class TensorboardConfig:
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTPUJob"

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "TensorboardConfig":
        d = d or {}
        return cls(enabled=bool(_get(d, C.TENSORBOARD_ENABLED, False)),
                   output_path=str(_get(d, C.TENSORBOARD_OUTPUT_PATH, "")),
                   job_name=str(_get(d, C.TENSORBOARD_JOB_NAME, "DeepSpeedTPUJob")))


class DeepSpeedTPUConfig:
    """Parsed, validated, fully-resolved training configuration."""

    def __init__(self,
                 config: Union[str, Dict[str, Any], None],
                 world_size: Optional[int] = None):
        if config is None:
            config = {}
        if isinstance(config, str):
            if not os.path.exists(config):
                raise ConfigError(f"config file not found: {config}")
            with open(config, "r") as f:
                self._param_dict = json.load(f)
        elif isinstance(config, dict):
            self._param_dict = dict(config)
        else:
            raise ConfigError(f"config must be a path or dict, got {type(config)}")

        d = self._param_dict
        self.world_size = int(world_size) if world_size is not None else self._default_world()

        # --- mesh / parallel shape -------------------------------------------------
        self.mesh = MeshConfig.from_dict(d.get(C.MESH))
        self.data_parallel_size = self.mesh.resolve_data(self.world_size)

        # --- elasticity: takes control of the batch triple when enabled ------------
        # (reference runtime/config.py:679-733)
        self.elasticity = dict(d.get(C.ELASTICITY, {}))
        self.elasticity_enabled = bool(self.elasticity.get("enabled", False))
        # Live elasticity (resilience/elastic.py): in-process shrink/grow
        # + straggler eviction. Parsed here beside the ladder it rides;
        # compatibility walls live in _validate.
        self.elasticity_live = LiveElasticityConfig.from_dict(
            self.elasticity.get(C.ELASTICITY_LIVE))
        if self.elasticity_live.enabled:
            # Walled HERE, before the batch triple resolves: a live config
            # missing the ladder (or splitting the model over pipe) would
            # otherwise die on a misleading batch-math error instead of
            # the real cause. The remaining tier walls live in _validate.
            if not self.elasticity_enabled:
                raise ConfigError(
                    "elasticity.live requires the elastic batch ladder "
                    "(elasticity.enabled with max_train_batch_size/"
                    "micro_batch_sizes): the in-process world change picks "
                    "its new (world, micro, gas) from the ladder so the "
                    "global batch — and convergence — never changes")
            if (self.mesh.pipe > 1
                    or int(dict(d.get(C.PIPELINE, {})).get("stages", 1)) > 1):
                raise ConfigError(
                    "elasticity.live cannot compose with pipeline "
                    "parallelism: the pipe engine shards the MODEL over "
                    "the pipe axis — losing a slice loses layers, not "
                    "data-parallel replicas; use the plain engine")
        if self.elasticity_enabled:
            # The ladder solver must not see the live sub-block as an
            # unknown elasticity key (ElasticityConfig ignores extras, but
            # elastic_config_hash canonicalises only the batch-math keys —
            # live knobs are deliberately NOT convergence-relevant).
            self._apply_elasticity(d)

        # --- batch triple ----------------------------------------------------------
        micro = d.get(C.TRAIN_MICRO_BATCH_SIZE_PER_GPU,
                      d.get(C.TRAIN_MICRO_BATCH_SIZE_PER_CHIP))
        self.train_batch_size, self.train_micro_batch_size_per_gpu, \
            self.gradient_accumulation_steps = self._resolve_batch_triple(
                d.get(C.TRAIN_BATCH_SIZE), micro,
                d.get(C.GRADIENT_ACCUMULATION_STEPS), self.data_parallel_size)

        # --- optimizer / scheduler -------------------------------------------------
        opt = d.get(C.OPTIMIZER)
        self.optimizer_name: Optional[str] = None
        self.optimizer_params: Dict[str, Any] = {}
        self.optimizer_fused_update = C.OPTIMIZER_FUSED_UPDATE_DEFAULT
        if opt is not None:
            if C.OPTIMIZER_TYPE not in opt:
                raise ConfigError("optimizer block requires a 'type'")
            self.optimizer_name = str(opt[C.OPTIMIZER_TYPE]).lower()
            self.optimizer_params = dict(opt.get(C.OPTIMIZER_PARAMS, {}))
            self.optimizer_fused_update = bool(opt.get(
                C.OPTIMIZER_FUSED_UPDATE, C.OPTIMIZER_FUSED_UPDATE_DEFAULT))
        self.optimizer_legacy_fusion = bool(d.get("legacy_fusion", False))

        sched = d.get(C.SCHEDULER)
        self.scheduler_name: Optional[str] = None
        self.scheduler_params: Dict[str, Any] = {}
        if sched is not None:
            if C.SCHEDULER_TYPE not in sched:
                raise ConfigError("scheduler block requires a 'type'")
            self.scheduler_name = str(sched[C.SCHEDULER_TYPE])
            self.scheduler_params = dict(sched.get(C.SCHEDULER_PARAMS, {}))

        # --- precision -------------------------------------------------------------
        self.fp16 = FP16Config.from_dict(d.get(C.FP16))
        bf16_block = d.get(C.BF16, d.get(C.BFLOAT16))
        self.bf16_enabled = bool(_get(bf16_block or {}, C.BF16_ENABLED, False))
        if self.fp16.enabled and self.bf16_enabled:
            raise ConfigError("fp16 and bf16 cannot both be enabled")
        self.amp_enabled = bool(_get(d.get(C.AMP) or {}, C.AMP_ENABLED, False))
        self.gradient_clipping = float(_get(d, C.GRADIENT_CLIPPING,
                                            C.GRADIENT_CLIPPING_DEFAULT))
        self.prescale_gradients = bool(_get(d, C.PRESCALE_GRADIENTS,
                                            C.PRESCALE_GRADIENTS_DEFAULT))
        self.gradient_predivide_factor = float(_get(d, C.GRADIENT_PREDIVIDE_FACTOR,
                                                    C.GRADIENT_PREDIVIDE_FACTOR_DEFAULT))
        # communication_data_type: the ICI reduction dtype for the
        # grad-sync strategy (comm/grad_sync.py) and the 1-bit path's
        # dense intra-slice pre-reduction. None ≡ the accumulator's
        # native dtype.
        self.communication_data_type = d.get(C.COMMUNICATION_DATA_TYPE)
        if self.communication_data_type is not None:
            self.communication_data_type = \
                str(self.communication_data_type).lower()
            if self.communication_data_type not in (
                    "fp32", "float32", "bf16", "bfloat16", "fp16",
                    "float16"):
                raise ConfigError(
                    f"communication_data_type must be one of fp32/float32/"
                    f"bf16/bfloat16/fp16/float16, got "
                    f"'{self.communication_data_type}'")
        # data_types.grad_accum_dtype (later-DeepSpeed key): the GAS
        # accumulator's dtype. The reference's fp16 engine accumulates in
        # half precision the same way (fp16 flat buffers); fp32 stays the
        # safe default here.
        dt_block = d.get("data_types") or {}
        self.grad_accum_dtype = str(
            dt_block.get("grad_accum_dtype", "float32"))
        if self.grad_accum_dtype not in ("float32", "fp32", "bfloat16",
                                         "bf16"):
            raise ConfigError(
                f"data_types.grad_accum_dtype must be float32 or bfloat16, "
                f"got '{self.grad_accum_dtype}'")

        # --- subsystem blocks ------------------------------------------------------
        self.zero_config = ZeroConfig.from_dict(d.get(C.ZERO_OPTIMIZATION))
        self.zero_enabled = self.zero_config.enabled
        self.activation_checkpointing_provided = C.ACTIVATION_CHECKPOINTING in d
        self.activation_checkpointing = ActivationCheckpointingConfig.from_dict(
            d.get(C.ACTIVATION_CHECKPOINTING))
        self.flops_profiler = FlopsProfilerConfig.from_dict(d.get(C.FLOPS_PROFILER))
        self.pld = PLDConfig.from_dict(d.get(C.PROGRESSIVE_LAYER_DROP))
        self.aio = AIOConfig.from_dict(d.get(C.AIO))
        self.tensorboard = TensorboardConfig.from_dict(d.get(C.TENSORBOARD))
        self.telemetry = TelemetryConfig.from_dict(d.get(C.TELEMETRY))
        self.resilience = ResilienceConfig.from_dict(d.get(C.RESILIENCE))
        self.comm = CommConfig.from_dict(d.get(C.COMM))
        self.guardrails = GuardrailsConfig.from_dict(d.get(C.GUARDRAILS))
        self.serving = ServingConfig.from_dict(d.get(C.SERVING))
        self.autotuning = AutotuningConfig.from_dict(d.get(C.AUTOTUNING))
        self.moe = MoeConfig.from_dict(d.get(C.MOE))
        self.sparse_attention = d.get(C.SPARSE_ATTENTION)
        self.pipeline = dict(d.get(C.PIPELINE, {}))
        self.eigenvalue = dict(d.get(C.EIGENVALUE, {}))
        self.quantize_training = dict(d.get(C.QUANTIZE_TRAINING, {}))

        # --- misc ------------------------------------------------------------------
        self.steps_per_print = int(_get(d, C.STEPS_PER_PRINT, C.STEPS_PER_PRINT_DEFAULT))
        self.wall_clock_breakdown = bool(_get(d, C.WALL_CLOCK_BREAKDOWN,
                                              C.WALL_CLOCK_BREAKDOWN_DEFAULT))
        self.memory_breakdown = bool(_get(d, C.MEMORY_BREAKDOWN,
                                          C.MEMORY_BREAKDOWN_DEFAULT))
        self.dump_state = bool(_get(d, C.DUMP_STATE, C.DUMP_STATE_DEFAULT))
        # Numerics debug mode (SURVEY §5's determinism/debug lever): every
        # train_batch verifies loss and params are finite (one host sync
        # per step — a DEBUG tool) and raises naming the step + leaves.
        self.check_numerics = bool(_get(d, C.CHECK_NUMERICS,
                                        C.CHECK_NUMERICS_DEFAULT))
        self.sparse_gradients_enabled = bool(_get(d, C.SPARSE_GRADIENTS,
                                                  C.SPARSE_GRADIENTS_DEFAULT))

        self._validate()

    # ------------------------------------------------------------------
    def _apply_elasticity(self, d: Dict[str, Any]) -> None:
        """Let the elastic config own the batch triple (reference
        runtime/config.py:679-733): compute (batch, micro, gas) for the
        current world size and write them into the param dict."""
        from deepspeed_tpu.elasticity import (ElasticityConfigError,
                                              compute_elastic_config,
                                              ensure_immutable_elastic_config)
        from deepspeed_tpu.utils.logging import logger
        from deepspeed_tpu.version import __version__

        final_batch, valid, micro = compute_elastic_config(
            d, __version__, world_size=self.world_size)
        ensure_immutable_elastic_config(self.elasticity)
        batch_keys = (C.TRAIN_BATCH_SIZE, C.TRAIN_MICRO_BATCH_SIZE_PER_GPU,
                      C.TRAIN_MICRO_BATCH_SIZE_PER_CHIP,
                      C.GRADIENT_ACCUMULATION_STEPS)
        if not self.elasticity.get("ignore_non_elastic_batch_info", False):
            if any(k in d for k in batch_keys):
                raise ElasticityConfigError(
                    "batch parameters found in config but elastic training "
                    "controls them; set "
                    "'ignore_non_elastic_batch_info': true to silence")
        gas = final_batch // (micro * self.world_size)
        logger.info("[Elasticity] batch=%d micro=%d gas=%d valid chip "
                    "counts: %s", final_batch, micro, gas, valid)
        d[C.TRAIN_BATCH_SIZE] = final_batch
        d[C.TRAIN_MICRO_BATCH_SIZE_PER_GPU] = micro
        d[C.GRADIENT_ACCUMULATION_STEPS] = gas
        self.elastic_valid_world_sizes = valid

    @staticmethod
    def _default_world() -> int:
        try:
            import jax

            return jax.device_count()
        except Exception:
            return 1

    @staticmethod
    def _resolve_batch_triple(train: Optional[int], micro: Optional[int],
                              gas: Optional[int], dp: int):
        """Solve/validate train = micro × gas × dp (reference config.py:822-893)."""
        if train is not None:
            train = int(train)
        if micro is not None:
            micro = int(micro)
        if gas is not None:
            gas = int(gas)

        if all(v is not None for v in (train, micro, gas)):
            if train != micro * gas * dp:
                raise ConfigError(
                    f"batch sizes inconsistent: train_batch_size={train} != "
                    f"micro({micro}) × gas({gas}) × dp({dp})")
        elif train is not None and micro is not None:
            if train % (micro * dp) != 0:
                raise ConfigError(
                    f"train_batch_size {train} not divisible by micro×dp={micro * dp}")
            gas = train // (micro * dp)
        elif train is not None and gas is not None:
            if train % (gas * dp) != 0:
                raise ConfigError(
                    f"train_batch_size {train} not divisible by gas×dp={gas * dp}")
            micro = train // (gas * dp)
        elif micro is not None:
            gas = gas or 1
            train = micro * gas * dp
        elif train is not None:
            gas = 1
            if train % dp != 0:
                raise ConfigError(f"train_batch_size {train} not divisible by dp={dp}")
            micro = train // dp
        else:
            raise ConfigError(
                "at least one of train_batch_size / train_micro_batch_size_per_gpu "
                "must be specified")
        for name, v in (("train_batch_size", train),
                        ("train_micro_batch_size_per_gpu", micro),
                        ("gradient_accumulation_steps", gas)):
            if v <= 0:
                raise ConfigError(f"{name} must be positive, got {v}")
        return train, micro, gas

    def _validate(self) -> None:
        if self.zero_config.stage >= 2 and self.pipeline.get("stages", self.mesh.pipe) > 1 \
                and self.mesh.pipe > 1:
            raise ConfigError("ZeRO stage >= 2 is incompatible with pipeline parallelism; "
                              "use stage 1 (reference pipe/engine.py:56)")
        if self.fp16.enabled and self.amp_enabled:
            raise ConfigError("fp16 and amp cannot both be enabled")
        if self.zero_config.zeropp.active:
            # Validated HERE (not only in the engine) so the user-level
            # initialize(model=..., offload_param=...) path fails with
            # the real cause instead of crashing in the offload-tier
            # model conversion it runs before engine construction.
            if self.zero_config.offload_param.enabled:
                raise ConfigError(
                    "zero_optimization.zeropp cannot compose with "
                    "offload_param: the hpZ secondary replica lives in "
                    "HBM while the offloaded primary partition lives in "
                    "host memory — the explicit quantized param gather "
                    "is a mesh collective, not a host fetch; drop "
                    "offload_param or disable zeropp")
            if self.zero_config.offload_optimizer.enabled:
                raise ConfigError(
                    "zero_optimization.zeropp cannot compose with "
                    "offload_optimizer: the offload tier's params reach "
                    "the device by host transfer, not a mesh all-gather "
                    "— there is no wire hop for qwZ to quantize; use a "
                    "device-resident optimizer tier")
        if self.elasticity_live.enabled:
            # Live elasticity rebuilds the mesh + step functions in-process
            # from gathered host state; the tiers below own their own state
            # layout or wire protocol and cannot be resharded behind their
            # backs — fail at parse with the real cause. (The ladder and
            # pipeline walls fire earlier, in __init__, before the batch
            # triple can mask them.)
            if self.zero_config.zeropp.active:
                raise ConfigError(
                    "elasticity.live cannot compose with "
                    "zero_optimization.zeropp yet: the explicit param "
                    "gather plan bakes the mesh into its wire layout — "
                    "drop zeropp or disable elasticity.live")
            if (self.zero_config.offload_param.enabled
                    or self.zero_config.offload_optimizer.enabled):
                raise ConfigError(
                    "elasticity.live cannot compose with the offload "
                    "tiers: host-resident master/param state is laid out "
                    "per-partition and the in-process reshard path "
                    "(install_state_arrays) only re-places device state")
            if str(self.optimizer_name or "").startswith("onebit"):
                raise ConfigError(
                    "elasticity.live cannot compose with 1-bit "
                    "optimizers: the error-compensated compressed-"
                    "momentum buffers are rank-local and do not survive "
                    "a world change")
        if self.autotuning.enabled:
            # The tuner's measured trials swap configs in-process through
            # the fused data-parallel tiers' _elastic_rebuild path; the
            # tiers below own their own state layout or wire protocol and
            # cannot be rebuilt behind their backs — same walls (and the
            # same reasons) as elasticity.live. The host-IMPLIED optimizer
            # tier (optimizer.type "cpuadam") resolves only at engine
            # level; deepspeed_tpu.autotune() re-checks it there.
            if (self.mesh.pipe > 1
                    or int(self.pipeline.get("stages", 1)) > 1):
                raise ConfigError(
                    "autotuning cannot compose with pipeline parallelism: "
                    "the pipe engine compiles its own schedule and the "
                    "in-process trial rebuild only re-places the fused "
                    "data-parallel tiers")
            if (self.zero_config.offload_param.enabled
                    or self.zero_config.offload_optimizer.enabled):
                raise ConfigError(
                    "autotuning cannot compose with the offload tiers: "
                    "host-resident master/param state is laid out per-"
                    "partition and the in-process trial rebuild "
                    "(install_state_arrays) only re-places device state")
            if str(self.optimizer_name or "").startswith("onebit"):
                raise ConfigError(
                    "autotuning cannot compose with 1-bit optimizers: the "
                    "error-compensated compressed-momentum buffers are "
                    "rank-local and do not survive a trial rebuild")
        if self.moe.enabled:
            # Expert-parallel composition walls (docs/MOE.md): the tiers
            # below own a state layout or program the expert-axis-sharded
            # stacked params cannot ride — fail at parse with the real
            # cause. These walls are also what makes the moe autotuner
            # axes prune invalid combos for free.
            if self.moe.num_experts % max(self.mesh.expert, 1) != 0:
                raise ConfigError(
                    f"moe.num_experts ({self.moe.num_experts}) must "
                    f"divide by the mesh expert axis "
                    f"({self.mesh.expert}): experts are one stacked "
                    f"leaf sharded over that axis")
            if (self.mesh.pipe > 1
                    or int(self.pipeline.get("stages", 1)) > 1):
                raise ConfigError(
                    "moe cannot compose with pipeline parallelism: the "
                    "pipe engine stacks its blocks into one scanned "
                    "program — a per-layer FFN/MoE swap breaks the "
                    "homogeneous stack; use the fused data-parallel "
                    "engine")
            if (self.zero_config.offload_param.enabled
                    or self.zero_config.offload_optimizer.enabled):
                raise ConfigError(
                    "moe cannot compose with the offload tiers: the "
                    "host-resident master partition is laid out over "
                    "(data,) flat shards and the expert-axis-sharded "
                    "stacked params do not fit it")
            if str(self.optimizer_name or "").startswith("onebit"):
                raise ConfigError(
                    "moe cannot compose with 1-bit optimizers: the "
                    "error-feedback buffers assume the (data,)-only "
                    "grad bucket layout, which expert-axis-sharded "
                    "grads break")
        if (self.telemetry.memory.enabled and self.guardrails.watchdog.enabled
                and self.telemetry.memory.oom_exit_code
                == self.guardrails.watchdog.exit_code):
            # The supervisor maps the watchdog rc to an IMMEDIATE restart
            # and the OOM rc to NO restart — one rc cannot mean both, and
            # the collision would hot-loop every deterministic OOM.
            raise ConfigError(
                f"telemetry.memory.oom_exit_code "
                f"({self.telemetry.memory.oom_exit_code}) collides with "
                f"guardrails.watchdog.exit_code — the supervisor restarts "
                f"watchdog exits immediately but must NOT restart OOM "
                f"exits; pick distinct codes")

    # convenience accessors mirroring the reference's getters ------------------
    @property
    def precision_dtype(self) -> str:
        if self.bf16_enabled:
            return "bfloat16"
        if self.fp16.enabled:
            return "float16"
        return "float32"

    @property
    def loss_scale(self) -> float:
        return self.fp16.loss_scale if self.fp16.enabled else 1.0

    @property
    def dynamic_loss_scale(self) -> bool:
        return self.fp16.enabled and self.fp16.dynamic_loss_scale

    def print_config(self) -> None:
        from deepspeed_tpu.utils.logging import logger

        logger.info("DeepSpeedTPUConfig:")
        logger.info(json.dumps(self._param_dict, indent=2, sort_keys=True, default=str))
