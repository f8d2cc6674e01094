"""MoE observatory: the moe/* gauge family, engine-side.

The model computes the per-step MoE statistics in-program (moe/layer.py
``_dispatch_stats`` — load-balance loss, capacity overflow fraction,
expert utilization, modeled dispatch wire bytes) and the engine's train
step threads them out of the GAS scan with the model's other per-step
counters (one dict, ``step_aux["counters"]``), exactly the numerics
observatory's economy: ``note_step`` stores device-array REFERENCES (no
sync on the step path) and ``flush`` — the telemetry cadence boundary,
``steps_per_print`` — pays ONE ``device_get`` for the whole dict.

``build_moe_monitor`` returns None unless BOTH the ``moe`` config block
and telemetry are enabled; every engine hook is ``is None``-gated, so
the off path adds zero work and the lowered step stays bit-identical
(tests/test_moe.py pins it).
"""

from typing import Any, Dict, Optional

import jax

# Every moe/* tag this module can emit — pinned against
# docs/OBSERVABILITY.md in BOTH directions by tests/test_doc_lint.py,
# like NUMERICS/GOODPUT_METRIC_TAGS.
MOE_METRIC_TAGS = frozenset({
    "moe/load_balance_loss",
    "moe/capacity_overflow_frac",
    "moe/expert_utilization",
    "moe/dispatch_bytes_ici",
})

# The model-output aux keys the engine's step threads through (the
# models/gpt.py moe_stats contract); order irrelevant, names are
# "moe_" + the gauge suffix.
MOE_AUX_KEYS = (
    "moe_load_balance_loss",
    "moe_capacity_overflow_frac",
    "moe_expert_utilization",
    "moe_dispatch_bytes_ici",
)


class MoEMonitor:
    """Engine-side flush point for the moe/* gauges."""

    def __init__(self) -> None:
        self.telemetry = None          # TelemetryFacade, attached late
        self._pending: Optional[Dict[str, Any]] = None
        self._step = -1
        self._gas = 1

    def attach(self, telemetry) -> None:
        self.telemetry = telemetry

    def note_step(self, stats: Dict[str, Any], step: int,
                  gas: int = 1) -> None:
        """Store the step's aux stat references — never a device sync
        (flush pays the one fetch at the cadence boundary)."""
        self._pending = dict(stats)
        self._step = int(step)
        self._gas = max(int(gas), 1)

    def _fetch(self) -> Dict[str, float]:
        fetched = jax.device_get(self._pending)
        self._pending = None
        return {k: float(v) for k, v in fetched.items()}

    def flush(self) -> None:
        if self.telemetry is None or not getattr(
                self.telemetry, "enabled", False) or self._pending is None:
            return
        vals = self._fetch()
        reg = self.telemetry.registry
        for key, v in vals.items():
            if key not in MOE_AUX_KEYS:
                continue        # another layer's counters (the trace's)
            if key == "moe_dispatch_bytes_ici":
                # The model reports per-microstep modeled wire bytes
                # (averaged over the GAS scan of a constant); the gauge
                # is per OPTIMIZER step.
                v *= self._gas
            reg.gauge("moe/" + key[len("moe_"):]).set(v, step=self._step)

    @property
    def last_step(self) -> int:
        return self._step


def build_moe_monitor(config) -> Optional[MoEMonitor]:
    """The engine's single construction point: None — and therefore zero
    step-path work — unless the moe block AND telemetry are enabled."""
    moe = getattr(config, "moe", None)
    tcfg = getattr(config, "telemetry", None)
    if moe is None or not moe.enabled:
        return None
    if tcfg is None or not getattr(tcfg, "enabled", False):
        return None
    return MoEMonitor()
