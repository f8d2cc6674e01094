"""Device-time observatory — measured op-level attribution + roofline.

Every comm/compute number the stack reported before this module was
*modeled*: ``comm/exposed_frac`` comes from the grad-sync plan's bandwidth
model and ``engine/mfu`` from XLA ``cost_analysis`` over host-clock step
times. The ground truth sits in ``jax.profiler`` captures that only
hand-run probe scripts ever parsed. This module closes the loop
(docs/OBSERVABILITY.md "Device-time observatory"):

- **Production capture scheduling** — every ``every_steps`` committed
  steps the observatory starts a ``jax.profiler`` capture through the
  engine's :class:`~deepspeed_tpu.telemetry.tracer.StepTracer`, lets it
  run for ``capture_steps`` steps, stops it, parses the capture through
  the shared ``telemetry/traceparse.py`` and GCs all but the newest
  ``keep_last`` capture dirs — attribution runs unattended instead of via
  hand-run probes. Capture dirs are host-scoped (the PR 6
  ``metrics.<host>.jsonl`` convention) so multi-host captures on shared
  storage never collide.
- **Measured op-level attribution** — every HLO op in the capture lands
  in an attribution category (matmul / elementwise fusions / collectives
  / copies+transposes / other, plus the host-dispatch ``gap`` computed
  from the timeline union), emitted as ``devicetime/*`` gauges; the
  top-K hottest-op table names the Pallas-tier candidates (ROADMAP
  item 5).
- **Roofline classification** — the measured per-category time joins the
  step's ``cost_analysis`` flops/bytes (via the goodput accountant's
  :meth:`flops_info`): the step's operational intensity against the
  chip's ridge point classifies each category compute- vs HBM-bound, and
  ``devicetime/mfu_measured`` (flops over *measured device window* time)
  cross-checks the modeled ``engine/mfu``.
- **Measured comm exposure** — collective device time not overlapped by
  compute on the device's other streams becomes
  ``comm/measured_exposed_frac``; when it diverges from the modeled
  ``comm/exposed_frac`` by more than ``divergence_warn`` the observatory
  warns LOUDLY and drops a ``devicetime/divergence`` trace instant — a
  wrong bandwidth model must not silently steer ROADMAP item 1.

Zero-overhead contract (the PR 2/3/5/6/7 gate): ``telemetry.devicetime``
defaults off and :func:`build_devicetime` then returns ``None`` — the
engine holds ``devicetime = None`` and the hook is one attribute check.
Enabled, the steady-state per-step cost is two integer comparisons; all
real work (profiler start/stop, one device drain at capture close so the
capture brackets the issued work, parse, gauge emission, GC) happens at
capture boundaries, never on the in-between step path. The observatory
never touches the jitted step functions — the lowered step is
bit-identical with the block on or off.
"""

import os
import shutil
from typing import Any, Dict, List, Optional

from deepspeed_tpu.telemetry import traceparse
from deepspeed_tpu.telemetry.goodput import _atomic_write_json
from deepspeed_tpu.utils.logging import logger

BREAKDOWN_FILE = "devicetime_breakdown.json"
BREAKDOWN_FORMAT = 1
CAPTURE_PREFIX = "capture_step"

DIVERGENCE_INSTANT = "devicetime/divergence"

# Every metric tag this module can emit (the per-category gauges, the
# capture counter, the divergence instant and the measured exposed-comm
# gauge) — pinned against docs/OBSERVABILITY.md in BOTH directions by
# tests/test_doc_lint.py, like GOODPUT/FLEET/MEMORY_METRIC_TAGS.
DEVICETIME_METRIC_TAGS = frozenset(
    {f"devicetime/{c}_sec" for c in traceparse.CATEGORIES}
    | {"devicetime/gap_sec", "devicetime/busy_sec", "devicetime/window_sec",
       "devicetime/steps_captured", "devicetime/step_time_sec",
       "devicetime/mfu_measured", "devicetime/captures",
       DIVERGENCE_INSTANT, "comm/measured_exposed_frac"})


def roofline_verdicts(intensity: Optional[float],
                      ridge: float) -> Dict[str, str]:
    """Per-category compute- vs HBM-bound classification: the step's
    measured-time-weighted categories joined with its cost_analysis
    operational intensity (flops/byte) against the chip ridge point.
    Matmul inherits the program's intensity verdict (it owns ~all the
    flops); elementwise fusions and copies are bandwidth traffic by
    construction; collectives are network-bound — their fix is overlap
    (ROADMAP item 1), not arithmetic."""
    matmul = "unknown"
    if intensity is not None and ridge > 0:
        matmul = "compute-bound" if intensity >= ridge else "hbm-bound"
    return {"matmul": matmul, "elementwise": "hbm-bound",
            "copy": "hbm-bound", "collective": "network-bound",
            "other": "mixed"}


class DeviceTimeObservatory:
    """Capture scheduling + measured attribution for one engine.

    ``step_hook(step)`` is called once per committed step (from the
    engine's ``_emit_step_telemetry``); everything else is internal.
    """

    def __init__(self, dcfg, run_dir: str, telemetry=None, goodput=None,
                 host: Optional[str] = None):
        self.cfg = dcfg
        self.telemetry = telemetry
        self.goodput = goodput
        from deepspeed_tpu.telemetry.fleet import (default_host,
                                                   telemetry_host_component)
        self._host_part = host if host is not None \
            else telemetry_host_component()
        self.host = self._host_part or default_host()
        self.capture_root = os.path.join(run_dir, dcfg.dir)
        from deepspeed_tpu.telemetry.fleet import host_scoped_path
        self.breakdown_path = os.path.join(
            run_dir, host_scoped_path(BREAKDOWN_FILE, self._host_part))
        self._capture_dir: Optional[str] = None
        self._capture_start_step: Optional[int] = None
        self._own_dirs: List[str] = []
        self.captures_done = 0
        self.last_analysis: Optional[Dict[str, Any]] = None
        self.last_breakdown: Optional[Dict[str, Any]] = None
        self._warned_no_peak = False

    # -- scheduling ------------------------------------------------------
    def step_hook(self, step: int) -> None:
        """Per committed step. Steady state is two int compares; profiler
        start/stop + parse happen only at capture boundaries."""
        if self._capture_dir is not None:
            if step - self._capture_start_step >= int(self.cfg.capture_steps):
                self._finish_capture(step)
        elif step > 0 and step % int(self.cfg.every_steps) == 0:
            self._start_capture(step)

    def _start_capture(self, step: int) -> None:
        tracer = getattr(self.telemetry, "tracer", None)
        if tracer is None or tracer.profiler_active:
            # A passthrough session (telemetry.trace.jax_profiler_dir) is
            # already running — scheduling must not fight it.
            return
        target = os.path.join(self.capture_root,
                              f"{CAPTURE_PREFIX}{step:08d}")
        started = tracer.start_jax_profiler(dir=target)
        if started is None:
            return
        # Track the HOST-SCOPED dir the tracer actually captured into
        # (root/<host> on multi-host runs): parsing/GC'ing the shared
        # root would ingest — and delete — other hosts' captures.
        self._capture_dir = started
        self._capture_start_step = step
        if started not in self._own_dirs:
            self._own_dirs.append(started)

    def _finish_capture(self, step: int) -> None:
        tracer = getattr(self.telemetry, "tracer", None)
        target, start_step = self._capture_dir, self._capture_start_step
        self._capture_dir = None
        self._capture_start_step = None
        try:
            # Drain the dispatch queue so the capture brackets exactly the
            # device work the captured steps issued (one sync per capture
            # close — never on the in-between step path).
            from deepspeed_tpu.utils import timer as _timer
            _timer._device_synchronize()
        except Exception:  # noqa: BLE001 — backend may be torn down
            pass
        if tracer is not None:
            tracer.stop_jax_profiler()
        steps_captured = max(1, step - start_step)
        try:
            analysis = traceparse.parse_capture_dir(target)
        except Exception as e:  # noqa: BLE001 — observability must never
            # take down the step loop it observes
            logger.warning("devicetime: capture parse failed: %s", e)
            return
        if not analysis["captures"] or analysis["window_sec"] <= 0:
            # A torn/empty capture (profiler failed to dump, no parseable
            # device events) must not overwrite the gauges with zeros —
            # and a zero measured_frac against a high modeled fraction
            # would fire a guaranteed-spurious divergence warning.
            logger.warning(
                "devicetime: capture at step %d produced no parseable "
                "device events (%s) — skipping emission", step, target)
            self._gc_captures()
            return
        self.captures_done += 1
        self.last_analysis = analysis
        self._emit(analysis, step, steps_captured)
        self._gc_captures()

    def _gc_captures(self) -> None:
        keep = int(self.cfg.keep_last)
        while len(self._own_dirs) > keep:
            victim = self._own_dirs.pop(0)
            shutil.rmtree(victim, ignore_errors=True)
            # Host-scoped capture: drop the shared per-step root too once
            # every host has GC'd its subdir (rmdir refuses non-empty).
            parent = os.path.dirname(victim)
            if os.path.basename(parent).startswith(CAPTURE_PREFIX):
                try:
                    os.rmdir(parent)
                except OSError:
                    pass

    # -- emission --------------------------------------------------------
    def _flops_info(self) -> Optional[Dict[str, Any]]:
        if self.goodput is None:
            return None
        return self.goodput.flops_info()

    def _gauge_value(self, tag: str) -> Optional[float]:
        tel = self.telemetry
        if tel is None:
            return None
        v = tel.registry.gauge(tag).value
        return float(v) if v is not None else None

    def _emit(self, analysis: Dict[str, Any], step: int,
              steps_captured: int) -> None:
        tel = self.telemetry
        if tel is None or not getattr(tel, "enabled", False):
            return
        reg = tel.registry
        for cat in traceparse.CATEGORIES:
            reg.gauge(f"devicetime/{cat}_sec").set(
                analysis["categories"][cat], step=step)
        reg.gauge("devicetime/gap_sec").set(analysis["gap_sec"], step=step)
        reg.gauge("devicetime/busy_sec").set(analysis["busy_sec"], step=step)
        reg.gauge("devicetime/window_sec").set(analysis["window_sec"],
                                               step=step)
        reg.gauge("devicetime/steps_captured").set(steps_captured, step=step)
        reg.counter("devicetime/captures").inc(step=step)

        # Measured step time: per-device window over the captured steps.
        n_dev = max(analysis["n_devices"], 1)
        step_time = (analysis["window_sec"] / n_dev / steps_captured
                     if analysis["window_sec"] > 0 else None)
        if step_time:
            reg.gauge("devicetime/step_time_sec").set(step_time, step=step)

        # Measured comm exposure vs the modeled gauge.
        window = analysis["window_sec"]
        measured_frac = (analysis["exposed_collective_sec"] / window
                         if window > 0 else 0.0)
        reg.gauge("comm/measured_exposed_frac").set(measured_frac, step=step)
        modeled_frac = self._gauge_value("comm/exposed_frac")
        if (modeled_frac is not None
                and abs(measured_frac - modeled_frac)
                > float(self.cfg.divergence_warn)):
            logger.warning(
                "devicetime: MEASURED exposed-comm fraction %.1f%% diverges "
                "from the modeled comm/exposed_frac %.1f%% by more than "
                "%.0f%% — the comm.ici_gbps/dcn_gbps bandwidth model (or "
                "the overlap assumption) is wrong; trust the capture.",
                100.0 * measured_frac, 100.0 * modeled_frac,
                100.0 * float(self.cfg.divergence_warn))
            tel.instant(DIVERGENCE_INSTANT, step=step,
                        measured=round(measured_frac, 4),
                        modeled=round(modeled_frac, 4))

        # Roofline + measured MFU (cost_analysis join).
        info = self._flops_info()
        mfu_measured = None
        intensity = None
        ridge = None
        if info is not None:
            import jax

            from deepspeed_tpu.profiling.flops_profiler import (
                mfu as _mfu, peak_hbm_gbps, peak_tflops)
            kind = jax.devices()[0].device_kind
            peak = info.get("peak_tflops_per_chip")
            if peak is None:
                peak = peak_tflops(kind)
            hbm = float(self.cfg.hbm_gbps) if self.cfg.hbm_gbps \
                else peak_hbm_gbps(kind)
            if info.get("bytes_per_step"):
                intensity = info["flops_per_step"] / info["bytes_per_step"]
            if peak is None or hbm is None:
                # No peak, no figure: the roofline verdict stays
                # "unknown" and the measured-MFU gauge is absent.
                if not self._warned_no_peak:
                    self._warned_no_peak = True
                    logger.info(
                        "devicetime: device kind %r has no entry in the "
                        "peak tables (profiling/flops_profiler) — "
                        "devicetime/mfu_measured and the roofline verdict "
                        "are not reported", kind)
            else:
                ridge = (peak * 1e12) / (hbm * 1e9) if hbm > 0 else 0.0
                if step_time:
                    mfu_measured = _mfu(info["flops_per_step"], step_time,
                                        n_chips=info["n_chips"],
                                        peak_tflops_per_chip=peak)
                    reg.gauge("devicetime/mfu_measured").set(mfu_measured,
                                                             step=step)
        verdicts = roofline_verdicts(intensity, ridge or 0.0)

        hot = traceparse.top_ops(analysis, int(self.cfg.top_k))
        self.last_breakdown = {
            "format": BREAKDOWN_FORMAT,
            "step": int(step),
            "host": self.host,
            "steps_captured": int(steps_captured),
            "n_devices": analysis["n_devices"],
            "categories_sec": dict(analysis["categories"]),
            "gap_sec": analysis["gap_sec"],
            "busy_sec": analysis["busy_sec"],
            "window_sec": analysis["window_sec"],
            "step_time_sec": step_time,
            "top_ops": hot,
            "roofline": {
                "intensity_flops_per_byte": intensity,
                "ridge_flops_per_byte": ridge,
                "verdicts": verdicts,
            },
            "mfu_measured": mfu_measured,
            "mfu_modeled": self._gauge_value("engine/mfu"),
            "exposed_comm": {
                "collective_sec": analysis["collective_sec"],
                "exposed_sec": analysis["exposed_collective_sec"],
                "measured_frac": measured_frac,
                "modeled_frac": modeled_frac,
            },
            "captures": list(analysis.get("captures", [])),
        }
        try:
            _atomic_write_json(self.breakdown_path, self.last_breakdown)
        except OSError as e:
            logger.warning("devicetime breakdown write failed: %s", e)
        self._log_table(hot, verdicts, analysis, step)

    def _log_table(self, hot, verdicts, analysis, step) -> None:
        lines = [f"devicetime @ step {step}: busy "
                 f"{analysis['busy_sec'] * 1e3:.1f} ms, gap "
                 f"{analysis['gap_sec'] * 1e3:.1f} ms "
                 f"({analysis['n_devices']} device row(s))"]
        for cat in traceparse.CATEGORIES:
            sec = analysis["categories"][cat]
            if sec > 0:
                lines.append(f"  {cat:<12} {sec * 1e3:>10.2f} ms "
                             f"[{verdicts.get(cat, '?')}]")
        if hot:
            lines.append("  hottest ops (Pallas-tier candidates):")
            for r in hot:
                lines.append(f"    {r['name']:<32} {r['sec'] * 1e3:>9.2f} ms "
                             f"x{r['count']:<5} {r['category']} "
                             f"({r.get('share_of_busy', 0.0):.1%} of busy)")
        logger.info("%s", "\n".join(lines))


def build_devicetime(tcfg, telemetry=None, goodput=None) -> \
        Optional[DeviceTimeObservatory]:
    """``None`` unless telemetry AND its devicetime block are enabled —
    the engine's hook gates on ``is None`` (the zero-overhead contract,
    same shape as goodput/fleet/memory)."""
    if tcfg is None or not tcfg.enabled or not tcfg.devicetime.enabled:
        return None
    return DeviceTimeObservatory(tcfg.devicetime, run_dir=tcfg.dir,
                                 telemetry=telemetry, goodput=goodput)
