"""Goodput accounting — run-level wall-clock attribution + MFU.

The telemetry layer answers *where does step time go* (tracer spans) and
*what happened* (metrics, recompile warnings). This module answers the
question a fleet operator asks about a whole run: **of N hours of
wall-clock, what fraction was productive training, what was lost to which
cause, and what MFU did the productive part achieve?**

:class:`GoodputAccountant` partitions every second of an attempt's wall
clock into one of :data:`CATEGORIES`:

- ``productive_step``   — a committed optimizer step advancing the run;
- ``ckpt_snapshot``     — device→host state copy on the step path;
- ``ckpt_write_stall``  — the step path *blocked* on checkpoint I/O
  (sync-write managers, ``wait()`` drains; async writes cost nothing here);
- ``rollback_restore``  — guardrails restoring a last-good snapshot;
- ``rollback_replay``   — steps re-executed after a rollback rewound the
  step counter (real compute, zero net progress);
- ``data_stall``        — host batch staging + device placement
  (``put_batch``);
- ``recompile``         — a step whose dispatch traced/compiled (the first
  step, and every retrace the detector flags);
- ``init_restore``      — process start → first step: imports, engine
  construction, ``auto_resume`` checkpoint restore;
- ``idle_other``        — everything else (the residual: user code between
  steps, eval batches, logging).

The accounting is **mark-based**: call sites mark phase *boundaries* and
the accountant attributes the elapsed interval, so the categories partition
the timeline exactly by construction (no double counting, no gaps while
the process lives). It performs **zero device syncs and zero host fetches**
— every primitive is ``time.monotonic()`` — so even the *enabled* path
rides free on an async-dispatch runtime; host wall-clock between marks
converges to device time in steady state because the dispatch queue is
bounded (the same argument ``ThroughputTimer(sync=False)`` rests on).
Disabled (``telemetry.goodput: false`` or telemetry off) the engine holds
``goodput = None`` and every hook is one attribute check.

MFU: the engine feeds the accountant the compiled step's XLA
``cost_analysis`` FLOPs once per compiled step function (no per-step
re-analysis); ``engine/mfu`` is then FLOPs / (mean measured step time ×
chips × per-dtype peak) through the shared
:func:`deepspeed_tpu.profiling.flops_profiler.mfu` helper — the same math
``bench.py`` reports.

Run manifest: each attempt persists ``run_manifest.aNNNN.<host>.json``
under the telemetry dir — run id, attempt index (``DSTPU_RESUME_ATTEMPT``),
host, start/end wall+monotonic timestamps, exit rc, restart cause, config
hash, the category totals and MFU. The engine writes it on start, refreshes
it at every metrics flush (so a SIGTERM keeps a recent snapshot) and
finalises it at exit; :func:`finalize_attempt_manifests` lets the
supervisor/launcher stamp the child's exit rc and restart cause after a
death the engine never saw coming. ``tools/goodput_report.py`` merges the
manifests + ``metrics.jsonl`` of every attempt into one run-level report,
turning inter-attempt downtime (backoff, re-init, restore, replay) from
invisible into attributed.
"""

import hashlib
import json
import os
import socket
import threading
import time
from typing import Any, Dict, Optional, Tuple

RUN_ID_ENV = "DSTPU_RUN_ID"
# Overrides the hostname in every per-host telemetry artifact (run
# manifests, host-scoped metrics/trace filenames, fleet rows) — ONE
# convention across goodput and the fleet layer.
TELEMETRY_HOST_ENV = "DSTPU_TELEMETRY_HOST"
# Stamped by the supervisor/launcher at child spawn so the accountant can
# attribute interpreter start-up (imports dwarf engine construction) to
# init_restore instead of leaving it invisible.
ATTEMPT_START_WALL_ENV = "DSTPU_ATTEMPT_START_WALL"

MANIFEST_PREFIX = "run_manifest."
MANIFEST_FORMAT = 1

CATEGORIES = (
    "productive_step",
    "ckpt_snapshot",
    "ckpt_write_stall",
    "rollback_restore",
    "rollback_replay",
    "data_stall",
    "recompile",
    "init_restore",
    # In-process elastic world change (resilience/elastic.py): drain +
    # state gather + mesh/step-fn rebuild + reshard. Mark-based like every
    # other category (the coordinator wraps the whole reshard in ONE
    # measure), so the exact-partition invariant holds and reshard time
    # never leaks into idle_other.
    "elastic_reshard",
    # Startup config search (autotuning/): candidate pruning + in-process
    # measured trials + winner adoption. The tuner quiesces the engine's
    # goodput hooks for the search window and books the WHOLE window with
    # one mark, so trial steps can never masquerade as productive_step
    # and the exact-partition invariant holds.
    "autotune_search",
    "idle_other",
)

_STEP_CATEGORIES = ("productive_step", "rollback_replay")

# Every metric tag this module can emit — the doc-drift lint
# (tests/test_doc_lint.py) checks these against docs/OBSERVABILITY.md in
# BOTH directions.
GOODPUT_METRIC_TAGS = frozenset(
    {f"goodput/{c}_sec" for c in CATEGORIES}
    | {"goodput/wall_sec", "goodput/goodput_frac",
       "goodput/steps_committed", "goodput/pipe_bubble_sec",
       # Sub-attributions riding INSIDE productive_step (aux gauges, not
       # partition categories): modeled exposed-collective time of the
       # hierarchical grad sync, and fleet-level time lost waiting on a
       # straggler host (telemetry/fleet.py).
       "goodput/exposed_comm_sec", "goodput/straggler_sec", "engine/mfu"})


def config_hash(param_dict: Optional[Dict[str, Any]]) -> str:
    """Stable short hash of a raw config dict (ties manifests of the same
    logical run together across attempts)."""
    blob = json.dumps(param_dict or {}, sort_keys=True, default=str)
    return hashlib.sha1(blob.encode()).hexdigest()[:12]


def default_run_id(run_dir: Optional[str]) -> str:
    """``DSTPU_RUN_ID`` when set; else derived from the run dir path so
    every attempt of a supervised run (same dir) agrees without
    coordination."""
    rid = os.environ.get(RUN_ID_ENV)
    if rid:
        return rid
    basis = os.path.abspath(run_dir) if run_dir else "unknown"
    return hashlib.sha1(basis.encode()).hexdigest()[:12]


def _atomic_write_json(path: str, doc: Dict[str, Any]) -> str:
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


class _Measure:
    """Context manager carving a closed interval out of the timeline: the
    measured span is attributed to ``category`` and the mark cursor jumps
    to the exit time, so the enclosing phase's next mark never re-counts
    it. Time pending *before* entry stays pending for the enclosing
    phase's own mark."""

    __slots__ = ("_acc", "_category", "_t0")

    def __init__(self, acc: "GoodputAccountant", category: str):
        self._acc = acc
        self._category = category
        self._t0 = 0.0

    def __enter__(self):
        self._t0 = self._acc._clock()
        return self

    def __exit__(self, *exc):
        now = self._acc._clock()
        dur = now - self._t0
        with self._acc._lock:
            self._acc._attribute_locked(self._category, dur)
            # Shift the cursor forward by exactly the carved duration:
            # time pending before entry stays pending (the enclosing
            # phase's next mark claims it); a mark that ran inside the
            # measured region clamps at `now` (never double-claimed).
            self._acc._last = min(now, self._acc._last + dur)
        return False


class GoodputAccountant:
    """Wall-clock attribution + MFU for ONE attempt of one run.

    Thread-safe (the checkpoint writer may attribute ``ckpt_write_stall``
    from ``wait()`` off the step thread). No jax imports, no device work.
    """

    def __init__(self,
                 registry=None,
                 run_dir: Optional[str] = None,
                 run_id: Optional[str] = None,
                 attempt: Optional[int] = None,
                 host: Optional[str] = None,
                 cfg_hash: str = "",
                 clock=time.monotonic,
                 wall_clock=time.time,
                 env: Optional[Dict[str, str]] = None):
        env = os.environ if env is None else env
        self.registry = registry
        self.run_dir = run_dir
        self.run_id = run_id if run_id is not None else default_run_id(run_dir)
        if attempt is None:
            from deepspeed_tpu.resilience.fault import RESUME_ATTEMPT_ENV
            attempt = int(env.get(RESUME_ATTEMPT_ENV, "0") or 0)
        self.attempt = int(attempt)
        self.host = (host or env.get(TELEMETRY_HOST_ENV)
                     or socket.gethostname().replace(os.sep, "_"))
        self.cfg_hash = cfg_hash
        self.pid = os.getpid()
        self._clock = clock
        self._wall = wall_clock
        self._lock = threading.Lock()
        self._totals: Dict[str, float] = {c: 0.0 for c in CATEGORIES}
        self._aux: Dict[str, float] = {}
        # Live-elasticity bookkeeping (resilience/elastic.py): world-change
        # timeline entries + eviction decisions, persisted in the run
        # manifest so goodput_report/fleet_report can render them.
        self._elastic: list = []
        self._evictions: list = []
        now_mono, now_wall = clock(), wall_clock()
        # Interpreter start-up happened before this object existed; when the
        # spawner stamped the start wall time, backdate the attempt to it
        # and book the lag as init_restore.
        lag = 0.0
        spawn = env.get(ATTEMPT_START_WALL_ENV)
        if spawn:
            try:
                lag = max(0.0, now_wall - float(spawn))
            except ValueError:
                lag = 0.0
        self.start_wall = now_wall - lag
        self.start_monotonic = now_mono - lag
        self._totals["init_restore"] += lag
        self._last = now_mono
        self._saw_step = False
        self._first_step: Optional[int] = None
        self._steps_committed = 0
        self._step_time_sum = 0.0
        self._step_count = 0
        self._last_step_dt: Optional[float] = None
        # MFU inputs: set once per compiled step fn by the engine.
        self._flops_per_step: Optional[float] = None
        self._bytes_per_step: Optional[float] = None
        self._n_chips = 1
        self._peak_tflops: Optional[float] = None
        self._flops_attempted = False
        self._finalized = False
        if run_dir:
            self.write_manifest()

    # -- attribution ----------------------------------------------------
    def _attribute_locked(self, category: str, seconds: float) -> None:
        if seconds > 0.0:
            self._totals[category] = self._totals.get(category, 0.0) + seconds

    def attribute(self, category: str, seconds: float) -> None:
        """Add ``seconds`` to a category WITHOUT moving the mark cursor
        (for time measured elsewhere). Prefer :meth:`mark`/:meth:`measure`
        — they keep the partition exact."""
        with self._lock:
            self._attribute_locked(category, seconds)

    def mark(self, category: str) -> float:
        """Attribute everything since the previous mark to ``category``
        and advance the cursor. Returns the attributed seconds."""
        now = self._clock()
        with self._lock:
            dt = now - self._last
            self._last = now
            self._attribute_locked(category, dt)
        return dt

    def measure(self, category: str) -> _Measure:
        """``with goodput.measure("init_restore"): ...`` — attribute a
        closed interval (see :class:`_Measure` for cursor semantics)."""
        return _Measure(self, category)

    def mark_gap(self) -> float:
        """The between-steps mark: init_restore until the first step has
        run, idle_other afterwards."""
        return self.mark("init_restore" if not self._saw_step
                         else "idle_other")

    def step_mark(self, category: str, committed_step: int) -> float:
        """End-of-step mark. ``category`` is one of productive_step /
        rollback_replay / recompile; productive and replay step durations
        feed the MFU step-time estimate (recompile steps are
        compile-inflated and excluded)."""
        dt = self.mark(category)
        with self._lock:
            self._saw_step = True
            if self._first_step is None:
                self._first_step = int(committed_step)
            self._steps_committed = max(self._steps_committed,
                                        int(committed_step))
            if category in _STEP_CATEGORIES:
                self._step_time_sum += dt
                self._step_count += 1
                self._last_step_dt = dt
        return dt

    def note_world_change(self, entry: Dict[str, Any]) -> None:
        """Append one world-change timeline entry (epoch, step, world,
        cause, reshard seconds) — rendered by tools/goodput_report.py as
        the per-attempt world-change timeline row."""
        with self._lock:
            self._elastic.append(dict(entry))

    def note_eviction(self, entry: Dict[str, Any]) -> None:
        """Record one straggler-eviction decision (host, z-score,
        projected gain, verdict) for the run manifest —
        tools/fleet_report.py renders these beside the straggler table."""
        with self._lock:
            self._evictions.append(dict(entry))

    def reset_flops(self) -> None:
        """Re-arm the once-per-compiled-step cost analysis — called after
        an in-process elastic reshard, whose rebuilt step function has a
        different FLOPs/chips profile (engine/mfu must not keep the old
        world's denominator)."""
        with self._lock:
            self._flops_attempted = False
            self._flops_per_step = None
            self._bytes_per_step = None

    def note_aux(self, name: str, seconds: float) -> None:
        """Cumulative auxiliary gauge (``goodput/<name>``) that is NOT part
        of the wall-clock partition — e.g. the pipeline engine's analytic
        bubble time, which overlaps productive_step."""
        with self._lock:
            self._aux[name] = self._aux.get(name, 0.0) + float(seconds)

    # -- MFU ------------------------------------------------------------
    @property
    def wants_flops(self) -> bool:
        return not self._flops_attempted

    def flops_failed(self) -> None:
        self._flops_attempted = True

    def set_flops(self, flops_per_step: float, n_chips: int = 1,
                  peak_tflops_per_chip: Optional[float] = None,
                  bytes_per_step: Optional[float] = None) -> None:
        """FLOPs (and, when known, bytes accessed) of ONE compiled global
        step (XLA cost_analysis), the chip count it ran across, and the
        per-chip peak — set once per compiled step function by the
        engine. ``bytes_per_step`` feeds the device-time observatory's
        roofline classification (telemetry/devicetime.py)."""
        self._flops_attempted = True
        if flops_per_step and flops_per_step > 0:
            self._flops_per_step = float(flops_per_step)
            self._n_chips = max(int(n_chips), 1)
            self._peak_tflops = peak_tflops_per_chip
            if bytes_per_step and bytes_per_step > 0:
                self._bytes_per_step = float(bytes_per_step)

    def flops_info(self) -> Optional[Dict[str, Any]]:
        """The cost-analysis record :meth:`set_flops` captured (None until
        the engine has fed it): flops / bytes accessed per step, chip
        count, per-chip peak — the device-time observatory's roofline and
        measured-MFU inputs."""
        if self._flops_per_step is None:
            return None
        return {"flops_per_step": self._flops_per_step,
                "bytes_per_step": self._bytes_per_step,
                "n_chips": self._n_chips,
                "peak_tflops_per_chip": self._peak_tflops}

    def mean_step_time(self) -> Optional[float]:
        with self._lock:
            if self._step_count == 0:
                return None
            return self._step_time_sum / self._step_count

    def last_step_time(self) -> Optional[float]:
        """Duration of the most recent measured (productive/replay) step —
        the denominator of the per-step ``comm/exposed_frac`` gauge."""
        with self._lock:
            return self._last_step_dt

    def step_time_stats(self) -> Tuple[float, int]:
        """(cumulative measured step seconds, count) — the fleet
        aggregator differences these across flushes."""
        with self._lock:
            return self._step_time_sum, self._step_count

    def aux_totals(self) -> Dict[str, float]:
        """Copy of the auxiliary (non-partition) gauge totals."""
        with self._lock:
            return dict(self._aux)

    def mfu(self) -> Optional[float]:
        """Model FLOPs utilisation of the measured (productive+replay)
        steps, through the shared flops_profiler helper — one source of
        truth with bench.py. ``None`` (gauge absent) until FLOPs and a
        step time exist, and always on a device with no peak-table
        entry."""
        dt = self.mean_step_time()
        if (self._flops_per_step is None or self._peak_tflops is None
                or dt is None or dt <= 0):
            return None
        from deepspeed_tpu.profiling.flops_profiler import mfu as _mfu
        return _mfu(self._flops_per_step, dt, n_chips=self._n_chips,
                    peak_tflops_per_chip=self._peak_tflops)

    # -- readout / emission --------------------------------------------
    def totals(self) -> Dict[str, float]:
        """Category seconds + ``wall_sec``. The explicit categories plus
        the idle_other residual sum to wall_sec exactly (the un-marked
        tail since the last mark rides in idle_other)."""
        now = self._clock()
        with self._lock:
            out = dict(self._totals)
            pending = max(0.0, now - self._last)
            gap_cat = "init_restore" if not self._saw_step else "idle_other"
            out[gap_cat] += pending
            out["wall_sec"] = now - self.start_monotonic
        return out

    def emit(self, step: int) -> None:
        """Emit cumulative ``goodput/*`` gauges (attempt-tagged, so merged
        multi-attempt ``metrics.jsonl`` files stay attributable) and
        ``engine/mfu`` when the FLOPs are known."""
        reg = self.registry
        if reg is None:
            return
        t = self.totals()
        wall = t.pop("wall_sec")
        for cat in CATEGORIES:
            reg.gauge(f"goodput/{cat}_sec").set(t[cat], step=step,
                                                attempt=self.attempt)
        reg.gauge("goodput/wall_sec").set(wall, step=step,
                                          attempt=self.attempt)
        reg.gauge("goodput/goodput_frac").set(
            (t["productive_step"] / wall) if wall > 0 else 0.0,
            step=step, attempt=self.attempt)
        reg.gauge("goodput/steps_committed").set(
            self._steps_committed, step=step, attempt=self.attempt)
        with self._lock:
            aux = dict(self._aux)
        for name, sec in aux.items():
            reg.gauge(f"goodput/{name}").set(sec, step=step,
                                             attempt=self.attempt)
        m = self.mfu()
        if m is not None:
            reg.gauge("engine/mfu").set(m, step=step, attempt=self.attempt)

    # -- manifest -------------------------------------------------------
    def manifest_path(self) -> Optional[str]:
        if not self.run_dir:
            return None
        return os.path.join(self.run_dir,
                            f"{MANIFEST_PREFIX}a{self.attempt:04d}."
                            f"{self.host}.json")

    def manifest(self, exit_rc: Optional[int] = None,
                 restart_cause: Optional[str] = None,
                 final: bool = False) -> Dict[str, Any]:
        t = self.totals()
        wall = t.pop("wall_sec")
        return {
            "format": MANIFEST_FORMAT,
            "run_id": self.run_id,
            "attempt": self.attempt,
            "host": self.host,
            "pid": self.pid,
            "config_hash": self.cfg_hash,
            "start_wall": self.start_wall,
            "start_monotonic": self.start_monotonic,
            "end_wall": self._wall() if final else None,
            "end_monotonic": self._clock() if final else None,
            "exit_rc": exit_rc,
            "restart_cause": restart_cause,
            "wall_sec": wall,
            "categories": t,
            "aux": self.aux_totals(),
            "first_step": self._first_step,
            "steps_committed": self._steps_committed,
            "mean_step_time_sec": self.mean_step_time(),
            "mfu": self.mfu(),
            "n_chips": self._n_chips,
            "flops_per_step": self._flops_per_step,
            "elastic": [dict(e) for e in self._elastic],
            "eviction_decisions": [dict(e) for e in self._evictions],
        }

    def write_manifest(self, exit_rc: Optional[int] = None,
                       restart_cause: Optional[str] = None,
                       final: bool = False) -> Optional[str]:
        """Atomic manifest (re)write. Called on construction, at every
        metrics flush (crash-freshness) and from :meth:`finalize`."""
        path = self.manifest_path()
        if path is None:
            return None
        try:
            return _atomic_write_json(
                path, self.manifest(exit_rc=exit_rc,
                                    restart_cause=restart_cause, final=final))
        except OSError as e:  # a full disk must never kill the run
            from deepspeed_tpu.utils.logging import logger
            logger.warning("goodput manifest write failed: %s", e)
            return None

    def finalize(self, exit_rc: Optional[int] = None) -> None:
        """End-of-attempt manifest (idempotent; wired to atexit by
        build_goodput). The engine usually cannot know its own exit rc —
        the supervisor stamps it post-mortem via
        :func:`finalize_attempt_manifests`."""
        if self._finalized:
            return
        self._finalized = True
        self.write_manifest(exit_rc=exit_rc, final=True)


def build_goodput(tcfg, telemetry=None, cfg_hash: str = "",
                  register_atexit: bool = True) -> Optional[GoodputAccountant]:
    """``None`` unless the telemetry block is enabled AND its ``goodput``
    flag is on — the engine's hooks gate on ``is None`` (the zero-cost
    contract, same shape as guardrails)."""
    if tcfg is None or not tcfg.enabled or not getattr(tcfg, "goodput", False):
        return None
    registry = telemetry.registry if telemetry is not None else None
    acc = GoodputAccountant(registry=registry, run_dir=tcfg.dir,
                            cfg_hash=cfg_hash)
    if register_atexit:
        import atexit
        atexit.register(acc.finalize)
    return acc


# ---------------------------------------------------------------------------
# Supervisor-side manifest finalisation
# ---------------------------------------------------------------------------

def classify_exit(rc: int, immediate_restart_rcs=(), oom_rcs=(),
                  warned_rcs=()) -> str:
    """Human-readable restart cause from a child exit code."""
    if rc == 0:
        return "clean"
    if rc in set(oom_rcs or ()):
        # The memory observatory's distinct rc (telemetry/memory.py):
        # deterministic OOM — a config bug, not a preemption.
        return "oom"
    if rc in set(immediate_restart_rcs or ()):
        return "watchdog"
    if rc in set(warned_rcs or ()):
        # The live-elasticity coordinator's distinct rc (resilience/
        # elastic.py): the grace-window SIGTERM arrived and WAS handled —
        # state drained to disk — but no surviving capacity fit a valid
        # elastic world, so the process exited deliberately. Distinct
        # from "preemption" (rc -15: the warning was never caught).
        return "preemption_warned"
    if rc < 0 or rc in (128 + 15, 128 + 9):  # signal deaths (Popen: -sig)
        return "preemption"
    return "crash"


def stamp_eviction_decisions(run_dir: str, attempt: int,
                             decisions: list) -> int:
    """Supervisor-side: stamp straggler-eviction decisions (host,
    z-score, projected gain, verdict) onto every host manifest of one
    attempt — the post-mortem record tools/fleet_report.py renders. The
    child's own in-process decisions (GoodputAccountant.note_eviction)
    already live in the manifest; the supervisor's entries merge after
    them, deduplicated by (host, step). Returns manifests touched."""
    if not decisions:
        return 0
    prefix = f"{MANIFEST_PREFIX}a{attempt:04d}."
    touched = 0
    try:
        entries = sorted(os.listdir(run_dir)) if os.path.isdir(run_dir) else []
    except OSError:
        entries = []
    for name in entries:
        if not (name.startswith(prefix) and name.endswith(".json")):
            continue
        path = os.path.join(run_dir, name)
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        have = doc.get("eviction_decisions") or []
        seen = {(d.get("host"), d.get("step")) for d in have}
        for d in decisions:
            if (d.get("host"), d.get("step")) not in seen:
                have.append(dict(d))
        doc["eviction_decisions"] = have
        _atomic_write_json(path, doc)
        touched += 1
    return touched


def finalize_attempt_manifests(run_dir: str, attempt: int, rc: int,
                               cause: str, start_wall: float,
                               end_wall: float) -> int:
    """Stamp exit rc / restart cause / end time onto every host manifest
    of one attempt (the child may have died without running atexit). A
    child that died before engine construction left no manifest at all —
    write a stub so the attempt still appears in the report. Returns the
    number of manifests touched."""
    prefix = f"{MANIFEST_PREFIX}a{attempt:04d}."
    touched = 0
    try:
        entries = sorted(os.listdir(run_dir)) if os.path.isdir(run_dir) else []
    except OSError:
        entries = []
    for name in entries:
        if not (name.startswith(prefix) and name.endswith(".json")):
            continue
        path = os.path.join(run_dir, name)
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        doc["exit_rc"] = rc
        doc["restart_cause"] = cause
        if doc.get("end_wall") is None:
            doc["end_wall"] = end_wall
            # Best effort: the child's monotonic clock is gone; extend
            # wall_sec to the supervisor-observed lifetime so the report's
            # unattributed tail (death after the last refresh) is visible.
            doc["wall_sec"] = max(float(doc.get("wall_sec") or 0.0),
                                  end_wall - float(doc.get("start_wall")
                                                   or start_wall))
        _atomic_write_json(path, doc)
        touched += 1
    if touched == 0 and run_dir:
        _atomic_write_json(
            os.path.join(run_dir, f"{prefix}unknown.json"),
            {"format": MANIFEST_FORMAT, "run_id": default_run_id(run_dir),
             "attempt": int(attempt), "host": "unknown", "pid": None,
             "config_hash": "", "start_wall": start_wall,
             "start_monotonic": None, "end_wall": end_wall,
             "end_monotonic": None, "exit_rc": rc, "restart_cause": cause,
             "wall_sec": max(0.0, end_wall - start_wall),
             "categories": {}, "steps_committed": 0,
             "mean_step_time_sec": None, "mfu": None, "n_chips": None,
             "flops_per_step": None})
        touched = 1
    return touched
