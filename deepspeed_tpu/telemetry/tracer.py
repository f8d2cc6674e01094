"""Step tracer — the program's one span primitive.

``StepTracer.span(name, **ids)`` always opens a
``jax.profiler.TraceAnnotation("ds.<name>", **ids)``: while a
``jax.profiler`` session runs, whoever started it, the span lands on the
host plane of the profiler's own trace, on the same clock as the device
ops, with ``ids`` as the event's stats. With no session it costs well
under a microsecond and records nothing. ``device_scope(name)`` is the
device-side counterpart: a ``jax.named_scope("ds.<name>")`` whose name the
compiler keeps in every op's metadata (docs/OBSERVABILITY.md, "Spans in
the profiler's trace").

When the tracer is enabled (a path is configured) it ALSO records named
spans (dataloader / forward / backward / optimizer_step /
ckpt_snapshot / ckpt_write / ...) as Chrome trace-event JSON, the format
Perfetto and ``chrome://tracing`` open directly, plus instant and counter
events. ``tools/trace_report.py`` renders the same file as a per-span time
breakdown table.

Span semantics on an async-dispatch runtime: XLA queues device work and
returns, so a host-side wall-clock span around a dispatch measures the
*dispatch*, not the compute. When ``sync_spans`` is on (the default for an
enabled tracer), the tracer drains the device queue at every span boundary —
the span then brackets exactly the device work issued inside it, which is
the T3-style "where does step time go" attribution. The sync barrier is
gated on the tracer being enabled: a disabled tracer's ``span()`` is the
profiler annotation alone, which performs **zero** ``block_until_ready``
calls and records no Chrome event. ``sync_spans`` belongs to the
Chrome-JSON file only.

Optional ``jax.profiler`` passthrough: give ``jax_profiler_dir`` and the
tracer starts a profiler session alongside (device-level XLA timeline, for
the cases where host spans aren't enough).
"""

import collections
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

import jax

# Every span and device scope of the program carries this prefix in the
# profiler's trace (benchmarks/program_trace.py finds them by it).
PREFIX = "ds."

# The device scopes the program opens, each with the part of a jitted
# program it brackets. Forward and backward need none: JAX writes
# ``jvp(...)`` and ``transpose(jvp(...))`` into the name stack itself.
DEVICE_SCOPES = {
    "cast_params": "master weights to the compute dtype (and the zeropp "
                   "gather), once per optimizer step",
    "accumulate": "a micro-batch's gradients added into the accumulator",
    "grad_sync": "an explicit gradient sync (hierarchical, 1-bit)",
    "optimizer": "unscale, norm, clip, update, overflow select, zeroing",
    "prefill": "the serving prefill program",
    "pack": "a prefilled cache scattered into pool blocks",
    "decode": "the serving decode program (mixed and speculative alike)",
    "kv_gather": "the pool indexed by live blocks or the table, dequant",
    "kv_write": "the new K and V scattered into the pool",
    "sample": "logits to token ids",
    "mla": "latent attention: both low-rank paths, their inner norms, RoPE, "
           "the attention itself, the output projection",
    "moe_route": "the dropless layer's router: scores, top-k, weights",
    "moe_dispatch": "assignments sorted by held expert, group sizes, the "
                    "token rows gathered into the sorted buffer",
    "moe_experts": "the held experts' grouped matmuls and their SwiGLU",
    "moe_shared": "the shared expert",
    "moe_combine": "the buffer's rows back to their tokens, weighted sum",
    "mtp": "the multi-token-prediction module (its block and head included)",
    "ssm": "a Mamba-2 mixer whole: projections, convolution, recurrence, "
           "gate and group norm",
    "ssm_conv": "the causal depthwise convolution in front of the recurrence",
    "ssm_scan": "the recurrence over a whole prompt, in chunks (prefill)",
    "ssm_step": "the recurrence's one step on the slots' state (decode)",
    "attn": "a grouped-query attention mixer: projections, the attention "
            "over the cache, the output projection",
}


def device_scope(name: str):
    """``jax.named_scope("ds.<name>")`` for a name of ``DEVICE_SCOPES``,
    as a context manager or a function decorator: HLO metadata only, the
    executable computes the same thing."""
    if name not in DEVICE_SCOPES:
        raise KeyError(f"unknown device scope {name!r}; "
                       f"known: {sorted(DEVICE_SCOPES)}")
    return jax.named_scope(PREFIX + name)


def profiler_session_live() -> bool:
    """Whether a ``jax.profiler`` session is recording: exactly when a
    span's stats reach a trace. What is worth fetching only for a trace
    (``TPUEngine._trace_step_counters``) asks this first."""
    return jax.profiler.TraceAnnotation.is_enabled()


def _device_sync() -> None:
    """Drain the device queue. Routed through ``utils.timer`` so the whole
    codebase has ONE sync primitive (tests count calls by patching it)."""
    from deepspeed_tpu.utils import timer as _timer

    _timer._device_synchronize()


class _ProfilerSpan(jax.profiler.TraceAnnotation):
    """The annotation-only span of a disabled tracer: an event in the
    profiler's trace while a session runs, nothing otherwise."""

    __slots__ = ()
    duration = 0.0


class _Span:
    """The enabled tracer's span: the profiler annotation plus a Chrome
    event, with the device drained at both ends under ``sync_spans``."""

    __slots__ = ("_tracer", "name", "args", "_t0", "duration", "_ann")

    def __init__(self, tracer: "StepTracer", name: str, args: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._t0 = 0.0
        self.duration = 0.0
        self._ann = jax.profiler.TraceAnnotation(PREFIX + name, **args)

    def __enter__(self):
        if self._tracer.sync_spans:
            _device_sync()
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set_metadata(self, **ids) -> None:
        """More identifiers for a span that is open (a request's ``rid``
        exists only once the scheduler has drawn it)."""
        self.args.update(ids)
        self._ann.set_metadata(**ids)

    def __exit__(self, *exc):
        if self._tracer.sync_spans:
            _device_sync()
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self.duration = t1 - self._t0
        self._tracer._record(self.name, self._t0, t1, self.args)
        return False


class StepTracer:
    """Chrome trace-event recorder. Thread-safe (the checkpoint writer
    thread emits ckpt_write spans concurrently with the step loop).

    Bounded: at most ``max_events`` events are held (a ring — the OLDEST
    are dropped first, keeping the recent window that matters for triage;
    ``dropped_events`` counts evictions and the saved trace carries the
    count as metadata). This caps both host RAM and the cost of each
    ``save()`` rewrite at a constant, so periodic flushing over an
    arbitrarily long run does O(steps × max_events) work, never
    O(steps²). ``save()`` is also skipped when nothing was recorded since
    the last write."""

    def __init__(self, path: Optional[str] = None, enabled: Optional[bool] = None,
                 sync_spans: bool = True,
                 jax_profiler_dir: Optional[str] = None,
                 max_events: int = 200_000,
                 host: Optional[str] = None):
        self.path = path
        self.enabled = bool(path) if enabled is None else bool(enabled)
        # Sync barriers strictly require an enabled tracer — the zero-cost
        # contract of disabled telemetry.
        self.sync_spans = bool(sync_spans) and self.enabled
        self.jax_profiler_dir = jax_profiler_dir
        self.host = host
        self._events = collections.deque(maxlen=int(max_events))
        self.dropped_events = 0
        self._dirty = False
        self._lock = threading.Lock()
        self._epoch = time.perf_counter()
        # Wall-clock anchor of the ts=0 epoch, persisted in the trace
        # metadata so tools/fleet_report.py can clock-align traces from
        # different hosts onto one timeline.
        self._epoch_wall = time.time()
        self._pid = os.getpid()
        self._profiler_active = False
        self._profiler_dir: Optional[str] = None
        self._atexit_registered = False
        if self.enabled:
            self._meta("process_name", {"name": "deepspeed_tpu"})
            if jax_profiler_dir:
                self.start_jax_profiler()

    def _append(self, ev: Dict[str, Any]) -> None:
        """Caller holds the lock."""
        if len(self._events) == self._events.maxlen:
            self.dropped_events += 1
        self._events.append(ev)
        self._dirty = True

    # -- event helpers --------------------------------------------------
    def _us(self, t: float) -> float:
        return (t - self._epoch) * 1e6

    def _meta(self, name: str, args: Dict[str, Any]) -> None:
        with self._lock:
            self._append({"name": name, "ph": "M", "pid": self._pid,
                          "tid": threading.get_ident(), "args": args})

    def _record(self, name: str, t0: float, t1: float,
                args: Dict[str, Any]) -> None:
        ev = {"name": name, "ph": "X", "pid": self._pid,
              "tid": threading.get_ident(), "ts": self._us(t0),
              "dur": (t1 - t0) * 1e6}
        if args:
            ev["args"] = args
        with self._lock:
            self._append(ev)

    # -- public API -----------------------------------------------------
    def span(self, name: str, **ids):
        """Context manager round a region of host code: always a
        ``ds.<name>`` annotation in the profiler's trace, and a Chrome
        event as well when the tracer is enabled. ``ids`` are host ints,
        floats or short strings already in hand (never a device value).
        The handle exposes ``.duration`` in seconds after exit, 0.0 on
        the annotation-only path."""
        if not self.enabled:
            return _ProfilerSpan(PREFIX + name, **ids)
        return _Span(self, name, ids)

    def instant(self, name: str, **args) -> None:
        if not self.enabled:
            return
        ev = {"name": name, "ph": "i", "s": "t", "pid": self._pid,
              "tid": threading.get_ident(),
              "ts": self._us(time.perf_counter())}
        if args:
            ev["args"] = args
        with self._lock:
            self._append(ev)

    def counter(self, name: str, value: float) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._append({
                "name": name, "ph": "C", "pid": self._pid,
                "tid": threading.get_ident(),
                "ts": self._us(time.perf_counter()),
                "args": {"value": float(value)}})

    def _async(self, ph: str, name: str, aid, cat: str,
               args: Dict[str, Any]) -> None:
        ev = {"name": name, "ph": ph, "cat": cat, "id": str(aid),
              "pid": self._pid, "tid": threading.get_ident(),
              "ts": self._us(time.perf_counter())}
        if args:
            ev["args"] = args
        with self._lock:
            self._append(ev)

    def async_begin(self, name: str, aid, cat: str = "request",
                    **args) -> None:
        """Open an async-track span (Chrome ``ph: b``): async events live
        on their own (cat, id) track, so long-lived arcs — a serving
        request's queue -> prefill -> decode lifecycle — render alongside
        the step spans without nesting inside them. Pair with
        :meth:`async_end` on the same (name, cat, id)."""
        if not self.enabled:
            return
        self._async("b", name, aid, cat, args)

    def async_end(self, name: str, aid, cat: str = "request",
                  **args) -> None:
        if not self.enabled:
            return
        self._async("e", name, aid, cat, args)

    @property
    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def span_names(self) -> set:
        with self._lock:
            return {e["name"] for e in self._events if e.get("ph") == "X"}

    # -- jax.profiler passthrough --------------------------------------
    @property
    def profiler_active(self) -> bool:
        return self._profiler_active

    @staticmethod
    def host_scoped_profile_dir(target: str) -> str:
        """Multi-host capture dirs must not collide on shared storage:
        whenever the run spans processes (or ``DSTPU_TELEMETRY_HOST``
        forces it) the capture lands in a per-host subdir — the same
        convention that host-scopes ``metrics.<host>.jsonl``. Single-host
        paths come back unchanged."""
        try:
            from deepspeed_tpu.telemetry.fleet import \
                telemetry_host_component
            part = telemetry_host_component()
        except Exception:  # noqa: BLE001 — backendless: single-host
            part = None
        return os.path.join(target, part) if part else target

    def start_jax_profiler(self, dir: Optional[str] = None) -> \
            Optional[str]:
        """Start a ``jax.profiler`` capture into ``dir`` (the device-time
        observatory's scheduled captures) or the configured passthrough
        ``jax_profiler_dir``. Returns the host-scoped directory actually
        captured into, or None (already active / no dir / profiler
        unavailable)."""
        target = dir or self.jax_profiler_dir
        if self._profiler_active or not target:
            return None
        try:
            import jax
            target = self.host_scoped_profile_dir(target)
            os.makedirs(target, exist_ok=True)
            jax.profiler.start_trace(target)
            self._profiler_active = True
            self._profiler_dir = target
            # Guarantee stop_trace even when a crash skips close(): an
            # exception between start and stop otherwise leaks the
            # profiler session (and its capture buffer) for the rest of
            # the process. stop is idempotent, so a clean close() +
            # atexit double-fire is harmless.
            if not self._atexit_registered:
                import atexit
                atexit.register(self.stop_jax_profiler)
                self._atexit_registered = True
            return target
        except Exception as e:  # noqa: BLE001 — profiler is best-effort
            from deepspeed_tpu.utils.logging import logger
            logger.warning("jax.profiler passthrough unavailable: %s", e)
            return None

    def stop_jax_profiler(self) -> Optional[str]:
        """Stop the active capture (idempotent). Returns the directory it
        was writing into, or None when nothing was active."""
        if not self._profiler_active:
            return None
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001
            pass
        self._profiler_active = False
        d = getattr(self, "_profiler_dir", None)
        self._profiler_dir = None
        return d

    # -- persistence ----------------------------------------------------
    def save(self) -> Optional[str]:
        """Write the trace file (atomic rename). Cheap to call on a cadence:
        a no-op when nothing was recorded since the last write, and the
        rewrite cost is capped by ``max_events`` — a preemption loses at
        most the events since the previous flush."""
        if not self.enabled or not self.path:
            return None
        with self._lock:
            if not self._dirty:
                return self.path
            events = list(self._events)
            dropped = self.dropped_events
            self._dirty = False
        parent = os.path.dirname(self.path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        doc = {"traceEvents": events, "displayTimeUnit": "ms",
               # wall_epoch: wall-clock time of ts=0 — the cross-host
               # clock-alignment anchor fleet_report merges on.
               "metadata": {"wall_epoch": self._epoch_wall,
                            "host": self.host}}
        if dropped:
            doc["metadata"]["dropped_events"] = dropped
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, self.path)
        return self.path

    flush = save

    def close(self) -> None:
        self.stop_jax_profiler()
        self.save()
