"""What ``run.py``, the drivers and the metric readers share: finding
files by the names the data gives, the context a run carries, the compile
counter, the profiler switch and the reduction of a traced window."""

import atexit
import glob
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

OUT_DIR = os.path.join(ROOT, ".bench_out")   # traces; listed in .gitignore


def capture_dir(cell: str, rehearsal: bool) -> str:
    """Where a traced run of ``cell`` leaves its capture, the last one
    taking the place of the one before: one directory a cell on the chip,
    and one a cell and PROCESS in a rehearsal, because the tests rehearse
    one cell from several worker processes at once and each removes what
    it finds."""
    return os.path.join(OUT_DIR, f"{cell}.rehearsal.{os.getpid()}"
                        if rehearsal else cell)


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts: str) -> Any:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py``, a dot in ``name`` being a
    directory: found by the name a data file or ``BENCHMARK.json`` gives."""
    path = os.path.join(HERE, kind, *name.split(".")) + ".py"
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{kind[:-1]} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}; it has "
                   f"{[e['name'] for e in entries]}")


def with_rehearsal_sizes(doc: Dict) -> Dict:
    """The data file with its ``rehearsal`` block laid over it (one level
    deep for nested blocks)."""
    out = dict(doc)
    for key, value in doc.get("rehearsal", {}).items():
        out[key] = ({**doc[key], **value}
                    if isinstance(value, dict) and isinstance(doc.get(key),
                                                              dict)
                    else value)
    return out


def open_cell(workload: str, rehearsal: bool = False):
    """``(bench, cell, config, traffic)`` of a cell of ``BENCHMARK.json``,
    at the rehearsal's sizes if asked."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find(bench["workloads"], workload, "workload")
    config = load_json(ROOT, find(bench["configs"], cell["config"],
                                  "config")["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if rehearsal:
        config = with_rehearsal_sizes(config)
        traffic = with_rehearsal_sizes(traffic)
    return bench, cell, config, traffic


def start_device(cell: Dict, rehearsal: bool = False):
    """``(device, peaks, cache dir)``: a TPU whose kind has published
    peaks and enough chips for the cell, with the persistent compile
    cache placed, or the process exits with no result. A rehearsal takes
    the CPU, no peaks and no cache, and refuses a TPU."""
    import jax
    from benchmarks import flops

    dev = jax.devices()[0]          # a backend that cannot start raises
    if rehearsal:
        if dev.platform == "tpu":
            sys.exit("--rehearsal is for a machine without a chip; this "
                     "one has a TPU: run the plain command")
        peaks, cache = None, "off (rehearsal)"
    else:
        if dev.platform != "tpu":
            sys.exit(f"the benchmark needs a TPU; jax found platform "
                     f"{dev.platform!r} ({dev.device_kind}). Nothing ran.")
        peaks = flops.load_peaks(dev.device_kind)
        from deepspeed_tpu.utils.compile_cache import configure_compile_cache
        cache = configure_compile_cache()
        # Cache every program, however quick its compile: a run after the
        # first finds them all.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if jax.device_count() < cell["chips"]:
        sys.exit(f"cell {cell['name']!r} needs {cell['chips']} chip(s); "
                 f"jax found {jax.device_count()}. Nothing ran.")
    return dev, peaks, cache


def metrics_of(bench: Dict, kind: str, cell: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


COMPILE_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
                  "/jax/core/compile/backend_compile_duration":
                      "backend_compile"}


@dataclass
class CompileEvent:
    """One span JAX reports while it makes a program: ``kind`` is
    ``trace``, ``lower`` or ``backend_compile`` (XLA compiling, or the
    persistent cache loading), times on ``time.perf_counter``'s clock."""
    kind: str
    fun_name: str
    start: float
    end: float


class Compiles:
    """Counts what ``jax.monitoring`` reports: every executable built or
    loaded from the persistent cache (``backend_compile``), and the
    cache's hits and misses. It also keeps the log: each trace, lowering
    and backend compile with its function's name, start and end
    (``events``), and the time each hit and miss arrived
    (``cache_events``: ``("hit" or "miss", time)``), all on the clock of
    ``Run.t0`` and of the window's start."""

    def __init__(self):
        import jax.monitoring as mon
        self.count = self.hits = self.misses = 0
        self.events: List[CompileEvent] = []
        self.cache_events: List[tuple] = []
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        mon.register_event_time_span_listener(self._on_span)

    def _on_duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
            self.cache_events.append(("hit", time.perf_counter()))
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
            self.cache_events.append(("miss", time.perf_counter()))

    def _on_span(self, event, start, end, **kw):
        kind = COMPILE_EVENTS.get(event)
        if kind is None:
            return
        # JAX times the span on time.time(); the listener runs as it ends
        shift = time.perf_counter() - time.time()
        self.events.append(CompileEvent(kind, str(kw.get("fun_name", "")),
                                        start + shift, end + shift))


@dataclass
class Run:
    """What a driver, a family and a metric reader are handed."""
    cell: Dict
    config: Dict
    traffic: Dict
    family: Any
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    peaks: Optional[Dict]
    compiles: Compiles
    t0: float = 0.0
    window_start: Optional[float] = None         # where set-up ends
    xplane_dir: str = ""
    phases: List = field(default_factory=list)   # (name, seconds) of set-up
    memory_peak_bytes: Optional[int] = None      # see note_memory_peak

    def mark(self, phase: str) -> None:
        """Close a phase of set-up: its seconds since the last mark (or
        the process's start) go on an earlier line of the output."""
        now = time.perf_counter()
        self.phases.append((phase, now - self.t0
                            - sum(sec for _, sec in self.phases)))

    def note_memory_peak(self) -> None:
        """A driver calls this when the system under test has done its
        work, before the reference runs on the same device: the peak
        reported is then the system's and not the yardstick's."""
        self.memory_peak_bytes = memory_peak_bytes()

    @property
    def chips(self) -> int:
        return self.cell["chips"]

    def annotate(self, name: str, **kw):
        """A host span that lands in the profiler's trace as
        ``bench.<name>`` (and costs next to nothing when none runs)."""
        import jax
        return jax.profiler.TraceAnnotation("bench." + name, **kw)

    def start_trace(self) -> None:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # op and annotation events only
        options.host_tracer_level = 2
        self.xplane_dir = capture_dir(self.cell["name"], self.rehearsal)
        shutil.rmtree(self.xplane_dir, ignore_errors=True)
        if self.rehearsal:      # a process's own directory goes with it
            atexit.register(shutil.rmtree, self.xplane_dir,
                            ignore_errors=True)
        jax.profiler.start_trace(self.xplane_dir, profiler_options=options)

    def stop_trace(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def xplane(self) -> Optional[str]:
        found = sorted(glob.glob(os.path.join(
            self.xplane_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the runtime counts them
    over the process so far."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def device_info(run: Run) -> Dict:
    import jax
    dev = jax.devices()[0]
    peak = run.memory_peak_bytes
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count(),
            "memory_peak_bytes": memory_peak_bytes() if peak is None
            else peak}


def read_layer_metrics(wanted: List[Dict], run: Run, observed: Dict):
    """Reduce the traced window and let the reader of each of the cell's
    per-layer metrics (``wanted``) take its number. A reader returns
    ``None`` where it finds nothing to read, and the metric is then left
    out."""
    from benchmarks import trace_reduce
    reduced = None
    path = run.xplane()
    if path is not None:
        trace = trace_reduce.load_xplane(path)
        if any(trace.devices.values()):
            reduced = trace_reduce.reduce(trace)
    if reduced is None and not run.rehearsal:
        sys.exit(f"the traced window holds no device operation "
                 f"(xplane: {path})")
    values = {}
    for m in wanted:
        value = load_module("layer_metrics", m["name"]).read(
            run, observed, reduced)
        if value is not None:
            values[m["name"]] = value
    return values, reduced
