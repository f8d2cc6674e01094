"""Shared perfetto / Chrome-trace parsing — ONE module, stdlib only.

Before this module the tree had three divergent parsers of the same two
formats: ``tools/trace_report.py`` (StepTracer Chrome traces),
``tools/fleet_report.py --profile-dir`` (``jax.profiler`` perfetto
captures, measured collective time) and ``tools/profile_gpt2.py`` (ad-hoc
cost prints next to a hand-run capture). All of them — plus the
device-time observatory (``telemetry/devicetime.py``), which turns the
same captures into ``devicetime/*`` gauges — now route through here.

Deliberately **stdlib-only and import-clean** (json, gzip, glob, re — no
jax, no numpy, no package imports): the report tools load this file by
path (``importlib.util.spec_from_file_location``) so they keep running on
hosts without jax installed, exactly as before the consolidation.

Two input families, one vocabulary:

- **StepTracer traces** (``trace.json``): host-side span events. The
  ``load_doc`` / ``load_many`` / ``summarize`` family (formerly
  tools/trace_report.py) renders them as per-span breakdowns.
- **``jax.profiler`` captures** (``**/*.trace.json.gz`` under a profile
  dir): device-level XLA op events. ``parse_capture_dir`` classifies
  every HLO op into an attribution category (:data:`CATEGORIES`),
  computes per-device busy/idle unions and the overlap-aware **exposed
  collective time** (collective device time NOT covered by compute on any
  other stream of the same device — the T3-style measured ground truth
  the modeled ``comm/exposed_frac`` is checked against).

:data:`COLLECTIVE_RE` is the one collective-op-name list in the tree.
"""

import collections
import glob as _glob
import gzip
import json
import os
import re
import zlib
from typing import Any, Dict, Iterable, List, Optional, Tuple

# ---------------------------------------------------------------------------
# Op classification
# ---------------------------------------------------------------------------

# XLA collective op names inside a capture (also matches the -start/-done
# async halves). THE one list: fleet_report, devicetime and the report
# tools all import it from here.
COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute",
    re.IGNORECASE)

# Device-time attribution categories (the order reports render them in).
# "gap" (host-dispatch idle between ops) is computed from the timeline
# union, not from op names, so it is not listed here.
CATEGORIES = ("matmul", "elementwise", "collective", "copy", "other")

# HLO op-name charset: lowercase + digits + [-_.]. Runtime/host events
# (``ThreadpoolListener::StartRegion``, ``PjitFunction(<lambda>)``,
# ``$profiler.py:91 start_trace``) all contain characters outside it and
# are excluded from device-time attribution.
_NON_HLO_CHAR_RE = re.compile(r"[^a-z0-9_.\-]")

_MATMUL_STEMS = frozenset({"dot", "dot-general", "convolution", "conv"})
_COPY_STEMS = frozenset({
    "copy", "copy-start", "copy-done", "transpose", "bitcast", "reshape",
    "pad", "slice", "dynamic-slice", "dynamic-update-slice", "concatenate",
    "gather", "scatter", "broadcast", "reverse",
})
_ELEMENTWISE_STEMS = frozenset({
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "exponential", "exponential-minus-one", "exp", "log", "log-plus-one",
    "tanh", "logistic", "rsqrt", "sqrt", "power", "negate", "abs", "sign",
    "floor", "ceil", "round", "clamp", "compare", "select", "and", "or",
    "xor", "not", "convert", "reduce", "reduce-window", "reduce-precision",
    "map", "iota", "rng", "rng-bit-generator", "sine", "cosine",
    "is-finite", "shift-left", "shift-right-logical",
    "shift-right-arithmetic", "rem", "atan2", "cbrt", "expm1", "log1p",
})


def op_stem(name: str) -> str:
    """``'dot.3'`` -> ``'dot'``; ``'fusion.12.remat'`` -> ``'fusion'``."""
    return name.lstrip("%").split(".")[0]


def classify_op(name: str) -> Optional[str]:
    """Attribution category for one event name, or ``None`` when the name
    is not an HLO op (runtime scaffolding, host python frames)."""
    if not name or _NON_HLO_CHAR_RE.search(name):
        return None
    if COLLECTIVE_RE.search(name):
        return "collective"
    stem = op_stem(name)
    if (stem in _MATMUL_STEMS or "gemm" in stem or "matmul" in stem
            or "einsum" in stem or "attention" in stem):
        # Pallas attention kernels (flash/paged/chunked_prefill) surface
        # as custom-call events named after the kernel fn — their cycles
        # are MXU work.
        return "matmul"
    if stem in _COPY_STEMS:
        return "copy"
    if stem in _ELEMENTWISE_STEMS or "fusion" in stem or "adam" in stem:
        # fused_adam_update_kernel: one VPU pass over the flat blocks.
        return "elementwise"
    return "other"


# ---------------------------------------------------------------------------
# Loading (shared by trace_report / fleet_report / devicetime)
# ---------------------------------------------------------------------------

def open_trace(path: str) -> Dict[str, Any]:
    """Load a Chrome-trace document — plain ``.json`` or gzipped
    ``.json.gz`` — normalising the bare-array variant to a dict."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        doc = json.load(f)
    if isinstance(doc, list):
        doc = {"traceEvents": doc}
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: not a Chrome trace (dict or list)")
    events = doc.get("traceEvents", [])
    if not isinstance(events, list):
        raise ValueError(f"{path}: traceEvents is not a list")
    return doc


# trace_report's historical name for the same load.
load_doc = open_trace


def load_events(path: str) -> List[Dict[str, Any]]:
    return open_trace(path)["traceEvents"]


def host_label(path: str, doc: Dict[str, Any]) -> str:
    """Source-host label: trace metadata first, then the
    ``<stem>.<host>.json`` filename component, then the file stem."""
    host = (doc.get("metadata") or {}).get("host")
    if host:
        return str(host)
    stem = os.path.basename(path)
    if stem.endswith(".json"):
        stem = stem[:-len(".json")]
    parts = stem.split(".")
    return parts[-1] if len(parts) > 1 else stem


def load_many(paths: List[str]) -> List[Dict[str, Any]]:
    """Load several trace files into one event list, each event's name
    prefixed with its source host."""
    events: List[Dict[str, Any]] = []
    for path in paths:
        doc = open_trace(path)
        label = host_label(path, doc)
        for ev in doc["traceEvents"]:
            if "name" in ev and ev.get("ph") != "M":
                ev = dict(ev)
                ev["name"] = f"{label}:{ev['name']}"
            events.append(ev)
    return events


def expand_paths(args_traces: List[str]) -> List[str]:
    """Expand glob patterns (quoted globs reach us unexpanded) and keep
    explicit paths as-is."""
    out: List[str] = []
    for t in args_traces:
        matches = sorted(_glob.glob(t))
        out.extend(matches if matches else [t])
    return out


# ---------------------------------------------------------------------------
# Span summaries (formerly tools/trace_report.py)
# ---------------------------------------------------------------------------

def percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    if len(sorted_vals) == 1:
        return sorted_vals[0]
    pos = (q / 100.0) * (len(sorted_vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    return sorted_vals[lo] + (sorted_vals[hi] - sorted_vals[lo]) * (pos - lo)


def _self_durations(spans: List[Dict[str, Any]]) -> List[float]:
    """Each complete event's duration less what its children cover, a
    child being an event of the same thread that lies inside it
    (``train_batch`` encloses ``train_step``, ``serve_step`` encloses
    ``prefill``): self times add up to the time spans cover, durations
    count an enclosed span twice."""
    start = [float(ev.get("ts", 0.0)) for ev in spans]
    end = [s + float(ev.get("dur", 0.0)) for s, ev in zip(start, spans)]
    out = [e - s for s, e in zip(start, end)]
    threads: Dict[Any, List[int]] = {}
    for i, ev in enumerate(spans):
        # load_many prefixes a name with its host: two hosts' threads may
        # share pid and tid, and never nest
        host = ev.get("name", "").rpartition(":")[0]
        threads.setdefault((host, ev.get("pid"), ev.get("tid")),
                           []).append(i)
    for idx in threads.values():
        idx.sort(key=lambda i: (start[i], -end[i]))
        stack: List[int] = []
        for i in idx:
            while stack and end[stack[-1]] <= start[i]:
                stack.pop()
            if stack:
                out[stack[-1]] -= min(end[i], end[stack[-1]]) - start[i]
            stack.append(i)
    return out


def summarize(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Per-span-name totals / percentiles, counter last-values, instant
    counts — the trace_report table's data. ``share`` is a span's share
    of SELF time, so the column adds up to 100% although spans nest."""
    spans: Dict[str, List[float]] = {}
    self_us: Dict[str, float] = {}
    counters: Dict[str, float] = {}
    instants: Dict[str, int] = {}
    complete = [ev for ev in events if ev.get("ph") == "X"]
    for ev, own in zip(complete, _self_durations(complete)):
        name = ev.get("name", "<unnamed>")
        spans.setdefault(name, []).append(float(ev.get("dur", 0.0)))
        self_us[name] = self_us.get(name, 0.0) + max(own, 0.0)
    for ev in events:
        ph = ev.get("ph")
        name = ev.get("name", "<unnamed>")
        if ph == "C":
            args = ev.get("args") or {}
            # last write wins: counters carry running totals
            for k, v in args.items():
                counters[name if k == "value" else f"{name}.{k}"] = float(v)
        elif ph == "i" or ph == "I":
            instants[name] = instants.get(name, 0) + 1
    rows = []
    for name, durs in spans.items():
        durs.sort()
        total = sum(durs)
        rows.append({
            "name": name,
            "count": len(durs),
            "total_ms": total / 1e3,
            "self_ms": self_us[name] / 1e3,
            "mean_ms": total / len(durs) / 1e3,
            "p50_ms": percentile(durs, 50) / 1e3,
            "p99_ms": percentile(durs, 99) / 1e3,
        })
    grand = sum(r["self_ms"] for r in rows) or 1.0
    for r in rows:
        r["share"] = r["self_ms"] / grand
    return {"spans": rows, "counters": counters, "instants": instants}


# ---------------------------------------------------------------------------
# Interval math
# ---------------------------------------------------------------------------

def merge_intervals(ivs: List[Tuple[float, float]]) -> \
        List[Tuple[float, float]]:
    """Sorted union of (start, end) intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(ivs):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def interval_total(merged: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


def uncovered_time(iv: Tuple[float, float],
                   merged: List[Tuple[float, float]]) -> float:
    """Length of ``iv`` not covered by the merged interval union — the
    exposed share of one collective against the compute union."""
    s, e = iv
    if e <= s:
        return 0.0
    covered = 0.0
    for ms, me in merged:
        if me <= s:
            continue
        if ms >= e:
            break
        covered += min(e, me) - max(s, ms)
    return (e - s) - covered


def uncovered_segments(iv: Tuple[float, float],
                       merged: List[Tuple[float, float]]
                       ) -> List[Tuple[float, float]]:
    """The contiguous pieces of ``iv`` not covered by the merged
    interval union. ``sum(e - s) == uncovered_time(iv, merged)`` by
    construction; the LONGEST piece is the overlap-quality signal the
    grad-sync A/B probe reads (tools/probe_comm.py): a GAS-boundary
    sync exposes one long contiguous collective block, the overlapped
    schedule splits it into per-microstep slivers."""
    s, e = iv
    if e <= s:
        return []
    out: List[Tuple[float, float]] = []
    cur = s
    for ms, me in merged:
        if me <= cur:
            continue
        if ms >= e:
            break
        if ms > cur:
            out.append((cur, min(ms, e)))
        cur = max(cur, me)
        if cur >= e:
            break
    if cur < e:
        out.append((cur, e))
    return out


# ---------------------------------------------------------------------------
# jax.profiler capture analysis (device-time attribution)
# ---------------------------------------------------------------------------

def _empty_analysis() -> Dict[str, Any]:
    return {
        "categories": {c: 0.0 for c in CATEGORIES},
        "ops": {},
        "busy_sec": 0.0,
        "window_sec": 0.0,
        "gap_sec": 0.0,
        "collective_sec": 0.0,
        "exposed_collective_sec": 0.0,
        "max_exposed_segment_sec": 0.0,
        "n_devices": 0,
        "n_events": 0,
        "captures": [],
    }


def analyze_capture_doc(doc: Dict[str, Any]) -> Dict[str, Any]:
    """Device-time attribution of one capture document.

    Classifies every HLO-op duration event into :data:`CATEGORIES` and
    computes, per device row (pid — a ``/device:...`` process when the
    capture has any, else every process, the CPU-backend layout):

    - ``busy_sec``: union of op intervals across the device's streams
      (device-seconds; concurrent streams don't double-count);
    - ``window_sec``: first-op to last-op span (the capture's device
      timeline);
    - ``gap_sec``: ``window - busy`` — host-dispatch gaps between ops;
    - ``exposed_collective_sec``: the UNION of the device's collective
      intervals minus the union of its *non-collective* op intervals —
      wall time where a collective is on the wire and no compute hides
      it, the measured exposed-comm ground truth. Union semantics (not
      per-event sums) so N streams running the same collective
      concurrently — the CPU backend's one-process-many-shards layout —
      count the wall time once; ``exposed <= window`` by construction.

    Per-category and per-op seconds are straight duration sums
    (device-seconds); all quantities aggregate across devices like the
    fleet's per-host rows sum across chips.
    """
    out = _empty_analysis()
    events = doc.get("traceEvents") or []
    device_pids = set()
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            nm = str((ev.get("args") or {}).get("name", ""))
            if nm.startswith("/device:"):
                device_pids.add(ev.get("pid"))
    per_pid: Dict[Any, List[Tuple[float, float, str, str]]] = \
        collections.defaultdict(list)
    for ev in events:
        if ev.get("ph") != "X":
            continue
        if device_pids and ev.get("pid") not in device_pids:
            continue
        name = ev.get("name", "")
        cat = classify_op(name)
        if cat is None:
            continue
        try:
            ts = float(ev.get("ts", 0.0)) / 1e6
            dur = float(ev.get("dur", 0.0)) / 1e6
        except (TypeError, ValueError):
            continue
        if dur <= 0:
            continue
        per_pid[ev.get("pid")].append((ts, ts + dur, cat, name))
    for pid, rows in per_pid.items():
        compute, everything, collectives = [], [], []
        for s, e, cat, name in rows:
            dur = e - s
            out["categories"][cat] += dur
            op = out["ops"].setdefault(
                name, {"sec": 0.0, "count": 0, "category": cat})
            op["sec"] += dur
            op["count"] += 1
            out["n_events"] += 1
            everything.append((s, e))
            if cat == "collective":
                collectives.append((s, e))
                out["collective_sec"] += dur
            else:
                compute.append((s, e))
        comp_merged = merge_intervals(compute)
        all_merged = merge_intervals(everything)
        busy = interval_total(all_merged)
        span = (all_merged[-1][1] - all_merged[0][0]) if all_merged else 0.0
        out["busy_sec"] += busy
        out["window_sec"] += span
        out["gap_sec"] += max(0.0, span - busy)
        for iv in merge_intervals(collectives):
            for us, ue in uncovered_segments(iv, comp_merged):
                out["exposed_collective_sec"] += ue - us
                out["max_exposed_segment_sec"] = max(
                    out["max_exposed_segment_sec"], ue - us)
    out["n_devices"] = len(per_pid)
    return out


def collective_burstiness(doc: Dict[str, Any], op_filter: str = "all-to-all",
                          win_frac: float = 0.05) -> float:
    """How concentrated the matching collectives' wall time is: the max
    share of their total duration inside any contiguous
    ``win_frac``-of-capture span (windows anchored at each matching
    interval's start).

    The overlap A/B's schedule-geometry signal (tools/probe_comm.py): a
    GAS-boundary grad sync fires its whole DCN stage (`all-to-all`
    chains) in ONE burst — high burstiness — while the overlapped
    schedule spreads it across microsteps. Geometry, not contention: it
    reads event timestamps only, so it stays meaningful on the CPU
    backend where nothing can truly run concurrently. Returns 0.0 when
    no op matches."""
    match: List[Tuple[float, float]] = []
    allops: List[Tuple[float, float]] = []
    for ev in (doc.get("traceEvents") or []):
        if ev.get("ph") != "X":
            continue
        name = ev.get("name", "")
        if classify_op(name) is None:
            continue
        try:
            ts = float(ev.get("ts", 0.0)) / 1e6
            dur = float(ev.get("dur", 0.0)) / 1e6
        except (TypeError, ValueError):
            continue
        if dur <= 0:
            continue
        allops.append((ts, ts + dur))
        if op_filter in name:
            match.append((ts, ts + dur))
    if not match:
        return 0.0
    km = merge_intervals(match)
    am = merge_intervals(allops)
    window = am[-1][1] - am[0][0]
    if window <= 0:
        return 0.0
    w = window * win_frac
    total = sum(e - s for s, e in km)
    best = 0.0
    for s0, _ in km:
        inwin = sum(min(e, s0 + w) - max(s, s0)
                    for s, e in km if e > s0 and s < s0 + w)
        best = max(best, inwin / total if total else 0.0)
    return best


def collective_burstiness_dir(profile_dir: str,
                              op_filter: str = "all-to-all",
                              win_frac: float = 0.05) -> float:
    """Max :func:`collective_burstiness` over every ``*.trace.json.gz``
    under ``profile_dir`` (torn captures skipped)."""
    best = 0.0
    pattern = os.path.join(profile_dir, "**", "*.trace.json.gz")
    for path in sorted(_glob.glob(pattern, recursive=True)):
        try:
            best = max(best, collective_burstiness(
                open_trace(path), op_filter=op_filter, win_frac=win_frac))
        except (OSError, EOFError, ValueError, zlib.error):
            continue
    return best


def merge_analyses(analyses: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    out = _empty_analysis()
    for a in analyses:
        for c in CATEGORIES:
            out["categories"][c] += a["categories"].get(c, 0.0)
        for name, op in a["ops"].items():
            tgt = out["ops"].setdefault(
                name, {"sec": 0.0, "count": 0, "category": op["category"]})
            tgt["sec"] += op["sec"]
            tgt["count"] += op["count"]
        for k in ("busy_sec", "window_sec", "gap_sec", "collective_sec",
                  "exposed_collective_sec", "n_events"):
            out[k] += a[k]
        out["max_exposed_segment_sec"] = max(
            out["max_exposed_segment_sec"],
            a.get("max_exposed_segment_sec", 0.0))
        out["n_devices"] = max(out["n_devices"], a["n_devices"])
        out["captures"].extend(a.get("captures", []))
    return out


def parse_capture_path(path: str) -> Dict[str, Any]:
    a = analyze_capture_doc(open_trace(path))
    a["captures"] = [path]
    return a


def parse_capture_dir(profile_dir: str) -> Dict[str, Any]:
    """Merged device-time analysis over every ``*.trace.json.gz`` under
    ``profile_dir`` (recursive — jax.profiler nests
    ``plugins/profile/<date>/``). Torn/empty captures are tolerated: an
    unreadable file is skipped, an empty dir yields the zero analysis."""
    analyses = []
    pattern = os.path.join(profile_dir, "**", "*.trace.json.gz")
    for path in sorted(_glob.glob(pattern, recursive=True)):
        try:
            a = analyze_capture_doc(open_trace(path))
        except (OSError, EOFError, ValueError, zlib.error):
            continue
        a["captures"] = [os.path.relpath(path, profile_dir)]
        analyses.append(a)
    return merge_analyses(analyses)


def top_ops(analysis: Dict[str, Any], k: int = 10) -> List[Dict[str, Any]]:
    """The hottest-op table: top-``k`` ops by total device seconds — the
    Pallas-tier candidate list."""
    rows = [{"name": n, **op} for n, op in analysis["ops"].items()]
    rows.sort(key=lambda r: r["sec"], reverse=True)
    busy = analysis["busy_sec"] or 1.0
    for r in rows[:k]:
        r["share_of_busy"] = r["sec"] / busy
    return rows[:k]


def scan_profile_dir(profile_dir: str) -> Dict[str, Dict[str, float]]:
    """Measured collective vs total device time per capture file — the
    historical ``fleet_report --profile-dir`` output, byte-compatible
    (total = sum of ALL duration events, collective by
    :data:`COLLECTIVE_RE`)."""
    out: Dict[str, Dict[str, float]] = {}
    pattern = os.path.join(profile_dir, "**", "*.trace.json.gz")
    for path in sorted(_glob.glob(pattern, recursive=True)):
        try:
            doc = open_trace(path)
        except (OSError, EOFError, ValueError, zlib.error):
            continue
        total = coll = 0.0
        for ev in doc.get("traceEvents", []):
            if ev.get("ph") != "X":
                continue
            dur = float(ev.get("dur", 0.0))
            total += dur
            if COLLECTIVE_RE.search(ev.get("name", "")):
                coll += dur
        rel = os.path.relpath(path, profile_dir)
        out[rel] = {"collective_ms": coll / 1e3, "total_ms": total / 1e3,
                    "collective_frac": (coll / total) if total > 0 else 0.0}
    return out
