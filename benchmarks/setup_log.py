"""What the ``setup.*`` readers share: the log of JAX's compile events
that ``harness.Compiles`` keeps, cut where set-up ends (``run.window_start``),
reduced to unions of time, and tabled by program and by the driver's phase
of set-up.

Only events that END before the window's start are read. JAX traces an
inner ``jit`` inside its caller's trace, so trace and lowering time is the
UNION of their spans, never their sum; the same holds for the backend
compiles, though they do not nest in practice."""

import bisect
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks import trace_reduce as tr
from benchmarks.harness import say

TRACE_LOWER = ("trace", "lower")
COMPILE_LOAD = ("backend_compile",)
AFTER_MARKS = "after the last mark"
UNKNOWN = "<unknown>"
ROWS = 15


def log_of(run):
    """The run's compile log, or ``None`` where it keeps none or its
    window has not opened (a ``Run`` that only replays a capture)."""
    compiles = getattr(run, "compiles", None)
    if compiles is None or getattr(run, "window_start", None) is None:
        return None
    return compiles


def events_before_window(run, kinds: Sequence[str]) -> Optional[List]:
    """The log's events of ``kinds`` that end before the window opens;
    ``None`` where the run keeps no log."""
    log = log_of(run)
    if log is None:
        return None
    return [e for e in log.events
            if e.kind in kinds and e.end < run.window_start]


def union_seconds(events) -> float:
    return tr.total(tr.merge((e.start, e.end) for e in events))


def cache_before_window(run) -> Optional[Tuple[int, int]]:
    """``(hits, misses)`` of the persistent cache that arrived before the
    window opened; ``None`` where the run keeps no log."""
    log = log_of(run)
    if log is None:
        return None
    seen = [kind for kind, t in log.cache_events if t < run.window_start]
    return seen.count("hit"), seen.count("miss")


def program_of(fun_name: str) -> str:
    """One name for a program across its events: JAX names the trace by
    the function (``step``) and the lowering and compile by the module
    (``jit(step)``)."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def programs_of(events) -> Dict[int, str]:
    """``id(event) -> program`` for a log's events. JAX names the module of
    a ``functools.partial`` ``<unknown>``; such a lowering takes the name
    of the trace that ended last before it began (the outermost: an inner
    trace ends before its caller's), and such a compile the name of the
    lowering that ended last before it."""
    names = {id(e): program_of(e.fun_name) for e in events}
    for kind, source in (("lower", "trace"), ("backend_compile", "lower")):
        done = sorted((e for e in events if e.kind == source),
                      key=lambda e: e.end)
        ends = [e.end for e in done]
        for e in events:
            if e.kind == kind and names[id(e)] == UNKNOWN:
                i = bisect.bisect_right(ends, e.start)
                if i:
                    names[id(e)] = names[id(done[i - 1])]
    return names


def phase_of(run, t: float) -> str:
    """The driver's phase of set-up (``Run.mark``) in which time ``t``
    fell."""
    end = run.t0
    for name, seconds in run.phases:
        end += seconds
        if t <= end:
            return name
    return AFTER_MARKS


def table(run) -> Optional[List[Dict]]:
    """One row per (phase, program) of the events before the window:
    executables built or loaded, the union of its trace and lowering
    spans, the union of its backend compiles, and the persistent cache's
    hits and misses inside those compiles; largest first."""
    events = events_before_window(run, TRACE_LOWER + COMPILE_LOAD)
    if events is None:
        return None
    names = programs_of(events)
    groups: Dict[Tuple[str, str], List] = {}
    for e in events:
        groups.setdefault((phase_of(run, e.end), names[id(e)]),
                          []).append(e)
    compiles = [e for e in events if e.kind in COMPILE_LOAD]
    cache = {}
    for kind, t in run.compiles.cache_events:
        if t >= run.window_start:
            continue
        around = [e for e in compiles if e.start <= t <= e.end]
        if around:      # the innermost compile that was running
            key = min(around, key=lambda e: e.end - e.start)
            cache.setdefault(id(key), []).append(kind)
    rows = []
    for (phase, program), group in groups.items():
        mine = [e for e in group if e.kind in COMPILE_LOAD]
        kinds = [k for e in mine for k in cache.get(id(e), [])]
        rows.append({
            "phase": phase, "program": program, "executables": len(mine),
            "trace_lower_s": union_seconds(
                [e for e in group if e.kind in TRACE_LOWER]),
            "compile_load_s": union_seconds(mine),
            "hits": kinds.count("hit"), "misses": kinds.count("miss")})
    rows.sort(key=lambda r: -(r["trace_lower_s"] + r["compile_load_s"]))
    return rows


def print_table(run) -> None:
    """The set-up table on an earlier line of the run's output: the
    ``ROWS`` largest rows and the total, which is unions over all rows
    (a program traced inside another lies inside its caller's row)."""
    rows = table(run)
    if rows is None:
        return
    say(f"set-up by program, events that end before the window (host "
        f"seconds; {len(rows)} rows, the {min(ROWS, len(rows))} largest; "
        f"rows overlap, a function traced inside another lies inside its "
        f"caller's trace too):")
    say("  phase | program | executables | trace+lower s | compile/load s "
        "| hits | misses")
    for r in rows[:ROWS]:
        say(f"  {r['phase']} | {r['program']} | {r['executables']} | "
            f"{r['trace_lower_s']:.4f} | {r['compile_load_s']:.4f} | "
            f"{r['hits']} | {r['misses']}")
    compiles = run.compiles
    before = events_before_window(run, COMPILE_LOAD)
    hits, misses = cache_before_window(run)
    after = sum(1 for e in compiles.events
                if e.kind in COMPILE_LOAD and e.end >= run.window_start)
    say(f"  total | {len(before)} executables | trace+lower "
        f"{union_seconds(events_before_window(run, TRACE_LOWER)):.4f} s "
        f"(union) | compile/load {union_seconds(before):.4f} s (union) | "
        f"{hits} hits | {misses} misses | of set-up "
        f"{run.window_start - run.t0:.4f} s; {after} more backend compiles "
        f"after the window opened, {compiles.count} in all")
