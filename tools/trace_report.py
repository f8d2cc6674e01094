#!/usr/bin/env python
"""Summarize a Chrome trace-event file into a per-span time breakdown.

The artifact perf PRs cite: feed it the trace the engine's step tracer
writes (``telemetry.trace``; docs/OBSERVABILITY.md) and get a table of
where step time goes — total / count / mean / p50 / p99 / share per span
name — plus counter summaries (e.g. ``telemetry/recompiles``) and instant
events (retrace markers).

Parsing lives in the shared ``telemetry/traceparse.py`` (itself stdlib
only); this tool loads it by file path — no package import, no jax — so
it still runs anywhere a trace file lands. Rendering and the CLI stay
here.

Multiple traces (or a glob): every span row is prefixed with its source
host (``hostA:train_step``) — from each file's ``metadata.host``, or the
``trace.<host>.json`` filename component multi-host runs write — so one
table covers a fleet until ``tools/fleet_report.py`` replaces it.

Usage:
    python tools/trace_report.py TRACE.json [...] [--sort total|mean|count]
    python tools/trace_report.py 'run/telemetry/trace.*.json'
    python tools/trace_report.py --selftest
"""

import argparse
import importlib.util
import json
import os
import sys
import tempfile
from typing import Any, Dict


def _load_traceparse():
    """Load telemetry/traceparse.py by path: the module is stdlib-only,
    and a spec-load keeps this tool runnable on hosts where the package
    (and jax) cannot import."""
    cached = sys.modules.get("dstpu_traceparse")
    if cached is not None:
        return cached
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "deepspeed_tpu", "telemetry", "traceparse.py")
    spec = importlib.util.spec_from_file_location("dstpu_traceparse", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # One instance per process: a tool importing another tool (or tests
    # loading several) must see the same COLLECTIVE_RE/CATEGORIES objects.
    sys.modules["dstpu_traceparse"] = mod
    return mod


_tp = _load_traceparse()

# Historical module-level API (tests and other tools import these from
# here) — one implementation, in traceparse.
load_doc = _tp.load_doc
load_events = _tp.load_events
host_label = _tp.host_label
load_many = _tp.load_many
expand_paths = _tp.expand_paths
summarize = _tp.summarize
_percentile = _tp.percentile


def render(summary: Dict[str, Any], sort: str = "total") -> str:
    key = {"total": "total_ms", "mean": "mean_ms", "count": "count"}[sort]
    rows = sorted(summary["spans"], key=lambda r: r[key], reverse=True)
    out = []
    # ``share`` is of SELF time (a span less the spans inside it), so the
    # column adds up though train_batch and serve_step enclose others
    hdr = (f"{'span':<24} {'count':>7} {'total ms':>12} {'self ms':>12} "
           f"{'mean ms':>10} {'p50 ms':>10} {'p99 ms':>10} {'share':>7}")
    out.append(hdr)
    out.append("-" * len(hdr))
    for r in rows:
        out.append(f"{r['name']:<24} {r['count']:>7} {r['total_ms']:>12.3f} "
                   f"{r['self_ms']:>12.3f} "
                   f"{r['mean_ms']:>10.3f} {r['p50_ms']:>10.3f} "
                   f"{r['p99_ms']:>10.3f} {r['share']:>6.1%}")
    if not rows:
        out.append("(no complete spans in trace)")
    if summary["counters"]:
        out.append("")
        out.append("counters (latest value):")
        for name, v in sorted(summary["counters"].items()):
            out.append(f"  {name}: {v:g}")
    if summary["instants"]:
        out.append("")
        out.append("instant events:")
        for name, n in sorted(summary["instants"].items()):
            out.append(f"  {name}: x{n}")
    return "\n".join(out)


def _selftest() -> int:
    """Synthesize a trace, run the full load→summarize→render path, and
    verify the numbers — exercised from the test suite and CI."""
    events = []
    # 3 steps of a synthetic loop: dataloader 1ms, forward 4ms, backward
    # 0.01ms, optimizer_step 2ms; one ckpt pair; one recompile marker.
    t = 0.0
    for step in range(3):
        for name, dur_ms in (("dataloader", 1.0), ("forward", 4.0),
                             ("backward", 0.01), ("optimizer_step", 2.0)):
            events.append({"name": name, "ph": "X", "pid": 1, "tid": 1,
                           "ts": t, "dur": dur_ms * 1e3,
                           "args": {"step": step}})
            t += dur_ms * 1e3
    events.append({"name": "ckpt_snapshot", "ph": "X", "pid": 1, "tid": 2,
                   "ts": t, "dur": 500.0})
    events.append({"name": "ckpt_write", "ph": "X", "pid": 1, "tid": 2,
                   "ts": t + 500.0, "dur": 1500.0})
    # serving spans (serving/engine.py) ride the same timeline/report
    events.append({"name": "prefill", "ph": "X", "pid": 1, "tid": 1,
                   "ts": t + 2000.0, "dur": 800.0,
                   "args": {"rid": 0, "bucket": 16}})
    events.append({"name": "decode_step", "ph": "X", "pid": 1, "tid": 1,
                   "ts": t + 2800.0, "dur": 300.0, "args": {"active": 2}})
    events.append({"name": "recompile", "ph": "i", "s": "t", "pid": 1,
                   "tid": 1, "ts": t, "args": {"fn": "train_step"}})
    events.append({"name": "telemetry/recompiles", "ph": "C", "pid": 1,
                   "tid": 1, "ts": t, "args": {"value": 1.0}})
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "trace.json")
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
        summary = summarize(load_events(path))
        text = render(summary)
    by_name = {r["name"]: r for r in summary["spans"]}
    assert len(by_name) == 8, by_name.keys()
    assert by_name["forward"]["count"] == 3
    assert by_name["prefill"]["count"] == 1
    assert abs(by_name["decode_step"]["total_ms"] - 0.3) < 1e-9
    assert abs(by_name["forward"]["total_ms"] - 12.0) < 1e-9
    assert abs(by_name["optimizer_step"]["mean_ms"] - 2.0) < 1e-9
    assert summary["counters"]["telemetry/recompiles"] == 1.0
    assert summary["instants"]["recompile"] == 1
    assert "forward" in text and "share" in text
    top = max(summary["spans"], key=lambda r: r["total_ms"])
    assert top["name"] == "forward"
    # multi-file path: span rows gain their source-host prefix (metadata
    # host preferred, filename component as fallback)
    with tempfile.TemporaryDirectory() as td:
        for host, with_meta in (("hostA", True), ("hostB", False)):
            with open(os.path.join(td, f"trace.{host}.json"), "w") as f:
                doc = {"traceEvents": [
                    {"name": "train_step", "ph": "X", "pid": 1, "tid": 1,
                     "ts": 0.0, "dur": 1000.0}]}
                if with_meta:
                    doc["metadata"] = {"host": host}
                json.dump(doc, f)
        paths = expand_paths([os.path.join(td, "trace.*.json")])
        assert len(paths) == 2, paths
        multi = summarize(load_many(paths))
    names = {r["name"] for r in multi["spans"]}
    assert names == {"hostA:train_step", "hostB:train_step"}, names
    print(text)
    print("\nselftest ok")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", nargs="*",
                    help="Chrome trace-event JSON file(s) or glob; with "
                         "more than one, rows are host-prefixed")
    ap.add_argument("--sort", choices=("total", "mean", "count"),
                    default="total")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary as JSON instead of a table")
    ap.add_argument("--selftest", action="store_true",
                    help="run the built-in round-trip check and exit")
    args = ap.parse_args(argv)
    if args.selftest:
        return _selftest()
    if not args.trace:
        ap.error("trace file required (or --selftest)")
    paths = expand_paths(args.trace)
    events = (load_events(paths[0]) if len(paths) == 1
              else load_many(paths))
    summary = summarize(events)
    if args.json:
        print(json.dumps(summary, indent=1))
    else:
        print(render(summary, sort=args.sort))
    return 0


if __name__ == "__main__":
    sys.exit(main())
