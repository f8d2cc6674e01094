#!/usr/bin/env python
"""Perf regression gate: compare a bench JSON against a committed baseline.

A comm- or kernel-level change whose win (or loss) is real must be
*measured*, and a measured loss must fail loudly.
This gate compares ``bench.py``'s per-section result rows (the
``"sections"`` block every bench JSON now carries) against a committed
baseline with per-section noise-floored thresholds:

- a section's effective threshold is ``max(--threshold, noise floor)``.
  The floors are NOMINAL: the run-to-run spread of the current chip
  route has not been measured (ROADMAP S1f replaces them with paired
  parent/change runs and quartiles);
- throughput/MFU metrics regress when they DROP beyond the threshold;
  latency metrics (``ttft``/``*_ms``) regress when they RISE;
- exit code 2 on any regression (0 clean, 1 usage/missing-file) — the
  distinct rc the bench driver can branch on;
- ``--update-baseline`` rewrites the baseline from the candidate after a
  deliberate perf change landed.

Pre-``sections`` bench JSONs (BENCH_r03.json) are still comparable: their
known flat keys map onto sections via ``_LEGACY_KEYS``.

Stdlib-only (json, argparse) so it runs in any CI context, and
``--selftest`` (tier-1) proves the gate passes a clean run and catches an
injected regression with a nonzero rc.

Usage:
    python tools/bench_gate.py BENCH.json [--baseline BENCH_baseline.json]
    python tools/bench_gate.py BENCH.json --update-baseline
    python tools/bench_gate.py --selftest
"""

import argparse
import json
import os
import re
import sys
import tempfile
from typing import Any, Dict, List

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(REPO, "BENCH_baseline.json")

# Per-section relative noise floors — nominal, not measured on the
# current chip route (see the module docstring). The 16k row runs few
# steps (coarser timing); serving TTFT percentiles ride scheduler jitter.
NOISE_FLOORS = {
    "bert128": 0.10,
    "bert512": 0.10,
    "gpt2": 0.10,
    "gpt2_dropout": 0.10,
    "long16k": 0.12,
    "inference": 0.10,
    "serving": 0.15,
    # dispatch A/B: tiny model, few steps per window -> coarse timing
    "moe_gpt": 0.12,
    # optimizer-step A/B: sub-ms windows on a ~1M-param tree
    "fused_optimizer": 0.15,
}
DEFAULT_FLOOR = 0.10

# Metrics where SMALLER is better (latency-shaped); everything else is
# throughput-shaped (bigger is better).
_LOWER_BETTER_RE = re.compile(r"ttft|latency|_ms$")

# Flat-key -> (section, metric) map for bench JSONs that predate the
# sections schema.
_LEGACY_KEYS = {
    "value": ("bert128", "samples_per_sec"),
    "tflops": ("bert128", "tflops"),
    "mfu": ("bert128", "mfu"),
    "bert_seq512_samples_per_sec": ("bert512", "samples_per_sec"),
    "gpt2_tokens_per_sec": ("gpt2", "tokens_per_sec"),
    "gpt2_mfu": ("gpt2", "mfu"),
    "gpt2_dropout_tokens_per_sec": ("gpt2_dropout", "tokens_per_sec"),
    "gpt2_dropout_mfu": ("gpt2_dropout", "mfu"),
    "gpt2_seq16k_dense_tokens_per_sec": ("long16k", "dense_tokens_per_sec"),
    "gpt2_seq16k_bigbird_tokens_per_sec":
        ("long16k", "bigbird_tokens_per_sec"),
    "gpt2_seq16k_sparse_speedup": ("long16k", "sparse_speedup"),
    "gpt2_generate_b1_tokens_per_sec": ("inference", "b1_tokens_per_sec"),
    "gpt2_generate_b8_tokens_per_sec": ("inference", "b8_tokens_per_sec"),
    "serving_tokens_per_sec": ("serving", "tokens_per_sec"),
    "serving_ttft_p50_ms": ("serving", "ttft_p50_ms"),
    "serving_ttft_p99_ms": ("serving", "ttft_p99_ms"),
    "serving_mean_occupancy": ("serving", "mean_occupancy"),
}


def sections_of(doc: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """The per-section metric rows of one bench JSON: the ``sections``
    block when present (bench.py emits it), else the legacy flat keys
    mapped through ``_LEGACY_KEYS``. Non-numeric values are dropped."""
    raw = doc.get("sections")
    if not isinstance(raw, dict):
        raw = {}
        for key, (section, metric) in _LEGACY_KEYS.items():
            if doc.get(key) is not None:
                raw.setdefault(section, {})[metric] = doc[key]
    out: Dict[str, Dict[str, float]] = {}
    for section, rows in raw.items():
        if not isinstance(rows, dict):
            continue
        for metric, value in rows.items():
            if metric == "partial":
                # Row annotation (bench.py: timing lost windows to a
                # transient failure), not a metric — never gated.
                continue
            if isinstance(value, (int, float)) and not isinstance(
                    value, bool):
                out.setdefault(section, {})[metric] = float(value)
    return out


def lower_is_better(metric: str) -> bool:
    return bool(_LOWER_BETTER_RE.search(metric))


def compare(baseline: Dict[str, Any], candidate: Dict[str, Any],
            threshold: float = 0.05) -> Dict[str, Any]:
    """Row-by-row comparison. Only metrics present in BOTH are judged; a
    section/metric missing from the candidate is reported (a silently
    vanished bench row is itself suspicious) but is not a regression —
    partial bench records are a designed-for state."""
    base_s = sections_of(baseline)
    cand_s = sections_of(candidate)
    rows: List[Dict[str, Any]] = []
    missing: List[str] = []
    for section in sorted(base_s):
        floor = NOISE_FLOORS.get(section, DEFAULT_FLOOR)
        thr = max(float(threshold), floor)
        for metric in sorted(base_s[section]):
            old = base_s[section][metric]
            new = cand_s.get(section, {}).get(metric)
            if new is None:
                missing.append(f"{section}/{metric}")
                continue
            if old == 0:
                continue                      # no meaningful ratio
            delta = (new - old) / abs(old)
            if lower_is_better(metric):
                verdict = ("REGRESSION" if delta > thr
                           else "improvement" if delta < -thr else "ok")
            else:
                verdict = ("REGRESSION" if delta < -thr
                           else "improvement" if delta > thr else "ok")
            rows.append({"section": section, "metric": metric,
                         "baseline": old, "value": new,
                         "delta_frac": delta, "threshold": thr,
                         "verdict": verdict})
    new_metrics = sorted(
        f"{s}/{m}" for s in cand_s for m in cand_s[s]
        if m not in base_s.get(s, {}))
    regressions = [r for r in rows if r["verdict"] == "REGRESSION"]
    return {"rows": rows, "missing": missing, "new_metrics": new_metrics,
            "n_regressions": len(regressions), "ok": not regressions}


def render(report: Dict[str, Any]) -> str:
    out = []
    hdr = (f"{'section':<14} {'metric':<26} {'baseline':>12} {'value':>12} "
           f"{'delta':>8} {'thresh':>7}  verdict")
    out.append(hdr)
    out.append("-" * len(hdr))
    for r in report["rows"]:
        out.append(
            f"{r['section']:<14} {r['metric']:<26} {r['baseline']:>12.4g} "
            f"{r['value']:>12.4g} {r['delta_frac']:>+7.1%} "
            f"{r['threshold']:>6.0%}  {r['verdict']}")
    if report["missing"]:
        out.append("")
        out.append("missing from candidate (rows the baseline has): "
                   + ", ".join(report["missing"]))
    if report["new_metrics"]:
        out.append("")
        out.append("new in candidate (not yet in baseline): "
                   + ", ".join(report["new_metrics"]))
    out.append("")
    out.append("GATE: " + ("ok" if report["ok"] else
                           f"{report['n_regressions']} REGRESSION(S)"))
    return "\n".join(out)


def update_baseline(candidate_path: str, baseline_path: str) -> None:
    with open(candidate_path) as f:
        doc = json.load(f)
    base = {
        "source": os.path.basename(candidate_path),
        "metric": doc.get("metric"),
        "environment": doc.get("environment"),
        "sections": sections_of(doc),
    }
    tmp = baseline_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(base, f, indent=1, sort_keys=True)
    os.replace(tmp, baseline_path)


# ---------------------------------------------------------------------------
# Selftest
# ---------------------------------------------------------------------------

def _selftest() -> int:
    baseline = {"sections": {
        "gpt2": {"tokens_per_sec": 147691.0, "mfu": 0.60},
        "serving": {"tokens_per_sec": 900.0, "ttft_p50_ms": 12.0},
    }}
    # 1. clean run: inside every noise floor -> rc 0
    ok_run = {"sections": {
        "gpt2": {"tokens_per_sec": 143000.0, "mfu": 0.59},
        "serving": {"tokens_per_sec": 880.0, "ttft_p50_ms": 13.0},
    }}
    rep = compare(baseline, ok_run)
    assert rep["ok"], rep
    # 2. injected throughput regression (-30%) -> caught
    bad_run = {"sections": {
        "gpt2": {"tokens_per_sec": 103000.0, "mfu": 0.60},
        "serving": {"tokens_per_sec": 900.0, "ttft_p50_ms": 12.0},
    }}
    rep = compare(baseline, bad_run)
    assert not rep["ok"] and rep["n_regressions"] == 1, rep
    assert rep["rows"][0]["metric"] != "ttft_p50_ms"
    # 3. latency direction: TTFT doubling is a regression even though the
    #    number went UP
    slow_serve = {"sections": {
        "gpt2": {"tokens_per_sec": 147691.0, "mfu": 0.60},
        "serving": {"tokens_per_sec": 900.0, "ttft_p50_ms": 24.0},
    }}
    rep = compare(baseline, slow_serve)
    bad = [r for r in rep["rows"] if r["verdict"] == "REGRESSION"]
    assert len(bad) == 1 and bad[0]["metric"] == "ttft_p50_ms", rep
    # 4. missing section reported, not failed; new metric surfaced
    partial = {"sections": {"gpt2": {"tokens_per_sec": 150000.0,
                                     "mfu": 0.61, "extra_row": 1.0}}}
    rep = compare(baseline, partial)
    assert rep["ok"]
    assert "serving/tokens_per_sec" in rep["missing"]
    assert "gpt2/extra_row" in rep["new_metrics"]
    # 4b. a whole ADDED section (new bench row family absent from the
    #     baseline — e.g. a PR that grows bench.py a numerics section) is
    #     informational, never a regression: the gate stays green and the
    #     rows surface under new_metrics so --update-baseline adopts them
    #     deliberately.
    added = {"sections": {
        "gpt2": {"tokens_per_sec": 147691.0, "mfu": 0.60},
        "serving": {"tokens_per_sec": 900.0, "ttft_p50_ms": 12.0},
        "numerics_probe": {"overhead_x": 1.02, "flush_fetch_ms": 0.4},
    }}
    rep = compare(baseline, added)
    assert rep["ok"] and rep["n_regressions"] == 0, rep
    assert "numerics_probe/overhead_x" in rep["new_metrics"], rep
    assert "numerics_probe/flush_fetch_ms" in rep["new_metrics"], rep
    text_added = render(rep)
    assert "new in candidate" in text_added and "GATE: ok" in text_added
    # 4c. the moe_gpt dispatch A/B section (bench.py sec_moe_gpt): new
    #     against an old baseline it is informational; once adopted, its
    #     step-time rows gate in the latency direction (a slower
    #     all-to-all is a regression even though the number went UP) and
    #     the static dispatch-bytes row gates as throughput-shaped only
    #     on real change.
    moe_rows = {"step_time_einsum_ms": 80.0, "step_time_scatter_ms": 75.0,
                "step_time_alltoall_ms": 70.0,
                "alltoall_vs_scatter_speedup": 1.07,
                "dispatch_bytes_ici_per_layer": 166400.0,
                "capacity_overflow_frac": 0.10}
    with_moe = {"sections": {**baseline["sections"], "moe_gpt": moe_rows}}
    rep = compare(baseline, with_moe)
    assert rep["ok"], rep
    assert "moe_gpt/step_time_alltoall_ms" in rep["new_metrics"], rep
    moe_base = {"sections": {"moe_gpt": moe_rows}}
    slow_a2a = {"sections": {"moe_gpt": {
        **moe_rows, "step_time_alltoall_ms": 95.0}}}
    rep = compare(moe_base, slow_a2a)
    bad = [r for r in rep["rows"] if r["verdict"] == "REGRESSION"]
    assert len(bad) == 1 and bad[0]["metric"] == "step_time_alltoall_ms", rep
    # 4d. kernel tier round 2 rows (bench.py bench_serving_chunked /
    #     bench_fused_optimizer): the serving chunked A/B rows and the
    #     fused_optimizer section are informational against an old
    #     baseline; once adopted, all of them are _ms rows and gate in
    #     the latency direction (a slower chunked mixed step or fused
    #     update is a regression even though the number went UP).
    k2_serving = {**baseline["sections"]["serving"],
                  "mixed_step_bucketed_ms": 9.0,
                  "mixed_step_chunked_ms": 7.0,
                  "ttft_p99_bucketed_ms": 120.0,
                  "ttft_p99_chunked_ms": 60.0}
    k2_fused = {"optimizer_step_xla_ms": 2.0,
                "optimizer_step_fused_ms": 1.5}
    with_k2 = {"sections": {**baseline["sections"],
                            "serving": k2_serving,
                            "fused_optimizer": k2_fused}}
    rep = compare(baseline, with_k2)
    assert rep["ok"], rep
    assert "serving/mixed_step_chunked_ms" in rep["new_metrics"], rep
    assert "fused_optimizer/optimizer_step_fused_ms" in rep["new_metrics"], \
        rep
    k2_base = {"sections": {"serving": k2_serving,
                            "fused_optimizer": k2_fused}}
    slow_k2 = {"sections": {
        "serving": {**k2_serving, "ttft_p99_chunked_ms": 110.0},
        "fused_optimizer": {**k2_fused, "optimizer_step_fused_ms": 2.5}}}
    rep = compare(k2_base, slow_k2)
    bad = sorted(r["metric"] for r in rep["rows"]
                 if r["verdict"] == "REGRESSION")
    assert bad == ["optimizer_step_fused_ms", "ttft_p99_chunked_ms"], rep
    # 5. legacy flat-key bench JSONs map onto sections
    legacy = sections_of({"value": 532.98, "gpt2_tokens_per_sec": 147691.0,
                          "serving_ttft_p50_ms": 9.1, "metric": "x",
                          "errors": ["not-a-number"]})
    assert legacy["bert128"]["samples_per_sec"] == 532.98
    assert legacy["serving"]["ttft_p50_ms"] == 9.1
    # 6. the full CLI round-trip: update-baseline, pass, then fail rc 2
    with tempfile.TemporaryDirectory() as td:
        cand = os.path.join(td, "bench.json")
        basep = os.path.join(td, "BENCH_baseline.json")
        with open(cand, "w") as f:
            json.dump({"metric": "m", "sections": baseline["sections"]}, f)
        assert main([cand, "--baseline", basep, "--update-baseline"]) == 0
        assert main([cand, "--baseline", basep]) == 0
        with open(cand, "w") as f:
            json.dump(bad_run, f)
        rc = main([cand, "--baseline", basep])
        assert rc == 2, rc
        text = render(compare(baseline, bad_run))
    assert "REGRESSION" in text and "GATE:" in text
    print(text)
    print("\nselftest ok")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("bench", nargs="?",
                    help="candidate bench JSON (bench.py stdout line or "
                         "BENCH_partial.json)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help=f"committed baseline (default: {DEFAULT_BASELINE})")
    ap.add_argument("--threshold", type=float, default=0.05,
                    help="relative regression threshold; per-section noise "
                         "floors raise it (default 0.05)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from the candidate and exit")
    ap.add_argument("--json", action="store_true",
                    help="emit the comparison as JSON")
    ap.add_argument("--selftest", action="store_true",
                    help="run the built-in gate check and exit")
    args = ap.parse_args(argv)
    if args.selftest:
        return _selftest()
    if not args.bench:
        ap.error("bench JSON required (or --selftest)")
    if not os.path.exists(args.bench):
        print(f"bench file not found: {args.bench}", file=sys.stderr)
        return 1
    if args.update_baseline:
        update_baseline(args.bench, args.baseline)
        print(f"[bench_gate] baseline <- {args.bench} ({args.baseline})")
        return 0
    if not os.path.exists(args.baseline):
        print(f"baseline not found: {args.baseline} (seed one with "
              f"--update-baseline)", file=sys.stderr)
        return 1
    with open(args.baseline) as f:
        baseline = json.load(f)
    with open(args.bench) as f:
        candidate = json.load(f)
    report = compare(baseline, candidate, threshold=args.threshold)
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        print(render(report))
    return 0 if report["ok"] else 2


if __name__ == "__main__":
    sys.exit(main())
