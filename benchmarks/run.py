"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last, where the driver gives them, ``compared``: every
number ``correct`` held beside its limit (they are the last lines of
standard error too). With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics. Everything else the run
has to say goes on earlier lines.

This file knows no cell, configuration, traffic mix, family or metric by
name. The cell names a configuration (``configs/<name>.json``, which
names its ``family``: ``families/<family>.py``) and a traffic mix
(``traffic/<mix>.json``, which names its ``driver``:
``drivers/<driver>.py``); each per-layer metric ``a.b`` is read by
``layer_metrics/a/b.py``. A later PR adds files and entries.

It runs on a TPU whose ``device_kind`` is in ``peaks.json`` or not at
all. ``--rehearsal`` is the one way it runs without a chip: the same
control flow at the tiny sizes the data files give under ``rehearsal``,
on the CPU; every timing in its result line is ``null``, and the driver's
command cannot reach it.
"""

import time

T0 = time.perf_counter()        # process start, as near as Python shows it

import json                     # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402
import argparse                 # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import (Compiles, Run, device_info,  # noqa: E402
                                load_module, metrics_of, open_cell,
                                read_layer_metrics, say, start_device)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU; timings print as null")
    args = ap.parse_args(argv)

    bench, cell, config, traffic = open_cell(args.workload, args.rehearsal)
    family = load_module("families", config["family"])
    driver = load_module("drivers", traffic["driver"])

    import jax
    import deepspeed_tpu  # noqa: F401  (fails here in a bare checkout)

    dev, peaks, cache = start_device(cell, args.rehearsal)
    tag = "[REHEARSAL on cpu: no device number below is a measurement] " \
        if args.rehearsal else ""
    say(f"{tag}cell {cell['name']}: config {cell['config']} x traffic "
        f"{cell['traffic']} on {cell['chips']} of {jax.device_count()} "
        f"{dev.device_kind!r} device(s); seed {args.seed}, "
        f"{args.seconds:g}s, trace {args.trace}; compile cache {cache}")

    run = Run(cell=cell, config=config, traffic=traffic, family=family,
              seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
              rehearsal=args.rehearsal, peaks=peaks, compiles=Compiles(),
              t0=T0)
    run.mark("imports and device start-up")
    out = driver.run(run)
    run.window_start = out["window_start"]

    setup_s = out["window_start"] - run.t0
    say(f"{tag}set-up (process start to the window's first step or "
        f"request) {setup_s:.2f}s; whole run "
        f"{time.perf_counter() - run.t0:.2f}s")
    say(f"{tag}set-up phases: " + ", ".join(
        f"{name} {sec:.2f}s" for name, sec in run.phases))
    in_window = out["observed"]["compiles_in_window"]
    say(f"{tag}executables built or loaded: {run.compiles.count} "
        f"(persistent cache: {run.compiles.hits} hits, "
        f"{run.compiles.misses} misses), {in_window} of them inside the "
        f"window")
    why_not = list(out["why_not"])
    if in_window:
        why_not.append(f"{in_window} program(s) compiled inside the "
                       f"measured window")
    for reason in why_not:
        say(f"{tag}NOT CORRECT: {reason}")

    device = device_info(run)
    result = {"correct": not why_not, "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": {}, "device": device}
    if run.trace:
        wanted = metrics_of(bench, "per_layer", cell["name"])
        values, reduced = read_layer_metrics(wanted, run, out["observed"])
        if reduced is not None:
            from benchmarks import trace_reduce
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
            result["breakdown"] = trace_reduce.breakdown(reduced)
    else:
        values = dict(out["end_to_end"])
        values["setup_s"] = setup_s
        wanted = metrics_of(bench, "end_to_end", cell["name"])
        missing = [m["name"] for m in wanted if m["name"] not in values]
        if missing:
            sys.exit(f"driver {traffic['driver']!r} did not report "
                     f"{missing} for cell {cell['name']!r}")
    for m in wanted:
        if m["name"] not in values:
            continue            # a reader that found nothing to read
        value = values[m["name"]]
        if args.rehearsal and m["source"] != "program_counter":
            value = None        # a CPU timing is not a device metric
        result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    if args.rehearsal:
        result["rehearsal"] = True
    # Each number ``correct`` compared, beside its limit: last in the
    # result line and the last lines of standard error, which is what the
    # driver's record keeps of a run that is not correct.
    compared = out.get("compared")
    if compared:
        result["compared"] = {k: {"value": v, "limit": limit}
                              for k, (v, limit) in compared.items()}
    say(json.dumps(result))
    for name, (value, limit) in (compared or {}).items():
        print(f"compared {name}: {value:.6g} (limit {limit:g})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
