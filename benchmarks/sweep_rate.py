"""Find a serving cell's knee: the highest arrival rate it sustains with no
growing backlog. Run once, by hand, on the chip, when the cell is defined
(or when an optimisation has moved the knee); the cell's traffic file then
carries ``rate_per_s`` = 0.8 x the knee as a number, and no run searches.

    python3 benchmarks/sweep_rate.py --workload <cell> --rates 4,6,8,10 [--seconds 20]

One process, one engine, one warm-up; then the cell's own traffic mix
(primed and stratified as its file says) at each rate for ``--seconds``,
the engine drained between rates. For each rate it prints what arrived and
finished, how many requests were waiting for a slot half-way and at the
end (a backlog that grows means the rate is past the knee), and the
latencies of the two halves (past the knee the second half is worse than
the first). The last line fits ``step ms = a + b x rows alive`` to the
decode-only steps of all rates and gives the median cost of a prefill
beside it: the cost model ``tests/yardstick/test_open_loop_simulator.py``
takes.
"""

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np                                                  # noqa: E402

from benchmarks import generate                                     # noqa: E402
from benchmarks.harness import (Compiles, Run, load_module, open_cell,  # noqa: E402
                                say, start_device)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="requests per second, comma separated, rising")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    _, cell, config, traffic = open_cell(args.workload)
    dev, peaks, _ = start_device(cell)     # a knee is a property of the chip
    run = Run(cell=cell, config=config, traffic=traffic,
              family=load_module("families", config["family"]),
              seed=args.seed, seconds=args.seconds, trace=False,
              rehearsal=False, peaks=peaks, compiles=Compiles(),
              t0=time.perf_counter())
    driver = load_module("drivers", traffic["driver"])
    srv, _ = driver.build(run)
    driver.warm_up(srv, traffic, config["vocab_size"])
    say(f"{cell['name']} on {dev.device_kind!r}: warm after "
        f"{time.perf_counter() - run.t0:.1f}s; {args.seconds:g}s per rate")
    pct = generate.percentile
    prime = float(traffic["prime_seconds"])
    decode_only, admitting = [], []     # (rows alive, ms) of every step
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(traffic, rate_per_s=rate)
        requests = generate.open_loop_requests(
            mix, config["vocab_size"], args.seed, args.seconds)
        client = driver.Client(run, srv, requests)
        compiles0 = run.compiles.count
        t0 = time.perf_counter() + prime    # the window opens at 0
        client.drive(t0, args.seconds + 120.0)
        drained = time.perf_counter() - t0
        srv.run_until_complete(timeout_sec=600)   # whatever is left over
        obs = driver.summarize(client, srv, requests, args.seconds)

        def waiting_at(t):
            """Submitted by ``t`` and not yet given a first token."""
            return sum(1 for r in client.track.values()
                       if r["submitted"] <= t
                       and (not r["stamps"] or r["stamps"][0] > t))

        half = args.seconds / 2
        first = [r for r in client.track.values() if 0 <= r["due"] < half]
        second = [r for r in client.track.values() if r["due"] >= half]
        ttft = lambda rs: [(r["stamps"][0] - r["due"]) * 1e3
                           for r in rs if r["stamps"]]
        steps = [s for s in client.steps if 0 <= s[0] < args.seconds]
        for _, sec, rows, admitted in steps:
            (admitting if admitted else decode_only).append(
                (rows, sec * 1e3))
        say(f"rate {rate:5.1f}/s: due {obs['attempted']:4d} failed "
            f"{obs['failed']:3d} | waiting at T/2 {waiting_at(half):3d} at "
            f"T {waiting_at(args.seconds):3d} | drained {drained:5.1f}s "
            f"after start | ttft ms p50/p90 first half "
            f"{pct(ttft(first), 50):7.1f}/{pct(ttft(first), 90):7.1f} "
            f"second half {pct(ttft(second), 50):7.1f}/"
            f"{pct(ttft(second), 90):7.1f} | itl ms p50/p95 "
            f"{pct(obs['itl_ms'], 50):6.2f}/{pct(obs['itl_ms'], 95):6.2f} "
            f"| step ms p50 {pct([s[1] * 1e3 for s in steps], 50):6.2f} "
            f"| mean active {np.mean([s[2] for s in steps]):5.1f} "
            f"| compiles {run.compiles.count - compiles0}")
        srv.results.clear()
    srv.close()
    everything = decode_only + admitting
    a, b, extra = driver.step_cost({
        "active": [r for r, _ in everything],
        "step_ms": [m for _, m in everything],
        "admitted": [0] * len(decode_only) + [1] * len(admitting)})
    say(f"fit over {len(decode_only)} decode-only steps of all rates: step "
        f"ms = {a:.2f} + {b:.3f} x rows alive; a step that admits a prompt "
        f"costs {extra:.2f} ms more (median of {len(admitting)})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
