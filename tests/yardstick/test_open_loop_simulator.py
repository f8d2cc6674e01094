"""A deterministic simulator of the serving cell's open loop, and what it
proves of the SCHEDULE: the real ``generate.open_loop_requests`` and the
real ``Client`` / ``summarize`` of ``drivers/serve_open_loop.py`` against
a fake engine on a virtual clock, whose step costs what a cost model says
(``a + b x rows alive + c x positions alive``, plus a prefill for the step
that admits). No
device, no host jitter: what spreads here over seeds is the schedule's
doing alone, so a traffic file's ``prime_seconds`` and ``stratum_seconds``
can be chosen before any chip time is spent (PERF.md section 6, PR 34,
has what the chip then said)."""

import os
import statistics
import sys
from contextlib import nullcontext
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import generate  # noqa: E402
from benchmarks.harness import HERE, load_json, load_module  # noqa: E402

DRIVER = load_module("drivers", "serve_open_loop")
TRAFFIC = load_json(HERE, "traffic", "chat-poisson.json")
VOCAB = 50257
SECONDS = 30.0
SEEDS = range(2147483701, 2147483713)          # twelve, as large as the driver's


class Clock:
    """Virtual seconds: only the fake engine's steps and the client's
    waits move it."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, seconds):
        self.t += max(seconds, 1e-6)


class FakeEngine:
    """What ``Client`` and ``summarize`` use of ``ServeEngine``, with the
    engine's admission rule (one prefill a step while a slot is free, the
    admitted request decoding in the same step) and a step that costs
    ``a + b x rows alive + c x positions alive`` ms (the attention walks
    every row's cached positions), plus ``prefill`` ms where it admits."""

    def __init__(self, clock, slots, a, b, c, prefill):
        self.clock, self.slots = clock, slots
        self.a, self.b, self.c, self.prefill = a, b, c, prefill
        self.queue, self.running, self.results = [], {}, {}
        self.pool = SimpleNamespace(used_blocks=0)
        self.n = 0

    def submit(self, prompt, max_new_tokens):
        self.n += 1
        self.queue.append((self.n, len(prompt), max_new_tokens))
        return self.n

    def idle(self):
        return not self.queue and not self.running

    def step(self):
        info = {"prefilled": [], "finished": [], "active": 0}
        ms = 0.0
        if self.queue and len(self.running) < self.slots:
            rid, prompt_len, want = self.queue.pop(0)
            self.running[rid] = [prompt_len, want, 1]   # the first token
            info["prefilled"].append(rid)
            ms += self.prefill
        info["active"] = len(self.running)
        if self.running:
            positions = sum(row[0] + row[2] for row in self.running.values())
            ms += self.a + self.b * len(self.running) + self.c * positions
        for rid, row in list(self.running.items()):
            row[2] += 1                 # max_new_tokens >= 2 in every mix
            if row[2] >= row[1]:
                del self.running[rid]
                info["finished"].append(rid)
                self.results[rid] = {
                    "status": "finished", "preempted_count": 0,
                    "prompt_len": row[0], "tokens": [0] * (row[0] + row[1])}
        self.clock.t += ms / 1e3
        return info


RUN = SimpleNamespace(annotate=lambda name, **kw: nullcontext())


def simulate(traffic, seed, cost, slots, seconds=SECONDS):
    """One window under ``cost`` = (a, b, c, prefill) ms. Returns
    ``summarize``'s observations with how many requests were still waiting
    for a first token at the window's end."""
    clock = Clock()
    srv = FakeEngine(clock, slots, *cost)
    requests = generate.open_loop_requests(traffic, VOCAB, seed, seconds)
    prime = float(traffic["prime_seconds"])
    clock.t = -prime                    # the client's clock: 0 at the window
    client = DRIVER.Client(RUN, srv, requests, clock=clock, sleep=clock.sleep)
    client.drive(0.0, seconds + 600.0)
    obs = DRIVER.summarize(client, srv, requests, seconds)
    obs["waiting_at_end"] = sum(
        1 for r in client.track.values() if r["submitted"] <= seconds
        and (not r["stamps"] or r["stamps"][0] > seconds))
    return obs


def spread_of_p95(traffic, cost, slots):
    """Quartile spread of ``itl_ms_p95`` over the twelve seeds, as a share
    of their median (the contract's measure), the median, and the mean
    rows alive."""
    runs = [simulate(traffic, seed, cost, slots) for seed in SEEDS]
    assert not any(r["failed"] for r in runs)
    p95 = [generate.percentile(r["itl_ms"], 95) for r in runs]
    q1, _, q3 = statistics.quantiles(p95, n=4)
    rows = statistics.mean(statistics.mean(r["active"]) for r in runs)
    return (q3 - q1) / statistics.median(p95), statistics.median(p95), rows


def knee(traffic, cost, slots, rates):
    """The highest of ``rates`` (rising) that leaves no more requests
    waiting at the window's end than a step can admit: what
    ``sweep_rate.py`` looks for on the chip, here on the virtual clock."""
    held = None
    for rate in rates:
        obs = simulate(dict(traffic, rate_per_s=rate), 1, cost, slots)
        if obs["failed"] or obs["waiting_at_end"] > 2:
            break
        held = rate
    return held


def without_keys(traffic):
    """What the schedule was before PR 34: no priming, and the seed
    shuffling over the whole window."""
    return dict(traffic, prime_seconds=0, stratum_seconds=SECONDS)


# (a, b, c, prefill) in ms and slots. GPT's of PR 27: PERF.md section 5 then:
# a decode-only step 6.4 ms on the host's clock, a prefill 3.6. PR 33's:
# 8.5 ms + 0.41 ms a row alive, 32 slots, as its builder measured on the
# chip; its prefill is taken as GPT's. The third is what ``sweep_rate.py``
# fitted to this cell's steps on the chip (my chip run, PR 34: 5.94 ms +
# 0.333 ms a row alive, a step that admits 5.85 more), with the row's cost
# put where the traced run found it: 0.06 ms on the host and the rest on
# the positions the attention walks (0.0009 ms each: 0.27 ms a row of 300).
# Under it the simulated knee is 18.5/s where the chip's sweep found 18.
COSTS = {
    "gpt-flat-pr27": ((6.4, 0.0, 0.0, 3.6), 64),
    "pr33-rows": ((8.5, 0.41, 0.0, 3.6), 32),
    "gpt-fit-pr34": ((5.94, 0.06, 0.0009, 5.85), 64),
}
GRID = [r / 2 for r in range(4, 120)]           # 2.0, 2.5, ... /s


@pytest.mark.parametrize("name", list(COSTS))
def test_the_cells_keys_hold_the_spread_of_p95_under_a_cost_model(name):
    """At 0.8 x the simulated knee, with the keys ``chat-poisson.json``
    carries, ``itl_ms_p95`` spreads at most 2.5% over twelve seeds; what
    it reads without them is printed beside it."""
    cost, slots = COSTS[name]
    assert "prime_seconds" in TRAFFIC and "stratum_seconds" in TRAFFIC
    found = knee(TRAFFIC, cost, slots, GRID)
    assert found is not None and found < GRID[-1]
    mix = dict(TRAFFIC, rate_per_s=0.8 * found)
    spread, p95, rows = spread_of_p95(mix, cost, slots)
    bare = spread_of_p95(without_keys(mix), cost, slots)
    print(f"{name}: simulated knee {found}/s, at {0.8 * found:.1f}/s "
          f"itl_ms_p95 {p95:.2f} ms, {rows:.1f} rows alive, spread over "
          f"twelve seeds {spread:.2%} with prime_seconds "
          f"{TRAFFIC['prime_seconds']} and stratum_seconds "
          f"{TRAFFIC['stratum_seconds']}; without them {bare[0]:.2%} "
          f"(p95 {bare[1]:.2f} ms, {bare[2]:.1f} rows)")
    assert spread <= 0.025


def test_the_simulator_is_a_function_of_the_seed():
    cost, slots = COSTS["pr33-rows"]
    a = simulate(TRAFFIC, 7, cost, slots, seconds=6.0)
    b = simulate(TRAFFIC, 7, cost, slots, seconds=6.0)
    c = simulate(TRAFFIC, 8, cost, slots, seconds=6.0)
    assert a == b and a["itl_ms"] != c["itl_ms"]
    assert a["attempted"] == c["attempted"] and not a["failed"]
