"""Driver ``serve_open_loop``: requests through
``deepspeed_tpu.init_serving()``, sent when they are due.

Traffic parameters: ``rate_per_s``, ``prompt_len``, ``output_len``
(lognormal, clipped), ``max_total_len``, ``token_zipf_exponent``,
``prime_seconds``, ``stratum_seconds`` (both ``generate.py``'s),
``drain_seconds``, ``trace_seconds``, ``check_requests``. The engine's
settings are the configuration's ``serving`` block.

The client loop is the benchmark's own and has one thread: submit what is
due, call ``step()``, stamp the tokens the step report says arrived. A
request's clock starts when it was DUE, not when the loop got round to
submitting it, so a stall is charged to every request it delayed. A token
arrives when the ``step()`` that made it returns; a request's first two
tokens arrive together (the step that prefills it also decodes it).

The client's clock reads 0 where the window opens. The requests of the
priming stretch are due before that, at negative times: they are sent and
have to finish like any other, and nothing of them is measured. What is
measured are the requests due in ``[0, seconds)`` and, of their token
gaps, those that close before ``seconds``: past it no request arrives any
more and the engine runs empty, which is no part of a steady state.
"""

import gc
import time

import numpy as np

from benchmarks import generate
from benchmarks.harness import say

# ``correct`` is decided on LOGITS (``check_against_reference``). After
# the window the sampled requests are replayed through the same engine and
# programs with the decode's float32 logits captured; at every position a
# decode step emitted, e = max over the vocabulary |engine - reference|
# in units of the standard deviation of that position's reference logits
# over the vocabulary (0.64 for this model's random weights, and another
# model's is its own: the unit keeps the limits a statement of precision
# and not of one model's scale), the reference being the plain float32
# forward of the same bfloat16 weights. The WORST gap of an emitted token
# under the reference's best logit (what PR 22 judged, limit 0.08) is
# printed and decides nothing: with random weights the best two logits tie
# within rounding somewhere in every few thousand positions, so a worst
# value is set by a coin. The numbers held are a median and three shares
# of far positions, each a constant here that no data file overrides, each
# set between two readings on the chip at the cell's own size and load (my
# chip runs, PR 34; PERF.md section 2 has every reading, ``control.py``
# the controls and the faults):
#
# E_MEDIAN_TOL  the median of e over the decode positions. LOWER reading:
#               bfloat16 reads 0.0418-0.0438 over 35 seeds and 82 runs,
#               the same digits for the same seed. UPPER reading: the
#               control, the reference in the program's place with every
#               matrix rounded to float8 e4m3, reads 0.418-0.441 on four
#               seeds, 9.5 x the lower. Between them stands the program's
#               own int8 path (a scale a column), the mildest step down:
#               0.1005-0.1104 on six seeds, 2.3 x the lower and so no
#               upper reading by the rule of three; the limit is put
#               under it all the same, 2.0 x over the largest sound
#               reading (40 times the sound readings' own half-range), so
#               that path fails too, by 1.14-1.25 x, and the control by
#               4.75 x.
# E_FAR         a position is FAR where e, or an emitted token's gap, is
#               over a quarter of the logits' spread: 3.5 x the farthest
#               bfloat16 position of any run (0.072) and 1.6 x the int8
#               path's (0.153); a decode step that reads one position
#               back moves its position by 0.8-1.4 spreads, a token that
#               is not the logits' choice lies several spreads under.
# FAR_SHARE_TOL the share of far positions allowed: e over the decode
#               positions, the emitted token's gap over all served
#               tokens, and over the 32 first tokens alone. LOWER
#               reading: 0 in every sound run. UPPER readings, the faults
#               of ``control.py`` on three seeds each: one position back
#               at each request's first decode step 0.0115-0.0470 of e
#               (43-178 positions: the cache entry it overwrites shows
#               later too), at every step of one slot 0.0314-0.0381;
#               every decode token altered 0.9908-0.9911 of the gaps;
#               every first token altered 1.0 of the first gaps (and
#               0.0089-0.0092 of all). 0.002 is 7 to 9 positions of a
#               sample's 3,200-4,700, a sixth of the smallest fault, and
#               one of 32 first tokens is over it.
#
# A request's first token is the prefill program's, whose logits the
# engine does not hand out: the first tokens are judged by their gap
# under the reference's best (PR 22's measure), and the cache the prefill
# wrote by every decode position that reads it. The gap of ALL served
# tokens is held too: e compares the logits a step made, not the token it
# then handed out, so a token altered between the two shows there alone.
E_MEDIAN_TOL = 0.088
E_FAR = 0.25
FAR_SHARE_TOL = 0.002


def seeded_params(run, model):
    """The weights as the configuration states them: bfloat16, made on the
    device in one jitted call from ``--seed``."""
    import jax
    import jax.numpy as jnp

    example = run.family.example_batch()

    def init(key):
        params = model.init({"params": key, "dropout": key},
                            example)["params"]
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), params)

    return jax.block_until_ready(
        jax.jit(init)(jax.random.PRNGKey(run.seed)))


def build(run):
    """Model, seeded weights, serving engine. Returns the engine and the
    weights as the configuration states them, which the reference takes:
    whatever the engine makes of them (``control.py --plant int8_path``
    has it round them to int8) is the engine's."""
    import deepspeed_tpu

    model, _ = run.family.build_model(run.config)
    params = seeded_params(run, model)
    run.mark("seeded weights")
    srv = deepspeed_tpu.init_serving(
        model, params=params, config={"serving": run.config["serving"]})
    run.mark("engine construction")
    return srv, params


def warm_up(srv, traffic, vocab):
    """One short request per prompt bucket the traffic can reach (the
    engine pads prompts to powers of two) compiles every prefill program
    and the decode program."""
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    lengths, n = [], 1
    while n < lo:
        n *= 2
    while True:
        lengths.append(min(n, hi))
        if n >= hi:
            break
        n *= 2
    rng = np.random.default_rng(0)
    for length in lengths:
        srv.submit(rng.integers(0, vocab, length).tolist(), 2)
    srv.run_until_complete(timeout_sec=1100)
    srv.results.clear()
    return lengths


class Client:
    """The open loop. ``requests`` are dicts with ``due`` (seconds from
    the loop's start), ``prompt`` and ``max_new_tokens``."""

    def __init__(self, run, srv, requests, clock=time.perf_counter,
                 sleep=time.sleep):
        self.run, self.srv, self.requests = run, srv, requests
        self.clock, self.sleep = clock, sleep   # a simulator brings its own
        self.next = 0
        self.live = set()
        self.track = {}              # rid -> the request's record
        self.steps = []     # (end, seconds, active, admitted) per step()
        self.mismatch = 0

    def submit_due(self, now):
        with self.run.annotate("submit"):
            while (self.next < len(self.requests)
                   and self.requests[self.next]["due"] <= now):
                req = self.requests[self.next]
                rid = self.srv.submit(req["prompt"], req["max_new_tokens"])
                self.track[rid] = {"index": self.next, "due": req["due"],
                                   "submitted": now, "stamps": []}
                self.next += 1

    def drive(self, t0, until, hook=None):
        """Run the loop until every request is submitted and finished, or
        the clock passes ``until`` seconds. ``hook(now)`` runs once per
        iteration (the traced run switches the profiler with it)."""
        clock = self.clock
        while True:
            now = clock() - t0
            if hook is not None:
                hook(now)
            self.submit_due(now)
            if now > until:
                return
            if self.srv.idle():
                if self.next >= len(self.requests):
                    return
                with self.run.annotate("wait"):
                    self.sleep(max(0.0, min(
                        0.0005, self.requests[self.next]["due"] - now)))
                continue
            t_step = clock()
            with self.run.annotate("step"):
                info = self.srv.step()
            end = clock()
            self.steps.append((end - t0, end - t_step, info["active"],
                               len(info["prefilled"])))
            for rid in info["prefilled"]:
                self.track[rid]["stamps"].append(end - t0)
                self.live.add(rid)
            if info["active"] != len(self.live):
                self.mismatch += 1
            if info["active"]:
                for rid in self.live:
                    self.track[rid]["stamps"].append(end - t0)
            self.live.difference_update(info["finished"])


def summarize(client, srv, requests, horizon):
    """Latencies of the requests due in ``[0, horizon)``, and of their
    token gaps those that close before ``horizon``. Every request due
    before ``horizon``, the priming stretch's too, is attempted; one that
    did not finish with the tokens it asked for is ``failed`` and has no
    latency."""
    ttft, gaps, late, failed = [], [], [], 0
    due = {rid: rec for rid, rec in client.track.items()
           if rec["due"] < horizon}
    never_sent = sum(1 for r in requests[client.next:]
                     if r["due"] < horizon)
    for rid, rec in due.items():
        res = srv.results.get(rid)
        want = requests[rec["index"]]["max_new_tokens"]
        done = (res is not None and res["status"] == "finished"
                and not res["preempted_count"]
                and len(res["tokens"]) - res["prompt_len"] == want
                and len(rec["stamps"]) == want)
        if not done:
            failed += 1
            continue
        if rec["due"] < 0:
            continue                    # priming: sent, finished, not read
        stamps = np.asarray(rec["stamps"])
        ttft.append((stamps[0] - rec["due"]) * 1e3)
        gaps.extend(np.diff(stamps)[stamps[1:] < horizon] * 1e3)
        late.append((rec["submitted"] - rec["due"]) * 1e3)
    in_window = [s for s in client.steps if 0 <= s[0] < horizon]
    return {"attempted": len(due) + never_sent,
            "failed": failed + never_sent,
            "ttft_ms": ttft, "itl_ms": gaps, "late_ms": late,
            "step_ms": [s[1] * 1e3 for s in in_window],
            "active": [s[2] for s in in_window],
            "admitted": [s[3] for s in in_window]}


def step_cost(obs):
    """``(a, b, prefill)`` in ms: the window's decode-only steps fitted to
    ``a + b x rows alive``, and what a step that admits a prompt costs
    more (median). ``None`` where the window holds too few of either."""
    rows, ms, admitted = (np.asarray(obs[k], float)
                          for k in ("active", "step_ms", "admitted"))
    plain = (admitted == 0) & (rows > 0)
    if plain.sum() < 10 or len(set(rows[plain])) < 2 or not admitted.any():
        return None
    b, a = np.polyfit(rows[plain], ms[plain], 1)
    extra = np.median(ms[admitted > 0] - (a + b * rows[admitted > 0]))
    return float(a), float(b), float(extra)


def run(run):
    traffic, config = run.traffic, run.config
    vocab = config["vocab_size"]
    srv, params = build(run)
    lengths = warm_up(srv, traffic, vocab)
    run.mark("warm-up of every prompt bucket and decode")
    say(f"warm-up: prompts of {lengths} tokens, 2 new tokens each")
    requests = generate.open_loop_requests(traffic, vocab, run.seed,
                                           run.seconds)
    prime = float(traffic["prime_seconds"])
    horizon = run.seconds
    hook = None
    if run.trace:
        # The traced stretch follows the window: the same mix from another
        # seed, due after the horizon, on the engine the window left
        # loaded.
        extra = generate.open_loop_requests(
            dict(traffic, prime_seconds=0), vocab, run.seed + 1,
            traffic["trace_seconds"])
        requests = requests + [dict(r, due=r["due"] + horizon)
                               for r in extra]
        hook = TraceSwitch(run, horizon,
                           horizon + traffic["trace_seconds"])
    client = Client(run, srv, requests)
    run.mark("request schedule")

    compiles0 = run.compiles.count
    start = time.perf_counter()         # set-up ends: the first request
    t0 = start + prime                  # the window opens: the clock's 0
    client.drive(t0, horizon + traffic.get("trace_seconds", 0) * run.trace
                 + traffic["drain_seconds"], hook)
    if hook is not None:
        hook.close()

    obs = summarize(client, srv, requests, horizon)
    obs["compiles_in_window"] = run.compiles.count - compiles0
    obs["slots"] = config["serving"]["max_batch_size"]
    why_not = []
    if obs["failed"]:
        why_not.append(f"{obs['failed']} of {obs['attempted']} requests "
                       f"due before the window's end did not finish with "
                       f"the tokens they asked for")
    if client.mismatch:
        why_not.append(f"the step report's active count disagreed with "
                       f"the client's on {client.mismatch} steps")
    if not srv.idle():
        why_not.append("the engine did not drain within "
                       f"{traffic['drain_seconds']}s of the last arrival")
    elif srv.pool.used_blocks:
        why_not.append(f"the KV pool kept {srv.pool.used_blocks} blocks "
                       f"after the drain")
    if not obs["itl_ms"]:
        why_not.append("no request due in the window finished")
        obs["ttft_ms"], obs["itl_ms"], obs["late_ms"] = [0.0], [0.0], [0.0]
    pct = generate.percentile
    # The time to first token is read per layer (serve.ttft_ms_p50 and
    # _p90): the driver's check refused it as an end-to-end metric
    # (PERF.md, PR 22).
    end_to_end = {"itl_ms_p95": pct(obs["itl_ms"], 95)}
    say(f"window: {len(obs['ttft_ms'])} requests due in {horizon:g}s at "
        f"{traffic['rate_per_s']}/s after {prime:g}s of priming "
        f"({obs['attempted']} attempted with it), {obs['failed']} failed; "
        f"{len(obs['step_ms'])} steps, step ms p50 "
        f"{pct(obs['step_ms'] or [0], 50):.2f} p95 "
        f"{pct(obs['step_ms'] or [0], 95):.2f}; mean active rows "
        f"{np.mean(obs['active'] or [0]):.1f} of {obs['slots']}")
    say(f"ttft ms (n={len(obs['ttft_ms'])}): " + " ".join(
        f"p{q} {pct(obs['ttft_ms'], q):.1f}" for q in (50, 75, 80, 90, 95))
        + f" max {max(obs['ttft_ms']):.1f}; itl ms "
        f"(n={len(obs['itl_ms'])}): " + " ".join(
        f"p{q} {pct(obs['itl_ms'], q):.2f}" for q in (50, 90, 95, 99)))
    if not generate.tail_is_supported(len(obs["itl_ms"]), 95):
        say(f"note: p95 of {len(obs['itl_ms'])} gaps has fewer than ten "
            f"samples beyond it")
    cost = step_cost(obs)
    if cost:
        say("a decode-only step of the window costs {:.2f} ms + {:.3f} ms a "
            "row alive, a step that admits a prompt {:.2f} ms more (what "
            "the simulator of tests/yardstick takes)".format(*cost))
    say(f"generator lateness (submitted - due) ms: p50 "
        f"{pct(obs['late_ms'], 50):.2f} p99 {pct(obs['late_ms'], 99):.2f} "
        f"max {max(obs['late_ms']):.2f}")
    run.note_memory_peak()      # the system's; the reference comes after
    compared = {"failed": [obs["failed"], 0],
                "compiles_in_window": [obs["compiles_in_window"], 0]}
    sample = sample_of_finished(run, srv, client, horizon)
    if sample and srv.idle():           # else: not correct, said above
        held = {}
        served = replay_with_logits(run, srv, sample, held)
        srv.close()
        del srv, client         # the pool goes; the reference has the chip
        gc.collect()
        check_against_reference(run, params, sample, served, held)
        why_not += [f"{name} is {value:.4g}, over its limit {limit:g}"
                    for name, (value, limit) in held.items()
                    if not value <= limit]
        compared.update(held)
    else:
        srv.close()
    return {"window_start": start, "end_to_end": end_to_end,
            "attempted": obs["attempted"], "failed": obs["failed"],
            "why_not": why_not, "observed": obs, "compared": compared}


class TraceSwitch:
    """Turns the profiler on at ``start`` seconds of the client's clock
    and off at ``stop``, between two steps of the loop."""

    def __init__(self, run, start, stop):
        self.run, self.start, self.stop = run, start, stop
        self.window = None
        self.state = "before"

    def __call__(self, now):
        if self.state == "before" and now >= self.start:
            self.run.start_trace()
            self.window = self.run.annotate("window")
            self.window.__enter__()
            self.state = "tracing"
        elif self.state == "tracing" and now >= self.stop:
            self.close()

    def close(self):
        if self.state == "tracing":
            self.window.__exit__(None, None, None)
            self.run.stop_trace()
        self.state = "done"


def sample_of_finished(run, srv, client, horizon):
    """``check_requests`` of the window's finished requests, drawn from
    the seed, the longest always among them: each as ``{"prompt": ids,
    "tokens": prompt + served ids}``."""
    finished = sorted(
        rid for rid, rec in client.track.items()
        if 0 <= rec["due"] < horizon
        and srv.results.get(rid, {}).get("status") == "finished")
    if not finished:
        return []
    total = lambda rid: len(srv.results[rid]["tokens"])
    longest = max(finished, key=total)
    rest = [rid for rid in finished if rid != longest]
    rng = np.random.default_rng(run.seed)
    picked = rng.choice(rest, size=min(run.traffic["check_requests"] - 1,
                                       len(rest)), replace=False) \
        if rest else []
    out = []
    for rid in [longest] + [int(r) for r in picked]:
        res = srv.results[rid]
        out.append({"prompt": res["tokens"][:res["prompt_len"]],
                    "tokens": list(res["tokens"])})
    return out


def replay_with_logits(run, srv, sample, held):
    """The sampled requests once more through the same engine and the same
    compiled programs, all at once so that they share the decode batch as
    the window's did, with ``capture_logits`` on (a host switch: the
    decode program returns its logits always). Returns, per request, the
    float32 logits row behind every token a decode step emitted (all but
    its first). Greedy decoding on the same programs emits the window's
    tokens again and compiles nothing: ``held`` gets both counts, each
    beside its limit of 0."""
    srv.capture_logits = True
    compiles0 = run.compiles.count
    rids = {srv.submit(r["prompt"], len(r["tokens"]) - len(r["prompt"])): i
            for i, r in enumerate(sample)}
    rows = [[] for _ in sample]
    t0 = time.perf_counter()
    while not srv.idle():
        info = srv.step()
        if "logits" in info:
            for slot, rid in info["slots"].items():
                rows[rids[rid]].append(info["logits"][slot].copy())
    srv.capture_logits = False
    differ = sum(srv.results[rid]["tokens"] != sample[i]["tokens"]
                 for rid, i in rids.items())
    compiled = run.compiles.count - compiles0
    held["replay_requests_that_differ"] = [differ, 0]
    held["compiles_in_replay"] = [compiled, 0]
    say(f"replay of {len(sample)} sampled requests with the decode's "
        f"logits captured: {sum(len(r) for r in rows)} decode positions, "
        f"{differ} requests whose tokens differ from the window's, "
        f"{compiled} programs compiled; {time.perf_counter() - t0:.1f}s")
    return rows


def logit_statistics(e, gap, first_gap):
    """The numbers ``correct`` holds, each beside its limit: the median of
    ``e`` over the decode positions, and the share of far values of ``e``,
    of the emitted token's gap over all served tokens, and of the first
    tokens' gaps alone."""
    out = {"e_median": [float(np.median(e)), E_MEDIAN_TOL]}
    for name, x in (("e", e), ("gap", gap), ("first_gap", first_gap)):
        out[name + "_far_share"] = [float(np.mean(np.asarray(x) > E_FAR)),
                                    FAR_SHARE_TOL]
    return out


def check_against_reference(run, params, sample, served, held):
    """The sampled requests through the plain reference, a few rows at a
    time: one full float32 forward over prompt + served tokens, held
    against the logits the replay captured (``served``). ``held`` gets
    ``logit_statistics``' numbers, each beside its limit."""
    import jax
    import jax.numpy as jnp

    # one shape whatever the sample: one program, cached after a first run
    width = run.traffic["max_total_len"]
    outputs = run.traffic["output_len"]["max"]
    vocab = run.config["vocab_size"]
    block = 4
    logits_fn = run.family.reference_logits(run.config)

    def against(params, ids, first, engine):
        """``ids [block, width]``; ``first``: where each row's first
        served token is predicted (prompt_len - 1). Returns ``e``, the
        emitted token's gap and the reference logits' spread at the
        ``outputs`` positions from there."""
        at = jnp.clip(first[:, None] + jnp.arange(outputs)[None], 0,
                      width - 1)
        logits = jnp.take_along_axis(logits_fn(params, ids),
                                     at[..., None], axis=1)
        emitted = jnp.take_along_axis(ids, jnp.clip(at + 1, 0, width - 1),
                                      axis=1)
        chosen = jnp.take_along_axis(logits, emitted[..., None],
                                     axis=-1)[..., 0]
        spread = logits.std(-1)
        return (jnp.abs(engine - logits).max(-1) / spread,
                (logits.max(-1) - chosen) / spread, spread)

    against = jax.jit(against)
    t0 = time.perf_counter()
    e, gap, first_gap, spread = [], [], [], []
    for lo in range(0, len(sample), block):
        ids = np.zeros((block, width), np.int32)
        first = np.zeros((block,), np.int32)
        engine = np.zeros((block, outputs, vocab), np.float32)
        part = list(zip(sample[lo:lo + block], served[lo:lo + block]))
        for i, (r, rows) in enumerate(part):
            ids[i, :len(r["tokens"])] = r["tokens"]
            first[i] = len(r["prompt"]) - 1
            if rows:
                engine[i, 1:1 + len(rows)] = rows
        e_b, gap_b, spread_b = (np.asarray(x) for x in against(
            params, ids, first, engine))
        for i, (r, rows) in enumerate(part):
            n = len(r["tokens"]) - len(r["prompt"])
            e.extend(e_b[i, 1:1 + len(rows)])
            gap.extend(gap_b[i, :n])
            first_gap.append(gap_b[i, 0])
            spread.extend(spread_b[i, :n])
    stats = logit_statistics(e, gap, first_gap)
    held.update(stats)
    in_logits = np.multiply(gap, spread)
    say(f"reference check on {len(sample)} requests, in units of the "
        f"standard deviation of a position's reference logits over the "
        f"vocabulary (median {np.median(spread):.4f}): {len(e)} decode "
        f"positions, e = max over the vocabulary |engine - reference| "
        f"median {stats['e_median'][0]:.4f} (limit {E_MEDIAN_TOL:g}) p95 "
        f"{np.percentile(e, 95):.4f} max {max(e):.4f}, share over "
        f"{E_FAR:g}: {stats['e_far_share'][0]:.4f} (limit "
        f"{FAR_SHARE_TOL:g}); {len(first_gap)} first tokens, gap under "
        f"the reference's best median {np.median(first_gap):.4f} "
        f"max {max(first_gap):.4f}, share over {E_FAR:g}: "
        f"{stats['first_gap_far_share'][0]:.4f}; "
        f"{time.perf_counter() - t0:.1f}s")
    say(f"the emitted token's reference logit below the reference's best "
        f"over {len(gap)} served tokens: median "
        f"{np.median(gap):.4f}, share over {E_FAR:g}: "
        f"{stats['gap_far_share'][0]:.4f}; for the record and deciding "
        f"nothing, in logits as PR 22 judged it by its worst (limit "
        f"0.08): max {in_logits.max():.4f} mean {in_logits.mean():.4f}")
