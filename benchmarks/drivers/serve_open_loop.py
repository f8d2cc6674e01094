"""Driver ``serve_open_loop``: requests through
``deepspeed_tpu.init_serving()``, sent when they are due.

Traffic parameters: ``rate_per_s``, ``prompt_len``, ``output_len``
(lognormal, clipped), ``max_total_len``, ``token_zipf_exponent``,
``drain_seconds``, ``trace_seconds``, ``check_requests``. The engine's
settings are the configuration's ``serving`` block.

The client loop is the benchmark's own and has one thread: submit what is
due, call ``step()``, stamp the tokens the step report says arrived. A
request's clock starts when it was DUE, not when the loop got round to
submitting it, so a stall is charged to every request it delayed. A token
arrives when the ``step()`` that made it returns; a request's first two
tokens arrive together (the step that prefills it also decodes it).
"""

import time

import numpy as np

from benchmarks import generate
from benchmarks.harness import say

# The engine serves bfloat16 weights and activations; the reference runs
# the same weights in float32. With seeded random weights the logits of a
# position differ by hundredths between the two (measured on the v5e:
# PERF.md, Findings, PR 22), so the argmax flips on near-ties and tokens
# cannot be compared. What must hold: the token the engine emitted scores
# within LOGIT_GAP_TOL of the reference's best token at that position.
# Logits of this model at initialisation have a standard deviation of
# about 0.6 across the vocabulary, and the runner-up sits ~0.1 below the
# best, so an 8-bit path or a wrong cache position fails this at once.
LOGIT_GAP_TOL = 0.08


def build(run):
    """Model, seeded bfloat16 weights made on the device in one jitted
    call, serving engine."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu

    model, _ = run.family.build_model(run.config)
    example = run.family.example_batch()

    def init(key):
        params = model.init({"params": key, "dropout": key},
                            example)["params"]
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), params)

    params = jax.block_until_ready(
        jax.jit(init)(jax.random.PRNGKey(run.seed)))
    run.mark("seeded weights")
    srv = deepspeed_tpu.init_serving(
        model, params=params, config={"serving": run.config["serving"]})
    run.mark("engine construction")
    return srv


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

    def __init__(self, run, srv, requests):
        self.run, self.srv, self.requests = run, srv, requests
        self.next = 0
        self.live = set()
        self.track = {}              # rid -> the request's record
        self.steps = []              # (end, seconds, active) per step()
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
        clock = time.perf_counter
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
                    time.sleep(max(0.0, min(
                        0.0005, self.requests[self.next]["due"] - now)))
                continue
            t_step = clock()
            with self.run.annotate("step"):
                info = self.srv.step()
            end = clock()
            self.steps.append((end - t0, end - t_step, info["active"]))
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
    """Latencies of the requests due before ``horizon``; a request that did
    not finish with the tokens it asked for is ``failed`` and has no
    latency."""
    ttft, gaps, late, failed = [], [], [], 0
    measured = {rid: rec for rid, rec in client.track.items()
                if rec["due"] < horizon}
    never_sent = sum(1 for r in requests[client.next:]
                     if r["due"] < horizon)
    for rid, rec in measured.items():
        res = srv.results.get(rid)
        want = requests[rec["index"]]["max_new_tokens"]
        done = (res is not None and res["status"] == "finished"
                and not res["preempted_count"]
                and len(res["tokens"]) - res["prompt_len"] == want
                and len(rec["stamps"]) == want)
        if not done:
            failed += 1
            continue
        ttft.append((rec["stamps"][0] - rec["due"]) * 1e3)
        gaps.extend(np.diff(rec["stamps"]) * 1e3)
        late.append((rec["submitted"] - rec["due"]) * 1e3)
    in_window = [s for s in client.steps if s[0] < horizon]
    return {"attempted": len(measured) + never_sent,
            "failed": failed + never_sent,
            "ttft_ms": ttft, "itl_ms": gaps, "late_ms": late,
            "step_ms": [s[1] * 1e3 for s in in_window],
            "active": [s[2] for s in in_window]}


def run(run):
    traffic, config = run.traffic, run.config
    vocab = config["vocab_size"]
    srv = build(run)
    lengths = warm_up(srv, traffic, vocab)
    run.mark("warm-up of every prompt bucket and decode")
    say(f"warm-up: prompts of {lengths} tokens, 2 new tokens each")
    requests = generate.open_loop_requests(traffic, vocab, run.seed,
                                           run.seconds)
    horizon = run.seconds
    hook = None
    if run.trace:
        # The traced stretch follows the window and needs live traffic of
        # its own: the same mix from another seed, due after the horizon.
        extra = generate.open_loop_requests(
            traffic, vocab, run.seed + 1, traffic["trace_seconds"])
        requests = requests + [dict(r, due=r["due"] + horizon)
                               for r in extra]
        hook = TraceSwitch(run, horizon,
                           horizon + traffic["trace_seconds"])
    client = Client(run, srv, requests)
    run.mark("request schedule")

    compiles0 = run.compiles.count
    t0 = time.perf_counter()
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
                       f"due in the window did not finish with the "
                       f"tokens they asked for")
    if client.mismatch:
        why_not.append(f"the step report's active count disagreed with "
                       f"the client's on {client.mismatch} steps")
    if not srv.idle():
        why_not.append("the engine did not drain within "
                       f"{traffic['drain_seconds']}s of the last arrival")
    elif srv.pool.used_blocks:
        why_not.append(f"the KV pool kept {srv.pool.used_blocks} blocks "
                       f"after the drain")
    if not obs["ttft_ms"]:
        why_not.append("no request finished")
        obs["ttft_ms"], obs["itl_ms"], obs["late_ms"] = [0.0], [0.0], [0.0]
    pct = generate.percentile
    # The time to first token is read per layer (serve.ttft_ms_p50 and
    # _p90): 60 requests a window do not steady it (PERF.md, PR 22).
    end_to_end = {"itl_ms_p95": pct(obs["itl_ms"], 95)}
    say(f"window: {obs['attempted']} requests due in {horizon:g}s at "
        f"{traffic['rate_per_s']}/s, {obs['failed']} failed; "
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
    say(f"generator lateness (submitted - due) ms: p50 "
        f"{pct(obs['late_ms'], 50):.2f} p99 {pct(obs['late_ms'], 99):.2f} "
        f"max {max(obs['late_ms']):.2f}")
    run.note_memory_peak()      # the system's; the reference comes after
    why_not += check_against_reference(run, srv, client, requests, horizon)
    srv.close()
    return {"window_start": t0, "end_to_end": end_to_end,
            "attempted": obs["attempted"], "failed": obs["failed"],
            "why_not": why_not, "observed": obs}


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


def check_against_reference(run, srv, client, requests, horizon):
    """A seeded sample of finished requests through the plain reference:
    one full float32 forward over prompt + output, and at every position
    that produced a token, how far the emitted token's logit lies below
    the reference's largest."""
    import jax
    import jax.numpy as jnp

    finished = sorted(
        rid for rid, rec in client.track.items()
        if rec["due"] < horizon
        and srv.results.get(rid, {}).get("status") == "finished")
    if not finished:
        return []
    rng = np.random.default_rng(run.seed)
    sample = rng.choice(finished, size=min(run.traffic["check_requests"],
                                           len(finished)), replace=False)
    rows = [srv.results[int(rid)] for rid in sample]
    width = max(len(r["tokens"]) for r in rows)
    width = -(-width // 128) * 128 if width > 128 else width
    width = min(width, run.traffic["max_total_len"])
    ids = np.zeros((len(rows), width), np.int32)
    emitted = np.zeros((len(rows), width), bool)   # position predicts a token
    for i, r in enumerate(rows):
        n = len(r["tokens"])
        ids[i, :n] = r["tokens"]
        emitted[i, r["prompt_len"] - 1:n - 1] = True
    logits_fn = run.family.reference_logits(run.config)

    def gaps(params, ids):
        logits = logits_fn(params, ids)
        nxt = jnp.roll(ids, -1, axis=1)
        chosen = jnp.take_along_axis(logits, nxt[..., None], axis=-1)[..., 0]
        return logits.max(-1) - chosen

    t0 = time.perf_counter()
    gap = np.asarray(jax.jit(gaps)(srv.engine.params, ids))
    worst = float(gap[emitted].max())
    say(f"reference check on {len(rows)} requests, {int(emitted.sum())} "
        f"emitted tokens: emitted token's reference logit below the "
        f"reference's best by max {worst:.4f} mean "
        f"{float(gap[emitted].mean()):.4f} (tol {LOGIT_GAP_TOL:g}); "
        f"{time.perf_counter() - t0:.1f}s")
    if not worst <= LOGIT_GAP_TOL:
        return [f"an emitted token scores {worst:.4f} below the "
                f"reference's best logit (> {LOGIT_GAP_TOL:g})"]
    return []
