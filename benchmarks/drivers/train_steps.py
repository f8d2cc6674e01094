"""Driver ``train_steps``: optimizer steps through
``deepspeed_tpu.initialize()`` / ``engine.train_batch()``.

Traffic parameters: ``seq_len``, ``micro_batch_per_chip``,
``gradient_accumulation_steps``, ``pool_batches``,
``token_zipf_exponent``, ``warmup_steps`` (two at least), ``trace_steps``,
``reference_chunk_sequences`` and whatever the family's ``make_batch``
reads (``mask_rate``).

The loop dispatches step *i* and then fetches step *i-1*'s loss: one
step of lag keeps the device fed, and every timestamp follows a host
fetch, so none measures an enqueue. Each step takes the next batch of a
pool built from the seed in set-up; the host-to-device put is inside the
window, host RNG is not.

``correct`` is decided on what the timed program itself returns: the
losses of the first two ``train_batch()`` calls, against the plain
reference's loss at the seeded weights and after one reference optimizer
step (``check_against_reference``). The reference runs when the window
and the trace are over, so none of it is in ``setup_s``.
"""

import math
import time

import numpy as np

from benchmarks import generate
from benchmarks.harness import load_module, say

# The engine computes in bfloat16 from float32 master weights; the
# reference computes in float32 at "highest" matmul precision from the
# same master weights. What was seen of the difference is in PERF.md
# (Findings, PR 22):
#
# LOSS_RTOL: on the v5e the engine's forward loss of a few sequences lay
# within 1.8e-4 of the reference's in thirteen runs, and the first
# ``train_batch()`` loss of a whole global batch within 9e-5. The
# tolerance leaves ten times the former: a wrong mask, a dropped position
# or an 8-bit path moves the loss by percents.
#
# STEP_RTOL: how far the engine's second loss may lie from the
# reference's, as a share of what one reference optimizer step takes off
# the loss. At real size that step takes 0.7 to 1.2 off a loss of 10 to
# 11, and the engine's update was within 1.4% of the step's effect. An
# update that is dropped, halved, doubled, of the wrong sign or without
# its bias correction is off by 50% or more.
LOSS_RTOL = 2e-3
STEP_RTOL = 0.1


class Loop:
    """Dispatch step i, then fetch step i-1's loss."""

    def __init__(self, run, engine, pool):
        self.run, self.engine, self.pool = run, engine, pool
        self.i = 0
        self.pending = None
        self.reset()

    def reset(self):
        self.losses, self.done = [], []
        self.put_ms, self.dispatch_ms = [], []

    def advance(self, batch=None):
        if batch is None:
            batch = self.pool[self.i % len(self.pool)]
            self.i += 1
        t0 = time.perf_counter()
        with self.run.annotate("put"):
            placed = self.engine.put_batch(batch, leading_gas_dim=True)
        t1 = time.perf_counter()
        with self.run.annotate("dispatch"):
            loss = self.engine.train_batch(placed)
        t2 = time.perf_counter()
        self.put_ms.append((t1 - t0) * 1e3)
        self.dispatch_ms.append((t2 - t1) * 1e3)
        self.collect()
        self.pending = loss

    def collect(self):
        if self.pending is not None:
            with self.run.annotate("fetch"):
                self.losses.append(float(self.pending))
            self.done.append(time.perf_counter())
            self.pending = None


def engine_config(config, traffic):
    """The configuration's engine settings with the traffic's batch."""
    return {**config["train_engine"],
            "train_micro_batch_size_per_gpu": traffic["micro_batch_per_chip"],
            "gradient_accumulation_steps":
                traffic["gradient_accumulation_steps"]}


def build(run):
    """Model, seeded weights born in their ZeRO sharding, engine, pool,
    and a host copy of the weights the engine starts from."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import build_mesh

    config, traffic, family = run.config, run.traffic, run.family
    mesh = build_mesh(data=run.chips, devices=jax.devices()[:run.chips])
    model, _ = family.build_model(config)
    settings = engine_config(config, traffic)
    stage = settings.get("zero_optimization", {}).get("stage", 0)
    params, _ = deepspeed_tpu.zero_init(
        model, family.example_batch(), mesh=mesh, zero_stage=stage,
        rngs={"params": jax.random.PRNGKey(run.seed),
              "dropout": jax.random.PRNGKey(run.seed + 1)})
    jax.block_until_ready(params)
    run.mark("seeded weights")
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, params=params, mesh=mesh, config=settings)
    del params          # the engine holds its own copy
    jax.block_until_ready(engine.state)
    run.mark("engine construction")
    # what the reference starts from, kept on the host until the window
    # is over
    initial = jax.device_get(engine.state.params)
    rng = np.random.default_rng(run.seed + 1)
    pool = [family.make_batch(tokens, traffic, rng)
            for tokens in generate.train_pool(
                traffic, config["vocab_size"], run.chips, run.seed)]
    run.mark("initial weights to the host, batch pool")
    return engine, pool, initial


def run(run):
    traffic = run.traffic
    if traffic["warmup_steps"] < 2:
        raise ValueError("warmup_steps must be at least 2: the reference "
                         "is held against the first two steps' losses")
    engine, pool, initial = build(run)
    loop = Loop(run, engine, pool)
    tokens_per_step = (traffic["gradient_accumulation_steps"]
                       * traffic["micro_batch_per_chip"] * run.chips
                       * traffic["seq_len"])

    # Warm-up: with ZeRO >= 1 the step is traced a second time at step 2,
    # so three synchronous steps see every program the window will use.
    # The first two take the SAME batch, the pool's first, from the seeded
    # weights: the second loss then shows what the first update did to the
    # very batch it was computed from, and the reference is held against
    # both.
    for i in range(traffic["warmup_steps"]):
        loop.advance(pool[0] if i < 2 else None)
        loop.collect()
        run.mark(f"warm-up step {i + 1}")
    first_losses = list(loop.losses)
    say(f"warm-up losses {[round(x, 4) for x in first_losses]}")
    loop.reset()

    compiles0 = run.compiles.count
    t_start = time.perf_counter()
    while time.perf_counter() - t_start < run.seconds:
        loop.advance()
    loop.collect()
    losses, done = list(loop.losses), list(loop.done)
    n = len(done)
    tokens_per_s_per_chip = (n * tokens_per_step / (done[-1] - t_start)
                             / run.chips)
    step_s = np.diff([t_start] + done)
    observed = {"tokens_per_s_per_chip": tokens_per_s_per_chip,
                "dispatch_ms": list(loop.dispatch_ms),
                "compiles_in_window": run.compiles.count - compiles0}
    say(f"window: {n} optimizer steps of {tokens_per_step} tokens in "
        f"{done[-1] - t_start:.3f}s; step seconds median "
        f"{np.median(step_s):.4f} min {step_s.min():.4f} max "
        f"{step_s.max():.4f}; dispatch ms median "
        f"{np.median(loop.dispatch_ms):.2f}, put ms median "
        f"{np.median(loop.put_ms):.2f}")
    say(f"window losses {[round(x, 4) for x in losses]}")

    if run.trace:
        loop.reset()
        run.start_trace()
        with run.annotate("window"):
            for _ in range(traffic["trace_steps"]):
                loop.advance()
            loop.collect()
        run.stop_trace()

    why_not = []
    bad = [x for x in losses if not math.isfinite(x)]
    skipped = int(engine.skipped_steps)
    if bad:
        why_not.append(f"{len(bad)} non-finite losses in the window")
    if skipped:
        why_not.append(f"{skipped} optimizer steps skipped")
    k = min(3, max(1, n // 2))
    if not np.mean(losses[-k:]) < np.mean(losses[:k]):
        why_not.append(f"the loss did not fall over the window: "
                       f"{losses[:k]} -> {losses[-k:]}")
    run.note_memory_peak()      # the system's; the reference comes after
    release(engine)
    why_not += check_against_reference(run, initial, pool[0], first_losses)
    return {"window_start": t_start,
            "end_to_end": {"tokens_per_s_per_chip": tokens_per_s_per_chip},
            "attempted": n, "failed": len(bad) + skipped,
            "why_not": why_not, "observed": observed}


def release(engine):
    """The engine has done its work: its state goes back to the device,
    and the reference has the chip to itself."""
    import jax
    for leaf in jax.tree_util.tree_leaves(engine.state):
        if isinstance(leaf, jax.Array):
            leaf.delete()


def reference_programs(nll, first_step):
    """The three jitted programs of the reference. A global batch is cut
    into ``[gas, chunks, sequences, ...]`` and taken one chunk at a time,
    so the reference never holds more than one chunk's activations."""
    import jax
    import jax.numpy as jnp

    def forward(params, batch):
        """Sum and count of the negative log-likelihood of every chunk."""
        return jax.lax.map(lambda micro: jax.lax.map(
            lambda chunk: nll(params, chunk), micro), batch)

    def gradient(params, batch, counts):
        """Gradient of the loss as ``train_batch()`` reports it: the mean
        over the micro-batches of each one's mean over its labelled
        positions (``counts``, from ``forward``)."""
        weights = jnp.broadcast_to(
            1.0 / (counts.sum(1, keepdims=True) * counts.shape[0]),
            counts.shape)
        flat = jax.tree_util.tree_map(
            lambda x: x.reshape(-1, *x.shape[2:]), (batch, weights))

        def add(total, chunk_weight):
            chunk, weight = chunk_weight
            g = jax.grad(lambda p: nll(p, chunk)[0])(params)
            return jax.tree_util.tree_map(
                lambda t, x: t + weight * x, total, g), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        return jax.lax.scan(add, zeros, flat)[0]

    return jax.jit(forward), jax.jit(gradient), jax.jit(first_step)


def mean_loss(sums, counts):
    return float((np.asarray(sums).sum(1) / np.asarray(counts).sum(1)).mean())


def check_against_reference(run, initial, batch, first_losses):
    """What the timed program returned against the plain float32
    reference, from the same seeded weights on the same global batch,
    which the first two ``train_batch()`` calls both took:

    - the first loss against the reference's loss at those weights (the
      forward pass);
    - the second against the reference's loss after ONE reference
      optimizer step on the reference's gradient, within ``STEP_RTOL`` of
      what that step takes off the loss (the backward pass, the
      accumulation, the reduction over chips and the update: whatever is
      wrong there moves the second loss).

    Both losses are a function of the seed alone, so a change that leaves
    the arithmetic alone reproduces them digit for digit."""
    import jax

    t0 = time.perf_counter()
    opt = run.config["train_engine"]["optimizer"]
    first_step = load_module(
        "reference", "optimizers." + opt["type"].lower()).first_step
    forward, gradient, step = reference_programs(
        run.family.reference_nll(run.config),
        lambda params, grads: first_step(params, grads, **opt["params"]))
    size = run.traffic["reference_chunk_sequences"]
    batch = {k: np.asarray(v).reshape(v.shape[0], -1, size, *v.shape[2:])
             for k, v in batch.items()}
    initial = jax.device_put(initial)

    sums, counts = forward(initial, batch)
    stepped = step(initial, gradient(initial, batch, counts))
    return compare(first_losses, mean_loss(sums, counts),
                   mean_loss(*forward(stepped, batch)),
                   f"{time.perf_counter() - t0:.1f}s")


def compare(first_losses, loss_0, loss_1, took=""):
    """The reasons, if any, for which the engine's first two losses do not
    match the reference's: ``loss_0`` at the seeded weights and ``loss_1``
    after one reference optimizer step, on the same batch."""
    engine_0, engine_1 = first_losses[:2]
    d_0 = abs(engine_0 - loss_0) / abs(loss_0)
    fell = loss_0 - loss_1
    d_step = abs(engine_1 - loss_1) / abs(fell)
    say(f"reference check: step 1 loss engine {engine_0:.6f} reference "
        f"{loss_0:.6f} (rel {d_0:.2e}, tol {LOSS_RTOL:g}); step 2 loss, "
        f"same batch, engine {engine_1:.6f} reference {loss_1:.6f} after "
        f"one reference step, which took {fell:.6f} off it (engine off by "
        f"{d_step:.2%} of that, tol {STEP_RTOL:.0%}); {took}")
    why_not = []
    if not d_0 <= LOSS_RTOL:
        why_not.append(f"the first step's loss differs from the reference "
                       f"by {d_0:.2e} (> {LOSS_RTOL:g})")
    if not d_step <= STEP_RTOL:
        why_not.append(
            f"the second step's loss is off the reference's by "
            f"{d_step:.2%} of what one reference optimizer step takes off "
            f"it (> {STEP_RTOL:.0%}): the engine's first update is not "
            f"that step")
    return why_not
