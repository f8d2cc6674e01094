"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, the public entry points only, one model for both halves
(GPT-2 124M: all 12 layers, hidden 768, vocab 50257 — nothing cut, random
weights from a seed):

- trainer: ``deepspeed_tpu.initialize()`` (ZeRO-2, bf16, Adam, ``mesh=None``
  = every local device) and a handful of ``engine.train_batch()`` steps at
  micro batch 16 x seq 512 per chip on a fixed seeded batch. On a
  multi-device host the same global batch is also trained under ZeRO-3 and
  on ONE device, and the loss trajectories must agree at bf16 tolerance.
- dropless trainer: the ``glm4_moe_lite`` block at its tiny preset (latent
  attention, the dropless expert layer holding 4 of 8 experts, a shared
  expert, an MTP module) through the same entry points on one device; its
  first two ``train_batch()`` losses on one batch are held to the
  benchmark's plain float32 reference and one reference Adam step, by the
  benchmark's own comparison.
- server: ``deepspeed_tpu.init_serving()`` with the default serving
  config, a mixed trace of ``submit()``s, ``run_until_complete()`` — twice,
  compared with itself.
- kernels: every entry of ``deepspeed_tpu/ops/kernel_cases.py`` (every
  ``pallas_call`` in the tree) compiled by Mosaic and compared with its
  ``jax.numpy`` reference at the bf16 floor of ``utils/parity.py``.

It exits non-zero, printing no result line, unless JAX's default backend
is a TPU whose ``device_kind`` is in the peak table and every check of
every phase passed. It sets no ``JAX_PLATFORMS`` itself, starts no child
process, needs no network and no file git would not commit. Its timings
are smoke, not a benchmark. Details land in ``chiprun_out/chip_smoke/``.

``--cpu-rehearsal`` is the ONLY way it runs without a chip: the same
control flow at a tiny size with the kernels in the Pallas interpreter.
Every line it prints says so, and it proves nothing about the device.

Last line of stdout on success:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
"""

import argparse
import dataclasses
import functools
import importlib.metadata
import json
import os
import re
import sys
import time

import jax
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import make_glm4_moe_lite, make_gpt
from deepspeed_tpu.ops.kernel_cases import kernel_cases
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.profiling.flops_profiler import TPU_PEAK_TFLOPS
from deepspeed_tpu.utils.compile_cache import configure_compile_cache
from deepspeed_tpu.utils.parity import bf16_mismatch

OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "chiprun_out", "chip_smoke")
LOSS_RTOL = 2e-2     # bf16 trajectory tolerance (__graft_entry__'s bf16 rungs)
TRAIN_STEPS = 6


class Report:
    """Collects check results. A failed CHECK is recorded and the run goes
    on to the next check (one chip call should say everything that is
    wrong); an EXCEPTION is never caught — it ends the run."""

    def __init__(self, tag: str):
        self.tag = tag                # "" or "[CPU REHEARSAL] "
        self.failures = []
        self.details = {}

    def say(self, msg: str) -> None:
        print(f"{self.tag}{msg}", flush=True)

    def check(self, ok, what: str) -> None:
        self.say(f"  {'ok  ' if ok else 'FAIL'} {what}")
        if not ok:
            self.failures.append(what)

    def run_phase(self, name: str, phase, *args) -> None:
        before = len(self.failures)
        self.say(f"--- {name}")
        phase(self, *args)
        n = len(self.failures) - before
        self.say(f"PHASE {name}: " + ("PASS" if n == 0 else f"FAIL ({n})"))


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------
def run_trainer(rep, model, params, samples, micro, stage, mesh, what):
    """Train TRAIN_STEPS steps on the fixed global batch ``samples``
    [G, seq] over ``mesh`` (None = every local device); returns the engine,
    the engine-shaped batch and a record of losses and timings."""
    n_dev = mesh.size if mesh is not None else jax.device_count()
    gas = samples.shape[0] // (micro * n_dev)
    batches = {"input_ids": samples.reshape(gas, micro * n_dev, -1)}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, params=params, mesh=mesh,
        config={
            "train_micro_batch_size_per_gpu": micro,
            "gradient_accumulation_steps": gas,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": stage},
            "bf16": {"enabled": True},
        })
    losses, times = [], []
    for _ in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        losses.append(float(engine.train_batch(batches)))   # host fetch
        times.append(time.perf_counter() - t0)
    # Steady = the last three steps: with ZeRO >= 1 the step is traced a
    # second time at step 2 (the state comes back from step 1 under other
    # shardings than it was first placed with), and on a multi-device mesh
    # that second program is a second full compile.
    steady = float(np.median(times[-3:]))
    traces = engine._train_step._cache_size()
    rec = {"what": what, "devices": n_dev, "gas": gas, "losses": losses,
           "step_sec": times, "steady_step_sec": steady,
           "train_step_traces": traces}
    rep.say(f"  {what}: {n_dev} device(s), gas {gas}, loss "
            f"{losses[0]:.4f} -> {losses[-1]:.4f}; step traced {traces}x; "
            f"seconds per step {[round(t, 2) for t in times]}, steady "
            f"{steady * 1e3:.0f} ms/step")
    rep.check(all(np.isfinite(losses)), f"{what}: losses finite")
    rep.check(losses[-1] < losses[0], f"{what}: loss fell")
    return engine, batches, rec


def inspect_step(rep, engine, batches, n_layers, stage, rehearsal):
    """What the compiled train step contains: Mosaic flash calls (attention
    did not go to xla_attention or to the interpreter) and, over several
    devices, the collectives ZeRO claims. Compiles the step ahead of time
    a second time — a persistent-cache hit."""
    t0 = time.perf_counter()
    compiled = engine._train_step.lower(
        engine.state, engine.put_batch(batches, leading_gas_dim=True),
        engine._current_lr()).compile()
    text = compiled.as_text()
    counts = {op: len(re.findall(rf"\b{op}(?:-start)?\(", text))
              for op in ("all-gather", "reduce-scatter", "all-reduce")}
    n_mosaic = text.count('custom_call_target="tpu_custom_call"')
    temp = compiled.memory_analysis().temp_size_in_bytes
    rep.say(f"  compiled ZeRO-{stage} step: {n_mosaic} Mosaic calls, "
            f"collectives {counts}, {temp / 2**30:.2f} GiB temporaries "
            f"(inspection compile {time.perf_counter() - t0:.1f}s)")
    if rehearsal:
        rep.say("  (rehearsal: attention runs in XLA below the seq-512 "
                "crossover — no Mosaic calls to count)")
    else:
        # flash_fwd + flash_bwd per layer
        rep.check(n_mosaic == 2 * n_layers,
                  f"train step holds the two Mosaic flash kernels a layer "
                  f"({n_mosaic} == {2 * n_layers})")
    if engine.mesh.size > 1:
        rep.check(counts["all-gather"] > 0
                  and counts["reduce-scatter"] + counts["all-reduce"] > 0,
                  f"ZeRO-{stage} step reduces gradients and all-gathers "
                  f"parameters across devices")
    return {"mosaic_calls": n_mosaic, "collectives": counts,
            "temp_bytes": int(temp)}


def check_partitioned(rep, engine, stage, min_size):
    """Large optimizer-state leaves are really partitioned over every
    device (a sharding that is only a label would show the full shape)."""
    n_dev = engine.mesh.size
    big = [x for x in jax.tree_util.tree_leaves(engine.state.opt_state)
           if x.size >= min_size]
    whole = [x.shape for x in big
             if x.sharding.shard_shape(x.shape) == x.shape
             or len(x.sharding.device_set) != n_dev]
    rep.check(big and not whole,
              f"ZeRO-{stage}: all {len(big)} large optimizer-state leaves "
              f"partitioned over {n_dev} devices (unpartitioned: {whole})")


def trainer_phase(rep, model, cfg, params, rehearsal):
    n_dev = jax.device_count()
    micro, seq = (2, 32) if rehearsal else (16, 512)
    # One fixed global batch per optimizer step, the same sequences on any
    # device count: 4 micro batches per device-step on one chip, so a
    # 4-chip host sees them as one micro batch per chip.
    rng = np.random.default_rng(0)
    samples = rng.integers(0, cfg.vocab_size, (micro * max(n_dev, 4), seq),
                           dtype=np.int32)
    records = []
    engine, batches, rec = run_trainer(rep, model, params, samples, micro, 2,
                                       None, "ZeRO-2 over all devices")
    rec.update(inspect_step(rep, engine, batches, cfg.num_layers, 2,
                            rehearsal))
    records.append(rec)
    rep.check(engine.mesh.shape["data"] == n_dev,
              f"mesh=None put every device on the data axis "
              f"({dict(engine.mesh.shape)})")
    if n_dev > 1:
        min_size = 1 << (12 if rehearsal else 20)
        check_partitioned(rep, engine, 2, min_size)
        del engine
        engine, batches, rec3 = run_trainer(
            rep, model, params, samples, micro, 3, None,
            "ZeRO-3 over all devices")
        rec3.update(inspect_step(rep, engine, batches, cfg.num_layers, 3,
                                 rehearsal))
        check_partitioned(rep, engine, 3, min_size)
        del engine
        _, _, ref = run_trainer(
            rep, model, params, samples, micro, 2,
            build_mesh(data=1, devices=jax.devices()[:1]),
            "reference on ONE device")
        records += [rec3, ref]
        for r in (rec, rec3):
            err = np.max(np.abs(np.array(r["losses"]) - ref["losses"])
                         / np.abs(ref["losses"]))
            rep.check(err <= LOSS_RTOL,
                      f"{r['what']}: loss trajectory matches one device "
                      f"(max rel err {err:.2e} <= {LOSS_RTOL})")
    rep.details["trainer"] = records


def dropless_trainer_phase(rep, rehearsal):
    """Two ``train_batch()`` calls of the tiny ``glm4_moe_lite`` block on
    one batch, bf16 under ZeRO-2 on one device, against the benchmark's
    plain reference: the first loss at the seeded weights, the second
    after one reference Adam step (``drivers/train_steps.compare``)."""
    from benchmarks.harness import load_module
    from benchmarks.reference import glm4_moe_lite as reference
    from benchmarks.reference.optimizers import adam

    lr, gas = 1e-3, 2
    model, cfg = make_glm4_moe_lite(n_held_experts=4, first_held_expert=2)
    seq = 32 if rehearsal else 128
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (gas, 2, seq), dtype=np.int32)
    params = jax.device_get(model.init(
        {"params": jax.random.PRNGKey(0)}, {"input_ids": ids[0]})["params"])
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, params=params,
        mesh=build_mesh(data=1, devices=jax.devices()[:1]),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": gas,
                "optimizer": {"type": "Adam", "params": {"lr": lr}},
                "zero_optimization": {"stage": 2},
                "bf16": {"enabled": True}})
    losses = [float(engine.train_batch({"input_ids": ids}))
              for _ in range(2)]

    kw = reference.settings({
        **dataclasses.asdict(cfg),
        "assumed": {"mtp_loss_weight": cfg.mtp_loss_weight}})

    @jax.jit
    def reference_losses(p):
        mean = lambda q: sum(reference.loss(q, {"input_ids": micro}, **kw)
                             for micro in ids) / gas
        loss_0, grads = jax.value_and_grad(mean)(p)
        return loss_0, mean(adam.first_step(p, grads, lr=lr))

    loss_0, loss_1 = map(float, reference_losses(params))
    why_not = load_module("drivers", "train_steps").compare(
        losses, loss_0, loss_1)
    rep.check(not why_not, "dropless trainer: two train_batch() losses "
              f"match the reference and one reference Adam step {why_not}")
    rep.details["dropless_trainer"] = {
        "losses": losses, "reference": [loss_0, loss_1]}


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------
def server_phase(rep, model, cfg, params, rehearsal):
    # (prompt length, new tokens): three prefill buckets, more requests
    # than the default 8 decode slots so slots are reused.
    lens = (5, 12, 20, 30) if rehearsal else (9, 14, 40, 60, 150, 200)
    rng = np.random.default_rng(1)
    trace = [(int(lens[i % len(lens)]), int(rng.integers(4, 25)))
             for i in range(12)]
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).tolist()
               for n, _ in trace]
    srv = deepspeed_tpu.init_serving(model, params=params, config={})
    passes = []
    for _ in range(2):
        t0 = time.perf_counter()
        rids = [srv.submit(p, n) for p, (_, n) in zip(prompts, trace)]
        res = srv.run_until_complete(timeout_sec=900)
        passes.append(([res[r] for r in rids], time.perf_counter() - t0))
        srv.results.clear()
    (first, t_first), (second, t_second) = passes
    new_tokens = sum(n for _, n in trace)
    rep.say(f"  12 requests, {new_tokens} new tokens: first pass "
            f"{t_first:.1f}s (with compiles), second pass {t_second:.2f}s")
    rep.check(all(r["status"] == "finished"
                  and len(r["tokens"]) - r["prompt_len"] == n
                  for r, (_, n) in zip(first + second, trace + trace)),
              "every request finished with the asked number of tokens")
    rep.check(max(srv.stats["slot_assignments"].values()) >= 2,
              f"a decode slot was reused ({srv.stats['slot_assignments']})")
    det = srv.engine.recompile_detector
    rep.check(det.compiles("serving.decode_step") == 1
              and det.retraces("serving.decode_step") == 0,
              "serving.decode_step compiled once over both passes")
    rep.check([r["tokens"] for r in first] == [r["tokens"] for r in second],
              "the second pass reproduced the first token for token")
    rep.check(srv.pool.used_blocks == 0, "the KV pool drained to zero")
    srv.close()
    rep.details["server"] = {"first_pass_sec": t_first,
                             "second_pass_sec": t_second,
                             "new_tokens": new_tokens,
                             "compiles": dict(det.stats)}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def kernels_phase(rep, rehearsal):
    results = {}
    for case in kernel_cases():
        args = case.make_args(np.random.default_rng(0))
        t0 = time.perf_counter()
        got = jax.block_until_ready(
            jax.jit(functools.partial(case.run, rehearsal))(*args))
        dt = time.perf_counter() - t0
        want = jax.jit(case.reference)(*args)
        bad = [m for m in map(bf16_mismatch,
                              jax.tree_util.tree_leaves(got),
                              jax.tree_util.tree_leaves(want)) if m]
        results[case.name] = {"ok": not bad, "compile_and_run_sec": dt,
                              "mismatch": bad}
        rep.check(not bad, f"{case.name} ({dt:.1f}s)"
                  + (f": {'; '.join(bad)}" if bad else ""))
    rep.details["kernels"] = results


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="tiny interpret-mode rehearsal of the control flow on the CPU; "
             "proves nothing about the device")
    rehearsal = ap.parse_args(argv).cpu_rehearsal
    rep = Report("[CPU REHEARSAL] " if rehearsal else "")

    # The rehearsal compiles seconds of tiny CPU programs: no cache for it.
    cache_dir = "off (rehearsal)" if rehearsal else configure_compile_cache()
    dev = jax.devices()[0]          # a backend that cannot start raises here
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    rep.say(f"platform {dev.platform}, device_kind {dev.device_kind!r}, "
            f"{device['count']} device(s), jax {jax.__version__}, libtpu "
            f"{importlib.metadata.version('libtpu')}, compile cache "
            f"{cache_dir}")
    if rehearsal:
        if dev.platform == "tpu":
            sys.exit("--cpu-rehearsal is for a machine without a chip; "
                     "this one has a TPU — run the plain command")
    elif dev.platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; jax found platform "
                 f"{dev.platform!r} ({dev.device_kind}). Nothing was run. "
                 f"(--cpu-rehearsal rehearses the control flow on the CPU.)")
    elif dev.device_kind not in TPU_PEAK_TFLOPS:
        sys.exit(f"device kind {dev.device_kind!r} has no entry in "
                 f"profiling/flops_profiler.TPU_PEAK_TFLOPS; known: "
                 f"{sorted(TPU_PEAK_TFLOPS)}")

    model, cfg = make_gpt("tiny" if rehearsal else "gpt2", dropout_rate=0.0)
    # Host copies: each engine places (and may donate) its own.
    params = jax.device_get(model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        {"input_ids": np.zeros((1, 8), np.int32)})["params"])
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    rep.say(f"model: GPT {cfg.num_layers} layers x hidden {cfg.hidden_size}, "
            f"vocab {cfg.vocab_size}, {n_params / 1e6:.1f}M parameters")

    t_start = time.perf_counter()
    rep.run_phase("trainer", trainer_phase, model, cfg, params, rehearsal)
    rep.run_phase("dropless trainer", dropless_trainer_phase, rehearsal)
    rep.run_phase("server", server_phase, model, cfg, params, rehearsal)
    rep.run_phase("kernels", kernels_phase, rehearsal)

    stats = [d.memory_stats() for d in jax.local_devices()]
    peak = max((s or {}).get("peak_bytes_in_use", 0) for s in stats)
    rep.say(f"peak HBM in use (max over devices): "
            + (f"{peak / 2**30:.2f} GiB" if peak else "not reported"))
    rep.say(f"total {time.perf_counter() - t_start:.0f}s after start-up")

    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump({"device": device, "rehearsal": rehearsal,
                   "jax": jax.__version__, "compile_cache": cache_dir,
                   "peak_bytes_in_use": peak, "failures": rep.failures,
                   **rep.details}, f, indent=1)
    if rep.failures:
        rep.say(f"FAILED: {len(rep.failures)} check(s):")
        for what in rep.failures:
            rep.say(f"  - {what}")
        return 1
    # the rehearsal's line carries its tag and flag: never the chip result
    rep.say(json.dumps({"ok": True, **({"rehearsal": True} if rehearsal
                                       else {}), "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
