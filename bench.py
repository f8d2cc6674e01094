"""Headline benchmark — BERT-large ZeRO-2 pretraining throughput per chip.

Mirrors the reference's flagship numbers (BASELINE.md):
- BERT-Large seq-128 pretraining: 272 samples/s on 1x V100 with the fused
  CUDA transformer kernel (docs/_tutorials/bert-pretraining.md:387).
- BERT-Large seq-512: 52 samples/s (same table).
- GPT-2 tokens/sec/chip (BASELINE.json second tracked metric).

The headline metric rides in the single stdout JSON line; the secondary
GPT-2 number, the seq-512 BERT row, achieved TFLOP/s and MFU are extra keys
on the same line (stdout stays exactly one JSON line). Diagnostics print to
stderr.

Methodology: the fused ``engine.train_batch`` path — one XLA dispatch per
optimizer step (micro-batch scan + apply in a single program), steps queued
asynchronously, one scalar loss fetch closing the timed window (the host
clock stops only after the device has finished). Gradient accumulation
(gas=8) amortises the optimizer apply exactly as the reference's BERT
configs do (large effective batches).

This is a device benchmark: every row is a time on the chip, so ``main()``
refuses to run on any platform but ``tpu`` (and on a TPU whose
``device_kind`` has no peak-table entry) — a CPU run never writes a row
under a device metric's name. One process; a section that fails fails
once, and any failed section makes the exit code non-zero.
"""

import json
import os
import sys
import time
import traceback

import jax
import numpy as np

# ONE source of truth for MFU math + per-chip peak TFLOP/s tables
# (telemetry/goodput.py's engine/mfu gauge divides by the same numbers).
from deepspeed_tpu.profiling.flops_profiler import mfu as compute_mfu
from deepspeed_tpu.profiling.flops_profiler import peak_tflops
from deepspeed_tpu.utils.compile_cache import configure_compile_cache

# Partial results land here after EVERY completed section, so a run that
# is killed mid-way (time limit, OOM abort) still leaves the rows that
# finished on disk.
PARTIAL_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_partial.json")

BASELINE_BERT_SEQ128 = 272.0   # samples/s, 1x V100, fused kernels
BASELINE_BERT_SEQ512 = 52.0    # samples/s, 1x V100
# GPT-2 has no single published reference tokens/s in-tree; BASELINE.json
# tracks it as a metric. Use the V100 BERT-large FLOP rate (64 TFLOP/s)
# converted to GPT-2-small tokens as the comparable bar: 64e12 / (6*124e6)
# ~= 86k tokens/s.
BASELINE_GPT2_TOKENS = 86000.0


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def train_flops_per_step(n_params, batch, seq, hidden, layers):
    """Analytic fwd+bwd FLOPs: 6*N per token for the dense path plus the
    attention score/value matmuls (12*S*H per token per layer, fwd+bwd)."""
    tokens = batch * seq
    dense = 6.0 * n_params * tokens
    attn = 12.0 * layers * hidden * seq * tokens
    return dense + attn


def count_params(tree):
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def time_train_batches(engine, batches, steps, warmup, windows=3):
    """Queue `steps` fused steps asynchronously; a scalar loss fetch (a
    host read of the last step's output) closes each window.

    Returns (best, median) of `windows` consecutive windows: the best
    window is the headline (what the reference's published per-GPU
    numbers report too), the median the spread-inclusive view of the
    same run (ADVICE r3: the `vs_baseline` ratios divide a best-case
    window by average-style reference constants)."""
    for _ in range(warmup):
        loss = engine.train_batch(batches)
    _ = float(loss)
    times = []
    for _ in range(max(1, windows)):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine.train_batch(batches)
        _ = float(loss)
        times.append(time.perf_counter() - t0)
    return min(times), float(np.median(times))


def bench_bert(seq, micro_bs, gas, steps, warmup):
    import deepspeed_tpu
    from deepspeed_tpu.models import make_bert

    # No remat: at these batch sizes HBM has headroom and full recompute
    # would pay ~30% extra FLOPs for nothing.
    model, cfg = make_bert("bert-large", dropout_rate=0.0, remat=False,
                           max_seq_len=max(seq, 128))
    rng = np.random.default_rng(0)
    n_chips = max(len(jax.devices()), 1)
    bs = micro_bs * n_chips
    ids = rng.integers(0, cfg.vocab_size, (gas, bs, seq), dtype=np.int32)
    labels = np.where(rng.random((gas, bs, seq)) < 0.15, ids, -100)
    batches = {"input_ids": ids,
               "attention_mask": np.ones((gas, bs, seq), np.int32),
               "labels": labels.astype(np.int32)}
    one = jax.tree_util.tree_map(lambda x: x[0], batches)
    params = model.init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(1)}, one)["params"]
    n_params = count_params(params)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, params=params,
        config={
            "train_micro_batch_size_per_gpu": micro_bs,
            "gradient_accumulation_steps": gas,
            "optimizer": {"type": "Lamb", "params": {"lr": 2e-3}},
            "zero_optimization": {"stage": 2},
            # bf16 accumulator ≡ the reference's fp16 grad buffers; gas=8
            # amortizes the (LAMB-norm-heavy) apply — measured +18% on
            # BERT-128 (2026-07-30, PROFILE.md).
            "data_types": {"grad_accum_dtype": "bfloat16"},
            "bf16": {"enabled": True},
        })
    dt, dt_med = time_train_batches(engine, batches, steps, warmup)
    samples = gas * bs * steps
    sps = samples / dt / n_chips
    flops = train_flops_per_step(n_params, samples, seq,
                                 cfg.hidden_size, cfg.num_layers)
    tflops = flops / dt / 1e12 / n_chips
    return sps, tflops, n_params, samples / dt_med / n_chips, flops, dt


def bench_gpt2(steps, warmup, dropout_rate=0.0):
    import deepspeed_tpu
    from deepspeed_tpu.models import make_gpt

    micro_bs, seq, gas = 16, 512, 8
    model, cfg = make_gpt("gpt2", dropout_rate=dropout_rate, remat=False,
                          max_seq_len=max(seq, 128))
    rng = np.random.default_rng(0)
    n_chips = max(len(jax.devices()), 1)
    bs = micro_bs * n_chips
    batches = {"input_ids": rng.integers(0, cfg.vocab_size, (gas, bs, seq),
                                         dtype=np.int32)}
    one = jax.tree_util.tree_map(lambda x: x[0], batches)
    params = model.init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(1)}, one)["params"]
    n_params = count_params(params)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, params=params,
        config={
            "train_micro_batch_size_per_gpu": micro_bs,
            "gradient_accumulation_steps": gas,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 2},
            "data_types": {"grad_accum_dtype": "bfloat16"},
            "bf16": {"enabled": True},
        })
    dt, dt_med = time_train_batches(engine, batches, steps, warmup)
    tokens = gas * bs * seq * steps
    tokens_per_sec = tokens / dt / n_chips
    flops = train_flops_per_step(n_params, gas * bs * steps, seq,
                                 cfg.hidden_size, cfg.num_layers)
    tflops = flops / dt / 1e12 / n_chips
    return tokens_per_sec, tflops, tokens / dt_med / n_chips, flops, dt


def bench_gpt2_long(steps, warmup, sparse: bool, seq=16384):
    """Long-sequence row (seq 16384): dense flash attention vs config-driven
    BigBird block-sparse — the reference's 10x-longer-sequence story
    (BASELINE.md sparse attention row), driven through the
    `sparse_attention` config block end-to-end. Measured r4 (fwd+bwd
    stacks): bigbird blk-256 at 5.8% density = 3.0x dense flash at 16k,
    1.5x (blk-512) at 4k (tools/probe_sparse_block.py)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import make_gpt

    micro_bs, gas = 1, 4
    model, cfg = make_gpt("gpt2", dropout_rate=0.0, remat=False,
                          max_seq_len=seq)
    rng = np.random.default_rng(0)
    batches = {"input_ids": rng.integers(0, cfg.vocab_size,
                                         (gas, micro_bs, seq),
                                         dtype=np.int32)}
    one = jax.tree_util.tree_map(lambda x: x[0], batches)
    params = model.init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(1)}, one)["params"]
    config = {
        "train_micro_batch_size_per_gpu": micro_bs,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 2},
        "data_types": {"grad_accum_dtype": "bfloat16"},
        "bf16": {"enabled": True},
    }
    if sparse:
        config["sparse_attention"] = {
            "mode": "bigbird", "block": 256, "num_random_blocks": 1,
            "num_sliding_window_blocks": 3, "num_global_blocks": 1,
            "attention": "unidirectional",
        }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, params=params, config=config)
    dt, _ = time_train_batches(engine, batches, steps, warmup, windows=2)
    tokens = gas * micro_bs * seq * steps
    return tokens / dt


def bench_inference(batch, new_tokens=128, prompt=128, windows=3):
    """Generation throughput (tokens/s) through the inference engine's
    jitted prefill+decode: the reference stakes latency claims on its
    inference kernels (docs/_tutorials/inference-tutorial.md); this is the
    capability-parity evidence row (KV cache, one dispatch per call)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import make_gpt

    model, cfg = make_gpt("gpt2", dropout_rate=0.0,
                          max_seq_len=prompt + new_tokens)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, prompt), dtype=np.int32)
    params = model.init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(1)},
                        {"input_ids": ids[:1]})["params"]
    eng = deepspeed_tpu.init_inference(model, params=params)
    out = eng.generate(ids, max_new_tokens=new_tokens)   # compile
    _ = np.asarray(out[0, -1])
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        out = eng.generate(ids, max_new_tokens=new_tokens)
        _ = np.asarray(out[0, -1])   # fence
        best = min(best, time.perf_counter() - t0)
    return batch * new_tokens / best


# Serving-section config (bench rows must stay attributable: this block
# is recorded verbatim in the environment block). Tiny GPT family: the
# section measures the serving machinery (continuous batching, paged KV,
# bucketed prefill), not model FLOPs. (ROADMAP R0 replaces it with a
# real-width cell.)
SERVING_BENCH_CFG = {
    "max_batch_size": 4,
    "kv_block_size": 16,
    "kv_num_blocks": 128,
    "int8_kv_cache": False,
    "max_model_len": 112,
}

# Request-observatory config for the serving section
# (telemetry/requests.py): sources the TPOT/e2e percentile rows from the
# real per-request accounting surface. Recorded in the environment block
# like SERVING_BENCH_CFG so the latency rows stay attributable.
SERVING_REQUESTS_CFG = {
    "enabled": True,
    "window_sec": 10.0,
}

# Resilience config for the serving overload A/B row
# (serving/resilience.py; docs/SERVING.md "Serving under failure").
# Depth-bounded shedding only: deterministic on a cold engine, so the
# A/B row is reproducible. Recorded in the environment block.
SERVING_RESILIENCE_CFG = {
    "enabled": True,
    "max_queue_depth": 6,
}


def bench_serving(n_requests=12):
    """Offline serving throughput + latency SLOs through the
    continuous-batching engine (serving/engine.py, docs/SERVING.md): a
    fixed mixed trace of prompt/output lengths submitted up front,
    measured to drain. TTFT comes from the engine's histogram; TPOT/e2e
    come from the request observatory (telemetry/requests.py) enabled
    per SERVING_REQUESTS_CFG. Returns (tokens/s, ttft p50 ms,
    ttft p99 ms, mean occupancy, tpot p50 ms, tpot p99 ms, e2e p99
    ms)."""
    import tempfile

    import deepspeed_tpu
    from deepspeed_tpu.models import make_gpt

    model, cfg = make_gpt("tiny", dropout_rate=0.0, max_seq_len=128)
    rng = np.random.default_rng(0)
    params = model.init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(1)},
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    # memory-sink metrics: the latency percentiles come from the real
    # telemetry surface; the request records land in a throwaway dir.
    with tempfile.TemporaryDirectory() as td:
        srv = deepspeed_tpu.init_serving(
            model, params=params,
            config={"serving": SERVING_BENCH_CFG,
                    "telemetry": {"enabled": True, "dir": td,
                                  "metrics": {"sinks": ["memory"]},
                                  "trace": {"enabled": False},
                                  "requests": dict(SERVING_REQUESTS_CFG)}})
        prompts = [rng.integers(0, cfg.vocab_size,
                                (int(rng.integers(6, 48)),)).tolist()
                   for _ in range(n_requests)]
        outs = [int(rng.integers(8, 48)) for _ in range(n_requests)]
        # warmup: compile the decode program AND every prefill bucket the
        # trace will hit off the clock (one representative prompt per
        # bucket), so the timed window measures the serving machinery,
        # not XLA compile latency
        seen = set()
        for p in prompts:
            b = srv._bucket_of(len(p))
            if b not in seen:
                seen.add(b)
                srv.submit(p, 2)
        srv.run_until_complete()
        srv.results.clear()
        # drop warmup observations: the compile-latency TTFTs/TPOTs and
        # warmup decode steps must not leak into the reported
        # percentiles/occupancy
        reg = srv.telemetry.registry
        for tag in ("serving/ttft_ms", "requests/tpot_ms",
                    "requests/e2e_ms", "requests/queue_wait_ms"):
            reg.histogram(tag).reset()
        srv.stats.update(decode_steps=0, occupancy_sum=0.0,
                         slot_assignments={})
        t0 = time.perf_counter()
        for p, n in zip(prompts, outs):
            srv.submit(p, n)
        srv.run_until_complete()
        dt = time.perf_counter() - t0
        hist = reg.histogram("serving/ttft_ms")
        tpot = reg.histogram("requests/tpot_ms")
        e2e = reg.histogram("requests/e2e_ms")
        out = (sum(outs) / dt, hist.percentile(50), hist.percentile(99),
               srv.mean_occupancy, tpot.percentile(50),
               tpot.percentile(99), e2e.percentile(99))
        srv.close()
    return out


def bench_serving_fastpath():
    """Decode fast-path A/B rows (docs/SERVING.md "Decode fast path"):
    (1) mean decode-step wall ms on the same mixed trace with the gather
    program vs the paged decode-attention kernel (outputs are asserted
    token-identical); (2) cold vs warm-prompt-head TTFT under the prefix
    cache; (3) speculative-decode accept rate and effective tokens per
    verify step.
    Returns a dict of row values."""
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import make_gpt

    # fp32 like tests/test_serving.py: the token-identity asserts compare
    # numerically-different-but-equivalent paths (gather vs kernel,
    # k+1-query verify vs 1-query decode) whose bf16 argmax tie-flips
    # are noise, not bugs.
    # head_dim 128 (2 heads x 128): the smallest geometry the compiled
    # paged kernel tiles (paged_decode_ok) — tiny's head_dim 16 is
    # rejected at engine construction on a TPU.
    model, cfg = make_gpt("tiny", dropout_rate=0.0, max_seq_len=128,
                          hidden_size=256, num_heads=2, dtype=jnp.float32)
    rng = np.random.default_rng(1)
    params = model.init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(1)},
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]

    def build(**overrides):
        return deepspeed_tpu.init_serving(
            model, params=params, dtype=jnp.float32,
            config={"serving": {**SERVING_BENCH_CFG, **overrides},
                    "telemetry": {"enabled": True, "dir": ".",
                                  "metrics": {"sinks": ["memory"]},
                                  "trace": {"enabled": False}}})

    prompts = [rng.integers(0, cfg.vocab_size,
                            (int(rng.integers(6, 48)),)).tolist()
               for _ in range(8)]
    outs = [int(rng.integers(16, 40)) for _ in range(8)]

    def run(srv):
        # warmup (compiles off the clock), then the timed trace
        for p in prompts:
            srv.submit(p, 2)
        srv.run_until_complete()
        srv.results.clear()
        srv._decode_tokens, srv._decode_sec = 0, 0.0
        # spec counters too: warmup runs at max_new_tokens=2 truncate
        # accepts and would drag the reported accept rate down
        srv.stats.update(decode_steps=0, spec_rounds=0, spec_proposed=0,
                         spec_accepted=0, spec_new_tokens=0)
        for p, n in zip(prompts, outs):
            srv.submit(p, n)
        res = srv.run_until_complete()
        toks = [res[r]["tokens"] for r in sorted(res)]
        ms = 1e3 * srv._decode_sec / max(1, srv.stats["decode_steps"])
        return toks, ms, srv

    rows = {}
    toks_off, ms_off, _ = run(build())
    toks_on, ms_on, _ = run(build(decode_attention="kernel"))
    assert toks_on == toks_off, "kernel decode diverged from gather"
    rows["decode_step_gather_ms"] = round(ms_off, 3)
    rows["decode_step_kernel_ms"] = round(ms_on, 3)

    # cold vs warm-head TTFT: one cold prefill caches a 96-token head,
    # every later request adopts it and prefills only its 4-token tail.
    # Requests are submitted one at a time so TTFT measures prefill, not
    # queue wait behind another row's decode.
    wmodel, wcfg = make_gpt("tiny", dropout_rate=0.0, max_seq_len=256,
                            hidden_size=128, num_layers=3, num_heads=4,
                            dtype=jnp.float32)
    wparams = wmodel.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    srv = deepspeed_tpu.init_serving(
        wmodel, params=wparams, dtype=jnp.float32,
        config={"serving": {**SERVING_BENCH_CFG, "max_model_len": 240,
                            "prefix_cache": True},
                "telemetry": {"enabled": True, "dir": ".",
                              "metrics": {"sinks": ["memory"]},
                              "trace": {"enabled": False}}})
    head = rng.integers(0, wcfg.vocab_size, (96,)).tolist()
    warm = [head + rng.integers(0, wcfg.vocab_size, (4,)).tolist()
            for _ in range(7)]
    hist = srv.telemetry.registry.histogram("serving/ttft_ms")
    srv.submit(warm[0], 2)                    # bucket warmup (compile)
    srv.run_until_complete()
    srv.submit(warm[1], 2)                    # tail-program warmup
    srv.run_until_complete()
    # cold: full prefill, re-measured with the cache cleared between
    # runs (median of 3 — a single observation is noise-prone);
    # the last run leaves the head registered for the warm half
    hist.reset()
    for _ in range(3):
        srv.prefix_cache.clear()
        srv.submit(warm[0], 4)
        srv.run_until_complete()
    rows["cold_ttft_ms"] = round(hist.percentile(50), 3)
    hist.reset()
    for p in warm[2:]:                        # warm: tail prefill only
        srv.submit(p, 4)
        srv.run_until_complete()
    assert srv.prefix_cache.hits >= len(warm) - 2
    rows["warm_ttft_p50_ms"] = round(hist.percentile(50), 3)

    # speculative decoding: accept rate + effective tokens per verify
    toks_spec, _ms, srv = run(build(
        speculative={"enabled": True, "k": 4}))
    assert toks_spec == toks_off, "speculative decode diverged from greedy"
    st = srv.stats
    rows["spec_accept_rate"] = round(
        st["spec_accepted"] / max(1, st["spec_proposed"]), 4)
    rows["spec_tokens_per_step"] = round(
        st["spec_new_tokens"] / max(1, st["spec_rounds"]), 3)
    return rows


def bench_serving_overload(n_requests=24):
    """Serving overload A/B (docs/SERVING.md "Serving under failure"):
    the same burst trace — offered load well past the 4-slot engine's
    capacity — with shedding off (everything queues; tail TTFT collapses
    under queue wait) vs the admission controller on per
    SERVING_RESILIENCE_CFG (overflow sheds at submit; admitted requests
    keep their TTFT). Returns shed fraction + admitted TTFT p99 rows for
    both arms."""
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import make_gpt

    model, cfg = make_gpt("tiny", dropout_rate=0.0, max_seq_len=128,
                          dtype=jnp.float32)
    rng = np.random.default_rng(3)
    params = model.init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(1)},
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    prompts = [rng.integers(0, cfg.vocab_size,
                            (int(rng.integers(6, 48)),)).tolist()
               for _ in range(n_requests)]
    outs = [int(rng.integers(16, 40)) for _ in range(n_requests)]

    def run(resilient):
        scfg = dict(SERVING_BENCH_CFG)
        if resilient:
            scfg["resilience"] = dict(SERVING_RESILIENCE_CFG)
        srv = deepspeed_tpu.init_serving(
            model, params=params, dtype=jnp.float32,
            config={"serving": scfg,
                    "telemetry": {"enabled": True, "dir": ".",
                                  "metrics": {"sinks": ["memory"]},
                                  "trace": {"enabled": False}}})
        # warmup: compile every prefill bucket + decode off the clock
        seen = set()
        for p in prompts:
            b = srv._bucket_of(len(p))
            if b not in seen:
                seen.add(b)
                srv.submit(p, 2)
        srv.run_until_complete()
        srv.results.clear()
        hist = srv.telemetry.registry.histogram("serving/ttft_ms")
        hist.reset()
        for p, n in zip(prompts, outs):      # the burst: all at once
            srv.submit(p, n)
        res = srv.run_until_complete()
        shed = sum(1 for r in res.values() if r.get("status") == "shed")
        ttft_p99 = hist.percentile(99)        # admitted requests only:
        srv.close()                           # shed rows never observe
        return shed / len(res), ttft_p99

    shed_off, ttft_off = run(resilient=False)
    shed_on, ttft_on = run(resilient=True)
    assert shed_off == 0.0, "shedding happened with resilience off"
    return {
        "overload_shed_frac_off": round(shed_off, 4),
        "overload_shed_frac_on": round(shed_on, 4),
        "overload_admitted_ttft_p99_off_ms": round(ttft_off, 2),
        "overload_admitted_ttft_p99_on_ms": round(ttft_on, 2),
    }


def bench_serving_chunked():
    """Chunked-prefill admission A/B (docs/SERVING.md "Chunked prefill
    admission"): the same bursty trace — a burst of prompts whose lengths
    span several prefill buckets — served by the bucketed per-bucket
    prefill programs vs the single ragged mixed program. Cold engines on
    both sides: the bucketed path pays one compile per bucket it meets
    INSIDE the burst's TTFT window, the chunked path compiles its one
    mixed program once, which is the headline latency win on any
    backend. Outputs are asserted token-identical (same greedy trace).
    Rows: mean decode/mixed step wall ms + TTFT p99 per mode."""
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import make_gpt

    # head_dim 128: the geometry the compiled ragged kernel tiles (see
    # bench_serving_fastpath).
    model, cfg = make_gpt("tiny", dropout_rate=0.0, max_seq_len=128,
                          hidden_size=256, num_heads=2, dtype=jnp.float32)
    rng = np.random.default_rng(5)
    params = model.init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(1)},
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    # Bursty: lengths span >= 3 prefill buckets, all submitted at once.
    lens = [10, 20, 40, 70, 12, 44, 22, 68]
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).tolist()
               for n in lens]
    outs = [int(rng.integers(8, 20)) for _ in lens]

    def run(chunked):
        srv = deepspeed_tpu.init_serving(
            model, params=params, dtype=jnp.float32,
            config={"serving": {
                        **SERVING_BENCH_CFG,
                        "chunked_prefill": {"enabled": chunked,
                                            "token_budget": 32}},
                    "telemetry": {"enabled": True, "dir": ".",
                                  "metrics": {"sinks": ["memory"]},
                                  "trace": {"enabled": False}}})
        for p, n in zip(prompts, outs):
            srv.submit(p, n)
        res = srv.run_until_complete()
        toks = [res[r]["tokens"] for r in sorted(res)]
        ms = 1e3 * srv._decode_sec / max(1, srv.stats["decode_steps"])
        p99 = srv.telemetry.registry.histogram(
            "serving/ttft_ms").percentile(99)
        return toks, ms, p99

    toks_b, ms_b, p99_b = run(False)
    toks_c, ms_c, p99_c = run(True)
    assert toks_c == toks_b, "chunked admission diverged from bucketed"
    return {
        "mixed_step_bucketed_ms": round(ms_b, 3),
        "mixed_step_chunked_ms": round(ms_c, 3),
        "ttft_p99_bucketed_ms": round(p99_b, 2),
        "ttft_p99_chunked_ms": round(p99_c, 2),
    }


def bench_fused_optimizer():
    """Fused blockwise Adam A/B (docs/PERFORMANCE.md "Kernel tier round
    2"): jitted XLA elementwise update chain vs the single-pass Pallas
    kernel over the same ~1M-element parameter tree, single device.
    Trajectories are asserted to match (the kernel bit-matches the op
    order, tests/test_fused_update.py). Rows: mean optimizer step wall
    ms per mode."""
    import jax.numpy as jnp

    from deepspeed_tpu.ops.adam.fused_adam import FusedAdam
    from deepspeed_tpu.ops.adam.fused_update import fused_adam_apply

    rng = np.random.default_rng(3)
    params = {
        "dense": jnp.asarray(rng.standard_normal((1024, 768)), jnp.float32),
        "embed": jnp.asarray(rng.standard_normal((512, 512)), jnp.float32),
        "bias": jnp.asarray(rng.standard_normal((768,)), jnp.float32),
    }
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.standard_normal(p.shape), p.dtype) * 0.01,
        params)
    opt = FusedAdam(lr=1e-3, weight_decay=0.01, adamw_mode=True)
    state = opt.init(params)

    xla_step = jax.jit(lambda g, s, p: opt.update(g, s, p, lr=1e-3))
    fused_step = jax.jit(
        lambda g, s, p: fused_adam_apply(opt, g, s, p, lr=1e-3))

    def time_step(fn):
        p, s = params, state
        p, s = fn(grads, s, p)                    # compile off the clock
        jax.block_until_ready(p)
        t0 = time.time()
        reps = 5
        for _ in range(reps):
            p, s = fn(grads, s, p)
        jax.block_until_ready(p)
        return 1e3 * (time.time() - t0) / reps, p

    ms_xla, p_xla = time_step(xla_step)
    ms_fused, p_fused = time_step(fused_step)
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree_util.tree_leaves(p_xla),
                              jax.tree_util.tree_leaves(p_fused)))
    assert err < 1e-5, f"fused update trajectory diverged ({err})"
    return {
        "optimizer_step_xla_ms": round(ms_xla, 3),
        "optimizer_step_fused_ms": round(ms_fused, 3),
    }


def _section_rows(result, name, **rows):
    """Record one section's metric rows under ``result["sections"]`` — the
    schema ``tools/bench_gate.py`` compares against the committed
    baseline (the flat top-level keys stay for the driver's one-line
    record; this block is the gate's contract)."""
    result.setdefault("sections", {})[name] = {
        k: v for k, v in rows.items() if v is not None}


def _flush_partial(result):
    try:
        tmp = PARTIAL_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, PARTIAL_PATH)
    except OSError as e:  # a full disk must not kill the bench itself
        log(f"[bench] WARNING: partial flush failed: {e}")


def _record_headroom(name, result):
    """Headroom (tightest device's bytes_limit − peak,
    telemetry/memory.py) recorded AFTER each section. The peak is the
    process-lifetime high-water mark (jax never resets it), so each
    value is the margin left after everything run SO FAR — monotone
    non-increasing across sections; the last section's value is the
    run's overall minimum margin."""
    from deepspeed_tpu.telemetry.memory import min_headroom_bytes
    result.setdefault("peak_headroom_bytes", {})[name] = min_headroom_bytes()


def run_section(name, fn, result):
    """Run one bench section, once. A section that raises records its
    error and the bench moves on to the next (the other sections' rows
    are still evidence) — but it is a failed section: ``main`` exits
    non-zero when any section returned False."""
    try:
        fn()
        _record_headroom(name, result)
    except Exception as e:  # noqa: BLE001 — boundary: isolate every section
        log(f"[bench] section {name!r} FAILED: {type(e).__name__}: {e}")
        log(traceback.format_exc())
        result.setdefault("errors", []).append(
            f"{name}: {type(e).__name__}: {e}")
        return False
    finally:
        _flush_partial(result)
    return True


def main():
    cache_dir = configure_compile_cache()
    dev = jax.devices()[0]      # a backend that cannot start raises here
    platform = dev.platform
    if platform != "tpu":
        # No row, no JSON line: a CPU timing is not a device metric.
        sys.exit(f"bench.py measures the TPU; jax found platform "
                 f"{platform!r} ({dev.device_kind}). Nothing was measured.")
    peak = peak_tflops(dev.device_kind, dtype="bfloat16")
    if peak is None:
        sys.exit(f"bench.py: device kind {dev.device_kind!r} has no entry "
                 f"in profiling/flops_profiler.TPU_PEAK_TFLOPS — add its "
                 f"published peak before benchmarking on it.")
    n_chips_all = len(jax.devices())
    log(f"[bench] platform {platform}, {dev.device_kind} x {n_chips_all}, "
        f"jax {jax.__version__}, compile cache {cache_dir}")
    # Evict any stale partial from a previous run: a run that dies before
    # its first section must never leave an older BENCH_partial.json
    # masquerading as this run's record.
    result = {
        "metric": "BERT-large seq128 ZeRO-2 pretrain throughput (tpu)",
        "value": None,
        "unit": "samples/sec/chip",
        "vs_baseline": None,
    }
    _flush_partial(result)
    # Environment block: the conditions the rows were measured under, so
    # numbers stay comparable across PRs. telemetry is explicitly "off" —
    # none of the bench configs enable the telemetry block, so no sync'd
    # spans or per-step gauges perturb the timed windows; a future PR that
    # benches with telemetry on must say so here. goodput rides telemetry
    # (telemetry/goodput.py), so it is off too — its accountant is pure
    # host clock reads, but the env block records the whole config anyway.
    result["environment"] = {
        "platform": platform,
        "device_kind": dev.device_kind,
        "devices": n_chips_all,
        "jax": jax.__version__,
        "telemetry": "off",
        "goodput": "off",
        # Fleet telemetry (telemetry/fleet.py) would add a per-flush
        # collective + host fetch; the timed windows run without it, and
        # a future fleet-on BENCH round must record its fleet block here
        # so rows stay attributable.
        "fleet": "off",
        # Device-time observatory (telemetry/devicetime.py) off: no
        # scheduled jax.profiler captures perturb the timed windows; a
        # future BENCH round capturing mid-bench must record its
        # devicetime block here so rows stay attributable.
        "devicetime": "off",
        # Memory observatory (telemetry/memory.py) off: no per-step
        # headroom gauges and no attribution AOT compile in the timed
        # windows. Per-round peak headroom is still recorded under
        # "peak_headroom_bytes" (a free post-section memory_stats read)
        # so capacity regressions show up next to the throughput rows.
        "memory": "off",
        # Numerics observatory (telemetry/numerics.py) off: the in-
        # program per-group stat reductions would ride inside the timed
        # step programs; a future BENCH round measuring with numerics on
        # must record its block here so rows stay attributable.
        "numerics": "off",
        # Live elasticity (resilience/elastic.py) off: no SIGTERM handler
        # and no step-boundary coordinator checks in the timed windows
        # (the contract says off is free — bit-identical lowered step —
        # but the env block records the whole config anyway).
        "elasticity": "off",
        "peak_tflops_per_chip": peak,
        # Gradient-sync strategy the rows were measured under
        # (comm/grad_sync.py): none of the training-section configs set
        # a comm block, so the implicit full-precision path is timed —
        # overlap_grad_sync included, since overlap only exists inside
        # the hierarchical strategy. The comm_overlap section below
        # measures the overlapped schedule explicitly and records its
        # own config in its rows. A future PR benching the training
        # sections with hierarchical sync on must record its comm block
        # here so BENCH_*.json rows stay attributable.
        "comm": {"hierarchical": "off", "overlap_grad_sync": "off"},
        # ZeRO++ weight path (zero_optimization.zeropp): the training
        # sections above run with the block OFF (bit-identical implicit
        # param path); the zeropp A/B section below measures the
        # explicit quantized weight gather and records its own config
        # in its rows. A future PR benching the training sections with
        # qwZ/hpZ on must record its zeropp block here so BENCH_*.json
        # rows stay attributable.
        "zeropp": {"quantized_weights": "off", "hpz": "off"},
        # Autotuning (autotuning/; docs/PERFORMANCE.md "Autotuning") off:
        # every training section above times the config it declares — no
        # startup search swaps knobs under a timed window. The autotune
        # A/B section below runs the search explicitly and records the
        # adopted candidate in its own rows, so a tuned baseline adopted
        # via tools/bench_gate.py --update-baseline stays attributable.
        "autotuning": "off",
        # MoE (moe/; docs/MOE.md) off on every training section above:
        # no `moe` config block, so the lowered steps are bit-identical
        # to the pre-MoE programs (the zero-overhead contract,
        # tests/test_moe.py). The moe_gpt A/B section below is the only
        # MoE workload and records its dispatch mode in its own rows.
        "moe": "off",
        # Serving-section config (docs/SERVING.md): the continuous-
        # batching rows below were measured under exactly this block.
        # Its memory-sink telemetry is scoped to the serving engine and
        # never touches the training sections' timed windows.
        "serving": dict(SERVING_BENCH_CFG),
        # Request observatory (telemetry/requests.py) behind the serving
        # section's tpot_p50_ms/tpot_p99_ms/e2e_p99_ms rows.
        "requests": dict(SERVING_REQUESTS_CFG),
        # Serving resilience (serving/resilience.py) behind the overload
        # A/B rows; every other serving row runs with resilience off.
        "serving_resilience": dict(SERVING_RESILIENCE_CFG),
        # Kernel tier round 2 (docs/PERFORMANCE.md): both kernels OFF on
        # every section except their own A/B rows — chunked admission in
        # the serving section's chunked_* rows, the fused Adam pass in
        # the fused_optimizer section. Off is the zero-overhead default
        # (bit-identical lowered programs, tests/test_chunked_prefill.py
        # / test_fused_update.py).
        "chunked_prefill": "off",
        "fused_update": "off",
    }

    steps, warmup = 10, 2

    def sec_bert128():
        t0 = time.time()
        sps128, tf128, n_params, sps128_med, flops, dt = bench_bert(
            seq=128, micro_bs=32, gas=8, steps=steps, warmup=warmup)
        mfu128 = compute_mfu(flops, dt, n_chips=n_chips_all,
                             peak_tflops_per_chip=peak)
        log(f"[bench] BERT-large seq128: {sps128:.1f} samples/s/chip, "
            f"{tf128:.1f} TFLOP/s, MFU {mfu128:.1%} "
            f"({n_params / 1e6:.0f}M params, "
            f"setup+run {time.time() - t0:.0f}s)")
        result["value"] = round(sps128, 2)
        result["vs_baseline"] = round(sps128 / BASELINE_BERT_SEQ128, 4)
        result["tflops"] = round(tf128, 1)
        result["mfu"] = round(mfu128, 4)
        # median-of-windows companion (ADVICE r3): drift-inclusive view of
        # the same run; `value`/`vs_baseline` stay best-of-windows.
        result["value_median_window"] = round(sps128_med, 2)
        _section_rows(result, "bert128", samples_per_sec=result["value"],
                      tflops=result["tflops"], mfu=result["mfu"])

    def sec_bert512():
        t0 = time.time()
        sps512, tf512, _, sps512_med, flops, dt = bench_bert(
            seq=512, micro_bs=8, gas=8, steps=steps, warmup=warmup)
        mfu512 = compute_mfu(flops, dt, n_chips=n_chips_all,
                             peak_tflops_per_chip=peak)
        log(f"[bench] BERT-large seq512: {sps512:.1f} samples/s/chip, "
            f"{tf512:.1f} TFLOP/s, MFU {mfu512:.1%} "
            f"({time.time() - t0:.0f}s)")
        result["bert_seq512_samples_per_sec"] = round(sps512, 2)
        result["bert_seq512_vs_baseline"] = round(
            sps512 / BASELINE_BERT_SEQ512, 4)
        result["bert_seq512_median_window"] = round(sps512_med, 2)
        _section_rows(result, "bert512",
                      samples_per_sec=result["bert_seq512_samples_per_sec"],
                      mfu=round(mfu512, 4))

    def sec_gpt2():
        t0 = time.time()
        gpt2_tps, gpt2_tf, gpt2_tps_med, flops, dt = bench_gpt2(
            steps, warmup)
        gpt2_mfu = compute_mfu(flops, dt, n_chips=n_chips_all,
                               peak_tflops_per_chip=peak)
        log(f"[bench] GPT-2 seq512: {gpt2_tps:.0f} tokens/s/chip, "
            f"{gpt2_tf:.1f} TFLOP/s, MFU {gpt2_mfu:.1%} "
            f"({time.time() - t0:.0f}s)")
        result["gpt2_tokens_per_sec"] = round(gpt2_tps, 0)
        result["gpt2_vs_baseline"] = round(gpt2_tps / BASELINE_GPT2_TOKENS, 4)
        result["gpt2_median_window"] = round(gpt2_tps_med, 0)
        result["gpt2_mfu"] = round(gpt2_mfu, 4)
        _section_rows(result, "gpt2",
                      tokens_per_sec=result["gpt2_tokens_per_sec"],
                      mfu=result["gpt2_mfu"])

    def sec_gpt2_dropout():
        # Dropout-on variant: real pretraining configs keep the flash
        # path via in-kernel dropout.
        t0 = time.time()
        gpt2_do_tps, gpt2_do_tf, _, flops, dt = bench_gpt2(
            steps, warmup, dropout_rate=0.1)
        do_mfu = compute_mfu(flops, dt, n_chips=n_chips_all,
                             peak_tflops_per_chip=peak)
        log(f"[bench] GPT-2 seq512 dropout=0.1: {gpt2_do_tps:.0f} "
            f"tokens/s/chip, {gpt2_do_tf:.1f} TFLOP/s, MFU "
            f"{do_mfu:.1%} ({time.time() - t0:.0f}s)")
        result["gpt2_dropout_tokens_per_sec"] = round(gpt2_do_tps, 0)
        result["gpt2_dropout_mfu"] = round(do_mfu, 4)
        _section_rows(result, "gpt2_dropout",
                      tokens_per_sec=result["gpt2_dropout_tokens_per_sec"],
                      mfu=result["gpt2_dropout_mfu"])

    def sec_long():
        t0 = time.time()
        long_dense = bench_gpt2_long(steps=4, warmup=1, sparse=False)
        result["gpt2_seq16k_dense_tokens_per_sec"] = round(long_dense, 0)
        _flush_partial(result)
        long_sparse = bench_gpt2_long(steps=4, warmup=1, sparse=True)
        log(f"[bench] GPT-2 seq16384: dense {long_dense:.0f} tok/s, "
            f"bigbird {long_sparse:.0f} tok/s "
            f"({long_sparse / long_dense:.2f}x, {time.time() - t0:.0f}s)")
        result["gpt2_seq16k_bigbird_tokens_per_sec"] = round(long_sparse, 0)
        result["gpt2_seq16k_sparse_speedup"] = round(
            long_sparse / long_dense, 3)
        _section_rows(
            result, "long16k",
            dense_tokens_per_sec=result["gpt2_seq16k_dense_tokens_per_sec"],
            bigbird_tokens_per_sec=result[
                "gpt2_seq16k_bigbird_tokens_per_sec"],
            sparse_speedup=result["gpt2_seq16k_sparse_speedup"])

    def sec_inference():
        t0 = time.time()
        tps1 = bench_inference(batch=1)
        result["gpt2_generate_b1_tokens_per_sec"] = round(tps1, 1)
        _flush_partial(result)
        tps8 = bench_inference(batch=8)
        log(f"[bench] GPT-2 generate (KV cache, prompt 128 + 128 new): "
            f"b1 {tps1:.1f} tok/s, b8 {tps8:.1f} tok/s "
            f"({time.time() - t0:.0f}s)")
        result["gpt2_generate_b8_tokens_per_sec"] = round(tps8, 1)
        _section_rows(
            result, "inference",
            b1_tokens_per_sec=result["gpt2_generate_b1_tokens_per_sec"],
            b8_tokens_per_sec=result["gpt2_generate_b8_tokens_per_sec"])

    def sec_serving():
        # Continuous-batching serving row (tiny GPT): the serving
        # machinery's offline throughput + TTFT SLO percentiles.
        t0 = time.time()
        tps, p50, p99, occ, tpot50, tpot99, e2e99 = bench_serving()
        log(f"[bench] serving (tiny GPT, {SERVING_BENCH_CFG['max_batch_size']}"
            f" slots): {tps:.1f} tok/s, TTFT p50 {p50:.1f} ms / p99 "
            f"{p99:.1f} ms, TPOT p50 {tpot50:.1f} ms / p99 {tpot99:.1f} ms, "
            f"e2e p99 {e2e99:.1f} ms, occupancy {occ:.1%} "
            f"({time.time() - t0:.0f}s)")
        result["serving_tokens_per_sec"] = round(tps, 1)
        result["serving_ttft_p50_ms"] = round(p50, 2)
        result["serving_ttft_p99_ms"] = round(p99, 2)
        result["serving_tpot_p50_ms"] = round(tpot50, 3)
        result["serving_tpot_p99_ms"] = round(tpot99, 3)
        result["serving_e2e_p99_ms"] = round(e2e99, 2)
        result["serving_mean_occupancy"] = round(occ, 4)
        # decode fast path A/B (docs/SERVING.md): gather-vs-kernel decode
        # step, cold-vs-warm-head TTFT, speculative accept evidence — all
        # on the same trace, token-identity asserted inside.
        t0 = time.time()
        fp = bench_serving_fastpath()
        log(f"[bench] serving fast path: decode gather "
            f"{fp['decode_step_gather_ms']:.2f} ms vs kernel "
            f"{fp['decode_step_kernel_ms']:.2f} ms; TTFT cold "
            f"{fp['cold_ttft_ms']:.1f} ms vs warm p50 "
            f"{fp['warm_ttft_p50_ms']:.1f} ms; spec accept "
            f"{fp['spec_accept_rate']:.1%}, "
            f"{fp['spec_tokens_per_step']:.2f} tok/verify "
            f"({time.time() - t0:.0f}s)")
        for key, val in fp.items():
            result[f"serving_{key}"] = val
        # overload A/B (docs/SERVING.md "Serving under failure"):
        # offered load > capacity, shedding off vs on.
        t0 = time.time()
        ov = bench_serving_overload()
        log(f"[bench] serving overload: shed "
            f"{ov['overload_shed_frac_off']:.0%} off vs "
            f"{ov['overload_shed_frac_on']:.0%} on; admitted TTFT p99 "
            f"{ov['overload_admitted_ttft_p99_off_ms']:.1f} ms off vs "
            f"{ov['overload_admitted_ttft_p99_on_ms']:.1f} ms on "
            f"({time.time() - t0:.0f}s)")
        for key, val in ov.items():
            result[f"serving_{key}"] = val
        # chunked-prefill admission A/B (docs/SERVING.md "Chunked
        # prefill admission"): bursty multi-bucket trace, bucketed
        # per-bucket programs vs the one ragged mixed program —
        # token-identity asserted inside.
        t0 = time.time()
        ck = bench_serving_chunked()
        log(f"[bench] serving chunked prefill: step "
            f"{ck['mixed_step_bucketed_ms']:.2f} ms bucketed vs "
            f"{ck['mixed_step_chunked_ms']:.2f} ms chunked; TTFT p99 "
            f"{ck['ttft_p99_bucketed_ms']:.1f} ms vs "
            f"{ck['ttft_p99_chunked_ms']:.1f} ms "
            f"({time.time() - t0:.0f}s)")
        for key, val in ck.items():
            result[f"serving_{key}"] = val
        # tpot/e2e rows are `*_ms`, so bench_gate treats them as
        # lower-is-better automatically (latency regresses upward).
        _section_rows(result, "serving",
                      tokens_per_sec=result["serving_tokens_per_sec"],
                      ttft_p50_ms=result["serving_ttft_p50_ms"],
                      ttft_p99_ms=result["serving_ttft_p99_ms"],
                      tpot_p50_ms=result["serving_tpot_p50_ms"],
                      tpot_p99_ms=result["serving_tpot_p99_ms"],
                      e2e_p99_ms=result["serving_e2e_p99_ms"],
                      mean_occupancy=result["serving_mean_occupancy"],
                      **fp, **ov, **ck)

    def gpt_ab_times(gas, make_config):
        # Shared 2-slice tiny-GPT A/B harness for the comm_overlap and
        # zeropp sections: build the model once, then time an off/on
        # engine pair — make_config(variant) supplies each variant's
        # config block on top of the common batch/optimizer base.
        import deepspeed_tpu
        from deepspeed_tpu.models import make_gpt
        from deepspeed_tpu.parallel.mesh import build_mesh

        import jax.numpy as jnp

        # micro_bs 1 per chip: the global microbatch is the chip count
        # (put_batch shards over dcn x data).
        seq, bs = 64, n_chips_all
        model, cfg = make_gpt(
            "tiny", dropout_rate=0.0, dtype=jnp.bfloat16,
            max_seq_len=max(seq, 128))
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, (gas, bs, seq),
                           dtype=np.int32)
        params = model.init({"params": jax.random.PRNGKey(0),
                             "dropout": jax.random.PRNGKey(1)},
                            {"input_ids": ids[0]})["params"]
        times = {}
        for variant in ("off", "on"):
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=model, params=params, mesh=build_mesh(slices=2),
                config={
                    "train_micro_batch_size_per_gpu": 1,
                    "gradient_accumulation_steps": gas,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                    **make_config(variant),
                })
            dt, _ = time_train_batches(engine, {"input_ids": ids},
                                       max(steps, 2), warmup, windows=2)
            times[variant] = dt / max(steps, 2)
            del engine
        return times

    def sec_comm_overlap():
        # Overlapped gradient sync A/B (docs/PERFORMANCE.md "Overlapped
        # gradient sync"): tiny GPT on a 2-slice mesh, hierarchical int8
        # sync with overlap off vs on. step-time rows are *_ms so the
        # gate treats upward drift as regression.
        t0 = time.time()
        times = gpt_ab_times(4, lambda variant: {
            "zero_optimization": {"stage": 2},
            "comm": {"hierarchical": "on", "dcn_quant_bits": 8,
                     "quant_block_size": 256,
                     "overlap_grad_sync": variant},
        })
        speedup = times["off"] / times["on"] if times["on"] else 0.0
        log(f"[bench] comm overlap A/B (tiny GPT, 2-slice int8): "
            f"off {times['off'] * 1e3:.1f} ms/step, on "
            f"{times['on'] * 1e3:.1f} ms/step ({speedup:.2f}x, "
            f"{time.time() - t0:.0f}s)")
        result["comm_overlap_step_speedup"] = round(speedup, 3)
        _section_rows(
            result, "comm_overlap",
            step_time_overlap_off_ms=round(times["off"] * 1e3, 3),
            step_time_overlap_on_ms=round(times["on"] * 1e3, 3),
            overlap_step_speedup=round(speedup, 3))

    def sec_zeropp():
        # ZeRO++ weight path A/B (docs/PERFORMANCE.md "ZeRO++ weight
        # path"): tiny GPT stage-3 on a 2-slice mesh, zeropp off vs
        # qwZ-int8 + hpZ. step-time rows are *_ms so the gate treats
        # upward drift as regression. The baseline adopts this section
        # via the documented --update-baseline green-round flow
        # (tools/bench_gate.py treats a new section as informational
        # until then).
        t0 = time.time()
        times = gpt_ab_times(2, lambda variant: {
            "zero_optimization": {
                "stage": 3, "stage3_param_persistence_threshold": 0,
                **({"zeropp": {"quantized_weights": "int8", "hpz": "on",
                               "quant_block_size": 256}}
                   if variant == "on" else {}),
            },
        })
        speedup = times["off"] / times["on"] if times["on"] else 0.0
        log(f"[bench] zeropp A/B (tiny GPT stage-3, 2-slice): off "
            f"{times['off'] * 1e3:.1f} ms/step, qwZ-int8+hpZ "
            f"{times['on'] * 1e3:.1f} ms/step ({speedup:.2f}x, "
            f"{time.time() - t0:.0f}s)")
        result["zeropp_step_speedup"] = round(speedup, 3)
        _section_rows(
            result, "zeropp",
            step_time_zeropp_off_ms=round(times["off"] * 1e3, 3),
            step_time_zeropp_on_ms=round(times["on"] * 1e3, 3),
            zeropp_step_speedup=round(speedup, 3))

    def sec_autotune():
        # Tuned-vs-default A/B (docs/PERFORMANCE.md "Autotuning"): tiny
        # GPT on a 2-slice mesh; the default engine times its declared
        # config, the tuned engine runs the startup search (micro x gas
        # re-split + the DCN quantization knobs) and times the adopted
        # one. The tuner trials the default too, so tuned <= default up
        # to timing noise — the gate's *_ms rows treat upward drift as
        # regression, and a green round can adopt the tuned row as
        # baseline via the documented --update-baseline flow (the gate
        # treats the new section as informational until then).
        import deepspeed_tpu
        from deepspeed_tpu.models import make_gpt
        from deepspeed_tpu.parallel.mesh import build_mesh

        import jax.numpy as jnp

        t0 = time.time()
        seq, gas0 = 64, 4
        model, mcfg = make_gpt(
            "tiny", dropout_rate=0.0, dtype=jnp.bfloat16,
            max_seq_len=max(seq, 128))
        rng = np.random.default_rng(0)
        params = model.init(
            {"params": jax.random.PRNGKey(0),
             "dropout": jax.random.PRNGKey(1)},
            {"input_ids": np.zeros((2, seq), np.int32)})["params"]

        def make_batches(micro, gas):
            return {"input_ids": rng.integers(
                0, mcfg.vocab_size, (gas, micro, seq), dtype=np.int32)}

        base = {
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": gas0,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 2},
        }
        times, adopted = {}, None
        for variant in ("default", "tuned"):
            cfg_v = dict(base)
            if variant == "tuned":
                cfg_v["autotuning"] = {
                    "micro_gas": [[1, gas0], [gas0, 1]],
                    "dcn_quant_bits": [8, 32],
                    "top_k": 3, "trial_steps": max(steps, 2),
                    "trial_warmup": warmup,
                }
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=model, params=params, mesh=build_mesh(slices=2),
                config=cfg_v)
            if variant == "tuned":
                res = deepspeed_tpu.autotune(engine, make_batches)
                adopted = res["adopted"]["name"]
            batches = make_batches(
                engine.train_micro_batch_size_per_gpu * engine.dp_size,
                engine.gradient_accumulation_steps)
            dt, _ = time_train_batches(engine, batches, max(steps, 2),
                                       warmup, windows=2)
            times[variant] = dt / max(steps, 2)
            del engine
        speedup = (times["default"] / times["tuned"]
                   if times["tuned"] else 0.0)
        log(f"[bench] autotune A/B (tiny GPT, 2-slice): default "
            f"{times['default'] * 1e3:.1f} ms/step, tuned "
            f"{times['tuned'] * 1e3:.1f} ms/step ({speedup:.2f}x, "
            f"adopted '{adopted}', {time.time() - t0:.0f}s)")
        result["autotune_adopted"] = adopted
        result["autotune_step_speedup"] = round(speedup, 3)
        _section_rows(
            result, "autotune",
            step_time_default_ms=round(times["default"] * 1e3, 3),
            step_time_tuned_ms=round(times["tuned"] * 1e3, 3),
            autotune_step_speedup=round(speedup, 3))

    def sec_moe_gpt():
        # MoE GPT dispatch A/B (docs/MOE.md): tiny 4-expert GPT on a
        # data x expert=2 mesh, the SAME model timed under each dispatch
        # mode — einsum oracle vs slot-scatter vs explicit all-to-all
        # (moe/dispatch.py). The modes are numerically parity-tested
        # (tests/test_moe.py), so the rows are a pure schedule/layout
        # comparison. The timed engines keep telemetry OFF (env block
        # above); the
        # overflow row comes from one short untimed telemetry-on run,
        # and the wire row from the static dispatch-bytes model.
        import deepspeed_tpu
        from deepspeed_tpu.models import build_specs, make_gpt
        from deepspeed_tpu.models.gpt import gpt_partition_rules
        from deepspeed_tpu.parallel.mesh import build_mesh
        from deepspeed_tpu.telemetry.registry import InMemorySink

        import jax.numpy as jnp

        t0 = time.time()
        seq = 64
        experts = 4
        model, mcfg = make_gpt(
            "tiny", dropout_rate=0.0, dtype=jnp.bfloat16,
            max_seq_len=max(seq, 128), moe_experts=experts, moe_k=1,
            moe_layer_freq=2)
        rng = np.random.default_rng(0)
        mesh = build_mesh(data=-1, expert=2)
        dp = n_chips_all // 2
        # micro 2/chip: tokens (2*dp*seq) divide the dispatch grid
        # (data-like x expert = n_chips) for the all-to-all manual region
        ids = rng.integers(0, mcfg.vocab_size, (1, 2 * dp, seq),
                           dtype=np.int32)
        params = model.init({"params": jax.random.PRNGKey(0),
                             "dropout": jax.random.PRNGKey(1)},
                            {"input_ids": ids[0]})["params"]
        specs = build_specs(params, gpt_partition_rules(),
                            mesh_axes=dict(mesh.shape))

        def moe_engine(dispatch, telemetry=None):
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=model, params=params, mesh=mesh,
                param_partition_specs=specs,
                config={
                    "train_micro_batch_size_per_gpu": 2,
                    "gradient_accumulation_steps": 1,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                    "zero_optimization": {"stage": 1},
                    "moe": {"enabled": True, "num_experts": experts,
                            "k": 1, "dispatch": dispatch},
                    **(telemetry or {}),
                })
            return engine

        times = {}
        for mode in ("einsum", "scatter", "alltoall"):
            engine = moe_engine(mode)
            dt, _ = time_train_batches(engine, {"input_ids": ids},
                                       max(steps, 2), warmup, windows=2)
            times[mode] = dt / max(steps, 2)
            del engine
        # Untimed stats run (scatter — the mode is irrelevant for the
        # routing stats): real overflow fraction off the moe/* gauges.
        import tempfile as _tempfile
        with _tempfile.TemporaryDirectory() as tdir:
            engine = moe_engine("scatter", telemetry={
                "telemetry": {"enabled": True, "dir": tdir},
                "steps_per_print": 1})
            sink = engine.telemetry.registry.add_sink(InMemorySink())
            for _ in range(2):
                engine.train_batch({"input_ids": ids})
            overflow = [r["value"] for r in sink.rows
                        if r["tag"] == "moe/capacity_overflow_frac"]
            del engine
        from deepspeed_tpu.moe.dispatch import modeled_dispatch_bytes_ici
        tokens = 2 * dp * seq
        capacity = max(4, int(np.ceil(tokens / experts * 1.25)))
        wire = modeled_dispatch_bytes_ici(
            num_experts=experts, capacity=capacity, hidden=mcfg.hidden_size,
            dtype=jnp.bfloat16, mesh=mesh)
        a2a_vs_scatter = (times["scatter"] / times["alltoall"]
                          if times["alltoall"] else 0.0)
        log(f"[bench] moe_gpt dispatch A/B (tiny {experts}-expert GPT, "
            f"expert=2): einsum {times['einsum'] * 1e3:.1f} ms/step, "
            f"scatter {times['scatter'] * 1e3:.1f} ms/step, alltoall "
            f"{times['alltoall'] * 1e3:.1f} ms/step "
            f"({a2a_vs_scatter:.2f}x vs scatter), overflow "
            f"{(overflow[-1] if overflow else 0):.3f}, modeled wire "
            f"{wire} B/layer ({time.time() - t0:.0f}s)")
        result["moe_gpt_alltoall_vs_scatter"] = round(a2a_vs_scatter, 3)
        _section_rows(
            result, "moe_gpt",
            step_time_einsum_ms=round(times["einsum"] * 1e3, 3),
            step_time_scatter_ms=round(times["scatter"] * 1e3, 3),
            step_time_alltoall_ms=round(times["alltoall"] * 1e3, 3),
            alltoall_vs_scatter_speedup=round(a2a_vs_scatter, 3),
            dispatch_bytes_ici_per_layer=int(wire),
            capacity_overflow_frac=round(
                overflow[-1] if overflow else 0.0, 4))

    def sec_fused_optimizer():
        # Fused blockwise Adam A/B (docs/PERFORMANCE.md "Kernel tier
        # round 2"): XLA elementwise chain vs the one-pass Pallas
        # kernel, same trajectory asserted inside.
        t0 = time.time()
        fo = bench_fused_optimizer()
        log(f"[bench] fused optimizer A/B (~1.05M params, 1 device): "
            f"xla {fo['optimizer_step_xla_ms']:.2f} ms vs fused "
            f"{fo['optimizer_step_fused_ms']:.2f} ms "
            f"({time.time() - t0:.0f}s)")
        for key, val in fo.items():
            result[key] = val
        _section_rows(result, "fused_optimizer", **fo)

    sections = [("bert128", sec_bert128), ("bert512", sec_bert512),
                ("gpt2", sec_gpt2), ("gpt2_dropout", sec_gpt2_dropout),
                ("long16k", sec_long), ("inference", sec_inference),
                ("serving", sec_serving),
                ("fused_optimizer", sec_fused_optimizer)]
    # The 2-slice overlap A/B needs an even multi-device split; a
    # one-chip run has no such mesh to build (not a failure).
    if n_chips_all >= 2 and n_chips_all % 2 == 0:
        sections += [("comm_overlap", sec_comm_overlap),
                     ("autotune", sec_autotune)]
    # The zeropp A/B additionally needs a data axis > 1 AND a
    # power-of-two chip count: on exactly 2 devices build_mesh(slices=2)
    # gives dcn=2 x data=1 (the hpZ gather axis is size 1), and an odd
    # data axis (6 devices -> data=3) divides none of tiny-GPT's
    # power-of-two dims — either way ParamGatherPlan gathers nothing and
    # the "on" row would baseline a noise-only no-op as a qwZ
    # measurement.
    if n_chips_all >= 4 and (n_chips_all & (n_chips_all - 1)) == 0:
        sections += [("zeropp", sec_zeropp)]
    # The MoE dispatch A/B needs an expert axis of 2 with a data axis
    # left over (>= 4 even chips); the all-to-all manual region also
    # wants the token count divisible by the full dispatch grid, which
    # the micro-batch choice above guarantees for even chip counts.
    if n_chips_all >= 4 and n_chips_all % 2 == 0:
        sections += [("moe_gpt", sec_moe_gpt)]
    failed = [name for name, fn in sections
              if not run_section(name, fn, result)]

    if result["value"] is None:
        # Headline fallback: if the BERT-128 section failed, promote the
        # best surviving row so `value` is never null while data is
        # present elsewhere.
        for vkey, bkey, metric, unit in (
                ("gpt2_tokens_per_sec", "gpt2_vs_baseline",
                 "GPT-2 seq512 ZeRO-2 pretrain throughput",
                 "tokens/sec/chip"),
                ("bert_seq512_samples_per_sec", "bert_seq512_vs_baseline",
                 "BERT-large seq512 ZeRO-2 pretrain throughput",
                 "samples/sec/chip"),
                ("gpt2_dropout_tokens_per_sec", None,
                 "GPT-2 seq512 dropout-on pretrain throughput",
                 "tokens/sec/chip"),
                ("gpt2_seq16k_dense_tokens_per_sec", None,
                 "GPT-2 seq16384 pretrain throughput", "tokens/sec/chip")):
            if result.get(vkey):
                result["metric"] = f"{metric} ({platform})"
                result["unit"] = unit
                result["value"] = result[vkey]
                result["vs_baseline"] = result[bkey] if bkey else None
                break

    _flush_partial(result)
    print(json.dumps(result))
    # The surviving rows are printed, but the run is green only when
    # every section ran to its end.
    if failed:
        log(f"[bench] FAILED sections: {', '.join(failed)}")
        sys.exit(1)


if __name__ == "__main__":
    main()
