"""Acceptance probe: kernel tier round 2 is correct and cheaper.

Three claims of docs/PERFORMANCE.md "Kernel tier round 2" /
docs/SERVING.md "Chunked prefill admission", measured on a tiny GPT over
the CPU backend (Pallas interpreter for both kernels):

1. **One compile, lower tail latency** — a bursty burst of prompts whose
   lengths span several prefill buckets is served token-identically by
   the chunked admission mode, its TTFT p99 beats the bucketed path on
   the same cold engines (the bucketed path pays one cold compile per
   bucket inside the burst's latency window), and the recompile detector
   proves the mixed program compiled exactly ONCE while the bucketed
   engine built O(buckets) prefill programs.
2. **Chunked admission is exact** — mid-prompt chunk boundaries, decode
   rows and prefill rows sharing one program: the full greedy traces
   match the bucketed oracle byte for byte.
3. **Fused update preserves the trajectory** — the one-pass blockwise
   Adam kernel steps a real training engine to the same parameters as
   the XLA elementwise chain (the throughput claim is a TPU round's;
   the probe pins the math).

Run: JAX_PLATFORMS=cpu python tools/probe_chunked_prefill.py [--selftest]
(tier-1 via tests/test_chunked_prefill.py)
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, _ROOT)

# Bursty: lengths span >= 3 prefill buckets, all submitted up front.
LENS = [6, 14, 28, 44, 9, 30]
OUTS = [8, 5, 7, 4, 9, 6]


def _build(params_model, **overrides):
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.config.config import ServingConfig
    from deepspeed_tpu.serving import ServeEngine
    from deepspeed_tpu.telemetry import (InMemorySink, MetricsRegistry,
                                         StepTracer, Telemetry)

    model, params = params_model
    scfg = ServingConfig(**{"max_batch_size": 2, "kv_block_size": 4,
                            "kv_num_blocks": 64, "max_model_len": 64,
                            **overrides})
    eng = deepspeed_tpu.init_inference(model, params=params,
                                       dtype=jnp.float32)
    reg = MetricsRegistry()
    reg.add_sink(InMemorySink())
    # The engine's own (enabled-by-default) detector proves the
    # one-compile claim; the registry feeds the TTFT histogram.
    tel = Telemetry(reg, StepTracer(path=None, enabled=False),
                    eng.recompile_detector)
    return ServeEngine(eng, config=scfg, telemetry=tel)


def _run_burst(srv, prompts, outs):
    rids = [srv.submit(p, n) for p, n in zip(prompts, outs)]
    res = srv.run_until_complete()
    toks = [res[r]["tokens"] for r in rids]
    p99 = srv.telemetry.registry.histogram("serving/ttft_ms").percentile(99)
    return toks, p99


def main(argv=None) -> int:
    selftest = "--selftest" in (argv if argv is not None else sys.argv[1:])
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models import make_gpt

    print(f"platform: {jax.devices()[0].platform} (CPU correctness drive)")
    model, cfg = make_gpt("tiny", dropout_rate=0.0, max_seq_len=80,
                          dtype=jnp.float32)
    params = model.init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(1)},
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    pm = (model, params)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).tolist()
               for n in LENS]

    # -- 1 + 2. bucketed oracle vs chunked admission, cold engines ------
    bsrv = _build(pm)
    base, p99_b = _run_burst(bsrv, prompts, OUTS)
    csrv = _build(pm, chunked_prefill=True, chunked_token_budget=16)
    got, p99_c = _run_burst(csrv, prompts, OUTS)
    assert got == base, "chunked admission diverged from the bucketed oracle"
    n_buckets = len(bsrv._prefill_jit) + len(bsrv._tail_prefill_jit)
    det = csrv.engine.recompile_detector
    compiles = det.compiles("serving.mixed_step")
    retraces = det.retraces("serving.mixed_step")
    print(f"token identity: {len(LENS)} bursty requests match the "
          f"bucketed oracle byte for byte")
    print(f"compile count: mixed program {compiles} compile / {retraces} "
          f"retraces vs {n_buckets} bucketed prefill programs")
    assert compiles == 1 and retraces == 0, (
        f"mixed program must compile exactly once "
        f"({compiles} compiles, {retraces} retraces)")
    assert n_buckets >= 2, (
        f"burst was meant to span several buckets (saw {n_buckets})")
    assert len(csrv._prefill_jit) + len(csrv._tail_prefill_jit) == 0, \
        "chunked engine built bucketed prefill programs"
    print(f"TTFT p99: {p99_c:.1f} ms chunked vs {p99_b:.1f} ms bucketed")
    assert p99_c < p99_b, (
        f"chunked TTFT p99 ({p99_c:.1f} ms) should beat bucketed "
        f"({p99_b:.1f} ms) on a cold bursty trace")

    # -- 3. fused update: same trajectory as the XLA chain --------------
    sys.path.insert(0, os.path.join(_ROOT, "tests"))
    from simple_model import mlp_loss_fn, mlp_params, random_batch

    from deepspeed_tpu import initialize
    from deepspeed_tpu.parallel.mesh import build_mesh

    def engine(fused):
        cfg_d = {"train_micro_batch_size_per_gpu": 8,
                 "gradient_accumulation_steps": 1,
                 "optimizer": {"type": "Adam", "params": {"lr": 1e-2},
                               "fused_update": fused},
                 "zero_optimization": {"stage": 2}}
        e, _, _, _ = initialize(loss_fn=mlp_loss_fn, params=mlp_params(),
                                config=cfg_d, mesh=build_mesh())
        return e

    brng = np.random.default_rng(0)
    batches = [random_batch(brng, batch_size=8) for _ in range(3)]
    a, b = engine(False), engine(True)
    for bt in batches:
        for e in (a, b):
            loss = e.forward(bt)
            e.backward(loss)
            e.step()
    err = max(float(jnp.max(jnp.abs(x - y)))
              for x, y in zip(jax.tree_util.tree_leaves(a.state.params),
                              jax.tree_util.tree_leaves(b.state.params)))
    print(f"fused update: ZeRO-2 trajectory max param delta {err:.2e} "
          f"after {len(batches)} steps")
    assert err < 1e-5, f"fused update trajectory diverged ({err:.2e})"

    if selftest:
        print("selftest ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
