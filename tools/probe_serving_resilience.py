"""Acceptance probe: serving survives chaos with token-identical output.

The claims of docs/SERVING.md "Serving under failure", measured on a tiny
GPT over the CPU backend:

1. **Chaos → recover → token identity** — with an injected
   decode-dispatch fault mid-trace (FaultPlan ``serve_decode_fault``),
   the engine retries, rebuilds its KV pools + decode programs
   in-process, replays every live sequence, and every request finishes
   with output byte-identical to the fault-free run. A persistent-fault
   window (wider than the retry budget) forces the full rebuild path and
   still matches.
2. **Leak-free terminal aborts** — deadline expiry and cancellation
   release every KV block exactly once: after a chaos trace with aborts
   the pool drains to zero (the BlockPool refcounts raise on any double
   free, so this is structural, not statistical).
3. **Shed-fraction gate** — under a FaultPlan request storm with
   admission control on, the engine sheds a bounded fraction: some
   requests shed (the gate works), but never ALL of them (admitted work
   keeps flowing), and every shed rid has a terminal ``shed`` record.

Run: JAX_PLATFORMS=cpu python tools/probe_serving_resilience.py [--selftest]
(tier-1 via tests/test_serving_resilience.py)
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, _ROOT)

TRACE = [(5, 10), (9, 4), (3, 8), (12, 5), (7, 7)]


def _build(params_model, fault=None, **overrides):
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.config.config import ServingConfig
    from deepspeed_tpu.resilience import FaultPlan
    from deepspeed_tpu.serving import ServeEngine

    model, params = params_model
    scfg = ServingConfig(**{"max_batch_size": 2, "kv_block_size": 4,
                            "kv_num_blocks": 64, "max_model_len": 48,
                            **overrides})
    eng = deepspeed_tpu.init_inference(model, params=params,
                                       dtype=jnp.float32)
    plan = FaultPlan.resolve(fault) if fault else None
    return ServeEngine(eng, config=scfg, fault_plan=plan)


def _run_trace(srv, prompts, outs):
    rids = [srv.submit(p, n) for p, n in zip(prompts, outs)]
    res = srv.run_until_complete(timeout_sec=120.0)
    return [res[r]["tokens"] for r in rids]


def main(argv=None) -> int:
    selftest = "--selftest" in (argv if argv is not None else sys.argv[1:])
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models import make_gpt

    print(f"platform: {jax.devices()[0].platform} (CPU correctness drive)")
    model, cfg = make_gpt("tiny", dropout_rate=0.0, max_seq_len=64,
                          dtype=jnp.float32)
    params = model.init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(1)},
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    pm = (model, params)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, (t,)).tolist()
               for t, _ in TRACE]
    outs = [n for _, n in TRACE]

    # -- 1. chaos -> recover -> token identity --------------------------
    base = _run_trace(_build(pm), prompts, outs)
    for name, fault in (
            ("transient (retry heals)",
             {"serve_decode_fault_at_step": 4}),
            ("persistent (rebuild+replay)",
             {"serve_decode_fault_at_step": 4,
              "serve_decode_fault_count": 3})):
        srv = _build(pm, fault=fault, resilience=True,
                     resil_retry_base_sec=0.01)
        got = _run_trace(srv, prompts, outs)
        assert got == base, f"{name}: outputs diverged from fault-free run"
        c = srv._resil.counters
        print(f"chaos [{name}]: retries={c['retries']} "
              f"recoveries={c['recoveries']} — all {len(TRACE)} requests "
              f"token-identical to the fault-free run")
        if "persistent" in name:
            assert c["recoveries"] >= 1, c
        else:
            assert c["retries"] >= 1 and c["recoveries"] == 0, c

    # -- 2. leak-free terminal aborts -----------------------------------
    srv = _build(pm, resilience=True)
    rids = [srv.submit(p, n) for p, n in zip(prompts, outs)]
    srv.step()                               # admit + first tokens
    assert srv.cancel(rids[0])
    srv.run_until_complete(timeout_sec=120.0)
    assert srv.results[rids[0]]["status"] == "cancelled", srv.results[rids[0]]
    assert srv.pool.used_blocks == 0, (
        f"leak: {srv.pool.used_blocks} blocks held after drain with a "
        f"cancelled request")
    print(f"terminal aborts: cancel keeps partial output "
          f"({len(srv.results[rids[0]]['tokens'])} tokens), pool drains "
          f"to 0")

    # -- 3. shed-fraction gate under a request storm --------------------
    srv = _build(pm, fault={"serve_storm_at_step": 2,
                            "serve_storm_requests": 12},
                 resilience=True, resil_max_queue_depth=3)
    shed_rids = [srv.submit(p, n) for p, n in zip(prompts, outs)]
    res = srv.run_until_complete(timeout_sec=120.0)
    statuses = [r["status"] for r in res.values()]
    n_shed = statuses.count("shed")
    n_fin = statuses.count("finished")
    assert n_shed > 0, "storm over a depth-3 queue shed nothing"
    assert n_fin >= len(TRACE), (
        f"admitted work starved: only {n_fin} finished under the storm")
    assert all(res[r]["status"] in ("finished", "shed")
               for r in shed_rids), "a submitted rid lost its record"
    frac = n_shed / len(res)
    print(f"load shedding: {n_shed}/{len(res)} shed ({frac:.0%}), "
          f"{n_fin} finished — admitted work kept flowing")
    assert 0.0 < frac < 1.0

    if selftest:
        print("selftest ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
