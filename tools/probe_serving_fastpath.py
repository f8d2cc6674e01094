"""Acceptance probe: the paged-KV decode fast path is correct.

Two claims of docs/SERVING.md "Decode fast path", checked on a tiny
GPT over the CPU backend (Pallas interpreter for the kernel):

1. **Token identity** — the same mixed request trace produces
   byte-identical outputs with the fast path fully off (the default
   decode over the batch's live blocks), with the paged
   decode-attention kernel forced, with the
   prefix cache on, and with speculative decoding on. Every fast-path
   piece is a pure-performance lever.
2. **Prefix reuse works** — a shared-prompt-head workload drives
   ``serving/prefix_hits`` above zero and adopted blocks above zero, and
   released/cleared refcounts drain the pool completely (leak check).

Run: JAX_PLATFORMS=cpu python tools/probe_serving_fastpath.py [--selftest]
(tier-1 via tests/test_serving_fastpath.py)
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, _ROOT)

TRACE = [(5, 10), (9, 4), (3, 8), (12, 5), (7, 7)]


def _build(params_model, **overrides):
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.config.config import ServingConfig
    from deepspeed_tpu.serving import ServeEngine

    model, params = params_model
    scfg = ServingConfig(**{"max_batch_size": 2, "kv_block_size": 4,
                            "kv_num_blocks": 64, "max_model_len": 48,
                            **overrides})
    eng = deepspeed_tpu.init_inference(model, params=params,
                                       dtype=jnp.float32)
    return ServeEngine(eng, config=scfg)


def _run_trace(srv, prompts, outs):
    rids = [srv.submit(p, n) for p, n in zip(prompts, outs)]
    res = srv.run_until_complete()
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

    # -- 1. token identity across every fast-path configuration --------
    base_srv = _build(pm)
    base = _run_trace(base_srv, prompts, outs)
    rows = [("off (gather)", base_srv)]
    for name, over in (
            ("kernel", {"decode_attention": "kernel"}),
            ("auto (no TPU: off)", {"decode_attention": "auto"}),
            ("prefix_cache", {"prefix_cache": True}),
            ("speculative k=3", {"spec_decode": True, "spec_k": 3}),
            ("all on", {"decode_attention": "kernel", "prefix_cache": True,
                        "spec_decode": True, "spec_k": 3})):
        srv = _build(pm, **over)
        got = _run_trace(srv, prompts, outs)
        assert got == base, f"{name}: outputs diverged from the off path"
        rows.append((name, srv))
    print("token identity: every configuration matches the off path "
          f"({len(TRACE)} requests)")
    print(f"{'config':24s} {'kernel steps':>12s} {'read pos':>12s} "
          f"{'live pos':>12s} {'spec acc/prop':>14s}")
    for name, srv in rows:
        st = srv.stats
        print(f"{name:24s} {st['kernel_steps']:12d} "
              f"{st['read_positions']:12d} {st['live_positions']:12d} "
              f"{st['spec_accepted']:6d}/{st['spec_proposed']:<6d}")

    # -- 2. prefix reuse + refcount leak check --------------------------
    head = rng.integers(0, cfg.vocab_size, (16,)).tolist()
    srv = _build(pm, prefix_cache=True)
    warm_prompts = [head + rng.integers(0, cfg.vocab_size, (3,)).tolist()
                    for _ in range(4)]
    _run_trace(srv, warm_prompts, [6] * 4)
    hits, reused = srv.prefix_cache.hits, srv.prefix_cache.blocks_reused
    assert hits > 0, "shared-head workload produced no prefix hits"
    assert reused > 0, "no blocks were adopted"
    held = srv.pool.used_blocks
    assert held == srv.prefix_cache.nodes, (
        f"leak: {held} blocks held vs {srv.prefix_cache.nodes} cache nodes "
        f"after drain")
    srv.prefix_cache.clear()
    assert srv.pool.used_blocks == 0, "pool not empty after cache clear"
    print(f"prefix reuse: {hits} hits, {reused} blocks adopted, pool "
          f"drains to 0 after clear")

    if selftest:
        print("selftest ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
