"""Round-4 bench-config sweep: GPT-2 gas/micro-batch, one process A/B."""
import sys, time
import jax
import numpy as np
sys.path.insert(0, "/root/repo")


def run(name, micro_bs, gas, steps=8, windows=2):
    import deepspeed_tpu
    from deepspeed_tpu.models import make_gpt

    model, cfg = make_gpt("gpt2", dropout_rate=0.0, remat=False,
                          max_seq_len=512)
    rng = np.random.default_rng(0)
    seq = 512
    batches = {"input_ids": rng.integers(0, cfg.vocab_size,
                                         (gas, micro_bs, seq),
                                         dtype=np.int32)}
    one = jax.tree_util.tree_map(lambda x: x[0], batches)
    params = model.init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(1)}, one)["params"]
    try:
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, params=params,
            config={"train_micro_batch_size_per_gpu": micro_bs,
                    "gradient_accumulation_steps": gas,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                    "zero_optimization": {"stage": 2},
                    "data_types": {"grad_accum_dtype": "bfloat16"},
                    "bf16": {"enabled": True}})
        for _ in range(2):
            loss = engine.train_batch(batches)
        _ = float(loss)
        best = float("inf")
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = engine.train_batch(batches)
            _ = float(loss)
            best = min(best, time.perf_counter() - t0)
        tps = gas * micro_bs * seq * steps / best
        print(f"[{name}] {tps:,.0f} tok/s", flush=True)
        return tps
    except Exception as e:
        print(f"[{name}] FAILED: {type(e).__name__} {str(e)[:80]}",
              flush=True)
        return 0.0


def main():
    from deepspeed_tpu.utils.compile_cache import configure_compile_cache
    print("platform:", jax.devices()[0].platform, "compile cache:",
          configure_compile_cache(), flush=True)
    run("mb16 gas8  (bench)", 16, 8)
    run("mb16 gas16       ", 16, 16, steps=4)
    run("mb24 gas8        ", 24, 8)
    run("mb32 gas8        ", 32, 8)
    run("mb8  gas16       ", 8, 16, steps=4)


if __name__ == "__main__":
    main()
