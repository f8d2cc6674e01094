"""Dropout-regime A/B (r3 VERDICT task 2): GPT-2 bench config with
dropout 0.1 — threefry nn.Dropout vs counter-hash dropout
(ops/dropout.py) vs dropout-off, one process."""
import sys, time
import jax
import numpy as np
sys.path.insert(0, "/root/repo")


def run(name, dropout_rate, fast, steps=8, windows=2):
    import deepspeed_tpu
    from deepspeed_tpu.models import make_gpt

    model, cfg = make_gpt("gpt2", dropout_rate=dropout_rate, remat=False,
                          max_seq_len=512, fast_dropout=fast)
    rng = np.random.default_rng(0)
    micro_bs, seq, gas = 16, 512, 8
    batches = {"input_ids": rng.integers(0, cfg.vocab_size,
                                         (gas, micro_bs, seq),
                                         dtype=np.int32)}
    one = jax.tree_util.tree_map(lambda x: x[0], batches)
    params = model.init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(1)}, one)["params"]
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
    print(f"[{name}] {tps:,.0f} tok/s (loss {float(loss):.3f})", flush=True)
    return tps


def main():
    from deepspeed_tpu.utils.compile_cache import configure_compile_cache
    print("platform:", jax.devices()[0].platform, "compile cache:",
          configure_compile_cache(), flush=True)
    off = run("dropout off       ", 0.0, False)
    slow = run("dropout threefry  ", 0.1, False)
    fast = run("dropout hash      ", 0.1, True)
    print(f"threefry {slow/off:.1%} of off; hash {fast/off:.1%} of off "
          f"(hash vs threefry {fast/slow - 1:+.1%})", flush=True)


if __name__ == "__main__":
    main()
