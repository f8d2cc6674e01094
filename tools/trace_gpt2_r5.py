"""Capture a jax.profiler trace of the bench-config GPT-2 train_batch on
the real chip (round-5: locate the residual gap between 60% MFU and the
HBM roofline before picking the next kernel lever)."""

import sys
import time

import jax
import numpy as np

sys.path.insert(0, "/root/repo")


def main():
    import deepspeed_tpu
    from deepspeed_tpu.models import make_gpt
    from deepspeed_tpu.utils.compile_cache import configure_compile_cache

    print("platform:", jax.devices()[0].platform, "compile cache:",
          configure_compile_cache(), flush=True)
    model, cfg = make_gpt("gpt2", dropout_rate=0.0, remat=False,
                          max_seq_len=512)
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
        config={
            "train_micro_batch_size_per_gpu": micro_bs,
            "gradient_accumulation_steps": gas,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 2},
            "data_types": {"grad_accum_dtype": "bfloat16"},
            "bf16": {"enabled": True},
        })
    for _ in range(2):
        loss = engine.train_batch(batches)
    _ = float(loss)
    with jax.profiler.trace("/root/repo/chiprun_out/profiles/gpt2_r5"):
        for _ in range(2):
            loss = engine.train_batch(batches)
        _ = float(loss)
    print("trace written", flush=True)


if __name__ == "__main__":
    main()
