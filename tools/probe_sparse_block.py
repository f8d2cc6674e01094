"""Sparse-kernel block-size sweep at seq 4096 vs dense flash (fwd+bwd,
8-layer stacks, in-run A/B)."""
import functools, sys, time
import jax, jax.numpy as jnp, numpy as np
sys.path.insert(0, "/root/repo")
from deepspeed_tpu.ops.sparse_attention import (BigBirdSparsityConfig,
                                               sparse_attention)
from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

LAYERS, B, H, D, S = 8, 2, 12, 64, 4096

def timed(fn, q, steps=8, warmup=2):
    grad = jax.jit(jax.grad(lambda q: jnp.sum(fn(q).astype(jnp.float32))))
    for _ in range(warmup):
        g = grad(q)
    float(jnp.sum(g.astype(jnp.float32)))
    t0 = time.perf_counter()
    for _ in range(steps):
        g = grad(q)
    float(jnp.sum(g.astype(jnp.float32)))
    return (time.perf_counter() - t0) / steps * 1e3

def main():
    from deepspeed_tpu.utils.compile_cache import configure_compile_cache
    print("platform:", jax.devices()[0].platform, "compile cache:",
          configure_compile_cache(), flush=True)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16) * 0.1

    def stack(q, one):
        x = q
        for _ in range(LAYERS):
            x = one(x)
        return x

    t = timed(lambda x: stack(x, lambda y: flash_attention(
        y, y, y, causal=True)), q)
    print(f"dense flash      : {t:7.1f} ms", flush=True)
    for blk in (64, 128, 256, 512):
        sc = BigBirdSparsityConfig(num_heads=H, block=blk,
                                   num_random_blocks=1,
                                   num_sliding_window_blocks=3,
                                   num_global_blocks=1,
                                   attention="unidirectional")
        layout = sc.make_layout(S)
        dens = layout.sum() / layout.size
        t = timed(lambda x: stack(x, lambda y: sparse_attention(
            y, y, y, layout, blk, causal=True, impl="pallas")), q)
        print(f"bigbird blk {blk:4d}: {t:7.1f} ms (density {dens:.2%})",
              flush=True)

main()
