"""Round-4 probe: odd 128-multiple self-attention lengths (640/768/896/
1152) — xla fallback vs degraded-block pallas vs PADDED pallas (pad to
512-multiple, mask the tail). In-run A/B, 8-layer BERT-large-shaped
attention stacks, fwd+bwd, scalar-fence timing."""

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "/root/repo")
from deepspeed_tpu.ops.transformer import attention as att  # noqa: E402

LAYERS, B, H, D = 8, 8, 16, 64


def stack_loss(q, k, v, impl, dropout_rng):
    rate = 0.0 if dropout_rng is None else 0.1
    x = q
    for i in range(LAYERS):
        rng = (None if dropout_rng is None
               else jax.random.fold_in(dropout_rng, i))
        x = att.attention(x, k, v, causal=False, impl=impl,
                          dropout_rate=rate, dropout_rng=rng,
                          deterministic=dropout_rng is None)
    return jnp.sum(x.astype(jnp.float32))


def timed(s, impl, dropout, steps=10, warmup=2):
    rng = np.random.default_rng(0)
    shape = (B, s, H, D)
    q = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16) * 0.1
    k = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16) * 0.1
    v = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16) * 0.1
    key = jax.random.PRNGKey(1) if dropout else None

    grad = jax.jit(jax.grad(
        functools.partial(stack_loss, impl=impl, dropout_rng=key),
        argnums=(0, 1, 2)))
    for _ in range(warmup):
        g = grad(q, k, v)
    float(jnp.sum(g[0].astype(jnp.float32)))
    t0 = time.perf_counter()
    for _ in range(steps):
        g = grad(q, k, v)
    float(jnp.sum(g[0].astype(jnp.float32)))
    return (time.perf_counter() - t0) / steps * 1e3


def main():
    from deepspeed_tpu.utils.compile_cache import configure_compile_cache
    print("platform:", jax.devices()[0].platform, "compile cache:",
          configure_compile_cache(), flush=True)
    for dropout in (False, True):
        for s in (640, 768, 896, 1152):
            xla = timed(s, "xla", dropout)
            deg = timed(s, "pallas", dropout)
            pad = timed(s, "pallas_pad", dropout)
            best = min((xla, "xla"), (deg, "pallas"), (pad, "pallas_pad"))
            print(f"seq {s:5d} dropout={int(dropout)}: xla {xla:6.1f}  "
                  f"pallas-degraded {deg:6.1f}  pallas-padded {pad:6.1f} ms"
                  f"  -> {best[1]}", flush=True)


if __name__ == "__main__":
    main()
