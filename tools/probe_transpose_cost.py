"""How much do the [B,S,H,D]<->[B*H,S,D] layout moves around the flash
kernel cost at bench shapes? 12-layer fwd+bwd loops, one process, real
chip. If this is <2% of the microbatch, the packed-layout kernel isn't
worth building."""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "/root/repo")

from deepspeed_tpu.ops.transformer.flash_attention import _flash_bhsd


def bench(name, fn, *args, steps=20):
    f = jax.jit(fn)
    out = f(*args)
    _ = float(jnp.sum(out).astype(jnp.float32))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = f(*args)
        _ = float(jnp.sum(out).astype(jnp.float32))
        best = min(best, (time.perf_counter() - t0) / steps)
    print(f"[{name}] {best * 1e3:.3f} ms", flush=True)
    return best


def main(b=16, s=512, h=12, d=64, layers=12):
    rng = np.random.default_rng(0)
    seed = jnp.zeros((1,), jnp.int32)
    scale = 1.0 / d ** 0.5
    x_bhsd = jnp.asarray(rng.standard_normal((b * h, s, d)), jnp.bfloat16)
    x_bshd = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)

    def flash(q):  # layout-native: no moves
        def body(h_, _):
            o = _flash_bhsd(h_, h_, h_, seed, True, scale, 512, 512,
                            False, 0.0)
            return o, None
        out, _ = jax.lax.scan(body, q, None, length=layers)
        return jnp.sum(out.astype(jnp.float32))

    def flash_t(q):  # model layout: transpose in+out each layer
        def body(h_, _):
            qt = h_.transpose(0, 2, 1, 3).reshape(b * h, s, d)
            o = _flash_bhsd(qt, qt, qt, seed, True, scale, 512, 512,
                            False, 0.0)
            o = o.reshape(b, h, s, d).transpose(0, 2, 1, 3)
            return o, None
        out, _ = jax.lax.scan(body, x_bshd, None, length=layers)
        return jnp.sum(out.astype(jnp.float32))

    from deepspeed_tpu.utils.compile_cache import configure_compile_cache
    print("platform:", jax.devices()[0].platform, "compile cache:",
          configure_compile_cache(), flush=True)
    bench("fwd   native   ", flash, x_bhsd)
    bench("fwd   transpose", flash_t, x_bshd)
    bench("f+b   native   ", jax.grad(flash), x_bhsd)
    bench("f+b   transpose", jax.grad(flash_t), x_bshd)


if __name__ == "__main__":
    main()
