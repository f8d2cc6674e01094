"""Micro A/B: ln_matmul Pallas kernel vs XLA's unfused LN+matmul, fwd-only
and fwd+bwd, 12-iteration loops amortizing dispatch (one process, real
chip). Locates where the end-to-end deficit (probe_fused_r5: 0.90x) lives."""

import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "/root/repo")

from deepspeed_tpu.ops.transformer.fused import ln_matmul, ln_matmul_reference


def bench(name, fn, *args, steps=30):
    f = jax.jit(fn)
    out = f(*args)
    _ = float(jnp.sum(jax.tree_util.tree_leaves(out)[0]).astype(jnp.float32))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = f(*args)
        _ = float(jnp.sum(
            jax.tree_util.tree_leaves(out)[0]).astype(jnp.float32))
        best = min(best, (time.perf_counter() - t0) / steps)
    print(f"[{name}] {best * 1e3:.3f} ms", flush=True)
    return best


def main(n=8192, d=768, f=2304, act=None, layers=12):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.bfloat16)
    gamma = jnp.ones(d, jnp.float32)
    beta = jnp.zeros(d, jnp.float32)
    ws = jnp.asarray(rng.standard_normal((layers, d, f)) / np.sqrt(d),
                     jnp.bfloat16)
    bias = jnp.zeros(f, jnp.bfloat16)
    proj = jnp.asarray(rng.standard_normal((layers, f, d)) / np.sqrt(f),
                       jnp.bfloat16)
    print(f"== n={n} d={d} f={f} act={act} x{layers}", flush=True)

    def stack(op):
        # layers x (ln+matmul -> proj back to d) so shapes chain.
        def run(x, ws, proj):
            def body(h, wp):
                w, p = wp
                y = op(h, gamma, beta, w, bias)
                return jnp.dot(y, p, preferred_element_type=jnp.float32
                               ).astype(h.dtype), None
            h, _ = jax.lax.scan(body, x, (ws, proj))
            return h
        return run

    fused = stack(partial(ln_matmul, activation=act))
    ref = stack(partial(ln_matmul_reference, activation=act))

    bench("fwd  fused", fused, x, ws, proj)
    bench("fwd  xla  ", ref, x, ws, proj)

    def grad_of(run):
        def loss(x, ws, proj):
            return jnp.sum(run(x, ws, proj).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1))

    bench("f+b  fused", grad_of(fused), x, ws, proj)
    bench("f+b  xla  ", grad_of(ref), x, ws, proj)


if __name__ == "__main__":
    from deepspeed_tpu.utils.compile_cache import configure_compile_cache
    print("platform:", jax.devices()[0].platform, "compile cache:",
          configure_compile_cache(), flush=True)
    main(act=None)
    main(f=3072, act="gelu")
