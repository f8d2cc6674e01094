"""Component-level profile of the GPT-2 bench config — a chip tool.

Decomposes the train step into its big pieces by timing jitted
sub-programs at the exact bench shapes (B=16, S=512, gas=4, GPT-2 small),
plus XLA cost_analysis bytes/flops so HBM-bound phases are identifiable,
then captures a jax.profiler trace and reduces it with
telemetry/traceparse.py. Writes findings to stdout. Refuses to run off
the TPU: every number it prints is a device time.
"""

import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import make_gpt
from deepspeed_tpu.models.gpt import cross_entropy_with_ignore, shift_labels
from deepspeed_tpu.utils.compile_cache import configure_compile_cache


def log(msg):
    print(msg, flush=True)


def fence(out):
    """Close the timing window with a scalar fetch: the host clock stops
    only after the device has produced the value."""
    leaf = jax.tree_util.tree_leaves(out)[0]
    return float(jnp.sum(leaf).astype(jnp.float32))


def timeit(fn, *args, iters=20, warmup=3):
    out = None
    for _ in range(warmup):
        out = fn(*args)
    fence(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    fence(out)
    return (time.perf_counter() - t0) / iters


def analyze(fn, *args, name=""):
    lowered = jax.jit(fn).lower(*args)
    compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    flops = ca.get("flops", 0.0)
    bytes_acc = ca.get("bytes accessed", 0.0)
    log(f"[cost] {name}: flops={flops/1e12:.2f}T bytes={bytes_acc/1e9:.2f}GB "
        f"(ridge: {flops/max(bytes_acc,1):.0f} flop/byte)")
    return compiled


def main():
    cache_dir = configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"profile_gpt2.py times the TPU; jax found platform "
                 f"{dev.platform!r}. Nothing was measured.")
    log(f"device: {dev.device_kind} ({dev.platform}) x {len(jax.devices())}, "
        f"compile cache {cache_dir}")
    B, S, GAS = 16, 512, 4
    model, cfg = make_gpt("gpt2", dropout_rate=0.0, remat=False,
                          max_seq_len=max(S, 128))
    D, V, L, H = cfg.hidden_size, cfg.vocab_size, cfg.num_layers, cfg.num_heads
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, V, (B, S), dtype=np.int32))
    batch = {"input_ids": ids}
    params = model.init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(1)}, batch)["params"]
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    tokens = B * S
    step_flops = (6.0 * n_params + 12.0 * L * D * S) * tokens
    log(f"model: {n_params/1e6:.0f}M params, {step_flops/1e12:.2f} TFLOP per "
        f"fwd+bwd microbatch (B={B} S={S})")

    def loss_fn(p, b):
        out = model.apply({"params": p}, b, deterministic=True)
        return out["loss"]

    # --- 1. full fwd+bwd microbatch ------------------------------------
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    analyze(jax.value_and_grad(loss_fn), params, batch, name="fwd+bwd")
    t_fwdbwd = timeit(grad_fn, params, batch)
    log(f"[time] fwd+bwd microbatch: {t_fwdbwd*1e3:.1f} ms "
        f"-> {step_flops/t_fwdbwd/1e12:.1f} TFLOP/s")

    # --- 2. fwd only ----------------------------------------------------
    fwd = jax.jit(loss_fn)
    t_fwd = timeit(fwd, params, batch)
    log(f"[time] fwd only: {t_fwd*1e3:.1f} ms")

    # --- 3. trunk only (no loss head): mean of final hidden -------------
    def trunk_loss(p, b):
        out = model.apply({"params": p}, b, deterministic=True)
        # logits are produced; sum them cheaply? No — that keeps the head.
        return out["loss"]

    # Instead: a model clone whose head is removed is intrusive; approximate
    # by timing the head in isolation at the same shapes.
    x = jnp.asarray(rng.normal(size=(B, S, D)), jnp.bfloat16)
    wte = params["wte"].astype(jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (B, S), dtype=np.int32))

    def head_loss(wte_, x_):
        logits = jnp.einsum("bsd,vd->bsv", x_.astype(jnp.bfloat16),
                            wte_.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        return cross_entropy_with_ignore(logits, labels)

    head_grad = jax.jit(jax.value_and_grad(head_loss, argnums=(0, 1)))
    analyze(jax.value_and_grad(head_loss, argnums=(0, 1)), wte, x,
            name="xent head fwd+bwd (fp32 logits)")
    t_head = timeit(head_grad, wte, x)
    head_flops = 6.0 * V * D * tokens
    log(f"[time] xent head fwd+bwd: {t_head*1e3:.1f} ms "
        f"({100*t_head/t_fwdbwd:.0f}% of microbatch; matmul-only would be "
        f"{head_flops/1e12:.2f} TFLOP -> {head_flops/t_head/1e12:.1f} TFLOP/s)")

    # --- 4. head with bf16 logits + fp32 logsumexp ----------------------
    def head_loss_bf16(wte_, x_):
        logits = jnp.einsum("bsd,vd->bsv", x_.astype(jnp.bfloat16),
                            wte_.astype(jnp.bfloat16))  # bf16 out
        logits = logits.astype(jnp.float32)
        valid = labels >= 0
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        nll = jnp.where(valid, lse - picked, 0.0)
        return nll.sum() / jnp.maximum(valid.sum(), 1)

    head_grad16 = jax.jit(jax.value_and_grad(head_loss_bf16, argnums=(0, 1)))
    t_head16 = timeit(head_grad16, wte, x)
    log(f"[time] xent head bf16-logits: {t_head16*1e3:.1f} ms")

    # --- 5. attention fwd+bwd at bench shape, flash vs xla --------------
    from deepspeed_tpu.ops.transformer.attention import attention
    dh = D // H
    q = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.bfloat16)

    for impl in ("pallas", "xla"):
        def attn_loss(q_, k_, v_, impl=impl):
            return attention(q_, k_, v_, causal=True, impl=impl).astype(
                jnp.float32).sum()

        g = jax.jit(jax.grad(attn_loss, argnums=(0, 1, 2)))
        t = timeit(g, q, k, v)
        # one layer's attention; model has L of them
        log(f"[time] attention fwd+bwd ({impl}): {t*1e3:.2f} ms/layer "
            f"-> x{L} = {t*L*1e3:.1f} ms ({100*t*L/t_fwdbwd:.0f}% of "
            f"microbatch)")

    # --- 6. MLP + qkv matmuls sanity: one block fwd+bwd -----------------
    from deepspeed_tpu.models.gpt import GPTBlock
    blk = GPTBlock(cfg)
    bp = blk.init({"params": jax.random.PRNGKey(0)}, x, None, True)["params"]

    def blk_loss(p_, x_):
        return blk.apply({"params": p_}, x_, None, True).astype(jnp.float32).sum()

    gblk = jax.jit(jax.grad(blk_loss, argnums=(0, 1)))
    t_blk = timeit(gblk, bp, x)
    blk_flops = 6.0 * (12 * D * D) * tokens + 12.0 * D * S * tokens
    log(f"[time] one block fwd+bwd: {t_blk*1e3:.2f} ms -> x{L} = "
        f"{t_blk*L*1e3:.1f} ms ({100*t_blk*L/t_fwdbwd:.0f}% of microbatch; "
        f"{blk_flops/t_blk/1e12:.1f} TFLOP/s)")

    # --- 7. embedding fwd+bwd -------------------------------------------
    wpe = params["wpe"].astype(jnp.float32)

    def embed_loss(wte_, wpe_):
        xx = wte_[ids].astype(jnp.bfloat16) + wpe_[:S][None].astype(jnp.bfloat16)
        return xx.astype(jnp.float32).sum()

    gemb = jax.jit(jax.grad(embed_loss, argnums=(0, 1)))
    t_emb = timeit(gemb, wte, wpe)
    log(f"[time] embedding fwd+bwd (gather/scatter): {t_emb*1e3:.2f} ms "
        f"({100*t_emb/t_fwdbwd:.0f}% of microbatch)")

    # --- 8. optimizer apply at GPT-2 scale ------------------------------
    from deepspeed_tpu.ops.adam.fused_adam import FusedAdam
    opt = FusedAdam(lr=1e-4)
    ost = opt.init(params)
    grads = jax.tree_util.tree_map(lambda p: jnp.ones_like(p, jnp.float32), params)

    def apply_fn(g, o, p):
        return opt.update(g, o, p, lr=jnp.float32(1e-4))

    japply = jax.jit(apply_fn)
    t_apply = timeit(japply, grads, ost, params)
    full_step = GAS * t_fwdbwd + t_apply
    log(f"[time] optimizer apply: {t_apply*1e3:.1f} ms "
        f"(amortized 1/{GAS} per microbatch)")
    log(f"[model] gas*{t_fwdbwd*1e3:.1f} + {t_apply*1e3:.1f} = "
        f"{full_step*1e3:.1f} ms/step -> "
        f"{GAS*step_flops/full_step/1e12:.1f} TFLOP/s overall")

    # --- 9. real capture -> measured attribution ------------------------
    # One parser in the tree: the capture round-trips through
    # telemetry/traceparse.py (the same module the devicetime observatory
    # and the report tools use) instead of a hand-rolled scan.
    from deepspeed_tpu.telemetry import traceparse
    trace_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out", "profiles", "gpt2")
    with jax.profiler.trace(trace_dir):
        for _ in range(3):
            out = grad_fn(params, batch)
        jax.block_until_ready(out)
    log(f"[trace] written to {trace_dir}")
    analysis = traceparse.parse_capture_dir(trace_dir)
    log(f"[trace] measured attribution over "
        f"{len(analysis['captures'])} capture(s), "
        f"{analysis['n_devices']} device row(s): busy "
        f"{analysis['busy_sec'] * 1e3:.1f} ms, gap "
        f"{analysis['gap_sec'] * 1e3:.1f} ms")
    for cat in traceparse.CATEGORIES:
        sec = analysis["categories"][cat]
        if sec > 0:
            log(f"[trace]   {cat:<12} {sec * 1e3:>10.2f} ms")
    for r in traceparse.top_ops(analysis, 10):
        log(f"[trace]   hot: {r['name']:<32} {r['sec'] * 1e3:>9.2f} ms "
            f"x{r['count']} ({r['category']})")


if __name__ == "__main__":
    main()
