"""Nemotron-H's hybrid block (``model_type`` ``nemotron_h``, as in
NVIDIA-Nemotron-3-Super-120B-A12B): every layer is ONE mixer with a
pre-norm and a residual, and ``pattern`` says which, a character a layer:
``M`` a Mamba-2 mixer, ``*`` grouped-query attention, ``E`` latent experts.

Equations (``x`` is ``[T, hidden]``; RMSNorm is ``w x / sqrt(mean(x^2) +
eps)``; no bias but the convolution's)::

    x <- x + mixer_l(RMSNorm_l(x))      for each character of the pattern
    logits = RMSNorm_f(x) W_head        untied head [hidden, vocab]

    M   [z | xBC | dt] = x W_in         d_inner, d_inner + 2 G N, H wide
        xBC <- silu(conv(xBC) + b)      causal, depthwise, last K positions
        [u | B | C] = xBC               u [H, P], B and C [G, N]
        dt <- softplus(dt + dt_bias);   A = -exp(A_log)        (per head)
        h_t = exp(dt_t A) h_{t-1} + dt_t u_t (x) B_t;  y_t = h_t C_t + D u_t
        y <- GroupRMSNorm(y silu(z))    within each of G groups of channels
        out = y W_out                   (ops/ssm.py has the recurrence)

    *   q = x W_q [heads x d], k, v = x W_k, x W_v [kv_heads x d]
        softmax(q k^T / sqrt(d)) v, causal, a key/value head serving
        heads / kv_heads query heads; out = . W_o. NO positional encoding:
        the Mamba layers carry order.

    E   moe/dropless.py's layer with a latent: sigmoid router over all
        published experts on the full-width x, top-k of score + bias,
        weights renormalised and scaled; zl = x W_latent_in; the HELD
        experts' sum of w_e W2_e relu(W1_e zl)^2; back through
        W_latent_out; plus the shared expert S2 relu(S1 x)^2 on x.

Precision: activations and weights in ``dtype`` (bfloat16 when served:
the residual stream too), matmuls accumulate in float32; float32 for every
norm, the router's INPUT (its layer's norm is not rounded on the way to
it) and scores, ``dt``, ``A``, the SSM recurrence and its state, the
softmax's statistics, and a mixer's result on its way into the stream
(``_out_proj``: the add rounds once, and the next layer's norm reads the
sum before that rounding); the shared expert's two matmuls read
their float32 inputs as two bfloat16 halves (``moe/dropless.py:
_in_two_halves``). With 22 of 512 experts a token a
near tie flips on the chip in a tenth to a quarter of the tokens a layer
against a float32 forward, and each rounding spared is fewer flips
(PERF.md section 6, PR 35).

**Serving** (``serving/engine.py`` asks a model three things; GPT answers
them too): :meth:`NemotronH.serving_cache_spec` (``recurrent`` for ``M``,
``kv`` sized by the KEY/VALUE heads for ``*``, ``none`` for ``E``),
:meth:`serve_prefill` (one right-padded prompt; each layer hands back what
the slot keeps: the state after the prompt's LAST REAL position and the
convolution's tail there, or its keys and values) and :meth:`serve_decode`
(one token a row through the slots' state and the paged pool). The MTP
module of the published model is not here: plain generation never
evaluates it.
"""

import math
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.glm4_moe_lite import RMSNorm, _dense
from deepspeed_tpu.moe.dropless import (SERVING_COUNTERS, DroplessMoE,
                                        DroplessMoEConfig,
                                        dropless_partition_rules)
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.ops.transformer.attention import attention
from deepspeed_tpu.telemetry.tracer import device_scope

F32 = jnp.float32
PREFILL = "prefill"         # ``cache=PREFILL``: hand back what a slot keeps


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    pattern: str = "EMEMEMEMEM*"
    max_seq_len: int = 2048     # no position table: what serving reserves
    # attention
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    # Mamba-2
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # latent experts
    n_routed_experts: int = 512         # the router's width
    n_held_experts: int = 128           # whose weights live on this chip
    first_held_expert: int = 0
    experts_per_token: int = 22
    moe_intermediate: int = 2688
    moe_latent: int = 1024
    shared_intermediate: int = 5376
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if not self.pattern or set(self.pattern) - set("M*E"):
            raise ValueError(f"pattern {self.pattern!r}: one of 'M', '*', "
                             f"'E' a layer")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("mamba_num_heads must be a multiple of n_groups")

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def moe(self) -> DroplessMoEConfig:
        return DroplessMoEConfig(
            hidden_size=self.hidden_size,
            expert_intermediate=self.moe_intermediate,
            n_routed_experts=self.n_routed_experts,
            n_held_experts=self.n_held_experts,
            first_held_expert=self.first_held_expert,
            experts_per_token=self.experts_per_token,
            shared_intermediate=self.shared_intermediate,
            routed_scaling_factor=self.routed_scaling_factor,
            norm_topk_prob=self.norm_topk_prob, dtype=self.dtype,
            latent_size=self.moe_latent, activation="relu2", out_dtype=F32,
            shared_two_pass=True)


TINY = NemotronHConfig(
    vocab_size=512, hidden_size=64, pattern="EM*", max_seq_len=128,
    num_heads=4, num_kv_heads=2, head_dim=16, mamba_num_heads=3,
    mamba_head_dim=16, n_groups=1, ssm_state_size=16, chunk_size=8,
    n_routed_experts=8, n_held_experts=4, experts_per_token=2,
    moe_intermediate=48, moe_latent=32, shared_intermediate=96,
    routed_scaling_factor=2.5, dtype=jnp.float32)


def _conv_init(width: int):
    """Uniform in +-1/sqrt(K): what a depthwise Conv1d of kernel ``K``
    starts from, weight and bias, where nobody says otherwise (the Mamba-2
    reference does not)."""
    bound = 1.0 / math.sqrt(width)
    return lambda key, shape, dtype=F32: jax.random.uniform(
        key, shape, dtype, -bound, bound)


def _out_proj(features: int, dtype, name: str) -> nn.Dense:
    """A mixer's last matmul: inputs in ``dtype``, the float32 accumulator
    handed over as it is, so that the residual add rounds ONCE (mixer
    output + stream, then to ``dtype``) where a rounded output added to
    the stream rounds twice. With 22 of 512 experts a token, what reaches
    the next router decides near ties: every rounding spared is fewer
    choices flipped against a float32 forward."""
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name,
                    kernel_init=nn.initializers.normal(0.02),
                    dot_general=partial(jax.lax.dot_general,
                                        preferred_element_type=F32))


def _prefilling(cache) -> bool:
    return isinstance(cache, str) and cache == PREFILL


def _dt_bias_init(lo: float, hi: float, floor: float):
    """The inverse softplus of a step drawn log-uniform in ``[lo, hi]`` and
    floored: Mamba-2's, what ``time_step_min`` / ``_max`` / ``_floor`` of
    the config are for."""
    def init(key, shape, dtype=F32):
        dt = jnp.exp(jax.random.uniform(key, shape, F32)
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


class Mamba2Mixer(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, x, cache=None, length=None):
        """``x [B, S, hidden]``. ``cache``: None (a plain forward),
        ``PREFILL`` (also return ``(state, tail)`` after position
        ``length - 1``) or the slots' ``RecurrentLayerState`` (S is 1).
        Returns ``(y, what the cache becomes)``."""
        cfg = self.cfg
        b, s, _ = x.shape
        heads, p = cfg.mamba_num_heads, cfg.mamba_head_dim
        g, n, dt_ = cfg.n_groups, cfg.ssm_state_size, cfg.dtype
        wide = _dense(cfg.d_inner + cfg.conv_dim + heads, dt_, "in_proj")(x)
        z, xbc, dt = jnp.split(
            wide, [cfg.d_inner, cfg.d_inner + cfg.conv_dim], axis=-1)
        conv_init = _conv_init(cfg.conv_kernel)
        kernel = self.param("conv_kernel", conv_init,
                            (cfg.conv_kernel, cfg.conv_dim), F32)
        bias = self.param("conv_bias", conv_init, (cfg.conv_dim,), F32)
        a = -jnp.exp(self.param(
            "A_log", lambda *_: jnp.log(jnp.arange(1, heads + 1, dtype=F32)),
            (heads,), F32).astype(F32))
        d = self.param("D", nn.initializers.ones, (heads,), F32)
        dt = jax.nn.softplus(dt.astype(F32) + self.param(
            "dt_bias", _dt_bias_init(cfg.time_step_min, cfg.time_step_max,
                                     cfg.time_step_floor),
            (heads,), F32).astype(F32))

        decoding = cache is not None and not _prefilling(cache)
        state, tail = cache.arrays if decoding else (None, None)
        conv, new_tail = ssm.causal_conv(xbc, kernel, bias, tail, length)
        xbc = jax.nn.silu(conv).astype(dt_)
        u, bm, cm = jnp.split(xbc, [cfg.d_inner, cfg.d_inner + g * n], -1)
        u = u.reshape(b, s, heads, p)
        bm, cm = bm.reshape(b, s, g, n), cm.reshape(b, s, g, n)
        if decoding:
            y, new_state = ssm.ssm_step(u[:, 0], dt[:, 0], a, bm[:, 0],
                                        cm[:, 0], d, state, cache.live)
            y = y[:, None]
            new_tail = jnp.where(cache.live[:, None, None],
                                 new_tail.astype(tail.dtype), tail)
            cache = cache.replaced((new_state, new_tail))
        else:
            if length is not None:      # the padding moves no state
                dt = jnp.where((jnp.arange(s) < length)[None, :, None],
                               dt, 0.0)
            y, new_state = ssm.ssm_scan(u, dt, a, bm, cm, d,
                                        chunk=cfg.chunk_size)
            if _prefilling(cache):
                cache = (new_state, new_tail)
        # gate, then a norm within each group of channels
        y = y.reshape(b, s, cfg.d_inner) * jax.nn.silu(z.astype(F32))
        grouped = y.reshape(b, s, g, cfg.d_inner // g)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, -1, keepdims=True) + cfg.rms_eps)
        weight = self.param("norm_weight", nn.initializers.ones,
                            (cfg.d_inner,), F32)
        y = (grouped.reshape(b, s, cfg.d_inner)
             * weight.astype(F32)).astype(dt_)
        return _out_proj(cfg.hidden_size, dt_, "out_proj")(y), cache


class GroupedQueryAttention(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, x, cache=None):
        """``cache``: None, ``PREFILL`` (also return this prompt's ``(k,
        v)``, ``[B, S, kv_heads, d]``) or the layer's ``PagedLayerCache``
        (the chunk is written and the row's whole window read)."""
        cfg = self.cfg
        b, s, _ = x.shape
        h, kvh, hd, dt = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                          cfg.dtype)
        q = _dense(h * hd, dt, "q_proj")(x).reshape(b, s, h, hd)
        k = _dense(kvh * hd, dt, "k_proj")(x).reshape(b, s, kvh, hd)
        v = _dense(kvh * hd, dt, "v_proj")(x).reshape(b, s, kvh, hd)
        if cache is None or _prefilling(cache):
            # right-padding lies after every real position: causality
            # alone keeps it out of their attention
            wide = partial(jnp.repeat, repeats=h // kvh, axis=2)
            o = attention(q, wide(k), wide(v), causal=True,
                          deterministic=True)
            cache = (k, v) if cache is not None else None
        else:
            cache, keys, values, seen = cache.update(k, v)
            # [B, S, kvh, group, d] against [B, L, kvh, d]: every query
            # head of a group reads its key/value head, nothing repeated
            scores = jnp.einsum(
                "bskgd,blkd->bkgsl", q.reshape(b, s, kvh, h // kvh, hd),
                keys, preferred_element_type=F32) * hd ** -0.5
            scores = jnp.where(seen[:, :, None], scores,
                               jnp.finfo(F32).min)
            probs = jax.nn.softmax(scores, axis=-1)
            o = jnp.einsum("bkgsl,blkd->bskgd", probs.astype(dt), values,
                           preferred_element_type=F32).astype(dt)
        return _out_proj(cfg.hidden_size, dt, "o_proj")(
            o.reshape(b, s, h * hd)), cache


class NemotronH(nn.Module):
    """``__call__(batch)`` -> ``{"logits"}`` (float32, ``[B, S, vocab]``);
    with ``cache`` also ``"cache"`` (a layer's entry each) and
    ``"counters"`` (``SERVING_COUNTERS`` over the expert layers, int32:
    assignments, touched experts and rows computed summed, the fullest
    expert's rows the largest)."""

    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, batch, deterministic: bool = True, cache=None,
                 live=None, length=None):
        cfg = self.cfg
        ids = batch["input_ids"]
        embed = self.param("embed_tokens", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size), F32)
        x = total = embed[ids].astype(cfg.dtype)
        serving = cache is not None
        if serving and live is None:    # a prompt: what is not padding
            live = (jnp.arange(ids.shape[1]) < length)[None]
        norm = partial(RMSNorm, cfg.rms_eps, cfg.dtype)
        new_cache, counted = [], []
        for i, kind in enumerate(cfg.pattern):
            # an expert layer's norm stays float32: the router's scores
            # are float32 from float32 inputs, so that only the bfloat16
            # of the stream itself can flip a near tie among 512 experts
            # (the experts and the shared expert round it to ``dtype``)
            h = (RMSNorm(cfg.rms_eps, F32, name=f"norm_{i}")(total)
                 if kind == "E" else norm(name=f"norm_{i}")(total))
            mine = None if not serving else (
                cache if _prefilling(cache) else cache[i])
            if kind == "M":
                with device_scope("ssm"):
                    y, kept = Mamba2Mixer(cfg, name=f"mixer_{i}")(
                        h, mine, length)
            elif kind == "*":
                with device_scope("attn"):
                    y, kept = GroupedQueryAttention(
                        cfg, name=f"mixer_{i}")(h, mine)
            else:
                rows = (jnp.broadcast_to(live.reshape(live.shape[0], -1),
                                         ids.shape) if serving else None)
                y, counters = DroplessMoE(cfg.moe(), name=f"mixer_{i}")(
                    h, live=rows)
                kept = None
                counted.append(counters)
            new_cache.append(kept)
            # the stream is ``dtype`` (residual_in_fp32 false); the mixer's
            # float32 result is added before the one rounding, and the
            # NEXT norm reads the sum as it is before that rounding: what
            # is carried on is ``x``, what the layer after it sees of this
            # layer is not rounded on the way (fewer flipped choices again)
            total = x.astype(F32) + y.astype(F32)
            x = total.astype(cfg.dtype)
        x = norm(name="norm_f")(total)
        out = {"logits": _dense(cfg.vocab_size, cfg.dtype, "lm_head")(
            x).astype(F32)}
        if serving:
            out["cache"] = tuple(new_cache)
            if counted:
                out["counters"] = jnp.stack([
                    jnp.stack([c[k] for c in counted]).max()
                    if k == "held_rows_max" else sum(c[k] for c in counted)
                    for k in SERVING_COUNTERS]).astype(jnp.int32)
        return out

    # -- what the serving engine asks of a model ------------------------
    def serving_cache_spec(self) -> Tuple:
        from deepspeed_tpu.serving.kv_cache import kv, none, recurrent
        cfg = self.cfg
        mamba = recurrent({
            "state": ((cfg.mamba_num_heads, cfg.mamba_head_dim,
                       cfg.ssm_state_size), F32),
            "conv_tail": ((cfg.conv_kernel - 1, cfg.conv_dim), cfg.dtype)})
        by_kind = {"M": mamba, "*": kv(cfg.num_kv_heads, cfg.head_dim),
                   "E": none()}
        return tuple(by_kind[kind] for kind in cfg.pattern)

    SERVING_COUNTERS = tuple("moe_" + name for name in SERVING_COUNTERS)

    def serve_prefill(self, params, ids, length, dtype=None):
        """One right-padded prompt ``ids [1, bucket]`` of ``length`` real
        tokens -> ``{"logits", "cache", "counters"}``; ``cache[i]`` is
        ``(k, v)`` ``[1, bucket, kv_heads, d]`` for a ``kv`` layer, the
        arrays of its spec (``[1, *shape]`` each) for a ``recurrent`` one."""
        return self.apply({"params": params}, {"input_ids": ids},
                          cache=PREFILL, length=length)

    def serve_decode(self, params, ids, pos_ids, cache, live=None):
        """One token a row, ``ids [slots, 1]``, through ``cache`` (a
        layer's view each: ``PagedLayerCache``, ``RecurrentLayerState``,
        None); ``live [slots]`` bool marks the rows that hold a request.
        Positions are not embedded (``pos_ids`` is the engine's business:
        it sits in the cache views)."""
        if ids.shape[1] != 1:
            raise ValueError(
                "a recurrent layer's state advances one position a step: "
                f"serve_decode takes one token a row, got {ids.shape[1]}")
        return self.apply({"params": params}, {"input_ids": ids},
                          cache=cache, live=live)


def nemotron_h_partition_rules() -> Tuple[Tuple[str, Optional[Tuple]], ...]:
    """The held experts' leading axis on ``expert``; everything else
    replicated (each chip serves its own requests)."""
    return dropless_partition_rules()


def make_nemotron_h(cfg: NemotronHConfig = TINY, **overrides
                    ) -> Tuple[NemotronH, NemotronHConfig]:
    if overrides:
        cfg = replace(cfg, **overrides)
    return NemotronH(cfg), cfg
