"""GLM-4.7-Flash's block (``model_type`` ``glm4_moe_lite``): RMSNorm,
multi-head latent attention, gated MLPs, sigmoid top-k dropless experts
beside a shared expert, an untied head, and a multi-token-prediction
module that shares the embedding and the head.

The configuration's names are the published ``config.json``'s. Three of
them can describe one chip's share of a layer that several chips divide
(expert parallelism; docs/MOE.md): ``n_held_experts`` /
``first_held_expert`` say which of the ``n_routed_experts`` the router
scores live here, and ``vocab_size`` may be a slice of the vocabulary's
rows. The model then computes what that chip computes: its experts' part
of every expert layer (``moe/dropless.py``), and logits, loss and ids over
its rows.

Equations (``x`` is ``[T, hidden]``; every norm is RMSNorm with a learned
scale; no bias anywhere):

- block: ``x += MLA(norm(x))``; ``x += FFN(norm(x))``; the FFN is a SwiGLU
  of ``intermediate_size`` in the first ``first_k_dense_replace`` layers
  and the expert layer after them;
- MLA: ``c_q = norm(x W_qa)``, ``q = c_q W_qb`` per head ``[nope | rope]``;
  ``[c_kv | k_r] = x W_kva``, ``c_kv = norm(c_kv)``, ``[k_nope | v] =
  c_kv W_kvb`` per head; ``k_r`` is ONE rotary key per position, shared by
  all heads; RoPE (``rope_theta``, rotate-half pairing) on ``q_rope`` and
  ``k_r``; causal softmax attention over ``q = [q_nope | q_rope]``,
  ``k = [k_nope | k_r]`` scaled by ``1/sqrt(nope + rope)``; ``W_o``;
- MTP (depth 1): ``h' = [norm_e(Emb(t_{i+1})) | norm_h(h_i)] W_eh`` with
  ``h`` the last block's output before the final norm, one block, a norm,
  the main head: logits for ``t_{i+2}``;
- ``loss = CE_main + mtp_loss_weight * CE_mtp``, each a mean over its own
  labelled positions.

Attention goes through ``ops/transformer/attention.py`` (the flash kernels
take it as head_dim ``nope + rope`` when ``v_head_dim`` equals it), the
loss through ``ops/xent.py``.
"""

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.moe.dropless import (DroplessMoE, DroplessMoEConfig,
                                        dropless_partition_rules)
from deepspeed_tpu.ops.embedding import embedding_lookup
from deepspeed_tpu.ops.transformer.attention import attention
from deepspeed_tpu.ops.xent import fused_cross_entropy
from deepspeed_tpu.telemetry.tracer import device_scope

IGNORE = -100


@dataclass(frozen=True)
class Glm4MoeLiteConfig:
    # the published keys (defaults: zai-org/GLM-4.7-Flash)
    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 64          # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    num_nextn_predict_layers: int = 1
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    # the chip's share of each expert layer (None: every routed expert)
    n_held_experts: Optional[int] = None
    first_held_expert: int = 0
    # not in the published config; DeepSeek-V3's, whose module this is
    mtp_loss_weight: float = 0.3
    dtype: Any = jnp.bfloat16           # activation/compute dtype
    remat: bool = False                 # recompute each block in backward

    def __post_init__(self):
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("multi-token prediction is built at depth 0 "
                             f"or 1, not {self.num_nextn_predict_layers}")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def moe(self) -> DroplessMoEConfig:
        held = (self.n_routed_experts if self.n_held_experts is None
                else self.n_held_experts)
        return DroplessMoEConfig(
            hidden_size=self.hidden_size,
            expert_intermediate=self.moe_intermediate_size,
            n_routed_experts=self.n_routed_experts, n_held_experts=held,
            first_held_expert=self.first_held_expert,
            experts_per_token=self.num_experts_per_tok,
            shared_intermediate=(self.n_shared_experts
                                 * self.moe_intermediate_size),
            routed_scaling_factor=self.routed_scaling_factor,
            norm_topk_prob=self.norm_topk_prob, dtype=self.dtype)


# The CPU tests' preset: every mechanism, nothing at its published size.
TINY = Glm4MoeLiteConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
    num_experts_per_tok=2)


def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name,
                    kernel_init=nn.initializers.normal(0.02))


class RMSNorm(nn.Module):
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        weight = self.param("weight", nn.initializers.ones, (x.shape[-1],),
                            jnp.float32)
        x32 = x.astype(jnp.float32)
        scale = jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True)
                              + self.eps)
        return (x32 * scale * weight.astype(jnp.float32)).astype(self.dtype)


def rotate(x: jax.Array, theta: float) -> jax.Array:
    """RoPE over the last axis of ``x [B, S, ..., d]`` at positions
    ``0..S-1``: pairs ``(i, i + d/2)`` turn by ``pos * theta**(-2i/d)``
    (the rotate-half pairing), in float32."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    angle = angle.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


class LatentAttention(nn.Module):
    cfg: Glm4MoeLiteConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dt, heads = cfg.dtype, cfg.num_attention_heads
        nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        b, s, _ = x.shape
        norm = partial(RMSNorm, cfg.rms_norm_eps, dt)

        c_q = norm(name="q_a_layernorm")(
            _dense(cfg.q_lora_rank, dt, "q_a_proj")(x))
        q = _dense(heads * (nope + rope), dt, "q_b_proj")(c_q)
        q = q.reshape(b, s, heads, nope + rope)
        q = jnp.concatenate(
            [q[..., :nope], rotate(q[..., nope:], cfg.rope_theta)], -1)

        kv = _dense(cfg.kv_lora_rank + rope, dt, "kv_a_proj_with_mqa")(x)
        c_kv = norm(name="kv_a_layernorm")(kv[..., :cfg.kv_lora_rank])
        k_rope = rotate(kv[..., cfg.kv_lora_rank:], cfg.rope_theta)
        kv = _dense(heads * (nope + vd), dt, "kv_b_proj")(c_kv)
        kv = kv.reshape(b, s, heads, nope + vd)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope[:, :, None], (b, s, heads, rope))], -1)

        # The flash kernels take one head size for queries, keys and
        # values; a value head of another size takes the XLA path.
        impl = "auto" if vd == nope + rope else "xla"
        o = attention(q, k, kv[..., nope:], causal=True, impl=impl)
        return _dense(cfg.hidden_size, dt, "o_proj")(
            o.reshape(b, s, heads * vd))


class GatedMLP(nn.Module):
    intermediate: int
    hidden: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        gate = _dense(self.intermediate, self.dtype, "gate_proj")(x)
        up = _dense(self.intermediate, self.dtype, "up_proj")(x)
        return _dense(self.hidden, self.dtype, "down_proj")(
            nn.silu(gate) * up)


class Glm4MoeLiteBlock(nn.Module):
    """``x -> (x, counters)``; ``counters`` is the expert layer's (empty
    for a dense layer)."""

    cfg: Glm4MoeLiteConfig
    dense: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        norm = partial(RMSNorm, cfg.rms_norm_eps, cfg.dtype)
        with device_scope("mla"):
            x = x + LatentAttention(cfg, name="self_attn")(
                norm(name="input_layernorm")(x))
        h = norm(name="post_attention_layernorm")(x)
        if self.dense:
            return x + GatedMLP(cfg.intermediate_size, cfg.hidden_size,
                                cfg.dtype, name="mlp")(h), {}
        y, counters = DroplessMoE(cfg.moe, name="mlp")(h)
        return x + y, counters


class Glm4MoeLite(nn.Module):
    """Causal LM. ``__call__(batch)`` returns ``{"loss", "logits",
    "mtp_logits", "step_counters"}`` (``flax_module_loss_fn`` takes it as
    it takes GPT); ``batch`` is ``{"input_ids": [B, S]}`` with optional
    ``labels`` (next-token labels, ``-100`` ignored). The MTP labels are
    the main ones shifted once more."""

    cfg: Glm4MoeLiteConfig

    @nn.compact
    def __call__(self, batch, deterministic: bool = False):
        del deterministic               # no dropout in this family
        cfg = self.cfg
        dt = cfg.dtype
        ids = batch["input_ids"]
        embed = self.param("embed_tokens", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        norm = partial(RMSNorm, cfg.rms_norm_eps, dt)
        block = (nn.remat(Glm4MoeLiteBlock) if cfg.remat
                 else Glm4MoeLiteBlock)

        def left(t, fill):
            return jnp.pad(t[:, 1:], ((0, 0), (0, 1)), constant_values=fill)

        def logits_and_loss(h, labels):
            h = h.astype(dt)
            logits = jnp.einsum("bsd,vd->bsv", h, head.astype(dt),
                                preferred_element_type=jnp.float32)
            return logits, fused_cross_entropy(h, head.astype(dt), labels,
                                               ignore_index=IGNORE)

        per_layer = []
        x = embedding_lookup(embed, ids).astype(dt)
        for i in range(cfg.num_hidden_layers):
            x, counters = block(cfg, dense=i < cfg.first_k_dense_replace,
                                name=f"layers_{i}")(x)
            per_layer.append(counters)
        labels = batch.get("labels")
        if labels is None:
            labels = left(ids, IGNORE)
        logits, loss = logits_and_loss(norm(name="norm")(x), labels)
        out = {"logits": logits}

        if cfg.num_nextn_predict_layers:
            with device_scope("mtp"):
                # Position i joins what the main model made of t_0..t_i
                # with the embedding of t_{i+1} and predicts t_{i+2}. The
                # last position has no next token: it takes id 0, no one
                # attends to it, and its label is ignored.
                nxt = embedding_lookup(embed, left(ids, 0)).astype(dt)
                h = jnp.concatenate([norm(name="mtp_enorm")(nxt),
                                     norm(name="mtp_hnorm")(x)], -1)
                h = _dense(cfg.hidden_size, dt, "mtp_eh_proj")(h)
                h, counters = block(cfg, name="mtp_block")(h)
                per_layer.append(counters)
                mtp_logits, mtp_loss = logits_and_loss(
                    norm(name="mtp_norm")(h), left(labels, IGNORE))
            loss = loss + cfg.mtp_loss_weight * mtp_loss
            out["mtp_logits"] = mtp_logits

        moe = [c for c in per_layer if c]
        if moe:
            # the mean over the expert layers, the MTP block's among them
            out["step_counters"] = {
                "moe_" + k: sum(c[k] for c in moe) / len(moe)
                for k in moe[0]}
        out["loss"] = loss
        return out


def glm4_moe_lite_partition_rules() -> Tuple[Tuple[str, Optional[Tuple]], ...]:
    """``(regex, dims)`` for ``models.partition.build_specs``: the held
    experts' leading axis on ``expert``; the rows of the embedding and of
    the head, the heads of the up-projections and the gated MLP's inner
    width on ``model``; the low-rank down-projections and every norm
    replicated."""
    return dropless_partition_rules() + (
        (r".*(embed_tokens|lm_head)$", ("model", None)),
        (r".*(q_b_proj|kv_b_proj)/kernel$", (None, "model")),
        (r".*o_proj/kernel$", ("model", None)),
        (r".*mlp/(gate_proj|up_proj)/kernel$", (None, "model")),
        (r".*mlp/down_proj/kernel$", ("model", None)),
    )


def make_glm4_moe_lite(cfg: Glm4MoeLiteConfig = TINY, **overrides
                       ) -> Tuple[Glm4MoeLite, Glm4MoeLiteConfig]:
    if overrides:
        cfg = replace(cfg, **overrides)
    return Glm4MoeLite(cfg), cfg
