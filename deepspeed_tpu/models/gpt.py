"""GPT-2 family — flagship causal-LM models, TPU-first.

The reference ships no model zoo of its own; its flagship benchmarks wrap
Megatron GPT-2 (``tests/model/Megatron_GPT2``, ``docs/_tutorials/megatron.md``).
Here the GPT family is in-tree flax so every subsystem (ZeRO, TP, pipeline,
sequence parallel, kernels) has a first-class target.

TPU-first choices:
- combined QKV projection (one big [D, 3D] matmul for the MXU, the same
  layout the reference's fused kernel uses via ``attn_qkvw``);
- bf16 activations with fp32 LayerNorm/softmax;
- attention goes through ``deepspeed_tpu.ops.transformer.attention`` so the
  Pallas flash kernel is a config flag, not a model rewrite;
- optional ``jax.checkpoint`` (remat) per block — activation checkpointing
  (reference ``runtime/activation_checkpointing/checkpointing.py``) as a
  model-level policy;
- tensor-parallel PartitionSpecs provided by ``gpt_partition_rules()``:
  attention/MLP weights split over the ``model`` axis Megatron-style
  (column-parallel qkv/fc-in, row-parallel proj/fc-out).
"""

from dataclasses import dataclass, field, replace
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.attention import attention
from deepspeed_tpu.ops.xent import fused_cross_entropy


from deepspeed_tpu.ops.dropout import dropout_module as _dropout_mod


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_ratio: int = 4
    dropout_rate: float = 0.1
    dtype: Any = jnp.bfloat16          # activation/compute dtype
    attention_impl: str = "auto"
    remat: bool = False                 # activation checkpointing per block
    tie_embeddings: bool = True
    layer_norm_epsilon: float = 1e-5
    fused_ce: bool = True               # ops/xent.py fused CE head
    # exact fp32-logits numerics inside the fused CE (parity-sensitive
    # bf16 runs; costs the fp32 [N,V] HBM pass the fused op avoids)
    fused_ce_fp32_logits: bool = False
    # None -> 1/sqrt(head_dim); GPT-Neo trains UNSCALED attention (1.0)
    attention_scale: Any = None
    # Row-sparse cross-rank embedding-grad exchange (config
    # `sparse_gradients: true` — reference engine.py:1530-1586):
    # (mesh, axes) — what deepspeed_tpu.initialize() bakes in (the
    # ENGINE's mesh, never the ambient default) — or True / a bare axes
    # tuple for custom loops (resolved against the ambient mesh).
    sparse_embedding_grad: Any = None
    # Counter-hash activation dropout (ops/dropout.py) instead of flax's
    # threefry bernoulli — the reference's fused-dropout economy
    # (csrc/transformer/dropout_kernels.cu); measured A/B in PROFILE.md.
    fast_dropout: bool = True
    # Block-sparse attention config dict (the DeepSpeed `sparse_attention`
    # block: mode/block/num_local_blocks/...). When set, training attention
    # routes through ops.sparse_attention (long-sequence O(s·√s) path);
    # decode (kv_cache) stays dense. deepspeed_tpu.initialize() injects
    # this from the engine config automatically.
    sparse_attention: Any = None
    # MoE-GPT (the GShard/Switch "every other layer is MoE" family): with
    # moe_experts > 0, every moe_layer_freq-th block's FFN becomes a
    # deepspeed_tpu.moe.MoE layer (expert-parallel via moe_partition_rules)
    # and the load-balance aux losses fold into the training loss.
    moe_experts: int = 0
    moe_k: int = 1
    moe_layer_freq: int = 2            # every Nth block is MoE
    moe_capacity_factor: float = 1.25
    moe_aux_alpha: float = 0.01
    moe_eval_capacity_factor: float = 2.0
    moe_min_capacity: int = 4
    moe_router_jitter: float = 0.0     # train-only router input jitter
    # Dispatch mode: "scatter" | "einsum" | "alltoall" (moe/dispatch.py —
    # the explicit expert-axis exchange; needs moe_mesh or the ambient
    # default mesh). deepspeed_tpu.initialize() injects these from the
    # engine's `moe` config block, pinning the ENGINE's mesh like
    # sparse_embedding_grad.
    moe_dispatch: str = "scatter"
    moe_mesh: Any = None
    # When True the model output dict grows moe_* stat scalars (mean over
    # the MoE layers; dispatch bytes summed) for the engine's moe/*
    # gauges (telemetry/moe.py).
    moe_stats: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def num_params(self) -> int:
        d, l, v = self.hidden_size, self.num_layers, self.vocab_size
        per_layer = 12 * d * d + 13 * d
        return v * d + self.max_seq_len * d + l * per_layer + 2 * d


# Named configurations (sizes follow the public GPT-2 family).
GPT_CONFIGS: Dict[str, GPTConfig] = {
    "tiny": GPTConfig(vocab_size=512, max_seq_len=128, hidden_size=64,
                      num_layers=2, num_heads=4, dropout_rate=0.0),
    "gpt2": GPTConfig(hidden_size=768, num_layers=12, num_heads=12),
    "gpt2-medium": GPTConfig(hidden_size=1024, num_layers=24, num_heads=16),
    "gpt2-large": GPTConfig(hidden_size=1280, num_layers=36, num_heads=20),
    "gpt2-xl": GPTConfig(hidden_size=1600, num_layers=48, num_heads=25),
}


class GPTBlock(nn.Module):
    """Pre-LN transformer block (attention + MLP or MoE FFN).

    With ``moe=True`` the dense MLP is replaced by a
    :class:`deepspeed_tpu.moe.MoE` layer and the return value grows a
    trailing load-balance aux-loss scalar."""

    cfg: GPTConfig
    moe: bool = False

    @nn.compact
    def __call__(self, x, attn_mask=None, deterministic: bool = True,
                 kv_cache=None, pos=None):
        """``kv_cache``: optional ``(k, v)`` arrays of shape
        [B, max_len, H, Dh] for incremental decoding (the TPU-native analogue
        of the reference inference kernels' attention cache,
        csrc/transformer/inference/). With a cache, new k/v are written at
        ``pos`` and attention runs over the full cache under a
        position-validity mask (static shapes — jit/scan friendly). A
        non-tuple cache is taken as a paged-cache layer view
        (``serving/kv_cache.PagedLayerCache``): it owns the write/gather
        and per-row positions (continuous batching). Returns
        ``(x, cache')`` in cache mode, plain ``x`` otherwise.
        """
        cfg = self.cfg
        d = cfg.hidden_size
        dt = cfg.dtype
        h = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon,
                         dtype=jnp.float32, name="ln_1")(x).astype(dt)
        qkv = nn.Dense(3 * d, dtype=dt, name="c_attn")(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        b, s = q.shape[0], q.shape[1]
        shape = (b, s, cfg.num_heads, cfg.head_dim)
        q, k, v = q.reshape(shape), k.reshape(shape), v.reshape(shape)
        drop_rng = (None if deterministic or cfg.dropout_rate == 0.0
                    else self.make_rng("dropout"))
        if kv_cache is not None:
            if isinstance(kv_cache, tuple):
                ck, cv = kv_cache
                ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                                  (0, pos, 0, 0))
                cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                                  (0, pos, 0, 0))
                kv_cache = (ck, cv)
                # Key j is visible to query i iff j <= pos + i (cached past
                # plus the causal prefix of this chunk).
                qpos = pos + jnp.arange(s)
                kpos = jnp.arange(ck.shape[1])
                dec_mask = (kpos[None, :] <= qpos[:, None])[None, None]
                if attn_mask is not None:
                    dec_mask = jnp.logical_and(dec_mask, attn_mask)
                o = attention(q, ck, cv, causal=False, mask=dec_mask,
                              deterministic=True, impl="xla",
                              softmax_scale=cfg.attention_scale)
            elif (getattr(kv_cache, "live", None) is not None
                    and attn_mask is None):
                # Serving's default decode: over the live blocks' list.
                kv_cache, o = kv_cache.attend_live(
                    q, k, v, softmax_scale=cfg.attention_scale)
            elif (getattr(kv_cache, "attn_impl", "gather")
                    in ("kernel", "chunked") and attn_mask is None):
                # Paged decode fast path: the Pallas kernel streams K/V
                # blocks through the block table, no gathered copy made
                # ("chunked": the ragged mixed batch). Visibility as below.
                kv_cache, o = kv_cache.update_attend(
                    q, k, v, softmax_scale=cfg.attention_scale)
            else:
                # Paged decode (serving/kv_cache.py): the cache object
                # scatters this chunk through its block table at per-ROW
                # positions and hands back the gathered static-shape K/V
                # plus its own visibility mask — rows in a continuous
                # batch sit at different sequence lengths, so the scalar
                # ``pos`` is unused here.
                kv_cache, ck, cv, dec_mask = kv_cache.update(k, v)
                if attn_mask is not None:
                    dec_mask = jnp.logical_and(dec_mask, attn_mask)
                o = attention(q, ck, cv, causal=False, mask=dec_mask,
                              deterministic=True, impl="xla",
                              softmax_scale=cfg.attention_scale)
        elif cfg.sparse_attention is not None:
            # Config-driven block-sparse path (reference
            # sparse_attention_utils.py model surgery). Attention-prob
            # dropout is not applied under the sparse executor (the
            # reference's sparse path likewise has none); residual/MLP
            # dropouts still apply.
            from deepspeed_tpu.ops.sparse_attention.utils import \
                get_sparse_self_attention

            ssa = get_sparse_self_attention(cfg.sparse_attention,
                                            cfg.num_heads)
            km = None
            if attn_mask is not None:
                km = attn_mask[:, 0, 0, :]   # [B,1,1,S] -> [B,S] key mask
            o = ssa(q, k, v, causal=True, key_mask=km,
                    softmax_scale=cfg.attention_scale)
        else:
            o = attention(q, k, v, causal=True, mask=attn_mask,
                          dropout_rate=cfg.dropout_rate, dropout_rng=drop_rng,
                          deterministic=deterministic, impl=cfg.attention_impl,
                          softmax_scale=cfg.attention_scale)
        o = o.reshape(b, s, d)
        o = nn.Dense(d, dtype=dt, name="c_proj")(o)
        o = _dropout_mod(cfg)(cfg.dropout_rate, deterministic=deterministic)(o)
        x = x + o

        aux = None
        h = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon,
                         dtype=jnp.float32, name="ln_2")(x).astype(dt)
        if self.moe:
            from deepspeed_tpu.moe import MoE, MoEConfig

            moe_out = MoE(MoEConfig(
                hidden_size=d, num_experts=cfg.moe_experts, k=cfg.moe_k,
                capacity_factor=cfg.moe_capacity_factor,
                eval_capacity_factor=cfg.moe_eval_capacity_factor,
                min_capacity=cfg.moe_min_capacity,
                router_jitter=cfg.moe_router_jitter,
                dispatch=cfg.moe_dispatch, mesh=cfg.moe_mesh,
                stats=cfg.moe_stats,
                expert_intermediate=cfg.mlp_ratio * d, dtype=dt),
                name="moe")(h, deterministic=deterministic)
            if cfg.moe_stats:
                # Bundle (aux, stats) so the block's return arity
                # stays fixed; GPT unpacks the pair.
                h, aux_loss, moe_stats = moe_out
                aux = (aux_loss, moe_stats)
            else:
                h, aux = moe_out
        else:
            h = nn.Dense(cfg.mlp_ratio * d, dtype=dt, name="c_fc")(h)
            h = nn.gelu(h, approximate=True)
            h = nn.Dense(d, dtype=dt, name="mlp_proj")(h)
        h = _dropout_mod(cfg)(cfg.dropout_rate, deterministic=deterministic)(h)
        x = x + h
        out = (x, kv_cache) if kv_cache is not None else x
        if self.moe:
            return (out + (aux,)) if isinstance(out, tuple) else (out, aux)
        return out


class GPT(nn.Module):
    """Causal LM. ``__call__(batch)`` returns {"loss", "logits"} so it plugs
    straight into ``deepspeed_tpu.models.adapter.flax_module_loss_fn``.

    batch: {"input_ids": [B,S] int32, optional "labels" (shifted internally if
    absent), optional "attention_mask": [B,S] 1=keep}.
    """

    cfg: GPTConfig

    @nn.compact
    def __call__(self, batch, deterministic: bool = False,
                 cache=None, pos=None):
        """Training/eval: ``__call__(batch)`` → {"loss", "logits"}.

        Incremental decoding (inference engine): pass ``cache`` (per-layer
        tuple of (k, v) arrays from :func:`init_kv_cache`) and the write
        offset ``pos`` → {"logits", "cache"}; no loss is computed.
        """
        cfg = self.cfg
        ids = batch["input_ids"]
        b, s = ids.shape
        wte = self.param("wte", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        wpe = self.param("wpe", nn.initializers.normal(0.01),
                         (cfg.max_seq_len, cfg.hidden_size), jnp.float32)
        pos_ids = batch.get("position_ids") if isinstance(batch, dict) else None
        if pos_ids is not None:
            # Per-row positions [B, S] — left-padded prompts re-base their
            # learned positions so row content starts at position 0.
            pe = jnp.take(wpe, pos_ids, axis=0)
        elif pos is None:
            pe = wpe[:s][None]
        else:
            pe = jnp.take(wpe, pos + jnp.arange(s), axis=0)[None]
        from deepspeed_tpu.ops.embedding import embedding_lookup
        tok = embedding_lookup(
            wte, ids, sparse_grad_axes=cfg.sparse_embedding_grad)
        x = tok.astype(cfg.dtype) + pe.astype(cfg.dtype)
        x = _dropout_mod(cfg)(cfg.dropout_rate, deterministic=deterministic)(x)

        attn_mask = None
        if "attention_mask" in batch and batch["attention_mask"] is not None:
            am = batch["attention_mask"]          # [B, S] 1=keep
            if cache is not None:
                # Cache mode: the key axis is the cache length, not this
                # chunk. A [B, cache_len] mask is taken as the full
                # key-validity mask (fixed across decode — pad slots stay
                # masked); a [B, S] mask covers positions pos..pos+S and
                # keys already cached (< pos) stay visible.
                lmax = (cache[0][0].shape[1] if isinstance(cache[0], tuple)
                        else cache[0].key_len)
                if am.shape[1] == lmax:
                    km = am.astype(jnp.bool_)
                else:
                    if not isinstance(cache[0], tuple):
                        # Paged caches hold PER-ROW positions: a [B, S]
                        # chunk mask has no single key offset to land at,
                        # and splicing it at 0 would silently mask the
                        # wrong keys for every row.
                        raise ValueError(
                            f"paged cache mode takes a full [B, "
                            f"{lmax}] key-validity attention_mask; got "
                            f"{tuple(am.shape)} (per-chunk masks cannot "
                            f"be placed on a shared key axis with "
                            f"per-row positions)")
                    km = jnp.ones((b, lmax), jnp.bool_)
                    km = jax.lax.dynamic_update_slice(
                        km, am.astype(jnp.bool_),
                        (0, pos if pos is not None else 0))
                attn_mask = km[:, None, None, :]
            else:
                attn_mask = am[:, None, None, :].astype(jnp.bool_)

        block = GPTBlock
        if cfg.remat:
            block = nn.remat(GPTBlock, static_argnums=(3,))
        # Bucket-boundary grad-sync markers (comm/overlap.py): each block
        # reads its params through an identity marker whose custom_vjp
        # backward reduce-scatters the block's grads over ICI *between*
        # the layer backwards — the intra-backward overlap axis of the
        # overlapped gradient sync (docs/PERFORMANCE.md). Inert (zero
        # trace footprint) unless the engine's grad-sync plan installs
        # its hook; wrapping sits OUTSIDE remat so the scatter is not
        # rematerialized.
        from deepspeed_tpu.comm.overlap import marked_block

        def layer_block(i):
            return marked_block(block, f"h_{i}")(
                cfg, moe=is_moe(i), name=f"h_{i}")
        # Progressive Layer Drop (reference progressive_layer_drop.py +
        # engine hooks): per-step keep prob p_l = 1 - l/L * (1 - theta);
        # the engine injects batch["pld_theta"] when pld.enabled.
        pld_theta = batch.get("pld_theta") if isinstance(batch, dict) else None
        new_cache = []
        aux_total = jnp.float32(0.0)
        moe_layer_stats = []

        def is_moe(i):
            return (cfg.moe_experts > 0
                    and i % cfg.moe_layer_freq == cfg.moe_layer_freq - 1)

        for i in range(cfg.num_layers):
            if cache is not None:
                out = layer_block(i)(x, attn_mask, True, cache[i], pos)
                x, layer_kv = out[0], out[1]   # aux (if any) unused in decode
                new_cache.append(layer_kv)
            else:
                y = layer_block(i)(x, attn_mask, deterministic)
                aux_i = None
                if is_moe(i):
                    y, aux_i = y
                    if cfg.moe_stats:
                        aux_i, stats_i = aux_i
                        moe_layer_stats.append(stats_i)
                if pld_theta is not None and not deterministic:
                    from deepspeed_tpu.runtime.progressive_layer_drop import \
                        pld_keep_gate
                    gate = pld_keep_gate(self.make_rng("dropout"), i,
                                         cfg.num_layers, pld_theta)
                    y = jnp.where(gate, y, x)
                    if aux_i is not None:
                        # a PLD-dropped MoE layer contributed nothing —
                        # its balance loss must not push its router
                        aux_i = jnp.where(gate, aux_i, 0.0)
                if aux_i is not None:
                    aux_total = aux_total + aux_i
                x = y

        x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=jnp.float32,
                         name="ln_f")(x)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x.astype(cfg.dtype),
                                wte.astype(cfg.dtype),
                                preferred_element_type=jnp.float32)
        else:
            logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                              name="lm_head")(x.astype(cfg.dtype)).astype(jnp.float32)

        if cache is not None:
            return {"logits": logits, "cache": tuple(new_cache)}
        # Loss goes through the fused CE head (ops/xent.py): compute-dtype
        # logits, lse-only residual, backward recompute — the [B,S,V] fp32
        # materializations are the single biggest HBM sink at GPT-2 scale
        # (PROFILE.md). The `logits` output above is untouched; XLA
        # dead-code-eliminates it whenever the caller only uses the loss.
        # (A caller reading BOTH loss and logits pays the head matmul twice
        # — the fp32-logits einsum and the fused op's compute-dtype one
        # can't CSE; acceptable for eval loops, free for training.)
        labels = shift_labels(batch)
        if cfg.tie_embeddings and cfg.fused_ce:
            loss = fused_cross_entropy(x.astype(cfg.dtype),
                                       wte.astype(cfg.dtype), labels,
                                       logits_fp32=cfg.fused_ce_fp32_logits)
        else:
            loss = cross_entropy_with_ignore(logits, labels)
        if cfg.moe_experts > 0:
            loss = loss + cfg.moe_aux_alpha * aux_total
        out = {"loss": loss, "logits": logits}
        if moe_layer_stats:
            # moe_* stat scalars for the engine's moe/* gauges
            # (telemetry/moe.py MOE_AUX_KEYS): mean over the MoE layers,
            # except the modeled wire bytes, which sum.
            n = float(len(moe_layer_stats))
            for key in moe_layer_stats[0]:
                total = sum(s[key] for s in moe_layer_stats)
                out["moe_" + key] = (
                    total if key == "dispatch_bytes_ici" else total / n)
        return out


    # -- what the serving engine asks of a model (serving/engine.py) -----
    def serving_cache_spec(self) -> Tuple:
        """One ``LayerCacheSpec`` a layer: every block keeps keys and
        values, a key/value head a query head."""
        from deepspeed_tpu.serving.kv_cache import kv
        return tuple(kv(self.cfg.num_heads, self.cfg.head_dim)
                     for _ in range(self.cfg.num_layers))

    def serve_prefill(self, params, ids, length, dtype=None):
        """One right-padded prompt ``ids [1, bucket]`` -> ``{"logits",
        "cache"}``, ``cache[i]`` the layer's ``(k, v)`` ``[1, bucket, H,
        D]``. Causality keeps the padding out of the real positions, so
        ``length`` is not needed here."""
        cache = init_kv_cache(self.cfg, 1, ids.shape[1], dtype=dtype)
        return self.apply({"params": params}, {"input_ids": ids},
                          deterministic=True, cache=cache, pos=0)

    def serve_decode(self, params, ids, pos_ids, cache, live=None):
        """``ids`` / ``pos_ids [rows, S]`` through the paged ``cache`` (a
        layer's view each) -> ``{"logits", "cache"}``."""
        return self.apply({"params": params},
                          {"input_ids": ids, "position_ids": pos_ids},
                          deterministic=True, cache=cache, pos=None)


def init_kv_cache(cfg: GPTConfig, batch_size: int, max_len: int,
                  dtype=None) -> Tuple:
    """Per-layer (k, v) cache arrays [B, max_len, H, Dh] for incremental
    decoding. Static shapes — the decode loop updates in place via
    ``dynamic_update_slice`` so the whole generate fits in one jitted scan."""
    dtype = dtype if dtype is not None else cfg.dtype
    shape = (batch_size, max_len, cfg.num_heads, cfg.head_dim)
    return tuple(
        (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
        for _ in range(cfg.num_layers))


def shift_labels(batch) -> jax.Array:
    """Next-token labels: explicit ``labels`` or input_ids shifted left with
    the trailing position ignored. Shared by the plain and pipeline heads."""
    labels = batch.get("labels")
    if labels is None:
        ids = batch["input_ids"]
        labels = jnp.pad(ids[:, 1:], ((0, 0), (0, 1)), constant_values=-100)
    return labels


def cross_entropy_with_ignore(logits: jax.Array, labels: jax.Array,
                              ignore_index: int = -100) -> jax.Array:
    """Token-mean cross entropy, fp32, ignoring ``ignore_index`` positions."""
    valid = labels != ignore_index
    safe = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, nll, 0.0)
    return nll.sum() / jnp.maximum(valid.sum(), 1)


# ---------------------------------------------------------------------------
# Tensor-parallel partition rules (Megatron-style column/row split)
# ---------------------------------------------------------------------------

def gpt_partition_rules() -> Tuple[Tuple[str, Tuple], ...]:
    """(regex, spec-dims) pairs consumed by models.partition.build_specs —
    the shared Megatron-style block rules plus GPT-specific extras. Mirrors
    the reference's inference TP slicing (module_inject/replace_module.py:11).
    """
    from deepspeed_tpu.models.partition import transformer_block_rules
    from deepspeed_tpu.moe import moe_partition_rules

    return transformer_block_rules() + moe_partition_rules() + (
        (r".*wpe$", (None, None)),
        (r".*lm_head/kernel$", (None, "model")),
    )


def make_gpt(name_or_cfg="tiny", **overrides) -> Tuple[GPT, GPTConfig]:
    cfg = (GPT_CONFIGS[name_or_cfg] if isinstance(name_or_cfg, str)
           else name_or_cfg)
    if overrides:
        cfg = replace(cfg, **overrides)
    return GPT(cfg), cfg
