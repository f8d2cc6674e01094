"""Pipeline model description — the ``PipelineModule`` analogue.

Reference (``deepspeed/runtime/pipe/module.py``): a layer list built from
``LayerSpec``/``TiedLayerSpec`` (:25, :73), partitioned over stages by
uniform/param-count/regex policies (:355), with tied-embedding comm groups.

TPU-native contract (``PipeModel``): the pipelined segment must be a stack
of structurally identical blocks (leading dim L sharded over ``pipe``);
embedding + head are plain functions outside the pipeline, so weight tying
is ordinary parameter sharing instead of a dedicated allreduce group.
``LayerSpec`` is kept for API familiarity and for host-side stage
assignment of *heterogeneous* inference pipelines (partition_uniform /
partition_balanced, reference runtime/utils.py:342,:408 — in
deepspeed_tpu.runtime.utils).
"""

from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp


class LayerSpec:
    """Delayed-build layer descriptor (reference pipe/module.py:25)."""

    def __init__(self, typename, *args, **kwargs):
        self.typename = typename
        self.args = args
        self.kwargs = kwargs

    def build(self):
        return self.typename(*self.args, **self.kwargs)

    def __repr__(self):
        return f"LayerSpec({getattr(self.typename, '__name__', self.typename)})"


class TiedLayerSpec(LayerSpec):
    """Layer sharing weights with another layer by key (reference :73).
    In the functional pipeline, tying is expressed by both layers reading
    the same param subtree — record the key so builders can wire it."""

    def __init__(self, key, typename, *args, forward_fn=None, **kwargs):
        super().__init__(typename, *args, **kwargs)
        self.key = key
        self.forward_fn = forward_fn


@dataclass
class PipeModel:
    """Functional pipeline model: loss = head(embed(batch) |> blocks).

    - embed_fn(params, batch, rng)                  -> activations [mb, ...]
    - block_fn(one_block_params, x, aux, rng)       -> activations
    - head_fn(params, activations, batch)           -> scalar loss
    - aux_fn(params, batch) -> per-microbatch side input for the blocks
      (e.g. an attention mask) or None
    - params: {"embed": ..., "blocks": stacked [L, ...], "head": ...}

    embed_fn/head_fn receive the FULL params dict, so weight tying (e.g.
    the LM head reading params["embed"]["wte"]) is plain parameter sharing.
    """

    embed_fn: Callable
    block_fn: Callable
    head_fn: Callable
    params: Any
    num_blocks: int
    aux_fn: Optional[Callable] = None
    # block_fn takes a 5th arg: the GLOBAL layer index (stage offset +
    # local position) — needed by per-layer schedules (PLD).
    block_takes_layer_idx: bool = False
    # block_fn returns (h, aux_scalar): the pipeline masks bubble ticks,
    # psums the aux over pipe, and the engine adds mean-per-microbatch
    # aux to the loss (MoE load-balance losses).
    block_returns_aux: bool = False

    def check(self, pipe_size: int) -> None:
        if self.num_blocks % pipe_size:
            raise ValueError(
                f"{self.num_blocks} blocks not divisible by pipe={pipe_size}")


def gpt_pipe_model(cfg, rng_key=None, example_batch=None,
                   params=None) -> PipeModel:
    """Build a PipeModel from the in-tree GPT family (models/gpt.py):
    embedding + dropout outside, L GPTBlocks pipelined (attention masks
    travel as aux), ln_f + LM head (tied per cfg.tie_embeddings) +
    cross-entropy outside. ``params``: an existing flat GPT param tree
    (wte/wpe/h_i/ln_f layout) to re-pack instead of fresh-initialising —
    used when a caller hands pretrained weights to the pipeline or
    param-offload tiers."""
    import flax.linen as nn

    from deepspeed_tpu.models.gpt import GPT, GPTBlock, shift_labels

    if rng_key is None:
        rng_key = jax.random.PRNGKey(0)
    if example_batch is None:
        example_batch = {"input_ids": jnp.zeros((2, 16), jnp.int32)}

    # Initialise through the reference model so shapes/naming match the
    # non-pipelined family, then re-pack into the PipeModel layout.
    if params is not None:
        flat = params
    else:
        model = GPT(cfg)
        variables = model.init({"params": rng_key, "dropout": rng_key},
                               example_batch)
        flat = variables["params"]

    moe = getattr(cfg, "moe_experts", 0) > 0
    if moe and cfg.moe_layer_freq != 1:
        raise ValueError(
            "MoE x pipeline needs structurally identical blocks "
            "(the stacked-block contract): use moe_layer_freq=1 so every "
            f"block carries the MoE FFN (got {cfg.moe_layer_freq})")
    block = GPTBlock(cfg, moe=moe)
    from deepspeed_tpu.parallel.pipe.pipeline import stack_blocks

    blocks = stack_blocks([flat[f"h_{i}"] for i in range(cfg.num_layers)])
    head = {"ln_f": flat["ln_f"]}
    if not cfg.tie_embeddings:
        head["lm_head"] = flat["lm_head"]
    params = {
        "embed": {"wte": flat["wte"], "wpe": flat["wpe"]},
        "blocks": blocks,
        "head": head,
    }

    def embed_fn(params, batch, rng):
        from deepspeed_tpu.ops.embedding import embedding_lookup

        ids = batch["input_ids"]
        s = ids.shape[1]
        emb = params["embed"]
        tok = embedding_lookup(
            emb["wte"], ids,
            sparse_grad_axes=getattr(cfg, "sparse_embedding_grad", None))
        x = tok.astype(cfg.dtype) + emb["wpe"][:s][None].astype(cfg.dtype)
        if rng is not None and cfg.dropout_rate > 0.0:
            keep = jax.random.bernoulli(rng, 1.0 - cfg.dropout_rate, x.shape)
            x = jnp.where(keep, x / (1.0 - cfg.dropout_rate), 0.0)
        return x

    def aux_fn(params, batch):
        am = batch.get("attention_mask")
        # [mb, S] -> broadcastable [mb, 1, 1, S] attend-mask for GPTBlock.
        mask = (None if am is None
                else am[:, None, None, :].astype(jnp.bool_))
        theta = batch.get("pld_theta")
        if theta is None:
            return mask
        # Progressive Layer Drop rides as aux so every stage sees the
        # step's theta (reference threads it through engine.forward,
        # /root/reference/deepspeed/runtime/engine.py:1085; here the
        # pipelined schedule delivers it with the microbatch).
        return {"attn_mask": mask, "pld_theta": jnp.float32(theta)}

    def _unpack_aux(aux):
        if isinstance(aux, dict):
            return aux.get("attn_mask"), aux.get("pld_theta")
        return aux, None

    def block_fn(p, x, aux, rng, layer_idx=0):
        mask, theta = _unpack_aux(aux)
        if rng is None or cfg.dropout_rate == 0.0:
            # MoE routing needs a (deterministic-OK) rng collection only
            # when dropout is active; the top-k router itself is
            # deterministic.
            y = block.apply({"params": p}, x, mask, True)
        else:
            y = block.apply({"params": p}, x, mask, False,
                            rngs={"dropout": rng})
        aux_l = None
        if moe:
            y, aux_l = y
        if theta is not None and rng is not None:
            # The SAME keep schedule as the flat families — one shared
            # implementation so the pipelined trajectory cannot drift.
            from deepspeed_tpu.runtime.progressive_layer_drop import \
                pld_keep_gate
            gate = pld_keep_gate(jax.random.fold_in(rng, 0x9E37),
                                 layer_idx, cfg.num_layers, theta)
            y = jnp.where(gate, y, x)
            if aux_l is not None:
                # a dropped MoE layer contributed nothing — its balance
                # loss must not push its router (same rule as the flat
                # family, models/gpt.py)
                aux_l = jnp.where(gate, aux_l, 0.0)
        if moe:
            # alpha folded in here so the engine can just ADD the psum'd
            # scalar: loss = mean_m(ce_m) + sum(aux)/M.
            return y, cfg.moe_aux_alpha * aux_l
        return y

    # Final LN through flax's own LayerNorm (same impl/epsilon as the
    # non-pipelined GPT's ln_f) + the model's decode convention (tied einsum
    # or separate lm_head) + shared label shift, so the two loss paths
    # cannot drift.
    ln_f = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=jnp.float32)

    def head_fn(params, x, batch):
        from deepspeed_tpu.models.gpt import cross_entropy_with_ignore
        from deepspeed_tpu.ops.xent import fused_cross_entropy

        h = ln_f.apply({"params": params["head"]["ln_f"]}, x)
        labels = shift_labels(batch)
        if cfg.tie_embeddings:
            w, wt = params["embed"]["wte"], False
        else:
            w, wt = params["head"]["lm_head"]["kernel"], True
        if not getattr(cfg, "fused_ce", True):
            # Honor the family's opt-out (ADVICE r3): exact fp32 logits +
            # stock log-softmax CE, as models/gpt.py's unfused branch.
            logits = jnp.einsum("bsd,vd->bsv" if not wt else "bsd,dv->bsv",
                                h.astype(cfg.dtype), w.astype(cfg.dtype),
                                preferred_element_type=jnp.float32)
            return cross_entropy_with_ignore(logits, labels)
        return fused_cross_entropy(
            h.astype(cfg.dtype), w.astype(cfg.dtype), labels,
            w_transposed=wt,
            logits_fp32=getattr(cfg, "fused_ce_fp32_logits", False))

    return PipeModel(embed_fn=embed_fn, block_fn=block_fn,
                     head_fn=head_fn, aux_fn=aux_fn, params=params,
                     num_blocks=cfg.num_layers, block_takes_layer_idx=True,
                     block_returns_aux=moe)
