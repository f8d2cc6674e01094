"""The ``glm4_moe_lite`` family (``deepspeed_tpu.models.glm4_moe_lite``):
causal LM with latent attention, dropless experts beside a shared expert
and a multi-token-prediction module. The configuration file holds the keys
of the published ``config.json``; ``n_routed_experts`` and ``vocab_size``
there are what THIS chip holds of a layer that ``deployment`` says several
chips share, and ``published`` gives the whole."""

import numpy as np

from benchmarks import flops_glm4_moe_lite as count
from benchmarks.reference import glm4_moe_lite as reference

CAUSAL = True


def build_model(config):
    import jax.numpy as jnp
    from deepspeed_tpu.models import Glm4MoeLiteConfig, make_glm4_moe_lite

    if config["assumed"]["dropout"]:
        raise ValueError("this family has no dropout")
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("group-limited routing is not built")
    model, cfg = make_glm4_moe_lite(Glm4MoeLiteConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        first_k_dense_replace=config["first_k_dense_replace"],
        num_attention_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_routed_experts=config["published"]["n_routed_experts"],
        n_held_experts=config["n_routed_experts"],
        first_held_expert=config["first_held_expert"],
        n_shared_experts=config["n_shared_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        routed_scaling_factor=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"],
        num_nextn_predict_layers=config["num_nextn_predict_layers"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        mtp_loss_weight=config["assumed"]["mtp_loss_weight"],
        remat=config["train_model"]["remat"], dtype=jnp.bfloat16))
    return seeded_as(model, config["assumed"]["embedding_init_std"]), cfg


def seeded_as(model, embedding_std):
    """``model`` with seeded weights as the CELL starts from them: the
    model's own (every matrix normal 0.02, the family's initializer_range)
    but the embedding's rows, stretched to ``embedding_std``. The program
    knows nothing of it: only ``init`` is wrapped, and the reference takes
    whatever weights the engine started from. Why the cell wants it is in
    the configuration's ``assumed``."""
    scale = embedding_std / 0.02

    class Seeded(type(model)):
        def init(self, *args, **kwargs):
            variables = super().init(*args, **kwargs)
            params = dict(variables["params"])
            params["embed_tokens"] = params["embed_tokens"] * scale
            return {**variables, "params": params}

    return Seeded(model.cfg)


def example_batch():
    return {"input_ids": np.zeros((1, 8), np.int32)}


def make_batch(tokens, traffic, rng):
    return {"input_ids": tokens}


def forward_flops_per_token(config, traffic):
    return count.forward_flops_per_token(config, traffic["seq_len"])


def hidden_layers_heads(config):
    """What ``kernel.flash_roofline`` sizes the flash kernels by: their
    width (heads x head size), and every layer that has attention, the
    MTP module's among them."""
    heads = config["num_attention_heads"]
    return (heads * config["v_head_dim"],
            config["num_hidden_layers"] + config["num_nextn_predict_layers"],
            heads)


def expert_layers(config):
    return (config["num_hidden_layers"] - config["first_k_dense_replace"]
            + config["num_nextn_predict_layers"])


def reference_nll(config):
    """``(params, batch) -> (sum, count)`` of the plain reference."""
    kw = reference.settings(config)
    return lambda params, batch: reference.nll(params, batch, **kw)


def reference_logits(config):
    kw = reference.settings(config)
    return lambda params, input_ids: reference.logits(params, input_ids,
                                                      **kw)
