"""The ``nemotron_h`` family (``deepspeed_tpu.models.nemotron_h``): one
mixer a layer (Mamba-2, grouped-query attention, latent experts), untied
head, served. The configuration file holds the keys of the published
``config.json``; ``n_routed_experts`` is what this chip HOLDS, the
router's width is ``published.n_routed_experts``."""

import numpy as np

from benchmarks import flops_nemotron_h as count
from benchmarks.reference import nemotron_h as reference

CAUSAL = True


def build_model(config):
    import jax.numpy as jnp
    from deepspeed_tpu.models.nemotron_h import (NemotronHConfig,
                                                 make_nemotron_h)

    c = config
    inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    if inner != c["expand"] * c["hidden_size"]:
        raise ValueError("mamba_num_heads x mamba_head_dim must be expand x "
                         "hidden_size")
    if len(c["hybrid_override_pattern"]) != c["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern names one mixer a layer")
    if c["num_nextn_predict_layers"]:
        raise ValueError("the MTP module is not part of the served model")
    return make_nemotron_h(NemotronHConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        pattern=c["hybrid_override_pattern"],
        max_seq_len=c["serving"]["max_model_len"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        mamba_num_heads=c["mamba_num_heads"],
        mamba_head_dim=c["mamba_head_dim"], n_groups=c["n_groups"],
        ssm_state_size=c["ssm_state_size"], conv_kernel=c["conv_kernel"],
        chunk_size=c["chunk_size"], time_step_min=c["time_step_min"],
        time_step_max=c["time_step_max"],
        time_step_floor=c["time_step_floor"],
        n_routed_experts=c["published"]["n_routed_experts"],
        n_held_experts=c["n_routed_experts"],
        first_held_expert=c["first_held_expert"],
        experts_per_token=c["num_experts_per_tok"],
        moe_intermediate=c["moe_intermediate_size"],
        moe_latent=c["moe_latent_size"],
        shared_intermediate=c["moe_shared_expert_intermediate_size"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        norm_topk_prob=c["norm_topk_prob"],
        rms_eps=c["layer_norm_epsilon"], dtype=jnp.bfloat16))


def example_batch():
    return {"input_ids": np.zeros((1, 8), np.int32)}


def reference_config(config):
    """The keys the reference reads, the router at its published width."""
    return dict(config, n_routed_experts=config["published"][
        "n_routed_experts"])


def reference_logits(config):
    c = reference_config(config)
    return lambda params, input_ids: reference.logits(params, input_ids, c)


def parameters(config):
    return count.parameters(config)
