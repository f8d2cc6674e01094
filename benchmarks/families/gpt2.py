"""The GPT-2 family (``deepspeed_tpu.models.gpt``): causal LM, tied head.
The configuration file holds the keys of the published ``config.json``."""

import numpy as np

from benchmarks import flops
from benchmarks.reference import gpt2 as reference

CAUSAL = True


def build_model(config):
    from deepspeed_tpu.models import make_gpt
    from deepspeed_tpu.models.gpt import GPTConfig

    inner = config.get("n_inner") or 4 * config["n_embd"]
    if inner % config["n_embd"]:
        raise ValueError("n_inner must be a multiple of n_embd")
    return make_gpt(GPTConfig(
        vocab_size=config["vocab_size"], max_seq_len=config["n_positions"],
        hidden_size=config["n_embd"], num_layers=config["n_layer"],
        num_heads=config["n_head"], mlp_ratio=inner // config["n_embd"],
        layer_norm_epsilon=config["layer_norm_epsilon"],
        tie_embeddings=config["tie_word_embeddings"],
        dropout_rate=config["assumed"]["dropout"]))


def example_batch():
    return {"input_ids": np.zeros((1, 8), np.int32)}


def make_batch(tokens, traffic, rng):
    return {"input_ids": tokens}


def forward_flops_per_token(config, traffic):
    return flops.gpt_forward_flops_per_token(
        hidden=config["n_embd"], layers=config["n_layer"],
        vocab=config["vocab_size"], seq=traffic["seq_len"],
        intermediate=config.get("n_inner") or 0)


def hidden_layers_heads(config):
    return config["n_embd"], config["n_layer"], config["n_head"]


def reference_nll(config):
    """``(params, batch) -> (sum, count)`` of the plain reference."""
    return lambda params, batch: reference.nll(
        params, batch, n_head=config["n_head"],
        eps=config["layer_norm_epsilon"])


def reference_logits(config):
    return lambda params, input_ids: reference.logits(
        params, input_ids, n_head=config["n_head"],
        eps=config["layer_norm_epsilon"])
