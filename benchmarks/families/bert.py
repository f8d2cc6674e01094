"""The BERT family (``deepspeed_tpu.models.bert``): bidirectional encoder
with the masked-LM head. The configuration file holds the keys of the
published ``config.json``."""

import numpy as np

from benchmarks import flops
from benchmarks.reference import bert as reference

CAUSAL = False


def build_model(config):
    from deepspeed_tpu.models import make_bert
    from deepspeed_tpu.models.bert import BertConfig

    if config["intermediate_size"] % config["hidden_size"]:
        raise ValueError("intermediate_size must be a multiple of "
                         "hidden_size")
    return make_bert(BertConfig(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        type_vocab_size=config["type_vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        mlp_ratio=config["intermediate_size"] // config["hidden_size"],
        layer_norm_epsilon=config["layer_norm_eps"],
        pre_layer_norm=config["assumed"]["pre_layer_norm"],
        dropout_rate=config["assumed"]["dropout"]))


def example_batch():
    ids = np.zeros((1, 8), np.int32)
    return {"input_ids": ids, "attention_mask": np.ones_like(ids),
            "labels": ids}


def make_batch(tokens, traffic, rng):
    """Full-length sequences; ``mask_rate`` of the positions carry their
    own id as the label, the rest -100. (No [MASK] substitution: it would
    change no shape and no operation.)"""
    masked = rng.random(tokens.shape) < traffic["mask_rate"]
    return {"input_ids": tokens,
            "attention_mask": np.ones_like(tokens),
            "labels": np.where(masked, tokens, -100).astype(np.int32)}


def forward_flops_per_token(config, traffic):
    return flops.bert_forward_flops_per_token(
        hidden=config["hidden_size"], layers=config["num_hidden_layers"],
        vocab=config["vocab_size"], seq=traffic["seq_len"],
        intermediate=config["intermediate_size"],
        mask_rate=traffic["mask_rate"])


def hidden_layers_heads(config):
    return (config["hidden_size"], config["num_hidden_layers"],
            config["num_attention_heads"])


def reference_nll(config):
    """``(params, batch) -> (sum, count)`` of the plain reference."""
    return lambda params, batch: reference.nll(
        params, batch, n_head=config["num_attention_heads"],
        eps=config["layer_norm_eps"])
