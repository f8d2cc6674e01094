"""In-tree model families (flagship targets for every subsystem)."""

from deepspeed_tpu.models.adapter import flax_module_loss_fn, supervised_loss_fn
from deepspeed_tpu.models.bert import (BERT_CONFIGS, BertConfig, BertModel,
                                       bert_partition_rules, make_bert)
from deepspeed_tpu.models.glm4_moe_lite import (
    Glm4MoeLite, Glm4MoeLiteConfig, glm4_moe_lite_partition_rules,
    make_glm4_moe_lite)
from deepspeed_tpu.models.gpt import (GPT, GPT_CONFIGS, GPTConfig,
                                      cross_entropy_with_ignore,
                                      gpt_partition_rules, make_gpt)
from deepspeed_tpu.models.partition import build_specs

__all__ = [
    "GPT", "GPTConfig", "GPT_CONFIGS", "make_gpt", "gpt_partition_rules",
    "BertModel", "BertConfig", "BERT_CONFIGS", "make_bert",
    "bert_partition_rules", "Glm4MoeLite", "Glm4MoeLiteConfig",
    "make_glm4_moe_lite", "glm4_moe_lite_partition_rules", "build_specs", "flax_module_loss_fn",
    "supervised_loss_fn", "cross_entropy_with_ignore",
]
