"""Mixture-of-Experts + expert parallelism (planned-fresh per SURVEY §2.4;
API follows the later deepspeed.moe.layer.MoE surface)."""

from deepspeed_tpu.moe.dispatch import (alltoall_dispatch,
                                        modeled_dispatch_bytes_ici)
from deepspeed_tpu.moe.dropless import (DroplessMoE, DroplessMoEConfig,
                                        dropless_partition_rules)
from deepspeed_tpu.moe.layer import MoE, MoEConfig, moe_partition_rules

__all__ = ["MoE", "MoEConfig", "moe_partition_rules",
           "DroplessMoE", "DroplessMoEConfig", "dropless_partition_rules",
           "alltoall_dispatch", "modeled_dispatch_bytes_ici"]
