"""Parameters, bytes and operations that the ``nemotron_h`` block REQUIRES,
from the configuration's keys alone, beside ``flops.py`` (which a PR that
adds a family does not edit). Serving counts: what ONE decode step has to
read and write for the WORK it does (the rows that are alive, the experts
that any of them chose), whatever the program does besides. Matmul FLOPs
are 2 per multiply-add; nothing for norms, activations, the convolution,
the router's top-k, the sort or the gathers.
"""

from typing import Dict

BF16, F32 = 2, 4


def kinds(c: Dict) -> Dict[str, int]:
    pattern = c["hybrid_override_pattern"]
    return {kind: pattern.count(kind) for kind in "ME*"}


def parameters(c: Dict) -> Dict[str, int]:
    """Per part held on this chip: ``n_routed_experts`` experts of the
    ``published`` router width, ``vocab_size`` rows."""
    h = c["hidden_size"]
    inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    conv = inner + 2 * c["n_groups"] * c["ssm_state_size"]
    heads, kv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                    c["head_dim"])
    latent, wide = c["moe_latent_size"], c["moe_intermediate_size"]
    routed = c["published"]["n_routed_experts"]
    parts = {
        "mamba_layer": (h * (inner + conv + c["mamba_num_heads"])
                        + (c["conv_kernel"] + 1) * conv
                        + 3 * c["mamba_num_heads"] + inner + inner * h + h),
        "attention_layer": h * heads * d + 2 * h * kv * d + heads * d * h + h,
        "expert_layer_outside_routed": (
            h * routed + routed + 2 * h * latent
            + 2 * h * c["moe_shared_expert_intermediate_size"] + h),
        "routed_expert": 2 * latent * wide,
        "embedding_and_head": 2 * c["vocab_size"] * h + h,
    }
    n = kinds(c)
    parts["total"] = (
        n["M"] * parts["mamba_layer"] + n["*"] * parts["attention_layer"]
        + n["E"] * (parts["expert_layer_outside_routed"]
                    + c["n_routed_experts"] * parts["routed_expert"])
        + parts["embedding_and_head"])
    return parts


def state_bytes_per_slot_layer(c: Dict) -> int:
    """What one Mamba-2 layer keeps a slot: the float32 SSM state and the
    bfloat16 tail of the convolution."""
    inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    conv = inner + 2 * c["n_groups"] * c["ssm_state_size"]
    return (inner * c["ssm_state_size"] * F32
            + (c["conv_kernel"] - 1) * conv * BF16)


def kv_bytes_per_position_layer(c: Dict) -> int:
    """K and V of one attention layer, by KEY/VALUE heads."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * BF16


def ssm_state_step_bytes(c: Dict, live_rows: float) -> float:
    """A decode step reads and writes the state of the rows that are
    alive, in every Mamba-2 layer."""
    return 2.0 * live_rows * kinds(c)["M"] * state_bytes_per_slot_layer(c)


def experts_step_flops(c: Dict, held_assignments: float) -> float:
    """The held experts' two matmuls over the counted assignments."""
    return (held_assignments * 2.0 * 2.0 * c["moe_latent_size"]
            * c["moe_intermediate_size"])


def experts_step_bytes(c: Dict, experts_touched: float,
                       held_assignments: float) -> float:
    """The weights of every expert that a row chose (``experts_touched``:
    summed over the expert layers), read once, and each assignment's
    latent read and written."""
    weights = (experts_touched * 2.0 * c["moe_latent_size"]
               * c["moe_intermediate_size"] * BF16)
    return weights + held_assignments * 2.0 * c["moe_latent_size"] * BF16


def decode_step_bytes(c: Dict, *, live_rows: float, experts_touched: float,
                      held_assignments: float, live_positions: float
                      ) -> float:
    """ALL one decode step must read and write: every weight outside the
    routed experts (the 11 layers and the head; of the embedding only the
    rows' own), the experts touched, the live rows' state both ways, the
    live K/V read and this step's written."""
    p, n = parameters(c), kinds(c)
    h = c["hidden_size"]
    weights = (n["M"] * p["mamba_layer"] + n["*"] * p["attention_layer"]
               + n["E"] * p["expert_layer_outside_routed"]
               + c["vocab_size"] * h + h) * BF16
    kv = (live_positions + live_rows) * n["*"] * kv_bytes_per_position_layer(c)
    return (weights + live_rows * h * BF16
            + experts_step_bytes(c, experts_touched, held_assignments)
            + ssm_state_step_bytes(c, live_rows) + kv)

