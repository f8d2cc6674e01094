"""Operations and bytes that the ``glm4_moe_lite`` block REQUIRES, from
shapes alone, beside ``flops.py`` (which a PR that adds a family does not
edit). Matmul FLOPs (2 per multiply-add) of one forward pass per token, as
there; nothing for norms, RoPE, softmax, SiLU, the router's top-k, the
sort, the gathers or recomputation.

The held experts are counted at the EXPECTED number of held assignments a
token, ``experts_per_token * held / routed`` (0.5 for 4 of 64 with 8
held): the router is seeded, so the count of a run differs by its routing.
The program counts the actual number (``moe.held_load_max_over_mean`` and
the roofline below read it).
"""

from typing import Dict

from benchmarks import flops


def mla_projection_flops_per_token(c: Dict) -> float:
    """``q_a``, ``q_b``, ``kv_a``, ``kv_b`` and ``o`` of one layer."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    return 2.0 * (h * c["q_lora_rank"]
                  + c["q_lora_rank"] * heads * (nope + rope)
                  + h * (c["kv_lora_rank"] + rope)
                  + c["kv_lora_rank"] * heads * (nope + v)
                  + heads * v * h)


def swiglu_flops_per_token(hidden: int, intermediate: int) -> float:
    return 2.0 * 3.0 * hidden * intermediate


def held_assignments_per_token(c: Dict) -> float:
    """Expected, for a router that spreads its choices evenly."""
    return (c["num_experts_per_tok"] * c["n_routed_experts"]
            / c["published"]["n_routed_experts"])


def forward_flops_per_token(c: Dict, seq: int) -> float:
    """The configuration as it is run: ``n_routed_experts`` experts held
    of ``published.n_routed_experts``, ``vocab_size`` rows of the
    vocabulary, ``num_hidden_layers`` layers of which the first
    ``first_k_dense_replace`` are dense, and ``num_nextn_predict_layers``
    MTP modules (an ``eh_proj``, one expert layer, the head again)."""
    h = c["hidden_size"]
    heads, v = c["num_attention_heads"], c["v_head_dim"]
    if c["qk_nope_head_dim"] + c["qk_rope_head_dim"] != v:
        raise ValueError("attention is counted at one head size")
    attention = (mla_projection_flops_per_token(c)
                 + flops.attention_flops_per_token(heads * v, seq, True))
    dense = attention + swiglu_flops_per_token(h, c["intermediate_size"])
    expert = (attention
              + 2.0 * h * c["published"]["n_routed_experts"]      # router
              + (c["n_shared_experts"] + held_assignments_per_token(c))
              * swiglu_flops_per_token(h, c["moe_intermediate_size"]))
    head = 2.0 * h * c["vocab_size"]
    n_dense = c["first_k_dense_replace"]
    mtp = c["num_nextn_predict_layers"]
    return (n_dense * dense + (c["num_hidden_layers"] - n_dense) * expert
            + head + mtp * (2.0 * 2 * h * h + expert + head))


# ---------------------------------------------------------------------------
# The held experts' grouped matmuls of a training pass
# ---------------------------------------------------------------------------

def experts_train_flops(*, rows: float, hidden: int,
                        intermediate: int) -> float:
    """Gate, up and down over ``rows`` held assignments: forward once,
    backward twice (the gradient of the rows and of the weights)."""
    return flops.TRAIN_OVER_FORWARD * rows * swiglu_flops_per_token(
        hidden, intermediate)


def experts_train_bytes(*, rows: float, held: int, passes: int, hidden: int,
                        intermediate: int, itemsize: int = 2) -> float:
    """HBM bytes the same work has to move at least. Per layer pass
    (``passes``: one per expert layer and micro-batch) the held experts'
    three weight tensors are read forward, read again backward and their
    gradients written (3 x weights). Per row: ``x`` read and ``y`` written
    forward, ``dy`` read and ``dx`` written backward, ``x`` read again for
    the weights' gradient (5 x hidden), and the gate and up activations
    written forward and read back (4 x intermediate)."""
    weights = 3.0 * held * hidden * intermediate * itemsize
    per_row = (5.0 * hidden + 4.0 * intermediate) * itemsize
    return passes * 3.0 * weights + rows * per_row
