"""GPT-2 (Radford et al. 2019; ``openai-community/gpt2*``): learned
positions, pre-LN blocks, ``gelu_new``, tied output head. No departures
from the published model; dropout is off, as in every cell."""

import jax
import jax.numpy as jnp

from benchmarks.reference import common as c


def logits(params, input_ids, *, n_head: int, eps: float = 1e-5):
    """``[batch, seq]`` token ids -> ``[batch, seq, vocab]`` float32
    logits. ``params`` is the system's flax tree (``wte``, ``wpe``,
    ``h_<i>``, ``ln_f``)."""
    with jax.default_matmul_precision("highest"):
        p = c.to_f32(params)
        n_layer = sum(1 for k in p if k.startswith("h_"))
        s = input_ids.shape[1]
        x = p["wte"][input_ids] + p["wpe"][:s][None]

        def block(x, lp):
            x = x + c.attention(c.layer_norm(x, lp["ln_1"], eps),
                                lp["c_attn"], lp["c_proj"], n_head, True)
            return x + c.mlp(c.layer_norm(x, lp["ln_2"], eps),
                             lp["c_fc"], lp["mlp_proj"])

        x = c.run_layers(block, x, [p[f"h_{i}"] for i in range(n_layer)])
        return c.layer_norm(x, p["ln_f"], eps) @ p["wte"].T


def nll(params, batch, *, n_head: int, eps: float = 1e-5):
    """``(sum, count)`` of the next-token negative log-likelihood over all
    positions but the last of every sequence."""
    ids = batch["input_ids"]
    labels = jnp.pad(ids[:, 1:], ((0, 0), (0, 1)), constant_values=-100)
    return c.token_nll(logits(params, ids, n_head=n_head, eps=eps), labels)


def loss(params, batch, **kw):
    """Next-token cross entropy, mean over those positions."""
    return c.mean_of(nll(params, batch, **kw))
