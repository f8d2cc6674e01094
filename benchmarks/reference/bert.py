"""BERT (Devlin et al. 2019; ``google-bert/bert-large-uncased``) with the
masked-LM head, as the repository runs it. Departures from the published
model, both the repository's and both listed under ``assumed`` in the
configuration file:

- pre-LN blocks with a final LayerNorm and no embedding LayerNorm
  (DeepSpeed's ``pre_layer_norm=True`` kernel default); the published
  model is post-LN;
- the tanh approximation of GELU; the published model uses the erf form.
"""

import jax

from benchmarks.reference import common as c


def logits(params, batch, *, n_head: int, eps: float = 1e-12):
    """MLM logits ``[batch, seq, vocab]`` in float32 from the system's flax
    tree (``wte``, ``wpe``, ``tte``, ``layer_<i>``, ``ln_f``,
    ``mlm_transform``, ``mlm_ln``, ``mlm_bias``). Every position is kept
    (full-length sequences), token type 0."""
    with jax.default_matmul_precision("highest"):
        p = c.to_f32(params)
        ids = batch["input_ids"]
        n_layer = sum(1 for k in p if k.startswith("layer_"))
        x = p["wte"][ids] + p["wpe"][:ids.shape[1]][None] + p["tte"][0]

        def block(x, lp):
            x = x + c.attention(c.layer_norm(x, lp["ln_attn"], eps),
                                lp["c_attn"], lp["c_proj"], n_head, False)
            return x + c.mlp(c.layer_norm(x, lp["ln_mlp"], eps),
                             lp["c_fc"], lp["mlp_proj"])

        x = c.run_layers(block, x,
                         [p[f"layer_{i}"] for i in range(n_layer)])
        x = c.layer_norm(x, p["ln_f"], eps)
        h = c.layer_norm(c.gelu_tanh(c.dense(x, p["mlm_transform"])),
                         p["mlm_ln"], eps)
        return h @ p["wte"].T + p["mlm_bias"]


def nll(params, batch, *, n_head: int, eps: float = 1e-12):
    """``(sum, count)`` of the negative log-likelihood over the masked
    positions (label != -100)."""
    return c.token_nll(logits(params, batch, n_head=n_head, eps=eps),
                       batch["labels"])


def loss(params, batch, **kw):
    """Cross entropy, mean over the masked positions."""
    return c.mean_of(nll(params, batch, **kw))
