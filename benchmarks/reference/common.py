"""What the two references share: the block's arithmetic and the layer
loop. Independent of ``deepspeed_tpu``: only ``jax.numpy``."""

import jax
import jax.numpy as jnp

F32 = jnp.float32


def layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def dense(x, p):
    return x @ p["kernel"] + p["bias"]


def gelu_tanh(x):
    """GPT-2's ``gelu_new``; the repo's BERT uses it too (see bert.py)."""
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def attention(x, p_attn, p_proj, n_head, causal):
    """Full softmax attention: the whole [seq, seq] score matrix."""
    b, s, d = x.shape
    q, k, v = jnp.split(dense(x, p_attn), 3, axis=-1)
    heads = lambda t: t.reshape(b, s, n_head, d // n_head).transpose(0, 2, 1, 3)
    q, k, v = heads(q), heads(k), heads(v)
    scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(F32(d // n_head))
    if causal:
        keep = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(keep, scores, -jnp.inf)
    out = jax.nn.softmax(scores, axis=-1) @ v
    return dense(out.transpose(0, 2, 1, 3).reshape(b, s, d), p_proj)


def mlp(x, p_fc, p_proj):
    return dense(gelu_tanh(dense(x, p_fc)), p_proj)


def to_f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, F32), tree)


def run_layers(block, x, layers):
    """Apply ``block(x, layer_params)`` for every entry of ``layers``, in
    order. The loop is a scan over the stacked layers with each block
    recomputed in the backward pass: the same arithmetic as the Python
    loop, but one block's program to compile and one block's activations
    to hold, so the reference fits beside the system on the chip."""
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    body = jax.checkpoint(lambda h, p: (block(h, p), None))
    x, _ = jax.lax.scan(body, x, stacked)
    return x


def token_nll(logits, labels, ignore=-100):
    """``(sum, count)`` of the negative log-likelihood over the positions
    whose label is not ``ignore``. Kept apart so that a batch cut into
    chunks adds up to exactly its own mean."""
    valid = labels != ignore
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None],
                               axis=-1)[..., 0]
    return jnp.where(valid, nll, 0.0).sum(), valid.sum()


def mean_of(nll):
    total, count = nll
    return total / jnp.maximum(count, 1)
