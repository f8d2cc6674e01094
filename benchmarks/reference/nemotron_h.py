"""Nemotron-H's hybrid block (``model_type`` ``nemotron_h``), written a
second time from the description in ISSUE 35 and the published config's
keys: plain ``jax.numpy``, float32 at "highest" matmul precision, nothing
from ``deepspeed_tpu``. One mixer a layer, with a pre-norm and a residual:

``M``  Mamba-2. The recurrence is a ``lax.scan`` over SINGLE positions (no
       chunks, no matmul form): ``h <- exp(dt A) h + dt u (x) B``,
       ``y = h C + D u``.
``*``  grouped-query attention as a full masked ``[seq, seq]`` softmax, no
       positional encoding.
``E``  latent experts as a loop over the HELD experts, each run on every
       token and weighted by a mask (no sort, no grouped matmul); sigmoid
       router over all published experts on the full-width input, top-k of
       score + bias, weights renormalised over the k chosen and scaled.

No cache and no batching tricks: one full forward over whatever ids it is
given. It is handed the same share of the model as the system (the held
experts, the vocabulary slice) as the system's parameter tree, in the
precision the configuration states (bfloat16), and WIDENS one layer at a
time (inside an expert layer, one expert at a time): 4.65B parameters are
18.6 GB in float32, more than the chip has.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32


def f32(x):
    return jnp.asarray(x, F32)


def rms_norm(x, weight, eps):
    return f32(weight) * x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def mamba(x, p, c):
    """``x [batch, seq, hidden]`` float32 through one Mamba-2 mixer."""
    b, s, _ = x.shape
    heads, p_dim = c["mamba_num_heads"], c["mamba_head_dim"]
    groups, n, k = c["n_groups"], c["ssm_state_size"], c["conv_kernel"]
    inner = heads * p_dim
    conv_dim = inner + 2 * groups * n
    wide = x @ f32(p["in_proj"]["kernel"])
    z, xbc, dt = (wide[..., :inner], wide[..., inner:inner + conv_dim],
                  wide[..., inner + conv_dim:])
    # causal depthwise convolution: position t sees t-k+1 .. t
    kernel, bias = f32(p["conv_kernel"]), f32(p["conv_bias"])
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = silu(bias + sum(padded[:, j:j + s] * kernel[j] for j in range(k)))
    u = xbc[..., :inner].reshape(b, s, heads, p_dim)
    bm = xbc[..., inner:inner + groups * n].reshape(b, s, groups, n)
    cm = xbc[..., inner + groups * n:].reshape(b, s, groups, n)
    per_group = heads // groups
    bm, cm = jnp.repeat(bm, per_group, 2), jnp.repeat(cm, per_group, 2)
    dt = jnp.logaddexp(dt + f32(p["dt_bias"]), 0.0)          # softplus
    a, d = -jnp.exp(f32(p["A_log"])), f32(p["D"])

    def position(h, at):
        u_t, dt_t, b_t, c_t = at        # [b, H, P], [b, H], [b, H, N] x 2
        h = (jnp.exp(dt_t * a)[..., None, None] * h
             + (dt_t[..., None] * u_t)[..., None] * b_t[:, :, None, :])
        return h, (h * c_t[:, :, None, :]).sum(-1) + d[:, None] * u_t

    swap = lambda t: jnp.swapaxes(t, 0, 1)
    _, y = jax.lax.scan(position, jnp.zeros((b, heads, p_dim, n), F32),
                        (swap(u), swap(dt), swap(bm), swap(cm)))
    y = swap(y).reshape(b, s, inner) * silu(z)
    grouped = y.reshape(b, s, groups, inner // groups)
    grouped = grouped / jnp.sqrt(
        jnp.mean(grouped * grouped, -1, keepdims=True)
        + c["layer_norm_epsilon"])
    y = grouped.reshape(b, s, inner) * f32(p["norm_weight"])
    return y @ f32(p["out_proj"]["kernel"])


def attention(x, p, c):
    b, s, _ = x.shape
    heads, kv_heads, d = (c["num_attention_heads"], c["num_key_value_heads"],
                          c["head_dim"])
    q = (x @ f32(p["q_proj"]["kernel"])).reshape(b, s, heads, d)
    k = (x @ f32(p["k_proj"]["kernel"])).reshape(b, s, kv_heads, d)
    v = (x @ f32(p["v_proj"]["kernel"])).reshape(b, s, kv_heads, d)
    k, v = (jnp.repeat(t, heads // kv_heads, 2) for t in (k, v))

    def one_sequence(qkv):              # the whole [seq, seq] score matrix
        q, k, v = qkv
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(d))
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores,
                           -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    # a sequence at a time: at 2048 positions the scores of one are 0.5 GiB
    out = jax.lax.map(one_sequence, (q, k, v))
    return out.reshape(b, s, heads * d) @ f32(p["o_proj"]["kernel"])


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def route(x, p, c):
    """``(chosen [T, k], weights [T, k])`` over ALL published experts."""
    scores = 1.0 / (1.0 + jnp.exp(-(x @ f32(p["router"]))))
    biased = scores + f32(p["e_score_correction_bias"])
    chosen = jnp.argsort(-biased, axis=-1)[:, :c["num_experts_per_tok"]]
    weights = jnp.take_along_axis(scores, chosen, -1)
    if c["norm_topk_prob"]:
        weights = weights / weights.sum(-1, keepdims=True)
    return chosen, weights * c["routed_scaling_factor"]


def experts(x, p, c, record=None):
    """The held experts' part plus the shared expert, ``x [T, hidden]``.
    ``record``: a list that gets this layer's ``(chosen, weights)`` (a
    test forces them on the system to read what a flipped choice costs;
    outside ``jit`` only)."""
    chosen, weights = route(x, p, c)
    if record is not None:
        record.append((chosen, weights))
    latent = x @ f32(p["latent_in"]["kernel"])
    first = c["first_held_expert"]

    def one(total, held):
        e, w_up, w_down = held
        weight = jnp.where(chosen == first + e, weights, 0.0).sum(-1)
        out = relu2(latent @ f32(w_up)) @ f32(w_down)
        return total + weight[:, None] * out, None

    n_held = p["experts_up"].shape[0]
    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(latent),
        (jnp.arange(n_held), p["experts_up"], p["experts_down"]))
    shared = relu2(x @ f32(p["shared_up"]["kernel"])) \
        @ f32(p["shared_down"]["kernel"])
    return routed @ f32(p["latent_out"]["kernel"]) + shared


def logits(params, input_ids, config, record=None):
    """``[batch, seq]`` ids -> ``[batch, seq, vocab]`` float32 logits.
    ``params``: the system's tree (``embed_tokens``, ``norm_<i>``,
    ``mixer_<i>``, ``norm_f``, ``lm_head``); ``config``: the published
    keys as the configuration file holds them. ``record``: see
    :func:`experts`; the expert layers append in their order."""
    c = config
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed_tokens"][input_ids])
        b, s, hidden = x.shape
        for i, kind in enumerate(c["hybrid_override_pattern"]):
            p = params[f"mixer_{i}"]
            h = rms_norm(x, params[f"norm_{i}"]["weight"],
                         c["layer_norm_epsilon"])
            if kind == "M":
                x = x + mamba(h, p, c)
            elif kind == "*":
                x = x + attention(h, p, c)
            else:
                x = x + experts(h.reshape(b * s, hidden), p, c,
                                record).reshape(b, s, hidden)
        x = rms_norm(x, params["norm_f"]["weight"], c["layer_norm_epsilon"])
        return x @ f32(params["lm_head"]["kernel"])
