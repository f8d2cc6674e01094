"""GLM-4.7-Flash's block (``zai-org/GLM-4.7-Flash``, ``model_type``
``glm4_moe_lite``; the module of DeepSeek-V3, arXiv:2412.19437, sections
2.1 and 2.2): RMSNorm, multi-head latent attention, SwiGLU, sigmoid top-k
experts beside a shared expert, an untied head, one multi-token-prediction
module. Plain ``jax.numpy`` in float32 at "highest" matmul precision: no
kernel, no sort, no cache. Independent of ``deepspeed_tpu``; ``params`` is
the system's flax tree (``embed_tokens``, ``layers_<i>``, ``norm``,
``lm_head``, ``mtp_*``).

With ``x`` of ``[T, hidden]`` and ``norm`` RMSNorm with a learned scale:

- block: ``x += MLA(norm(x))``; ``x += FFN(norm(x))``;
- MLA: ``c_q = norm(x W_qa)``; ``q = c_q W_qb``, per head ``[nope |
  rope]``; ``[c_kv | k_r] = x W_kva``; ``c_kv = norm(c_kv)``; ``[k_nope |
  v] = c_kv W_kvb`` per head; ``k_r`` is one rotary key per position that
  every head shares; ``softmax_causal(q k^T / sqrt(nope + rope)) v``,
  heads concatenated, times ``W_o``;
- expert layer: ``s = sigmoid(x W_r)``; chosen = top-k of ``s + b``;
  ``w = s[chosen] / (sum s[chosen] + 1e-20) * factor``; ``y = sum_e w_e
  SwiGLU_e(x) + SwiGLU_shared(x)``;
- MTP: ``h' = [norm(Emb(t_{i+1})) | norm(h_i)] W_eh``, one block, a norm,
  the main head; ``loss = CE_main + weight * CE_mtp``.

Departures from the published description, each also in PERF.md:

1. *The share.* ``experts_*`` holds the experts ``[first_held,
   first_held + held)`` of the router's width; the sum over chosen
   experts runs over those alone, after normalising over all k chosen.
   The rows of ``embed_tokens`` and ``lm_head`` are a slice of the
   vocabulary: logits and loss are over the slice.
2. *The correction bias* ``b`` is read from the tree and never moved (its
   published update is a rule outside the gradient).
3. *RoPE pairing and the order of concatenation* are the Hugging Face
   loader's for this family (rotate-half: dimension ``i`` pairs with ``i +
   rope/2``; ``[nope | rope]``, ``[c_kv | k_r]``, ``[k_nope | v]``). With
   seeded weights another order is a relabelling.
4. *The last MTP position* has no next token; it takes id 0, nothing
   attends to it and its label is ignored, as in the system.
5. *Same arithmetic, less memory:* every block is recomputed in the
   backward pass, attention runs one head at a time and the experts one
   at a time (``jax.lax.map`` / ``scan`` over ``jax.checkpoint``), so one
   4096-token sequence fits beside three float32 copies of the weights.
"""

import jax
import jax.numpy as jnp

from benchmarks.reference import common as c

F32 = jnp.float32


def rms_norm(x, p, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * p["weight"]


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def rope(x, theta):
    """``x [batch, seq, ..., d]`` turned at positions ``0..seq-1``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = jnp.arange(x.shape[1], dtype=F32)[:, None] * freq
    angle = angle.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def expanded_keys_and_values(x, p, *, n_head, nope, rope_dim, rank, eps,
                             theta):
    """Per-head keys ``[B, S, H, nope + rope]`` and values ``[B, S, H,
    v]``, written out from the latent."""
    b, s, _ = x.shape
    kv = x @ p["kv_a_proj_with_mqa"]["kernel"]
    c_kv = rms_norm(kv[..., :rank], p["kv_a_layernorm"], eps)
    k_r = rope(kv[..., rank:], theta)
    kv = (c_kv @ p["kv_b_proj"]["kernel"]).reshape(b, s, n_head, -1)
    k_r = jnp.broadcast_to(k_r[:, :, None], (b, s, n_head, rope_dim))
    return jnp.concatenate([kv[..., :nope], k_r], -1), kv[..., nope:]


def mla(x, p, *, n_head, nope, rope_dim, rank, eps, theta):
    b, s, _ = x.shape
    c_q = rms_norm(x @ p["q_a_proj"]["kernel"], p["q_a_layernorm"], eps)
    q = (c_q @ p["q_b_proj"]["kernel"]).reshape(b, s, n_head, nope + rope_dim)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], -1)
    k, v = expanded_keys_and_values(x, p, n_head=n_head, nope=nope,
                                    rope_dim=rope_dim, rank=rank, eps=eps,
                                    theta=theta)
    keep = jnp.tril(jnp.ones((s, s), bool))

    def one_head(qkv):                              # each [B, S, d]
        qh, kh, vh = qkv
        scores = qh @ kh.transpose(0, 2, 1) / jnp.sqrt(F32(nope + rope_dim))
        return jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1) @ vh

    by_head = lambda t: t.transpose(2, 0, 1, 3)
    out = jax.lax.map(jax.checkpoint(one_head),
                      (by_head(q), by_head(k), by_head(v)))
    return out.transpose(1, 2, 0, 3).reshape(b, s, -1) @ p["o_proj"]["kernel"]


def router_weights(x, p, *, k, factor, norm_topk):
    """``[T, E]``: the weight of every expert for every token, zero where
    it was not chosen. Top-k by k rounds of arg-max over ``s + b``."""
    s = jax.nn.sigmoid(x @ p["router"])
    remaining = s + p["e_score_correction_bias"]
    chosen = jnp.zeros(s.shape, bool)
    for _ in range(k):
        pick = jax.nn.one_hot(jnp.argmax(remaining, -1), s.shape[-1],
                              dtype=bool)
        chosen |= pick
        remaining = jnp.where(pick, -jnp.inf, remaining)
    w = jnp.where(chosen, s, 0.0)
    if norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * factor


def expert_layer(x, p, *, k, factor, norm_topk, first_held, shared=True):
    """The part of the layer the held experts give, plus the shared
    expert (``shared=False`` leaves it out: a test adds the shares up)."""
    b, s, d = x.shape
    x = x.reshape(b * s, d)
    held = p["experts_gate"].shape[0]
    w = router_weights(x, p, k=k, factor=factor, norm_topk=norm_topk)
    w = w[:, first_held:first_held + held].T                # [held, T]

    def add(y, expert):
        w_e, w_gate, w_up, w_down = expert
        return y + w_e[:, None] * swiglu(x, w_gate, w_up, w_down), None

    y = (swiglu(x, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
                p["shared_down"]["kernel"]) if shared else jnp.zeros_like(x))
    y, _ = jax.lax.scan(jax.checkpoint(add), y, (
        w, p["experts_gate"], p["experts_up"], p["experts_down"]))
    return y.reshape(b, s, d)


def settings(config):
    """What the functions below need of a configuration: the benchmark's
    file, or any mapping with the published names (a model's dataclass as
    a dict, with the MTP weight under ``assumed``)."""
    return dict(
        n_head=config["num_attention_heads"],
        nope=config["qk_nope_head_dim"], rope_dim=config["qk_rope_head_dim"],
        rank=config["kv_lora_rank"], eps=config["rms_norm_eps"],
        theta=float(config["rope_theta"]), k=config["num_experts_per_tok"],
        factor=config["routed_scaling_factor"],
        norm_topk=config["norm_topk_prob"],
        first_held=config["first_held_expert"],
        mtp_weight=config["assumed"]["mtp_loss_weight"])


def block(x, p, *, k, factor, norm_topk, first_held, eps, **attn):
    x = x + mla(rms_norm(x, p["input_layernorm"], eps), p["self_attn"],
                eps=eps, **attn)
    h = rms_norm(x, p["post_attention_layernorm"], eps)
    if "router" not in p["mlp"]:
        m = p["mlp"]
        y = swiglu(h, m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                   m["down_proj"]["kernel"])
    else:
        y = expert_layer(h, p["mlp"], k=k, factor=factor,
                         norm_topk=norm_topk, first_held=first_held)
    return x + y


def logits(params, input_ids, *, mtp_weight=None, **kw):
    """``[batch, seq]`` ids -> ``(main, mtp)`` float32 logits ``[batch,
    seq, vocab]``: ``main[i]`` scores ``t_{i+1}``, ``mtp[i]`` scores
    ``t_{i+2}`` (``None`` without an MTP module in the tree)."""
    del mtp_weight
    with jax.default_matmul_precision("highest"):
        p = c.to_f32(params)
        eps = kw["eps"]
        run = jax.checkpoint(lambda x, lp: block(x, lp, **kw))
        n = sum(1 for name in p if name.startswith("layers_"))
        x = p["embed_tokens"][input_ids]
        for i in range(n):
            x = run(x, p[f"layers_{i}"])
        main = rms_norm(x, p["norm"], eps) @ p["lm_head"].T
        if "mtp_block" not in p:
            return main, None
        nxt = jnp.pad(input_ids[:, 1:], ((0, 0), (0, 1)))
        h = jnp.concatenate(
            [rms_norm(p["embed_tokens"][nxt], p["mtp_enorm"], eps),
             rms_norm(x, p["mtp_hnorm"], eps)], -1)
        h = run(h @ p["mtp_eh_proj"]["kernel"], p["mtp_block"])
        return main, rms_norm(h, p["mtp_norm"], eps) @ p["lm_head"].T


def nll(params, batch, *, mtp_weight, **kw):
    """``(sum, count)`` such that ``sum / count`` is ``CE_main + weight *
    CE_mtp`` as ``train_batch()`` reports it: ``count`` is the main
    loss's (every position but the last), and the MTP sum (every position
    but the last two) is scaled by ``weight * count / count_mtp`` before
    it is added. Every sequence is full, so the ratio is the same in every
    chunk and chunks add up to exactly their own mean."""
    ids = batch["input_ids"]
    left = lambda t: jnp.pad(t[:, 1:], ((0, 0), (0, 1)),
                             constant_values=-100)
    main, mtp = logits(params, ids, **kw)
    total, count = c.token_nll(main, left(ids))
    if mtp is not None:
        mtp_total, mtp_count = c.token_nll(mtp, left(left(ids)))
        total = total + mtp_weight * mtp_total * count / mtp_count
    return total, count


def loss(params, batch, **kw):
    return c.mean_of(nll(params, batch, **kw))
