"""Fused softmax cross-entropy head.

The [N, V] logits tensor is the biggest intermediate in LM training — at
the GPT-2 bench shape (16x512 tokens x 50257 vocab) it is 1.6 GB in fp32,
and the stock jax path materializes it several times over (einsum output,
``log_softmax`` residual saved for backward, backward softmax grad):
measured 9.5 ms of the 73 ms GPT-2 microbatch, almost all HBM traffic
(``tools/perf_probe_r3.py``, PROFILE.md). This op removes most of it:

- logits are stored in the model's compute dtype (fp32 MXU accumulation,
  bf16 store under mixed precision — halves every HBM pass; exact fp32
  when the model computes in fp32). For parity-sensitive runs,
  ``logits_fp32=True`` computes the logits einsum with
  ``preferred_element_type=float32`` — identical numerics to the unfused
  ``cross_entropy_with_ignore`` path at the cost of the fp32 HBM pass;
- the custom VJP saves only the per-row logsumexp: backward *recomputes*
  the logits (one extra MXU matmul — cheap) instead of reading a saved
  fp32 log-softmax from HBM;
- ``dlogits = (softmax − onehot)·g`` fuses into the two backward matmuls
  (``one_hot`` lowers to an elementwise compare, so no [N, V] one-hot
  buffer exists).

Reference analogue: none — torch autograd keeps the log-softmax
activations; this is the HBM-economy redesign the TPU roofline demands
(head matmul runs at ~180 flop/byte; the stock CE passes run at ~0).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=None)
def _make_fused_nll(with_bias: bool, logits_fp32: bool):
    """Build the custom-VJP per-token NLL for one (bias, dtype) variant.

    With ``logits_fp32`` every logits(-grad) einsum carries
    ``preferred_element_type=float32`` so bf16 inputs never round the
    logits to bf16 before the logsumexp (the unfused path's numerics)."""
    pet = jnp.float32 if logits_fp32 else None

    def logits_of(x, w, b):
        out = jnp.einsum("nd,vd->nv", x, w,
                         preferred_element_type=pet).astype(jnp.float32)
        return out + b if with_bias else out

    def nll_of(logits, labels):
        m = jnp.max(logits, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
        picked = jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
        return lse - picked, lse

    if with_bias:
        @jax.custom_vjp
        def fused(x, w, b, labels):
            return nll_of(logits_of(x, w, b), labels)[0]

        def fwd(x, w, b, labels):
            nll, lse = nll_of(logits_of(x, w, b), labels)
            return nll, (x, w, b, labels, lse)

        def bwd(res, g):
            x, w, b, labels, lse = res
            v = w.shape[0]
            logits = logits_of(x, w, b)
            p = jnp.exp(logits - lse[:, None])
            dlog32 = ((p - jax.nn.one_hot(labels, v, dtype=jnp.float32))
                      * g[:, None])
            dlogits = dlog32 if logits_fp32 else dlog32.astype(x.dtype)
            dx = jnp.einsum("nv,vd->nd", dlogits, w,
                            preferred_element_type=pet).astype(x.dtype)
            dw = jnp.einsum("nv,nd->vd", dlogits, x,
                            preferred_element_type=pet).astype(w.dtype)
            db = dlog32.sum(axis=0).astype(b.dtype)
            return dx, dw, db, np.zeros(labels.shape, jax.dtypes.float0)
    else:
        @jax.custom_vjp
        def fused(x, w, labels):
            return nll_of(logits_of(x, w, None), labels)[0]

        def fwd(x, w, labels):
            nll, lse = nll_of(logits_of(x, w, None), labels)
            return nll, (x, w, labels, lse)

        def bwd(res, g):
            x, w, labels, lse = res
            v = w.shape[0]
            logits = logits_of(x, w, None)
            p = jnp.exp(logits - lse[:, None])
            dlog32 = ((p - jax.nn.one_hot(labels, v, dtype=jnp.float32))
                      * g[:, None])
            dlogits = dlog32 if logits_fp32 else dlog32.astype(x.dtype)
            dx = jnp.einsum("nv,vd->nd", dlogits, w,
                            preferred_element_type=pet).astype(x.dtype)
            dw = jnp.einsum("nv,nd->vd", dlogits, x,
                            preferred_element_type=pet).astype(w.dtype)
            return dx, dw, np.zeros(labels.shape, jax.dtypes.float0)

    fused.defvjp(fwd, bwd)
    return fused


def fused_cross_entropy(x: jax.Array, w: jax.Array, labels: jax.Array,
                        ignore_index: int = -100,
                        w_transposed: bool = False,
                        bias: jax.Array = None,
                        logits_fp32: bool = False) -> jax.Array:
    """Token-mean cross entropy of ``x @ w.T`` against ``labels``,
    ignoring ``ignore_index`` positions — drop-in for
    ``cross_entropy_with_ignore(logits, labels)`` that never materializes
    fp32 logits (under mixed precision) nor a saved log-softmax.

    x: [..., D] activations (compute dtype), w: [V, D] tied-embedding
    layout (or [D, V] with ``w_transposed``), labels: [...] int.
    ``logits_fp32`` keeps the unfused path's exact fp32-logits numerics
    (ADVICE r3: bf16 configs otherwise see a silent numerics change).
    """
    d = x.shape[-1]
    n = int(np.prod(x.shape[:-1]))
    if w_transposed:
        w = w.T
    xf = x.reshape(n, d)
    lf = labels.reshape(n)
    valid = lf != ignore_index
    safe = jnp.where(valid, lf, 0).astype(jnp.int32)
    if bias is not None:
        nll = _make_fused_nll(True, bool(logits_fp32))(
            xf, w.astype(x.dtype), bias.astype(jnp.float32), safe)
    else:
        nll = _make_fused_nll(False, bool(logits_fp32))(
            xf, w.astype(x.dtype), safe)
    nll = jnp.where(valid, nll, 0.0)
    return nll.sum() / jnp.maximum(valid.sum(), 1)
