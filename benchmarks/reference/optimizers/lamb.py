"""The first step of LAMB (You et al. 2020, algorithm 2), from zero
moments, with the trust ratio clamped as DeepSpeed's ``FusedLamb`` clamps
it (``max_coeff`` 10, ``min_coeff`` 0.01) and taken as 1 for a tensor
whose weights or whose update are all zero.

After one step the corrected moments are the gradient and its square
whatever the betas are, so a tensor's update is ``u = g / (|g| + eps)
+ weight_decay * w`` and its step ``lr * clip(|w| / |u|) * u``. Found and
called as ``adam.py`` is."""

import jax
import jax.numpy as jnp


def first_step(params, grads, *, lr, eps=1e-8, weight_decay=0.0,
               max_coeff=10.0, min_coeff=0.01, betas=None):
    del betas                   # they cancel in the first corrected step

    def leaf(w, g):
        update = g / (jnp.abs(g) + eps) + weight_decay * w
        w_norm = jnp.sqrt(jnp.sum(jnp.square(w)))
        u_norm = jnp.sqrt(jnp.sum(jnp.square(update)))
        trust = jnp.where((w_norm > 0) & (u_norm > 0), w_norm / u_norm, 1.0)
        return w - lr * jnp.clip(trust, min_coeff, max_coeff) * update

    return jax.tree_util.tree_map(leaf, params, grads)
