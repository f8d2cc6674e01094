"""The first step of Adam (Kingma & Ba 2015, algorithm 1, bias-corrected;
decoupled weight decay as Loshchilov & Hutter 2019), from zero moments.

After one step the corrected moments are the gradient and its square
whatever the betas are, so the step is ``lr * g / (|g| + eps)``. Found by
the ``optimizer.type`` of a configuration's ``train_engine`` and called
with its ``params``; a key this file does not know is an error, so a
setting the reference ignores can never pass for checked."""

import jax
import jax.numpy as jnp


def first_step(params, grads, *, lr, eps=1e-8, weight_decay=0.0,
               betas=None):
    del betas                   # they cancel in the first corrected step

    def leaf(w, g):
        update = g / (jnp.abs(g) + eps)
        return w - lr * (update + weight_decay * w)

    return jax.tree_util.tree_map(leaf, params, grads)
