"""Row-sparse gradient exchange — the CSR embedding-gradient capability.

Reference: ``deepspeed/runtime/engine.py:1530-1586`` (``sparse_gradients``:
embedding grads travel as CSR tensors — ``csr_tensor.py`` — so the
allreduce moves touched rows instead of the full [V, D] table).

TPU framing (see runtime/sparse_tensor.py for the full rationale): XLA AD
always materialises dense gradients, so the ENGINE's automatic grad
allreduce cannot be sparsified behind the user's back. But the capability
itself — exchanging only touched embedding rows across data ranks — is
expressible as an explicit collective for custom training loops: each rank
contributes ``(ids [N], rows [N, D])`` (its microbatch's per-token
gradients, pre-scatter), the exchange is an ``all_gather`` of both
(``2 · n · N · D`` bytes vs ``2 · V · D`` for the dense ring allreduce —
the win whenever ``n·N ≪ V``, i.e. giant vocab, small batch), and the
dense [V, D] gradient is rebuilt locally by scatter-add AFTER the wire.

``row_sparse_allreduce`` runs inside a data-manual shard_map;
``row_sparse_allreduce_jit`` is the jit-level entry used by tests.
"""

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.parallel.mesh import DATA_AXIS


def rows_from_tokens(ids: jax.Array, g_tokens: jax.Array
                     ) -> Tuple[jax.Array, jax.Array]:
    """Flatten per-token embedding grads to (ids [N], rows [N, D]) — the
    CSR-building step (reference csr_tensor.py from dense rows)."""
    d = g_tokens.shape[-1]
    return ids.reshape(-1), g_tokens.reshape(-1, d)


def scatter_rows(ids: jax.Array, rows: jax.Array, vocab: int) -> jax.Array:
    """(ids, rows) -> dense [V, D] gradient by scatter-add."""
    return jnp.zeros((vocab, rows.shape[-1]), rows.dtype).at[ids].add(rows)


def row_sparse_allreduce(ids: jax.Array, rows: jax.Array, vocab: int,
                         axis=DATA_AXIS,
                         mean: bool = True) -> jax.Array:
    """Inside a manual shard_map over ``axis`` (one name or a tuple of
    names): gather every rank's (ids, rows) and scatter-add into the dense
    [V, D] mean gradient — wire bytes scale with touched rows, not
    vocab."""
    all_ids = jax.lax.all_gather(ids, axis, axis=0, tiled=True)
    all_rows = jax.lax.all_gather(rows, axis, axis=0, tiled=True)
    dense = scatter_rows(all_ids, all_rows, vocab)
    if mean:
        dense = dense / jax.lax.psum(1, axis)
    return dense


def row_sparse_allreduce_jit(ids: jax.Array, rows: jax.Array, vocab: int,
                             mesh: Mesh, axis: str = DATA_AXIS,
                             mean: bool = True) -> jax.Array:
    """jit-level entry: ``ids`` [n, N] / ``rows`` [n, N, D] carry each
    rank's contribution on the leading (sharded) dim; returns the dense
    averaged [V, D] gradient, replicated."""
    def body(i, r):
        return row_sparse_allreduce(i[0], r[0], vocab, axis, mean)

    mapped = shard_map(body, mesh=mesh,
                       in_specs=(P(axis), P(axis)),
                       out_specs=P(),
                       axis_names={axis}, check_vma=False)
    return jax.jit(mapped)(ids, rows)


__all__ = ["row_sparse_allreduce", "row_sparse_allreduce_jit",
           "rows_from_tokens", "scatter_rows"]
