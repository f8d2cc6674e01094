"""Embedding lookup with a row-sparse cross-rank gradient.

The forward is an ordinary row gather; its backward is XLA's scatter-add
into the [V, D] table. With ``sparse_grad_axes`` the backward exchanges
the touched rows over the data axes instead of all-reducing the dense
table gradient (config ``sparse_gradients: true``; parity-tested against
the dense path in tests/test_sparse_grads.py).
"""

import jax
import jax.numpy as jnp
import numpy as np


def _make_lookup_sparse(mesh, axes):
    """Embedding lookup whose VJP exchanges TOUCHED ROWS over the data
    axes instead of letting GSPMD all-reduce the dense [V, D] cotangent —
    the engine-automatic ``sparse_gradients`` path (reference
    deepspeed/runtime/engine.py:1530-1586 exchanges CSR index/value
    tensors; here the exchange is an all_gather of (ids, per-token rows)
    inside the op's custom VJP, wire bytes ∝ batch tokens, then a local
    scatter-add rebuilds the dense gradient on every rank)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.comm.sparse import row_sparse_allreduce, scatter_rows

    @jax.custom_vjp
    def lookup(table, ids):
        return jnp.take(table, ids, axis=0)

    def fwd(table, ids):
        return jnp.take(table, ids, axis=0), (table, ids)

    def bwd(res, g):
        table, ids = res
        v, d = table.shape
        flat_ids = ids.reshape(ids.shape[0], -1)
        rows = g.reshape(g.shape[0], -1, d).astype(jnp.float32)
        if mesh is None or all(mesh.shape.get(a, 1) <= 1 for a in axes):
            dense = scatter_rows(flat_ids.reshape(-1),
                                 rows.reshape(-1, d), v)
        else:
            spec = P(axes if len(axes) > 1 else axes[0])

            def body(i, r):
                # Cotangents SUM over data shards (GSPMD convention);
                # the loss's global-batch mean already divided.
                return row_sparse_allreduce(i.reshape(-1),
                                            r.reshape(-1, d), v,
                                            axis=axes, mean=False)

            dense = shard_map(body, mesh=mesh, in_specs=(spec, spec),
                              out_specs=P(), axis_names=set(axes),
                              check_vma=False)(flat_ids, rows)
        return dense.astype(table.dtype), np.zeros(ids.shape,
                                                   jax.dtypes.float0)

    lookup.defvjp(fwd, bwd)
    return lookup


def resolve_sparse_grad_spec(setting):
    """Model-config helper -> ``(mesh, axes)`` or None (dense path).

    ``setting`` forms: falsy -> None; ``(mesh, axes)`` (what
    ``deepspeed_tpu.initialize()`` bakes in — the ENGINE's mesh, pinned
    at surgery time so the exchange never binds to whatever ambient mesh
    an unrelated engine registered first); a bare axes tuple or ``True``
    -> the ambient default mesh (custom-loop use; in a multi-mesh
    process prefer the explicit form)."""
    if not setting:
        return None
    from deepspeed_tpu.parallel.mesh import (DATA_AXIS, DCN_AXIS,
                                             get_default_mesh)
    from jax.sharding import Mesh

    if (isinstance(setting, tuple) and len(setting) == 2
            and isinstance(setting[0], Mesh)):
        return setting
    mesh = get_default_mesh()
    if setting is True:
        if mesh is None:
            return None
        from deepspeed_tpu.parallel.mesh import data_like_axes

        # Size-1 everywhere still routes through the sparse path (local
        # scatter only) so the config toggle is honored uniformly.
        return mesh, data_like_axes(mesh)
    return mesh, tuple(setting)


def embedding_lookup(table: jax.Array, ids: jax.Array,
                     sparse_grad_axes=None) -> jax.Array:
    """``table[ids]`` ([V, D] x [...] int -> [..., D]) with a selectable
    gradient path: XLA scatter-add (default) or — with
    ``sparse_grad_axes`` (mesh axis names, batch dim 0) — the row-sparse
    cross-rank exchange (config ``sparse_gradients: true``)."""
    if sparse_grad_axes:
        spec = resolve_sparse_grad_spec(sparse_grad_axes)
        if spec is None:
            return jnp.take(table, ids, axis=0)
        mesh, axes = spec
        return _make_lookup_sparse(mesh, tuple(axes))(table, ids)
    return jnp.take(table, ids, axis=0)
