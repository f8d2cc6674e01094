"""Grouped matmul for groups of few rows — Pallas TPU kernel ``moe_gmm``.

``grouped_matmul(lhs [m, K], rhs [G, K, N], group_sizes [G], tm)`` is
``jax.lax.ragged_dot``: rows ``[start_g, start_g + size_g)`` of ``lhs``
times ``rhs[g]``, the groups laid out one after another from row 0; rows
past the last group are no group's and are left as the kernel finds them
(the caller masks them, as it does for ``ragged_dot``).

What it is for: the held experts at serving, where a group (an expert)
gets a few rows, 3 at decode and 1-89 a prefill. XLA's kernel costs the
same whatever the rows a group, 4 or 512 (PR 36's probe: about 42 us a
touched group where its weights need 6.7): it computes a large row tile
(PR 26: 512 rows) for every group it touches, rows that do not exist.
Here the grid walks the visits, a visit being one (group, ``tm``-row
tile) pair the groups overlap, in row order: each touched group's
weights are read once (two visits of one group in a row read them once:
the block index does not change), a row tile shared by
several groups stays in VMEM while they fill it, and empty groups and the
rows past the last group cost nothing. Every output row is one float32
accumulation over the whole contraction, rounded once to ``lhs``'s dtype:
``ragged_dot``'s arithmetic.

The backward is ``ragged_dot``'s own (``custom_vjp``): the kernel is for
the forward of the shapes ``moe/dropless.py`` sends it; a training shape
it caught would keep its gradient.

``interpret=True`` runs the same kernel through the Pallas interpreter for
CPU tests.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_matmul", "row_tile", "row_tiles_visited"]

# Where XLA's kernel gives way: under this many rows a group expected.
MAX_ROWS_PER_GROUP = 128
# The row tile. Measured on a v5e (tools/probe_grouped_matmul.py, PR 36;
# PERF.md section 6), device us of one matmul at nemotron3s-serve-chat's
# shapes, up 1024 -> 2688 and down 2688 -> 1024, 128 held experts:
#
#                     rows a  ragged
#                     group   _dot   tm 16  tm 32  tm 64  tm 128
#   decode       up    3.3    3620    938    923    922    919
#                down         2738    951    929    920    918
#   prefill 256  up   10.9    5333   1075   1015   1001    997
#                down         3659   1129   1039    995    990
#   prefill 1024 up   43.6    5696   1407   1235   1207   1181
#                down         3896   1657   1345   1181   1149
#   prefill 2048 up   88.9    6269   1817   1446   1437   1422
#                down         4258   2344   1697   1402   1360
#
# The weights set the time (a touched expert's 5.5 MB a matmul: 830-964 us
# needed), so a row tile of 128 costs nothing at 3 rows a group and saves
# grid steps at 89: it is the fastest at every shape. XLA's kernel takes
# 5.4-6.1 ms whatever the rows a group from 4 to 512 (all 128 groups
# touched): 42-48 us a touched group where its weights need 6.7.
ROW_TILE = 128


def row_tile(rows_per_group: float) -> Optional[int]:
    """The kernel's row tile for groups that expect ``rows_per_group``
    rows; None (XLA's kernel) from ``MAX_ROWS_PER_GROUP`` rows a group
    up."""
    return ROW_TILE if rows_per_group < MAX_ROWS_PER_GROUP else None


def _tiles(group_sizes: jax.Array, tm: int) -> Tuple[jax.Array, jax.Array]:
    """``(tiles, first)``: how many ``tm``-row tiles each group overlaps (0
    for an empty one), and the first of them."""
    ends = jnp.cumsum(group_sizes)
    first = (ends - group_sizes) // tm
    return jnp.where(group_sizes > 0, (ends + tm - 1) // tm - first, 0), first


def _visits(group_sizes: jax.Array, m: int, tm: int
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(group_of, tile_of, n)``: the first ``n`` entries are the grid's
    visits in row order, each a group and a row tile it overlaps. Sized for
    the most there can be: every row tile once, plus one for each group
    that starts inside a tile. (A comparison against the running count of
    tiles, not ``jnp.repeat``: the set-up traces this once a program shape,
    and ``repeat`` costs several times more to trace.)"""
    groups = group_sizes.shape[0]
    tiles, first = _tiles(group_sizes, tm)
    upto = jnp.cumsum(tiles)                   # visits through group g
    visit = jnp.arange(m // tm + groups - 1, dtype=jnp.int32)
    group_of = jnp.minimum((upto[None, :] <= visit[:, None]).sum(
        1, dtype=jnp.int32), groups - 1)
    tile_of = (first - (upto - tiles))[group_of] + visit
    return (group_of, jnp.minimum(tile_of, m // tm - 1).astype(jnp.int32),
            upto[-1].astype(jnp.int32))


def row_tiles_visited(group_sizes: jax.Array, tm: int) -> jax.Array:
    """How many ``tm``-row tiles the kernel computes for these groups
    (int32): ``tm`` times it is the rows it computes."""
    return _tiles(group_sizes, tm)[0].sum(dtype=jnp.int32)


def _kernel(offsets, group_of, tile_of, lhs_ref, rhs_ref, out_ref, *, tm):
    # one visit: the rows of this tile that are this group's get their
    # product; the tile's other rows keep what an earlier group wrote
    visit = pl.program_id(0)
    g = group_of[visit]
    row = tile_of[visit] * tm + jax.lax.broadcasted_iota(
        jnp.int32, out_ref.shape, 0)
    mine = (row >= offsets[g]) & (row < offsets[g + 1])
    product = jnp.dot(lhs_ref[...], rhs_ref[...],
                      preferred_element_type=jnp.float32)
    out_ref[...] = jnp.where(mine, product.astype(out_ref.dtype),
                             out_ref[...])


# jitted: a program's expert layers call it at one shape each, and the
# kernel is then traced and lowered once a shape, not once a call (ten
# calls a serving program: 6-7 s of the cell's set-up, PR 36)
@functools.partial(jax.jit, static_argnums=(3, 4))
def _forward(lhs, rhs, group_sizes, tm, interpret):
    m, k = lhs.shape
    n = rhs.shape[2]
    pad = (-m) % tm
    if pad:
        lhs = jnp.concatenate([lhs, jnp.zeros((pad, k), lhs.dtype)])
    group_of, tile_of, visits = _visits(group_sizes, m + pad, tm)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(group_sizes).astype(jnp.int32)])
    # an expert's weights are read whole (blocks of 2 MB in place of 5.5
    # read 5-19% slower, PR 36's probe), two in VMEM at once: the next
    # streams in while this one is used
    blocks = 2 * (tm * k + k * n + tm * n) * lhs.dtype.itemsize
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(visits,),
            # o, g, t: the prefetched offsets, and each visit's group and
            # row tile
            in_specs=[pl.BlockSpec((tm, k), lambda v, o, g, t: (t[v], 0)),
                      pl.BlockSpec((None, k, n),
                                   lambda v, o, g, t: (g[v], 0, 0))],
            out_specs=pl.BlockSpec((tm, n), lambda v, o, g, t: (t[v], 0))),
        out_shape=jax.ShapeDtypeStruct((m + pad, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(16 * 2**20, blocks + 8 * 2**20)),
        interpret=interpret,
        name="moe_gmm",
    )(offsets, group_of, tile_of, lhs, rhs)
    return out[:m] if pad else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   tm: int, interpret: bool = False) -> jax.Array:
    """``jax.lax.ragged_dot(lhs, rhs, group_sizes)`` in ``tm``-row tiles
    (``tm`` a multiple of 16; ``lhs`` and ``rhs`` of one dtype)."""
    return _forward(lhs, rhs, group_sizes, tm, interpret)


def _grouped_matmul_fwd(lhs, rhs, group_sizes, tm, interpret):
    return (_forward(lhs, rhs, group_sizes, tm, interpret),
            (lhs, rhs, group_sizes))


def _grouped_matmul_bwd(tm, interpret, res, g):
    lhs, rhs, group_sizes = res
    _, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, group_sizes),
                     lhs, rhs)
    return vjp(g) + (None,)


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
