"""Paged/blockwise KV cache — the serving tier's memory system.

vLLM's PagedAttention insight (arXiv 2309.06180) re-done TPU-native: the
KV cache is a **preallocated pool of fixed-size blocks** plus per-sequence
**block tables**, so sequences of wildly different lengths share one HBM
allocation with no fragmentation and no reallocation as they grow. Every
device op here is **static-shape** — pool, block table and gather sizes
are fixed at engine build — so XLA compiles the decode program once and
never retraces as sequences grow, join or leave (the per-request
``dynamic_update_slice`` cache of ``inference/engine.py`` recompiles per
(batch, length) pair; this is what replaces it under continuous batching).

Layout (per transformer layer, all layers share one block table):

- ``k``/``v`` pool: ``[num_blocks, block_size, heads * head_dim]`` in the
  model's compute dtype — or **int8** with per-(token, head) fp32 scales
  ``[num_blocks, block_size, heads]`` when ``int8=True``. Quantization is
  the SAME deterministic RTNE blockwise round-trip the DCN gradient path
  uses (:func:`deepspeed_tpu.comm.quantize.quantize_blockwise` with
  ``block_size=head_dim``) — one int8 implementation in the tree.

  Heads are **folded into the lane axis**, and this is the ONE stored
  form: allocated, carried between programs, scattered into and gathered
  from as such, by every program and both Pallas kernels. Why: a
  ``[N, BS, H, 64]`` array has a 64-wide minor axis, which row-major
  ``(8, 128)`` tiles would pad to 128 lanes; the TPU runtime avoids the
  padding by handing such an array over in a compact layout with the
  BLOCK axis minor-most (``{0,3,2,1}``), which no gather or scatter over
  blocks can use. Every program that took the pool therefore copied the
  whole of it into row-major on entry and back on exit (70 of a 163 ms
  decode step and all 70 ms of a pack at gpt2-medium with 4097 blocks;
  PERF.md section 6, PR 25). ``[N, BS, H * D]`` has a minor axis that is
  a multiple of 128 at every real model's width, arrives row-major, and
  is read and written in place. Only the small activations (this step's
  chunk, the gathered window) are reshaped to and from ``[.., H, D]``.
- block table: ``[batch_slots, max_blocks_per_seq]`` int32, row ``b``
  listing the pool blocks of the sequence in slot ``b``. **Block 0 is a
  reserved scratch block**: inactive slots point at it, so their (masked,
  discarded) decode writes land somewhere harmless and the program needs
  no branch on slot liveness.

- live list (the default decode, :meth:`PagedLayerCache.attend_live`):
  ``[chunks, G, R + 2]`` int32 built on the host each step by
  :func:`live_block_list` — the blocks every active row reads, a row
  after a row, in runs of R blocks of ONE row (the last run of a row
  padded), and with each run the slot that owns it and the position of
  its first key; G runs make a chunk — plus the number of chunks that
  hold anything. The decode program walks that many chunks and no more,
  so what it reads follows what is live, not the ``slots x max_blocks``
  the table reserves; the trip count is data, so it is still ONE
  compiled program.

Host-side block accounting (:class:`BlockPool`) is plain python — a free
list is microseconds per step and never touches the device.

**Which layers have what** is the model's to say (``model.
serving_cache_spec()``, one :class:`LayerCacheSpec` a layer):

- :func:`kv` ``(heads, head_dim)``: a K and a V pool as above, sized by the
  layer's KEY/VALUE heads (a grouped-query layer with 2 of them at 128
  holds 256 lanes a position whatever its query heads);
- :func:`recurrent` ``(shapes)``: state that does not grow with positions,
  one array ``[slots, *shape]`` per named entry, a row a batch slot
  (:class:`RecurrentLayerState` inside the decode program): a Mamba-2
  layer's float32 SSM state and the tail of its convolution. A prefill
  starts from zeros and :func:`pack_prefill` writes the slot's rows
  whole, so admission needs no clearing pass and release none either:
  the slot's rows are simply the next request's to overwrite;
- :func:`none`: nothing (an expert layer, an MLP).
"""

import functools
from dataclasses import dataclass
from typing import Any, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.comm.quantize import quantize_blockwise
from deepspeed_tpu.telemetry.tracer import device_scope


@dataclass(frozen=True)
class LayerCacheSpec:
    """What one layer keeps between the steps of a request."""
    kind: str                           # "kv" | "recurrent" | "none"
    heads: int = 0                      # kv: key/value heads
    head_dim: int = 0
    shapes: Tuple[Tuple[str, Tuple[int, ...], Any], ...] = ()  # recurrent


def kv(heads: int, head_dim: int) -> LayerCacheSpec:
    return LayerCacheSpec("kv", int(heads), int(head_dim))


def recurrent(shapes: Dict[str, Tuple[Tuple[int, ...], Any]]
              ) -> LayerCacheSpec:
    """``shapes``: name -> (shape of ONE slot's entry, dtype)."""
    return LayerCacheSpec("recurrent", shapes=tuple(
        (name, tuple(shape), dtype) for name, (shape, dtype)
        in shapes.items()))


def none() -> LayerCacheSpec:
    return LayerCacheSpec("none")


class BlockPool:
    """Host-side free-list allocator over ``num_blocks`` pool slots.

    Block 0 is reserved as the scratch block for inactive batch slots and
    is never handed out; ``capacity`` is therefore ``num_blocks - 1``.

    Blocks are **ref-counted** so the prefix cache can share immutable
    prompt-head blocks copy-on-write across sequences
    (``serving/scheduler.py PrefixCache``): ``alloc`` hands out blocks at
    refcount 1, ``share`` bumps an already-allocated block, and
    ``release`` decrements — a block returns to the free list only when
    its last holder lets go. A pool with no sharing behaves exactly like
    the plain free list it used to be.
    """

    SCRATCH = 0

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(f"need >= 2 blocks (1 is reserved scratch), "
                             f"got {num_blocks}")
        self.num_blocks = int(num_blocks)
        self._free: List[int] = list(range(1, self.num_blocks))
        # Mirror of _free for O(1) double-free checks: releasing a long
        # sequence must stay microseconds even at multi-thousand-block
        # pools.
        self._free_set = set(self._free)
        self._refs: Dict[int, int] = {}

    @property
    def capacity(self) -> int:
        return self.num_blocks - 1

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.capacity - len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` blocks or None (never a partial grant — the caller either
        admits a sequence whole or leaves it queued)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        taken, self._free = self._free[:n], self._free[n:]
        self._free_set.difference_update(taken)
        for b in taken:
            self._refs[b] = 1
        return taken

    def share(self, blocks: List[int]) -> None:
        """Take one more reference on already-allocated blocks (the COW
        adoption path — a new sequence, or the prefix cache itself,
        becomes a co-holder of an immutable prompt-head block)."""
        for b in blocks:
            if b == self.SCRATCH:
                raise ValueError("scratch block cannot be shared")
            if b not in self._refs:
                raise ValueError(f"share of unallocated block {b}")
        for b in blocks:
            self._refs[b] += 1

    def refcount(self, block: int) -> int:
        return self._refs.get(block, 0)

    def release(self, blocks: List[int]) -> None:
        """Drop one reference per block; a block frees only at zero."""
        for b in blocks:
            if b == self.SCRATCH:
                raise ValueError("scratch block cannot be released")
            if b in self._free_set or b not in self._refs:
                raise ValueError(f"double free of block {b}")
        for b in blocks:
            self._refs[b] -= 1
            if self._refs[b] == 0:
                del self._refs[b]
                self._free.append(b)
                self._free_set.add(b)


def live_block_list(rows: Iterable[Tuple[int, List[int], int]],
                    block_size: int, run: int, group: int,
                    chunks: int) -> Tuple[np.ndarray, int, int]:
    """The batch's live KV blocks as one flat list, for
    :meth:`PagedLayerCache.attend_live`.

    ``rows``: ``(slot, block_table, pos)`` of each active sequence, ``pos``
    being the position this step writes. Every block of a row up to and
    including the one that holds ``pos`` is listed, a row after a row, in
    RUNS of ``run`` blocks; a row's last run is padded with the scratch
    block, so a run holds blocks of ONE row. ``group`` runs, whoever's,
    make a chunk, which is what the program takes a loop iteration.

    Returns ``(live, n_blocks, n_chunks)``: ``live`` is ``[chunks, group,
    run + 2]`` int32, a run a line: its pool blocks, then the slot that
    owns it (-1: nobody's, padding), then the position of its first key
    in that row; ``n_blocks`` the blocks listed and ``n_chunks`` the
    leading chunks that hold any. Pad blocks inside a run need no mark:
    their keys lie past ``pos``.

    ``chunks`` is fixed per engine (every slot at the table's width), so
    the program that takes the list has one signature."""
    live = np.zeros((chunks * group, run + 2), np.int32)
    live[:, run] = -1
    n_blocks = n_runs = 0
    for slot, table, pos in rows:
        nb = pos // block_size + 1
        nr = -(-nb // run)
        if len(table) < nb or n_runs + nr > len(live):
            raise ValueError(
                f"slot {slot} writes position {pos} with {len(table)} "
                f"blocks of {block_size}; the list holds {len(live)} runs "
                f"of {run}")
        blocks = np.zeros((nr * run,), np.int32)
        blocks[:nb] = table[:nb]
        mine = live[n_runs:n_runs + nr]
        mine[:, :run] = blocks.reshape(nr, run)
        mine[:, run] = slot
        mine[:, run + 1] = np.arange(nr) * run * block_size
        n_blocks += nb
        n_runs += nr
    return (live.reshape(chunks, group, run + 2), n_blocks,
            -(-n_runs // group))


def init_paged_pools(cfg, num_blocks: int, block_size: int,
                     int8: bool = False, dtype=None) -> Tuple:
    """:func:`init_serving_state` for a model whose every layer keeps keys
    and values, a key/value head a query head (``cfg.num_layers``,
    ``cfg.num_heads``, ``cfg.head_dim``)."""
    return init_serving_state(
        (kv(cfg.num_heads, cfg.head_dim),) * cfg.num_layers, num_blocks,
        block_size, slots=0, int8=int8,
        dtype=dtype if dtype is not None else cfg.dtype)


def init_serving_state(specs: Tuple[LayerCacheSpec, ...], num_blocks: int,
                       block_size: int, slots: int, int8: bool = False,
                       dtype=jnp.bfloat16) -> Tuple:
    """One entry a layer, by its spec. A ``kv`` layer: ``(k, v, k_scale,
    v_scale)`` pool arrays (scales are None in the fp path).
    Zero-initialised: scratch/unwritten slots dequantize to exact zeros,
    so masked attention terms stay exactly ``0 * 0``. K/V pools are
    ``[num_blocks, block_size, heads * head_dim]`` (the module docstring
    says why). The int8 SCALE pools stay ``[num_blocks, block_size,
    heads]``: at 1/32 of the pool's bytes their own layout round trip is
    small, and the two obvious folds (``[N, BS * H]`` written through a
    reshape, or by an element scatter) each still compile to pool-sized
    copies or reshapes. A ``recurrent`` layer: a tuple of ``[slots,
    *shape]`` zeros in the spec's order. A layer that keeps nothing:
    ``None``."""
    layers = []
    for spec in specs:
        if spec.kind == "kv":
            lanes = (num_blocks, block_size, spec.heads * spec.head_dim)
            scales = (num_blocks, block_size, spec.heads)
            layers.append(
                (jnp.zeros(lanes, jnp.int8), jnp.zeros(lanes, jnp.int8),
                 jnp.ones(scales, jnp.float32), jnp.ones(scales, jnp.float32))
                if int8 else
                (jnp.zeros(lanes, dtype), jnp.zeros(lanes, dtype), None, None))
        elif spec.kind == "recurrent":
            layers.append(tuple(jnp.zeros((slots,) + shape, dt)
                                for _, shape, dt in spec.shapes))
        else:
            layers.append(None)
    return tuple(layers)


@jax.tree_util.register_pytree_node_class
class RecurrentLayerState:
    """One recurrent layer's view of its state inside the decode program:
    ``arrays``, each ``[slots, ...]`` in the spec's order, and ``live``
    (``[slots]`` bool), the rows that hold a request. The layer reads its
    rows, returns ``replaced(new arrays)``, and leaves the rows of dead
    slots as they are."""

    def __init__(self, arrays: Tuple, live: jax.Array):
        self.arrays = tuple(arrays)
        self.live = live

    def tree_flatten(self):
        return (self.arrays, self.live), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def replaced(self, arrays) -> "RecurrentLayerState":
        return RecurrentLayerState(arrays, self.live)

    @property
    def pools(self) -> Tuple:
        """What the engine keeps of this layer, as ``PagedLayerCache``
        names it."""
        return self.arrays


def _quant_tokens(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """[..., H, D] float -> (int8 [..., H, D], fp32 scales [..., H]) —
    one RTNE quantization block per (token, head) vector."""
    q, s = quantize_blockwise(x.astype(jnp.float32), x.shape[-1])
    return q, s[..., 0]        # head_dim is one block: drop the block axis


@jax.tree_util.register_pytree_node_class
class PagedLayerCache:
    """One layer's view of the paged cache inside a jitted decode/prefill
    program: pools + the batch's block table and write positions.

    Passed as the per-layer cache to the GPT family's cache mode; the
    block calls :meth:`update` with this step's ``k``/``v`` chunk and gets
    back the updated cache, the full gathered K/V and the key-validity
    mask. All shapes are static: the gather is always
    ``[B, max_blocks * block_size, H, D]`` regardless of true lengths.
    """

    def __init__(self, k: jax.Array, v: jax.Array,
                 k_scale: Optional[jax.Array], v_scale: Optional[jax.Array],
                 block_table: jax.Array, pos: jax.Array,
                 block_size: int, dtype_name: str = "bfloat16",
                 attn_impl: str = "gather", clamp_writes: bool = False,
                 live: Optional[jax.Array] = None,
                 n_chunks: Optional[jax.Array] = None):
        self.k = k
        self.v = v
        self.k_scale = k_scale
        self.v_scale = v_scale
        self.block_table = block_table      # [B, MB] int32
        self.pos = pos                      # [B] int32 — next write index
        self.block_size = int(block_size)
        self.dtype_name = dtype_name
        # Static (aux) knobs of the serving fast path (docs/SERVING.md):
        # ``attn_impl`` — "gather" (the materializing path: the table
        # window through :meth:`update`) or "kernel" (the Pallas paged
        # decode-attention kernel; the model's paged branch reads it).
        # ``clamp_writes`` — route out-of-window writes to the scratch
        # block instead of relying on in-bounds positions; the
        # speculative-decode verify chunk can legally overshoot a
        # sequence's allocated blocks (rejected-token lookahead) and its
        # garbage must land somewhere harmless. Off by default: the plain
        # decode path never overshoots and must not pay the extra ops.
        self.attn_impl = str(attn_impl)
        self.clamp_writes = bool(clamp_writes)
        # The batch's live blocks (:func:`live_block_list`) and how many
        # chunks of them hold anything: given, the model's paged branch
        # takes :meth:`attend_live` and reads nothing else of the pool.
        self.live = live                    # [chunks, G, R + 2] int32 or None
        self.n_chunks = n_chunks            # [] int32

    # -- pytree ---------------------------------------------------------
    def tree_flatten(self):
        return ((self.k, self.v, self.k_scale, self.v_scale,
                 self.block_table, self.pos, self.live, self.n_chunks),
                (self.block_size, self.dtype_name, self.attn_impl,
                 self.clamp_writes))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children[:6], *aux, *children[6:])

    def _written(self, k, v, ks, vs) -> "PagedLayerCache":
        """This view over the pools a write returned."""
        return PagedLayerCache(k, v, ks, vs, self.block_table, self.pos,
                               self.block_size, self.dtype_name,
                               self.attn_impl, self.clamp_writes,
                               self.live, self.n_chunks)

    # -- properties -----------------------------------------------------
    @property
    def int8(self) -> bool:
        return self.k_scale is not None

    @property
    def key_len(self) -> int:
        """Static gathered key-axis length (max_blocks * block_size)."""
        return self.block_table.shape[1] * self.block_size

    @property
    def pools(self) -> Tuple:
        return (self.k, self.v, self.k_scale, self.v_scale)

    # -- traced ops -----------------------------------------------------
    @device_scope("kv_write")
    def _write(self, pool, scale, chunk):
        """Scatter ``chunk`` [B, S, H, D] at per-row positions
        ``pos..pos+S-1`` through the block table."""
        b, s = chunk.shape[:2]
        idx = self.pos[:, None] + jnp.arange(s)[None, :]        # [B, S]
        rows = jnp.arange(b)[:, None]
        if self.clamp_writes:
            # Out-of-window positions (speculative lookahead past a
            # sequence's last real write) land in the scratch block —
            # never in a real block another row (or this one) owns.
            mb = self.block_table.shape[1]
            blk = self.block_table[rows,
                                   jnp.minimum(idx // self.block_size,
                                               mb - 1)]
            blk = jnp.where(idx < mb * self.block_size, blk, 0)
        else:
            blk = self.block_table[rows, idx // self.block_size]  # [B, S]
        off = idx % self.block_size
        if scale is not None:
            q, sc = _quant_tokens(chunk)
            return (pool.at[blk, off].set(q.reshape(b, s, -1)),
                    scale.at[blk, off].set(sc))
        return pool.at[blk, off].set(
            chunk.reshape(b, s, -1).astype(pool.dtype)), None

    @device_scope("kv_gather")
    def _gather(self, pool, scale, heads: int):
        """[B, MB, BS, H*D] pool gather -> [B, L, H, D] keys/values."""
        b = self.block_table.shape[0]
        g = pool[self.block_table]                # [B, MB, BS, H*D]
        g = g.reshape(b, self.key_len, heads, -1)
        if scale is not None:
            # Per-(token, head) dequant — the inverse of _quant_tokens'
            # head_dim-block RTNE (comm/quantize.py round-trip semantics).
            sc = scale[self.block_table].reshape(b, self.key_len, heads)
            g = g.astype(jnp.float32) * sc[..., None]
        return g.astype(jnp.dtype(self.dtype_name))

    def update(self, k_new: jax.Array, v_new: jax.Array):
        """Write this step's ``[B, S, H, D]`` chunk, gather the full cache.

        Returns ``(new_cache, K [B, L, H, D], V, mask [B, 1, S, L])`` where
        the mask makes key ``j`` visible to query ``i`` iff
        ``j <= pos + i`` — the cached past plus this chunk's causal prefix
        (scratch and not-yet-written slots are always masked out).
        """
        b, s = k_new.shape[:2]
        k, ks = self._write(self.k, self.k_scale, k_new)
        v, vs = self._write(self.v, self.v_scale, v_new)
        new = self._written(k, v, ks, vs)
        heads = k_new.shape[2]
        kk = new._gather(k, ks, heads)
        vv = new._gather(v, vs, heads)
        qpos = self.pos[:, None] + jnp.arange(s)[None, :]        # [B, S]
        kpos = jnp.arange(self.key_len)
        mask = kpos[None, None, :] <= qpos[:, :, None]           # [B, S, L]
        return new, kk, vv, mask[:, None]                        # [B,1,S,L]

    def update_attend(self, q: jax.Array, k_new: jax.Array,
                      v_new: jax.Array,
                      softmax_scale: Optional[float] = None):
        """Fast-path form of :meth:`update`: write the chunk, then run
        the Pallas paged decode-attention kernel straight over the pools
        through the block table — the gathered ``[B, L, H, D]`` K/V copy
        (and, for int8 pools, its dequantized fp form) is never
        materialized. Returns ``(new_cache, o [B, S, H, D])``; visibility
        semantics are identical to the gather path (``kpos <= pos + i``,
        tier-1 parity-tested in tests/test_serving_fastpath.py)."""
        from deepspeed_tpu.ops.transformer.paged_attention import \
            paged_decode_attention

        k, ks = self._write(self.k, self.k_scale, k_new)
        v, vs = self._write(self.v, self.v_scale, v_new)
        new = self._written(k, v, ks, vs)
        o = paged_decode_attention(q, k, v, ks, vs, self.block_table,
                                   self.pos, block_size=self.block_size,
                                   softmax_scale=softmax_scale)
        return new, o.astype(q.dtype)

    def attend_live(self, q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                    softmax_scale: Optional[float] = None):
        """The default decode (one query a row): write this step's key
        and value as :meth:`update` does, then attend over ``self.live``,
        the flat list of the batch's live blocks, ``n_chunks`` chunks of
        it and no further. Returns ``(new_cache, o [B, 1, H, D])``.

        A chunk is G runs of R blocks, each run ONE row's
        (:func:`live_block_list`). Gather the chunk's blocks from the K
        and the V pool, score each run against ITS row's query, mask
        ``kpos <= pos[row]``, and take each run's partial softmax (max,
        sum, weighted values) by itself; then fold the runs, one after
        another, into their rows' running accumulators, the split-K
        combine of flash decoding. Visibility is exactly :meth:`update`'s;
        only the order of summation differs. That order is the row's own:
        a run's partial depends on nothing but the run, and a row's runs
        are folded in their order whichever chunk they fall in, so a row's
        output is the same bits in any slot and among any neighbours. Rows
        with no live block come out zero.

        Everything stays in the pool's stored form, heads folded into the
        lane axis: a head's 64 lanes are summed by a ``[H * D, H]``
        indicator matmul and its probabilities spread back over them by
        the transpose, so no ``[.., H, D]`` reshape of keys or values is
        made. int8 pools dequantize by their per-(token, head) scales,
        which factor out of both products."""
        k, ks = self._write(self.k, self.k_scale, k_new)
        v, vs = self._write(self.v, self.v_scale, v_new)
        new = self._written(k, v, ks, vs)
        b, _, h, d = q.shape
        g, r = self.live.shape[1], self.live.shape[2] - 2
        t = r * self.block_size                  # key positions of a run
        dt = jnp.dtype(self.dtype_name)
        scale = softmax_scale if softmax_scale is not None else d ** -0.5
        # float32 operands of a TPU matmul are rounded to bfloat16 unless
        # told otherwise; the one-hot and indicator factors are exact in
        # any precision, the other side must not be rounded.
        exact = functools.partial(jnp.einsum,
                                  precision=jax.lax.Precision.HIGHEST,
                                  preferred_element_type=jnp.float32)
        head_of_lane = (jnp.arange(h * d)[:, None] // d
                        == jnp.arange(h)[None, :])               # [HD, H]
        sum_lanes, spread_lanes = (head_of_lane.astype(jnp.float32),
                                   head_of_lane.astype(dt))
        qf = q.reshape(b, h * d)
        posf = self.pos.astype(jnp.float32)
        low = jnp.finfo(jnp.float32).min

        def lanes(x):                            # [B, H] -> [B, HD]
            return jnp.repeat(x, d, axis=1)

        def chunk(i, carry):
            runs = self.live[i]                                  # [G, R + 2]
            blocks, slots, start = runs[:, :r], runs[:, r], runs[:, r + 1]
            with device_scope("kv_gather"):
                kb, vb = (pool[blocks.reshape(-1)].reshape(g, t, h * d)
                          for pool in (k, v))                # [G, T, HD]
                if ks is not None:
                    ksb, vsb = (pool[blocks.reshape(-1)].reshape(g, t, h)
                                for pool in (ks, vs))        # [G, T, H]
            owner = slots[:, None] == jnp.arange(b)[None, :]     # [G, B]
            own = owner.astype(jnp.float32)
            qr = exact("gb,bk->gk", owner.astype(qf.dtype), qf)  # [G, HD]
            s = exact("gtk,kh->gth", kb.astype(jnp.float32) * qr[:, None],
                      sum_lanes) * scale                     # [G, T, H]
            if ks is not None:
                s = s * ksb
            seen = (start[:, None] + jnp.arange(t)[None, :]
                    <= exact("gb,b->g", own, posf)[:, None])[..., None]
            # a run's partial, by itself: a real run's first key is seen
            m_run = jnp.where(seen, s, low).max(axis=1)          # [G, H]
            p = jnp.where(seen, jnp.exp(s - m_run[:, None]), 0.0)
            l_run = p.sum(axis=1)                                # [G, H]
            pv = p * vsb if vs is not None else p
            spread = exact("gth,kh->gtk", pv.astype(dt), spread_lanes)
            acc_run = (spread * vb.astype(jnp.float32)).sum(axis=1)
            # fold the runs in their order, each into its own row
            m, l, acc = carry                    # [B, H], [B, H], [B, HD]
            for j in range(g):
                mine = owner[j][:, None]                         # [B, 1]
                m_new = jnp.maximum(m, m_run[j])
                keep, take = jnp.exp(m - m_new), jnp.exp(m_run[j] - m_new)
                l = jnp.where(mine, l * keep + take * l_run[j], l)
                acc = jnp.where(mine, acc * lanes(keep)
                                + lanes(take) * acc_run[j], acc)
                m = jnp.where(mine, m_new, m)
            return m, l, acc

        _, l, acc = jax.lax.fori_loop(
            0, self.n_chunks, chunk,
            (jnp.full((b, h), low, jnp.float32),
             jnp.zeros((b, h), jnp.float32),
             jnp.zeros((b, h * d), jnp.float32)))
        l = lanes(l)
        o = jnp.where(l > 0, acc / jnp.where(l > 0, l, 1.0), 0.0)
        return new, o.reshape(b, 1, h, d).astype(q.dtype)


@jax.tree_util.register_pytree_node_class
class ChunkedLayerCache:
    """One layer's view of the paged cache inside the **mixed** (chunked
    prefill) program: the batch axis is a flat ragged token batch
    ``[T]`` — decode tokens plus prefill chunks — where token ``t``
    belongs to batch slot ``slots[t]`` and sits at cache position
    ``pos[t]`` of its sequence. Pad tokens carry the spare all-scratch
    table row, so their writes land in block 0 and their (discarded)
    attention reads stay masked.

    Used by the GPT family's paged branch exactly like
    :class:`PagedLayerCache` with ``attn_impl == "kernel"`` — the model
    hands a ``[1, T, H, D]`` chunk to :meth:`update_attend` and gets the
    attended output back; visibility is per ragged segment
    (``kpos <= pos[t]`` over the token's own block-table row), which is
    exactly the bucketed path's causal semantics, so the two paths are
    token-identical (tier-1 parity-tested in
    tests/test_chunked_prefill.py).
    """

    attn_impl = "chunked"       # static: routes the model's paged branch

    def __init__(self, k: jax.Array, v: jax.Array,
                 k_scale: Optional[jax.Array], v_scale: Optional[jax.Array],
                 block_table: jax.Array, slots: jax.Array, pos: jax.Array,
                 block_size: int, dtype_name: str = "bfloat16"):
        self.k = k
        self.v = v
        self.k_scale = k_scale
        self.v_scale = v_scale
        self.block_table = block_table      # [B + 1, MB] int32 (row B: pads)
        self.slots = slots                  # [T] int32 — token's batch slot
        self.pos = pos                      # [T] int32 — token's position
        self.block_size = int(block_size)
        self.dtype_name = dtype_name

    # -- pytree ---------------------------------------------------------
    def tree_flatten(self):
        return ((self.k, self.v, self.k_scale, self.v_scale,
                 self.block_table, self.slots, self.pos),
                (self.block_size, self.dtype_name))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, block_size=aux[0], dtype_name=aux[1])

    @property
    def int8(self) -> bool:
        return self.k_scale is not None

    @property
    def pools(self) -> Tuple:
        return (self.k, self.v, self.k_scale, self.v_scale)

    # -- traced ops -----------------------------------------------------
    @device_scope("kv_write")
    def _write(self, pool, scale, chunk):
        """Scatter ``chunk`` [T, H, D] — one write per ragged token at
        its own ``(slot, pos)``. Pad tokens all collide on the scratch
        block; real tokens never do (positions within a sequence are
        distinct and tables are disjoint)."""
        blk = self.block_table[self.slots, self.pos // self.block_size]
        off = self.pos % self.block_size                         # [T]
        t = chunk.shape[0]
        if scale is not None:
            q, sc = _quant_tokens(chunk)
            return (pool.at[blk, off].set(q.reshape(t, -1)),
                    scale.at[blk, off].set(sc))
        return pool.at[blk, off].set(
            chunk.reshape(t, -1).astype(pool.dtype)), None

    def update_attend(self, q: jax.Array, k_new: jax.Array,
                      v_new: jax.Array,
                      softmax_scale: Optional[float] = None):
        """Write the ragged batch's K/V, then run the chunked-prefill
        kernel straight over the pools through per-token block tables.
        ``q``/``k_new``/``v_new``: [1, T, H, D] (the model's flat batch
        rides as one row). Returns ``(new_cache, o [1, T, H, D])``."""
        from deepspeed_tpu.ops.transformer.chunked_prefill import \
            chunked_prefill_attention

        k, ks = self._write(self.k, self.k_scale, k_new[0])
        v, vs = self._write(self.v, self.v_scale, v_new[0])
        new = ChunkedLayerCache(k, v, ks, vs, self.block_table, self.slots,
                                self.pos, self.block_size, self.dtype_name)
        table = self.block_table[self.slots]                     # [T, MB]
        o = chunked_prefill_attention(q[0], k, v, ks, vs, table, self.pos,
                                      block_size=self.block_size,
                                      softmax_scale=softmax_scale)
        return new, o[None].astype(q.dtype)


@device_scope("pack")
def pack_prefill(pools: Tuple, blocks: jax.Array,
                 k_stack: jax.Array, v_stack: jax.Array,
                 slot: Optional[jax.Array] = None,
                 states: Tuple = (), *,
                 kinds: Optional[Tuple[str, ...]] = None) -> Tuple:
    """Scatter a prefilled contiguous cache into pool blocks (jit this).

    ``pools``: one entry a layer (:func:`init_serving_state`);
    ``blocks``: [nb] int32 pool blocks assigned to the sequence;
    ``k_stack``/``v_stack``: [kv layers, T, H, D] from the prefill forward,
    with ``T == nb * block_size`` (bucketed — trailing positions beyond
    the true prompt length carry garbage that stays masked by ``pos``).
    ``states``: for each recurrent layer in order, the arrays the prefill
    left (``[1, *shape]`` each): they become row ``slot`` of that layer's
    state, whole. ``kinds`` (static): each layer's ``LayerCacheSpec.kind``;
    absent, every layer is a ``kv`` layer.
    """
    nb = blocks.shape[0]
    out = []
    states = iter(states)
    i = -1                              # index among the kv layers
    for pool, kind in zip(pools, kinds or ("kv",) * len(pools)):
        if kind == "none":
            out.append(None)
            continue
        if kind == "recurrent":
            out.append(tuple(
                mine.at[slot].set(new[0].astype(mine.dtype))
                for mine, new in zip(pool, next(states))))
            continue
        i += 1
        k, v, ks, vs = pool
        rows = (nb, k.shape[1], -1)     # whole blocks, [H, D] folded
        if ks is not None:
            kq, ksc = _quant_tokens(k_stack[i])
            vq, vsc = _quant_tokens(v_stack[i])
            out.append((k.at[blocks].set(kq.reshape(rows)),
                        v.at[blocks].set(vq.reshape(rows)),
                        ks.at[blocks].set(ksc.reshape(rows)),
                        vs.at[blocks].set(vsc.reshape(rows))))
        else:
            out.append((
                k.at[blocks].set(k_stack[i].reshape(rows).astype(k.dtype)),
                v.at[blocks].set(v_stack[i].reshape(rows).astype(v.dtype)),
                None, None))
    return tuple(out)
