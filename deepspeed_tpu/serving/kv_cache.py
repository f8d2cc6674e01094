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

Host-side block accounting (:class:`BlockPool`) is plain python — a free
list is microseconds per step and never touches the device.
"""

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.comm.quantize import quantize_blockwise
from deepspeed_tpu.telemetry.tracer import device_scope


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


def init_paged_pools(cfg, num_blocks: int, block_size: int,
                     int8: bool = False, dtype=None) -> Tuple:
    """Per-layer ``(k, v, k_scale, v_scale)`` pool arrays (scales are None
    in the fp path). Zero-initialised: scratch/unwritten slots dequantize
    to exact zeros, so masked attention terms stay exactly ``0 * 0``.

    K/V pools are ``[num_blocks, block_size, heads * head_dim]`` (the
    module docstring says why). The int8 SCALE pools stay
    ``[num_blocks, block_size, heads]``: at 1/32 of the pool's bytes
    their own layout round trip is small, and the two obvious folds
    (``[N, BS * H]`` written through a reshape, or by an element scatter)
    each still compile to pool-sized copies or reshapes."""
    dtype = dtype if dtype is not None else cfg.dtype
    shape = (num_blocks, block_size, cfg.num_heads * cfg.head_dim)
    sshape = (num_blocks, block_size, cfg.num_heads)
    layers = []
    for _ in range(cfg.num_layers):
        if int8:
            layers.append((jnp.zeros(shape, jnp.int8),
                           jnp.zeros(shape, jnp.int8),
                           jnp.ones(sshape, jnp.float32),
                           jnp.ones(sshape, jnp.float32)))
        else:
            layers.append((jnp.zeros(shape, dtype),
                           jnp.zeros(shape, dtype), None, None))
    return tuple(layers)


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
                 attn_impl: str = "gather", clamp_writes: bool = False):
        self.k = k
        self.v = v
        self.k_scale = k_scale
        self.v_scale = v_scale
        self.block_table = block_table      # [B, MB] int32
        self.pos = pos                      # [B] int32 — next write index
        self.block_size = int(block_size)
        self.dtype_name = dtype_name
        # Static (aux) knobs of the serving fast path (docs/SERVING.md):
        # ``attn_impl`` — "gather" (the materializing fallback, and the
        # bit-identical-to-PR-8 default) or "kernel" (the Pallas paged
        # decode-attention kernel; the model's paged branch reads it).
        # ``clamp_writes`` — route out-of-window writes to the scratch
        # block instead of relying on in-bounds positions; the
        # speculative-decode verify chunk can legally overshoot a
        # sequence's allocated blocks (rejected-token lookahead) and its
        # garbage must land somewhere harmless. Off by default: the plain
        # decode path never overshoots and must not pay the extra ops.
        self.attn_impl = str(attn_impl)
        self.clamp_writes = bool(clamp_writes)

    # -- pytree ---------------------------------------------------------
    def tree_flatten(self):
        return ((self.k, self.v, self.k_scale, self.v_scale,
                 self.block_table, self.pos),
                (self.block_size, self.dtype_name, self.attn_impl,
                 self.clamp_writes))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, block_size=aux[0], dtype_name=aux[1],
                   attn_impl=aux[2], clamp_writes=aux[3])

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
        new = PagedLayerCache(k, v, ks, vs, self.block_table, self.pos,
                              self.block_size, self.dtype_name,
                              self.attn_impl, self.clamp_writes)
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
        new = PagedLayerCache(k, v, ks, vs, self.block_table, self.pos,
                              self.block_size, self.dtype_name,
                              self.attn_impl, self.clamp_writes)
        o = paged_decode_attention(q, k, v, ks, vs, self.block_table,
                                   self.pos, block_size=self.block_size,
                                   softmax_scale=softmax_scale)
        return new, o.astype(q.dtype)


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
                 k_stack: jax.Array, v_stack: jax.Array) -> Tuple:
    """Scatter a prefilled contiguous cache into pool blocks (jit this).

    ``pools``: the per-layer ``(k, v, k_scale, v_scale)`` tuple;
    ``blocks``: [nb] int32 pool blocks assigned to the sequence;
    ``k_stack``/``v_stack``: [layers, T, H, D] from the prefill forward,
    with ``T == nb * block_size`` (bucketed — trailing positions beyond
    the true prompt length carry garbage that stays masked by ``pos``).
    """
    nb = blocks.shape[0]
    out = []
    for i, (k, v, ks, vs) in enumerate(pools):
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
