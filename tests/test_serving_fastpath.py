"""Decode fast path tests — paged decode-attention kernel, prefix-cache
reuse, speculative decoding (docs/SERVING.md "Decode fast path").

The acceptance gates:

- the Pallas kernel (interpret path) is **parity-exact within fp32
  rounding** against the gather+masked-attention reference — including
  partial last blocks, scrambled block tables and all-scratch (block 0)
  inactive rows — and within RTNE tolerance for int8 pools (dequantized
  in-kernel);
- every fast-path configuration (kernel, prefix cache, speculative, all
  together) produces outputs **token-identical** to the fully-off engine
  on a mixed continuous-batching trace, and ``auto`` where the kernel
  does not tile IS the fully-off decode;
- prefix COW survives youngest-first preemption (the evicted request
  re-admits warm and still finishes with correct tokens), and refcounts
  leak nothing: after ``run_until_complete`` the pool holds exactly the
  cache's blocks, and zero after a cache clear (or immediately, with the
  cache off);
- speculative decode is token-identical to greedy by construction and
  emits its accept-rate evidence;
- fast path fully off ⇒ the decode program attends over the flat list
  of the batch's live blocks (``PagedLayerCache.attend_live``): equal to
  ``update()`` + dense masked attention on raw pools, token-identical to
  ``generate()`` through the engine, ONE compiled program whose lowering
  holds no tensor over the reserved window, and no fast-path tags.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.config.config import ConfigError, ServingConfig
from deepspeed_tpu.models import make_gpt
from deepspeed_tpu.ops.transformer.attention import xla_attention
from deepspeed_tpu.ops.transformer.paged_attention import (
    paged_decode_attention, paged_decode_ok)
from deepspeed_tpu.serving import PagedLayerCache, ServeEngine
from deepspeed_tpu.serving.engine import resolve_decode_attention
from deepspeed_tpu.serving.kv_cache import (_quant_tokens, init_paged_pools,
                                            live_block_list)
from deepspeed_tpu.telemetry import (InMemorySink, MetricsRegistry,
                                     RecompileDetector, StepTracer,
                                     Telemetry)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def gpt_setup():
    # fp32 like tests/test_serving.py: the parity oracles compare
    # numerically-different-but-equivalent paths whose bf16 argmax
    # tie-flips are noise, not bugs.
    model, cfg = make_gpt("tiny", dropout_rate=0.0, max_seq_len=64,
                          dtype=jnp.float32)
    params = model.init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(1)},
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    return model, cfg, params


def _serve(model, params, telemetry=None, **overrides):
    scfg = ServingConfig(**{
        "max_batch_size": 2, "kv_block_size": 4, "kv_num_blocks": 64,
        "max_model_len": 48, **overrides})
    eng = deepspeed_tpu.init_inference(model, params=params,
                                       dtype=jnp.float32)
    return ServeEngine(eng, config=scfg, telemetry=telemetry)


def _mem_telemetry():
    reg = MetricsRegistry()
    sink = reg.add_sink(InMemorySink())
    tracer = StepTracer(path=None, enabled=False)
    return Telemetry(reg, tracer, RecompileDetector(enabled=False)), sink


TRACE = [(5, 12), (9, 3), (3, 10), (12, 4), (7, 8)]


def _run_trace(srv, cfg, seed=7):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, (t,)).tolist()
               for t, _ in TRACE]
    rids = [srv.submit(p, n) for p, (_, n) in zip(prompts, TRACE)]
    res = srv.run_until_complete()
    return prompts, [res[r]["tokens"] for r in rids]


@pytest.fixture(scope="module")
def off_run(gpt_setup):
    """The mixed trace with every fast-path piece off: ``(tokens, stats)``."""
    model, cfg, params = gpt_setup
    srv = _serve(model, params)
    return _run_trace(srv, cfg)[1], srv.stats


# ---------------------------------------------------------------------------
# Kernel parity vs the gather path
# ---------------------------------------------------------------------------

class TestPagedKernelParity:
    """The kernel-vs-gather numerics rungs, on raw pools (no model):
    scrambled non-contiguous tables, partial last blocks (pos mid-block),
    and an all-scratch inactive row — the exact decode-batch shapes."""

    B, H, D, BS, N, MB = 3, 4, 16, 4, 12, 5

    def _fixture(self, int8, seed=0):
        rng = np.random.default_rng(seed)
        shape = (self.N, self.BS, self.H, self.D)
        k = jnp.asarray(rng.normal(size=shape), jnp.float32)
        v = jnp.asarray(rng.normal(size=shape), jnp.float32)
        bt = np.zeros((self.B, self.MB), np.int32)
        bt[0, :3] = [3, 7, 2]            # scrambled, non-contiguous
        bt[1, :2] = [5, 1]
        # row 2 stays all-zeros: an inactive slot pointing at scratch
        pos = jnp.asarray([9, 5, 0], jnp.int32)   # 9, 5: partial blocks
        ks = vs = None
        if int8:
            k, ks = _quant_tokens(k)
            v, vs = _quant_tokens(v)
        # the stored form: heads folded into the lane axis
        k, v = (p.reshape(self.N, self.BS, self.H * self.D) for p in (k, v))
        return k, v, ks, vs, jnp.asarray(bt), pos

    def _reference(self, q, k, v, ks, vs, bt, pos):
        lc = PagedLayerCache(k, v, ks, vs, bt, pos, self.BS, "float32")
        kk, vv = lc._gather(k, ks, self.H), lc._gather(v, vs, self.H)
        s = q.shape[1]
        qpos = pos[:, None] + jnp.arange(s)[None, :]
        kpos = jnp.arange(lc.key_len)
        mask = (kpos[None, None, :] <= qpos[:, :, None])[:, None]
        return xla_attention(q, kk, vv, causal=False, mask=mask)

    @pytest.mark.parametrize("s", [1, 4])
    def test_fp32_parity(self, s):
        k, v, ks, vs, bt, pos = self._fixture(int8=False)
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(self.B, s, self.H, self.D)),
                        jnp.float32)
        want = self._reference(q, k, v, ks, vs, bt, pos)
        got = paged_decode_attention(q, k, v, ks, vs, bt, pos,
                                     block_size=self.BS)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=2e-6)

    def test_int8_in_kernel_dequant_parity(self):
        """int8 pools: the in-kernel dequant must agree with the gather
        path's dequantized copy within fp32 rounding (the dequantized
        values are identical by construction — only summation order
        differs)."""
        k, v, ks, vs, bt, pos = self._fixture(int8=True)
        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.normal(size=(self.B, 1, self.H, self.D)),
                        jnp.float32)
        want = self._reference(q, k, v, ks, vs, bt, pos)
        got = paged_decode_attention(q, k, v, ks, vs, bt, pos,
                                     block_size=self.BS)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=2e-6)

    def test_update_attend_matches_update_plus_attention(self):
        """The cache-level fast path (write + kernel) against the
        cache-level slow path (write + gather + masked attention)."""
        k, v, ks, vs, bt, pos = self._fixture(int8=False)
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.normal(size=(self.B, 1, self.H, self.D)),
                        jnp.float32)
        knew = jnp.asarray(rng.normal(size=(self.B, 1, self.H, self.D)),
                           jnp.float32)
        vnew = jnp.asarray(rng.normal(size=(self.B, 1, self.H, self.D)),
                           jnp.float32)
        slow = PagedLayerCache(k, v, ks, vs, bt, pos, self.BS, "float32")
        new_s, kk, vv, mask = slow.update(knew, vnew)
        want = xla_attention(q, kk, vv, causal=False, mask=mask)
        fast = PagedLayerCache(k, v, ks, vs, bt, pos, self.BS, "float32",
                               attn_impl="kernel")
        new_f, got = fast.update_attend(q, knew, vnew)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=2e-6)
        np.testing.assert_array_equal(np.asarray(new_f.k),
                                      np.asarray(new_s.k))

    def test_dispatch_gate(self):
        assert paged_decode_ok(128, 16)
        assert paged_decode_ok(256, 8)
        assert not paged_decode_ok(64, 16)      # head_dim not 128-aligned
        assert not paged_decode_ok(128, 5)      # block not 8-aligned


# ---------------------------------------------------------------------------
# The default decode: attention over the flat list of live blocks
# ---------------------------------------------------------------------------

class TestLiveBlockDecode:
    """``attend_live`` on raw pools against ``update()`` + dense masked
    attention, and through the engine against ``generate()``."""

    B, H, D, BS, N, MB = 6, 4, 16, 4, 40, 8
    # (slot, blocks, pos): one block only; ending exactly on a block
    # boundary (pos 11 is the last position of its third block); at the
    # table's full width (the last position of ``max_model_len``); two
    # rows reading the same two blocks (a shared prompt head) and then
    # their own. Slots 1 and 4 are dead: scratch table rows, no entry.
    ROWS = [(0, [9], 2),
            (2, [3, 7, 2], 11),
            (3, [11, 12, 13, 14, 15, 16, 17, 18], 31),
            (5, [20, 21, 30], 8)]
    SHARED = (2, [20, 21, 31, 32], 13)

    def _pools(self, kind, rng):
        shape = (self.N, self.BS, self.H, self.D)
        dtype = jnp.bfloat16 if kind == "bfloat16" else jnp.float32
        k = jnp.asarray(rng.normal(size=shape), dtype)
        v = jnp.asarray(rng.normal(size=shape), dtype)
        ks = vs = None
        if kind == "int8":
            k, ks = _quant_tokens(k)
            v, vs = _quant_tokens(v)
        k, v = (p.reshape(self.N, self.BS, self.H * self.D) for p in (k, v))
        return k, v, ks, vs, dtype

    def _inputs(self, rows, rng, dtype):
        bt = np.zeros((self.B, self.MB), np.int32)
        pos = np.zeros((self.B,), np.int32)
        for slot, blocks, p in rows:
            bt[slot, :len(blocks)] = blocks
            pos[slot] = p
        q, knew, vnew = (jnp.asarray(
            rng.normal(size=(self.B, 1, self.H, self.D)), dtype)
            for _ in range(3))
        return jnp.asarray(bt), jnp.asarray(pos), q, knew, vnew

    def _attend_live(self, pools, name, rows, chunk, bt, pos, q, knew, vnew):
        run, group = chunk
        live, _, n_chunks = live_block_list(
            rows, self.BS, run, group,
            -(-self.B * -(-self.MB // run) // group))
        flat = PagedLayerCache(*pools, bt, pos, self.BS, name,
                               live=jnp.asarray(live),
                               n_chunks=jnp.int32(n_chunks))
        new, got = jax.jit(PagedLayerCache.attend_live)(flat, q, knew, vnew)
        return new, np.asarray(got, np.float32), n_chunks

    # (blocks a run, runs a chunk). Runs of 8: every row in one, all in
    # one chunk; of 3: 1 + 1 + 3 + 1 runs in 3 chunks of two; of 2: 1 + 4 +
    # 2 + 2 (the shared rows among them) in 3 chunks of four
    @pytest.mark.parametrize("chunk,chunks,shared", [
        ((8, 4), 1, False), ((3, 2), 3, False), ((2, 4), 3, True)],
        ids=["1-chunk", "3-chunks", "3-chunks-shared-blocks"])
    @pytest.mark.parametrize("kind", ["float32", "bfloat16", "int8"])
    def test_matches_update_plus_dense_attention(self, kind, chunk, chunks,
                                                 shared):
        rng = np.random.default_rng(5)
        *pools, dtype = self._pools(kind, rng)
        rows = self.ROWS
        if shared:
            rows = [r for r in rows if r[0] != 2] + [self.SHARED]
        bt, pos, q, knew, vnew = self._inputs(rows, rng, dtype)
        name = jnp.dtype(dtype).name
        slow = PagedLayerCache(*pools, bt, pos, self.BS, name)
        new_s, kk, vv, mask = slow.update(knew, vnew)
        want = np.asarray(xla_attention(q, kk, vv, causal=False, mask=mask),
                          np.float32)
        new_f, got, n_chunks = self._attend_live(pools, name, rows, chunk,
                                                 bt, pos, q, knew, vnew)
        assert n_chunks == chunks
        alive = [r[0] for r in rows]
        dead = [b for b in range(self.B) if b not in alive]
        # float32: only the order of summation differs. bfloat16 rounds
        # the probabilities before they meet the values on both sides,
        # at different scalings (2**-9 of outputs of order one); int8
        # pools are dequantized exactly here and through a bfloat16-free
        # float32 copy there.
        tol = 2e-2 if kind == "bfloat16" else 5e-6
        np.testing.assert_allclose(got[alive], want[alive], atol=tol,
                                   rtol=tol)
        assert not got[dead].any()            # no live block: zeros
        # the write is update()'s (a jitted scale may differ in an ulp)
        for a, b in zip(new_f.pools, new_s.pools):
            if a is not None:
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    rtol=1e-6, atol=0)

    @pytest.mark.parametrize("kind", ["bfloat16", "int8"])
    def test_a_rows_output_is_the_same_bits_whoever_its_neighbours_are(
            self, kind):
        """A run holds one row's blocks, its partial depends on the run
        alone, and a row's runs are folded in their order: the same
        sequence alone in the batch, in another slot (its runs then start
        a chunk instead of ending one), or behind rows of other lengths
        gives the same bits. (Blocks packed with no regard to rows, one
        running maximum a row and chunk, did not: on the chip a second pass
        of one trace, its requests in other slots, gave other greedy tokens
        in bfloat16.)"""
        rng = np.random.default_rng(6)
        *pools, dtype = self._pools(kind, rng)
        name = jnp.dtype(dtype).name
        slot, blocks, p = self.ROWS[2]                  # the longest row
        bt, pos, q, knew, vnew = self._inputs(self.ROWS, rng, dtype)
        _, among, _ = self._attend_live(pools, name, self.ROWS, (3, 2), bt,
                                        pos, q, knew, vnew)
        # alone, in another slot, with the same query, key and value
        other = 1
        move = lambda a: jnp.zeros_like(a).at[other].set(a[slot])
        bt1 = jnp.zeros_like(bt).at[other].set(bt[slot])
        _, alone, _ = self._attend_live(
            pools, name, [(other, blocks, p)], (3, 2), bt1, move(pos),
            move(q), move(knew), move(vnew))
        np.testing.assert_array_equal(alone[other], among[slot])
        assert among[slot].any()

    def test_the_list_holds_each_rows_blocks_up_to_its_write(self):
        live, n_blocks, n_chunks = live_block_list(self.ROWS, self.BS, 3, 4,
                                                   5)
        assert live.shape == (5, 4, 5) and live.dtype == np.int32
        assert n_blocks == 1 + 3 + 8 + 3 == sum(p // self.BS + 1
                                                for _, _, p in self.ROWS)
        assert n_chunks == 2                    # 1 + 1 + 3 + 1 runs, by four
        # a run a line: three blocks, the owning slot, the first position
        assert live.reshape(-1, 5)[:8].tolist() == [
            [9, 0, 0, 0, 0],
            [3, 7, 2, 2, 0],
            [11, 12, 13, 3, 0], [14, 15, 16, 3, 12], [17, 18, 0, 3, 24],
            [20, 21, 30, 5, 0],
            [0, 0, 0, -1, 0], [0, 0, 0, -1, 0]]    # nobody's: padding
        assert (live.reshape(-1, 5)[8:] == [0, 0, 0, -1, 0]).all()
        # a table shorter than the write position is a fault, not a pad
        with pytest.raises(ValueError):
            live_block_list([(0, [9], 4)], self.BS, 3, 4, 5)
        with pytest.raises(ValueError):                  # nor a list too short
            live_block_list(self.ROWS, self.BS, 3, 4, 1)

    @pytest.mark.parametrize("over", [
        {}, {"int8_kv_cache": True}, {"prefix_cache": True}],
        ids=["plain", "int8-pool", "prefix-cache"])
    @pytest.mark.parametrize("run,group", [(2, 2), (16, 2)],
                             ids=["many-chunks", "one-chunk"])
    def test_engine_matches_generate_and_reads_what_is_live(
            self, gpt_setup, monkeypatch, run, group, over):
        """Greedy outputs of a mixed trace, token for token those of
        ``generate()``, with the list walked in several chunks (two runs
        of two blocks a chunk) and in one (the engine's own sizes); and on
        every dispatch what is read covers what is live."""
        model, cfg, params = gpt_setup
        monkeypatch.setattr(ServeEngine, "LIVE_RUN_BLOCKS", run)
        monkeypatch.setattr(ServeEngine, "LIVE_CHUNK_RUNS", group)
        srv = _serve(model, params, **over)
        rng = np.random.default_rng(7)
        head = rng.integers(0, cfg.vocab_size, (8,)).tolist()
        prompts = [head + rng.integers(0, cfg.vocab_size, (t,)).tolist()
                   for t, _ in TRACE]
        rids = [srv.submit(p, n) for p, (_, n) in zip(prompts[:3], TRACE)]
        dispatches = 0
        while not srv.idle():
            if srv._step_count == 2:     # two join a running batch
                rids += [srv.submit(p, n) for p, (_, n)
                         in zip(prompts[3:], TRACE[3:])]
            before = dict(srv.stats)
            srv.step()
            read, live, blocks, chunks = (
                srv.stats[key] - before[key] for key in (
                    "read_positions", "live_positions", "live_blocks",
                    "chunks"))
            if read:
                dispatches += 1
                assert read >= live > 0
                assert read == chunks * run * group * srv.block_size
                assert chunks >= -(-blocks // (run * group))
        assert dispatches == srv.stats["decode_steps"] > 5
        assert run == 16 or srv.stats["chunks"] > 2 * dispatches
        if "int8_kv_cache" in over:
            # a quantized cache may flip a near-tie against generate();
            # the oracle is the same pool through the paged kernel
            ref = _serve(model, params, decode_attention="kernel", **over)
            want = [ref.submit(p, n) for p, (_, n) in zip(prompts, TRACE)]
            ref.run_until_complete()
            want = [ref.results[r]["tokens"] for r in want]
        else:
            eng = deepspeed_tpu.init_inference(model, params=params,
                                               dtype=jnp.float32)
            want = [np.asarray(eng.generate(
                np.asarray([p], np.int32), max_new_tokens=n))[0].tolist()
                for p, (_, n) in zip(prompts, TRACE)]
        assert [srv.results[r]["tokens"] for r in rids] == want
        det = srv.engine.recompile_detector
        assert det.compiles("serving.decode_step") == 1
        assert det.retraces("serving.decode_step") == 0
        assert srv._decode_programs() == 1


# ---------------------------------------------------------------------------
# Engine-level token identity
# ---------------------------------------------------------------------------

# (on a TPU?, does the kernel's gate admit the geometry?) -> what decodes
RESOLVES = {
    "gather": {(False, False): "gather", (True, True): "gather",
               (True, False): "gather"},
    "kernel": {(False, False): "kernel", (True, True): "kernel",
               (True, False): ConfigError},
    "auto": {(False, False): "gather", (True, True): "kernel",
             (True, False): "gather"}}


@pytest.mark.parametrize("tpu,tiles", list(RESOLVES["auto"]),
                         ids=["off-tpu", "tpu-tiles", "tpu-no-tile"])
@pytest.mark.parametrize("mode", list(RESOLVES))
def test_decode_attention_resolves_by_its_table(mode, tpu, tiles):
    want = RESOLVES[mode][tpu, tiles]
    if want is ConfigError:
        with pytest.raises(ConfigError, match="decode_attention='kernel'"):
            resolve_decode_attention(mode, tpu, tiles, "head_dim=64")
    else:
        assert resolve_decode_attention(mode, tpu, tiles) == want
        # off the TPU the interpreter takes any geometry: the gate is moot
        assert tpu or resolve_decode_attention(mode, tpu, True) == want


class TestFastPathTokenIdentity:
    @pytest.mark.parametrize("over", [
        {"decode_attention": "kernel"},
        {"prefix_cache": True},
        {"spec_decode": True, "spec_k": 3},
        {"decode_attention": "kernel", "prefix_cache": True,
         "spec_decode": True, "spec_k": 3}],
        ids=["kernel", "prefix_cache", "spec_decode", "all-three"])
    def test_every_configuration_matches_off(self, gpt_setup, off_run,
                                             over):
        model, cfg, params = gpt_setup
        srv = _serve(model, params, **over)
        _, got = _run_trace(srv, cfg)
        assert got == off_run[0]

    def test_auto_where_the_kernel_does_not_tile_is_the_default_decode(
            self, gpt_setup, off_run):
        """``auto`` off the TPU (and on one whose geometry does not tile)
        is the default decode itself: ONE program, compiled once under the
        only decode scope the detector holds, reading what a ``gather``
        engine reads, and the same tokens."""
        model, cfg, params = gpt_setup
        off_tokens, off_stats = off_run
        srv = _serve(model, params, decode_attention="auto")
        _, got = _run_trace(srv, cfg)
        assert got == off_tokens
        det = srv.engine.recompile_detector
        assert [f for f in det.stats if "decode_step" in f] == [
            "serving.decode_step"]
        assert det.compiles("serving.decode_step") == 1
        assert det.retraces("serving.decode_step") == 0
        assert srv._decode_programs() == 1
        assert srv.stats["read_positions"] == off_stats["read_positions"] > 0
        assert srv.stats["chunks"] == off_stats["chunks"] > 0

    def test_kernel_gauge_emitted(self, gpt_setup):
        model, cfg, params = gpt_setup
        tel, sink = _mem_telemetry()
        srv = _serve(model, params, telemetry=tel,
                     decode_attention="kernel")
        _run_trace(srv, cfg)
        vals = sink.values("serving/decode_attn_kernel")
        assert vals and all(v == 1.0 for v in vals)
        assert srv.stats["kernel_steps"] == srv.stats["decode_steps"]


# ---------------------------------------------------------------------------
# Prefix-cache reuse
# ---------------------------------------------------------------------------

class TestPrefixCache:
    def test_shared_head_hits_and_identity(self, gpt_setup):
        """A shared-head workload: later requests adopt the head blocks
        (hit counters move), prefill only their tail, and outputs stay
        token-identical to one-shot generate()."""
        model, cfg, params = gpt_setup
        rng = np.random.default_rng(11)
        head = rng.integers(0, cfg.vocab_size, (16,)).tolist()
        prompts = [head + rng.integers(0, cfg.vocab_size, (3,)).tolist()
                   for _ in range(4)]
        tel, sink = _mem_telemetry()
        srv = _serve(model, params, telemetry=tel, prefix_cache=True)
        rids = [srv.submit(p, 6) for p in prompts]
        res = srv.run_until_complete()
        assert srv.prefix_cache.hits >= 3
        assert srv.prefix_cache.blocks_reused >= 9     # 4-block head x 3
        assert sink.values("serving/prefix_hits")
        for rid, p in zip(rids, prompts):
            want = np.asarray(srv.engine.generate(
                np.asarray([p], np.int32), max_new_tokens=6))[0]
            assert res[rid]["tokens"] == want.tolist()

    def test_cow_survives_preemption_and_restart_identity(self, gpt_setup):
        """Youngest-first preemption releases the victim's references but
        the cache keeps the prompt-head blocks alive: the evicted request
        re-admits WARM (hits grow) and still finishes token-identical."""
        model, cfg, params = gpt_setup
        rng = np.random.default_rng(5)
        head = rng.integers(0, cfg.vocab_size, (8,)).tolist()
        p0 = head + rng.integers(0, cfg.vocab_size, (3,)).tolist()
        p1 = head + rng.integers(0, cfg.vocab_size, (2,)).tolist()
        # capacity 11: the two runs need 8 + 7 - 2 shared = 13 blocks at
        # their peaks, so the younger must be evicted mid-flight (sharing
        # alone cannot absorb the pressure)
        srv = _serve(model, params, prefix_cache=True, kv_num_blocks=12,
                     max_model_len=32)
        r0 = srv.submit(p0, 20)
        r1 = srv.submit(p1, 18)
        res = srv.run_until_complete()
        assert srv.sched.preempted_total >= 1
        hits_after = srv.prefix_cache.hits
        assert hits_after >= 2     # p1's admission + its warm re-admission
        for rid, p, n in ((r0, p0, 20), (r1, p1, 18)):
            want = np.asarray(srv.engine.generate(
                np.asarray([p], np.int32), max_new_tokens=n))[0]
            assert res[rid]["tokens"] == want.tolist()

    def test_refcount_leak_check(self, gpt_setup):
        """After run_until_complete: with the cache off the pool is
        empty; with it on, exactly the cache's nodes hold blocks and a
        clear() drains the pool to zero (no leaked references)."""
        model, cfg, params = gpt_setup
        srv = _serve(model, params)
        _run_trace(srv, cfg)
        assert srv.pool.used_blocks == 0
        srv = _serve(model, params, prefix_cache=True)
        _run_trace(srv, cfg)
        assert srv.pool.used_blocks == srv.prefix_cache.nodes
        srv.prefix_cache.clear()
        assert srv.pool.used_blocks == 0
        assert srv.pool.free_blocks == srv.pool.capacity

    def test_pool_pressure_evicts_cache_before_sequences(self, gpt_setup):
        """Cold cache entries yield: a full-pool admission evicts LRU
        leaves instead of failing (or preempting a running row)."""
        model, cfg, params = gpt_setup
        rng = np.random.default_rng(13)
        srv = _serve(model, params, prefix_cache=True, kv_num_blocks=14,
                     max_model_len=32)
        a = srv.submit(rng.integers(0, cfg.vocab_size, (10,)).tolist(), 4)
        srv.run_until_complete()
        nodes_before = srv.prefix_cache.nodes
        assert nodes_before > 0
        b = srv.submit(rng.integers(0, cfg.vocab_size, (12,)).tolist(), 16)
        res = srv.run_until_complete()
        assert b in res and a in res
        assert srv.sched.preempted_total == 0


# ---------------------------------------------------------------------------
# Speculative decoding
# ---------------------------------------------------------------------------

class TestSpeculative:
    def test_greedy_identity_and_gauges(self, gpt_setup):
        model, cfg, params = gpt_setup
        tel, sink = _mem_telemetry()
        srv = _serve(model, params, telemetry=tel, spec_decode=True,
                     spec_k=3)
        prompts, got = _run_trace(srv, cfg)
        for p, (_, n), toks in zip(prompts, TRACE, got):
            want = np.asarray(srv.engine.generate(
                np.asarray([p], np.int32), max_new_tokens=n))[0]
            assert toks == want.tolist()
        assert srv.stats["spec_rounds"] > 0
        # k proposals per active row per round: at least one row active
        assert srv.stats["spec_proposed"] >= 3 * srv.stats["spec_rounds"]
        assert srv.stats["spec_accepted"] <= srv.stats["spec_proposed"]
        rates = sink.values("serving/spec_accept_rate")
        tpv = sink.values("serving/spec_tokens_per_verify")
        assert rates and 0.0 <= rates[-1] <= 1.0
        # every round appends at least one token per active row
        assert tpv and tpv[-1] >= 1.0

    def test_spec_respects_eos_and_max_tokens(self, gpt_setup):
        """Tokens accepted past EOS/max_new must be truncated exactly
        like greedy decode (finish checks run per appended token)."""
        model, cfg, params = gpt_setup
        rng = np.random.default_rng(9)
        prompt = rng.integers(0, cfg.vocab_size, (5,)).tolist()
        srv0 = _serve(model, params)
        rid0 = srv0.submit(prompt, 10)
        full = srv0.run_until_complete()[rid0]["tokens"]
        eos = full[len(prompt) + 4]
        srv = _serve(model, params, spec_decode=True, spec_k=4)
        rid = srv.submit(prompt, 10, eos_token_id=eos)
        got = srv.run_until_complete()[rid]["tokens"]
        srv0b = _serve(model, params)
        rid0b = srv0b.submit(prompt, 10, eos_token_id=eos)
        want = srv0b.run_until_complete()[rid0b]["tokens"]
        assert got == want

    def test_config_walls(self, gpt_setup):
        model, cfg, params = gpt_setup
        with pytest.raises(ConfigError, match="temperature"):
            ServingConfig.from_dict({"speculative": {"enabled": True},
                                     "temperature": 0.7})
        with pytest.raises(ConfigError, match="k must be"):
            ServingConfig.from_dict({"speculative": {"k": 0}})
        with pytest.raises(ConfigError, match="decode_attention"):
            ServingConfig.from_dict({"decode_attention": "warp"})
        with pytest.raises(ValueError, match="draft_layers"):
            _serve(model, params, spec_decode=True,
                   spec_draft_layers=cfg.num_layers)
        # capture_logits has no per-step row under spec — loud, not
        # silently empty
        srv = _serve(model, params, spec_decode=True, spec_k=2)
        srv.capture_logits = True
        srv.submit([1, 2, 3], 4)
        with pytest.raises(ValueError, match="capture_logits"):
            srv.run_until_complete()


# ---------------------------------------------------------------------------
# Off contract: bit-identical decode program, no fast-path tags
# ---------------------------------------------------------------------------

class TestOffContract:
    def test_decode_lowering_pinned_to_pr8_program(self, gpt_setup):
        """The pin, turned round: PR 8's decode program gathered, reshaped
        and attended over ``slots x max_blocks x block_size`` positions of
        keys whatever was live, and this test held the default engine to
        that program. The default decode now reads the list of live
        blocks, so its lowered text must hold NO operand or result over
        that window: not the gathered keys or values, not their scores or
        mask. PR 8's program, rebuilt here from the same public pieces
        (the cache without a list), shows that the search finds them."""
        import re

        from deepspeed_tpu.inference.engine import sample_logits

        model, cfg, params = gpt_setup
        srv = _serve(model, params)
        srv.submit([1, 2, 3, 4, 5], 3)
        srv.run_until_complete()
        decode = srv._decode_jit                  # what the engine runs
        nb, mb, bs = srv.scfg.max_batch_size, srv.max_blocks, srv.block_size
        shape = (srv._live_chunks, srv.LIVE_CHUNK_RUNS,
                 srv.LIVE_RUN_BLOCKS + 2)
        bt = jnp.zeros((nb, mb), jnp.int32)
        pos = jnp.zeros((nb,), jnp.int32)
        toks = jnp.zeros((nb,), jnp.int32)
        rng = jax.random.fold_in(srv._base_key, 0)
        args = (srv.engine.params, srv._pools, bt, pos, toks, rng)
        live = (jnp.zeros(shape, jnp.int32), jnp.int32(1))

        def pr8_decode_impl(params, pools, bt, pos, toks, rng):
            cache = tuple(
                PagedLayerCache(*pools[i], bt, pos, srv.block_size,
                                srv._dtype_name)
                for i in range(cfg.num_layers))
            out = srv.module.apply(
                {"params": srv.engine._materialized(params)},
                {"input_ids": toks[:, None], "position_ids": pos[:, None]},
                deterministic=True, cache=cache, pos=None)
            logits = out["logits"][:, -1].astype(jnp.float32)
            tok = sample_logits(logits, rng, srv.scfg.temperature,
                                srv.scfg.top_k)
            return tok, logits, tuple(c.pools for c in out["cache"])

        window = mb * bs                    # key positions a row reserves
        over_window = {nb * window * cfg.num_heads * cfg.head_dim,  # K, V
                       nb * window * cfg.num_heads,                 # scores
                       nb * window}                                 # mask

        def tensors_over_window(text):
            found = set()
            for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]", text):
                dims = [int(d) for d in dims.rstrip("x").split("x")]
                if window in dims and int(np.prod(dims)) in over_window:
                    found.add(tuple(dims))
            return found

        pr8 = jax.jit(pr8_decode_impl,
                      donate_argnums=(1,)).lower(*args).as_text()
        assert len(tensors_over_window(pr8)) >= 3       # the search reads
        ours = decode.lower(*args, *live).as_text()
        assert not tensors_over_window(ours)
        # and it takes the list: a run a line, with its slot and start
        assert "tensor<%dx%dx%dxi32>" % shape in ours
        assert "stablehlo.while" in ours and "stablehlo.while" not in pr8

    def test_off_emits_no_fastpath_tags(self, gpt_setup):
        """A fully-off engine's emitted tag set is byte-identical to the
        pre-fast-path engine's."""
        model, cfg, params = gpt_setup
        tel, sink = _mem_telemetry()
        srv = _serve(model, params, telemetry=tel)
        _run_trace(srv, cfg)
        new_tags = {"serving/decode_attn_kernel", "serving/prefix_hits",
                    "serving/prefix_blocks_reused",
                    "serving/spec_accept_rate",
                    "serving/spec_tokens_per_verify"}
        assert not (sink.tags() & new_tags)
        # and the one-decode-program contract still holds verbatim
        det = srv.engine.recompile_detector
        assert det.compiles("serving.decode_step") == 1
        assert det.retraces("serving.decode_step") == 0


# ---------------------------------------------------------------------------
# Probe CLI (tier-1 hook)
# ---------------------------------------------------------------------------

def test_probe_serving_fastpath_selftest():
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "probe_serving_fastpath.py"),
         "--selftest"], capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "selftest ok" in proc.stdout
    assert "token identity" in proc.stdout
    assert "prefix reuse" in proc.stdout
