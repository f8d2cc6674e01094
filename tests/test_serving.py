"""Serving engine tests — paged KV cache, continuous batching, SLO telemetry.

The acceptance gates of the serving tier (docs/SERVING.md):

- the paged cache is **block-table-exact** against a contiguous cache and
  the int8 pools round-trip within RTNE tolerance;
- an e2e mixed trace completes with outputs **token-identical** to
  one-shot ``generate()``, finished slots are backfilled mid-run, and the
  measured ``serving/batch_occupancy`` beats static batching on the same
  trace;
- preemption under KV pressure evicts the youngest sequence and the
  request still completes correctly;
- steady state compiles the decode program exactly once;
- serving telemetry honors the zero-overhead-when-disabled contract
  (same device-sync count off vs on-but-disabled, like
  telemetry/guardrails/goodput).
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
from deepspeed_tpu.models.gpt import init_kv_cache
from deepspeed_tpu.serving import (BlockPool, PagedLayerCache, ServeEngine,
                                   init_paged_pools, pack_prefill)
from deepspeed_tpu.serving.kv_cache import _quant_tokens
from deepspeed_tpu.telemetry import (InMemorySink, MetricsRegistry,
                                     RecompileDetector, StepTracer,
                                     Telemetry)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def gpt_setup():
    # fp32 like test_inference.py: the parity oracle is one-shot
    # generate(), and bf16 argmax tie-flips between the (numerically
    # different but equally valid) paged and contiguous paths are noise.
    model, cfg = make_gpt("tiny", dropout_rate=0.0, max_seq_len=64,
                          dtype=jnp.float32)
    rng = np.random.default_rng(0)
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


def _mem_telemetry(trace_path=None, sync_spans=False):
    reg = MetricsRegistry()
    sink = reg.add_sink(InMemorySink())
    tracer = StepTracer(path=trace_path, enabled=trace_path is not None,
                        sync_spans=sync_spans)
    return Telemetry(reg, tracer, RecompileDetector(enabled=False)), sink


# ---------------------------------------------------------------------------
# Block pool
# ---------------------------------------------------------------------------

class TestBlockPool:
    def test_alloc_release_roundtrip(self):
        pool = BlockPool(8)
        assert pool.capacity == 7
        a = pool.alloc(3)
        b = pool.alloc(4)
        assert len(a) == 3 and len(b) == 4
        assert BlockPool.SCRATCH not in a + b        # block 0 never granted
        assert pool.alloc(1) is None                  # exhausted, no partial
        pool.release(a)
        assert pool.free_blocks == 3
        assert pool.used_blocks == 4

    def test_double_free_and_scratch_guard(self):
        pool = BlockPool(4)
        a = pool.alloc(2)
        pool.release(a)
        with pytest.raises(ValueError, match="double free"):
            pool.release([a[0]])
        with pytest.raises(ValueError, match="scratch"):
            pool.release([0])

    def test_too_small(self):
        with pytest.raises(ValueError, match=">= 2"):
            BlockPool(1)


# ---------------------------------------------------------------------------
# Paged cache numerics
# ---------------------------------------------------------------------------

class TestPagedCache:
    def _packed(self, cfg, params, model, ids, int8, bs=4, nb=16):
        """Prefill ``ids`` [1, T] through the contiguous cache and pack
        into pool blocks [3, 7, ...]; returns (pools, k_stack, blocks)."""
        t = ids.shape[1]
        cache = init_kv_cache(cfg, 1, t, dtype=jnp.float32)
        out = model.apply({"params": params}, {"input_ids": ids},
                          deterministic=True, cache=cache, pos=0)
        k_stack = jnp.stack([c[0][0] for c in out["cache"]])
        v_stack = jnp.stack([c[1][0] for c in out["cache"]])
        pools = init_paged_pools(cfg, nb, bs, int8=int8, dtype=jnp.float32)
        blocks = jnp.asarray([3, 7], jnp.int32)       # non-contiguous
        pools = pack_prefill(pools, blocks, k_stack, v_stack)
        return pools, k_stack, v_stack, blocks

    def test_block_table_exact_vs_contiguous(self, gpt_setup):
        """The acceptance gate: gather through a (deliberately scrambled)
        block table reconstructs the contiguous cache EXACTLY."""
        model, cfg, params = gpt_setup
        rng = np.random.default_rng(1)
        ids = rng.integers(0, cfg.vocab_size, (1, 8), dtype=np.int32)
        pools, k_stack, v_stack, _ = self._packed(cfg, params, model, ids,
                                                  int8=False)
        bt = np.zeros((1, 8), np.int32)
        bt[0, :2] = [3, 7]
        lc = PagedLayerCache(*pools[0], jnp.asarray(bt),
                             jnp.asarray([8], jnp.int32), 4, "float32")
        got_k = np.asarray(lc._gather(lc.k, lc.k_scale,
                                      cfg.num_heads))[0, :8]
        got_v = np.asarray(lc._gather(lc.v, lc.v_scale,
                                      cfg.num_heads))[0, :8]
        np.testing.assert_array_equal(got_k, np.asarray(k_stack[0]))
        np.testing.assert_array_equal(got_v, np.asarray(v_stack[0]))

    def test_int8_pools_roundtrip_tolerance(self, gpt_setup):
        """int8 pools dequantize within the RTNE bound: per-(token, head)
        absmax / 127 (the comm/quantize.py contract)."""
        model, cfg, params = gpt_setup
        rng = np.random.default_rng(1)
        ids = rng.integers(0, cfg.vocab_size, (1, 8), dtype=np.int32)
        pools, k_stack, _, _ = self._packed(cfg, params, model, ids,
                                            int8=True)
        bt = np.zeros((1, 8), np.int32)
        bt[0, :2] = [3, 7]
        lc = PagedLayerCache(*pools[0], jnp.asarray(bt),
                             jnp.asarray([8], jnp.int32), 4, "float32")
        got = np.asarray(lc._gather(lc.k, lc.k_scale,
                                    cfg.num_heads))[0, :8]
        want = np.asarray(k_stack[0])
        bound = np.abs(want).max(axis=-1, keepdims=True) / 127.0 + 1e-7
        assert (np.abs(got - want) <= bound).all()

    def test_update_writes_at_per_row_positions(self, gpt_setup):
        """Two rows at DIFFERENT positions write through their own block
        tables and the validity mask exposes exactly pos+1 keys."""
        model, cfg, params = gpt_setup
        pools = init_paged_pools(cfg, 16, 4, int8=False, dtype=jnp.float32)
        bt = jnp.asarray([[1, 2, 0, 0], [5, 6, 7, 0]], jnp.int32)
        pos = jnp.asarray([2, 6], jnp.int32)
        lc = PagedLayerCache(*pools[0], bt, pos, 4, "float32")
        k_new = jnp.arange(2 * cfg.num_heads * cfg.head_dim,
                           dtype=jnp.float32).reshape(
            2, 1, cfg.num_heads, cfg.head_dim) + 1.0
        new, kk, vv, mask = lc.update(k_new, k_new * 2)
        kk = np.asarray(kk)
        np.testing.assert_array_equal(kk[0, 2], np.asarray(k_new[0, 0]))
        np.testing.assert_array_equal(kk[1, 6], np.asarray(k_new[1, 0]))
        m = np.asarray(mask)[:, 0, 0]                 # [B, L]
        assert m[0].sum() == 3 and m[1].sum() == 7    # kpos <= pos
        # row 0's write landed in block 1 offset 2 of the pool (heads
        # folded into the stored row)
        np.testing.assert_array_equal(np.asarray(new.k[1, 2]),
                                      np.asarray(k_new[0, 0]).reshape(-1))


@pytest.mark.parametrize("int8", [False, True], ids=["fp", "int8"])
class TestPoolForm:
    """The ONE stored form of the pool (kv_cache.py's module docstring):
    ``[num_blocks, block_size, heads * head_dim]``, heads folded into the
    lane axis, written and read as such by every program. What the model
    sees is unchanged: ``[B, L, H, D]``, element for element the
    contiguous cache (int8: its per-(token, head) RTNE round trip)."""

    NB, BS = 16, 4

    @staticmethod
    def _stored_values(x, int8):
        """What a pool hands back for ``x`` [..., H, D]."""
        if not int8:
            return np.asarray(x)
        q, s = _quant_tokens(x)
        return np.asarray(q, np.float32) * np.asarray(s)[..., None]

    def test_pool_shapes(self, gpt_setup, int8):
        _, cfg, _ = gpt_setup
        pools = init_paged_pools(cfg, self.NB, self.BS, int8=int8,
                                 dtype=jnp.float32)
        assert len(pools) == cfg.num_layers
        for k, v, ks, vs in pools:
            assert k.shape == v.shape == (
                self.NB, self.BS, cfg.num_heads * cfg.head_dim)
            assert k.dtype == v.dtype == (jnp.int8 if int8 else jnp.float32)
            if int8:
                assert ks.shape == vs.shape == (self.NB, self.BS,
                                                cfg.num_heads)
            else:
                assert ks is None and vs is None

    def test_write_then_gather_equals_contiguous(self, gpt_setup, int8):
        """Two rows written token by token through scrambled block tables
        read back as the contiguous ``[B, L, H, D]`` cache."""
        _, cfg, _ = gpt_setup
        rng = np.random.default_rng(5)
        b, length = 2, 10
        kv = jnp.asarray(rng.normal(size=(2, b, length, cfg.num_heads,
                                          cfg.head_dim)), jnp.float32)
        bt = jnp.asarray([[9, 2, 11, 0], [4, 13, 1, 0]], jnp.int32)
        pools = init_paged_pools(cfg, self.NB, self.BS, int8=int8,
                                 dtype=jnp.float32)[0]
        for t in range(0, length, 2):           # chunks of two tokens
            lc = PagedLayerCache(*pools, bt, jnp.full((b,), t, jnp.int32),
                                 self.BS, "float32")
            lc, kk, vv, _ = lc.update(kv[0][:, t:t + 2], kv[1][:, t:t + 2])
            pools = lc.pools
        assert lc.k.shape == (self.NB, self.BS,
                              cfg.num_heads * cfg.head_dim)
        assert kk.shape == (b, lc.key_len, cfg.num_heads, cfg.head_dim)
        np.testing.assert_array_equal(np.asarray(kk)[:, :length],
                                      self._stored_values(kv[0], int8))
        np.testing.assert_array_equal(np.asarray(vv)[:, :length],
                                      self._stored_values(kv[1], int8))

    def test_pack_then_gather_returns_the_stack(self, gpt_setup, int8):
        _, cfg, _ = gpt_setup
        rng = np.random.default_rng(6)
        k_stack, v_stack = (jnp.asarray(rng.normal(
            size=(cfg.num_layers, 2 * self.BS, cfg.num_heads,
                  cfg.head_dim)), jnp.float32) for _ in range(2))
        pools = pack_prefill(
            init_paged_pools(cfg, self.NB, self.BS, int8=int8,
                             dtype=jnp.float32),
            jnp.asarray([7, 3], jnp.int32), k_stack, v_stack)
        bt = jnp.asarray([[7, 3, 0]], jnp.int32)
        for i, layer in enumerate(pools):
            lc = PagedLayerCache(*layer, bt, jnp.asarray([8], jnp.int32),
                                 self.BS, "float32")
            for pool, scale, stack in ((lc.k, lc.k_scale, k_stack),
                                       (lc.v, lc.v_scale, v_stack)):
                got = np.asarray(lc._gather(pool, scale, cfg.num_heads))
                np.testing.assert_array_equal(
                    got[0, :2 * self.BS],
                    self._stored_values(stack[i], int8))


# ---------------------------------------------------------------------------
# Continuous batching end-to-end
# ---------------------------------------------------------------------------

class TestContinuousBatching:
    # (prompt_len, max_new_tokens) — mixed lengths, arrivals staggered so
    # later requests must backfill freed slots mid-run.
    TRACE = [(5, 12), (9, 3), (3, 10), (12, 4), (7, 8)]
    SUBMIT_AT = [0, 0, 2, 4, 4]        # engine step at which to submit

    @staticmethod
    def _static_occupancy(trace, slots):
        """Static batching on the same trace: batches of ``slots`` formed
        in order, each draining to its LONGEST member before the next
        starts. Returns busy-slot fraction."""
        steps = busy = 0
        for i in range(0, len(trace), slots):
            batch = [n for _, n in trace[i:i + slots]]
            steps += max(batch)
            busy += sum(batch)
        return busy / (slots * steps)

    def _run_trace(self, srv, cfg, rng=None):
        rng = rng or np.random.default_rng(7)
        prompts = [rng.integers(0, cfg.vocab_size, (t,)).tolist()
                   for t, _ in self.TRACE]
        rids, pending = [None] * len(self.TRACE), set(range(len(self.TRACE)))
        step = 0
        while pending or not srv.idle():
            for i in sorted(pending):
                if self.SUBMIT_AT[i] <= step:
                    rids[i] = srv.submit(prompts[i], self.TRACE[i][1])
                    pending.discard(i)
            srv.step()
            step += 1
            assert step < 200
        return prompts, rids

    def test_e2e_matches_generate_and_beats_static(self, gpt_setup):
        model, cfg, params = gpt_setup
        tel, sink = _mem_telemetry()
        srv = _serve(model, params, telemetry=tel)
        prompts, rids = self._run_trace(srv, cfg)

        # every request completed
        assert sorted(srv.results) == sorted(rids)
        # outputs are token-identical to one-shot generate()
        for i, (rid, prompt) in enumerate(zip(rids, prompts)):
            n = self.TRACE[i][1]
            want = np.asarray(srv.engine.generate(
                np.asarray([prompt], np.int32), max_new_tokens=n))[0]
            assert srv.results[rid]["tokens"] == want.tolist(), i
        # finished slots were backfilled mid-run: some slot served
        # multiple requests
        assert max(srv.stats["slot_assignments"].values()) >= 2
        # measured occupancy beats static batching on the same trace
        occ = sink.values("serving/batch_occupancy")
        occ = [o for o in occ if o > 0]
        measured = sum(occ) / len(occ)
        static = self._static_occupancy(self.TRACE, srv.scfg.max_batch_size)
        assert measured > static + 0.05, (measured, static)
        # the registry saw every SLO surface
        tags = sink.tags()
        assert {"serving/ttft_ms", "serving/batch_occupancy",
                "serving/kv_blocks_in_use", "serving/queue_depth",
                "serving/tokens_per_sec",
                "serving/requests_completed"} <= tags

    def test_decode_compiles_exactly_once(self, gpt_setup):
        model, cfg, params = gpt_setup
        srv = _serve(model, params)
        self._run_trace(srv, cfg)
        det = srv.engine.recompile_detector
        assert det.compiles("serving.decode_step") == 1
        assert det.retraces("serving.decode_step") == 0
        # prefill: one compile per bucket, no retraces under any name
        pre = [f for f in det.stats if f.startswith("serving.prefill_b")]
        assert pre, det.stats
        for f in pre:
            assert det.compiles(f) == 1 and det.retraces(f) == 0

    def test_int8_kv_matches_fp_within_tolerance(self, gpt_setup):
        """Same trace, fp vs int8 KV pools: greedy outputs identical and
        per-step decode logits within quantization tolerance."""
        model, cfg, params = gpt_setup
        rng = np.random.default_rng(3)
        prompt = rng.integers(0, cfg.vocab_size, (6,)).tolist()

        def run(int8):
            srv = _serve(model, params, int8_kv_cache=int8)
            srv.capture_logits = True
            rid = srv.submit(prompt, 8)
            logits = []
            while not srv.idle():
                info = srv.step()
                if "logits" in info:
                    for slot, r in info["slots"].items():
                        if r == rid:
                            logits.append(info["logits"][slot])
            return srv.results[rid]["tokens"], logits

        fp_toks, fp_logits = run(False)
        q_toks, q_logits = run(True)
        assert q_toks == fp_toks
        assert len(fp_logits) == len(q_logits) >= 7
        for a, b in zip(fp_logits, q_logits):
            rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-6)
            assert rel < 0.12, rel

    def test_preemption_under_kv_pressure(self, gpt_setup):
        """A pool too small for both sequences forces the YOUNGEST out
        (the oldest is never starved); the evicted request restarts from
        its prompt, still finishes correctly, and contributes exactly ONE
        TTFT observation despite prefilling twice."""
        model, cfg, params = gpt_setup
        rng = np.random.default_rng(5)
        tel, sink = _mem_telemetry()
        # capacity 11 blocks of 4 = 44 positions; two sequences needing
        # (8 prompt-bucket + 16 gen) ~ 6 blocks each fit only briefly
        srv = _serve(model, params, telemetry=tel, kv_num_blocks=12,
                     max_model_len=32)
        p0 = rng.integers(0, cfg.vocab_size, (7,)).tolist()
        p1 = rng.integers(0, cfg.vocab_size, (6,)).tolist()
        r0 = srv.submit(p0, 24)
        r1 = srv.submit(p1, 20)
        res = srv.run_until_complete()
        # exactly ONE eviction: after it, the victim's re-admission is
        # gated on full-lifetime free blocks, so the admit/prefill/evict
        # cycle cannot thrash
        assert srv.sched.preempted_total == 1
        for rid, p, n in ((r0, p0, 24), (r1, p1, 20)):
            want = np.asarray(srv.engine.generate(
                np.asarray([p], np.int32), max_new_tokens=n))[0]
            assert res[rid]["tokens"] == want.tolist()
        # youngest-first: the FIRST-admitted request ran straight through
        assert res[r0]["finish_step"] < res[r1]["finish_step"]
        assert sink.values("serving/preempted_seqs")[-1] >= 1
        # one TTFT observation per request, not per prefill attempt
        assert len(sink.values("serving/ttft_ms")) == 2

    def test_oldest_never_preempted_when_grower_is_youngest(self, gpt_setup):
        """The documented invariant directly: when the YOUNGEST sequence
        itself needs a block from a dry pool, IT is evicted — never the
        older sequence."""
        model, cfg, params = gpt_setup
        srv = _serve(model, params, kv_num_blocks=12, max_model_len=32)
        rng = np.random.default_rng(19)
        p = rng.integers(0, cfg.vocab_size, (6,)).tolist()
        r0 = srv.submit(p, 20)
        srv.step()                              # admit + prefill r0 alone
        r1 = srv.submit(p, 20)
        seen_r0 = set()
        while not srv.idle():
            srv.step()
            if srv.sched.running:
                seen_r0 |= {s.request.rid for s in srv.sched.active}
                # r0 must never leave the running set until it finishes
                if r0 not in srv.results:
                    assert any(s.request.rid == r0
                               for s in srv.sched.active)
        assert srv.sched.preempted_total >= 1
        assert srv.results[r0]["finish_step"] <= srv.results[r1]["finish_step"]

    def test_eos_stops_early(self, gpt_setup):
        """EOS: run once unstopped to learn a token the model will emit,
        then resubmit with that token as EOS and assert early stop."""
        model, cfg, params = gpt_setup
        rng = np.random.default_rng(9)
        prompt = rng.integers(0, cfg.vocab_size, (5,)).tolist()
        srv = _serve(model, params)
        rid = srv.submit(prompt, 10)
        full = srv.run_until_complete()[rid]["tokens"]
        eos = full[len(prompt) + 4]          # 5th generated token
        srv2 = _serve(model, params)
        rid2 = srv2.submit(prompt, 10, eos_token_id=eos)
        got = srv2.run_until_complete()[rid2]["tokens"]
        assert got == full[:len(prompt) + 5]

    def test_submit_validation(self, gpt_setup):
        model, cfg, params = gpt_setup
        srv = _serve(model, params)
        with pytest.raises(ValueError, match="empty prompt"):
            srv.submit([], 4)
        with pytest.raises(ValueError, match="max_new_tokens"):
            srv.submit([1, 2], 0)
        with pytest.raises(ValueError, match="max_model_len"):
            srv.submit(list(range(40)), 20)
        tiny = _serve(model, params, kv_num_blocks=4, max_model_len=32)
        with pytest.raises(ValueError, match="never be admitted"):
            tiny.submit(list(range(10)), 16)   # needs 7 blocks, pool has 3

    def test_boundary_request_fills_pool_exactly(self, gpt_setup):
        """The last sampled token writes no KV: a request whose highest
        write position lands exactly on the pool boundary is admitted
        and completes (off-by-one regression guard)."""
        model, cfg, params = gpt_setup
        # capacity 5 blocks of 4 = 20 positions; prompt 4 + 17 new tokens
        # writes positions 0..19 — exactly 5 blocks
        srv = _serve(model, params, kv_num_blocks=6, max_model_len=21)
        rng = np.random.default_rng(23)
        p = rng.integers(0, cfg.vocab_size, (4,)).tolist()
        rid = srv.submit(p, 17)
        res = srv.run_until_complete()
        want = np.asarray(srv.engine.generate(
            np.asarray([p], np.int32), max_new_tokens=17))[0]
        assert res[rid]["tokens"] == want.tolist()

    def test_paged_cache_rejects_chunk_mask(self, gpt_setup):
        """A [B, S] attention_mask is meaningless against a paged cache's
        per-row positions — the model must refuse it, not splice it at
        key position 0."""
        model, cfg, params = gpt_setup
        from deepspeed_tpu.serving.kv_cache import init_paged_pools
        pools = init_paged_pools(cfg, 8, 4, dtype=jnp.float32)
        bt = jnp.zeros((1, 4), jnp.int32).at[0, 0].set(1)
        cache = tuple(
            PagedLayerCache(*pools[i], bt, jnp.asarray([1], jnp.int32),
                            4, "float32")
            for i in range(cfg.num_layers))
        with pytest.raises(ValueError, match="key-validity"):
            model.apply({"params": params},
                        {"input_ids": jnp.zeros((1, 1), jnp.int32),
                         "attention_mask": jnp.ones((1, 1), jnp.int32)},
                        deterministic=True, cache=cache, pos=None)

    def test_serve_forever_drains_and_returns(self, gpt_setup):
        model, cfg, params = gpt_setup
        srv = _serve(model, params)
        rng = np.random.default_rng(11)
        rid = srv.submit(rng.integers(0, cfg.vocab_size, (4,)).tolist(), 5)
        srv.serve_forever()                   # returns once idle
        assert rid in srv.results

    def test_init_serving_api(self, gpt_setup, tmp_path):
        model, cfg, params = gpt_setup
        srv = deepspeed_tpu.init_serving(
            model, params=params, dtype=jnp.float32,
            config={"serving": {"max_batch_size": 2, "kv_block_size": 4,
                                "kv_num_blocks": 32, "max_model_len": 32},
                    "telemetry": {"enabled": True, "dir": str(tmp_path)}})
        rng = np.random.default_rng(13)
        rid = srv.submit(rng.integers(0, cfg.vocab_size, (5,)).tolist(), 4)
        srv.run_until_complete()
        srv.close()
        assert rid in srv.results
        # metrics JSONL landed in the telemetry dir with serving rows
        mpath = os.path.join(str(tmp_path), "metrics.jsonl")
        assert os.path.exists(mpath)
        with open(mpath) as f:
            assert any('"serving/' in line for line in f)


# ---------------------------------------------------------------------------
# Config validation
# ---------------------------------------------------------------------------

class TestServingConfig:
    def test_defaults_parse(self):
        cfg = ServingConfig.from_dict(None)
        assert cfg.max_batch_size == 8 and cfg.kv_block_size == 16

    @pytest.mark.parametrize("block,match", [
        ({"max_batch_size": 0}, "max_batch_size"),
        ({"kv_block_size": 0}, "kv_block_size"),
        ({"kv_num_blocks": 1}, "kv_num_blocks"),
        ({"max_prefills_per_step": 0}, "max_prefills"),
        ({"temperature": -1}, "temperature"),
        ({"top_k": -1}, "top_k"),
    ])
    def test_rejects_bad_values(self, block, match):
        with pytest.raises(ConfigError, match=match):
            ServingConfig.from_dict(block)

    def test_rides_the_main_config(self):
        from deepspeed_tpu.config.config import DeepSpeedTPUConfig
        cfg = DeepSpeedTPUConfig(
            {"train_micro_batch_size_per_gpu": 1,
             "serving": {"max_batch_size": 3}}, world_size=1)
        assert cfg.serving.max_batch_size == 3

    def test_non_gpt_module_rejected(self):
        import flax.linen as nn

        class Plain(nn.Module):
            @nn.compact
            def __call__(self, batch, deterministic=True):
                return {"logits": nn.Dense(4)(batch["x"])}

        eng = deepspeed_tpu.init_inference(
            Plain(), example_batch={"x": np.zeros((1, 4), np.float32)})
        with pytest.raises(ValueError, match="cache-capable"):
            ServeEngine(eng)


# ---------------------------------------------------------------------------
# Telemetry contract
# ---------------------------------------------------------------------------

class TestServingTelemetry:
    def _drive(self, srv, cfg, n=3):
        rng = np.random.default_rng(17)
        for i in range(n):
            srv.submit(rng.integers(0, cfg.vocab_size, (4 + i,)).tolist(),
                       4 + i)
        srv.run_until_complete()

    @pytest.mark.parametrize("mode", ["off", "disabled"])
    def test_zero_device_syncs_when_off_or_disabled(self, gpt_setup,
                                                    monkeypatch, mode):
        """The zero-overhead contract, tested like telemetry/guardrails/
        goodput: with no telemetry AND with a present-but-disabled
        facade, the serving loop performs ZERO device syncs."""
        model, cfg, params = gpt_setup
        from deepspeed_tpu.telemetry import null_telemetry
        tel = None if mode == "off" else null_telemetry()
        srv = _serve(model, params, telemetry=tel)
        from deepspeed_tpu.utils import timer as timer_mod
        calls = {"n": 0}
        monkeypatch.setattr(timer_mod, "_device_synchronize",
                            lambda: calls.__setitem__("n", calls["n"] + 1))
        self._drive(srv, cfg)
        assert calls["n"] == 0
        # and nothing was emitted anywhere
        assert not srv.telemetry.enabled
        assert srv.telemetry.registry.sinks == []

    def test_spans_land_in_the_shared_timeline(self, gpt_setup, tmp_path):
        """prefill/decode_step spans are recorded by the run's StepTracer
        and render through tools/trace_report.py — the same Perfetto view
        as training."""
        model, cfg, params = gpt_setup
        trace = str(tmp_path / "trace.json")
        tel, _ = _mem_telemetry(trace_path=trace)
        srv = _serve(model, params, telemetry=tel)
        self._drive(srv, cfg, n=2)
        names = tel.tracer.span_names()
        assert {"prefill", "decode_step"} <= names
        tel.tracer.save()
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
             trace], capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
        assert "prefill" in proc.stdout and "decode_step" in proc.stdout

    def test_generate_span_through_engine_tracer(self, gpt_setup, tmp_path):
        """The one-shot engine's dispatches are bracketed too when a
        tracer is wired (satellite: spans in the inference path)."""
        model, cfg, params = gpt_setup
        tracer = StepTracer(path=str(tmp_path / "t.json"), enabled=True,
                            sync_spans=False)
        eng = deepspeed_tpu.init_inference(model, params=params,
                                           dtype=jnp.float32, tracer=tracer)
        ids = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (1, 5), dtype=np.int32)
        eng.generate(ids, max_new_tokens=2)
        eng.forward({"input_ids": ids})
        assert {"generate", "inference_forward"} <= tracer.span_names()

    def test_report_renders_a_real_run(self, gpt_setup, tmp_path):
        """serving_report over a real engine's JSONL (not just the
        selftest's synthetic rows)."""
        model, cfg, params = gpt_setup
        srv = deepspeed_tpu.init_serving(
            model, params=params, dtype=jnp.float32,
            config={"serving": {"max_batch_size": 2, "kv_block_size": 4,
                                "kv_num_blocks": 32, "max_model_len": 32},
                    "telemetry": {"enabled": True, "dir": str(tmp_path),
                                  "trace": {"enabled": False}}})
        self._drive(srv, cfg)
        srv.close()
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "serving_report.py"),
             str(tmp_path)], capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
        assert "TTFT" in proc.stdout and "occupancy" in proc.stdout
        assert "completed       3 requests" in proc.stdout

    def test_selftest_cli(self):
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "serving_report.py"),
             "--selftest"], capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr + proc.stdout
        assert "selftest ok" in proc.stdout
