"""Decode fast path tests — paged decode-attention kernel, prefix-cache
reuse, speculative decoding (docs/SERVING.md "Decode fast path").

The acceptance gates:

- the Pallas kernel (interpret path) is **parity-exact within fp32
  rounding** against the gather+masked-attention reference — including
  partial last blocks, scrambled block tables and all-scratch (block 0)
  inactive rows — and within RTNE tolerance for int8 pools (dequantized
  in-kernel);
- every fast-path configuration (kernel, capped gather, prefix cache,
  speculative, all together) produces outputs **token-identical** to the
  fully-off engine on a mixed continuous-batching trace;
- prefix COW survives youngest-first preemption (the evicted request
  re-admits warm and still finishes with correct tokens), and refcounts
  leak nothing: after ``run_until_complete`` the pool holds exactly the
  cache's blocks, and zero after a cache clear (or immediately, with the
  cache off);
- speculative decode is token-identical to greedy by construction and
  emits its accept-rate evidence;
- fast path fully off ⇒ the decode program's lowering is bit-identical
  to the pre-fast-path (PR 8) program, reconstructed here from the same
  public pieces (jaxpr pin), and no fast-path tags are emitted.
"""

import functools
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
from deepspeed_tpu.serving.kv_cache import _quant_tokens, init_paged_pools
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
# Engine-level token identity + window capping
# ---------------------------------------------------------------------------

class TestFastPathTokenIdentity:
    def test_every_configuration_matches_off(self, gpt_setup):
        model, cfg, params = gpt_setup
        srv_off = _serve(model, params)
        _, base = _run_trace(srv_off, cfg)
        for over in ({"decode_attention": "kernel"},
                     {"decode_attention": "auto"},
                     {"prefix_cache": True},
                     {"spec_decode": True, "spec_k": 3},
                     {"decode_attention": "kernel", "prefix_cache": True,
                      "spec_decode": True, "spec_k": 3}):
            srv = _serve(model, params, **over)
            _, got = _run_trace(srv, cfg)
            assert got == base, over

    def test_capped_gather_shrinks_window(self, gpt_setup):
        """The capped-fallback satellite: under auto (no TPU -> capped
        gather) the decode key window tracks the max ACTIVE length, so
        the modeled gathered positions drop well below the full-window
        program's on the same trace."""
        model, cfg, params = gpt_setup
        srv_off = _serve(model, params)
        _run_trace(srv_off, cfg)
        srv = _serve(model, params, decode_attention="auto")
        _run_trace(srv, cfg)
        assert srv.stats["full_positions"] == \
            srv_off.stats["gathered_positions"]
        assert srv.stats["gathered_positions"] < \
            0.7 * srv.stats["full_positions"]
        # each window bucket is its own expected-first-compile scope —
        # no retraces under any of them
        det = srv.engine.recompile_detector
        scopes = [f for f in det.stats
                  if f.startswith("serving.decode_step_w")]
        assert scopes, det.stats
        for f in scopes:
            assert det.compiles(f) == 1 and det.retraces(f) == 0

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
        """Jaxpr pin: with the fast path fully off, the engine's decode
        program lowers bit-identically to the pre-fast-path (PR 8)
        decode impl, reconstructed here from the same public pieces —
        full-window gather, no window slicing, no kernel, no clamps."""
        from deepspeed_tpu.inference.engine import sample_logits

        model, cfg, params = gpt_setup
        srv = _serve(model, params)
        nb, mb = srv.scfg.max_batch_size, srv.max_blocks
        bt = jnp.zeros((nb, mb), jnp.int32)
        pos = jnp.zeros((nb,), jnp.int32)
        toks = jnp.zeros((nb,), jnp.int32)
        rng = jax.random.fold_in(srv._base_key, 0)
        args = (srv.engine.params, srv._pools, bt, pos, toks, rng)

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

        import re

        def canon(text):
            # the module carries the python function's name — the only
            # legitimate difference between the two lowerings
            return re.sub(r"module @\S+", "module @m", text)

        ours = jax.jit(functools.partial(srv._decode_impl,
                                         attn_impl="gather"),
                       donate_argnums=(1,)).lower(*args).as_text()
        pr8 = jax.jit(pr8_decode_impl,
                      donate_argnums=(1,)).lower(*args).as_text()
        assert canon(ours) == canon(pr8)

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
