"""Ragged chunked-prefill tests — the mixed decode+prefill kernel and
its admission mode (docs/SERVING.md "Chunked prefill admission").

The acceptance gates:

- the Pallas kernel (interpret path) is **parity-exact within fp32
  rounding** against a per-token gather+mask reference — mixed ragged
  batches, chunk boundaries mid-block, all-decode and all-prefill
  degenerate batches, scrambled block tables, pad rows on the scratch
  table row — and within RTNE tolerance for int8 pools (dequantized
  in-kernel with the whole-heads scale-block layout);
- chunked admission is **token-identical** to the bucketed oracle on a
  mixed continuous-batching trace, composing with int8 KV, the prefix
  cache, speculative decoding and resilience fault replay;
- the mixed program compiles exactly ONCE (recompile-detector-proven)
  while the bucketed engine builds O(buckets) prefill programs;
- chunked off ⇒ zero overhead: the engine builds no mixed state, emits
  no chunked tags, and config validation rejects the combinations the
  token-identity contract cannot honor.
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
from deepspeed_tpu.ops.transformer.chunked_prefill import \
    chunked_prefill_attention
from deepspeed_tpu.serving import ServeEngine
from deepspeed_tpu.serving.kv_cache import _quant_tokens
from deepspeed_tpu.telemetry import (InMemorySink, MetricsRegistry,
                                     RecompileDetector, StepTracer,
                                     Telemetry)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# kernel parity (interpret mode)
# ---------------------------------------------------------------------------

def _reference(q, k_pool, v_pool, table, pos, block_size, scale):
    """Per-token gather + causal-mask attention over the paged pools."""
    t, h, d = q.shape
    wb = table.shape[1]
    out = np.zeros((t, h, d), np.float32)
    kp = np.asarray(k_pool, np.float32)
    vp = np.asarray(v_pool, np.float32)
    for i in range(t):
        ks = kp[table[i]].reshape(wb * block_size, h, d)
        vs = vp[table[i]].reshape(wb * block_size, h, d)
        kpos = np.arange(wb * block_size)
        mask = kpos <= pos[i]
        for hh in range(h):
            s = (q[i, hh].astype(np.float32) @ ks[:, hh].T) * scale
            s = np.where(mask, s, -1e30)
            p = np.exp(s - s.max())
            p /= p.sum()
            out[i, hh] = p @ vs[:, hh]
    return out


def _pools(rng, nblocks, block_size, h, d, dtype=np.float32):
    k = rng.standard_normal((nblocks, block_size, h, d)).astype(dtype)
    v = rng.standard_normal((nblocks, block_size, h, d)).astype(dtype)
    return k, v


def _stored(pool):
    """[N, BS, H, D] -> the pool's stored form [N, BS, H*D]."""
    return jnp.asarray(pool).reshape(*pool.shape[:2], -1)


class TestChunkedPrefillKernel:
    @pytest.mark.parametrize("pos", [
        # mixed: decode rows (deep pos) + prefill chunk rows (ragged)
        [11, 3, 0, 1, 2, 5, 6, 7],
        # chunk boundary mid-block (block_size 4: positions 5..8 span it)
        [5, 6, 7, 8, 9, 10, 11, 12],
        # all-decode
        [9, 14, 3, 7, 12, 5, 8, 10],
        # all-prefill from zero
        [0, 1, 2, 3, 4, 5, 6, 7],
    ])
    def test_parity_fp(self, rng, pos):
        bs, h, d, wb = 4, 2, 128, 4
        t = len(pos)
        k, v = _pools(rng, 16, bs, h, d)
        q = rng.standard_normal((t, h, d)).astype(np.float32)
        # scrambled, per-row-distinct tables
        table = np.stack([rng.permutation(np.arange(1, 16))[:wb]
                          for _ in range(t)]).astype(np.int32)
        pos = np.asarray(pos, np.int32)
        got = chunked_prefill_attention(
            jnp.asarray(q), _stored(k), _stored(v), None, None,
            jnp.asarray(table), jnp.asarray(pos), block_size=bs)
        ref = _reference(q, k, v, table, pos, bs, d ** -0.5)
        np.testing.assert_allclose(np.asarray(got), ref, atol=2e-5)

    def test_parity_int8(self, rng):
        bs, h, d, wb, t = 4, 2, 128, 4, 6
        kf, vf = _pools(rng, 16, bs, h, d)
        kq, ks = _quant_tokens(jnp.asarray(kf))
        vq, vs = _quant_tokens(jnp.asarray(vf))
        q = rng.standard_normal((t, h, d)).astype(np.float32)
        table = np.stack([rng.permutation(np.arange(1, 16))[:wb]
                          for _ in range(t)]).astype(np.int32)
        pos = np.asarray([0, 5, 9, 2, 13, 7], np.int32)
        got = chunked_prefill_attention(
            jnp.asarray(q), _stored(kq), _stored(vq), ks, vs,
            jnp.asarray(table), jnp.asarray(pos), block_size=bs)
        # int8 reference: dequantize the pools, then exact attention
        kd = np.asarray(kq, np.float32) * np.asarray(ks)[:, :, :, None]
        vd = np.asarray(vq, np.float32) * np.asarray(vs)[:, :, :, None]
        ref = _reference(q, kd, vd, table, pos, bs, d ** -0.5)
        np.testing.assert_allclose(np.asarray(got), ref, atol=2e-5)

    def test_pad_rows_attend_scratch_only(self, rng):
        """A pad row (all-zeros table, pos 0) sees exactly pool block 0
        position 0 — well-defined output, no NaN."""
        bs, h, d = 4, 2, 128
        k, v = _pools(rng, 8, bs, h, d)
        q = rng.standard_normal((2, h, d)).astype(np.float32)
        table = np.zeros((2, 2), np.int32)
        pos = np.zeros((2,), np.int32)
        got = np.asarray(chunked_prefill_attention(
            jnp.asarray(q), _stored(k), _stored(v), None, None,
            jnp.asarray(table), jnp.asarray(pos), block_size=bs))
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got[0, 0], k[0, 0, 0] * 0 + v[0, 0, 0],
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# engine-level: token identity, one compile, composition
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gpt_setup():
    model, cfg = make_gpt("tiny", dropout_rate=0.0, max_seq_len=64,
                          dtype=jnp.float32)
    params = model.init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(1)},
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    return model, cfg, params


def _serve(model, params, telemetry=None, fault=None, **overrides):
    scfg = ServingConfig(**{
        "max_batch_size": 2, "kv_block_size": 4, "kv_num_blocks": 64,
        "max_model_len": 48, **overrides})
    eng = deepspeed_tpu.init_inference(model, params=params,
                                       dtype=jnp.float32)
    kw = {}
    if fault is not None:
        from deepspeed_tpu.resilience import FaultPlan
        kw["fault_plan"] = FaultPlan.resolve(fault)
    return ServeEngine(eng, config=scfg, telemetry=telemetry, **kw)


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
    res = srv.run_until_complete(timeout_sec=120.0)
    return prompts, [res[r]["tokens"] for r in rids]


class TestChunkedAdmission:
    @pytest.fixture(scope="class")
    def oracle(self, gpt_setup):
        model, cfg, params = gpt_setup
        _, toks = _run_trace(_serve(model, params), cfg)
        return toks

    @pytest.mark.parametrize("overrides", [
        {},                                      # plain
        {"chunked_token_budget": 2},             # minimum legal budget
        {"int8_kv_cache": True},
        {"prefix_cache": True},
        {"spec_decode": True, "spec_k": 2},
    ], ids=["plain", "tiny-budget", "int8", "prefix", "spec"])
    def test_token_identity(self, gpt_setup, oracle, overrides):
        model, cfg, params = gpt_setup
        base = oracle
        if overrides.get("int8_kv_cache"):
            # int8 quantization error shifts both paths the same way —
            # compare against an int8 bucketed oracle, not the fp one.
            _, base = _run_trace(_serve(model, params, int8_kv_cache=True),
                                 cfg)
        srv = _serve(model, params, chunked_prefill=True,
                     **{"chunked_token_budget": 16, **overrides})
        _, got = _run_trace(srv, cfg)
        assert got == base

    def test_one_compile_and_no_bucketed_programs(self, gpt_setup):
        model, cfg, params = gpt_setup
        srv = _serve(model, params, chunked_prefill=True,
                     chunked_token_budget=16)
        _run_trace(srv, cfg)
        det = srv.engine.recompile_detector
        assert det.compiles("serving.mixed_step") == 1
        assert det.retraces("serving.mixed_step") == 0
        assert len(srv._prefill_jit) == 0
        assert len(srv._tail_prefill_jit) == 0
        assert srv._decode_jit is None
        # vs the bucketed engine, which pays per-bucket programs
        bsrv = _serve(model, params)
        _run_trace(bsrv, cfg)
        assert len(bsrv._prefill_jit) + len(bsrv._tail_prefill_jit) >= 2

    def test_resilience_replay_token_identity(self, gpt_setup, oracle):
        """A persistent decode fault under chunked admission heals via
        rebuild + replay through the SAME mixed program and finishes
        token-identical to the fault-free bucketed run."""
        model, cfg, params = gpt_setup
        srv = _serve(model, params, chunked_prefill=True,
                     chunked_token_budget=16, resilience=True,
                     resil_retry_base_sec=0.01,
                     fault={"serve_decode_fault_at_step": 3,
                            "serve_decode_fault_count": 3})
        _, got = _run_trace(srv, cfg)
        assert got == oracle
        assert srv._resil.counters["recoveries"] >= 1

    def test_chunked_metrics_emitted(self, gpt_setup):
        model, cfg, params = gpt_setup
        tel, sink = _mem_telemetry()
        srv = _serve(model, params, telemetry=tel, chunked_prefill=True,
                     chunked_token_budget=16)
        _run_trace(srv, cfg)
        srv.telemetry.flush()
        tags = sink.tags()
        assert "serving/chunked_tokens_per_step" in tags
        assert "serving/prefill_chunks_in_flight" in tags


class TestChunkedOffContract:
    def test_off_engine_builds_no_mixed_state(self, gpt_setup):
        model, cfg, params = gpt_setup
        tel, sink = _mem_telemetry()
        srv = _serve(model, params, telemetry=tel)
        _run_trace(srv, cfg)
        assert srv._chunked is False and srv._mixed_jit is None
        srv.telemetry.flush()
        assert not (sink.tags() & {"serving/chunked_tokens_per_step",
                                   "serving/prefill_chunks_in_flight"})

    def test_config_validation(self):
        with pytest.raises(ConfigError, match="token_budget"):
            ServingConfig.from_dict({
                "max_batch_size": 8,
                "chunked_prefill": {"enabled": True, "token_budget": 4}})
        with pytest.raises(ConfigError, match="temperature"):
            ServingConfig.from_dict({
                "temperature": 0.7,
                "chunked_prefill": {"enabled": True}})
        with pytest.raises(ConfigError, match="unknown"):
            ServingConfig.from_dict({
                "chunked_prefill": {"enabled": True, "bogus": 1}})
        # present block defaults to enabled (the PR 15 convention)
        cfg = ServingConfig.from_dict({"chunked_prefill": {}})
        assert cfg.chunked_prefill is True
        assert ServingConfig.from_dict({}).chunked_prefill is False

    def test_untileable_geometry_on_tpu_raises(self, gpt_setup, monkeypatch):
        """A path the user asked for is never swapped for another: on a
        TPU, a geometry the compiled kernel cannot tile (tiny GPT:
        head_dim 16) is a ConfigError at engine construction — for chunked
        admission and for decode_attention='kernel' alike — while 'auto'
        stays a documented selection and resolves to gather."""
        import deepspeed_tpu.serving.engine as serving_engine
        model, cfg, params = gpt_setup
        monkeypatch.setattr(serving_engine, "on_tpu", lambda: True)
        with pytest.raises(ConfigError, match="chunked_prefill"):
            _serve(model, params, chunked_prefill=True,
                   chunked_token_budget=8)
        with pytest.raises(ConfigError, match="decode_attention='kernel'"):
            _serve(model, params, decode_attention="kernel")
        assert _serve(model, params,
                      decode_attention="auto")._attn_impl == "gather"


class TestProbeChunkedPrefillCLI:
    def test_selftest_passes(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "probe_chunked_prefill.py"),
             "--selftest"],
            capture_output=True, text=True, env=env, timeout=540)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "selftest ok" in proc.stdout
