"""Numerics observatory tests (telemetry/numerics.py; docs/OBSERVABILITY.md
"Numerics observatory"): per-layer-group stats correctness vs hand-computed
gradients, dtype saturation/underflow counters, the roundtrip_error
property suite (comm/quantize.py satellite), DCN int8 quantization-error
bounds on a 2-slice mesh, the zero-overhead off-contract (engine.numerics
None, zero device syncs, bit-identical lowered step vs a numerics-less
config), the single-flush-fetch on-contract, spike verdicts naming the
poisoned layer group (instant + crashdump), offload/pipe tier coverage,
the serving int8 KV error gauge, the fleet grad-norm field, the
get_global_grad_norm no-retrace satellite, and tools/numerics_report.py."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.quantize import (quantize_blockwise, roundtrip_error,
                                         roundtrip_error_parts)
from deepspeed_tpu.config.config import ConfigError, DeepSpeedTPUConfig
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.telemetry.numerics import (GRAD_SQ, N_GROUP_STATS,
                                              OTHER_GROUP, SATURATED,
                                              UNDERFLOWED, UPDATE_SQ,
                                              WEIGHT_SQ, NumericsPlan)

from simple_model import mlp_loss_fn, mlp_params, random_batches

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tel(tmp_path, numerics=None, sinks=("memory",), **extra_tel):
    tel = {"enabled": True, "dir": str(tmp_path),
           "trace": {"enabled": False},
           "metrics": {"sinks": list(sinks)}, **extra_tel}
    if numerics is not None:
        tel["numerics"] = numerics
    return tel


def _engine(config_extra=None, mesh=None, params=None):
    engine, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=mlp_loss_fn,
        params=params if params is not None else mlp_params(),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "steps_per_print": 1,
                **(config_extra or {})},
        mesh=mesh if mesh is not None else build_mesh(data=8))
    return engine


def _rows(engine, tag):
    return [r for r in engine.telemetry.registry.sinks[0].rows
            if r["tag"] == tag]


# ---------------------------------------------------------------------------
# Plan grouping
# ---------------------------------------------------------------------------
class TestPlanGrouping:
    def test_top_level_groups(self):
        plan = NumericsPlan(mlp_params())
        assert plan.group_names == ["head", "layer_0", "layer_1"]
        assert len(plan.leaf_group) == len(
            jax.tree_util.tree_leaves(mlp_params()))

    def test_group_cap_collapses_tail_into_other(self):
        params = {f"k{i:02d}": np.zeros((2,), np.float32) for i in range(9)}
        plan = NumericsPlan(params, max_groups=4)
        assert len(plan.group_names) == 4
        assert plan.group_names[-1] == OTHER_GROUP
        # 3 named + 6 collapsed
        other_idx = plan.group_names.index(OTHER_GROUP)
        assert plan.leaf_group.count(other_idx) == 6

    def test_config_validation(self):
        with pytest.raises(ConfigError):
            DeepSpeedTPUConfig({
                "train_micro_batch_size_per_gpu": 1,
                "telemetry": {"enabled": True, "dir": "/tmp",
                              "numerics": {"enabled": True,
                                           "max_groups": 0}}})


# ---------------------------------------------------------------------------
# roundtrip_error (comm/quantize.py satellite): property tests
# ---------------------------------------------------------------------------
class TestRoundtripError:
    def test_zero_blocks_exact(self):
        rel, mab = roundtrip_error(jnp.zeros((4, 256)), 8, 256)
        assert float(rel) == 0.0 and float(mab) == 0.0

    def test_error_bounded_by_half_scale(self):
        rng = np.random.default_rng(0)
        for block in (256, 1024):
            x = jnp.asarray(rng.standard_normal((4, 2048)), jnp.float32)
            rel, mab = roundtrip_error(x, 8, block)
            # RTNE: per-element error <= scale/2 where scale = absmax/127
            # per block; bound by the largest block's scale.
            blocks = np.asarray(x).reshape(4, 2048 // block, block)
            scale = np.abs(blocks).max(axis=-1) / 127.0
            assert float(mab) <= scale.max() * 0.5 * (1 + 1e-3)
            assert 0 < float(rel) < 0.05

    def test_nan_transparent(self):
        x = jnp.ones((256,)).at[3].set(jnp.nan)
        rel, mab = roundtrip_error(x, 8, 256)
        assert not np.isfinite(float(rel))
        assert not np.isfinite(float(mab))

    def test_bf16_tier_and_fp32_passthrough(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((1024,)), jnp.float32)
        rel16, _ = roundtrip_error(x, 16, 256)
        assert 0 < float(rel16) < 0.01         # bf16: ~2^-9 relative
        rel32, mab32 = roundtrip_error(x, 32, 256)
        assert float(rel32) == 0.0 and float(mab32) == 0.0

    def test_parts_compose_to_rel(self):
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((512,)), jnp.float32)
        esq, rsq, mab = roundtrip_error_parts(x, 8, 256)
        rel, mab2 = roundtrip_error(x, 8, 256)
        np.testing.assert_allclose(
            float(rel), np.sqrt(float(esq) / float(rsq)), rtol=1e-6)
        assert float(mab) == float(mab2)

    def test_roundtrip_matches_quantize_blockwise(self):
        """The helper measures the SAME transform the wire applies."""
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((2, 512)), jnp.float32)
        q, s = quantize_blockwise(x, 256)
        from deepspeed_tpu.comm.quantize import dequantize_blockwise
        dq = dequantize_blockwise(q, s, 256)
        rel, _ = roundtrip_error(x, 8, 256)
        manual = np.linalg.norm(np.asarray(dq - x)) / np.linalg.norm(
            np.asarray(x))
        np.testing.assert_allclose(float(rel), manual, rtol=1e-5)


# ---------------------------------------------------------------------------
# In-program statistics: correctness vs hand-computed grads
# ---------------------------------------------------------------------------
class TestInProgramStats:
    @pytest.mark.parametrize("stage", [0, 2])
    def test_group_stats_match_reference(self, eight_devices, tmp_path,
                                         stage):
        params0 = mlp_params()
        engine = _engine({"telemetry": _tel(tmp_path,
                                            numerics={"enabled": True}),
                          "zero_optimization": {"stage": stage}},
                         params=params0)
        assert engine.numerics is not None
        batches = random_batches(np.random.default_rng(0), gas=1,
                                 batch_size=16)
        engine.train_batch(batches)

        # Reference: one micro-batch, no dropout -> grads independent of
        # rng; gas=1, fp32 (no loss scale).
        batch0 = jax.tree_util.tree_map(lambda x: x[0], batches)
        ref_grads = jax.grad(
            lambda p: mlp_loss_fn(p, batch0, None))(params0)
        for group in engine.numerics.plan.group_names:
            got = [r for r in _rows(engine, "numerics/grad_norm")
                   if r["group"] == group][-1]["value"]
            want = float(np.sqrt(sum(
                float(jnp.sum(g.astype(jnp.float32) ** 2))
                for k, g in ref_grads.items() if k == group
                for g in jax.tree_util.tree_leaves(g))))
            np.testing.assert_allclose(got, want, rtol=1e-4)
            w = [r for r in _rows(engine, "numerics/weight_norm")
                 if r["group"] == group][-1]["value"]
            want_w = float(np.sqrt(sum(
                float(np.sum(np.square(np.asarray(l, np.float64))))
                for l in jax.tree_util.tree_leaves(params0[group]))))
            np.testing.assert_allclose(w, want_w, rtol=1e-4)
            u = [r for r in _rows(engine, "numerics/update_ratio")
                 if r["group"] == group][-1]["value"]
            assert 0 < u < 1.0          # Adam step with lr 1e-2
        # global norm = sqrt(sum of group squares)
        gg = _rows(engine, "numerics/global_grad_norm")[-1]["value"]
        want_g = float(np.sqrt(sum(
            float(jnp.sum(g.astype(jnp.float32) ** 2))
            for g in jax.tree_util.tree_leaves(ref_grads))))
        np.testing.assert_allclose(gg, want_g, rtol=1e-4)

    def test_saturation_and_underflow_counters(self):
        """Direct plan unit: fp16 compute dtype. 1e5 saturates (fp16 max
        65504), 1e-9 underflows to zero, 1.0 survives."""
        params = {"a": jnp.ones((3,), jnp.float32)}
        plan = NumericsPlan(params, compute_dtype=jnp.float16)
        grads = {"a": jnp.asarray([1e5, 1e-9, 1.0], jnp.float32)}
        stats = np.asarray(jax.jit(plan.group_stats)(grads, params))
        assert stats.shape == (1, N_GROUP_STATS)
        assert stats[0, SATURATED] == 1
        assert stats[0, UNDERFLOWED] == 1
        np.testing.assert_allclose(stats[0, GRAD_SQ],
                                   1e10 + 1e-18 + 1.0, rtol=1e-6)
        np.testing.assert_allclose(stats[0, WEIGHT_SQ], 3.0, rtol=1e-6)
        assert stats[0, UPDATE_SQ] == 0.0      # no new_params handed over

    def test_fp32_run_has_zero_counters(self, eight_devices, tmp_path):
        engine = _engine({"telemetry": _tel(tmp_path,
                                            numerics={"enabled": True})})
        engine.train_batch(random_batches(np.random.default_rng(0), gas=1,
                                          batch_size=16))
        for tag in ("numerics/saturation_count",
                    "numerics/underflow_count"):
            assert all(r["value"] == 0 for r in _rows(engine, tag))

    def test_micro_step_api_path(self, eight_devices, tmp_path):
        """forward/backward/step (the non-fused _apply_step path) feeds
        the same aux."""
        engine = _engine({"telemetry": _tel(tmp_path,
                                            numerics={"enabled": True})})
        rng = np.random.default_rng(0)
        batch = {k: v[0] for k, v in random_batches(rng, gas=1,
                                                    batch_size=16).items()}
        loss = engine.forward(batch)
        engine.backward(loss)
        engine.step()
        assert _rows(engine, "numerics/grad_norm")
        assert _rows(engine, "numerics/update_ratio")


# ---------------------------------------------------------------------------
# Off-contract: None facade, zero syncs, bit-identical lowered step
# ---------------------------------------------------------------------------
class TestOffContract:
    def test_disabled_numerics_is_none_no_tags_zero_syncs(
            self, eight_devices, tmp_path, monkeypatch):
        engine = _engine({"telemetry": _tel(tmp_path)})   # numerics absent
        assert engine.numerics is None
        batches = random_batches(np.random.default_rng(0), gas=1,
                                 batch_size=16)
        engine.train_batch(batches)               # compile outside window
        from deepspeed_tpu.utils import timer as timer_mod
        calls = {"n": 0}
        monkeypatch.setattr(timer_mod, "_device_synchronize",
                            lambda: calls.__setitem__("n", calls["n"] + 1))
        for _ in range(5):
            engine.train_batch(batches)
        assert calls["n"] == 0
        mem = engine.telemetry.registry.sinks[0]
        assert not {t for t in mem.tags() if t.startswith("numerics/")}
        # telemetry fully off => None too
        engine2 = _engine()
        assert engine2.numerics is None

    def test_lowered_step_bit_identical_when_off(self, eight_devices,
                                                 tmp_path):
        """numerics {"enabled": false} and a numerics-less telemetry
        block (and no telemetry at all) must lower to the SAME step
        text; enabled must differ (the stats really are in-program —
        otherwise this whole contract is vacuous)."""
        batches_np = random_batches(np.random.default_rng(0), gas=1,
                                    batch_size=16)
        texts = {}
        for name, extra in (
                ("absent", {"telemetry": _tel(tmp_path / "a")}),
                ("disabled", {"telemetry": _tel(
                    tmp_path / "b", numerics={"enabled": False})}),
                ("no_telemetry", {}),
                ("enabled", {"telemetry": _tel(
                    tmp_path / "c", numerics={"enabled": True})})):
            engine = _engine(extra)
            placed = engine.put_batch(batches_np, leading_gas_dim=True)
            texts[name] = engine._train_step.lower(
                engine.state, placed, jnp.float32(1e-2)).as_text()
        assert texts["absent"] == texts["disabled"] == texts["no_telemetry"]
        assert texts["enabled"] != texts["absent"]

    def test_lowered_step_bit_identical_when_off_hierarchical(
            self, eight_devices, tmp_path):
        """Same contract on the int8 2-slice grad-sync path: numerics
        off must not perturb the DCN stage's lowering."""
        texts = {}
        for name, numerics in (("absent", None),
                               ("disabled", {"enabled": False})):
            engine = _engine(
                {"gradient_accumulation_steps": 2,
                 "zero_optimization": {"stage": 2},
                 "comm": {"hierarchical": "on", "quant_block_size": 256},
                 "telemetry": _tel(tmp_path / name, numerics=numerics)},
                mesh=build_mesh(slices=2))
            batches = random_batches(np.random.default_rng(0), gas=2,
                                     batch_size=16)
            placed = engine.put_batch(batches, leading_gas_dim=True)
            texts[name] = engine._train_step.lower(
                engine.state, placed, jnp.float32(1e-2)).as_text()
        assert texts["absent"] == texts["disabled"]


# ---------------------------------------------------------------------------
# On-contract: zero step-path syncs, ONE fetch per flush boundary
# ---------------------------------------------------------------------------
class TestOnContract:
    def test_single_fetch_at_flush_boundary(self, eight_devices, tmp_path,
                                            monkeypatch):
        engine = _engine({"steps_per_print": 3,
                          "telemetry": _tel(tmp_path,
                                            numerics={"enabled": True})})
        batches = random_batches(np.random.default_rng(0), gas=1,
                                 batch_size=16)
        engine.train_batch(batches)               # compile + first flush
        from deepspeed_tpu.utils import timer as timer_mod
        syncs = {"n": 0}
        monkeypatch.setattr(timer_mod, "_device_synchronize",
                            lambda: syncs.__setitem__("n", syncs["n"] + 1))
        fetches = {"n": 0}
        real_fetch = engine.numerics._fetch

        def counting_fetch():
            fetches["n"] += 1
            return real_fetch()

        monkeypatch.setattr(engine.numerics, "_fetch", counting_fetch)
        for _ in range(6):                        # steps 2..7
            engine.train_batch(batches)
        # flush boundaries at steps 3 and 6 -> exactly two fetches, no
        # timer syncs anywhere on the step path.
        assert fetches["n"] == 2, fetches
        assert syncs["n"] == 0


# ---------------------------------------------------------------------------
# DCN int8 quantization error (the acceptance bound)
# ---------------------------------------------------------------------------
class TestDcnQuantError:
    def test_int8_two_slice_bounded(self, eight_devices, tmp_path):
        engine = _engine(
            {"gradient_accumulation_steps": 2,
             "zero_optimization": {"stage": 2},
             "comm": {"hierarchical": "on", "quant_block_size": 256},
             "telemetry": _tel(tmp_path, numerics={"enabled": True})},
            mesh=build_mesh(slices=2))
        assert engine.grad_sync_plan.measure_quant
        rng = np.random.default_rng(0)
        for _ in range(2):
            engine.train_batch(random_batches(rng, gas=2, batch_size=16))
        rel = _rows(engine, "numerics/dcn_quant_rel_err")
        assert rel, "dcn_quant_rel_err not emitted"
        # emitted, nonzero, bounded: rel-L2 < 1e-1 at block 256
        assert all(0 < r["value"] < 1e-1 for r in rel), rel
        mab = _rows(engine, "numerics/dcn_quant_max_abs_err")
        assert mab and all(0 < r["value"] < 1.0 for r in mab)
        assert all(r["bucket"] in range(
            engine.grad_sync_plan.num_buckets) for r in rel)

    def test_fp32_passthrough_measures_nothing(self, eight_devices,
                                               tmp_path):
        engine = _engine(
            {"gradient_accumulation_steps": 2,
             "comm": {"hierarchical": "on", "dcn_quant_bits": 32},
             "telemetry": _tel(tmp_path, numerics={"enabled": True})},
            mesh=build_mesh(slices=2))
        assert not engine.grad_sync_plan.measure_quant
        engine.train_batch(random_batches(np.random.default_rng(0), gas=2,
                                          batch_size=16))
        assert not _rows(engine, "numerics/dcn_quant_rel_err")
        assert _rows(engine, "numerics/grad_norm")    # stats still ride


# ---------------------------------------------------------------------------
# Spike verdicts name the poisoned layer group (instant + crashdump)
# ---------------------------------------------------------------------------
class TestSpikeNaming:
    def test_nan_poisoned_run_names_group(self, eight_devices, tmp_path):
        dumps = tmp_path / "dumps"
        engine = _engine({
            "steps_per_print": 100,
            "resilience": {"fault_injection": {"nan_loss_at_step": 3}},
            "guardrails": {
                "enabled": True,
                "detector": {"zscore_threshold": 1e9, "warmup_steps": 1},
                "rollback": {"snapshot_interval": 1,
                             "consecutive_spikes": 1, "skip_batches": 0},
                "watchdog": {"crashdump_dir": str(dumps)}},
            "telemetry": {**_tel(tmp_path, numerics={"enabled": True}),
                          "trace": {"enabled": True,
                                    "sync_spans": False}}})
        rng = np.random.default_rng(1)
        stream = [random_batches(rng, gas=1, batch_size=16)
                  for _ in range(8)]
        i = 0
        while engine.global_steps < 5:
            engine.train_batch(stream[i % len(stream)])
            i += 1
        names = engine.numerics.plan.group_names
        spikes = [e for e in engine.telemetry.tracer.events
                  if e.get("name") == "guardrails_spike"]
        assert spikes, "no spike instant"
        worst = spikes[0]["args"]["worst_group"]
        assert worst in names, (worst, names)
        spike_dirs = [d for d in os.listdir(dumps)
                      if d.startswith("spike_step")]
        assert spike_dirs, os.listdir(dumps)
        info = json.load(open(dumps / spike_dirs[0] / "info.json"))
        assert info["worst_group"] == worst
        assert info["reason"] == "nonfinite"
        table = {g["group"]: g for g in info["groups"]}
        assert set(table) == set(names)
        # NaN batch poisons every group's grads; the table says so
        assert not table[worst]["finite"]

    def test_dump_budget_bounds_disk(self, eight_devices, tmp_path):
        dumps = tmp_path / "dumps"
        engine = _engine({
            "steps_per_print": 100,
            "resilience": {"fault_injection": {"nan_loss_at_step": 2,
                                               "nan_loss_steps": 6}},
            "guardrails": {
                "enabled": True,
                "detector": {"zscore_threshold": 1e9, "warmup_steps": 1},
                "rollback": {"enabled": False},
                "watchdog": {"crashdump_dir": str(dumps)}},
            "telemetry": _tel(tmp_path, numerics={"enabled": True,
                                                  "max_spike_dumps": 2})})
        rng = np.random.default_rng(1)
        stream = [random_batches(rng, gas=1, batch_size=16)
                  for _ in range(8)]
        for i in range(8):
            engine.train_batch(stream[i % len(stream)])
        spike_dirs = [d for d in os.listdir(dumps)
                      if d.startswith("spike_step")]
        assert len(spike_dirs) == 2, spike_dirs


# ---------------------------------------------------------------------------
# Offload + pipe tiers
# ---------------------------------------------------------------------------
class TestOtherTiers:
    def test_offload_grad_stats_update_zero(self, eight_devices, tmp_path):
        engine = _engine({
            "zero_optimization": {"stage": 2,
                                  "offload_optimizer": {"device": "cpu"}},
            "telemetry": _tel(tmp_path, numerics={"enabled": True})})
        rng = np.random.default_rng(0)
        for _ in range(2):
            engine.train_batch(random_batches(rng, gas=1, batch_size=16))
        gn = _rows(engine, "numerics/grad_norm")
        assert gn and all(r["value"] > 0 for r in gn)
        # host-side optimizer: update norms reported as 0 by contract
        assert all(r["value"] == 0
                   for r in _rows(engine, "numerics/update_ratio"))

    def test_pipe_engine_stats(self, eight_devices, tmp_path):
        from deepspeed_tpu.models.gpt import GPTConfig
        from deepspeed_tpu.parallel.pipe import (PipelineEngine,
                                                 gpt_pipe_model)

        cfg = GPTConfig(vocab_size=128, max_seq_len=32, hidden_size=32,
                        num_layers=2, num_heads=2, dropout_rate=0.0,
                        dtype=jnp.float32)
        ds = DeepSpeedTPUConfig({
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "steps_per_print": 1,
            "telemetry": _tel(tmp_path, numerics={"enabled": True})})
        pipe = PipelineEngine(gpt_pipe_model(cfg), ds,
                              mesh=build_mesh(data=8, pipe=1))
        assert pipe.numerics is not None
        rng = np.random.default_rng(0)
        pipe.train_batch({"input_ids": rng.integers(
            0, 128, (2, 8, 16), dtype=np.int32)})
        gn = _rows(pipe, "numerics/grad_norm")
        groups = {r["group"] for r in gn}
        assert "blocks" in groups and gn
        assert all(r["value"] > 0 for r in gn)

    def test_onebit_logs_and_disables(self, eight_devices, tmp_path):
        engine = _engine({
            "optimizer": {"type": "OneBitAdam",
                          "params": {"lr": 1e-3, "freeze_step": 100}},
            "zero_optimization": {"stage": 0},
            "telemetry": _tel(tmp_path, numerics={"enabled": True})})
        assert engine.numerics is None            # documented unavailability
        engine.train_batch(random_batches(np.random.default_rng(0), gas=1,
                                          batch_size=16))


# ---------------------------------------------------------------------------
# Serving int8 KV error gauge
# ---------------------------------------------------------------------------
class TestServingKV:
    def test_int8_kv_prefill_emits_bounded_error(self):
        from deepspeed_tpu.config.config import ServingConfig
        from deepspeed_tpu.models import make_gpt
        from deepspeed_tpu.serving import ServeEngine
        from deepspeed_tpu.telemetry import (InMemorySink, MetricsRegistry,
                                             RecompileDetector, StepTracer,
                                             Telemetry)

        model, _cfg = make_gpt("tiny", dropout_rate=0.0, max_seq_len=64,
                               dtype=jnp.float32)
        params = model.init(
            {"params": jax.random.PRNGKey(0),
             "dropout": jax.random.PRNGKey(1)},
            {"input_ids": np.zeros((1, 8), np.int32)})["params"]
        reg = MetricsRegistry()
        sink = reg.add_sink(InMemorySink())
        tel = Telemetry(reg, StepTracer(enabled=False),
                        RecompileDetector(enabled=False))
        eng = deepspeed_tpu.init_inference(model, params=params,
                                           dtype=jnp.float32)
        srv = ServeEngine(eng, config=ServingConfig(
            max_batch_size=2, kv_block_size=4, kv_num_blocks=64,
            max_model_len=48, int8_kv_cache=True), telemetry=tel,
            measure_kv_quant_error=True)
        srv.submit([1, 2, 3, 4, 5], max_new_tokens=3)
        srv.run_until_complete()
        rel = [r for r in sink.rows
               if r["tag"] == "numerics/kv_quant_rel_err"]
        assert rel and all(0 <= r["value"] < 0.2 for r in rel), rel
        assert [r for r in sink.rows
                if r["tag"] == "numerics/kv_quant_max_abs_err"]

    def test_int8_without_numerics_opt_in_measures_nothing(self):
        """Telemetry-only serving deployments must not pay the
        per-prefill measure: without the numerics opt-in no error
        gauge is emitted and no measure program is ever built."""
        from deepspeed_tpu.config.config import ServingConfig
        from deepspeed_tpu.models import make_gpt
        from deepspeed_tpu.serving import ServeEngine
        from deepspeed_tpu.telemetry import (InMemorySink, MetricsRegistry,
                                             RecompileDetector, StepTracer,
                                             Telemetry)

        model, _cfg = make_gpt("tiny", dropout_rate=0.0, max_seq_len=64,
                               dtype=jnp.float32)
        params = model.init(
            {"params": jax.random.PRNGKey(0),
             "dropout": jax.random.PRNGKey(1)},
            {"input_ids": np.zeros((1, 8), np.int32)})["params"]
        reg = MetricsRegistry()
        sink = reg.add_sink(InMemorySink())
        tel = Telemetry(reg, StepTracer(enabled=False),
                        RecompileDetector(enabled=False))
        eng = deepspeed_tpu.init_inference(model, params=params,
                                           dtype=jnp.float32)
        srv = ServeEngine(eng, config=ServingConfig(
            max_batch_size=2, kv_block_size=4, kv_num_blocks=64,
            max_model_len=48, int8_kv_cache=True), telemetry=tel)
        srv.submit([1, 2, 3], max_new_tokens=2)
        srv.run_until_complete()
        assert not srv._measure_kv and not srv._kv_err_jit
        assert not [r for r in sink.rows
                    if r["tag"].startswith("numerics/")]

    def test_fp_kv_emits_nothing(self):
        from deepspeed_tpu.config.config import ServingConfig
        from deepspeed_tpu.models import make_gpt
        from deepspeed_tpu.serving import ServeEngine
        from deepspeed_tpu.telemetry import (InMemorySink, MetricsRegistry,
                                             RecompileDetector, StepTracer,
                                             Telemetry)

        model, _cfg = make_gpt("tiny", dropout_rate=0.0, max_seq_len=64,
                               dtype=jnp.float32)
        params = model.init(
            {"params": jax.random.PRNGKey(0),
             "dropout": jax.random.PRNGKey(1)},
            {"input_ids": np.zeros((1, 8), np.int32)})["params"]
        reg = MetricsRegistry()
        sink = reg.add_sink(InMemorySink())
        tel = Telemetry(reg, StepTracer(enabled=False),
                        RecompileDetector(enabled=False))
        eng = deepspeed_tpu.init_inference(model, params=params,
                                           dtype=jnp.float32)
        srv = ServeEngine(eng, config=ServingConfig(
            max_batch_size=2, kv_block_size=4, kv_num_blocks=64,
            max_model_len=48, int8_kv_cache=False), telemetry=tel)
        srv.submit([1, 2, 3], max_new_tokens=2)
        srv.run_until_complete()
        assert not [r for r in sink.rows
                    if r["tag"].startswith("numerics/")]


# ---------------------------------------------------------------------------
# Fleet grad-norm field
# ---------------------------------------------------------------------------
class TestFleetGradNorm:
    def test_fleet_vector_carries_grad_norm(self, eight_devices, tmp_path):
        engine = _engine({"telemetry": {
            **_tel(tmp_path, numerics={"enabled": True}),
            "fleet": {"enabled": True, "min_window": 1}}})
        batches = random_batches(np.random.default_rng(0), gas=1,
                                 batch_size=16)
        for _ in range(2):
            engine.train_batch(batches)
        mem = engine.telemetry.registry.sinks[0]
        gauge = engine.telemetry.registry.gauge(
            "numerics/global_grad_norm").value
        vals = mem.values("fleet/grad_norm_max")
        assert vals and vals[-1] > 0
        np.testing.assert_allclose(vals[-1], gauge, rtol=1e-6)

    def test_numerics_off_reports_zero(self, eight_devices, tmp_path):
        engine = _engine({"telemetry": {
            **_tel(tmp_path),
            "fleet": {"enabled": True, "min_window": 1}}})
        batches = random_batches(np.random.default_rng(0), gas=1,
                                 batch_size=16)
        for _ in range(2):
            engine.train_batch(batches)
        mem = engine.telemetry.registry.sinks[0]
        vals = mem.values("fleet/grad_norm_max")
        assert vals and vals[-1] == 0.0


# ---------------------------------------------------------------------------
# Satellite: get_global_grad_norm no longer re-jits per call
# ---------------------------------------------------------------------------
class TestGlobalNormNoRetrace:
    def test_single_trace_across_calls(self, eight_devices, tmp_path,
                                       monkeypatch):
        import deepspeed_tpu.runtime.engine as eng_mod
        from deepspeed_tpu.runtime.utils import global_norm

        engine = _engine({"telemetry": _tel(tmp_path)})
        rng = np.random.default_rng(0)
        engine.train_batch(random_batches(rng, gas=1, batch_size=16))
        # After a fused step the engine reports the norm that step
        # returned (ISSUE 24); the jitted norm of the accumulators, which
        # this test is about, serves the forward()/backward() path.
        engine.backward(engine.forward(
            {k: v[0] for k, v in random_batches(rng, gas=1,
                                                batch_size=16).items()}))
        traces = {"n": 0}

        def counted(tree):
            traces["n"] += 1
            return global_norm(tree)

        monkeypatch.setattr(eng_mod, "_GLOBAL_NORM_JIT", jax.jit(counted))
        for _ in range(5):
            engine.get_global_grad_norm()
        # ONE trace for five calls (the old inline jax.jit(global_norm)
        # built a fresh wrapper — and re-traced — per invocation) ...
        assert traces["n"] == 1, traces
        # ... and the recompile detector agrees: one expected compile,
        # zero retraces under the engine.global_norm name.
        rec = engine.telemetry.recompile
        assert rec.compiles("engine.global_norm") == 1
        assert rec.retraces("engine.global_norm") == 0


# ---------------------------------------------------------------------------
# Report tool
# ---------------------------------------------------------------------------
class TestNumericsReport:
    def test_selftest_cli(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "numerics_report.py"),
             "--selftest"],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "selftest ok" in proc.stdout

    def test_renders_engine_written_run_dir(self, eight_devices, tmp_path):
        """End to end: a numerics-on engine writes metrics.jsonl; the
        stdlib report renders per-group rows from it."""
        engine = _engine({"telemetry": _tel(tmp_path,
                                            numerics={"enabled": True},
                                            sinks=("jsonl",))})
        rng = np.random.default_rng(0)
        for _ in range(3):
            engine.train_batch(random_batches(rng, gas=1, batch_size=16))
        engine.telemetry.flush()
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "numerics_report.py"),
             str(tmp_path)],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        for group in engine.numerics.plan.group_names:
            assert group in proc.stdout
        assert "global grad norm" in proc.stdout


# ---------------------------------------------------------------------------
# Bench environment records the block
# ---------------------------------------------------------------------------
class TestBenchEnvironment:
    def test_bench_source_records_numerics_off(self):
        with open(os.path.join(REPO, "bench.py")) as f:
            src = f.read()
        assert '"numerics": "off"' in src
