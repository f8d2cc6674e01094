"""Goodput accounting tests (telemetry/goodput.py; docs/OBSERVABILITY.md
"Goodput accounting"): the accountant's exact wall-clock partition, the
engine hooks (categories, recompile/replay classification, run manifest,
engine/mfu), the shared MFU helper, multi-device HBM aggregation, the
zero-sync disabled contract, tools/goodput_report.py, and the end-to-end
2-attempt acceptance run (FaultPlan SIGTERM → supervisor auto-resume →
one merged cross-attempt report)."""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.profiling import flops_profiler as fp
from deepspeed_tpu.telemetry import InMemorySink, MetricsRegistry
from deepspeed_tpu.telemetry.goodput import (ATTEMPT_START_WALL_ENV,
                                             CATEGORIES, GoodputAccountant,
                                             classify_exit,
                                             finalize_attempt_manifests)

from simple_model import mlp_loss_fn, mlp_params, random_batches

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


class FakeClock:
    """Deterministic monotonic + wall clocks for partition-exactness
    assertions (the real clocks only support tolerance checks)."""

    def __init__(self, t0=100.0, wall0=1000.0):
        self.t = t0
        self.w0 = wall0 - t0

    def mono(self):
        return self.t

    def wall(self):
        return self.w0 + self.t

    def advance(self, dt):
        self.t += dt


def _accountant(tmp_path=None, registry=None, clk=None, env=None):
    clk = clk or FakeClock()
    acc = GoodputAccountant(
        registry=registry, run_dir=str(tmp_path) if tmp_path else None,
        attempt=0, host="testhost", cfg_hash="cafe",
        clock=clk.mono, wall_clock=clk.wall, env=env if env is not None
        else {})
    return acc, clk


# ---------------------------------------------------------------------------
# Accountant unit tests
# ---------------------------------------------------------------------------
class TestAccountant:
    def test_marks_partition_wall_clock_exactly(self):
        acc, clk = _accountant()
        clk.advance(2.0)
        acc.mark_gap()                       # pre-first-step -> init_restore
        clk.advance(0.5)
        acc.mark("data_stall")
        clk.advance(3.0)
        acc.step_mark("productive_step", 1)
        clk.advance(1.0)
        acc.mark_gap()                       # post-first-step -> idle_other
        clk.advance(0.25)                    # pending tail -> idle_other
        t = acc.totals()
        assert t["init_restore"] == pytest.approx(2.0)
        assert t["data_stall"] == pytest.approx(0.5)
        assert t["productive_step"] == pytest.approx(3.0)
        assert t["idle_other"] == pytest.approx(1.25)
        assert t["wall_sec"] == pytest.approx(6.75)
        # the partition is EXACT: categories sum to wall
        assert sum(t[c] for c in CATEGORIES) == pytest.approx(t["wall_sec"])

    def test_measure_carves_out_without_double_count(self):
        acc, clk = _accountant()
        clk.advance(1.0)                     # pending (enclosing phase)
        with acc.measure("rollback_restore"):
            clk.advance(4.0)
        clk.advance(0.5)
        acc.mark("productive_step")          # pending 1.0 + 0.5, not 5.5
        t = acc.totals()
        assert t["rollback_restore"] == pytest.approx(4.0)
        assert t["productive_step"] == pytest.approx(1.5)
        assert sum(t[c] for c in CATEGORIES) == pytest.approx(t["wall_sec"])

    def test_step_stats_feed_mfu_and_exclude_recompile(self):
        acc, clk = _accountant()
        clk.advance(10.0)
        acc.step_mark("recompile", 1)        # compile-inflated: excluded
        for step in (2, 3):
            clk.advance(2.0)
            acc.step_mark("productive_step", step)
        clk.advance(4.0)
        acc.step_mark("rollback_replay", 3)  # replay counts as a step time
        assert acc.mean_step_time() == pytest.approx(8.0 / 3)
        assert acc.mfu() is None             # no flops yet
        acc.set_flops(16e12, n_chips=2, peak_tflops_per_chip=100.0)
        want = fp.mfu(16e12, 8.0 / 3, n_chips=2, peak_tflops_per_chip=100.0)
        assert acc.mfu() == pytest.approx(want)
        assert not acc.wants_flops

    def test_spawn_env_backdates_to_init_restore(self):
        clk = FakeClock()
        acc = GoodputAccountant(
            run_dir=None, attempt=0, host="h", clock=clk.mono,
            wall_clock=clk.wall,
            env={ATTEMPT_START_WALL_ENV: repr(clk.wall() - 7.5)})
        t = acc.totals()
        assert t["init_restore"] == pytest.approx(7.5)
        assert t["wall_sec"] == pytest.approx(7.5)
        assert acc.start_wall == pytest.approx(clk.wall() - 7.5)

    def test_emit_tags_and_attempt_label(self):
        reg = MetricsRegistry()
        mem = reg.add_sink(InMemorySink())
        acc, clk = _accountant(registry=reg)
        clk.advance(1.0)
        acc.step_mark("productive_step", 1)
        acc.note_aux("pipe_bubble_sec", 0.25)
        acc.emit(step=1)
        tags = mem.tags()
        for c in CATEGORIES:
            assert f"goodput/{c}_sec" in tags
        assert {"goodput/wall_sec", "goodput/goodput_frac",
                "goodput/steps_committed",
                "goodput/pipe_bubble_sec"} <= tags
        row = next(r for r in mem.rows if r["tag"] == "goodput/wall_sec")
        assert row["attempt"] == 0
        assert mem.values("goodput/productive_step_sec")[-1] == \
            pytest.approx(1.0)
        assert mem.values("goodput/goodput_frac")[-1] == pytest.approx(1.0)

    def test_manifest_write_refresh_finalize(self, tmp_path):
        acc, clk = _accountant(tmp_path=tmp_path)
        path = acc.manifest_path()
        assert os.path.exists(path)          # written at construction
        clk.advance(2.0)
        acc.step_mark("productive_step", 5)
        acc.write_manifest()
        doc = json.load(open(path))
        assert doc["format"] == 1
        assert doc["attempt"] == 0 and doc["host"] == "testhost"
        assert doc["config_hash"] == "cafe"
        assert doc["end_wall"] is None and doc["exit_rc"] is None
        assert doc["steps_committed"] == 5 and doc["first_step"] == 5
        assert doc["categories"]["productive_step"] == pytest.approx(2.0)
        assert sum(doc["categories"].values()) == \
            pytest.approx(doc["wall_sec"])
        clk.advance(1.0)
        acc.finalize()
        doc = json.load(open(path))
        assert doc["end_wall"] is not None
        assert doc["end_monotonic"] is not None
        acc.finalize()                       # idempotent

    def test_classify_exit(self):
        assert classify_exit(0) == "clean"
        assert classify_exit(113, (113,)) == "watchdog"
        assert classify_exit(-15) == "preemption"
        assert classify_exit(143) == "preemption"
        assert classify_exit(1) == "crash"

    def test_supervisor_finalize_stamps_and_stubs(self, tmp_path):
        acc, clk = _accountant(tmp_path=tmp_path)
        clk.advance(3.0)
        acc.write_manifest()
        n = finalize_attempt_manifests(str(tmp_path), 0, -15, "preemption",
                                       1000.0, 1070.0)
        assert n == 1
        doc = json.load(open(acc.manifest_path()))
        assert doc["exit_rc"] == -15
        assert doc["restart_cause"] == "preemption"
        assert doc["end_wall"] == 1070.0
        # the supervisor-observed lifetime supersedes the stale wall_sec
        assert doc["wall_sec"] >= 3.0
        # a child that died before engine init leaves a stub
        n = finalize_attempt_manifests(str(tmp_path), 7, 1, "crash",
                                       2000.0, 2004.0)
        assert n == 1
        stub = json.load(open(tmp_path / "run_manifest.a0007.unknown.json"))
        assert stub["exit_rc"] == 1 and stub["wall_sec"] == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# MFU helper (flops_profiler satellites)
# ---------------------------------------------------------------------------
class TestMfuHelper:
    def test_peak_table_and_dtype_defaults(self):
        assert fp.peak_tflops("TPU v4", "bfloat16") == 275.0
        assert fp.peak_tflops("TPU v4", "float32") == 137.5
        assert fp.peak_tflops("TPU v5 lite", "bf16") == 197.0
        assert fp.peak_tflops("TPU v6 lite") == 918.0
        # fp16 rides the bf16 MXU path
        assert fp.peak_tflops("TPU v4", "float16") == 275.0
        # unknown kind: no peak at all (never another chip's)
        assert fp.peak_tflops("", "bfloat16") is None
        assert fp.peak_tflops(None, "fp32") is None
        assert fp.peak_tflops("cpu") is None
        assert fp.peak_hbm_gbps("cpu") is None
        assert fp.peak_hbm_gbps("TPU v5 lite") == 819.0

    def test_mfu_math_and_degenerate_inputs(self):
        # 100 TFLOP over 1 s on 1 chip with 200 TFLOP/s peak = 50%
        assert fp.mfu(100e12, 1.0, n_chips=1,
                      peak_tflops_per_chip=200.0) == pytest.approx(0.5)
        # chip count divides
        assert fp.mfu(100e12, 1.0, n_chips=4,
                      peak_tflops_per_chip=200.0) == pytest.approx(0.125)
        # device-kind lookup path
        assert fp.mfu(275e12, 1.0, n_chips=1, device_kind="TPU v4") == \
            pytest.approx(1.0)
        assert fp.mfu(None, 1.0, device_kind="TPU v4") == 0.0
        assert fp.mfu(0.0, 1.0, device_kind="TPU v4") == 0.0
        assert fp.mfu(1e12, 0.0, device_kind="TPU v4") == 0.0
        # no peak to divide by -> no figure, whatever the inputs
        assert fp.mfu(1e12, 1.0) is None
        assert fp.mfu(1e12, 1.0, device_kind="cpu") is None

    def test_profiler_method_uses_last_profile(self):
        prof = fp.FlopsProfiler()
        assert prof.mfu(1.0, peak_tflops_per_chip=100.0) == 0.0  # no profile

        def f(x):
            return (x @ x).sum()

        x = np.zeros((64, 64), np.float32)
        prof.profile_callable(f, x, measure=False, detailed=False)
        flops = prof.last["flops"]
        if flops > 0:  # CPU cost analysis may not report flops
            want = fp.mfu(flops, 2.0, n_chips=2, peak_tflops_per_chip=50.0)
            assert prof.mfu(2.0, peak_tflops_per_chip=50.0,
                            n_chips=2) == pytest.approx(want)


# ---------------------------------------------------------------------------
# Engine integration
# ---------------------------------------------------------------------------
def _engine(config_extra=None, world=8):
    engine, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=mlp_loss_fn, params=mlp_params(),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "zero_optimization": {"stage": 1},
                **(config_extra or {})},
        mesh=build_mesh(data=world))
    return engine


def _tel_cfg(tmp_path, goodput=True, sinks=("memory",)):
    return {"telemetry": {"enabled": True, "dir": str(tmp_path),
                          "trace": {"enabled": False},
                          "metrics": {"sinks": list(sinks)},
                          "goodput": goodput}}


class TestEngineGoodput:
    def test_fused_loop_categories_manifest_and_mfu(self, eight_devices,
                                                    tmp_path, monkeypatch):
        # give the test mesh's device kind a peak so engine/mfu is emitted
        # (the next test pins that without one the gauge is absent)
        monkeypatch.setitem(fp.TPU_PEAK_TFLOPS, jax.devices()[0].device_kind,
                            {"bfloat16": 1.0, "float32": 0.5})
        engine = _engine(_tel_cfg(tmp_path) | {"steps_per_print": 2})
        rng = np.random.default_rng(0)
        batches = random_batches(rng, gas=1, batch_size=16)
        for _ in range(5):
            engine.train_batch(batches)
        g = engine.goodput
        assert g is not None
        t = g.totals()
        assert t["recompile"] > 0            # first step's trace+compile
        assert t["productive_step"] > 0
        assert t["data_stall"] > 0
        # exact partition: explicit categories sum to wall
        assert sum(t[c] for c in CATEGORIES) == \
            pytest.approx(t["wall_sec"], rel=1e-6)
        # manifest refreshed at the steps_per_print cadence
        doc = json.load(open(g.manifest_path()))
        assert doc["steps_committed"] >= 4
        assert doc["first_step"] == 1
        # engine/mfu flowed through the ONE shared helper
        mem = engine.telemetry.registry.sinks[0]
        assert isinstance(mem, InMemorySink)
        if g._flops_per_step is not None:
            want = fp.mfu(g._flops_per_step, g.mean_step_time(),
                          n_chips=engine.mesh.size,
                          peak_tflops_per_chip=g._peak_tflops)
            assert g.mfu() == pytest.approx(want)
            assert mem.values("engine/mfu")[-1] == pytest.approx(want)
        assert mem.values("goodput/steps_committed")[-1] == 5
        assert not g.wants_flops             # analysed exactly once

    def test_no_mfu_gauge_on_a_device_without_a_peak(self, eight_devices,
                                                     tmp_path):
        """The CPU mesh's device kind is not in the peak table: the engine
        still records the step's FLOPs, but engine/mfu is absent — not a
        number computed against some other chip's peak."""
        engine = _engine(_tel_cfg(tmp_path))
        rng = np.random.default_rng(0)
        batches = random_batches(rng, gas=1, batch_size=16)
        for _ in range(3):
            engine.train_batch(batches)
        g = engine.goodput
        assert not g.wants_flops and g._peak_tflops is None
        assert g.mfu() is None
        mem = engine.telemetry.registry.sinks[0]
        assert mem.values("engine/mfu") == []

    def test_reference_loop_marks(self, eight_devices, tmp_path):
        from simple_model import random_batch
        engine = _engine(_tel_cfg(tmp_path))
        rng = np.random.default_rng(0)
        for _ in range(2):
            loss = engine.forward(random_batch(rng, batch_size=16))
            engine.backward(loss)
            engine.step()
        t = engine.goodput.totals()
        assert t["recompile"] > 0
        assert t["productive_step"] > 0
        assert sum(t[c] for c in CATEGORIES) == \
            pytest.approx(t["wall_sec"], rel=1e-6)

    def test_replay_classification_after_rollback_rewind(self, eight_devices,
                                                         tmp_path):
        engine = _engine(_tel_cfg(tmp_path))
        rng = np.random.default_rng(0)
        batches = random_batches(rng, gas=1, batch_size=16)
        for _ in range(3):
            engine.train_batch(batches)
        g = engine.goodput
        assert g.totals()["rollback_replay"] == 0.0
        # simulate what a guardrails rollback does: rewind the committed
        # step counter below the high-water mark
        engine._goodput_replay_until = engine.global_steps
        engine.global_steps -= 2
        engine.train_batch(batches)          # commits step 2 <= hwm 3
        engine.train_batch(batches)          # commits step 3 <= hwm 3
        t = g.totals()
        assert t["rollback_replay"] > 0.0
        engine.train_batch(batches)          # step 4: productive again
        assert g.totals()["rollback_replay"] == t["rollback_replay"]

    def test_ckpt_snapshot_attributed(self, eight_devices, tmp_path):
        engine = _engine(_tel_cfg(tmp_path) | {
            "resilience": {"enabled": True,
                           "checkpoint": {"dir": str(tmp_path / "ckpt"),
                                          "interval": 1,
                                          "async": False}}})
        rng = np.random.default_rng(0)
        batches = random_batches(rng, gas=1, batch_size=16)
        for _ in range(2):
            engine.train_batch(batches)
        t = engine.goodput.totals()
        assert t["ckpt_snapshot"] > 0.0
        assert t["ckpt_write_stall"] > 0.0   # sync writes stall the step
        assert sum(t[c] for c in CATEGORIES) == \
            pytest.approx(t["wall_sec"], rel=1e-6)

    def test_auto_resume_attributed_to_init_restore(self, eight_devices,
                                                    tmp_path):
        res = {"resilience": {"enabled": True,
                              "checkpoint": {"dir": str(tmp_path / "ckpt"),
                                             "interval": 1,
                                             "async": False}}}
        engine = _engine(_tel_cfg(tmp_path / "t1") | res)
        rng = np.random.default_rng(0)
        batches = random_batches(rng, gas=1, batch_size=16)
        for _ in range(2):
            engine.train_batch(batches)
        engine2 = _engine(_tel_cfg(tmp_path / "t2") | res)
        before = engine2.goodput.totals()["init_restore"]
        path, _ = engine2.auto_resume()
        assert path is not None
        assert engine2.goodput.totals()["init_restore"] > before

    # -- disabled-path contract (the PR 2/3 zero-sync gate, extended) ----
    def test_telemetry_off_means_goodput_none(self):
        engine = _engine()
        assert engine.goodput is None

    def test_goodput_flag_off_means_none_and_no_manifest(self, eight_devices,
                                                         tmp_path):
        engine = _engine(_tel_cfg(tmp_path, goodput=False))
        assert engine.goodput is None
        rng = np.random.default_rng(0)
        engine.train_batch(random_batches(rng, gas=1, batch_size=16))
        assert not [f for f in os.listdir(tmp_path)
                    if f.startswith("run_manifest.")]
        mem = engine.telemetry.registry.sinks[0]
        assert not any(t.startswith("goodput/") for t in mem.tags())

    @pytest.mark.parametrize("goodput_on", [False, True])
    def test_goodput_adds_zero_device_syncs(self, eight_devices, tmp_path,
                                            monkeypatch, goodput_on):
        """The accountant is pure host clock reads: with the tracer off,
        the step path performs ZERO device syncs whether goodput is on or
        off — the accountant never adds one."""
        engine = _engine(_tel_cfg(tmp_path, goodput=goodput_on))
        rng = np.random.default_rng(0)
        batches = random_batches(rng, gas=1, batch_size=16)
        for _ in range(2):
            engine.train_batch(batches)      # compile + flops analysis
        from deepspeed_tpu.utils import timer as timer_mod
        calls = {"n": 0}
        monkeypatch.setattr(timer_mod, "_device_synchronize",
                            lambda: calls.__setitem__("n", calls["n"] + 1))
        for _ in range(10):
            engine.train_batch(batches)
        assert calls["n"] == 0
        assert (engine.goodput is not None) == goodput_on


class TestHbmMultiDevice:
    def test_aggregates_across_local_devices(self, eight_devices, tmp_path,
                                             monkeypatch):
        """The satellite fix: peak = max over devices, in_use = sum, rows
        tagged with the reporting device count (the old code read only
        jax.local_devices()[0] and under-reported multi-chip hosts)."""
        engine = _engine(_tel_cfg(tmp_path))

        class FakeDev:
            def __init__(self, peak, use):
                self._stats = {"peak_bytes_in_use": peak,
                               "bytes_in_use": use}

            def memory_stats(self):
                return self._stats

        class Broken:
            def memory_stats(self):
                raise RuntimeError("no stats on this backend")

        monkeypatch.setattr(jax, "local_devices",
                            lambda: [FakeDev(100, 10), FakeDev(300, 20),
                                     FakeDev(200, 30), Broken()])
        engine._emit_step_telemetry()
        mem = engine.telemetry.registry.sinks[0]
        peak = next(r for r in mem.rows
                    if r["tag"] == "engine/hbm_peak_bytes")
        use = next(r for r in mem.rows
                   if r["tag"] == "engine/hbm_bytes_in_use")
        assert peak["value"] == 300.0 and peak["devices"] == 3
        assert use["value"] == 60.0 and use["devices"] == 3


# ---------------------------------------------------------------------------
# tools/goodput_report.py
# ---------------------------------------------------------------------------
def _load_report_mod():
    path = os.path.join(REPO, "tools", "goodput_report.py")
    spec = importlib.util.spec_from_file_location("goodput_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestGoodputReport:
    def test_selftest_cli(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "goodput_report.py"), "--selftest"],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        assert "selftest ok" in proc.stdout

    def test_merges_engine_written_run_dir(self, eight_devices, tmp_path):
        """A single-attempt dir produced by the REAL engine parses and
        balances."""
        engine = _engine(_tel_cfg(tmp_path, sinks=("jsonl",))
                         | {"steps_per_print": 1})
        rng = np.random.default_rng(0)
        batches = random_batches(rng, gas=1, batch_size=16)
        for _ in range(4):
            engine.train_batch(batches)
        engine.telemetry.flush()
        engine.goodput.finalize()
        mod = _load_report_mod()
        report = mod.merge_run(str(tmp_path))
        assert report["n_attempts"] == 1
        assert report["steps_committed"] == 4
        assert 0.0 < report["goodput_frac"] < 1.0
        assert report["attributed_frac"] > 0.95
        assert report["categories"]["recompile"] > 0
        text = mod.render(report)
        assert "productive_step" in text and "restarts:" in text


# ---------------------------------------------------------------------------
# End to end: SIGTERM mid-run -> supervisor restart -> ONE merged report
# ---------------------------------------------------------------------------
_TRAIN_SCRIPT = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, sys.argv[3])
    import numpy as np
    from deepspeed_tpu import initialize
    from deepspeed_tpu.parallel.mesh import build_mesh
    from simple_model import mlp_params, mlp_loss_fn, random_batches

    run_dir, total_steps = sys.argv[1], int(sys.argv[2])
    engine, _, _, _ = initialize(
        loss_fn=mlp_loss_fn, params=mlp_params(),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 1},
            "steps_per_print": 1,
            "telemetry": {"enabled": True, "dir": run_dir,
                          "trace": {"enabled": False},
                          "metrics": {"sinks": ["jsonl"]}},
            "resilience": {"enabled": True,
                           "checkpoint": {"dir": os.path.join(run_dir,
                                                              "ckpt"),
                                          "interval": 2, "async": False,
                                          "backoff_seconds": 0.01}},
        },
        mesh=build_mesh(data=8), rng_seed=0)
    engine.auto_resume()
    rng = np.random.default_rng(7)
    stream = [random_batches(rng, 1, batch_size=16)
              for _ in range(total_steps)]
    for i in range(engine.global_steps, total_steps):
        engine.train_batch(stream[i])
    engine.ckpt_manager.close()
    engine.telemetry.flush()
    engine.goodput.finalize()
""")


def test_e2e_sigterm_resume_merged_goodput_report(eight_devices, tmp_path):
    """The acceptance gate: a FaultPlan SIGTERM after step 3 kills attempt
    0, the supervisor restarts it, attempt 1 resumes from the step-2
    checkpoint and finishes; tools/goodput_report.py then merges both
    attempts into ONE report where per-category seconds sum to run
    wall-clock within 5%, goodput < 1 with nonzero restart +
    init_restore + cross-attempt replay attribution, and the reported MFU
    is the FlopsProfiler-derived value the attempts emitted."""
    from deepspeed_tpu.resilience import FAULT_PLAN_ENV, Supervisor

    run_dir = tmp_path / "run"
    run_dir.mkdir()
    total = 7
    sup = Supervisor(
        [sys.executable, "-c", _TRAIN_SCRIPT, str(run_dir), str(total),
         TESTS_DIR],
        max_restarts=2, backoff=0.05, run_dir=str(run_dir),
        env={"JAX_PLATFORMS": "cpu",
             FAULT_PLAN_ENV: json.dumps({"preempt_at_step": 3})})
    assert sup.run() == 0
    assert sup.restarts == 1

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "goodput_report.py"),
         str(run_dir), "--json"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    report = json.loads(proc.stdout)

    assert report["n_attempts"] == 2 and report["n_restarts"] == 1
    a0, a1 = report["attempts"]
    assert a0["restart_cause"] == "preemption" and a0["exit_rc"] != 0
    assert a1["restart_cause"] == "clean" and a1["exit_rc"] == 0
    assert a0["steps_committed"] == 3
    assert a1["steps_committed"] == total
    # attempt 1 resumed from the step-2 checkpoint below attempt 0's
    # high-water mark: the merge reclassifies the re-earned step as replay
    assert a1["first_step"] == 3
    assert report["categories"]["rollback_replay"] > 0

    # per-category seconds sum to total wall-clock within 5%
    total_attr = (sum(report["categories"].values())
                  + report["restart_sec"] + report["unaccounted_sec"])
    assert abs(total_attr - report["wall_sec"]) <= 0.05 * report["wall_sec"]
    assert report["attributed_frac"] >= 0.95

    # goodput < 1 with nonzero restart / init_restore attribution
    assert 0.0 < report["goodput_frac"] < 1.0
    assert report["restart_sec"] > 0.0
    assert report["categories"]["init_restore"] > 0.0
    assert report["categories"]["productive_step"] > 0.0

    # reported MFU is the FlopsProfiler-derived value the attempts emitted
    rows = [json.loads(l)
            for l in open(run_dir / "metrics.jsonl") if l.strip()]
    mfus = {}
    for r in rows:
        if r["tag"] == "engine/mfu":
            mfus[int(r.get("attempt", 0))] = r["value"]
    if mfus:  # CPU cost analysis reported flops
        assert report["mfu"] is not None
        assert (min(mfus.values()) - 1e-12 <= report["mfu"]
                <= max(mfus.values()) + 1e-12)
