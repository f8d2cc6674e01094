"""Telemetry subsystem tests (telemetry/; docs/OBSERVABILITY.md): registry
sinks + tags + histogram percentiles, Chrome trace-event schema, the
recompile detector's compile/hit/retrace accounting, engine span emission
(backward + dataloader included), the zero-sync contract of disabled
telemetry, and tools/trace_report.py."""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.telemetry import (InMemorySink, JSONLSink, MetricsRegistry,
                                     RECOMPILE_COUNTER, RecompileDetector,
                                     StepTracer, build_telemetry)

from simple_model import mlp_loss_fn, mlp_params, random_batch, random_batches

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _base_config(**extra):
    cfg = {"train_micro_batch_size_per_gpu": 2,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "zero_optimization": {"stage": 0}}
    cfg.update(extra)
    return cfg


def _engine(config_extra=None, world=8):
    engine, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=mlp_loss_fn, params=mlp_params(),
        config=_base_config(**(config_extra or {})),
        mesh=build_mesh(data=world))
    return engine


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_jsonl_round_trip_with_tags(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        reg = MetricsRegistry([JSONLSink(path)])
        reg.counter("requests").inc(step=1, route="train")
        reg.counter("requests").inc(2, step=2, route="eval")
        reg.gauge("hbm").set(123.0, step=2, device=0)
        reg.histogram("lat").observe(0.5, step=3)
        reg.flush()
        rows = [json.loads(l) for l in open(path)]
        by_tag = {}
        for r in rows:
            by_tag.setdefault(r["tag"], []).append(r)
        # counter rows carry the RUNNING TOTAL and per-call tags
        assert [r["value"] for r in by_tag["requests"]] == [1.0, 3.0]
        assert by_tag["requests"][0]["route"] == "train"
        assert by_tag["requests"][1]["route"] == "eval"
        assert by_tag["requests"][0]["kind"] == "counter"
        assert by_tag["hbm"][0] == {"tag": "hbm", "value": 123.0, "step": 2,
                                    "kind": "gauge", "device": 0}
        assert by_tag["lat"][0]["kind"] == "histogram"

    def test_histogram_percentiles(self):
        reg = MetricsRegistry([InMemorySink()])
        h = reg.histogram("t")
        for v in range(1, 101):
            h.observe(float(v))
        assert h.percentile(50) == pytest.approx(50.5)
        assert h.percentile(0) == 1.0
        assert h.percentile(100) == 100.0
        assert h.percentile(99) == pytest.approx(99.01)
        p50, p99 = h.percentiles((50, 99))
        assert (p50, p99) == (pytest.approx(50.5), pytest.approx(99.01))
        assert h.count == 100

    def test_in_memory_sink_and_default_step(self):
        reg = MetricsRegistry()
        mem = reg.add_sink(InMemorySink())
        reg.set_step(7)
        reg.gauge("g").set(1.0)
        assert mem.rows == [{"kind": "gauge", "tag": "g", "value": 1.0,
                             "step": 7}]

    def test_no_sinks_is_noop_and_broken_sink_is_contained(self):
        reg = MetricsRegistry()
        reg.counter("c").inc()  # no sinks: must not raise

        class Broken(InMemorySink):
            def emit(self, *a, **k):
                raise RuntimeError("boom")

        reg.add_sink(Broken())
        reg.counter("c").inc()  # contained, not raised

    def test_monitor_compat_add_scalar(self):
        reg = MetricsRegistry()
        mem = reg.add_sink(InMemorySink())
        reg.add_scalar("Train/Samples/train_loss", 0.5, 3)
        assert mem.rows[0]["tag"] == "Train/Samples/train_loss"
        assert mem.rows[0]["step"] == 3


# ---------------------------------------------------------------------------
# Step tracer — Chrome trace-event schema
# ---------------------------------------------------------------------------
class TestTracer:
    def test_chrome_trace_schema(self, tmp_path):
        path = str(tmp_path / "trace.json")
        tr = StepTracer(path=path, sync_spans=False)
        with tr.span("outer", step=1):
            with tr.span("inner"):
                pass
        tr.instant("marker", fn="f")
        tr.counter("recompiles", 2)
        tr.save()
        doc = json.load(open(path))
        assert isinstance(doc["traceEvents"], list)
        assert doc["displayTimeUnit"] == "ms"
        phases = {}
        for ev in doc["traceEvents"]:
            assert isinstance(ev["name"], str)
            assert ev["ph"] in ("X", "i", "C", "M")
            assert isinstance(ev["pid"], int)
            assert isinstance(ev["tid"], int)
            if ev["ph"] != "M":
                assert isinstance(ev["ts"], (int, float))
            if ev["ph"] == "X":
                assert ev["dur"] >= 0
            if ev["ph"] == "i":
                assert ev["s"] == "t"
            phases.setdefault(ev["ph"], []).append(ev)
        assert {e["name"] for e in phases["X"]} == {"outer", "inner"}
        outer = next(e for e in phases["X"] if e["name"] == "outer")
        inner = next(e for e in phases["X"] if e["name"] == "inner")
        # nesting: inner is contained within outer
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
        assert outer["args"] == {"step": 1}
        assert phases["C"][0]["args"] == {"value": 2.0}

    def test_disabled_tracer_is_noop(self, tmp_path):
        tr = StepTracer(path=None)
        with tr.span("x") as sp:
            pass
        assert sp.duration == 0.0
        assert tr.save() is None
        assert tr.events == []

    def test_bounded_ring_and_dirty_skip(self, tmp_path):
        path = str(tmp_path / "t.json")
        tr = StepTracer(path=path, sync_spans=False, max_events=8)
        for i in range(20):
            with tr.span(f"s{i}"):
                pass
        assert len(tr.events) == 8          # oldest evicted, RAM bounded
        assert tr.dropped_events == 13      # 20 spans + 1 meta - 8 kept
        tr.save()
        doc = json.load(open(path))
        assert doc["metadata"]["dropped_events"] == 13
        assert {e["name"] for e in doc["traceEvents"]} == {
            f"s{i}" for i in range(12, 20)}  # the recent window survives
        # no new events since last save: save() must not rewrite
        before = os.path.getmtime(path)
        os.utime(path, (before - 100, before - 100))
        tr.save()
        assert os.path.getmtime(path) == before - 100

    def test_span_handle_duration(self, tmp_path):
        tr = StepTracer(path=str(tmp_path / "t.json"), sync_spans=False)
        with tr.span("s") as sp:
            pass
        assert sp.duration >= 0.0

    def test_sync_gating(self, monkeypatch, tmp_path):
        from deepspeed_tpu.utils import timer as timer_mod
        calls = {"n": 0}
        monkeypatch.setattr(timer_mod, "_device_synchronize",
                            lambda: calls.__setitem__("n", calls["n"] + 1))
        # disabled tracer: zero syncs even with sync_spans requested
        tr = StepTracer(path=None, sync_spans=True)
        with tr.span("a"):
            pass
        assert calls["n"] == 0
        # enabled + sync_spans: a barrier on each span boundary
        tr = StepTracer(path=str(tmp_path / "t.json"), sync_spans=True)
        with tr.span("a"):
            pass
        assert calls["n"] == 2
        # enabled + sync off: still zero
        calls["n"] = 0
        tr = StepTracer(path=str(tmp_path / "t2.json"), sync_spans=False)
        with tr.span("a"):
            pass
        assert calls["n"] == 0


# ---------------------------------------------------------------------------
# Recompile detector
# ---------------------------------------------------------------------------
class TestRecompileDetector:
    def _batch(self, bs=4, dtype=np.float32):
        return {"x": np.zeros((bs, 8), dtype)}

    @staticmethod
    def _capture_warnings(monkeypatch):
        """The deepspeed_tpu logger doesn't propagate to root (caplog can't
        see it) — intercept warning() on the recompile module directly."""
        from deepspeed_tpu.telemetry import recompile as rc_mod
        msgs = []
        monkeypatch.setattr(
            rc_mod.logger, "warning",
            lambda fmt, *a, **k: msgs.append(fmt % a if a else fmt))
        return msgs

    def test_steady_state_is_silent(self, monkeypatch):
        msgs = self._capture_warnings(monkeypatch)
        det = RecompileDetector()
        assert det.check("step", self._batch()) == "compile"
        for _ in range(5):
            assert det.check("step", self._batch()) == "hit"
        assert not msgs
        assert det.stats["step"] == {"compiles": 1, "retraces": 0}

    def test_shape_change_fires(self, monkeypatch):
        msgs = self._capture_warnings(monkeypatch)
        reg = MetricsRegistry()
        mem = reg.add_sink(InMemorySink())
        tr = StepTracer(enabled=True, sync_spans=False)
        det = RecompileDetector(registry=reg, tracer=tr)
        det.check("step", self._batch(bs=4))
        assert det.check("step", self._batch(bs=3), step=7) == "retrace"
        assert msgs and "RECOMPILATION" in msgs[0] and "step" in msgs[0]
        assert "(4, 8)" in msgs[0] and "(3, 8)" in msgs[0]  # names the leaf
        assert det.stats["step"] == {"compiles": 2, "retraces": 1}
        assert mem.values(RECOMPILE_COUNTER) == [1.0]
        assert any(e["name"] == "recompile" for e in tr.events)

    def test_dtype_change_fires(self):
        det = RecompileDetector(warn=False)
        det.check("step", self._batch())
        assert det.check("step", self._batch(dtype=np.float64)) == "retrace"

    def test_revisited_signature_is_a_hit(self):
        # jit keeps old entries in its cache: bouncing between two shapes
        # retraces once per NEW shape, not per switch
        det = RecompileDetector(warn=False)
        det.check("step", self._batch(bs=4))
        assert det.check("step", self._batch(bs=3)) == "retrace"
        assert det.check("step", self._batch(bs=4)) == "hit"
        assert det.check("step", self._batch(bs=3)) == "hit"
        assert det.retraces("step") == 1

    def test_disabled_detector(self):
        det = RecompileDetector(enabled=False)
        assert det.check("step", self._batch()) == "hit"
        assert det.check("step", self._batch(bs=1)) == "hit"
        assert det.stats == {}

    def test_static_string_keys_by_value(self):
        det = RecompileDetector(warn=False)
        det.check("gen", {"static": "max_new_tokens=4"})
        assert det.check("gen", {"static": "max_new_tokens=8"}) == "retrace"


# ---------------------------------------------------------------------------
# Engine integration — the acceptance-criteria run
# ---------------------------------------------------------------------------
class TestEngineTelemetry:
    def _gpt_engine(self, tmp_path, seq=16):
        from deepspeed_tpu.models import make_gpt
        model, cfg = make_gpt("tiny", num_layers=2, dropout_rate=0.0,
                              dtype=jnp.float32)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, (8, seq), dtype=np.int32)
        params = model.init({"params": jax.random.PRNGKey(0),
                             "dropout": jax.random.PRNGKey(1)},
                            {"input_ids": ids})["params"]
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, params=params, mesh=build_mesh(data=8),
            config={
                "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "telemetry": {"enabled": True, "dir": str(tmp_path)},
                "resilience": {"enabled": True, "checkpoint": {
                    "dir": str(tmp_path / "ckpt"), "interval": 2}},
            })
        return engine, cfg

    def test_gpt_run_trace_spans_recompiles_and_report(self, eight_devices,
                                                       tmp_path):
        """The ISSUE acceptance run: 2-layer GPT on CPU, telemetry on —
        >= 6 distinct span names (incl. backward and dataloader), exactly
        the expected first-step compile, a flagged injected retrace, and a
        trace_report breakdown."""
        engine, cfg = self._gpt_engine(tmp_path)
        rng = np.random.default_rng(1)

        def batch(bs=8, seq=16):
            return {"input_ids": rng.integers(0, cfg.vocab_size, (bs, seq),
                                              dtype=np.int32)}

        # reference-style loop: forward / backward / step
        for _ in range(3):
            loss = engine.forward(batch())
            engine.backward(loss)
            engine.step()
        # fused loop
        for _ in range(2):
            engine.train_batch({"input_ids": batch()["input_ids"][None]})
        det = engine.telemetry.recompile
        assert det.stats["engine.micro_step"] == {"compiles": 1,
                                                  "retraces": 0}
        assert det.stats["engine.train_step"] == {"compiles": 1,
                                                  "retraces": 0}
        # injected shape change: the detector must flag the retrace
        engine.train_batch(
            {"input_ids": batch(bs=8, seq=8)["input_ids"][None]})
        assert det.stats["engine.train_step"] == {"compiles": 2,
                                                  "retraces": 1}
        if engine.ckpt_manager is not None:
            engine.ckpt_manager.wait()
        engine.telemetry.flush()

        trace_path = tmp_path / "trace.json"
        doc = json.load(open(trace_path))
        names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
        assert {"dataloader", "forward", "backward", "optimizer_step",
                "train_step", "ckpt_snapshot", "ckpt_write"} <= names
        assert len(names) >= 6
        # retrace marker landed in the trace too
        assert any(e["name"] == "recompile" for e in doc["traceEvents"]
                   if e.get("ph") == "i")

        # metrics jsonl got the registry fan-out
        rows = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
        tags = {r["tag"] for r in rows}
        assert "Train/Samples/train_loss" in tags
        assert RECOMPILE_COUNTER in tags
        assert "ckpt/write_latency_sec" in tags

        # trace_report renders a breakdown naming the spans
        report = _load_trace_report()
        summary = report.summarize(report.load_events(str(trace_path)))
        text = report.render(summary)
        assert "dataloader" in text and "ckpt_write" in text
        span_names = {r["name"] for r in summary["spans"]}
        assert len(span_names) >= 6

    def test_disabled_telemetry_zero_syncs(self, monkeypatch):
        """Acceptance: a 20-step loop with telemetry disabled performs ZERO
        telemetry-originated block_until_ready calls."""
        engine = _engine()  # default config: telemetry off, breakdown off
        rng = np.random.default_rng(0)
        batches = random_batches(rng, gas=1, batch_size=16)
        engine.train_batch(batches)  # compile outside the counted window

        from deepspeed_tpu.utils import timer as timer_mod
        calls = {"n": 0}
        monkeypatch.setattr(timer_mod, "_device_synchronize",
                            lambda: calls.__setitem__("n", calls["n"] + 1))
        for _ in range(20):
            engine.train_batch(batches)
        assert calls["n"] == 0
        assert engine.telemetry.enabled is False
        assert engine.telemetry.tracer.enabled is False
        # goodput rides telemetry: off => None facade, zero added hooks
        # (tests/test_goodput.py asserts the enabled path adds zero syncs)
        assert engine.goodput is None

    def test_enabled_telemetry_does_sync(self, monkeypatch, tmp_path):
        engine = _engine({"telemetry": {"enabled": True,
                                        "dir": str(tmp_path)}})
        rng = np.random.default_rng(0)
        batches = random_batches(rng, gas=1, batch_size=16)
        engine.train_batch(batches)
        from deepspeed_tpu.utils import timer as timer_mod
        calls = {"n": 0}
        monkeypatch.setattr(timer_mod, "_device_synchronize",
                            lambda: calls.__setitem__("n", calls["n"] + 1))
        engine.train_batch(batches)
        assert calls["n"] > 0  # sync'd span boundaries

    def test_wall_clock_breakdown_records_new_timers(self):
        engine = _engine({"wall_clock_breakdown": True})
        rng = np.random.default_rng(0)
        batch = random_batch(rng, batch_size=16)
        loss = engine.forward(batch)
        engine.backward(loss)
        engine.step()
        for name in ("dataloader", "forward", "backward", "step"):
            assert engine.timers.has_timer(name), name
            assert engine.timers(name).count >= 1, name

    def test_legacy_tensorboard_block_rides_registry(self, tmp_path):
        """tensorboard-only config (telemetry absent): scalars still land
        via the registry's tensorboard sink — the unified facade."""
        engine = _engine({"tensorboard": {"enabled": True,
                                          "output_path": str(tmp_path),
                                          "job_name": "job1"}})
        rng = np.random.default_rng(0)
        for _ in range(2):
            engine.train_batch(random_batches(rng, gas=1, batch_size=16))
        assert engine.telemetry.enabled is False
        assert engine.telemetry.registry.sinks  # the tensorboard sink
        files = os.listdir(tmp_path / "job1")
        assert files
        if "scalars.jsonl" in files:
            rows = [json.loads(l)
                    for l in open(tmp_path / "job1" / "scalars.jsonl")]
            assert "Train/Samples/train_loss" in {r["tag"] for r in rows}


class TestPipelineTelemetry:
    def test_bubble_gauges(self, eight_devices, tmp_path):
        from deepspeed_tpu.config.config import DeepSpeedTPUConfig
        from deepspeed_tpu.models.gpt import GPTConfig
        from deepspeed_tpu.parallel.pipe import PipelineEngine, gpt_pipe_model

        cfg = GPTConfig(vocab_size=128, max_seq_len=32, hidden_size=32,
                        num_layers=4, num_heads=2, dropout_rate=0.0,
                        dtype=jnp.float32)
        ds = DeepSpeedTPUConfig({
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 4,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "telemetry": {"enabled": True, "dir": str(tmp_path),
                          "metrics": {"sinks": ["memory"]}},
        })
        engine = PipelineEngine(gpt_pipe_model(cfg), ds,
                                mesh=build_mesh(data=4, pipe=2))
        rng = np.random.default_rng(0)
        batches = {"input_ids": rng.integers(0, cfg.vocab_size, (4, 8, 16),
                                             dtype=np.int32)}
        engine.train_batch(batches)
        mem = engine.telemetry.registry.sinks[0]
        assert isinstance(mem, InMemorySink)
        # 2 stages, 4 microbatches: bubble = (S-1)/(M+S-1) = 1/5
        assert mem.values("pipe/bubble_fraction") == [pytest.approx(0.2)]
        assert mem.values("pipe/bubble_time_sec")[0] > 0
        assert "pipe_step" in engine.telemetry.tracer.span_names()


# ---------------------------------------------------------------------------
# Satellites: timer + monitor fixes
# ---------------------------------------------------------------------------
class TestTimerSatellites:
    def test_avg_samples_per_sec_before_warmup_is_zero(self):
        from deepspeed_tpu.utils.timer import ThroughputTimer
        t = ThroughputTimer(batch_size=4, start_step=2, sync=False)
        assert t.avg_samples_per_sec() == 0.0  # not the old float("-1")
        t.start(); t.stop()
        assert t.avg_samples_per_sec() == 0.0
        for _ in range(4):
            t.start(); t.stop()
        assert t.avg_samples_per_sec() > 0.0

    def test_dead_init_timer_removed(self):
        from deepspeed_tpu.utils.timer import ThroughputTimer
        t = ThroughputTimer(batch_size=4)
        assert not hasattr(t, "_init_timer")
        assert not hasattr(t, "initialized")

    def test_wallclock_sync_gated_with_force_escape(self, monkeypatch):
        from deepspeed_tpu.utils import timer as timer_mod
        calls = {"n": 0}
        monkeypatch.setattr(timer_mod, "_device_synchronize",
                            lambda: calls.__setitem__("n", calls["n"] + 1))
        timers = timer_mod.SynchronizedWallClockTimer(enabled=False)
        timers("t").start(); timers("t").stop()
        assert calls["n"] == 0
        timers("t").start(force_sync=True)
        timers("t").stop(force_sync=True)
        assert calls["n"] == 2
        on = timer_mod.SynchronizedWallClockTimer(enabled=True)
        on("t").start(); on("t").stop()
        assert calls["n"] == 4

    def test_throughput_timer_sync_flag(self, monkeypatch):
        from deepspeed_tpu.utils import timer as timer_mod
        calls = {"n": 0}
        monkeypatch.setattr(timer_mod, "_device_synchronize",
                            lambda: calls.__setitem__("n", calls["n"] + 1))
        t = timer_mod.ThroughputTimer(batch_size=1, start_step=0, sync=False)
        t.start(); t.stop()
        assert calls["n"] == 0
        t = timer_mod.ThroughputTimer(batch_size=1, start_step=0, sync=True)
        t.start(); t.stop()
        assert calls["n"] == 2


class TestMonitorSatellites:
    def test_metrics_jsonl_extra_kwargs(self, tmp_path):
        from deepspeed_tpu.utils.monitor import MetricsJSONL
        m = MetricsJSONL(str(tmp_path / "m.jsonl"))
        m.add_scalar("t", 1.0, 0, attempt=2, kind="counter")
        m.flush()
        rows = m.read("t")
        assert rows == [{"tag": "t", "value": 1.0, "step": 0, "attempt": 2,
                         "kind": "counter"}]
        m.close()

    def test_tensorboard_fallback_flush_and_extra(self, tmp_path,
                                                  monkeypatch):
        # force the JSONL fallback path regardless of torch availability
        monkeypatch.setitem(sys.modules, "torch.utils.tensorboard", None)
        from deepspeed_tpu.utils.monitor import TensorboardMonitor
        mon = TensorboardMonitor(str(tmp_path), job_name="j")
        assert mon._writer is None and mon._jsonl is not None
        mon.add_scalar("a", 1.5, 3, source="test")
        mon.flush()  # must flush the fallback sink (the satellite fix)
        rows = [json.loads(l)
                for l in open(tmp_path / "j" / "scalars.jsonl")]
        assert rows == [{"tag": "a", "value": 1.5, "step": 3,
                         "source": "test"}]
        mon.close()


# ---------------------------------------------------------------------------
# tools/trace_report.py
# ---------------------------------------------------------------------------
def _load_trace_report():
    path = os.path.join(REPO, "tools", "trace_report.py")
    spec = importlib.util.spec_from_file_location("trace_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestTraceReport:
    def test_selftest_cli(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
             "--selftest"],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        assert "selftest ok" in proc.stdout

    def test_report_on_tracer_output(self, tmp_path):
        path = str(tmp_path / "t.json")
        tr = StepTracer(path=path, sync_spans=False)
        for _ in range(4):
            with tr.span("forward"):
                pass
            with tr.span("optimizer_step"):
                pass
        tr.counter("telemetry/recompiles", 1)
        tr.save()
        report = _load_trace_report()
        summary = report.summarize(report.load_events(path))
        by = {r["name"]: r for r in summary["spans"]}
        assert by["forward"]["count"] == 4
        assert summary["counters"]["telemetry/recompiles"] == 1.0
        assert abs(sum(r["share"] for r in summary["spans"]) - 1.0) < 1e-6
        text = report.render(summary, sort="count")
        assert "forward" in text

    def test_bare_array_trace_accepted(self, tmp_path):
        p = tmp_path / "bare.json"
        p.write_text(json.dumps([
            {"name": "s", "ph": "X", "pid": 1, "tid": 1, "ts": 0,
             "dur": 10.0}]))
        report = _load_trace_report()
        summary = report.summarize(report.load_events(str(p)))
        assert summary["spans"][0]["name"] == "s"
