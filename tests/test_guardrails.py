"""Guardrails subsystem (guardrails/, docs/RESILIENCE.md "Guardrails"):
EWMA/z-score anomaly detection, in-memory rollback from a snapshot ring,
the step watchdog's diagnostics-dump + distinct-rc contract, the shared
jittered-backoff helper, and the zero-cost-when-disabled guarantee.

The two acceptance gates live here: a FaultPlan-injected NaN-loss window
triggers detection -> in-memory rollback -> replay past the bad window with
a trajectory bit-identical to a clean run of the post-window stream; and a
FaultPlan-injected hang trips the watchdog (diagnostics dump, distinct exit
rc) with supervisor auto-resume — all on CPU.
"""

import json
import os
import random
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu import initialize
from deepspeed_tpu.config.config import ConfigError, DeepSpeedTPUConfig
from deepspeed_tpu.config.constants import \
    GUARDRAILS_WATCHDOG_EXIT_CODE_DEFAULT
from deepspeed_tpu.guardrails import (OK, SKIP, SPIKE, AnomalyDetector,
                                      EWMATracker, GuardrailsError,
                                      RollbackPolicy, SnapshotRing,
                                      StepWatchdog, backoff_delay,
                                      is_watchdog_exit, restore_snapshot,
                                      retry_call, take_snapshot)
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.resilience import FaultPlan, Supervisor, list_checkpoints
from deepspeed_tpu.runtime.dataloader import RepeatingLoader
from deepspeed_tpu.runtime.utils import has_inf_or_nan

from simple_model import mlp_params, mlp_loss_fn, random_batches

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


def _make_engine(guardrails=None, fault_injection=None, extra=None, dp=8):
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 1},
        "steps_per_print": 10_000,
    }
    if guardrails is not None:
        config["guardrails"] = guardrails
    if fault_injection is not None:
        config["resilience"] = {"fault_injection": fault_injection}
    config.update(extra or {})
    engine, _, _, _ = initialize(
        loss_fn=mlp_loss_fn, params=mlp_params(), config=config,
        mesh=build_mesh(data=dp, devices=jax.devices()[:dp]), rng_seed=0)
    return engine


def _stream(n, seed=7, batch_size=16):
    rng = np.random.default_rng(seed)
    return [random_batches(rng, 1, batch_size=batch_size) for _ in range(n)]


def _params_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(jax.device_get(a)),
                    jax.tree_util.tree_leaves(jax.device_get(b))):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=0, atol=0)


def _params_finite(tree) -> bool:
    flags = jax.jit(lambda t: jnp.stack(
        [jnp.all(jnp.isfinite(x.astype(jnp.float32)))
         for x in jax.tree_util.tree_leaves(t)]))(tree)
    return bool(jnp.all(flags))


# ---------------------------------------------------------------------------
# Shared retry helper
# ---------------------------------------------------------------------------

class TestRetry:
    def test_exponential_schedule_no_jitter(self):
        delays = [backoff_delay(a, 0.5, jitter=0.0) for a in range(4)]
        assert delays == [0.5, 1.0, 2.0, 4.0]

    def test_cap_applies_before_jitter(self):
        rng = random.Random(0)
        for a in range(20):
            d = backoff_delay(a, 1.0, max_delay=5.0, jitter=0.25, rng=rng)
            assert d <= 5.0 * 1.25 + 1e-9
        # a huge attempt index must not overflow
        assert backoff_delay(10_000, 1.0, max_delay=5.0, jitter=0.0) == 5.0

    def test_jitter_bounds_and_determinism(self):
        d1 = backoff_delay(3, 1.0, jitter=0.25, rng=random.Random(42))
        d2 = backoff_delay(3, 1.0, jitter=0.25, rng=random.Random(42))
        assert d1 == d2
        assert 8.0 * 0.75 <= d1 <= 8.0 * 1.25

    def test_validation(self):
        with pytest.raises(ValueError):
            backoff_delay(-1, 1.0)
        with pytest.raises(ValueError):
            backoff_delay(0, 1.0, jitter=1.5)

    def test_retry_call_retries_then_succeeds(self):
        calls, slept = [], []
        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"
        out = retry_call(flaky, max_retries=3, base=0.01, jitter=0.0,
                         sleep=slept.append)
        assert out == "ok" and len(calls) == 3
        assert slept == [0.01, 0.02]

    def test_retry_call_terminal_raises(self):
        slept = []
        def always():
            raise OSError("permanent")
        with pytest.raises(OSError, match="permanent"):
            retry_call(always, max_retries=2, base=0.01, jitter=0.0,
                       sleep=slept.append)
        assert len(slept) == 2


# ---------------------------------------------------------------------------
# Anomaly detector
# ---------------------------------------------------------------------------

class TestDetector:
    def test_warmup_absorbs_descent(self):
        det = AnomalyDetector(zscore_threshold=3.0, warmup_steps=10)
        # steep early descent: would be wildly out-of-distribution if the
        # z-score gate were armed from step 1
        for i, loss in enumerate([10.0, 6.0, 4.0, 3.0, 2.5, 2.2, 2.0]):
            assert det.observe(i, loss).kind == OK

    def test_nonfinite_is_spike_even_in_warmup(self):
        det = AnomalyDetector(warmup_steps=100)
        v = det.observe(0, float("nan"))
        assert v.kind == SPIKE and v.reason == "nonfinite"
        v = det.observe(1, 1.0, grad_norm=float("inf"))
        assert v.kind == SPIKE and v.reason == "nonfinite"

    def test_zscore_spike_not_absorbed_into_baseline(self):
        det = AnomalyDetector(zscore_threshold=4.0, warmup_steps=5,
                              ewma_alpha=0.1)
        for i in range(20):
            assert det.observe(i, 1.0 + 0.01 * ((-1) ** i)).kind == OK
        mean_before = det.loss_tracker.mean
        v = det.observe(20, 50.0)
        assert v.kind == SPIKE and v.reason == "zscore" and v.loss_z > 4.0
        assert det.loss_tracker.mean == mean_before  # spike excluded
        # the same spike magnitude again is still a spike (no drift)
        assert det.observe(21, 50.0).kind == SPIKE

    def test_grad_norm_spike(self):
        det = AnomalyDetector(zscore_threshold=4.0, warmup_steps=5,
                              ewma_alpha=0.1)
        for i in range(10):
            det.observe(i, 1.0 + 0.01 * (i % 2), grad_norm=2.0 + 0.01 * (i % 2))
        v = det.observe(10, 1.0, grad_norm=100.0)
        assert v.kind == SPIKE and v.norm_z > 4.0

    def test_overflow_is_skip_and_not_learned(self):
        det = AnomalyDetector(warmup_steps=2)
        det.observe(0, 1.0)
        count = det.loss_tracker.count
        v = det.observe(1, float("nan"), overflow=True)
        assert v.kind == SKIP and v.reason == "overflow"
        assert det.loss_tracker.count == count
        assert det.stats[SKIP] == 1

    def test_tracker_state_roundtrip_and_sigma_floor(self):
        t = EWMATracker(alpha=0.1)
        for x in [1.0, 1.0, 1.0]:
            t.update(x)
        assert t.sigma() > 0  # floor keeps z finite on a flat signal
        t2 = EWMATracker(alpha=0.1)
        t2.load_state_dict(t.state_dict())
        assert t2.mean == t.mean and t2.var == t.var and t2.count == t.count

    def test_validation(self):
        with pytest.raises(ValueError):
            AnomalyDetector(zscore_threshold=0.0)
        with pytest.raises(ValueError):
            AnomalyDetector(warmup_steps=0)
        with pytest.raises(ValueError):
            EWMATracker(alpha=0.0)


# ---------------------------------------------------------------------------
# has_inf_or_nan: native-dtype check (satellite)
# ---------------------------------------------------------------------------

class TestHasInfOrNan:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16,
                                       jnp.float16])
    def test_dtype_coverage(self, dtype):
        clean = {"a": jnp.ones((4, 4), dtype), "b": jnp.zeros((3,), dtype)}
        assert not bool(has_inf_or_nan(clean))
        dirty = dict(clean, b=jnp.array([1.0, jnp.nan, 2.0], dtype))
        assert bool(has_inf_or_nan(dirty))
        inf_t = dict(clean, a=jnp.full((4, 4), jnp.inf, dtype))
        assert bool(has_inf_or_nan(inf_t))

    def test_int_leaves_skipped(self):
        tree = {"step": jnp.array(3, jnp.int32),
                "w": jnp.ones((2,), jnp.float32)}
        assert not bool(has_inf_or_nan(tree))
        assert not bool(has_inf_or_nan({"step": jnp.array(3, jnp.int32)}))

    def test_no_fp32_upcast_for_half_precision(self):
        """The satellite's point: the predicate reads bf16/fp16 leaves in
        native dtype — no convert_element_type widening in the jaxpr."""
        tree = {"a": jnp.ones((8, 8), jnp.bfloat16),
                "b": jnp.ones((8,), jnp.float16)}
        jaxpr = str(jax.make_jaxpr(has_inf_or_nan)(tree))
        assert "convert_element_type" not in jaxpr

    def test_empty_tree(self):
        assert not bool(has_inf_or_nan({}))

    def test_fp16_overflow_semantics_kept(self):
        # fp16 inf (overflowed grad) must still be flagged — the loss
        # scaler's skip decision rides on it.
        big = jnp.array([65504.0], jnp.float16) * 2  # -> inf in fp16
        assert bool(has_inf_or_nan({"g": big}))


# ---------------------------------------------------------------------------
# RepeatingLoader: replay + skip (satellite)
# ---------------------------------------------------------------------------

class _CountingSampler:
    def __init__(self):
        self.epoch = 0

    def set_epoch(self, e):
        self.epoch = e


class _ListLoader:
    """Epoch-aware toy loader: item values encode (epoch, position)."""

    def __init__(self, n):
        self.n = n
        self.sampler = _CountingSampler()

    def __iter__(self):
        base = self.sampler.epoch * 100
        return iter(range(base, base + self.n))


class TestRepeatingLoaderReplaySkip:
    def test_skip_batches_matches_consumption(self):
        a, b = RepeatingLoader(_ListLoader(5)), RepeatingLoader(_ListLoader(5))
        for _ in range(3):
            next(a)
        a.skip_batches(4)                   # crosses the epoch boundary
        for _ in range(7):
            next(b)
        assert a.state_dict() == b.state_dict()
        assert next(a) == next(b)           # identical continuation

    def test_state_roundtrip_with_rollback_skip(self):
        """The rollback shape: consume, checkpoint (state_dict), consume a
        bad window, restore (load_state_dict), skip past the window — the
        stream continues exactly where a clean run that never saw the
        window would be."""
        src = RepeatingLoader(_ListLoader(4))
        for _ in range(3):
            next(src)
        saved = src.state_dict()
        for _ in range(2):
            next(src)                        # the poisoned window

        resumed = RepeatingLoader(_ListLoader(4))
        resumed.load_state_dict(saved)       # replay to the checkpoint
        resumed.skip_batches(2)              # advance past the bad window
        assert resumed.state_dict() == src.state_dict()
        assert [next(resumed) for _ in range(5)] == \
               [next(src) for _ in range(5)]

    def test_skip_across_epoch_boundary_restarts_iterator(self):
        """The __next__ StopIteration-restart edge: a skip landing exactly
        on the boundary rolls the epoch and re-seeds the sampler."""
        src = RepeatingLoader(_ListLoader(3))
        src.skip_batches(3)                  # consumes exactly one epoch
        assert src.state_dict() == {"epoch": 0, "batch_in_epoch": 3}
        assert next(src) == 100              # epoch 1 content (sampler-seeded)
        assert src.state_dict() == {"epoch": 1, "batch_in_epoch": 1}

    def test_skip_validation_and_zero(self):
        src = RepeatingLoader(_ListLoader(3))
        assert src.skip_batches(0) == 0
        assert src.state_dict() == {"epoch": 0, "batch_in_epoch": 0}
        with pytest.raises(ValueError):
            src.skip_batches(-1)


# ---------------------------------------------------------------------------
# Supervisor backoff cap + jitter + watchdog rc (satellite)
# ---------------------------------------------------------------------------

class TestSupervisorBackoff:
    def _sleeps(self, monkeypatch):
        from deepspeed_tpu.resilience import supervisor as sup_mod
        rec = []
        monkeypatch.setattr(sup_mod.time, "sleep", rec.append)
        return rec

    def test_delay_is_capped(self, monkeypatch):
        rec = self._sleeps(monkeypatch)
        sup = Supervisor([sys.executable, "-c", "import sys; sys.exit(3)"],
                         max_restarts=6, backoff=10.0, max_backoff=0.5,
                         jitter=0.25)
        assert sup.run() == 3
        assert len(rec) == 6
        assert all(d <= 0.5 * 1.25 + 1e-9 for d in rec)   # capped (pre-jitter)
        assert all(d > 0 for d in rec)

    def test_watchdog_rc_restarts_immediately(self, monkeypatch, tmp_path):
        rec = self._sleeps(monkeypatch)
        marker = tmp_path / "died_once"
        rc = GUARDRAILS_WATCHDOG_EXIT_CODE_DEFAULT
        script = textwrap.dedent(f"""
            import os, sys
            marker = {str(marker)!r}
            if not os.path.exists(marker):
                open(marker, "w").close()
                sys.exit({rc})   # watchdog-style death
            sys.exit(0)
        """)
        sup = Supervisor([sys.executable, "-c", script], max_restarts=3,
                         backoff=10.0)
        assert sup.run() == 0
        assert sup.exit_codes == [rc, 0]
        assert sup.immediate_restarts == 1
        assert rec == []                      # no backoff sleep at all
        assert is_watchdog_exit(rc) and not is_watchdog_exit(0)

    def test_custom_immediate_rc(self, monkeypatch, tmp_path):
        """A config-overridden watchdog exit_code keeps the no-backoff
        contract when passed through immediate_restart_rcs."""
        rec = self._sleeps(monkeypatch)
        marker = tmp_path / "died_once"
        script = textwrap.dedent(f"""
            import os, sys
            marker = {str(marker)!r}
            if not os.path.exists(marker):
                open(marker, "w").close()
                sys.exit(77)
            sys.exit(0)
        """)
        sup = Supervisor([sys.executable, "-c", script], max_restarts=3,
                         backoff=10.0, immediate_restart_rcs={77})
        assert sup.run() == 0
        assert sup.immediate_restarts == 1 and rec == []


# ---------------------------------------------------------------------------
# Config block
# ---------------------------------------------------------------------------

class TestGuardrailsConfig:
    BASE = {"train_micro_batch_size_per_gpu": 1}

    def test_defaults_off(self):
        cfg = DeepSpeedTPUConfig(dict(self.BASE))
        assert cfg.guardrails.enabled is False
        assert cfg.guardrails.nonfinite_grad_check is False
        assert cfg.guardrails.watchdog.enabled is False

    def test_nonfinite_gate_needs_both_flags(self):
        on = DeepSpeedTPUConfig({**self.BASE, "guardrails": {
            "enabled": True, "detector": {"check_nonfinite_grads": True}}})
        assert on.guardrails.nonfinite_grad_check is True
        half = DeepSpeedTPUConfig({**self.BASE, "guardrails": {
            "enabled": False, "detector": {"check_nonfinite_grads": True}}})
        assert half.guardrails.nonfinite_grad_check is False

    @pytest.mark.parametrize("block,match", [
        ({"detector": {"zscore_threshold": 0}}, "zscore_threshold"),
        ({"detector": {"warmup_steps": 0}}, "warmup_steps"),
        ({"detector": {"ewma_alpha": 0}}, "ewma_alpha"),
        ({"rollback": {"ring_size": 0}}, "ring_size"),
        ({"rollback": {"consecutive_spikes": 0}}, "consecutive_spikes"),
        ({"rollback": {"snapshot_interval": 0}}, "snapshot_interval"),
        ({"rollback": {"lr_decay": 0}}, "lr_decay"),
        ({"rollback": {"max_rollbacks": 0}}, "max_rollbacks"),
        ({"watchdog": {"enabled": True, "step_timeout_seconds": 0}},
         "step_timeout_seconds"),
        ({"watchdog": {"poll_interval_seconds": -1}},
         "poll_interval_seconds"),
        ({"watchdog": {"exit_code": 0}}, "exit_code"),
    ])
    def test_validation(self, block, match):
        with pytest.raises(ConfigError, match=match):
            DeepSpeedTPUConfig({**self.BASE,
                                "guardrails": {"enabled": True, **block}})

    def test_fault_plan_new_keys(self, monkeypatch):
        plan = FaultPlan.resolve({"nan_loss_at_step": 4, "nan_loss_steps": 2,
                                  "hang_at_step": 7})
        assert not plan.should_nan_loss(3)
        assert plan.should_nan_loss(4) and plan.should_nan_loss(5)
        assert not plan.should_nan_loss(6)
        assert plan.should_hang(7) and not plan.should_hang(8)
        monkeypatch.setenv("DSTPU_FAULT_PLAN", '{"hang_at_step": 2}')
        assert FaultPlan.resolve({}).should_hang(2)

    def test_poison_batch_floats_only(self):
        plan = FaultPlan(nan_loss_at_step=1)
        out = plan.poison_batch({"x": np.ones((2, 2), np.float32),
                                 "ids": np.ones((2,), np.int32)})
        assert np.isnan(out["x"]).all()
        assert (out["ids"] == 1).all()


# ---------------------------------------------------------------------------
# Snapshot ring + rollback policy
# ---------------------------------------------------------------------------

class TestRollback:
    def test_ring_bounded_newest_wins(self):
        ring = SnapshotRing(capacity=2)
        for i in range(5):
            ring.push(i)
        assert len(ring) == 2 and ring.newest() == 4
        ring.drop_newest()
        assert ring.newest() == 3
        with pytest.raises(ValueError):
            SnapshotRing(0)

    def test_snapshot_restore_bit_exact(self):
        engine = _make_engine()
        for b in _stream(3):
            engine.train_batch(b)
        snap = take_snapshot(engine)
        params_at_3 = jax.device_get(engine.state.params)
        for b in _stream(2, seed=11):
            engine.train_batch(b)
        assert engine.global_steps == 5
        rewound = restore_snapshot(engine, snap)
        assert rewound == 2 and engine.global_steps == 3
        _params_equal(engine.state.params, params_at_3)
        # continuation after restore is bit-identical to a fresh engine
        # trained on the same prefix (rng/opt_state restored too)
        fresh = _make_engine()
        for b in _stream(3):
            fresh.train_batch(b)
        tail = _stream(2, seed=23)
        got = [repr(float(engine.train_batch(b))) for b in tail]
        want = [repr(float(fresh.train_batch(b))) for b in tail]
        assert got == want

    def test_policy_streak_and_budget(self):
        ring = SnapshotRing(2)
        pol = RollbackPolicy(ring, consecutive_spikes=3)
        assert not pol.note_spike() and not pol.note_spike()
        pol.note_ok()                       # streak resets
        assert not pol.note_spike() and not pol.note_spike()
        assert pol.note_spike()             # third consecutive

    def test_policy_exhausted_budget_raises(self):
        engine = _make_engine()
        ring = SnapshotRing(4)
        pol = RollbackPolicy(ring, consecutive_spikes=1, max_rollbacks=1,
                             skip_batches=0)
        engine.train_batch(_stream(1)[0])
        ring.push(take_snapshot(engine))
        ring.push(take_snapshot(engine))
        pol.rollback(engine)
        with pytest.raises(GuardrailsError, match="budget exhausted"):
            pol.rollback(engine)

    def test_empty_ring_without_disk_raises(self):
        engine = _make_engine()
        pol = RollbackPolicy(SnapshotRing(1), consecutive_spikes=1,
                             escalate_to_disk=False)
        with pytest.raises(GuardrailsError, match="no in-memory snapshot"):
            pol.rollback(engine)

    def test_empty_ring_escalates_to_disk(self, tmp_path):
        engine = _make_engine(extra={"resilience": {
            "enabled": True,
            "checkpoint": {"dir": str(tmp_path), "interval": 100,
                           "backoff_seconds": 0.01}}})
        for b in _stream(2):
            engine.train_batch(b)
        engine.save_checkpoint_async()
        engine.ckpt_manager.wait()
        params_at_2 = jax.device_get(engine.state.params)
        engine.train_batch(_stream(1, seed=9)[0])
        pol = RollbackPolicy(SnapshotRing(1), consecutive_spikes=1,
                             skip_batches=0)
        summary = pol.rollback(engine)
        assert summary["source"] == "disk"
        assert engine.global_steps == 2
        _params_equal(engine.state.params, params_at_2)
        engine.ckpt_manager.close()

    def test_lr_decay_applies_on_rollback(self):
        engine = _make_engine()
        engine.train_batch(_stream(1)[0])
        gr_ring = SnapshotRing(1)
        gr_ring.push(take_snapshot(engine))
        pol = RollbackPolicy(gr_ring, consecutive_spikes=1, lr_decay=0.5,
                             skip_batches=0)
        pol.rollback(engine)
        assert pol.lr_scale == 0.5


# ---------------------------------------------------------------------------
# bf16/fp32 skip-on-nonfinite (engine.py:548 satellite)
# ---------------------------------------------------------------------------

class TestNonfiniteGradSkip:
    def _poisoned_stream(self):
        s = _stream(4)
        bad = {k: v.copy() for k, v in s[1].items()}
        bad["x"][:] = np.nan
        s[1] = bad
        return s

    def test_gate_on_skips_step_params_stay_finite(self):
        engine = _make_engine(
            guardrails={"enabled": True,
                        "detector": {"check_nonfinite_grads": True},
                        "rollback": {"enabled": False}},
            extra={"bf16": {"enabled": True}})
        s = self._poisoned_stream()
        engine.train_batch(s[0])
        params_before = jax.device_get(engine.state.params)
        engine.train_batch(s[1])                       # poisoned
        assert engine.skipped_steps == 1
        assert int(engine.state.step) == 1             # update refused
        _params_equal(engine.state.params, params_before)
        assert engine.guardrails.last_verdict.kind == SKIP
        engine.train_batch(s[2])
        assert int(engine.state.step) == 2
        assert _params_finite(engine.state.params)

    def test_gate_off_nan_commits(self):
        engine = _make_engine(extra={"bf16": {"enabled": True}})
        s = self._poisoned_stream()
        engine.train_batch(s[0])
        engine.train_batch(s[1])                       # poisoned, no gate
        assert engine.skipped_steps == 0
        assert not _params_finite(engine.state.params)  # the failure mode


# ---------------------------------------------------------------------------
# Zero cost when disabled (acceptance)
# ---------------------------------------------------------------------------

class TestZeroCostDisabled:
    def test_no_syncs_no_fetches_no_snapshots(self, monkeypatch):
        """Guardrails fully disabled => zero guardrails-originated host
        fetches AND zero telemetry-originated device syncs over a 10-step
        loop (the same contract/counting style as PR 2's zero-sync test)."""
        import deepspeed_tpu.guardrails as gr_mod
        from deepspeed_tpu.utils import timer as timer_mod
        fetches, syncs = {"n": 0}, {"n": 0}
        orig_fetch = gr_mod._host_fetch
        monkeypatch.setattr(gr_mod, "_host_fetch",
                            lambda x: (fetches.__setitem__("n", fetches["n"] + 1),
                                       orig_fetch(x))[1])
        monkeypatch.setattr(timer_mod, "_device_synchronize",
                            lambda: syncs.__setitem__("n", syncs["n"] + 1))
        import deepspeed_tpu.resilience.checkpoint as ckpt_mod
        snaps = {"n": 0}
        orig_snap = ckpt_mod.snapshot_engine
        monkeypatch.setattr(
            ckpt_mod, "snapshot_engine",
            lambda *a, **k: (snaps.__setitem__("n", snaps["n"] + 1),
                             orig_snap(*a, **k))[1])

        engine = _make_engine()                        # default: all off
        assert engine.guardrails is None
        for b in _stream(10):
            engine.train_batch(b)
        jax.block_until_ready(engine.state.params)
        assert fetches["n"] == 0
        assert syncs["n"] == 0
        assert snaps["n"] == 0

    def test_offload_tier_feeds_grad_norm(self):
        """The ZeRO-offload step path must feed the detector the unscaled
        grad norm like the device tiers do (it was silently None)."""
        engine = _make_engine(
            guardrails={"enabled": True, "rollback": {"enabled": False}},
            extra={"zero_optimization": {
                "stage": 1, "offload_optimizer": {"device": "cpu"}}})
        for b in _stream(3):
            engine.train_batch(b)
        det = engine.guardrails.detector
        assert det.stats[OK] == 3
        assert det.norm_tracker.count == 3      # norm observed every step
        assert det.norm_tracker.mean > 0.0

    def test_enabled_fetches_are_counted(self, monkeypatch):
        import deepspeed_tpu.guardrails as gr_mod
        fetches = {"n": 0}
        orig_fetch = gr_mod._host_fetch
        monkeypatch.setattr(gr_mod, "_host_fetch",
                            lambda x: (fetches.__setitem__("n", fetches["n"] + 1),
                                       orig_fetch(x))[1])
        engine = _make_engine(guardrails={"enabled": True,
                                          "rollback": {"enabled": False}})
        for b in _stream(3):
            engine.train_batch(b)
        assert fetches["n"] > 0
        assert engine.guardrails.detector.stats[OK] == 3


# ---------------------------------------------------------------------------
# E2E: NaN-loss window -> detect -> in-memory rollback -> replay past it
# ---------------------------------------------------------------------------

class _StreamLoader:
    def __init__(self, stream):
        self.stream = stream

    def __iter__(self):
        return iter(self.stream)


class TestRollbackEndToEnd:
    def test_nan_window_rollback_bit_identical_tail(self):
        """Acceptance: FaultPlan NaN-poisons the batches for step attempts
        [k+1, k+2] (consecutive_spikes=2 -> rollback to the step-k ring
        snapshot, the poisoned positions already consumed). The guarded
        run's trajectory must then be BIT-IDENTICAL to a clean run fed the
        same stream with the poisoned window excised — detection, restore
        and replay cost exactly the bad window, nothing else."""
        k, total = 4, 10
        stream = _stream(total + 2)
        guarded = _make_engine(
            guardrails={"enabled": True,
                        # stat gate effectively off: only nonfinite trips
                        "detector": {"zscore_threshold": 1e9,
                                     "warmup_steps": 1},
                        "rollback": {"snapshot_interval": 1, "ring_size": 2,
                                     "consecutive_spikes": 2,
                                     "skip_batches": 0}},
            fault_injection={"nan_loss_at_step": k + 1, "nan_loss_steps": 2})
        loader = RepeatingLoader(_StreamLoader(stream))
        guarded.register_data_skip_fn(loader.skip_batches)
        guarded_losses = {}
        attempts = 0
        while guarded.global_steps < total:
            before = guarded.global_steps
            loss = guarded.train_batch(next(loader))
            if guarded.global_steps == before + 1:
                # committed step (a rollback iteration rewinds instead;
                # its loss belongs to no surviving step). Re-committed
                # steps overwrite their poisoned first attempt.
                guarded_losses[guarded.global_steps] = repr(float(loss))
            attempts += 1
            assert attempts < 50, "rollback did not converge"

        # exactly one rollback, at the configured streak
        assert guarded.guardrails.policy.rollbacks == 1
        assert guarded.guardrails.detector.stats[SPIKE] == 2
        assert _params_finite(guarded.state.params)
        # every COMMITTED step's loss is finite (the NaN attempts were
        # rolled back and re-keyed to the restored step numbers)
        assert all(np.isfinite(float(v.strip("'")))
                   for v in guarded_losses.values())

        # clean run: same stream minus the two poisoned positions (k, k+1)
        clean = _make_engine()
        clean_stream = stream[:k] + stream[k + 2:]
        clean_losses = {}
        for i in range(total):
            loss = clean.train_batch(clean_stream[i])
            clean_losses[clean.global_steps] = repr(float(loss))

        assert guarded_losses == clean_losses   # bit-identical, full run
        _params_equal(guarded.state.params, clean.state.params)

    def test_spike_steps_never_checkpointed(self, tmp_path):
        """The interval auto-save is verdict-gated: a spike-committed
        (NaN) state must never become the newest on-disk checkpoint —
        it is exactly what escalation and post-watchdog auto-resume
        would restore."""
        engine = _make_engine(
            guardrails={"enabled": True,
                        "detector": {"zscore_threshold": 1e9,
                                     "warmup_steps": 1},
                        "rollback": {"snapshot_interval": 1,
                                     "consecutive_spikes": 2,
                                     "skip_batches": 0}},
            fault_injection={"nan_loss_at_step": 3, "nan_loss_steps": 2},
            extra={"resilience": {
                "enabled": True,
                "fault_injection": {"nan_loss_at_step": 3,
                                    "nan_loss_steps": 2},
                "checkpoint": {"dir": str(tmp_path), "interval": 1,
                               "backoff_seconds": 0.01}}})
        stream = _stream(10)
        i = 0
        while engine.global_steps < 6:
            engine.train_batch(stream[i % len(stream)])
            i += 1
        engine.ckpt_manager.wait()
        from deepspeed_tpu.resilience import find_restorable
        # every committed checkpoint holds finite params — the two NaN
        # spike steps (attempts 3, 4 -> steps 3 and 4 pre-rollback) were
        # skipped by the verdict gate
        for step, path in list_checkpoints(str(tmp_path)):
            found = find_restorable(str(tmp_path))
            assert found is not None
        _, manifest, arrays, _ = find_restorable(str(tmp_path))
        for name, arr in arrays.items():
            if name.startswith("params"):
                assert np.isfinite(arr).all(), name
        assert engine.guardrails.policy.rollbacks == 1
        engine.ckpt_manager.close()

    def test_rollback_emits_telemetry(self, tmp_path):
        engine = _make_engine(
            guardrails={"enabled": True,
                        "detector": {"zscore_threshold": 1e9,
                                     "warmup_steps": 1},
                        "rollback": {"snapshot_interval": 1,
                                     "consecutive_spikes": 1,
                                     "skip_batches": 0}},
            fault_injection={"nan_loss_at_step": 3},
            extra={"telemetry": {"enabled": True, "dir": str(tmp_path),
                                 "trace": {"sync_spans": False}}})
        stream = _stream(8)
        i = 0
        while engine.global_steps < 5:
            engine.train_batch(stream[i % len(stream)])
            i += 1
        engine.telemetry.flush()
        rows = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
        tags = {r["tag"] for r in rows}
        assert "guardrails/steps_ok" in tags
        assert "guardrails/steps_spike" in tags
        assert "guardrails/rollbacks" in tags
        assert "guardrails/snapshots" in tags
        assert "guardrails/loss_zscore" in tags
        doc = json.load(open(tmp_path / "trace.json"))
        instants = {e["name"] for e in doc["traceEvents"]
                    if e.get("ph") == "i"}
        assert {"guardrails_spike", "guardrails_rollback"} <= instants


# ---------------------------------------------------------------------------
# Watchdog
# ---------------------------------------------------------------------------

class TestWatchdog:
    def test_trip_dumps_and_exits_with_rc(self, tmp_path):
        exits = []
        wd = StepWatchdog(timeout=0.15, crashdump_dir=str(tmp_path),
                          poll_interval=0.02, exit_fn=exits.append)
        wd.start()
        wd.step_begin(7, label="unit_test_step")
        import time
        time.sleep(0.6)
        wd.stop()
        assert wd.tripped and exits == [GUARDRAILS_WATCHDOG_EXIT_CODE_DEFAULT]
        dumps = os.listdir(tmp_path)
        assert len(dumps) == 1 and dumps[0].startswith("watchdog_step7")
        ddir = tmp_path / dumps[0]
        info = json.load(open(ddir / "info.json"))
        assert info["step"] == 7 and info["label"] == "unit_test_step"
        assert info["elapsed_sec"] > 0.15
        stacks = open(ddir / "stacks.txt").read()
        assert "Thread" in stacks or "File" in stacks  # faulthandler output

    def test_idle_never_trips(self, tmp_path):
        exits = []
        wd = StepWatchdog(timeout=0.05, crashdump_dir=str(tmp_path),
                          poll_interval=0.01, exit_fn=exits.append)
        wd.start()
        import time
        time.sleep(0.3)           # never armed: between-step idle is fine
        wd.stop()
        assert not wd.tripped and exits == []

    def test_reentrant_brackets(self, tmp_path):
        exits = []
        wd = StepWatchdog(timeout=10.0, crashdump_dir=str(tmp_path),
                          exit_fn=exits.append)
        wd.step_begin(1, label="outer")
        wd.step_begin(1, label="inner")   # depth 2: must not re-arm
        assert wd._label == "outer"
        wd.step_end()
        assert wd._armed_at is not None   # still armed at depth 1
        wd.step_end()
        assert wd._armed_at is None

    def test_suspend_disarms_at_any_depth(self, tmp_path):
        """Rollback recovery calls suspend() from inside the (possibly
        nested pipe) bracket: fully disarmed, and the enclosing step_end
        finallys re-balance without going negative."""
        wd = StepWatchdog(timeout=10.0, crashdump_dir=str(tmp_path),
                          exit_fn=lambda rc: None)
        wd.step_begin(1, label="pipe_step")
        wd.step_begin(1)                  # nested base bracket
        wd.suspend()
        assert wd._armed_at is None and wd._depth == 0
        wd.step_end()
        wd.step_end()                     # clamped, no underflow
        assert wd._depth == 0
        wd.step_begin(2)                  # next step re-arms cleanly
        assert wd._armed_at is not None
        wd.step_end()

    def test_validation(self):
        with pytest.raises(ValueError):
            StepWatchdog(timeout=0)
        with pytest.raises(ValueError, match="poll_interval"):
            StepWatchdog(timeout=1.0, poll_interval=-0.5)


# ---------------------------------------------------------------------------
# E2E: injected hang -> watchdog dump + distinct rc -> supervisor resume
# ---------------------------------------------------------------------------

_HANG_SCRIPT = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, sys.argv[5])
    import numpy as np
    from deepspeed_tpu import initialize
    from deepspeed_tpu.parallel.mesh import build_mesh
    from simple_model import mlp_params, mlp_loss_fn, random_batches

    ckpt_dir, dump_dir, total, out = (sys.argv[1], sys.argv[2],
                                      int(sys.argv[3]), sys.argv[4])
    engine, _, _, _ = initialize(
        loss_fn=mlp_loss_fn, params=mlp_params(),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 1},
            "steps_per_print": 1000,
            "resilience": {"enabled": True,
                           "checkpoint": {"dir": ckpt_dir, "interval": 1,
                                          "backoff_seconds": 0.01}},
            "guardrails": {"enabled": True,
                           "rollback": {"enabled": False},
                           "watchdog": {"enabled": True,
                                        # far under the 120 s hang, and
                                        # over a step with its checkpoint
                                        # write on a host busy with five
                                        # other test workers (1 s was not)
                                        "step_timeout_seconds": 4.0,
                                        "poll_interval_seconds": 0.05,
                                        "crashdump_dir": dump_dir}},
        },
        mesh=build_mesh(data=8), rng_seed=0)
    engine.auto_resume()
    rng = np.random.default_rng(7)
    stream = [random_batches(rng, 1, batch_size=16) for _ in range(total)]
    with open(out, "a", buffering=1) as f:
        for i in range(engine.global_steps, total):
            loss = float(engine.train_batch(stream[i]))
            f.write(json.dumps({"step": i + 1, "loss": repr(loss)}) + "\\n")
    engine.ckpt_manager.close()
""")


def test_hang_watchdog_supervisor_resume(tmp_path):
    """Acceptance: a FaultPlan-injected hang at step 3 trips the watchdog
    (diagnostics dump, distinct rc), the supervisor restarts IMMEDIATELY
    (no backoff) and the resumed incarnation finishes the run."""
    total = 6
    ckpt, dump = tmp_path / "ckpt", tmp_path / "dump"
    out = tmp_path / "losses.jsonl"
    sup = Supervisor(
        [sys.executable, "-c", _HANG_SCRIPT, str(ckpt), str(dump),
         str(total), str(out), TESTS_DIR],
        max_restarts=2, backoff=30.0,    # a backoff sleep would time out
        env={"JAX_PLATFORMS": "cpu",
             "DSTPU_FAULT_PLAN": json.dumps(
                 {"hang_at_step": 3, "hang_seconds": 120})})
    rc = sup.run()
    assert rc == 0
    assert sup.exit_codes[0] == GUARDRAILS_WATCHDOG_EXIT_CODE_DEFAULT
    assert sup.immediate_restarts == 1 and sup.restarts == 1

    # the dump holds thread stacks naming the hang site
    dumps = [d for d in os.listdir(dump) if d.startswith("watchdog_")]
    assert len(dumps) == 1
    stacks = open(dump / dumps[0] / "stacks.txt").read()
    assert "hang" in stacks          # FaultPlan.hang's sleep frame
    info = json.load(open(dump / dumps[0] / "info.json"))
    assert info["exit_code"] == GUARDRAILS_WATCHDOG_EXIT_CODE_DEFAULT

    # the run completed every step, resuming from a committed checkpoint
    steps = {json.loads(l)["step"] for l in open(out)}
    assert steps == set(range(1, total + 1))
    assert [s for s, _ in list_checkpoints(str(ckpt))][-1] == total
