"""Acceptance probe: the in-process elastic reshard beats a cold restart.

The whole point of live elasticity (resilience/elastic.py) is removing the
cold-restart bill — interpreter + jax import, engine construction, XLA
compile, checkpoint deserialize — that ``init_restore`` dominates in the
goodput reports. This probe measures both paths on the same tiny-MLP job
over a 2-slice virtual CPU mesh:

- **in-process**: a running 8-chip engine is told slice 1 is preempted
  (``ElasticCoordinator.request_shrink``); the measured cost is the
  coordinator's own ``elastic/reshard_sec`` (drain + state gather + mesh
  and step-fn rebuild + reshard + first-step recompile);
- **cold restart**: a fresh subprocess builds the 4-chip engine, resumes
  from the checkpoint the first engine committed, and runs one step — the
  wall clock of the whole subprocess, which is exactly what a supervisor
  restart pays (the interpreter/import tax included; that is the honest
  comparison).

Asserts the in-process path is cheaper (``--selftest`` — wired into
tier-1 via tests/test_elastic.py).

Run: JAX_PLATFORMS=cpu python tools/probe_elasticity.py [--selftest]
"""

import json
import os
import subprocess
import sys
import tempfile
import time

os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "tests"))

GLOBAL_BATCH = 24
HIDDEN, LAYERS = 64, 2


def _config(ckpt_dir, live=True):
    cfg = {
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
        "mesh": {"slices": 2},
        "steps_per_print": 10_000,
        "elasticity": {
            "enabled": True,
            "max_train_batch_size": GLOBAL_BATCH,
            "micro_batch_sizes": [1, 2],
            "min_chips": 1, "max_chips": 64, "version": 0.1,
        },
        "resilience": {
            "enabled": True,
            "checkpoint": {"dir": ckpt_dir, "interval": 1, "keep_last": 2,
                           "async": False},
        },
    }
    if live:
        cfg["elasticity"]["live"] = {"enabled": True, "grace_seconds": 60.0}
    return cfg


def _batches(engine, seed=7):
    import numpy as np
    rng = np.random.default_rng(seed)
    gas = engine.gradient_accumulation_steps
    return {
        "x": rng.standard_normal(
            (gas, GLOBAL_BATCH // gas, HIDDEN)).astype(np.float32),
        "y": rng.standard_normal(
            (gas, GLOBAL_BATCH // gas, 8)).astype(np.float32),
    }


# The cold-restart side, run as its OWN process: a supervisor restart pays
# interpreter + imports + engine build + restore + first-step compile, and
# so does this script. mesh.slices=1 (the surviving slice), world 4.
_COLD_SCRIPT = r"""
import json, os, sys, time
t0 = time.monotonic()
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
root, ckpt_dir, out = sys.argv[1], sys.argv[2], sys.argv[3]
sys.path.insert(0, root)
sys.path.insert(0, os.path.join(root, "tests"))
import numpy as np
import deepspeed_tpu
from simple_model import mlp_loss_fn, mlp_params
GLOBAL_BATCH, HIDDEN = 24, 64
engine, _, _, _ = deepspeed_tpu.initialize(
    loss_fn=mlp_loss_fn, params=mlp_params(hidden=HIDDEN, layers=2),
    config={
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
        "mesh": {"slices": 1},
        "steps_per_print": 10_000,
        "elasticity": {"enabled": True, "max_train_batch_size": GLOBAL_BATCH,
                       "micro_batch_sizes": [1, 2], "min_chips": 1,
                       "max_chips": 64, "version": 0.1},
        "resilience": {"enabled": True,
                       "checkpoint": {"dir": ckpt_dir, "interval": 1}},
    }, rng_seed=0)
path, _ = engine.auto_resume()
assert path is not None, "cold restart found no checkpoint"
rng = np.random.default_rng(7)
gas = engine.gradient_accumulation_steps
batch = {
    "x": rng.standard_normal((gas, GLOBAL_BATCH // gas, HIDDEN)).astype(
        np.float32),
    "y": rng.standard_normal((gas, GLOBAL_BATCH // gas, 8)).astype(
        np.float32),
}
loss = float(engine.train_batch(batch))
engine.ckpt_manager.close()
with open(out, "w") as f:
    json.dump({"cold_restart_sec": time.monotonic() - t0,
               "restored": path is not None, "loss": loss,
               "world": engine.mesh.size,
               "global_steps": engine.global_steps}, f)
"""


def run_probe():
    import deepspeed_tpu
    from simple_model import mlp_loss_fn, mlp_params

    td = tempfile.mkdtemp(prefix="probe_elasticity_")
    ckpt_dir = os.path.join(td, "ckpt")
    engine, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=mlp_loss_fn, params=mlp_params(hidden=HIDDEN, layers=LAYERS),
        config=_config(ckpt_dir), rng_seed=0)
    assert engine.elastic is not None and engine.mesh.size == 8

    # Warm steps: compile the 8-chip program and commit checkpoints the
    # cold path will restore from.
    for _ in range(3):
        engine.train_batch(_batches(engine))
    engine.ckpt_manager.wait()

    # In-process shrink: slice 1 preempted -> world 4, measured by the
    # coordinator (drain + gather + rebuild). The first post-shrink step
    # carries the recompile, so time it into the in-process bill too —
    # the cold path's one step likewise carries its compile.
    engine.elastic.request_shrink(1)
    t0 = time.monotonic()
    engine.train_batch(_batches(engine))
    in_process_total = time.monotonic() - t0
    assert engine.mesh.size == 4, engine.mesh.size
    reshard_sec = float(engine.elastic.last_reshard_sec)
    engine.train_batch(_batches(engine))          # steady-state sanity
    engine.ckpt_manager.close()

    # Cold restart of the same shrink: fresh process, world 4, restore.
    out = os.path.join(td, "cold.json")
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-c", _COLD_SCRIPT, _ROOT, ckpt_dir, out],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    cold_wall = time.monotonic() - t0
    if proc.returncode != 0:
        print(proc.stdout, proc.stderr, file=sys.stderr)
        raise RuntimeError(f"cold-restart subprocess rc={proc.returncode}")
    with open(out) as f:
        cold = json.load(f)

    result = {
        "in_process_reshard_sec": round(reshard_sec, 4),
        "in_process_total_sec": round(in_process_total, 4),
        "cold_restart_sec": round(cold["cold_restart_sec"], 4),
        "cold_restart_wall_sec": round(cold_wall, 4),
        "speedup": round(cold["cold_restart_sec"]
                         / max(in_process_total, 1e-9), 2),
        "cold_world": cold["world"],
    }
    return result


def main(argv=None) -> int:
    selftest = "--selftest" in (argv or sys.argv[1:])
    result = run_probe()
    import jax
    print(f"platform: {jax.devices()[0].platform} (CPU correctness drive)")
    print(f"{'path':<28} {'seconds':>10}")
    print("-" * 40)
    print(f"{'in-process reshard only':<28} "
          f"{result['in_process_reshard_sec']:>10.3f}")
    print(f"{'in-process (+ first step)':<28} "
          f"{result['in_process_total_sec']:>10.3f}")
    print(f"{'cold supervisor restart':<28} "
          f"{result['cold_restart_sec']:>10.3f}")
    print(f"\nspeedup (cold / in-process): {result['speedup']:.1f}x")
    print(json.dumps(result))
    if selftest:
        # The acceptance gate: the in-process path (including its
        # recompile) must beat the cold restart (whose bill is dominated
        # by interpreter + jax import + engine re-construction — the
        # init_restore the goodput reports flagged).
        assert result["in_process_total_sec"] < result["cold_restart_sec"], \
            result
        assert result["cold_world"] == 4, result
        print("selftest ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
