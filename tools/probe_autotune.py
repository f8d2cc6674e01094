#!/usr/bin/env python3
"""Acceptance probe: the autotuner adopts the MEASURED winner.

A tiny two-candidate search on CPU (one engine, one process): the base
config splits its per-chip batch as micro 8 x gas 1; the challenger
re-splits it micro 1 x gas 8 — same global batch (the invariant the
ladder math guarantees), different scan length, measurably different
step time. The search trials both and must adopt whichever MEASURED
faster, with the loser's verdict (eliminated reason, or its trial rank)
recorded in the result — the evidence trail the issue asks for.

Asserts (``--selftest`` — wired into tier-1 via tests/test_autotuning.py):
- both candidates carry a measured step time;
- the adopted candidate is the measured minimum;
- the loser's record carries its rank and, when halved away, the reason;
- the engine leaves the search on the winning config with its pre-search
  step counter intact.

Run: JAX_PLATFORMS=cpu python tools/probe_autotune.py [--selftest]
"""

import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "tests"))

HIDDEN = 32


def run_probe():
    import numpy as np

    import deepspeed_tpu
    from simple_model import mlp_loss_fn, mlp_params

    td = tempfile.mkdtemp(prefix="probe_autotune_")
    engine, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=mlp_loss_fn, params=mlp_params(hidden=HIDDEN, layers=2),
        config={
            "train_micro_batch_size_per_gpu": 8,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2},
            "steps_per_print": 10_000,
            "autotuning": {"enabled": True,
                           "zero_stages": [2],
                           "micro_gas": [[8, 1], [1, 8]],
                           "zeropp": ["off"],
                           "top_k": 2, "trial_steps": 4,
                           "trial_warmup": 1},
        }, rng_seed=0)

    rng = np.random.default_rng(0)

    def make_batches(micro, gas):
        return {
            "x": rng.standard_normal((gas, micro, HIDDEN)).astype(
                np.float32),
            "y": rng.standard_normal((gas, micro, 8)).astype(np.float32),
        }

    steps_before = engine.global_steps
    result = deepspeed_tpu.autotune(engine, make_batches, result_dir=td)
    measured = {r["name"]: r["measured_step_ms"]
                for r in result["candidates"]
                if r["measured_step_ms"] is not None}
    loser = next(r for r in result["candidates"]
                 if r["name"] != result["adopted"]["name"])
    return engine, result, measured, loser, steps_before


def main(argv=None) -> int:
    selftest = "--selftest" in (argv or sys.argv[1:])
    engine, result, measured, loser, steps_before = run_probe()
    import jax
    print(f"platform: {jax.devices()[0].platform} (CPU correctness drive)")

    from deepspeed_tpu.autotuning import render_result_table
    print(render_result_table(result))
    row = {
        "adopted": result["adopted"]["name"],
        "adopted_ms": result["adopted"]["measured_step_ms"],
        "loser": loser["name"],
        "loser_status": loser["status"],
        "loser_ms": loser["measured_step_ms"],
        "loser_rank": loser["rank"],
        "search_sec": result["search_sec"],
    }
    print(json.dumps(row))
    if selftest:
        assert len(result["candidates"]) == 2, result["candidates"]
        assert len(measured) == 2, measured
        # The adopted candidate is the measured minimum — the tuner's
        # whole contract.
        best = min(measured, key=measured.get)
        assert result["adopted"]["name"] == best, (result["adopted"], measured)
        # The loser's verdict is recorded: its rank always, and the
        # halving reason when it was eliminated early.
        assert loser["rank"] is not None, loser
        assert loser["status"] in ("trialed", "eliminated"), loser
        if loser["status"] == "eliminated":
            assert "successive halving" in (loser["reason"] or ""), loser
        # The engine left the search ON the winner with state restored.
        assert engine.global_steps == steps_before, engine.global_steps
        mb, gas = (engine.train_micro_batch_size_per_gpu,
                   engine.gradient_accumulation_steps)
        assert [mb, gas] in ([8, 1], [1, 8]) and mb * gas == 8, (mb, gas)
        assert "result_path" in result and os.path.exists(
            result["result_path"])
        print("selftest ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
