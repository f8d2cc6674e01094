"""Device time of one Mamba-2 layer's decode step over the slots' state:
XLA's step (every slot computed, a dead slot's new state dropped by a
``where``) against ``ssm_step_live`` (``ops/ssm.py``: the live slots'
state alone, in place), at ``nemotron3s-serve-chat``'s shapes.

    python tools/probe_ssm_step.py                       # on a TPU
    JAX_PLATFORMS=cpu python tools/probe_ssm_step.py --rehearsal

The state is the cell's: 128 slots of 128 heads x 64 x 128 float32 (4 MiB
a slot), 8 groups of ``B`` and ``C``; ``alive`` slots of 128, drawn at
random, hold a request. Both programs take the state donated, as the
decode program does, and are called ``--reps`` times on the state the
last call left, under one ``jax.profiler`` capture; a row gives the device
microseconds a call, all its ops (``us``) and its Mosaic kernel alone
(``kernel_us``), beside the least time the live slots' state needs, read
and written at the chip's published HBM bandwidth (``least_us``). Results
also land in ``chiprun_out/probe_ssm_step.json``. ``--rehearsal`` is the
same control flow tiny through the interpreter, every time null.
"""

import argparse
import contextlib
import json
import os
import sys
import tempfile
from functools import partial
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import trace_reduce

from deepspeed_tpu.ops import ssm
from deepspeed_tpu.utils.compile_cache import configure_compile_cache

HBM = 819e9                          # v5e published (benchmarks/peaks.json)
SLOTS, HEADS, P, N, GROUPS = 128, 128, 64, 128, 8
ALIVE = (1, 16, 38, 80, 128)


def inputs(key, alive, rng):
    """The step's inputs at the cell's shapes, made on the device; ``alive``
    slots of ``SLOTS`` live, drawn by ``rng``."""
    ks = jax.random.split(key, 6)
    normal = lambda k, shape, dtype=jnp.float32: jax.random.normal(
        k, shape, jnp.float32).astype(dtype)
    live = np.zeros(SLOTS, bool)
    live[rng.choice(SLOTS, alive, replace=False)] = True
    return (normal(ks[0], (SLOTS, HEADS, P), jnp.bfloat16),
            jax.nn.softplus(normal(ks[1], (SLOTS, HEADS)) - 2.0),
            -jnp.arange(1, HEADS + 1, dtype=jnp.float32),
            normal(ks[2], (SLOTS, GROUPS, N), jnp.bfloat16),
            normal(ks[3], (SLOTS, GROUPS, N), jnp.bfloat16),
            jnp.ones((HEADS,), jnp.float32),
            normal(ks[4], (SLOTS, HEADS, P, N)),
            jnp.asarray(live))


def timed(step, args, reps, rehearsal):
    """``(us, kernel_us)``: device microseconds a call, all ops and the
    Mosaic calls alone (mean over ``reps``); the state (``args[6]``) is
    donated and each call takes the state the last one left."""
    *head, state, live = args
    _, state = step(*head, state, live)              # compile, warm
    jax.block_until_ready(state)
    if rehearsal:
        return None, None
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(reps):
            _, state = step(*head, state, live)
        jax.block_until_ready(state)
        jax.profiler.stop_trace()
        path = next(os.path.join(root, f) for root, _, files in os.walk(tmp)
                    for f in files if f.endswith(".xplane.pb"))
        ops = [op for dev in trace_reduce.load_xplane(path).devices.values()
               for op in dev]
    total = sum(op.end - op.start for op in ops)
    mosaic = sum(op.end - op.start for op in ops if trace_reduce.is_mosaic(op))
    return 1e6 * total / reps, 1e6 * mosaic / reps


def checked(kernel, args):
    """The kernel against XLA's step on the same inputs, on the chip: the
    live rows' largest difference (state, ``y``), and whether every dead
    slot's state came back as it went in and its ``y`` as 0."""
    live = args[-1]

    @jax.jit
    def compare(*args):
        with mock.patch.object(ssm, "on_tpu", lambda: False):
            y_want, new_want = ssm.ssm_step(*args)
        y, new = kernel(*args)
        far = lambda got, want: jnp.where(
            live.reshape((-1,) + (1,) * (got.ndim - 1)),
            jnp.abs(got - want), 0.0).max()
        dead = ~live.reshape((-1,) + (1,) * (new.ndim - 1))
        kept = jnp.all(jnp.where(dead, new == args[6], True)) & jnp.all(
            jnp.where(dead[..., 0], y == 0, True))
        return far(new, new_want), far(y, y_want), kept

    state_err, y_err, kept = jax.device_get(compare(*args))
    return {"state_err": float(state_err), "y_err": float(y_err),
            "dead_kept": bool(kept)}


def case(alive, path, reps, rehearsal, rng):
    if path == "xla":
        # the step as every other platform runs it (the platform probe is
        # read while the first call traces): every slot, a where
        step = jax.jit(ssm.ssm_step, donate_argnums=(6,))
        platform = mock.patch.object(ssm, "on_tpu", lambda: False)
    else:
        kernel = partial(ssm.ssm_step_live, interpret=rehearsal)
        step = jax.jit(kernel, donate_argnums=(6,))
        platform = contextlib.nullcontext()
    args = inputs(jax.random.PRNGKey(alive), alive, rng)
    least = 2 * alive * HEADS * P * N * 4 / HBM
    row = {"alive": alive, "path": path,
           "least_us": round(1e6 * least, 1)}
    try:
        if path != "xla":
            row.update(checked(kernel, args))
        with platform:
            us, kernel_us = timed(step, args, reps, rehearsal)
        row.update(us=us and round(us, 1),
                   kernel_us=kernel_us and round(kernel_us, 1))
        if us:
            row["roofline_pct"] = round(1e8 * least / us, 1)
    except Exception as e:  # noqa: BLE001 — a block Mosaic refuses is a row
        row["error"] = f"{type(e).__name__}: {e}"[:300]
    print(json.dumps(row), flush=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default="probe_ssm_step.json",
                    help="file name under chiprun_out/")
    a = ap.parse_args()
    if not a.rehearsal and jax.devices()[0].platform != "tpu":
        sys.exit(f"needs a TPU, found {jax.devices()[0].platform} "
                 "(--rehearsal runs the control flow on the CPU)")
    configure_compile_cache()
    global SLOTS, HEADS, P, N, GROUPS, ALIVE
    if a.rehearsal:
        SLOTS, HEADS, P, N, GROUPS, a.reps = 8, 8, 16, 16, 2, 1
        ALIVE = (1, 3, 8)
    rng = np.random.default_rng(0)
    rows = []
    for alive in ALIVE:
        for path in ("xla", "ssm_step_live"):
            rows.append(case(alive, path, a.reps, a.rehearsal, rng))
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", a.out), "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "rows": rows}, f,
                  indent=1)


if __name__ == "__main__":
    main()
