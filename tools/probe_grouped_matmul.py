"""Device time of the held experts' grouped matmul: XLA's kernel
(``jax.lax.ragged_dot``) against ``moe_gmm`` (``ops/grouped_matmul.py``)
per row tile, at ``nemotron3s-serve-chat``'s shapes.

    chiprun -- python tools/probe_grouped_matmul.py
    JAX_PLATFORMS=cpu python tools/probe_grouped_matmul.py --rehearsal

Groups come from routing: ``alive`` rows of a ``slots``-row buffer each
choose 22 of 512 experts uniformly, the 128 held ones keep theirs, so the
buffer is ``slots x 22`` rows with the real spread of rows a group. Shapes:
decode (128 slots, 73 alive, the cell's mean) and prefills of the 256, 1024
and 2048 buckets, each through the latent experts' two matmuls (``up``
1024 -> 2688, ``down`` 2688 -> 1024). First, the account ISSUE 36 rests on:
``ragged_dot`` with every group holding ``r`` rows, ``r`` from 4 to 512 —
if XLA's kernel computes a 512-row tile per group, its time is flat there.
Beside ``moe_gmm`` at each row tile, ``megablox`` is JAX's own Pallas
grouped matmul at the rule's row tile and the whole contraction.

Each case runs ``--reps`` times under one ``jax.profiler`` capture; a row
gives the device microseconds a call, all its ops (``us``) and its Mosaic
kernels alone (``kernel_us``), beside the least time its bytes (the touched
groups' weights once, the rows in and out) and its FLOPs (the real rows)
need at the chip's published peaks. Results also land in
``chiprun_out/probe_grouped_matmul.json``. ``--rehearsal`` is the same
control flow tiny through the interpreter, every time null.
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import trace_reduce
from jax.experimental.pallas.ops.tpu.megablox import gmm as megablox_gmm

from deepspeed_tpu.ops import grouped_matmul as gm
from deepspeed_tpu.utils.compile_cache import configure_compile_cache

ROUTED, HELD, K_TOKEN = 512, 128, 22
LATENT, FFN = 1024, 2688
HBM, PEAK = 819e9, 197e12            # v5e published (benchmarks/peaks.json)
MATMULS = {"up": (LATENT, FFN), "down": (FFN, LATENT)}
# (name, slots, alive)
ROUTED_SHAPES = [("decode", 128, 73), ("prefill256", 256, 256),
                 ("prefill1024", 1024, 1024), ("prefill2048", 2048, 2048)]
FLAT_ROWS = (4, 16, 64, 128, 256, 512)
ROW_TILES = (16, 32, 64, 128)


def routed_sizes(rng, slots, alive, held, routed=ROUTED, k=K_TOKEN):
    """Rows a held expert gets when ``alive`` of ``slots`` rows each choose
    ``k`` of ``routed`` experts at random."""
    chosen = np.stack([rng.choice(routed, k, replace=False)
                       for _ in range(alive)]).reshape(-1)
    return np.bincount(chosen[chosen < held], minlength=held).astype(np.int32)


def timed(fn, args, reps, rehearsal):
    """``(us, kernel_us)``: device microseconds a call, all ops and the
    Mosaic calls alone (mean over ``reps``)."""
    jax.block_until_ready(fn(*args))                 # compile, warm
    if rehearsal:
        return None, None
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(reps):
            r = fn(*args)
        jax.block_until_ready(r)
        jax.profiler.stop_trace()
        path = next(os.path.join(root, f) for root, _, files in os.walk(tmp)
                    for f in files if f.endswith(".xplane.pb"))
        ops = [op for dev in trace_reduce.load_xplane(path).devices.values()
               for op in dev]
    total = sum(op.end - op.start for op in ops)
    mosaic = sum(op.end - op.start for op in ops if trace_reduce.is_mosaic(op))
    return 1e6 * total / reps, 1e6 * mosaic / reps


def case(rng, label, sizes, m, matmul, path, tm, reps, rehearsal):
    k, n = MATMULS[matmul]
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((len(sizes), k, n)) * 0.02,
                      jnp.bfloat16)
    group_sizes = jnp.asarray(sizes)
    if path == "ragged_dot":
        fn = jax.jit(jax.lax.ragged_dot)
    elif path == "megablox":             # jax's own, whole contraction
        fn = jax.jit(lambda a, b, s: megablox_gmm(
            a, b, s, jnp.bfloat16, (tm, k, n), None, None, False, rehearsal))
    else:
        fn = jax.jit(lambda a, b, s: gm.grouped_matmul(a, b, s, tm,
                                                       rehearsal))
    rows, touched = int(sizes.sum()), int((sizes > 0).sum())
    need_bytes = touched * k * n * 2 + rows * (k + n) * 2
    need_flops = 2 * rows * k * n
    row = {"shape": label, "matmul": matmul, "path": path, "tm": tm,
           "m": m, "rows": rows, "touched": touched,
           "rows_per_touched": round(rows / max(touched, 1), 2),
           "least_us": round(1e6 * max(need_bytes / HBM, need_flops / PEAK),
                             1)}
    if path == "moe_gmm":
        row["rows_computed"] = tm * int(gm.row_tiles_visited(group_sizes, tm))
    try:
        us, kernel_us = timed(fn, (lhs, rhs, group_sizes), reps, rehearsal)
        row.update(us=us and round(us, 1), kernel_us=kernel_us and round(
            kernel_us, 1))
        if us:
            row["roofline_pct"] = round(100 * row["least_us"] / us, 1)
    except Exception as e:  # noqa: BLE001 — a tiling Mosaic refuses is a row
        row["error"] = f"{type(e).__name__}: {e}"[:300]
    print(json.dumps(row), flush=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default="probe_grouped_matmul.json",
                    help="file name under chiprun_out/")
    a = ap.parse_args()
    if not a.rehearsal and jax.devices()[0].platform != "tpu":
        sys.exit(f"needs a TPU, found {jax.devices()[0].platform} "
                 "(--rehearsal runs the control flow on the CPU)")
    configure_compile_cache()
    global HELD, ROUTED_SHAPES, FLAT_ROWS, MATMULS
    if a.rehearsal:
        HELD, a.reps = 8, 1
        MATMULS = {"up": (32, 48), "down": (48, 32)}
        ROUTED_SHAPES, FLAT_ROWS = [("decode", 64, 9)], (4, 16)
    rng = np.random.default_rng(0)
    rows = []
    # the account: XLA's kernel against rows a group, every group full
    for r in FLAT_ROWS:
        sizes = np.full(HELD, r, np.int32)
        for path, tm in [("ragged_dot", None),
                         ("moe_gmm", gm.ROW_TILE)]:
            rows.append(case(rng, f"flat{r}", sizes, HELD * r, "up", path,
                             tm, a.reps, a.rehearsal))
    for label, slots, alive in ROUTED_SHAPES:
        sizes = routed_sizes(rng, slots, alive, HELD)
        for matmul in MATMULS:
            rows.append(case(rng, label, sizes, slots * K_TOKEN, matmul,
                             "ragged_dot", None, a.reps, a.rehearsal))
            for tm in ROW_TILES:
                rows.append(case(rng, label, sizes, slots * K_TOKEN, matmul,
                                 "moe_gmm", tm, a.reps, a.rehearsal))
            tm = gm.row_tile(slots * K_TOKEN / ROUTED) or gm.ROW_TILE
            rows.append(case(rng, label, sizes, slots * K_TOKEN, matmul,
                             "megablox", tm, a.reps, a.rehearsal))
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", a.out), "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "rows": rows}, f,
                  indent=1)


if __name__ == "__main__":
    main()
