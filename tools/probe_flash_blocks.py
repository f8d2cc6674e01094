"""Device time of the flash kernels, one by one, per block pair.

    chiprun -- python tools/probe_flash_blocks.py            # the chip
    JAX_PLATFORMS=cpu python tools/probe_flash_blocks.py --rehearsal

For each shape (the two training cells' attention calls by default) and each
``block_q x block_k`` pair, ``flash_fwd`` and ``flash_bwd`` run ``--reps``
times each under one ``jax.profiler`` capture; the table is the median
device duration of each kernel's Mosaic call, in microseconds. Copied into
the ``tools/`` of a checkout that still has two backward kernels it times
``dq`` and ``dkv`` in place of ``bwd`` (taken apart by asking the backward
for one gradient: XLA drops the other call). Results also land in
``chiprun_out/probe_flash_blocks.json`` (``--out`` names another file there).
``--rehearsal`` is the same control flow tiny through the interpreter, every
time null.
"""

import argparse
import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import trace_reduce
from deepspeed_tpu.ops.transformer import flash_attention as fa
from deepspeed_tpu.utils.compile_cache import configure_compile_cache

# (batch*heads, seq, head_dim): gpt2m-train-s1024 (micro 4 x 16 heads) and
# glm47flash-train-s4096 (micro 1 x 20 heads)
CELL_SHAPES = [(64, 1024, 64), (20, 4096, 256)]
PAIRS = [(512, 1024), (512, 512), (512, 256), (256, 512), (256, 256)]


def kernels(bh, seq, d, block_q, block_k, interpret):
    """Each kernel as a jitted call that runs that one Mosaic call."""
    scale = 1.0 / d ** 0.5
    seed = jnp.zeros((1,), jnp.int32)

    def fwd(q, k, v):
        return fa._flash_forward(q, k, v, None, True, scale, block_q, block_k,
                                 interpret, seed=seed)

    def bwd(q, k, v, out, lse, g):
        return fa._flash_backward((q, k, v, None, out, lse, seed), g, True,
                                  scale, block_q, block_k, interpret)

    if hasattr(fa, "_bwd_dq_kernel"):       # a checkout before the one kernel
        return {"fwd": jax.jit(fwd),
                "dq": jax.jit(lambda *a: bwd(*a)[0]),
                "dkv": jax.jit(lambda *a: bwd(*a)[1:])}
    return {"fwd": jax.jit(fwd), "bwd": jax.jit(bwd)}


def mosaic_us(trace_dir):
    """Durations (us) of the capture's Mosaic calls, in time order."""
    paths = [os.path.join(root, f) for root, _, files in os.walk(trace_dir)
             for f in files if f.endswith(".xplane.pb")]
    trace = trace_reduce.load_xplane(paths[0])
    ops = sorted((op for dev in trace.devices.values() for op in dev
                  if trace_reduce.is_mosaic(op)), key=lambda op: op.start)
    return [(op.end - op.start) * 1e6 for op in ops]


def measure(bh, seq, d, block_q, block_k, reps, rehearsal):
    rng = np.random.default_rng(0)
    q, k, v, g = (jnp.asarray(rng.standard_normal((bh, seq, d)), jnp.bfloat16)
                  for _ in range(4))
    fns = kernels(bh, seq, d, block_q, block_k, interpret=rehearsal)
    out, lse = fns["fwd"](q, k, v)
    args = {name: (q, k, v) if name == "fwd" else (q, k, v, out, lse, g)
            for name in fns}
    for name, fn in fns.items():                       # compile, warm
        jax.block_until_ready(fn(*args[name]))
    if rehearsal:
        return {name: None for name in fns}
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for name, fn in fns.items():
            for _ in range(reps):
                r = fn(*args[name])
            jax.block_until_ready(r)
        jax.profiler.stop_trace()
        us = mosaic_us(tmp)
    assert len(us) == len(fns) * reps, (len(us), len(fns), reps)
    return {name: statistics.median(us[i * reps:(i + 1) * reps])
            for i, name in enumerate(fns)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--shape", action="append", default=[],
                    help="bh,seq,head_dim (repeatable; default: the cells')")
    ap.add_argument("--pair", action="append", default=[],
                    help="block_q,block_k (repeatable; default: the five)")
    ap.add_argument("--out", default="probe_flash_blocks.json",
                    help="file name under chiprun_out/")
    a = ap.parse_args()
    if not a.rehearsal and jax.devices()[0].platform != "tpu":
        sys.exit(f"needs a TPU, found {jax.devices()[0].platform} "
                 "(--rehearsal runs the control flow on the CPU)")
    configure_compile_cache()
    shapes = [tuple(map(int, s.split(","))) for s in a.shape] or CELL_SHAPES
    pairs = [tuple(map(int, p.split(","))) for p in a.pair] or PAIRS
    if a.rehearsal:
        shapes, a.reps = [(2, 512, 64)], 1
        pairs = [(256, 512), (256, 256)]
    rows = []
    for bh, seq, d in shapes:
        for bq, bk in pairs:
            bq, bk = fa.fit_block(bq, seq), fa.fit_block(bk, seq)
            row = {"bh": bh, "seq": seq, "head_dim": d, "block_q": bq,
                   "block_k": bk, "device": jax.devices()[0].device_kind}
            try:
                row["us"] = measure(bh, seq, d, bq, bk, a.reps, a.rehearsal)
            except Exception as e:  # noqa: BLE001 — a pair Mosaic refuses is a row
                row["error"] = f"{type(e).__name__}: {e}"[:300]
            if hasattr(fa, "causal_walk"):    # a parent checkout has none
                walk = fa.causal_walk(seq, seq, bq, bk)
                row.update(visited=walk.visited, crossed=walk.crossed,
                           total=walk.total)
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", a.out), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
