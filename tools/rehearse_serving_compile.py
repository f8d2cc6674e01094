"""Compile a serving cell's programs at their real size for a described
TPU v5e, without a chip (``benchmarks/rehearse_compile.py`` does the same
for the training cells' step). Run by hand before chip time is spent:

    JAX_PLATFORMS=cpu python3 tools/rehearse_serving_compile.py --workload <cell> [--buckets 2048,256] [--reference]

It builds the cell's ``ServeEngine`` on shapes (no weight and no pool is
made), lowers the decode program, the prefill and pack programs of the
given prompt buckets and, with ``--reference``, the benchmark's float32
reference over ``[4, max_total_len]`` ids as ``check_against_reference``
calls it, compiles each for one described chip, and prints the compiler's
memory account beside what the engine keeps resident (weights, pools,
state). What the compiler refuses here it would refuse on the chip. Nothing
runs: no result, no time.
"""

import argparse
import functools
import os
import sys
import time
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402
from jax.sharding import SingleDeviceSharding           # noqa: E402

GIB = 2.0 ** 30


def shapes_on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def nbytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree_util.tree_leaves(tree))


def abstract_engine(config, family, one_chip, devices):
    """The cell's ``ServeEngine`` with shapes where arrays would be."""
    import deepspeed_tpu.serving.engine as serving_engine
    import deepspeed_tpu.utils.platform as platform_mod
    from deepspeed_tpu.config.config import ServingConfig
    from deepspeed_tpu.inference.engine import (InferenceConfig,
                                                InferenceEngine)
    from deepspeed_tpu.telemetry import RecompileDetector

    # on_tpu() reads jax.devices() through its module's own `jax` name
    platform_mod.jax = types.SimpleNamespace(devices=lambda: devices)
    model, _ = family.build_model(config)
    rngs = {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)}
    params = shapes_on(jax.eval_shape(
        lambda r: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16),
            model.init(r, family.example_batch())["params"]), rngs),
        one_chip)
    engine = object.__new__(InferenceEngine)
    engine.module, engine.model_cfg = model, model.cfg
    engine.config = InferenceConfig(dtype=jnp.bfloat16)
    engine.params = params
    engine.recompile_detector = RecompileDetector(enabled=False)
    real = serving_engine.init_serving_state
    serving_engine.init_serving_state = lambda *a, **kw: shapes_on(
        jax.eval_shape(lambda: real(*a, **kw)), one_chip)
    try:
        srv = serving_engine.ServeEngine(
            engine, config=ServingConfig.from_dict(config["serving"]))
    finally:
        serving_engine.init_serving_state = real
    return srv, params


def report(label, lowered, t0):
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    text = compiled.as_text()
    print(f"  {label}: arguments {mem.argument_size_in_bytes / GIB:.2f} GiB, "
          f"outputs {mem.output_size_in_bytes / GIB:.2f}, aliased "
          f"{mem.alias_size_in_bytes / GIB:.2f}, temporaries "
          f"{mem.temp_size_in_bytes / GIB:.2f}; in all {total / GIB:.2f} "
          f"GiB; Mosaic calls {text.count('tpu_custom_call')}, grouped "
          f"matmuls {text.count('ragged-dot')}; "
          f"{time.perf_counter() - t0:.0f}s (this host's CPU)", flush=True)
    return mem


def main(argv=None) -> int:
    from benchmarks.harness import load_module, open_cell

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--buckets", default="",
                    help="prompt buckets to compile (default: the largest)")
    ap.add_argument("--reference", action="store_true")
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    one_chip = SingleDeviceSharding(devices[0])
    like = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    _, cell, config, traffic = open_cell(args.workload)
    if traffic["driver"] != "serve_open_loop":
        sys.exit(f"{cell['name']} is no serving cell")
    family = load_module("families", config["family"])
    srv, params = abstract_engine(config, family, one_chip, devices)
    slots, mb = srv.scfg.max_batch_size, srv.max_blocks
    print(f"{cell['name']}: resident on the chip: weights "
          f"{nbytes(params) / GIB:.2f} GiB, pools and state "
          f"{nbytes(srv._pools) / GIB:.2f} GiB; decode attention "
          f"{srv._attn_impl!r}", flush=True)
    key = like((2,), jnp.uint32)
    i32 = lambda *shape: like(shape, jnp.int32)

    t0 = time.perf_counter()
    extra = {} if srv._all_kv else {"alive": like((slots,), jnp.bool_)}
    live = ()
    if srv._attn_impl == "gather":
        live = (i32(srv._live_chunks, srv.LIVE_CHUNK_RUNS,
                    srv.LIVE_RUN_BLOCKS + 2), i32())
    decode = jax.jit(functools.partial(srv._decode_impl,
                                       attn_impl=srv._attn_impl),
                     donate_argnums=(1,))
    report("decode", decode.lower(params, srv._pools, i32(slots, mb),
                                  i32(slots), i32(slots), key, *live,
                                  **extra), t0)
    buckets = [int(b) for b in args.buckets.split(",") if b] or [
        srv._bucket_of(traffic["prompt_len"]["max"])]
    for bucket in buckets:
        t0 = time.perf_counter()
        prefill = jax.jit(functools.partial(srv._prefill_impl,
                                            bucket=bucket))
        lowered = prefill.lower(params, i32(1, bucket), i32(), key)
        report(f"prefill of bucket {bucket}", lowered, t0)
        _tok, _last, ks, vs, states, _counters = shapes_on(
            lowered.out_info, one_chip)
        t0 = time.perf_counter()
        blocks = i32(bucket // srv.block_size)
        report(f"pack of bucket {bucket}", srv._pack_jit.lower(
            srv._pools, blocks, ks, vs, i32(), states,
            kinds=srv._kinds), t0)
    if args.reference:
        t0 = time.perf_counter()
        width = traffic["max_total_len"]
        logits = jax.jit(family.reference_logits(config))
        report(f"reference over [4, {width}] ids",
               logits.lower(params, i32(4, width)), t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
