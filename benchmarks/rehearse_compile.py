"""Compile a training cell's whole optimizer step at its real size for a
described TPU v5e, without a chip. Run by hand before chip time is spent:

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_compile.py [--workload <cell>]

The TPU's compiler is installed here and compiles for a topology that is
described and not attached (``v5e:2x2``). What it refuses here (a program
that does not fit 16 GB, a kernel that cannot be partitioned) it would
refuse on the chip, and here it costs no chip time. It prints the step's
``memory_analysis()``, its collectives and Mosaic calls, and how long the
compile took. Nothing runs, so it says nothing about results or times, and
a compile that passes is not a chip run. ``--reference`` compiles the three
programs of the plain reference the cell is held against instead
(``drivers/train_steps.py``), for one chip: what they need has to fit.

The engine places its own parameters, which a described device cannot
hold. So this script, and not the program, stands in for the two places
that would touch a device: the platform probe (``on_tpu`` answers as the
chip would) and ``TPUEngine._init_state`` (shapes with shardings in place
of arrays).
"""

import argparse
import os
import re
import sys
import time
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax                                              # noqa: E402
import numpy as np                                      # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec   # noqa: E402

from benchmarks.harness import load_json, load_module, open_cell  # noqa: E402


def abstract_engine(cell, config, traffic, family, devices):
    """The cell's engine with shapes for state, on described devices."""
    import deepspeed_tpu
    import deepspeed_tpu.utils.platform as platform_mod
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.runtime.engine import TPUEngine, TrainState

    # on_tpu() reads jax.devices() through its module's own `jax` name
    platform_mod.jax = types.SimpleNamespace(devices=lambda: devices)

    mesh = build_mesh(data=cell["chips"], devices=devices[:cell["chips"]])
    model, _ = family.build_model(config)
    rngs = {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)}
    params = jax.eval_shape(
        lambda r: model.init(r, family.example_batch())["params"], rngs)

    real_init_state = TPUEngine._init_state

    def init_state(self, params, rng_seed):
        shapes = jax.eval_shape(
            lambda p: real_init_state(self, p, rng_seed), params)
        rep = PartitionSpec()
        specs = TrainState(
            step=rep, micro_step=rep, params=self.param_specs,
            opt_state=self.opt_state_specs_full, grad_acc=self.grad_specs,
            loss_scale=jax.tree_util.tree_map(lambda _: rep,
                                              shapes.loss_scale),
            skipped_steps=rep, rng=rep)
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
            shapes, specs)

    TPUEngine._init_state = init_state
    try:
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, params=params, mesh=mesh,
            config=load_module("drivers", "train_steps").engine_config(
                config, traffic))
    finally:
        TPUEngine._init_state = real_init_state
    return engine, mesh


def compile_step(cell, config, traffic, family, devices):
    engine, mesh = abstract_engine(cell, config, traffic, family, devices)
    shape = (traffic["gradient_accumulation_steps"],
             traffic["micro_batch_per_chip"] * cell["chips"],
             traffic["seq_len"])
    example = family.make_batch(np.zeros(shape, np.int32), traffic,
                                np.random.default_rng(0))
    spec = PartitionSpec(None, *tuple(engine.batch_spec))
    batch = {k: jax.ShapeDtypeStruct(
        v.shape, v.dtype,
        sharding=NamedSharding(mesh, PartitionSpec(*tuple(spec)[:v.ndim])))
        for k, v in example.items()}
    lr = jax.ShapeDtypeStruct((), np.float32,
                              sharding=NamedSharding(mesh, PartitionSpec()))
    t0 = time.perf_counter()
    lowered = engine._train_step.lower(engine.state, batch, lr)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    counts = {op: len(re.findall(rf"\b{op}(?:-start)?\(", text))
              for op in ("all-gather", "reduce-scatter", "all-reduce",
                         "all-to-all", "collective-permute")}
    gib = lambda b: f"{b / 2 ** 30:.2f} GiB"
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{cell['name']}: compiled for {cell['chips']} described "
          f"{devices[0].device_kind!r} chip(s); trace+lower {t1 - t0:.1f}s, "
          f"compile {t2 - t1:.1f}s (this host's CPU, not the chip's)")
    print(f"  per device: arguments {gib(mem.argument_size_in_bytes)}, "
          f"outputs {gib(mem.output_size_in_bytes)}, aliased "
          f"{gib(mem.alias_size_in_bytes)}, temporaries "
          f"{gib(mem.temp_size_in_bytes)}, program "
          f"{gib(mem.generated_code_size_in_bytes)}; arguments + outputs "
          f"- aliased + temporaries = {gib(total)}")
    print(f"  Mosaic calls {text.count('tpu_custom_call')}, collectives "
          f"{ {k: v for k, v in counts.items() if v} or 'none'}")
    return total


def compile_reference(cell, config, traffic, family, devices):
    """Memory of the reference's forward, gradient and optimizer step at
    the cell's real size, on one described chip."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    train_steps = load_module("drivers", "train_steps")
    one = SingleDeviceSharding(devices[0])
    like = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)
    model, _ = family.build_model(config)
    rngs = {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)}
    params = jax.tree_util.tree_map(
        lambda x: like(x.shape, jnp.float32), jax.eval_shape(
            lambda r: model.init(r, family.example_batch())["params"], rngs))
    opt = config["train_engine"]["optimizer"]
    first_step = load_module(
        "reference", "optimizers." + opt["type"].lower()).first_step
    forward, gradient, step = train_steps.reference_programs(
        family.reference_nll(config),
        lambda p, g: first_step(p, g, **opt["params"]))
    size = traffic["reference_chunk_sequences"]
    gas, rows = (traffic["gradient_accumulation_steps"],
                 traffic["micro_batch_per_chip"] * cell["chips"])
    example = family.make_batch(
        np.zeros((gas, rows, traffic["seq_len"]), np.int32), traffic,
        np.random.default_rng(0))
    batch = {k: like((gas, rows // size, size) + v.shape[2:], v.dtype)
             for k, v in example.items()}
    counts = like((gas, rows // size), jnp.int32)
    for label, fn, args in (("forward", forward, (params, batch)),
                            ("gradient", gradient, (params, batch, counts)),
                            ("optimizer step", step, (params, params))):
        t0 = time.perf_counter()
        mem = fn.lower(*args).compile().memory_analysis()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        print(f"{cell['name']}: reference {label}, chunks of {size} "
              f"sequences: arguments {mem.argument_size_in_bytes / 2 ** 30:.2f}"
              f" GiB, temporaries {mem.temp_size_in_bytes / 2 ** 30:.2f} GiB, "
              f"in all {total / 2 ** 30:.2f} GiB; compile "
              f"{time.perf_counter() - t0:.1f}s (this host's CPU)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append",
                    help="a training cell (default: all of them)")
    ap.add_argument("--reference", action="store_true",
                    help="compile the plain reference's programs instead")
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    for name in [w["name"] for w in load_json(
            ROOT, "BENCHMARK.json")["workloads"]]:
        if args.workload and name not in args.workload:
            continue
        _, cell, config, traffic = open_cell(name)
        if traffic["driver"] != "train_steps":
            if args.workload:
                sys.exit(f"{name}: only train_steps cells have a step to "
                         f"compile")
            continue
        family = load_module("families", config["family"])
        (compile_reference if args.reference else compile_step)(
            cell, config, traffic, family, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
