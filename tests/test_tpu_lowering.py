"""Every Pallas kernel compiles for a TPU v5e — checked without a chip.

The Pallas interpreter (what the CPU parity suites run) accepts any block
shape; Mosaic does not. libtpu can describe a v5e topology and compile
for it with no device present, so this file lowers and compiles each
entry of ``ops/kernel_cases.py`` with ``interpret=False`` (~1 s each) —
the test that would have caught two serving kernels that were never
compilable. It says nothing about speed, and nothing about numerics on
the chip (``chip_smoke.py`` does that).

The compiled text also shows what the compiler does to a program's
ARGUMENTS: the serving programs are held to reading and writing the paged
KV pool in the layout it arrives in, and the default decode to reading
the list of live blocks and nothing of the reserved window's size.
"""

import functools
import math
import re

import jax
import numpy as np
import pytest

from deepspeed_tpu.ops.kernel_cases import kernel_cases


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any libtpu refusal is a skip
        pytest.skip(f"libtpu cannot describe a v5e topology here: "
                    f"{type(e).__name__}: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def v5e_sharding(v5e_devices):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_devices[0])


@pytest.mark.parametrize("case", kernel_cases(), ids=lambda c: c.name)
def test_kernel_compiles_for_v5e(case, v5e_sharding):
    args = case.make_args(np.random.default_rng(0))
    abstract = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=v5e_sharding), args)
    compiled = jax.jit(functools.partial(case.run, False)).lower(
        *abstract).compile()
    # the kernel really went to Mosaic, not to the interpreter's XLA ops
    assert "tpu_custom_call" in compiled.as_text(), case.name


@pytest.mark.parametrize("axes,spec", [
    ({"data": 4}, ("data",)),
    ({"data": 2, "model": 2}, ("data", None, "model")),
    ({"pipe": 2, "data": 2}, ("data",)),
], ids=["data4", "data2-model2", "pipe2-data2"])
def test_flash_attention_compiles_on_a_four_chip_mesh(axes, spec, v5e_devices,
                                                      monkeypatch):
    """GSPMD refuses to partition a Mosaic call ("wrap the call in a
    shard_map"): under a multi-device jit — what every real multi-chip
    trainer is — ``attention()`` must put the flash kernel in a fully
    manual region. Interpret mode lowers to plain XLA ops, so the CPU mesh
    can never see this. Forward and backward, on four described chips."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import deepspeed_tpu.ops.transformer.attention as attention_mod
    import deepspeed_tpu.ops.transformer.flash_attention as flash_mod
    from deepspeed_tpu.parallel.mesh import ALL_AXES, pinned_mesh

    for mod in (attention_mod, flash_mod):      # dispatch as if on the chip
        monkeypatch.setattr(mod, "on_tpu", lambda: True)
    mesh = Mesh(np.array(v5e_devices).reshape(
        [axes.get(a, 1) for a in ALL_AXES]), ALL_AXES)
    x = jax.ShapeDtypeStruct(
        (8, 512, 4, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, PartitionSpec(*spec)))

    def loss(q, k, v):
        with pinned_mesh(mesh):     # what TPUEngine does around model code
            out = attention_mod.attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2   # fwd, bwd


# The benchmark's serving cell (gpt2m-serve-chat): 4097 blocks x 16
# positions, 16 heads x 64, 64 slots x 64 table columns.
POOL_BLOCKS, POOL_BLOCK, SLOTS, TABLE = 4097, 16, 64, 64
# `%name = dtype[dims]{minor_to_major:tiles} opcode(`, anywhere in a module
HLO_RESULT = re.compile(
    r"= \w+\[([\d,]+)\]\{([\d,]+)[^}]*\} ([\w\-]+)\(")


def _lower_serving_program(program, int8, sharding, layers=2):
    """The engine's decode program (the body of ``ServeEngine._decode_impl``
    over the list of live blocks) or its pack program, lowered for the
    described chip at gpt2-medium's width with ``layers`` of its 24 layers:
    each layer's pools meet the same scatter, gather and donation."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.gpt import make_gpt
    from deepspeed_tpu.serving.engine import ServeEngine
    from deepspeed_tpu.serving.kv_cache import (PagedLayerCache,
                                                init_paged_pools,
                                                pack_prefill)

    model, cfg = make_gpt("gpt2-medium", num_layers=layers, dropout_rate=0.0)
    run, group = ServeEngine.LIVE_RUN_BLOCKS, ServeEngine.LIVE_CHUNK_RUNS

    def on_chip(tree, dtype=None):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, dtype or a.dtype,
                                           sharding=sharding), tree)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)

    pools = on_chip(jax.eval_shape(lambda: init_paged_pools(
        cfg, POOL_BLOCKS, POOL_BLOCK, int8=int8, dtype=jnp.bfloat16)))

    if program == "decode":
        params = on_chip(jax.eval_shape(
            lambda rng: model.init(
                rng, {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"],
            jax.random.PRNGKey(0)), jnp.bfloat16)

        def decode(params, pools, bt, pos, toks, live, n_chunks):
            cache = tuple(PagedLayerCache(*pools[i], bt, pos, POOL_BLOCK,
                                          "bfloat16", live=live,
                                          n_chunks=n_chunks)
                          for i in range(layers))
            out = model.apply(
                {"params": params},
                {"input_ids": toks[:, None], "position_ids": pos[:, None]},
                deterministic=True, cache=cache, pos=None)
            return out["logits"][:, -1], tuple(c.pools for c in out["cache"])

        lowered = jax.jit(decode, donate_argnums=(1,)).lower(
            params, pools, ints(SLOTS, TABLE), ints(SLOTS), ints(SLOTS),
            ints(SLOTS * (TABLE // run) // group, group, run + 2), ints())
    else:                           # a 256-token prompt bucket
        stack = jax.ShapeDtypeStruct(
            (layers, 256, cfg.num_heads, cfg.head_dim), jnp.bfloat16,
            sharding=sharding)
        lowered = jax.jit(pack_prefill, donate_argnums=(0,)).lower(
            pools, ints(256 // POOL_BLOCK), stack, stack)
    return lowered.compile().as_text(), cfg


def _results(hlo, size, opcodes=None):
    """``(opcode, layout)`` of the results in ``hlo`` with ``size``
    elements, of ``opcodes`` alone if given."""
    return [(op, [int(i) for i in layout.split(",")])
            for dims, layout, op in HLO_RESULT.findall(hlo)
            if (opcodes is None or op in opcodes)
            and math.prod(map(int, dims.split(","))) == size]


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("program", ["decode", "pack"])
def test_serving_programs_take_the_pool_as_it_is_stored(program, int8,
                                                        v5e_sharding):
    """A pool stored ``[N, BS, H, 64]`` reaches a TPU program block-minor
    (``{0,3,2,1}``), and every program that scatters into or gathers from
    it then copies the WHOLE pool to row-major on entry and back on exit
    (``serving/kv_cache.py``'s module docstring has the why and what it
    cost on the chip). Stored ``[N, BS, H * D]`` it arrives row-major and
    is updated in place. So: no ``copy`` of a K/V pool's size anywhere in
    the compiled module, and every K/V pool parameter row-major. The int8
    SCALE pools ``[N, BS, H]`` are exempt: they do arrive block-minor and
    are copied, at 1/32 of the bytes (``init_paged_pools`` says why they
    stay)."""
    layers = 2
    text, cfg = _lower_serving_program(program, int8, v5e_sharding, layers)
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    pool_size = POOL_BLOCKS * POOL_BLOCK * cfg.num_heads * cfg.head_dim
    pool_params = _results(entry, pool_size, ("parameter",))
    assert len(pool_params) == 2 * layers            # the regex still reads
    assert all(layout == sorted(layout, reverse=True)
               for _, layout in pool_params), pool_params
    copies = _results(text, pool_size, ("copy",))   # fused ones included
    assert not copies, f"{len(copies)} whole-pool copies: {copies}"


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_the_default_decode_reads_the_live_blocks_and_no_window(
        int8, v5e_sharding):
    """What PR 8's decode program did whatever was live: gather ``slots x
    max_blocks`` blocks of keys and of values (64 x 64 x 16 positions x
    1024 lanes each), reshape them to ``[.., H, D]`` and attend over 1024
    masked keys a row: nine tenths of a 90 ms step at a ninth live
    (PERF.md section 6, PR 27). The default decode walks the list of live
    blocks in a loop with a traced trip count, a chunk at a time. So, in
    the text compiled for the chip: one ``while`` per layer; NOTHING of
    the window's size (no result of any op has the element count of the
    keys gathered over 64 x 1024 positions); and of the pool's size no
    copy, reshape, transpose or gather, only the pool itself, written in
    place."""
    layers = 2
    text, cfg = _lower_serving_program("decode", int8, v5e_sharding, layers)
    assert len(re.findall(r" while\(", text)) == layers
    window = SLOTS * TABLE * POOL_BLOCK * cfg.num_heads * cfg.head_dim
    found = _results(text, window)
    assert not found, f"results of the window's size: {found[:4]}"
    # a chunk of the list IS gathered: the search reads such results
    from deepspeed_tpu.serving.engine import ServeEngine
    chunk = (ServeEngine.LIVE_CHUNK_RUNS * ServeEngine.LIVE_RUN_BLOCKS
             * POOL_BLOCK * cfg.num_heads * cfg.head_dim)
    assert len(_results(text, chunk, ("fusion",))) >= 2 * layers
    pool_size = POOL_BLOCKS * POOL_BLOCK * cfg.num_heads * cfg.head_dim
    moved = _results(text, pool_size,
                     ("copy", "reshape", "transpose", "gather"))
    assert not moved, moved
    # fused gathers bear the name of what they fuse
    assert not [line for line in text.splitlines()
                if "/gather\"" in line and f"[{POOL_BLOCKS}," in
                line.split(" fusion(")[0]]


def test_the_dropless_expert_layer_at_the_cells_size(v5e_sharding):
    """``glm47flash-train-s4096``'s expert layer, forward and backward, at
    its real size (4096 tokens, 8 of 64 experts held, 4 a token): the
    held experts' three matmuls and their six transposes each reach the
    chip as XLA's grouped-matmul kernel (named ``ragged-dot-*``, which
    ``benchmarks/moe_trace.py`` reads by), not as a dense product per
    expert over the whole buffer; and no row travels by a scatter: the
    only scatters are the inverse permutation (int32) and the router's
    ``take_along_axis`` transpose (64 scores a token)."""
    import jax.numpy as jnp
    from deepspeed_tpu.moe.dropless import DroplessMoE, DroplessMoEConfig

    layer = DroplessMoE(DroplessMoEConfig(
        hidden_size=2048, expert_intermediate=1536, n_routed_experts=64,
        n_held_experts=8, experts_per_token=4, shared_intermediate=1536,
        routed_scaling_factor=1.8))
    on_chip = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                                 sharding=v5e_sharding)
    params = jax.tree_util.tree_map(
        lambda a: on_chip(a.shape), jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, 2048)))["params"]))

    def loss(p, x):
        y, counters = layer.apply({"params": p}, x)
        return y.astype(jnp.float32).sum(), counters

    hlo = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
                  ).lower(params, on_chip((1, 4096, 2048))).compile(
                  ).as_text()
    calls = re.findall(r"%(ragged-dot-[a-z]+)[.\d]* = ", hlo)
    assert calls.count("ragged-dot-none") == 9, sorted(set(calls))
    scattered = re.findall(r"= (\w+)\[([\d,]*)\][^=]* scatter\(", hlo)
    assert sorted(scattered) == [("f32", "262144"), ("s32", "16384")], \
        scattered


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_the_nemotron_h_mixers_at_the_cells_widths(phase, v5e_sharding):
    """``nemotron3s-serve-chat``'s three mixers at their published widths
    (one layer of each, fewer slots and a shorter prompt than the cell:
    the widths are what Mosaic and the grouped matmul tile by): the
    Mamba-2 recurrence (one step on the slots' float32 state; the chunked
    scan of a prompt), grouped-query attention over the paged window, and
    the latent experts, whose two matmuls reach the chip as XLA's
    grouped-matmul kernel at decode as in training. The whole cell at its
    real size: ``tools/rehearse_serving_compile.py``."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.nemotron_h import NemotronH, NemotronHConfig
    from deepspeed_tpu.serving.kv_cache import (PagedLayerCache,
                                                RecurrentLayerState,
                                                init_serving_state)

    model = NemotronH(NemotronHConfig(pattern="EM*", vocab_size=4096,
                                      n_held_experts=16))
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=v5e_sharding), tree)
    params = on_chip(jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), model.init(
            jax.random.PRNGKey(0),
            {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"])))
    slots, blocks, bs = 8, 16, 16
    if phase == "prefill":
        def run(p, ids, length):
            out = model.serve_prefill(p, ids, length)
            return out["logits"][:, -1], out["cache"], out["counters"]
        args = (params, on_chip(jax.ShapeDtypeStruct((1, 256), jnp.int32)),
                on_chip(jax.ShapeDtypeStruct((), jnp.int32)))
    else:
        pools = on_chip(jax.eval_shape(lambda: init_serving_state(
            model.serving_cache_spec(), slots * blocks + 1, bs, slots)))

        def run(p, pools, bt, pos, toks, live):
            cache = (None, RecurrentLayerState(pools[1], live),
                     PagedLayerCache(*pools[2], bt, pos, bs, "bfloat16",
                                     "window"))
            out = model.serve_decode(p, toks[:, None], pos[:, None], cache,
                                     live)
            return (out["logits"], out["cache"][1].arrays,
                    out["cache"][2].pools, out["counters"])
        ints = lambda *shape: on_chip(jax.ShapeDtypeStruct(shape, jnp.int32))
        args = (params, pools, ints(slots, blocks), ints(slots), ints(slots),
                on_chip(jax.ShapeDtypeStruct((slots,), jnp.bool_)))
    hlo = jax.jit(run).lower(*args).compile().as_text()
    assert len(set(re.findall(r"%(ragged-dot-none[.\d]*) = ", hlo))) == 2
