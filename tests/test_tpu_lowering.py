"""Every Pallas kernel compiles for a TPU v5e — checked without a chip.

The Pallas interpreter (what the CPU parity suites run) accepts any block
shape; Mosaic does not. libtpu can describe a v5e topology and compile
for it with no device present, so this file lowers and compiles each
entry of ``ops/kernel_cases.py`` with ``interpret=False`` (~1 s each) —
the test that would have caught two serving kernels that were never
compilable. It says nothing about speed, and nothing about numerics on
the chip (``chip_smoke.py`` does that).
"""

import functools

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
    assert compiled.as_text().count("tpu_custom_call") >= 3   # fwd, dq, dkv
