"""``ops/grouped_matmul.py`` (``moe_gmm``) against ``jax.lax.ragged_dot``
on the CPU through the Pallas interpreter, and the rule by which the
held experts take it (``moe/dropless.py: _grouped_dot``). That it compiles
for a v5e at the serving cell's shapes: ``tests/test_tpu_lowering.py``."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.moe.dropless as dropless
from deepspeed_tpu.ops.grouped_matmul import (_visits, grouped_matmul,
                                              row_tile, row_tiles_visited)

# group sizes over a buffer of M rows: what the sorted buffer can hold
M = 96
GROUPS = {
    "empty groups": [5, 0, 0, 17, 0, 3, 0, 0],
    "one group holds every row": [0, 0, M, 0, 0, 0, 0, 0],
    "groups straddle tiles": [7, 21, 13, 30, 1, 9, 15, 0],
    "a tail of rows in no group": [2, 4, 0, 3, 6, 1, 0, 5],
    "no row in any group": [0] * 8,
}


def in_groups(out, sizes):
    """Rows past the last group are no group's: both kernels leave them as
    they find them, and the layer zeroes them."""
    return jnp.where((jnp.arange(out.shape[0]) < sizes.sum())[:, None],
                     out, 0)


def bf16_steps(got, want):
    """How many bfloat16 steps apart ``got`` and ``want`` are, a step taken
    at ``want``'s own size, or at 1/256 of the outputs' rms where a sum
    cancels below that (there the float32 sum's own order decides the
    last bits, whoever adds it up)."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    size = np.maximum(np.abs(want), np.sqrt(np.mean(want ** 2)) / 256)
    size = np.maximum(size, np.finfo(np.float32).tiny)
    return np.abs(got - want) / 2.0 ** (np.floor(np.log2(size)) - 7)


@pytest.mark.parametrize("tm", [16, 32, 64, 128])
@pytest.mark.parametrize("groups", list(GROUPS), ids=list(GROUPS))
def test_the_kernel_is_ragged_dot_to_one_bfloat16_step(groups, tm):
    rng = np.random.default_rng(tm)
    sizes = jnp.asarray(GROUPS[groups], jnp.int32)
    lhs = jnp.asarray(rng.standard_normal((M, 64)), jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((8, 64, 256)), jnp.bfloat16)
    got = in_groups(grouped_matmul(lhs, rhs, sizes, tm, True), sizes)
    want = in_groups(jax.lax.ragged_dot(lhs, rhs, sizes), sizes)
    assert got.dtype == want.dtype == jnp.bfloat16
    assert bf16_steps(got, want).max() <= 1


def test_rows_that_are_not_a_multiple_of_the_tile():
    rng = np.random.default_rng(1)
    sizes = jnp.asarray([3, 0, 11, 4], jnp.int32)
    lhs = jnp.asarray(rng.standard_normal((21, 32)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((4, 32, 128)), jnp.float32)
    np.testing.assert_allclose(
        in_groups(grouped_matmul(lhs, rhs, sizes, 16, True), sizes),
        in_groups(jax.lax.ragged_dot(lhs, rhs, sizes), sizes), rtol=1e-6)


def test_the_gradient_is_ragged_dots():
    rng = np.random.default_rng(2)
    sizes = jnp.asarray([3, 0, 5, 8], jnp.int32)
    lhs = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((4, 16, 128)), jnp.float32)
    ct = jnp.asarray(rng.standard_normal((32, 128)), jnp.float32)

    def loss(dot):
        return lambda a, b: (in_groups(dot(a, b), sizes) * ct).sum()

    got = jax.grad(loss(lambda a, b: grouped_matmul(a, b, sizes, 16, True)),
                   (0, 1))(lhs, rhs)
    want = jax.grad(loss(lambda a, b: jax.lax.ragged_dot(a, b, sizes)),
                    (0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("groups", list(GROUPS), ids=list(GROUPS))
def test_the_visits_are_the_tiles_each_group_overlaps_in_row_order(groups):
    sizes = np.asarray(GROUPS[groups])
    ends = np.cumsum(sizes)
    for tm in (16, 32, 128):
        want = [(g, tile) for g, (s, e) in enumerate(zip(ends - sizes, ends))
                if e > s for tile in range(s // tm, (e - 1) // tm + 1)]
        group_of, tile_of, n = _visits(jnp.asarray(sizes, jnp.int32),
                                       M + (-M) % tm, tm)
        assert int(n) == len(want) == int(row_tiles_visited(
            jnp.asarray(sizes, jnp.int32), tm))
        assert list(zip(group_of[:int(n)].tolist(),
                        tile_of[:int(n)].tolist())) == want


# The rule's table (ISSUE 36): r = T x k / n_routed, the rows a held
# expert expects, and the row tile it gives; None is XLA's kernel. Every
# row tile of 16-128 was probed at each serving shape, and 128 was the
# fastest at every one (ops/grouped_matmul.py, PERF.md section 6).
@pytest.mark.parametrize("tokens,k,routed,tm", [
    (128, 22, 512, 128),        # nemotron3s-serve-chat's decode: r 5.5
    (32, 22, 512, 128),         # its smallest prefill bucket: r 1.4
    (512, 22, 512, 128),        # r 22
    (2048, 22, 512, 128),       # its largest prefill bucket: r 88
    (4096, 4, 64, None),        # glm47flash-train-s4096: r 256
    (4096, 16, 512, None),      # r 128: the edge, XLA's kernel from here
], ids=["decode", "prefill32", "prefill512", "prefill2048", "glm-train",
        "edge"])
def test_the_rule_reads_the_rows_a_group_expects(tokens, k, routed, tm):
    assert row_tile(tokens * k / routed) == tm


def layer_with(tm_path: bool, monkeypatch):
    """The latent relu^2 expert layer at decode, with the kernel's path
    taken (the platform probe answering TPU, the kernel interpreted) or
    not."""
    if tm_path:
        monkeypatch.setattr(dropless, "on_tpu", lambda: True)
        monkeypatch.setattr(dropless, "grouped_matmul",
                            partial(grouped_matmul, interpret=True))
    cfg = dropless.DroplessMoEConfig(
        hidden_size=64, expert_intermediate=128, n_routed_experts=64,
        n_held_experts=16, experts_per_token=6, shared_intermediate=64,
        latent_size=32, activation="relu2", dtype=jnp.float32)
    return dropless.DroplessMoE(cfg)


def test_the_layer_through_the_kernel_is_the_layer_through_ragged_dot(
        monkeypatch):
    x = jnp.asarray(np.random.default_rng(3).standard_normal((16, 1, 64)),
                    jnp.float32)
    live = jnp.arange(16) % 3 != 0
    plain = layer_with(False, monkeypatch)
    params = plain.init(jax.random.PRNGKey(0), x)["params"]
    want, counted = plain.apply({"params": params}, x, live=live[:, None])
    assert int(counted["rows_computed"]) == 0       # XLA's kernel: not ours
    got, counters = layer_with(True, monkeypatch).apply(
        {"params": params}, x, live=live[:, None])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # 16 x 6 / 64 = 1.5 rows an expert: 96 rows, padded to one tile of
    # 128, which every touched group visits
    assert set(counters) == set(dropless.SERVING_COUNTERS)
    held = int(counters["held_assignments"])
    assert held == int(counted["held_assignments"]) > 0
    assert int(counters["rows_computed"]) == 128 * int(
        counters["experts_touched"])
