"""The yardstick's own references, checked before they judge anything:
``benchmarks/reference/gpt2.py`` and ``bert.py`` against the repository's
models at a tiny size in float32 on the CPU, on the loss, the global
gradient norm and the logits; the reference optimizers' first step
against the repository's; and the chunked reference of a global batch
against the whole batch at once."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.drivers import train_steps  # noqa: E402
from benchmarks.harness import load_module  # noqa: E402
from benchmarks.reference import bert as ref_bert  # noqa: E402
from benchmarks.reference import gpt2 as ref_gpt2  # noqa: E402
from deepspeed_tpu.models import make_bert, make_gpt  # noqa: E402
from deepspeed_tpu.ops.adam.fused_adam import FusedAdam  # noqa: E402
from deepspeed_tpu.ops.lamb.fused_lamb import FusedLamb  # noqa: E402

RNGS = {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)}


def grad_norm(grads):
    return float(jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                              for g in jax.tree_util.tree_leaves(grads))))


def both(model_loss, reference_loss, params):
    l_m, g_m = jax.value_and_grad(model_loss)(params)
    l_r, g_r = jax.value_and_grad(reference_loss)(params)
    # float32 against float32: agreement to rounding, leaf by leaf
    assert float(l_m) == pytest.approx(float(l_r), rel=1e-5)
    assert grad_norm(g_m) == pytest.approx(grad_norm(g_r), rel=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(g_m),
                    jax.tree_util.tree_leaves(g_r)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5)


def test_gpt2_reference_matches_the_tiny_model():
    model, cfg = make_gpt("tiny", dtype=jnp.float32)
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (3, 32),
                                            dtype=np.int32)
    batch = {"input_ids": ids}
    params = model.init(RNGS, batch)["params"]
    both(lambda p: model.apply({"params": p}, batch,
                               deterministic=True)["loss"],
         lambda p: ref_gpt2.loss(p, batch, n_head=cfg.num_heads,
                                 eps=cfg.layer_norm_epsilon), params)
    want = model.apply({"params": params}, batch,
                       deterministic=True)["logits"]
    got = ref_gpt2.logits(params, ids, n_head=cfg.num_heads,
                          eps=cfg.layer_norm_epsilon)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_gpt2_reference_is_causal():
    """Right-padding a sequence must not move the logits before the pad:
    the serving check pads its sample to one width."""
    model, cfg = make_gpt("tiny", dtype=jnp.float32)
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (1, 24),
                                            dtype=np.int32)
    params = model.init(RNGS, {"input_ids": ids})["params"]
    kw = dict(n_head=cfg.num_heads, eps=cfg.layer_norm_epsilon)
    short = ref_gpt2.logits(params, ids[:, :16], **kw)
    padded = ref_gpt2.logits(params, ids, **kw)
    np.testing.assert_allclose(padded[:, :16], short, rtol=1e-5, atol=1e-6)


def test_bert_reference_matches_the_tiny_model():
    model, cfg = make_bert("tiny", dtype=jnp.float32)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (3, 32), dtype=np.int32)
    batch = {"input_ids": ids, "attention_mask": np.ones_like(ids),
             "labels": np.where(rng.random(ids.shape) < 0.15, ids,
                                -100).astype(np.int32)}
    params = model.init(RNGS, batch)["params"]
    both(lambda p: model.apply({"params": p}, batch,
                               deterministic=True)["loss"],
         lambda p: ref_bert.loss(p, batch, n_head=cfg.num_heads,
                                 eps=cfg.layer_norm_epsilon), params)


# ---------------------------------------------------------------------------
# The reference optimizers' first step, and the chunked global batch
# ---------------------------------------------------------------------------

def weights_and_grads():
    """A kernel, a bias that starts at zero, a scale that starts at one
    and an embedding with rows no token touched (gradient exactly 0)."""
    rng = np.random.default_rng(0)
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    params = {"kernel": f32(rng.normal(0, 0.02, (64, 48))),
              "bias": f32(np.zeros(48)), "scale": f32(np.ones(64)),
              "wte": f32(rng.normal(0, 0.02, (32, 64)))}
    grads = {k: f32(rng.normal(0, 1e-3, v.shape)) for k, v in params.items()}
    grads["wte"] = grads["wte"].at[5:9].set(0.0)
    return params, grads


@pytest.mark.parametrize("kind, system, settings", [
    ("adam", FusedAdam, {"lr": 1e-4}),
    ("adam", FusedAdam, {"lr": 3e-4, "weight_decay": 0.01, "eps": 1e-6,
                         "betas": [0.8, 0.9]}),
    ("lamb", FusedLamb, {"lr": 2e-3}),
    ("lamb", FusedLamb, {"lr": 1e-3, "weight_decay": 0.01,
                         "max_coeff": 0.05, "min_coeff": 0.03}),
])
def test_reference_first_step_is_the_repositorys(kind, system, settings):
    params, grads = weights_and_grads()
    reference = load_module("reference", "optimizers." + kind).first_step
    got = reference(params, grads, **settings)
    optimizer = system(**settings)
    want, _ = optimizer.update(grads, optimizer.init(params), params)
    for name in params:
        # the two steps agree to a thousandth of the largest (a float32
        # weight of 0.03 rounds to 4e-9, a step here is 3e-5 or more)
        moved = np.abs(np.asarray(want[name] - params[name])).max()
        assert moved > 0
        np.testing.assert_allclose(got[name] - params[name],
                                   want[name] - params[name], rtol=0,
                                   atol=1e-3 * moved, err_msg=name)


def test_a_setting_the_reference_optimizer_does_not_know_is_an_error():
    params, grads = weights_and_grads()
    for kind in ("adam", "lamb"):
        with pytest.raises(TypeError, match="bias_correction"):
            load_module("reference", "optimizers." + kind).first_step(
                params, grads, lr=1e-3, bias_correction=False)


def test_the_chunked_reference_is_the_whole_batch_at_once():
    """``reference_programs`` takes a global batch a chunk at a time. Its
    loss and gradient are those of ``train_batch()``'s loss, the mean over
    the micro-batches of each one's mean over its labelled positions,
    also where the chunks of a micro-batch hold unequal numbers of them."""
    model, cfg = make_bert("tiny", dtype=jnp.float32)
    rng = np.random.default_rng(0)
    gas, micro, seq, size = 2, 4, 16, 2
    ids = rng.integers(0, cfg.vocab_size, (gas, micro, seq), dtype=np.int32)
    masked = rng.random(ids.shape) < 0.3
    masked[0, :2] = False                     # a chunk with one label only
    masked[0, 0, 0] = True
    batch = {"input_ids": ids, "attention_mask": np.ones_like(ids),
             "labels": np.where(masked, ids, -100).astype(np.int32)}
    params = model.init(RNGS, {k: v[0] for k, v in batch.items()})["params"]
    kw = dict(n_head=cfg.num_heads, eps=cfg.layer_norm_epsilon)

    whole = lambda p: jnp.mean(jnp.stack([
        ref_bert.loss(p, {k: v[i] for k, v in batch.items()}, **kw)
        for i in range(gas)]))
    want_loss, want_grads = jax.value_and_grad(whole)(params)

    forward, gradient, _ = train_steps.reference_programs(
        lambda p, b: ref_bert.nll(p, b, **kw), lambda p, g: p)
    chunks = {k: v.reshape(gas, micro // size, size, seq)
              for k, v in batch.items()}
    sums, counts = forward(params, chunks)
    assert np.asarray(counts).tolist()[0][0] == 1
    assert train_steps.mean_loss(sums, counts) == \
        pytest.approx(float(want_loss), rel=1e-6)
    got = gradient(params, chunks, counts)
    assert grad_norm(got) == pytest.approx(grad_norm(want_grads), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-6)


def test_compare_passes_the_reference_and_fails_a_wrong_update():
    """``loss_0`` 11.0 and, after one reference step, ``loss_1`` 10.9."""
    ok = lambda first: train_steps.compare(first, 11.0, 10.9) == []
    assert ok([11.0003, 10.9004, 10.8])       # bfloat16 against float32
    assert ok([10.99, 10.905])                # a twentieth of the step off
    assert not ok([11.0, 10.92])              # a fifth of the step off
    assert not ok([11.1, 10.9])               # the forward pass is off
    assert not ok([11.0, 11.0])               # no update at all
    assert not ok([11.0, 10.95])              # half the step
    assert not ok([11.0, 10.8])               # twice the step
    assert not ok([11.0, 11.1])               # the wrong sign
    assert not ok([11.0, float("nan")])
