"""Model-family tests: forward shapes, loss finiteness, engine integration,
TP partition-rule coverage (the analogue of the reference's simple_model.py
fixtures + Megatron model tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

import deepspeed_tpu
from deepspeed_tpu.models import (build_specs, bert_partition_rules,
                                  gpt_partition_rules, make_bert, make_gpt)


def _gpt_batch(rng, cfg, batch=4, seq=32):
    ids = rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32)
    return {"input_ids": ids}


def _bert_batch(rng, cfg, batch=4, seq=32):
    ids = rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32)
    labels = np.where(rng.random((batch, seq)) < 0.15, ids, -100).astype(np.int32)
    return {"input_ids": ids, "attention_mask": np.ones((batch, seq), np.int32),
            "labels": labels,
            "next_sentence_label": rng.integers(0, 2, (batch,), dtype=np.int32)}


class TestGPT:
    def test_forward_loss(self):
        model, cfg = make_gpt("tiny")
        rng = np.random.default_rng(0)
        batch = _gpt_batch(rng, cfg)
        variables = model.init({"params": jax.random.PRNGKey(0),
                                "dropout": jax.random.PRNGKey(1)}, batch)
        out = model.apply(variables, batch, deterministic=True)
        assert out["logits"].shape == (4, 32, cfg.vocab_size)
        assert np.isfinite(float(out["loss"]))
        # random init → loss ≈ ln(vocab)
        assert abs(float(out["loss"]) - np.log(cfg.vocab_size)) < 1.0

    def test_grads_finite(self):
        model, cfg = make_gpt("tiny")
        rng = np.random.default_rng(0)
        batch = _gpt_batch(rng, cfg)
        variables = model.init({"params": jax.random.PRNGKey(0),
                                "dropout": jax.random.PRNGKey(1)}, batch)

        def loss_fn(p):
            return model.apply({"params": p}, batch, deterministic=True)["loss"]

        grads = jax.grad(loss_fn)(variables["params"])
        leaves = jax.tree_util.tree_leaves(grads)
        assert all(np.isfinite(np.asarray(g)).all() for g in leaves)
        # every param gets gradient signal somewhere
        nonzero = sum(float(np.abs(np.asarray(g)).sum()) > 0 for g in leaves)
        assert nonzero > len(leaves) * 0.8

    def test_remat_matches(self):
        model, cfg = make_gpt("tiny")
        model_r, _ = make_gpt("tiny", remat=True)
        rng = np.random.default_rng(0)
        batch = _gpt_batch(rng, cfg)
        variables = model.init({"params": jax.random.PRNGKey(0),
                                "dropout": jax.random.PRNGKey(1)}, batch)
        l0 = model.apply(variables, batch, deterministic=True)["loss"]
        l1 = model_r.apply(variables, batch, deterministic=True)["loss"]
        assert abs(float(l0) - float(l1)) < 1e-4

    def test_partition_rules_cover_params(self):
        model, cfg = make_gpt("tiny")
        batch = _gpt_batch(np.random.default_rng(0), cfg)
        variables = model.init({"params": jax.random.PRNGKey(0),
                                "dropout": jax.random.PRNGKey(1)}, batch)
        specs = build_specs(variables["params"], gpt_partition_rules(),
                            mesh_axes={"model": 2})
        leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
        assert all(isinstance(s, PartitionSpec) for s in leaves)
        sharded = [s for s in leaves if any(d is not None for d in tuple(s))]
        assert len(sharded) >= cfg.num_layers * 4  # qkv/fc kernels+biases

    def test_mesh_axes_size1_drops_sharding(self):
        model, cfg = make_gpt("tiny")
        batch = _gpt_batch(np.random.default_rng(0), cfg)
        variables = model.init({"params": jax.random.PRNGKey(0),
                                "dropout": jax.random.PRNGKey(1)}, batch)
        specs = build_specs(variables["params"], gpt_partition_rules(),
                            mesh_axes={"model": 1})
        for s in jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(x, PartitionSpec)):
            assert all(d is None for d in tuple(s))


class TestBert:
    def test_forward_loss_mlm_nsp(self):
        model, cfg = make_bert("tiny")
        rng = np.random.default_rng(0)
        batch = _bert_batch(rng, cfg)
        variables = model.init({"params": jax.random.PRNGKey(0),
                                "dropout": jax.random.PRNGKey(1)}, batch)
        out = model.apply(variables, batch, deterministic=True)
        assert out["logits"].shape == (4, 32, cfg.vocab_size)
        assert out["nsp_logits"].shape == (4, 2)
        assert np.isfinite(float(out["loss"]))

    def test_postln_variant(self):
        model, cfg = make_bert("tiny", pre_layer_norm=False)
        rng = np.random.default_rng(0)
        batch = _bert_batch(rng, cfg)
        variables = model.init({"params": jax.random.PRNGKey(0),
                                "dropout": jax.random.PRNGKey(1)}, batch)
        out = model.apply(variables, batch, deterministic=True)
        assert np.isfinite(float(out["loss"]))

    def test_partition_rules(self):
        model, cfg = make_bert("tiny")
        batch = _bert_batch(np.random.default_rng(0), cfg)
        variables = model.init({"params": jax.random.PRNGKey(0),
                                "dropout": jax.random.PRNGKey(1)}, batch)
        specs = build_specs(variables["params"], bert_partition_rules(),
                            mesh_axes={"model": 2})
        sharded = [s for s in jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
            if any(d is not None for d in tuple(s))]
        assert len(sharded) >= cfg.num_layers * 4


class TestEngineIntegration:
    @pytest.mark.parametrize("zero_stage", [0, 2])
    def test_gpt_trains_loss_decreases(self, zero_stage):
        model, cfg = make_gpt("tiny")
        rng = np.random.default_rng(0)
        batch = _gpt_batch(rng, cfg, batch=8, seq=32)
        ds_config = {
            "train_micro_batch_size_per_gpu": 8,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": zero_stage},
        }
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=ds_config,
            params=model.init({"params": jax.random.PRNGKey(0),
                               "dropout": jax.random.PRNGKey(1)}, batch)["params"])
        losses = []
        for _ in range(20):
            loss = engine(batch)
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        assert losses[-1] < losses[0] - 0.5, losses


class TestHashDropout:
    """Counter-hash dropout (ops/dropout.py; reference
    dropout_kernels.cu's fused-dropout economy)."""

    def test_statistics_and_scaling(self):
        from deepspeed_tpu.ops.dropout import hash_dropout

        x = jnp.ones((512, 512), jnp.float32)
        rate = 0.1
        y = hash_dropout(x, rate, jax.random.PRNGKey(0))
        kept = np.asarray(y) > 0
        assert abs(kept.mean() - (1 - rate)) < 0.01
        np.testing.assert_allclose(np.asarray(y)[kept], 1.0 / (1 - rate),
                                   rtol=1e-6)
        # mean preserved
        assert abs(float(jnp.mean(y)) - 1.0) < 0.02

    def test_deterministic_per_key_decorrelated_across_keys(self):
        from deepspeed_tpu.ops.dropout import hash_dropout

        x = jnp.ones((64, 64), jnp.float32)
        a = hash_dropout(x, 0.2, jax.random.PRNGKey(1))
        b = hash_dropout(x, 0.2, jax.random.PRNGKey(1))
        c = hash_dropout(x, 0.2, jax.random.PRNGKey(2))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.array_equal(np.asarray(a), np.asarray(c))

    def test_zero_rate_and_eval_identity(self):
        from deepspeed_tpu.ops.dropout import HashDropout, hash_dropout

        x = jnp.ones((8, 8), jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(hash_dropout(x, 0.0, jax.random.PRNGKey(0))),
            np.asarray(x))
        y = HashDropout(0.5, deterministic=True).apply({}, x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))

    def test_gpt_trains_with_fast_dropout(self, eight_devices):
        from deepspeed_tpu.models.gpt import make_gpt

        model, cfg = make_gpt("tiny", dropout_rate=0.1, fast_dropout=True)
        rng = np.random.default_rng(0)
        batches = {"input_ids": rng.integers(0, cfg.vocab_size, (2, 8, 32),
                                             dtype=np.int32)}
        params = model.init(
            {"params": jax.random.PRNGKey(0),
             "dropout": jax.random.PRNGKey(1)},
            {"input_ids": batches["input_ids"][0]})["params"]
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, params=params,
            config={"train_micro_batch_size_per_gpu": 1,
                    "gradient_accumulation_steps": 2,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 0}})
        losses = [float(engine.train_batch(batches)) for _ in range(8)]
        assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.3
