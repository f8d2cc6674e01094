"""MoE layer tests: routing correctness, capacity, aux loss, expert
parallelism on the virtual mesh, end-to-end training."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

import deepspeed_tpu
from deepspeed_tpu.moe import MoE, MoEConfig, moe_partition_rules
from deepspeed_tpu.models.partition import build_specs
from deepspeed_tpu.parallel.mesh import build_mesh


def make_moe(e=4, k=1, d=16, **kw):
    cfg = MoEConfig(hidden_size=d, num_experts=e, k=k, dtype=jnp.float32,
                    **kw)
    layer = MoE(cfg)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 8, d)), jnp.float32)
    params = layer.init({"params": jax.random.PRNGKey(0)}, x)["params"]
    return layer, params, x, cfg


class TestRouting:
    def test_output_shape_and_finite(self):
        layer, params, x, _ = make_moe()
        y, aux = layer.apply({"params": params}, x)
        assert y.shape == x.shape
        assert np.isfinite(np.asarray(y)).all()
        assert float(aux) > 0

    def test_top1_each_token_single_expert(self):
        layer, params, x, cfg = make_moe(e=4, k=1, capacity_factor=4.0)
        # inspect internals: rebuild dispatch from the router output
        from deepspeed_tpu.moe.layer import _topk_dispatch

        logits = x.reshape(-1, cfg.hidden_size).astype(jnp.float32) @ \
            params["router"]["kernel"]
        gates = jax.nn.softmax(logits, axis=-1)
        dispatch, combine, _ = _topk_dispatch(jnp.asarray(gates), 1, 16)
        per_token = np.asarray(dispatch).sum(axis=(1, 2))
        np.testing.assert_array_equal(per_token, np.ones_like(per_token))
        # top-1 combine weight is the RAW gate prob (Switch: y = p*E(x)) —
        # normalizing would zero the router's task-loss gradient
        top_prob = np.max(np.asarray(gates), axis=-1)
        np.testing.assert_allclose(np.asarray(combine).sum(axis=(1, 2)),
                                   top_prob, atol=1e-5)

    def test_router_gets_task_gradient_at_k1(self):
        layer, params, x, _ = make_moe(e=4, k=1, capacity_factor=4.0)

        def task_loss(p):
            y, _aux = layer.apply({"params": p}, x)
            return jnp.mean(y ** 2)

        g = jax.grad(task_loss)(params)["router"]["kernel"]
        assert float(jnp.abs(g).max()) > 1e-6, \
            "router must learn from the task loss, not only aux"

    def test_top2_routes_two_experts(self):
        from deepspeed_tpu.moe.layer import _topk_dispatch

        gates = jax.nn.softmax(jnp.asarray(
            np.random.default_rng(0).standard_normal((16, 4))), axis=-1)
        dispatch, combine, _ = _topk_dispatch(gates, 2, 16)
        per_token = np.asarray(dispatch).sum(axis=(1, 2))
        np.testing.assert_array_equal(per_token, np.full(16, 2.0))
        np.testing.assert_allclose(np.asarray(combine).sum(axis=(1, 2)),
                                   1.0, atol=1e-5)

    def test_capacity_drops_overflow(self):
        from deepspeed_tpu.moe.layer import _topk_dispatch

        # All tokens prefer expert 0; capacity 2 keeps only 2.
        gates = jnp.asarray(np.tile([[0.97, 0.01, 0.01, 0.01]], (8, 1)),
                            jnp.float32)
        dispatch, _, _ = _topk_dispatch(gates, 1, 2)
        assert float(np.asarray(dispatch).sum()) == 2.0

    def test_balanced_aux_loss_is_one(self):
        from deepspeed_tpu.moe.layer import _topk_dispatch

        # Perfectly uniform gates -> aux = E * sum(1/E * 1/E) = 1.
        gates = jnp.full((16, 4), 0.25, jnp.float32)
        _, _, aux = _topk_dispatch(gates, 1, 16)
        assert float(aux) == pytest.approx(1.0, rel=1e-5)


class TestExpertParallel:
    def test_sharded_experts_match_replicated(self, eight_devices):
        layer, params, x, _ = make_moe(e=8, capacity_factor=8.0)
        y_ref, _ = layer.apply({"params": params}, x)

        mesh = build_mesh(expert=4, data=2)
        specs = build_specs(params, moe_partition_rules(),
                            mesh_axes=dict(mesh.shape))
        sharded = jax.tree_util.tree_map(
            lambda p, sp: jax.device_put(p, NamedSharding(mesh, sp)),
            params, specs)
        w = sharded["experts_in"]
        assert w.sharding.shard_shape(w.shape)[0] == 2  # 8 experts / 4
        with mesh:
            y_sh, _ = jax.jit(
                lambda p, x: layer.apply({"params": p}, x))(sharded, x)
        np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-5)

    def test_moe_model_trains_with_engine(self, eight_devices, rng):
        import flax.linen as nn

        class TinyMoEModel(nn.Module):
            @nn.compact
            def __call__(self, batch, deterministic=False):
                x = batch["x"]
                y, aux = MoE(MoEConfig(hidden_size=16, num_experts=4,
                                       dtype=jnp.float32))(
                    x, deterministic=deterministic)
                loss = jnp.mean((y - batch["t"]) ** 2) + 0.01 * aux
                return {"loss": loss}

        model = TinyMoEModel()
        x = rng.standard_normal((2, 8, 8, 16)).astype(np.float32)
        t = rng.standard_normal((2, 8, 8, 16)).astype(np.float32)
        params = model.init({"params": jax.random.PRNGKey(0),
                             "dropout": jax.random.PRNGKey(1)},
                            {"x": x[0], "t": t[0]})["params"]
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, params=params,
            config={"train_micro_batch_size_per_gpu": 1,
                    "gradient_accumulation_steps": 2,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                    "zero_optimization": {"stage": 2}})
        first = float(engine.train_batch({"x": x, "t": t}))
        for _ in range(10):
            last = float(engine.train_batch({"x": x, "t": t}))
        assert last < first


class TestScatterDispatch:
    """Slot-scatter dispatch (round-2 VERDICT weak #4 / task 10b): parity
    with the GShard einsum oracle, and dispatch memory linear in T (no
    [T, E, C] intermediate)."""

    @pytest.mark.parametrize("k", [1, 2])
    def test_parity_with_einsum(self, k):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((2, 16, 32)), jnp.float32)
        outs = {}
        for disp in ("scatter", "einsum"):
            cfg = MoEConfig(hidden_size=32, num_experts=4, k=k,
                            capacity_factor=2.0, dtype=jnp.float32,
                            dispatch=disp)
            layer = MoE(cfg)
            params = layer.init({"params": jax.random.PRNGKey(0)}, x)["params"]
            y, aux = layer.apply({"params": params}, x)
            outs[disp] = (np.asarray(y), float(aux))
        np.testing.assert_allclose(outs["scatter"][0], outs["einsum"][0],
                                   atol=1e-5, rtol=1e-5)
        assert outs["scatter"][1] == outs["einsum"][1]

    def test_grad_parity_with_einsum(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((2, 8, 16)), jnp.float32)
        grads = {}
        for disp in ("scatter", "einsum"):
            cfg = MoEConfig(hidden_size=16, num_experts=4, k=2,
                            capacity_factor=2.0, dtype=jnp.float32,
                            dispatch=disp)
            layer = MoE(cfg)
            params = layer.init({"params": jax.random.PRNGKey(0)}, x)["params"]

            def loss(p):
                y, aux = layer.apply({"params": p}, x)
                return jnp.mean(y ** 2) + 0.01 * aux

            grads[disp] = jax.grad(loss)(params)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4),
            grads["scatter"], grads["einsum"])

    def test_no_tec_intermediate(self):
        """The traced scatter path must contain no array of size
        T*E*C (the one-hot product the einsum path materializes)."""
        t, e, d = 64, 8, 16
        # small expert_intermediate so the legitimate [E, C, d_ff] FFN
        # intermediate stays well below T*E*C
        cfg = MoEConfig(hidden_size=d, num_experts=e, k=1,
                        capacity_factor=2.0, dtype=jnp.float32,
                        dispatch="scatter", expert_intermediate=16)
        capacity = max(cfg.min_capacity, int(np.ceil(t / e * 2.0)))
        layer = MoE(cfg)
        x = jnp.zeros((1, t, d))
        params = layer.init({"params": jax.random.PRNGKey(0)}, x)["params"]
        jaxpr = jax.make_jaxpr(
            lambda p: layer.apply({"params": p}, x)[0])(params)

        def all_avals(jx, out):
            for eqn in jx.eqns:
                for v in eqn.outvars:
                    out.append(v.aval)
                for val in eqn.params.values():
                    inner = getattr(val, "jaxpr", None)
                    if inner is None and type(val).__name__ == "Jaxpr":
                        inner = val
                    if inner is not None:
                        all_avals(inner, out)
            return out

        tec = t * e * capacity
        sizes = [int(np.prod(a.shape)) for a in all_avals(jaxpr.jaxpr, [])
                 if hasattr(a, "shape")]
        assert not any(s >= tec for s in sizes), sorted(sizes)[-4:]


class TestMoEGPT:
    """MoE wired into the in-tree GPT family (round-2 VERDICT weak #4:
    'no in-tree model family wires MoE into a full LM')."""

    def _model(self):
        from deepspeed_tpu.models import make_gpt

        return make_gpt("tiny", vocab_size=256, max_seq_len=64,
                        hidden_size=32, num_layers=4, num_heads=2,
                        dropout_rate=0.0, dtype=jnp.float32,
                        moe_experts=4, moe_k=1, moe_layer_freq=2)

    def test_trains_end_to_end_with_expert_parallelism(self, eight_devices):
        from deepspeed_tpu.models import build_specs
        from deepspeed_tpu.models.gpt import gpt_partition_rules

        model, cfg = self._model()
        mesh = build_mesh(data=4, expert=2)
        rng = np.random.default_rng(0)
        batches = {"input_ids": rng.integers(0, 256, (2, 8, 32),
                                             dtype=np.int32)}
        one = {"input_ids": batches["input_ids"][0]}
        params = model.init({"params": jax.random.PRNGKey(0),
                             "dropout": jax.random.PRNGKey(1)}, one)["params"]
        # every 2nd block carries experts
        assert "moe" in params["h_1"] and "moe" in params["h_3"]
        assert "c_fc" in params["h_0"] and "moe" not in params["h_0"]
        specs = build_specs(params, gpt_partition_rules(),
                            mesh_axes=dict(mesh.shape))
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, params=params, mesh=mesh,
            param_partition_specs=specs,
            config={
                "train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 1},
            })
        # expert params sharded over the expert axis
        w = engine.state.params["h_1"]["moe"]["experts_in"]
        assert w.sharding.shard_shape(w.shape)[0] == w.shape[0] // 2
        losses = [float(engine.train_batch(batches)) for _ in range(6)]
        assert all(np.isfinite(losses)) and losses[-1] < losses[0]

    def test_generation_with_moe_blocks(self, eight_devices):
        """KV-cache decode runs through MoE blocks (aux discarded)."""
        model, cfg = self._model()
        ids = jnp.asarray(np.random.default_rng(0).integers(
            0, 256, (2, 8), dtype=np.int32))
        variables = model.init({"params": jax.random.PRNGKey(0),
                                "dropout": jax.random.PRNGKey(1)},
                               {"input_ids": ids})
        eng = deepspeed_tpu.init_inference(
            model, params=variables["params"], dtype=jnp.float32)
        out = eng.generate(ids, max_new_tokens=4)
        assert out.shape == (2, 12)


class TestAllToAllDispatch:
    """Explicit all-to-all dispatch (moe/dispatch.py): exact parity with
    the einsum oracle on a sharded mesh — keep regime, drop regime and
    gradients — plus the shape walls."""

    def _outs(self, disp, mesh, k=1, capacity_factor=2.0, shape=(2, 16, 32),
              grad=False):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        cfg = MoEConfig(hidden_size=shape[-1], num_experts=4, k=k,
                        capacity_factor=capacity_factor, dtype=jnp.float32,
                        dispatch=disp,
                        mesh=mesh if disp == "alltoall" else None)
        layer = MoE(cfg)
        params = layer.init({"params": jax.random.PRNGKey(0)}, x)["params"]
        if grad:
            def loss(p):
                y, aux = layer.apply({"params": p}, x)
                return jnp.mean(y ** 2) + 0.01 * aux
            return jax.grad(loss)(params)
        y, aux = jax.jit(
            lambda p: layer.apply({"params": p}, x))(params)
        return np.asarray(y), float(aux)

    @pytest.mark.parametrize("k", [1, 2])
    def test_parity_with_einsum(self, eight_devices, k):
        mesh = build_mesh(data=2, expert=4)
        y_ref, aux_ref = self._outs("einsum", mesh, k=k)
        y_a2a, aux_a2a = self._outs("alltoall", mesh, k=k)
        np.testing.assert_allclose(y_a2a, y_ref, atol=1e-5, rtol=1e-5)
        # routing (and thus aux) is shared math, but jit fuses the two
        # programs differently — allow fp roundoff on the scalar
        np.testing.assert_allclose(aux_a2a, aux_ref, rtol=1e-6)

    def test_parity_in_drop_regime(self, eight_devices):
        """capacity_factor=1.0 forces real drops — the explicit path
        must drop EXACTLY the oracle's tokens (global queue positions)."""
        mesh = build_mesh(data=2, expert=4)
        y_ref, _ = self._outs("einsum", mesh, capacity_factor=1.0,
                              shape=(4, 16, 32))
        y_a2a, _ = self._outs("alltoall", mesh, capacity_factor=1.0,
                              shape=(4, 16, 32))
        np.testing.assert_allclose(y_a2a, y_ref, atol=1e-5, rtol=1e-5)

    def test_grad_parity_with_einsum(self, eight_devices):
        mesh = build_mesh(data=2, expert=4)
        g_ref = self._outs("einsum", mesh, k=2, grad=True)
        g_a2a = self._outs("alltoall", mesh, k=2, grad=True)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4),
            g_a2a, g_ref)

    def test_expert_divisibility_wall(self, eight_devices):
        from deepspeed_tpu.moe.dispatch import alltoall_dispatch
        mesh = build_mesh(data=2, expert=4)
        with pytest.raises(ValueError, match="must divide"):
            alltoall_dispatch(
                jnp.zeros((16, 8)), [], jnp.zeros((6, 8, 16)),
                jnp.zeros((6, 16, 8)), capacity=4, dtype=jnp.float32,
                mesh=mesh)

    def test_token_divisibility_wall(self, eight_devices):
        from deepspeed_tpu.moe.dispatch import alltoall_dispatch
        mesh = build_mesh(data=2, expert=4)
        with pytest.raises(ValueError, match="dispatch grid"):
            alltoall_dispatch(
                jnp.zeros((12, 8)), [], jnp.zeros((4, 8, 16)),
                jnp.zeros((4, 16, 8)), capacity=4, dtype=jnp.float32,
                mesh=mesh)

    def test_modeled_bytes(self, eight_devices):
        from deepspeed_tpu.moe.dispatch import modeled_dispatch_bytes_ici
        mesh = build_mesh(data=2, expert=4)
        got = modeled_dispatch_bytes_ici(num_experts=8, capacity=16,
                                         hidden=32, dtype=jnp.float32,
                                         mesh=mesh)
        ec = 8 * 16
        per_cell = (2 * ec * 32 + ec) * 4 * 3 / 4
        assert got == int(8 * per_cell)
        # unsharded expert axis => the exchange is local, nothing modeled
        assert modeled_dispatch_bytes_ici(
            num_experts=8, capacity=16, hidden=32, dtype=jnp.float32,
            mesh=build_mesh(data=8)) == 0


class TestEvalCapacityAndJitter:
    """Config knobs that change routing between train and eval
    (MoEConfig.eval_capacity_factor, router_jitter)."""

    def test_eval_capacity_factor_applies_on_eval_path(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((2, 32, 16)), jnp.float32)
        cfg = MoEConfig(hidden_size=16, num_experts=4, k=1,
                        capacity_factor=0.25, eval_capacity_factor=4.0,
                        min_capacity=1, dtype=jnp.float32, stats=True)
        layer = MoE(cfg)
        params = layer.init({"params": jax.random.PRNGKey(0)}, x)["params"]
        _, _, train_stats = layer.apply({"params": params}, x,
                                        deterministic=False,
                                        rngs={"dropout": jax.random.PRNGKey(2)})
        _, _, eval_stats = layer.apply({"params": params}, x,
                                       deterministic=True)
        assert float(train_stats["capacity_overflow_frac"]) > 0.5
        assert float(eval_stats["capacity_overflow_frac"]) == 0.0

    def test_router_jitter_train_only(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((2, 16, 16)), jnp.float32)
        cfg = MoEConfig(hidden_size=16, num_experts=4, k=1,
                        capacity_factor=2.0, router_jitter=0.5,
                        dtype=jnp.float32)
        layer = MoE(cfg)
        params = layer.init({"params": jax.random.PRNGKey(0)}, x)["params"]
        # train: jitter perturbs routing, different rngs => different y
        y1, _ = layer.apply({"params": params}, x, deterministic=False,
                            rngs={"dropout": jax.random.PRNGKey(1)})
        y2, _ = layer.apply({"params": params}, x, deterministic=False,
                            rngs={"dropout": jax.random.PRNGKey(7)})
        assert float(jnp.abs(y1 - y2).max()) > 0
        # eval: jitter OFF — deterministic, and identical to a
        # jitter-free config's eval output
        e1 = layer.apply({"params": params}, x, deterministic=True)[0]
        quiet = MoE(MoEConfig(hidden_size=16, num_experts=4, k=1,
                              capacity_factor=2.0, router_jitter=0.0,
                              dtype=jnp.float32))
        e2 = quiet.apply({"params": params}, x, deterministic=True)[0]
        np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))


def _moe_gpt_engine(mesh, config, moe_overrides=None, seq=16):
    """MoE GPT engine through the config `moe` block: params are built
    from a model already carrying the shape-affecting moe fields, the
    `moe` surgery injects capacity/dispatch/mesh/stats."""
    from deepspeed_tpu.models import build_specs, make_gpt
    from deepspeed_tpu.models.gpt import gpt_partition_rules

    kw = dict(vocab_size=256, max_seq_len=seq, hidden_size=32,
              num_layers=2, num_heads=4, dropout_rate=0.0,
              dtype=jnp.float32, moe_experts=4, moe_k=1,
              moe_layer_freq=2)
    kw.update(moe_overrides or {})
    model, cfg = make_gpt("tiny", **kw)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 256, (8, seq), dtype=np.int32)
    params = model.init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(1)},
                        {"input_ids": ids})["params"]
    specs = build_specs(params, gpt_partition_rules(),
                        mesh_axes=dict(mesh.shape))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, params=params, mesh=mesh,
        param_partition_specs=specs, config=config)
    batches = {"input_ids": rng.integers(0, 256, (1, 8, seq),
                                         dtype=np.int32)}
    return engine, batches


class TestExpertZeroCompose:
    """Expert axis >= 2 composed with every ZeRO stage, through the
    config `moe` block (docs/MOE.md 'Composition')."""

    @pytest.mark.parametrize("stage", [0, 1, 2, 3])
    def test_trains_each_stage(self, eight_devices, stage):
        engine, batches = _moe_gpt_engine(
            build_mesh(data=4, expert=2),
            {"train_micro_batch_size_per_gpu": 2,
             "gradient_accumulation_steps": 1,
             "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
             "zero_optimization": {"stage": stage},
             "moe": {"enabled": True, "num_experts": 4, "k": 1,
                     "dispatch": "alltoall"}})
        w = engine.state.params["h_1"]["moe"]["experts_in"]
        assert w.sharding.shard_shape(w.shape)[0] == 2  # 4 experts / 2
        losses = [float(engine.train_batch(batches)) for _ in range(3)]
        assert all(np.isfinite(losses)) and losses[-1] < losses[0]

    def test_eight_experts_alltoall_zero2(self, eight_devices):
        """The ISSUE 16 acceptance rung verbatim: an 8-expert MoE GPT
        on the 8-device mesh, expert axis >= 2, ZeRO-2, all-to-all
        dispatch — trains with finite decreasing loss."""
        engine, batches = _moe_gpt_engine(
            build_mesh(data=2, expert=4),
            {"train_micro_batch_size_per_gpu": 4,
             "gradient_accumulation_steps": 1,
             "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
             "zero_optimization": {"stage": 2},
             "moe": {"enabled": True, "num_experts": 8, "k": 1,
                     "dispatch": "alltoall"}},
            moe_overrides={"moe_experts": 8})
        w = engine.state.params["h_1"]["moe"]["experts_in"]
        assert w.sharding.shard_shape(w.shape)[0] == 2  # 8 experts / 4
        losses = [float(engine.train_batch(batches)) for _ in range(3)]
        assert all(np.isfinite(losses)) and losses[-1] < losses[0]

    def test_expert_params_never_cross_dcn(self, eight_devices):
        """hpZ-style placement: on a 2-slice mesh, expert params stay
        intra-slice — no spec may name the dcn axis, so GSPMD has no
        license to move them over the cross-slice link."""
        engine, batches = _moe_gpt_engine(
            build_mesh(slices=2, data=-1, expert=2),
            {"train_micro_batch_size_per_gpu": 4,
             "gradient_accumulation_steps": 1,
             "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
             "zero_optimization": {"stage": 3},
             "moe": {"enabled": True, "num_experts": 4, "k": 1,
                     "dispatch": "scatter"}})
        for blk in ("h_1",):
            for leaf in ("experts_in", "experts_out"):
                spec = engine.state.params[blk]["moe"][leaf].sharding.spec
                flat = [a for part in spec if part is not None
                        for a in ((part,) if isinstance(part, str)
                                  else tuple(part))]
                assert "dcn" not in flat, (leaf, spec)
                assert "expert" in flat, (leaf, spec)
        loss = float(engine.train_batch(batches))
        assert np.isfinite(loss)


class TestMoEObservability:
    """moe/* gauge family + per-expert numerics groups, emitted by a
    real engine run (telemetry/moe.py, telemetry/numerics.py)."""

    def test_gauges_and_expert_groups_emit(self, eight_devices, tmp_path):
        from deepspeed_tpu.telemetry.moe import MOE_METRIC_TAGS
        from deepspeed_tpu.telemetry.registry import InMemorySink

        engine, batches = _moe_gpt_engine(
            build_mesh(data=4, expert=2),
            {"train_micro_batch_size_per_gpu": 2,
             "gradient_accumulation_steps": 1,
             "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
             "zero_optimization": {"stage": 1},
             "moe": {"enabled": True, "num_experts": 4, "k": 1,
                     "dispatch": "alltoall"},
             "telemetry": {"enabled": True, "dir": str(tmp_path),
                           "numerics": {"enabled": True}},
             "steps_per_print": 1})
        sink = engine.telemetry.registry.add_sink(InMemorySink())
        for _ in range(2):
            engine.train_batch(batches)
        tags = {r["tag"] for r in sink.rows}
        assert MOE_METRIC_TAGS <= tags, MOE_METRIC_TAGS - tags
        # every gauge value is finite and overflow is a fraction
        for r in sink.rows:
            if r["tag"] in MOE_METRIC_TAGS:
                assert np.isfinite(r["value"])
            if r["tag"] == "moe/capacity_overflow_frac":
                assert 0.0 <= r["value"] <= 1.0
        groups = {r.get("group") for r in sink.rows if r.get("group")}
        for i in range(4):
            assert f"moe_expert_{i}" in groups, groups

    def test_monitor_gated_on_config(self):
        from deepspeed_tpu.config.config import DeepSpeedTPUConfig
        from deepspeed_tpu.telemetry.moe import build_moe_monitor

        base = {"train_batch_size": 8, "mesh": {"expert": 2}}
        on = DeepSpeedTPUConfig(
            {**base, "moe": {"enabled": True, "num_experts": 4},
             "telemetry": {"enabled": True}}, world_size=8)
        assert build_moe_monitor(on) is not None
        no_moe = DeepSpeedTPUConfig(
            {**base, "telemetry": {"enabled": True}}, world_size=8)
        assert build_moe_monitor(no_moe) is None
        no_tel = DeepSpeedTPUConfig(
            {**base, "moe": {"enabled": True, "num_experts": 4}},
            world_size=8)
        assert build_moe_monitor(no_tel) is None


class TestMoEOffContract:
    """Zero-overhead-off: no `moe` config block => the lowered train
    step is bit-identical to an explicit `enabled: false` block, and the
    engine carries no monitor."""

    def _lowered(self, eight_devices_mesh_unused, extra):
        from deepspeed_tpu.models import make_gpt

        model, _ = make_gpt("tiny", vocab_size=256, max_seq_len=16,
                            hidden_size=32, num_layers=2, num_heads=4,
                            dropout_rate=0.0, dtype=jnp.float32)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 256, (8, 16), dtype=np.int32)
        params = model.init({"params": jax.random.PRNGKey(0),
                             "dropout": jax.random.PRNGKey(1)},
                            {"input_ids": ids})["params"]
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, params=params,
            config={"train_micro_batch_size_per_gpu": 1,
                    "gradient_accumulation_steps": 1,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 1}, **extra})
        batches = {"input_ids": ids[None, ...]}
        text = engine._train_step.lower(
            engine.state, batches, jnp.float32(1e-3)).as_text()
        return engine, text

    def test_absent_equals_disabled_bit_identical(self, eight_devices):
        eng_a, absent = self._lowered(eight_devices, {})
        eng_d, disabled = self._lowered(
            eight_devices, {"moe": {"enabled": False}})
        assert absent == disabled
        assert eng_a.moe_monitor is None and eng_d.moe_monitor is None

    def test_enabled_moe_changes_the_step(self, eight_devices, tmp_path):
        """The gauge plumbing is config-gated: the same MoE model lowers
        a different step once the `moe` block + telemetry are on (the
        moe aux rides the scan carry)."""
        mesh = build_mesh(data=4, expert=2)
        base = {"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 1}}
        texts = {}
        for name, extra in (
                ("off", {}),
                ("on", {"moe": {"enabled": True, "num_experts": 4, "k": 1,
                                "dispatch": "scatter"},
                        "telemetry": {"enabled": True,
                                      "dir": str(tmp_path)}})):
            engine, batches = _moe_gpt_engine(mesh, {**base, **extra})
            texts[name] = engine._train_step.lower(
                engine.state, batches, jnp.float32(1e-3)).as_text()
        assert texts["off"] != texts["on"]

    @pytest.mark.parametrize("listening", [False, True])
    def test_the_moe_stats_take_the_one_counters_path(
            self, eight_devices, tmp_path, listening):
        """One dict leaves the GAS scan (``step_aux["counters"]``): the
        model's moe_* stats ride it while the monitor listens, and both
        the monitor and the trace's hand-off get the same references;
        with no monitor nothing is stacked and nothing is kept."""
        from deepspeed_tpu.telemetry.moe import MOE_AUX_KEYS
        mesh = build_mesh(data=4, expert=2)
        config = {"train_micro_batch_size_per_gpu": 2,
                  "gradient_accumulation_steps": 1,
                  "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                  "zero_optimization": {"stage": 1}}
        if listening:
            config.update(
                moe={"enabled": True, "num_experts": 4, "k": 1,
                     "dispatch": "scatter"},
                telemetry={"enabled": True, "dir": str(tmp_path)})
        engine, batches = _moe_gpt_engine(mesh, config)
        engine.train_batch(batches)
        if not listening:
            assert engine.moe_monitor is None
            assert not engine._step_counters
            return
        (step, counters), = engine._step_counters
        assert step == 0 and set(counters) == set(MOE_AUX_KEYS)
        assert engine.moe_monitor._pending == counters
        assert engine.moe_monitor.last_step == 1


class TestProbeMoECLI:
    @pytest.mark.parametrize("probe", ["probe_moe.py"])
    def test_selftest_passes(self, probe):
        import subprocess
        import sys as _sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [_sys.executable, os.path.join(repo, "tools", probe),
             "--selftest"],
            capture_output=True, text=True, env=env, timeout=540)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert '"pass": true' in proc.stdout
