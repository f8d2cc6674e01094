"""The ``glm4_moe_lite`` block (``models/glm4_moe_lite.py``) and the
dropless expert layer (``moe/dropless.py``) at the tiny preset, in float32
on the CPU, against the benchmark's plain reference
(``benchmarks/reference/glm4_moe_lite.py``).

Tolerances. System and reference both compute in float32 here and differ
in the order of their sums (a sort and a grouped matmul against a dense
loop, a fused cross entropy against a log-softmax): logits and losses
agree to a few 1e-6 relative, gradients to 1e-5 of their leaf's largest
entry. ``RTOL`` leaves ten times that. ``test_a_wrong_variant_...`` shows
that it is tight: a bfloat16 router, a softmax router, a lost assignment,
a missing shared expert, a missing scaling factor, a missing
renormalisation and MTP labels shifted by one are each off by at least ten
times the tolerance on the same weights.
"""

import dataclasses
import glob
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import deepspeed_tpu  # noqa: E402
from benchmarks import program_trace as pt  # noqa: E402
from benchmarks.reference import common as ref_common  # noqa: E402
from benchmarks.reference import glm4_moe_lite as ref  # noqa: E402
from benchmarks.reference.optimizers import adam as ref_adam  # noqa: E402
from deepspeed_tpu.models import (build_specs,  # noqa: E402
                                  glm4_moe_lite_partition_rules,
                                  make_glm4_moe_lite)
from deepspeed_tpu.models.glm4_moe_lite import (TINY,  # noqa: E402
                                                LatentAttention)
from deepspeed_tpu.moe.dropless import (DroplessMoE,  # noqa: E402
                                        DroplessMoEConfig, route)
from deepspeed_tpu.parallel.mesh import build_mesh  # noqa: E402

RTOL = 1e-4         # of the largest entry compared; see the module's doc
SEQ = 16


def settings(cfg):
    """The reference's keywords for a model configuration."""
    return ref.settings({**dataclasses.asdict(cfg),
                         "assumed": {"mtp_loss_weight": cfg.mtp_loss_weight}})


def close(got, want, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() <= rtol * np.abs(want).max()


def far(got, want):
    """Off by at least ten times the tolerance."""
    return not close(got, want, 10 * RTOL)


@pytest.fixture(scope="module")
def setup():
    """The tiny model in float32 with seeded weights, every matrix four
    times its initial size so that the router's scores spread, the experts
    weigh beside the residual stream and a wrong variant shows."""
    model, cfg = make_glm4_moe_lite(dtype=jnp.float32)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, SEQ)).astype(np.int32)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        {"input_ids": ids})["params"]
    params = jax.tree_util.tree_map(
        lambda a: a * 4.0 if a.ndim >= 2 else a, params)
    batch = {"input_ids": ids}
    out = jax.jit(lambda p: model.apply({"params": p}, batch))(params)
    kw = settings(cfg)
    main, mtp = jax.jit(lambda p: ref.logits(p, ids, **kw))(params)
    return dict(model=model, cfg=cfg, params=params, batch=batch, out=out,
                kw=kw, ref_main=main, ref_mtp=mtp)


# ---------------------------------------------------------------------------
# The whole model against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("what", ["logits", "mtp_logits", "loss"])
def test_forward_matches_the_reference(setup, what):
    s = setup
    if what == "loss":
        want = ref.loss(s["params"], s["batch"], **s["kw"])
        assert close(s["out"]["loss"], want, 1e-5)
    else:
        want = s["ref_main"] if what == "logits" else s["ref_mtp"]
        assert close(s["out"][what], want)


@pytest.fixture(scope="module")
def gradients(setup):
    s = setup
    got = jax.jit(jax.grad(lambda p: s["model"].apply(
        {"params": p}, s["batch"])["loss"]))(s["params"])
    want = jax.jit(jax.grad(lambda p: ref.loss(p, s["batch"], **s["kw"])))(
        s["params"])
    return got, want


@pytest.mark.parametrize("group", [
    "embed_tokens", "lm_head", "layers_0", "layers_1", "layers_2", "norm",
    "mtp_enorm", "mtp_hnorm", "mtp_eh_proj", "mtp_block", "mtp_norm"])
def test_gradients_match_the_reference(gradients, group):
    got, want = gradients
    flat = lambda tree: jax.tree_util.tree_flatten_with_path(tree[group])[0]
    for (path, g), (_, w) in zip(flat(got), flat(want)):
        name = jax.tree_util.keystr(path)
        if name.endswith("e_score_correction_bias']"):
            assert not np.asarray(g).any() and not np.asarray(w).any()
        else:
            assert np.abs(np.asarray(w)).max() > 0, name
            assert close(g, w), name


def with_router(monkeypatch, scores_of):
    """The reference with another router: ``scores_of(x, W)``."""
    def router_weights(x, p, *, k, factor, norm_topk):
        s = scores_of(x, p["router"])
        _, chosen = jax.lax.top_k(s + p["e_score_correction_bias"], k)
        w = jnp.zeros_like(s).at[
            jnp.arange(s.shape[0])[:, None], chosen].set(
                jnp.take_along_axis(s, chosen, -1))
        w = w / (w.sum(-1, keepdims=True) + 1e-20) if norm_topk else w
        return w * factor
    monkeypatch.setattr(ref, "router_weights", router_weights)


def bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@pytest.mark.parametrize("variant", [
    "bf16_router", "softmax_router", "lost_assignment", "no_shared_expert",
    "no_scaling_factor", "no_renormalisation"])
def test_a_wrong_variant_of_the_expert_layer_fails_the_tolerance(
        setup, variant, monkeypatch):
    s = setup
    kw, params = dict(s["kw"]), s["params"]
    if variant == "bf16_router":
        with_router(monkeypatch, lambda x, w: bf16(jax.nn.sigmoid(
            bf16(bf16(x) @ bf16(w)))))
    elif variant == "softmax_router":
        with_router(monkeypatch, lambda x, w: jax.nn.softmax(x @ w, -1))
    elif variant == "lost_assignment":
        kw["k"] -= 1            # every token loses its weakest choice
    elif variant == "no_shared_expert":
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: a * 0.0 if "shared_down" in jax.tree_util.keystr(
                path) else a, params)
    elif variant == "no_scaling_factor":
        kw["factor"] = 1.0
    elif variant == "no_renormalisation":
        kw["norm_topk"] = False
    main, mtp = ref.logits(params, s["batch"]["input_ids"], **kw)
    assert far(s["out"]["logits"], main)
    assert far(s["out"]["mtp_logits"], mtp)


def cross_entropy(logits, labels):
    return float(ref_common.mean_of(ref_common.token_nll(logits, labels)))


def test_the_mtp_labels_are_shifted_by_two(setup):
    s = setup
    ids = s["batch"]["input_ids"]
    left = lambda t: np.pad(t[:, 1:], ((0, 0), (0, 1)),
                            constant_values=-100)
    main = cross_entropy(s["out"]["logits"], left(ids))
    by_two = cross_entropy(s["out"]["mtp_logits"], left(left(ids)))
    by_one = cross_entropy(s["out"]["mtp_logits"], left(ids))
    weight = s["cfg"].mtp_loss_weight
    loss = float(s["out"]["loss"])
    assert loss == pytest.approx(main + weight * by_two, rel=1e-5)
    assert abs(loss - (main + weight * by_one)) > 10 * RTOL * loss
    # position i sees t_0..t_{i+1} and no further: another t_{i+2} moves
    # nothing at i, another t_{i+1} does
    moved = ids.copy()
    moved[:, 9] = (moved[:, 9] + 1) % s["cfg"].vocab_size
    again = s["model"].apply({"params": s["params"]}, {"input_ids": moved})
    same = np.isclose(again["mtp_logits"], s["out"]["mtp_logits"],
                      rtol=1e-5, atol=1e-6).all(-1)
    assert same[:, :8].all() and not same[:, 8].any()


# ---------------------------------------------------------------------------
# Latent attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seq", [8, 32])
def test_mla_is_attention_over_explicitly_expanded_keys_and_values(seq):
    """The module against plain multi-head attention whose per-head keys
    and values were written out from the latent by the reference."""
    from deepspeed_tpu.ops.transformer.attention import xla_attention
    cfg = make_glm4_moe_lite(dtype=jnp.float32)[1]
    x = jax.random.normal(jax.random.PRNGKey(seq), (2, seq, cfg.hidden_size))
    module = LatentAttention(cfg)
    p = module.init(jax.random.PRNGKey(1), x)["params"]
    p = jax.tree_util.tree_map(lambda a: a * 4.0 if a.ndim >= 2 else a, p)
    kw = {k: v for k, v in settings(cfg).items()
          if k in ("n_head", "nope", "rope_dim", "rank", "eps", "theta")}
    with jax.default_matmul_precision("highest"):
        k, v = ref.expanded_keys_and_values(x, p, **kw)
        c_q = ref.rms_norm(x @ p["q_a_proj"]["kernel"],
                           p["q_a_layernorm"], cfg.rms_norm_eps)
        q = (c_q @ p["q_b_proj"]["kernel"]).reshape(
            2, seq, cfg.num_attention_heads, -1)
        q = jnp.concatenate([q[..., :kw["nope"]],
                             ref.rope(q[..., kw["nope"]:], kw["theta"])], -1)
        want = xla_attention(q, k, v, causal=True).reshape(
            2, seq, -1) @ p["o_proj"]["kernel"]
    assert k.shape == (2, seq, cfg.num_attention_heads, cfg.qk_head_dim)
    # one rotary key per position, the same in every head
    assert (np.asarray(k[..., kw["nope"]:])
            == np.asarray(k[:, :, :1, kw["nope"]:])).all()
    assert close(module.apply({"params": p}, x), want)


def test_rope_turns_pairs_by_their_position_and_keeps_their_length():
    from deepspeed_tpu.models.glm4_moe_lite import rotate
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 3, 8))
    got = rotate(x, 1e6)
    assert close(got, ref.rope(x, 1e6), 1e-6)
    assert np.allclose(got[:, 0], x[:, 0])                  # position 0
    pair = lambda t, i: np.hypot(t[..., i], t[..., i + 4])
    assert np.allclose(pair(np.asarray(got), 1), pair(np.asarray(x), 1),
                       rtol=1e-5)


# ---------------------------------------------------------------------------
# The router
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def routed():
    x = jax.random.normal(jax.random.PRNGKey(3), (64, 16))
    w = jax.random.normal(jax.random.PRNGKey(4), (16, 8)) * 0.5
    return x, w, jax.nn.sigmoid(x @ w)


@pytest.mark.parametrize("case", ["sigmoid_scores", "renormalised",
                                  "scaled_by_the_factor",
                                  "bias_moves_the_choice_not_the_weight",
                                  "float32_from_bfloat16"])
def test_the_router(routed, case):
    x, w, scores = routed
    zeros = jnp.zeros((8,))
    if case == "sigmoid_scores":
        chosen, weights = route(x, w, zeros, k=3, norm_topk_prob=False)
        top = np.sort(np.asarray(scores), -1)[:, ::-1][:, :3]
        assert close(weights, top, 1e-6)
        assert close(np.take_along_axis(np.asarray(scores),
                                        np.asarray(chosen), -1), top, 1e-6)
    elif case == "renormalised":
        _, weights = route(x, w, zeros, k=3)
        assert close(weights.sum(-1), np.ones(64), 1e-6)
        _, raw = route(x, w, zeros, k=3, norm_topk_prob=False)
        assert close(weights, raw / raw.sum(-1, keepdims=True), 1e-6)
    elif case == "scaled_by_the_factor":
        _, one = route(x, w, zeros, k=3)
        _, scaled = route(x, w, zeros, k=3, scaling_factor=1.8)
        assert close(scaled, 1.8 * one, 1e-6)
        assert close(scaled.sum(-1), np.full(64, 1.8), 1e-6)
    elif case == "bias_moves_the_choice_not_the_weight":
        bias = zeros.at[5].set(10.0)
        chosen, weights = route(x, w, bias, k=3, norm_topk_prob=False)
        assert (np.asarray(chosen)[:, 0] == 5).all()    # every token's first
        assert close(weights[:, 0], scores[:, 5], 1e-6)  # the score, no bias
        plain, _ = route(x, w, zeros, k=3)
        assert not (np.asarray(plain) == 5).any(-1).all()
    else:
        chosen, weights = route(x.astype(jnp.bfloat16), w, zeros, k=3,
                                norm_topk_prob=False)
        assert weights.dtype == jnp.float32
        exact = jax.nn.sigmoid(bf16(x) @ w)
        assert close(weights, np.take_along_axis(
            np.asarray(exact), np.asarray(chosen), -1), 1e-6)


# ---------------------------------------------------------------------------
# The dropless layer and the share
# ---------------------------------------------------------------------------

def layer_setup(held, first=0, n_routed=8, k=2, tokens=(2, 24)):
    cfg = DroplessMoEConfig(
        hidden_size=32, expert_intermediate=16, n_routed_experts=n_routed,
        n_held_experts=held, first_held_expert=first, experts_per_token=k,
        shared_intermediate=16, routed_scaling_factor=1.8,
        dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), tokens + (32,))
    return cfg, x


def whole_layer_params():
    cfg, x = layer_setup(8)
    p = DroplessMoE(cfg).init(jax.random.PRNGKey(8), x)["params"]
    return jax.tree_util.tree_map(lambda a: a * 8.0 if a.ndim >= 2 else a, p)


def share_of(params, first, held):
    """The parameters the chip that holds ``[first, first + held)`` has."""
    return {k: (v[first:first + held] if k.startswith("experts_") else v)
            for k, v in params.items()}


REF_LAYER = dict(k=2, factor=1.8, norm_topk=True)


@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_uncut_reference_layer(held):
    """Every chip routes over all eight experts, normalises over both
    chosen ones and computes its own experts' part; the shared expert,
    which every chip computes alike, is counted once."""
    whole = whole_layer_params()
    _, x = layer_setup(8)
    uncut = ref.expert_layer(x, whole, first_held=0, **REF_LAYER)
    shared = ref.expert_layer(x, share_of(whole, 0, 0), first_held=0,
                              **REF_LAYER)
    total = shared
    for first in range(0, 8, held):
        cfg, _ = layer_setup(held, first)
        mine = share_of(whole, first, held)
        y, _ = jax.jit(lambda p: DroplessMoE(cfg).apply({"params": p}, x))(
            mine)
        part = y - shared
        # the reference, given the same share, gives the same part
        assert close(y, jax.jit(lambda p: ref.expert_layer(
            x, p, first_held=first, **REF_LAYER))(mine))
        if held < 8:
            assert far(part, uncut - shared)    # a part is not the whole
        total = total + part
    assert close(total, uncut)


@pytest.mark.parametrize("expert,held_here", [(2, True), (5, True),
                                              (0, False), (7, False)])
def test_no_assignment_is_lost_when_every_token_picks_the_same_expert(
        expert, held_here):
    """A bias sends every token to one expert: if it is held, its group
    holds every token and none is dropped (a capacity router would keep
    ``tokens / experts * factor`` of them); if not, it gets none."""
    whole = whole_layer_params()
    whole["e_score_correction_bias"] = (
        whole["e_score_correction_bias"].at[expert].set(10.0))
    cfg, x = layer_setup(4, first=2)
    params = share_of(whole, 2, 4)
    y, counters = DroplessMoE(cfg).apply({"params": params}, x)
    tokens = x.shape[0] * x.shape[1]
    assert close(y, ref.expert_layer(x, params, first_held=2, **REF_LAYER))
    if held_here:
        assert float(counters["held_rows_max"]) == tokens
        assert float(counters["no_held_expert_share"]) == 0.0
        assert float(counters["held_assignments_per_token"]) >= 1.0
    else:
        assert float(counters["held_rows_max"]) < tokens
        assert float(counters["held_assignments_per_token"]) <= 1.0


def test_the_counters_count_the_held_assignments():
    whole = whole_layer_params()
    cfg, x = layer_setup(4, first=4)
    _, counters = DroplessMoE(cfg).apply(
        {"params": share_of(whole, 4, 4)}, x)
    flat = x.reshape(-1, 32)
    chosen, _ = route(flat, whole["router"],
                      whole["e_score_correction_bias"], k=2)
    chosen = np.asarray(chosen)
    rows = np.array([(chosen == e).sum() for e in range(4, 8)])
    assert float(counters["held_assignments_per_token"]) == pytest.approx(
        rows.sum() / len(flat))
    assert float(counters["held_rows_max"]) == rows.max()
    assert float(counters["held_rows_mean"]) == pytest.approx(rows.mean())
    assert float(counters["no_held_expert_share"]) == pytest.approx(
        1.0 - ((chosen >= 4).any(-1)).mean())


def test_the_layer_is_differentiable_through_its_gathers():
    """Dispatch and combine are gathers with hand-written transposes."""
    whole = whole_layer_params()
    cfg, x = layer_setup(4, first=2)
    params = share_of(whole, 2, 4)
    loss = lambda f: lambda p, x: (f(p, x) ** 2).sum()
    got = jax.grad(loss(lambda p, x: DroplessMoE(cfg).apply(
        {"params": p}, x)[0]), argnums=(0, 1))(params, x)
    want = jax.grad(loss(lambda p, x: ref.expert_layer(
        x, p, first_held=2, **REF_LAYER)), argnums=(0, 1))(params, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert close(g, w) or not (np.asarray(w).any()
                                   or np.asarray(g).any())


def test_a_share_outside_the_routers_width_is_refused():
    with pytest.raises(ValueError, match="held experts"):
        layer_setup(4, first=6)
    with pytest.raises(ValueError, match="depth 0 or 1"):
        make_glm4_moe_lite(num_nextn_predict_layers=2)


def test_partition_rules_put_experts_on_expert_and_vocabulary_on_model(
        setup):
    specs = build_specs(setup["params"], glm4_moe_lite_partition_rules())
    mlp = specs["layers_1"]["mlp"]
    assert tuple(mlp["experts_gate"]) == ("expert", None, None)
    assert tuple(mlp["experts_down"]) == ("expert", None, None)
    assert tuple(mlp["router"]) == ()
    assert tuple(specs["embed_tokens"]) == ("model", None)
    assert tuple(specs["lm_head"]) == ("model", None)
    attn = specs["layers_0"]["self_attn"]
    assert tuple(attn["q_b_proj"]["kernel"]) == (None, "model")
    assert tuple(attn["o_proj"]["kernel"]) == ("model", None)
    assert tuple(attn["q_a_proj"]["kernel"]) == ()
    assert tuple(specs["layers_0"]["mlp"]["down_proj"]["kernel"]) == \
        ("model", None)


# ---------------------------------------------------------------------------
# Through initialize() / train_batch()
# ---------------------------------------------------------------------------

BATCHES = {"input_ids": np.random.default_rng(5).integers(
    0, TINY.vocab_size, (2, 2, SEQ)).astype(np.int32)}
LR = 1e-3


def engine(setup, dtype=jnp.float32, **extra):
    model, _ = make_glm4_moe_lite(dtype=dtype)
    config = {"train_micro_batch_size_per_gpu": 1,
              "gradient_accumulation_steps": 2,
              "optimizer": {"type": "Adam", "params": {"lr": LR}},
              "zero_optimization": {"stage": 2}, **extra}
    return deepspeed_tpu.initialize(
        model=model, params=setup["params"], config=config,
        mesh=build_mesh(data=2, devices=jax.devices()[:2]))[0]


@pytest.fixture(scope="module")
def reference_losses(setup):
    """The reference's loss on ``BATCHES`` at the seeded weights, and
    after ONE reference Adam step on the reference's gradient."""
    def mean(p):
        return sum(ref.loss(p, {"input_ids": ids}, **setup["kw"])
                   for ids in BATCHES["input_ids"]) / 2

    @jax.jit
    def both(p):
        loss_0, grads = jax.value_and_grad(mean)(p)
        return loss_0, mean(ref_adam.first_step(p, grads, lr=LR))

    return tuple(map(float, both(setup["params"])))


def test_one_train_batch_under_zero2_is_one_reference_adam_step(
        setup, reference_losses):
    """The first loss is the reference's at the seeded weights; the second,
    on the same batch, the reference's after one reference step (float32
    here, so far tighter than the benchmark's 10% of the step's effect)."""
    eng = engine(setup)
    first = float(eng.train_batch(BATCHES))
    second = float(eng.train_batch(BATCHES))
    loss_0, loss_1 = reference_losses
    assert first == pytest.approx(loss_0, rel=1e-5)
    assert loss_0 - loss_1 > 0.01
    assert abs(second - loss_1) < 0.01 * (loss_0 - loss_1)
    assert int(eng.skipped_steps) == 0


def test_bf16_training_stays_within_the_benchmarks_tolerances(
        setup, reference_losses):
    """bf16 compute from float32 masters, as the cell runs it, held to the
    driver's own limits (``LOSS_RTOL``, ``STEP_RTOL``)."""
    from benchmarks.harness import load_module
    eng = engine(setup, dtype=jnp.bfloat16, bf16={"enabled": True},
                 data_types={"grad_accum_dtype": "bfloat16"})
    losses = [float(eng.train_batch(BATCHES)) for _ in range(2)]
    assert load_module("drivers", "train_steps").compare(
        losses, *reference_losses) == []


def test_step_counters_reach_the_trace_only_while_a_profiler_records(
        setup, tmp_path, monkeypatch):
    """Untraced, ``train_batch`` keeps references and fetches nothing;
    traced, each earlier step's counters become the stats of one
    ``ds.step_counters`` span under ``ds.train_batch``."""
    eng, batches = engine(setup), BATCHES
    fetched = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: fetched.append(x) or real(x))
    for _ in range(3):
        jax.block_until_ready(eng.train_batch(batches))
    assert not fetched and len(eng._step_counters) == 2

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                jax.block_until_ready(eng.train_batch(batches))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    trace = pt.load(path)
    spans = pt.spans_in_window(trace, "step_counters")
    # ``of_step`` is the ``step`` of that step's own spans: the traced
    # steps are 3 and 4, and the counters in the window are of 1, 2 and 3
    assert [s.stats["step"] for s in pt.spans_in_window(
        trace, "train_step")] == [3, 4]
    assert [s.stats["of_step"] for s in spans] == [1, 2, 3]
    assert all(trace.spans[s.parent].name == "train_batch" for s in spans)
    for s in spans:
        assert 0 < s.stats["moe_held_assignments_per_token"] <= 2
        assert s.stats["moe_held_rows_max"] >= s.stats["moe_held_rows_mean"]
        assert 0 <= s.stats["moe_no_held_expert_share"] < 1
    assert len(fetched) == 3
