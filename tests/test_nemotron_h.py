"""Nemotron-H's hybrid block (``models/nemotron_h.py``, ``ops/ssm.py``,
the latent form of ``moe/dropless.py``) and its way through the serving
engine, on the CPU at the rehearsal's size with seeded weights, held to
the benchmark's plain float32 reference (``benchmarks/reference/
nemotron_h.py``: the recurrence one position at a time, a full masked
softmax, the experts as a loop with a mask; nothing of ``deepspeed_tpu``).

Tolerances. Model and reference both compute in float32 here, so they
differ by the order of their sums alone: logits of size 0.2 agree to
2e-5 (``TOL``). A bfloat16 SSM state, or router scores rounded to
bfloat16, moves them by 1e-3 and more: ``test_a_step_down_in_precision_
would_fail`` holds the tolerance to that.
"""

import os
import sys
import time
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import deepspeed_tpu                                            # noqa: E402
from benchmarks.harness import load_module, open_cell           # noqa: E402
from benchmarks.reference import nemotron_h as reference        # noqa: E402
from deepspeed_tpu.config.config import ConfigError             # noqa: E402
from deepspeed_tpu.moe import dropless                          # noqa: E402
from deepspeed_tpu.ops import ssm                               # noqa: E402
from deepspeed_tpu.serving import engine as serving_engine    # noqa: E402

CELL = "nemotron3s-serve-chat"
TOL = 2e-5
_, _, CONFIG, _ = open_cell(CELL, rehearsal=True)
FAMILY = load_module("families", CONFIG["family"])
REF_CONFIG = FAMILY.reference_config(CONFIG)
SERVING = {"max_batch_size": 4, "kv_block_size": 4, "kv_num_blocks": 65,
           "max_model_len": 64}


def build(pattern="EM*ME", dtype=jnp.float32, seed=0, **overrides):
    """``(model, params, the reference's config)`` at the rehearsal's
    size; ``overrides`` are keys of the configuration file."""
    config = dict(CONFIG, hybrid_override_pattern=pattern,
                  num_hidden_layers=len(pattern), **overrides)
    model, cfg = FAMILY.build_model(config)
    model = type(model)(type(cfg)(**{**cfg.__dict__, "dtype": dtype}))
    params = model.init({"params": jax.random.PRNGKey(seed)},
                        FAMILY.example_batch())["params"]
    params = jax.tree_util.tree_map(lambda x: x.astype(dtype), params)
    return model, params, FAMILY.reference_config(config)


def ids_of(seed, batch, seq):
    return np.random.default_rng(seed).integers(
        0, CONFIG["vocab_size"], (batch, seq)).astype(np.int32)


def serve(model, params, dtype=jnp.float32, capture=False, telemetry=None,
          **serving):
    srv = deepspeed_tpu.init_serving(
        model, params=params, dtype=dtype,
        config={"serving": {**SERVING, **serving}})
    srv.capture_logits = capture
    if telemetry is not None:
        srv.telemetry = telemetry
    return srv


def run_to_the_end(srv, limit=120.0):
    """``step()`` until idle, with a time limit of its own; returns the
    decode logits of every step, by request id."""
    rows, t0 = {}, time.monotonic()
    while not srv.idle():
        info = srv.step()
        for slot, rid in info.get("slots", {}).items():
            rows.setdefault(rid, []).append(info["logits"][slot].copy())
        assert time.monotonic() - t0 < limit, "the engine loop hangs"
    return rows


# ---------------------------------------------------------------------------
# The model against the reference
# ---------------------------------------------------------------------------

def test_the_full_forward_is_the_references():
    model, params, ref = build()
    ids = ids_of(1, 2, 21)              # 21: no multiple of the chunk's 8
    got = jax.jit(lambda p, i: model.apply(
        {"params": p}, {"input_ids": i})["logits"])(params, ids)
    want = reference.logits(params, ids, ref)
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("what", ["bfloat16 SSM state",
                                  "bfloat16 router scores"])
def test_a_step_down_in_precision_would_fail(what, monkeypatch):
    """The tolerance is tight enough: the same forward with one float32
    quantity rounded to bfloat16 is outside it."""
    if what == "bfloat16 SSM state":
        real = ssm.ssm_scan

        def rounded(*args, **kw):
            # the chunk states carried in bfloat16: one chunk a position
            y, last = real(*args, **dict(kw, chunk=1))
            return y.astype(jnp.bfloat16).astype(jnp.float32), last
        monkeypatch.setattr(ssm, "ssm_scan", rounded)
    else:
        def rounded(x, kernel, bias, *, k, scaling_factor=1.0,
                    norm_topk_prob=True):
            # the router's matmul and scores in bfloat16: near ties fall
            # together and the top-k picks other experts
            half = jnp.bfloat16
            scores = jax.nn.sigmoid(
                x.astype(half) @ kernel.astype(half)).astype(jnp.float32)
            _, chosen = jax.lax.top_k(scores + bias, k)
            weights = jnp.take_along_axis(scores, chosen, axis=-1)
            weights = weights / weights.sum(-1, keepdims=True)
            return chosen.astype(jnp.int32), weights * scaling_factor
        monkeypatch.setattr(dropless, "route", rounded)
    model, params, ref = build()
    ids = ids_of(1, 2, 21)
    got = model.apply({"params": params}, {"input_ids": ids})["logits"]
    want = reference.logits(params, ids, ref)
    assert float(jnp.abs(got - want).max()) > 10 * TOL


@pytest.mark.parametrize("length,chunk", [(21, 8), (8, 8), (5, 8), (33, 16)])
def test_the_chunked_scan_is_the_one_position_recurrence(length, chunk):
    """Also from a state that is not zero, and with positions whose ``dt``
    is 0 (a prompt's padding): they leave the state as it is."""
    rng = np.random.default_rng(length)
    b, h, p, g, n = 2, 4, 8, 2, 16
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    u, bm, cm = f(b, length, h, p), f(b, length, g, n), f(b, length, g, n)
    dt = jax.nn.softplus(f(b, length, h))
    dt = dt.at[1, length - 2:].set(0.0)          # row 1 ends two earlier
    a, d, h0 = -jnp.exp(f(h) * 0.3), f(h), f(b, h, p, n)
    y, last = ssm.ssm_scan(u, dt, a, bm, cm, d, chunk=chunk, state=h0)
    state, ys = h0, []
    for t in range(length):
        y_t, state = ssm.ssm_step(u[:, t], dt[:, t], a, bm[:, t], cm[:, t],
                                  d, state)
        ys.append(y_t)
    np.testing.assert_allclose(y, jnp.stack(ys, 1), atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(last, state, atol=2e-4, rtol=1e-4)
    # row 1's state is what it was two positions before the end
    early = ssm.ssm_scan(u[1:, :length - 2], dt[1:, :length - 2], a,
                         bm[1:, :length - 2], cm[1:, :length - 2], d,
                         chunk=chunk, state=h0[1:])[1]
    np.testing.assert_allclose(last[1:], early, atol=2e-4, rtol=1e-4)


def test_a_dead_row_keeps_its_state_and_a_live_one_is_its_own():
    rng = np.random.default_rng(0)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    b, h, p, g, n = 3, 4, 8, 2, 16
    args = (f(b, h, p), jax.nn.softplus(f(b, h)), -jnp.exp(f(h)),
            f(b, g, n), f(b, g, n), f(h))
    state = f(b, h, p, n)
    live = jnp.asarray([True, False, True])
    y, new = ssm.ssm_step(*args, state, live)
    assert np.array_equal(new[1], state[1])
    alone = ssm.ssm_step(*(x[2:] if x.ndim > 1 else x for x in args),
                         state[2:])
    assert np.array_equal(new[2:], alone[1]) and np.array_equal(y[2:],
                                                                alone[0])


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """First held expert 0, 2, 4, 6 of 8, two held each: the routed parts
    add up and the shared expert is counted once."""
    hidden, t = CONFIG["hidden_size"], 24
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, t, hidden)),
                    jnp.float32)
    whole_cfg = dict(REF_CONFIG, n_routed_experts=8, first_held_expert=0)

    def layer(first, held):
        model, cfg = FAMILY.build_model(dict(
            CONFIG, n_routed_experts=held, first_held_expert=first))
        moe = dropless.DroplessMoE(type(cfg)(**{
            **cfg.__dict__, "dtype": jnp.float32}).moe())
        return moe

    whole = layer(0, 8)
    params = whole.init(jax.random.PRNGKey(3), x)["params"]
    want = reference.experts(x[0], params, whole_cfg)
    got_whole, _ = whole.apply({"params": params}, x)
    np.testing.assert_allclose(got_whole[0], want, atol=TOL, rtol=0)
    shared = (reference.relu2(x[0] @ params["shared_up"]["kernel"])
              @ params["shared_down"]["kernel"])
    total = 0.0
    for first in (0, 2, 4, 6):
        mine = dict(params, experts_up=params["experts_up"][first:first + 2],
                    experts_down=params["experts_down"][first:first + 2])
        part, _ = layer(first, 2).apply({"params": mine}, x)
        total = total + (part[0] - shared)
    np.testing.assert_allclose(total + shared, want, atol=2 * TOL, rtol=0)


def test_dead_rows_are_routed_nowhere_and_the_counters_count_the_rest():
    model, cfg = FAMILY.build_model(CONFIG)
    moe = dropless.DroplessMoE(type(cfg)(**{
        **cfg.__dict__, "dtype": jnp.float32}).moe())
    x = jnp.asarray(np.random.default_rng(4).normal(size=(6, 1, 64)),
                    jnp.float32)
    params = moe.init(jax.random.PRNGKey(5), x)["params"]
    live = jnp.asarray([True, False, True, True, False, False])
    y, counters = moe.apply({"params": params}, x, live=live[:, None])
    chosen, _ = dropless.route(x[:, 0], params["router"],
                               params["e_score_correction_bias"],
                               k=cfg.experts_per_token)
    held = np.asarray(chosen)[np.asarray(live)] < cfg.n_held_experts
    assert int(counters["held_assignments"]) == held.sum()
    assert int(counters["experts_touched"]) == len(set(
        np.asarray(chosen)[np.asarray(live)][held]))
    # a dead row gets the shared expert's part and nothing routed
    only_shared, _ = moe.apply({"params": params}, x,
                               live=jnp.zeros((6, 1), bool))
    assert np.array_equal(y[1], only_shared[1])
    alive, _ = moe.apply({"params": params}, x[:1], live=live[:1, None])
    np.testing.assert_allclose(y[0], alive[0], atol=1e-6)


# ---------------------------------------------------------------------------
# Through the serving engine: state and pool
# ---------------------------------------------------------------------------

def test_prefill_then_decode_gives_the_references_logits_everywhere():
    model, params, ref = build()
    srv = serve(model, params, capture=True)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 512, n).tolist() for n in (5, 13, 9, 21, 7)]
    rids = [srv.submit(p, 7) for p in prompts]
    rows = run_to_the_end(srv)
    assert any(n > 1 for n in srv.stats["slot_assignments"].values())
    for rid in rids:
        tokens = srv.results[rid]["tokens"]
        want = reference.logits(params, np.asarray([tokens], np.int32),
                                ref)[0]
        first = srv.results[rid]["prompt_len"]
        # the prefill's token, then every decode step's logits
        assert tokens[first] == int(jnp.argmax(want[first - 1]))
        got = np.stack(rows[rid])
        np.testing.assert_allclose(got, want[first:first + len(got)],
                                   atol=TOL, rtol=0)
        assert tokens[first + 1:] == list(np.argmax(got, -1))
    srv.close()


def test_a_slot_reused_gives_the_bits_a_fresh_engine_gives():
    model, params, _ = build()
    rng = np.random.default_rng(7)
    first = [rng.integers(0, 512, n).tolist() for n in (9, 17, 6, 11)]
    again = rng.integers(0, 512, 12).tolist()
    used = serve(model, params, capture=True)
    for p in first:
        used.submit(p, 5)
    run_to_the_end(used)
    rid = used.submit(again, 6)
    got = run_to_the_end(used)[rid]
    fresh = serve(model, params, capture=True)
    rid0 = fresh.submit(again, 6)
    want = run_to_the_end(fresh)[rid0]
    assert used.stats["slot_assignments"][used.results[rid]["slot"]] == 2
    assert used.results[rid]["tokens"] == fresh.results[rid0]["tokens"]
    assert np.array_equal(np.stack(got), np.stack(want))
    used.close(), fresh.close()


def test_another_slot_and_other_neighbours_give_the_same_tokens():
    """PR 27's promise, held for the new state: a request's output does
    not depend on which slot it sits in or on who else is alive."""
    model, params, _ = build()
    rng = np.random.default_rng(8)
    mine = rng.integers(0, 512, 10).tolist()
    others = [rng.integers(0, 512, n).tolist() for n in (4, 19, 8)]
    alone = serve(model, params, capture=True)
    rid0 = alone.submit(mine, 8)
    want = run_to_the_end(alone)[rid0]
    crowd = serve(model, params, capture=True)
    for p in others:
        crowd.submit(p, 11)
    rid = crowd.submit(mine, 8)
    got = run_to_the_end(crowd)[rid]
    assert crowd.results[rid]["slot"] != alone.results[rid0]["slot"]
    assert crowd.results[rid]["tokens"] == alone.results[rid0]["tokens"]
    np.testing.assert_allclose(np.stack(got), np.stack(want), atol=1e-6,
                               rtol=0)
    alone.close(), crowd.close()


def test_a_preempted_request_restarts_and_ends_with_the_same_tokens():
    model, params, _ = build()
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 512, 12).tolist() for _ in range(2)]
    roomy = serve(model, params)
    want = [roomy.submit(p, 30) for p in prompts]
    run_to_the_end(roomy)
    tight = serve(model, params, kv_num_blocks=17)
    got = [tight.submit(p, 30) for p in prompts]
    run_to_the_end(tight)
    assert tight.sched.preempted_total >= 1
    for a, b in zip(want, got):
        assert roomy.results[a]["tokens"] == tight.results[b]["tokens"]
    roomy.close(), tight.close()


@pytest.mark.parametrize("option,needle", [
    ({"prefix_cache": True}, "serving.prefix_cache"),
    ({"speculative": {"enabled": True}}, "serving.speculative"),
    ({"chunked_prefill": {"enabled": True}}, "serving.chunked_prefill"),
    ({"resilience": {"enabled": True}}, "serving.resilience"),
    ({"decode_attention": "kernel"}, "grouped"),
])
def test_what_recurrent_state_cannot_do_is_refused_at_construction(
        option, needle):
    model, params, _ = build("EM*")
    with pytest.raises(ConfigError, match=needle) as err:
        serve(model, params, **option)
    if "decode_attention" not in option:
        assert "recurrent" in str(err.value)


class RecordingTelemetry:
    """The engine's spans with their stats, and nothing else."""
    enabled = False

    def __init__(self):
        self.spans = []

    @contextmanager
    def span(self, name, **stats):
        record = {"name": name, **stats}
        self.spans.append(record)
        record["set_metadata"] = record.update
        yield type("Span", (), {"set_metadata": staticmethod(record.update)})

    def close(self):
        pass


def test_the_spans_carry_the_counters_of_the_new_state(monkeypatch):
    # they reach a span only while a profiler session records
    monkeypatch.setattr(serving_engine, "profiler_session_live",
                        lambda: True)
    model, params, _ = build()
    tel = RecordingTelemetry()
    srv = serve(model, params, telemetry=tel)
    rng = np.random.default_rng(10)
    for n in (6, 14, 9):
        srv.submit(rng.integers(0, 512, n).tolist(), 4)
    run_to_the_end(srv)
    decodes = [s for s in tel.spans if s["name"] == "decode_step"]
    prefills = [s for s in tel.spans if s["name"] == "prefill"]
    assert decodes and len(prefills) == 3
    layers, held, k = 2, CONFIG["n_routed_experts"], 2
    for s in decodes:
        assert s["state_slots_live"] == s["active"] >= 1
        assert 0 <= s["moe_held_assignments"] <= s["active"] * k * layers
        assert s["moe_experts_touched"] <= min(held * layers,
                                               s["moe_held_assignments"])
        assert s["moe_held_rows_max"] <= s["active"]
        assert s["read_positions"] >= s["live_positions"] > 0
    for s in prefills:
        assert 0 <= s["moe_held_assignments"] <= s["prompt_len"] * k * layers
    srv.close()


class Watched:
    """An output of the decode program that says when the host reads it:
    the engine may hand it on, and every read (``np.asarray``) goes on
    the list; any other use of it fails."""

    def __init__(self, name, array, reads):
        self.name, self.array, self.reads = name, array, reads
        self.nbytes = array.nbytes

    def __array__(self, dtype=None, copy=None):
        self.reads.append(self.name)
        return np.asarray(self.array, dtype=dtype)


def test_an_untraced_step_fetches_nothing_but_its_tokens(monkeypatch):
    """Of what the decode program returns the host reads the batch's
    tokens and nothing else; the model's counters too only while a
    profiler session records."""
    model, params, _ = build()
    srv = serve(model, params)
    rng = np.random.default_rng(12)
    for n in (6, 9):
        srv.submit(rng.integers(0, 512, n).tolist(), 8)
    while len(srv.sched.running) < 2:   # both prefilled, decoding
        srv.step()
    reads, program = [], srv._decode_jit

    def watched(*args, **kw):
        tok, logits, pools, counters = program(*args, **kw)
        return (Watched("tokens", tok, reads),
                Watched("logits", logits, reads), pools,
                Watched("counters", counters, reads))

    srv._decode_jit = watched
    for traced, want in ((False, ["tokens"]),
                         (True, ["tokens", "counters"]),
                         (False, ["tokens"])):
        monkeypatch.setattr(serving_engine, "profiler_session_live",
                            lambda: traced)
        reads.clear()
        info = srv.step()
        assert (info["active"], info["prefilled"]) == (2, [])
        assert reads == want, traced
    srv.close()


# ---------------------------------------------------------------------------
# The shared expert's two passes
# ---------------------------------------------------------------------------

def test_two_halves_read_a_float32_input_to_sixteen_bits():
    """hi + lo through one matmul of 2 T rows: what is lost of a float32
    input is 2^-16 of it and less, where one rounding loses up to 2^-9:
    the result is a hundred times nearer the float32 product."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(6, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 48)), jnp.bfloat16)
    seen = []

    def dense(rows):
        seen.append(rows)
        return jnp.dot(rows, w, preferred_element_type=jnp.float32)
    want = jnp.dot(x, w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    two = dropless._in_two_halves(dense, x, jnp.bfloat16)
    one = dense(x.astype(jnp.bfloat16))
    assert seen[0].shape == (12, 256) and seen[0].dtype == jnp.bfloat16
    assert two.shape == want.shape and two.dtype == jnp.float32
    far = lambda got: float(jnp.abs(got - want).max())
    assert far(one) > 1e-2              # 2^-9 of inputs of size 1 to 3
    assert far(two) < far(one) / 100


def test_two_passes_bring_the_expert_layer_nearer_its_float32_self():
    """One expert layer on bfloat16 weights and a float32 input (the
    router reads the same numbers, so the choices are the same): with the
    shared expert's inputs in two halves its result is nearer the float32
    layer's than with one rounding (0.47 of it here; the routed
    experts, a small part of the result, still round once)."""
    def layer(dtype, two_pass):
        return dropless.DroplessMoE(dropless.DroplessMoEConfig(
            hidden_size=64, expert_intermediate=48, n_routed_experts=8,
            n_held_experts=4, first_held_expert=0, experts_per_token=2,
            shared_intermediate=96, routed_scaling_factor=2.5, dtype=dtype,
            latent_size=32, activation="relu2", out_dtype=jnp.float32,
            shared_two_pass=two_pass))
    x = jnp.asarray(np.random.default_rng(7).normal(size=(2, 24, 64)),
                    jnp.float32)
    params = layer(jnp.float32, False).init(jax.random.PRNGKey(7), x)
    # matrices large enough that the shared expert is most of the result,
    # as at the cell's widths, and exactly bfloat16's numbers
    params = jax.tree_util.tree_map(
        lambda w: (w * 8).astype(jnp.bfloat16).astype(jnp.float32), params)
    want, _ = layer(jnp.float32, False).apply(params, x)
    far = {}
    for two_pass in (False, True):
        got, _ = layer(jnp.bfloat16, two_pass).apply(params, x)
        assert got.dtype == jnp.float32
        far[two_pass] = float(jnp.abs(got - want).mean())
    assert far[True] < 0.6 * far[False], far
    same, _ = layer(jnp.float32, True).apply(params, x)
    np.testing.assert_allclose(same, want, rtol=1e-5, atol=1e-5)


def test_two_passes_are_for_the_relu2_shared_expert_with_float32_out():
    base = dict(hidden_size=8, expert_intermediate=8, n_routed_experts=4,
                n_held_experts=4, first_held_expert=0, experts_per_token=2,
                shared_intermediate=8)
    dropless.DroplessMoEConfig(**base, activation="relu2",
                               out_dtype=jnp.float32, shared_two_pass=True)
    for wrong in (dict(activation="swiglu", out_dtype=jnp.float32),
                  dict(activation="relu2")):
        with pytest.raises(ValueError, match="shared_two_pass"):
            dropless.DroplessMoEConfig(**base, **wrong, shared_two_pass=True)


# ---------------------------------------------------------------------------
# What a flipped choice costs (ISSUE 35, part 4)
# ---------------------------------------------------------------------------

def test_e_with_own_and_with_the_references_routing(capsys):
    """bfloat16 against the float32 reference on the SAME bfloat16
    weights, in the unit the benchmark holds (a position's e = max over
    the vocabulary of |system - reference| over the standard deviation of
    that position's reference logits): once with the system's own routing
    and once with the reference's choices forced on it. Near ties flip a
    share of the choices; with matrices at normal 0.02 that costs little,
    and both readings keep well inside the driver's limit (0.088)."""
    # 8 of 64 experts a token, 16 held: enough near ties to see flips
    model, params, ref = build(
        "EMEM*", dtype=jnp.bfloat16, seed=11, num_experts_per_tok=8,
        n_routed_experts=16, published={"n_routed_experts": 64})
    ids = ids_of(11, 4, 64)
    recorded = []
    want = reference.logits(params, ids, ref, record=recorded)
    spread = want.std(-1)
    apply = lambda: model.apply({"params": params},
                                {"input_ids": ids})["logits"]
    real, own = dropless.route, []

    def noting(*args, **kw):
        own.append(real(*args, **kw))
        return own[-1]

    def forced(*args, **kw):
        chosen, weights = recorded[len(used)]
        used.append(1)
        return chosen.astype(jnp.int32), weights

    try:
        dropless.route = noting
        e_own = jnp.abs(apply() - want).max(-1) / spread
        used = []
        dropless.route = forced
        e_forced = jnp.abs(apply() - want).max(-1) / spread
    finally:
        dropless.route = real
    assert len(own) == len(recorded) == len(used) == 2
    flipped = [float(np.mean([set(a) != set(b) for a, b in zip(
        np.asarray(mine[0]), np.asarray(theirs[0]))]))
        for mine, theirs in zip(own, recorded)]
    with capsys.disabled():
        print(f"\ne, bfloat16 v float32 reference, {ids.size} positions: "
              f"own routing median {float(jnp.median(e_own)):.4f} max "
              f"{float(e_own.max()):.4f}; the reference's routing forced "
              f"median {float(jnp.median(e_forced)):.4f} max "
              f"{float(e_forced.max()):.4f}; share of tokens with a "
              f"flipped choice per expert layer {flipped}")
    assert float(jnp.median(e_forced)) <= float(jnp.median(e_own)) * 1.1
    assert float(jnp.median(e_own)) < 0.088
