"""``ops/ssm.py: ssm_step_live``, the Mamba-2 decode step over the live
slots alone, on the CPU through the Pallas interpreter: against
``ssm_step``'s own XLA step, and by bits where it must keep them (a dead
slot's state, a row's result in any slot). Then the rule by which it is
taken (a TPU and a live mask: the serving decode alone) and who bypasses
it. That it compiles for a v5e at ``nemotron3s-serve-chat``'s widths:
``tests/test_tpu_lowering.py``."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import deepspeed_tpu                                            # noqa: E402
from deepspeed_tpu.models import make_gpt                       # noqa: E402
from deepspeed_tpu.ops import ssm                               # noqa: E402
from deepspeed_tpu.serving import engine as serving_engine      # noqa: E402
from tests.test_nemotron_h import (RecordingTelemetry, build,    # noqa: E402
                                   run_to_the_end, serve)

# 8 slots of 8 heads x 8 x 16, two groups of four heads
B, H, P, G, N = 8, 8, 8, 2, 16
MASKS = {
    "one row alive": [3],
    "the first and the last slot": [0, B - 1],
    "a scattered set": [1, 2, 4, 7],
    "all alive": list(range(B)),
    "none alive": [],
}


def inputs(seed, b=B, p=P):
    """``(u, dt, a, b, c, d, state)`` at the small widths, the matrices in
    bfloat16 as the model hands them over."""
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return (f(b, H, p).astype(jnp.bfloat16), jax.nn.softplus(f(b, H)),
            -jnp.exp(f(H) * 0.5), f(b, G, N).astype(jnp.bfloat16),
            f(b, G, N).astype(jnp.bfloat16), f(H), f(b, H, p, N))


def mask(slots, b=B):
    return jnp.zeros((b,), bool).at[jnp.asarray(slots, jnp.int32)].set(True)


def kernel(*args):
    return ssm.ssm_step_live(*args, interpret=True)


# P 8: a row of a block holds a group's four heads, 32 lanes; P 64 (the
# cell's): two heads fill a row's 128 lanes, two rows a group
@pytest.mark.parametrize("p", [P, 64],
                         ids=["a group a row", "two heads a row"])
@pytest.mark.parametrize("alive", list(MASKS), ids=list(MASKS))
def test_the_kernel_is_the_xla_step_on_the_live_rows(alive, p):
    """Live rows: ``y`` and the new state to float32 rounding. Dead slots:
    the state the bits it came with, ``y`` exactly 0 (with none alive,
    every slot as it was)."""
    args = inputs(1, p=p)
    live = mask(MASKS[alive])
    want_y, want_state = ssm.ssm_step(*args, live)
    y, new = kernel(*args, live)
    assert y.shape == want_y.shape and new.dtype == jnp.float32
    on = np.asarray(live)
    np.testing.assert_allclose(np.asarray(y)[on], np.asarray(want_y)[on],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new)[on],
                               np.asarray(want_state)[on], rtol=1e-5,
                               atol=1e-5)
    state = np.asarray(args[-1])
    assert np.array_equal(np.asarray(new)[~on], state[~on])
    assert not np.asarray(y)[~on].any()


def test_a_rows_result_is_the_same_bits_in_any_slot_beside_any_neighbours():
    """A request's output depends on neither its slot nor its neighbours,
    at the kernel: one request's inputs in slot 2 among some neighbours
    and in slot 6 among others give the same bits (the layer's ``A`` and
    ``D`` are the same for every row)."""
    mine, first, second = inputs(2, 1), inputs(3), inputs(4)
    place = lambda args, slot: tuple(
        m if x.ndim == 1 else x.at[slot].set(m[0])
        for x, m in zip(args, mine))
    y2, new2 = kernel(*place(first, 2), mask([0, 2, 5]))
    y6, new6 = kernel(*place(second, 6), mask([1, 3, 4, 6, 7]))
    assert np.array_equal(y2[2], y6[6])
    assert np.array_equal(new2[2], new6[6])


@pytest.mark.parametrize("alive", list(MASKS), ids=list(MASKS))
def test_the_visits_are_the_live_slots_in_order_then_the_last_again(alive):
    slots, n = ssm.slot_visits(mask(MASKS[alive]))
    live = MASKS[alive]
    assert int(n[0]) == len(live)
    assert slots.tolist() == live + [live[-1] if live else 0] * (B - len(live))


# ---------------------------------------------------------------------------
# The rule: a TPU and a live mask
# ---------------------------------------------------------------------------

@pytest.fixture
def the_kernels_path(monkeypatch):
    """The platform probe answering TPU for the recurrence, the kernel
    interpreted; what each call visits is recorded as it runs."""
    visited = []
    real = ssm.ssm_step_live

    def recording(*args, **kw):
        jax.debug.callback(lambda n: visited.append(int(n[0])),
                           ssm.slot_visits(args[7])[1], ordered=True)
        return real(*args, **kw, interpret=True)

    monkeypatch.setattr(ssm, "on_tpu", lambda: True)
    monkeypatch.setattr(ssm, "ssm_step_live", recording)
    return visited


def kernels(closed) -> list:
    """The names of the ``pallas_call``s in a jaxpr, nested ones too."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["name"])
            for value in eqn.params.values():
                for sub in (value if isinstance(value, tuple) else (value,)):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        walk(sub)
    walk(closed.jaxpr)
    return found


def nemotron_engine(telemetry=None):
    model, params, _ = build()
    return model, params, serve(model, params, capture=True,
                                telemetry=telemetry)


def requests(srv, seed, lengths=(6, 14, 9, 11), new=5):
    rng = np.random.default_rng(seed)
    rids = [srv.submit(rng.integers(0, 512, n).tolist(), new)
            for n in lengths]
    return rids, run_to_the_end(srv)


def test_the_decode_program_takes_the_kernel_and_the_plain_forward_not(
        the_kernels_path):
    model, params, srv = nemotron_engine()
    requests(srv, 5, lengths=(7, 12), new=3)
    assert the_kernels_path, "the decode program took XLA's step"
    real, text = srv._decode_jit, []

    def traced(*args, **kw):
        text.append(jax.make_jaxpr(real)(*args, **kw))
        return real(*args, **kw)

    srv._decode_jit = traced
    requests(srv, 6, lengths=(5,), new=2)
    srv.close()
    assert kernels(text[0]) == ["ssm_step_live"] * model.cfg.pattern.count(
        "M")
    # live=None: the plain forward, and a prompt
    ids = jnp.zeros((1, 8), jnp.int32)
    assert not kernels(jax.make_jaxpr(lambda p: model.apply(
        {"params": p}, {"input_ids": ids}))(params))
    assert not kernels(jax.make_jaxpr(lambda p: model.serve_prefill(
        p, ids, jnp.int32(5)))(params))


def test_the_engine_through_the_kernel_gives_the_xla_paths_tokens(
        monkeypatch):
    """Prefill and decode of the same requests, XLA's step against the
    kernel's: the same tokens, the decode logits to float32 rounding."""
    _, _, plain = nemotron_engine()
    want_rids, want = requests(plain, 7)
    plain.close()
    visited = []
    real = ssm.ssm_step_live
    monkeypatch.setattr(ssm, "on_tpu", lambda: True)
    monkeypatch.setattr(ssm, "ssm_step_live", lambda *a, **kw: (
        visited.append(1), real(*a, **kw, interpret=True))[1])
    _, _, fast = nemotron_engine()
    rids, got = requests(fast, 7)
    assert visited
    for a, b in zip(want_rids, rids):
        assert fast.results[b]["tokens"] == plain.results[a]["tokens"]
        np.testing.assert_allclose(np.stack(got[b]), np.stack(want[a]),
                                   atol=1e-5, rtol=0)
    fast.close()


def test_the_kernel_visits_the_slots_the_span_counts(the_kernels_path,
                                                     monkeypatch):
    monkeypatch.setattr(serving_engine, "profiler_session_live",
                        lambda: True)
    tel = RecordingTelemetry()
    model, _, srv = nemotron_engine(telemetry=tel)
    requests(srv, 8, lengths=(6, 14, 9), new=4)
    jax.effects_barrier()
    srv.close()
    spans = [s["state_slots_live"] for s in tel.spans
             if s["name"] == "decode_step"]
    layers = model.cfg.pattern.count("M")
    assert spans and the_kernels_path == [n for n in spans
                                          for _ in range(layers)]


def test_a_gpt_engines_decode_program_lowers_as_before(monkeypatch):
    """The tiny GPT's decode program as the engine lowers it, the platform
    probe of the recurrence answering TPU: no recurrence, no kernel."""
    monkeypatch.setattr(ssm, "on_tpu", lambda: True)
    model, _ = make_gpt("tiny", dropout_rate=0.0, max_seq_len=64,
                        dtype=jnp.float32)
    params = model.init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(1)},
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    srv = deepspeed_tpu.init_serving(
        model, params=params, dtype=jnp.float32,
        config={"serving": {"max_batch_size": 2, "kv_block_size": 4,
                            "kv_num_blocks": 64, "max_model_len": 48}})
    srv.submit([1, 2, 3, 4, 5], 4)
    while srv._decode_jit is None:
        srv.step()
    real, text = srv._decode_jit, []

    def lowered(*args, **kw):
        text.append(real.lower(*args, **kw).as_text())
        return real(*args, **kw)

    srv._decode_jit = lowered
    srv.step()
    srv.close()
    assert "ssm_step" not in text[0] and "tpu_custom_call" not in text[0]
