"""The program's own spans, scopes and kernel names (ISSUE 24).

One span primitive (``StepTracer.span``) that speaks to ``jax.profiler``:
with no session it records nothing and syncs nothing; inside a capture the
``ds.*`` spans of the trainer and the server nest, carry their identifiers
and counts as stats, and stay few. The device side is checked on compiled
HLO text, whose ``op_name`` metadata carries every ``jax.named_scope``.
"""

import ast
import glob
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.config.config import ServingConfig
from deepspeed_tpu.models import make_gpt
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.serving import ServeEngine
from deepspeed_tpu.telemetry import StepTracer
from deepspeed_tpu.telemetry.tracer import (DEVICE_SCOPES, PREFIX,
                                            device_scope)

from simple_model import mlp_loss_fn, mlp_params, random_batch, random_batches

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import program_trace as pt  # noqa: E402
from benchmarks.harness import load_module  # noqa: E402

MAX_PREFILLS = 2


class Capture:
    """A ``jax.profiler`` session round a block, with the benchmark's
    ``bench.window`` annotation over all of it; ``.trace`` afterwards."""

    def __init__(self, directory):
        self.dir = str(directory)
        self.trace = None

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.window = jax.profiler.TraceAnnotation("bench.window")
        self.window.__enter__()
        return self

    def __exit__(self, *exc):
        self.window.__exit__(*exc)
        jax.profiler.stop_trace()
        self.path = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        self.trace = pt.load(self.path)
        return False


def named(trace, name):
    return [s for s in trace.spans if s.name == name]


def parent_name(trace, span):
    return None if span.parent is None else trace.spans[span.parent].name


def train_engine(**extra):
    config = {"train_micro_batch_size_per_gpu": 2,
              "gradient_accumulation_steps": 2,
              "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
              "zero_optimization": {"stage": 0}, **extra}
    engine, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=mlp_loss_fn, params=mlp_params(), config=config,
        mesh=build_mesh(data=8))
    return engine


@pytest.fixture(scope="module")
def gpt_setup():
    model, cfg = make_gpt("tiny", dropout_rate=0.0, max_seq_len=64,
                          dtype=jnp.float32)
    params = model.init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(1)},
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    return model, cfg, params


def serve_engine(gpt_setup, **overrides):
    model, _, params = gpt_setup
    scfg = ServingConfig(**{
        "max_batch_size": 4, "kv_block_size": 4, "kv_num_blocks": 64,
        "max_model_len": 48, "max_prefills_per_step": MAX_PREFILLS,
        **overrides})
    eng = deepspeed_tpu.init_inference(model, params=params,
                                       dtype=jnp.float32)
    return ServeEngine(eng, config=scfg)


# ---------------------------------------------------------------------------
# The primitive
# ---------------------------------------------------------------------------

def test_with_telemetry_off_a_span_records_nothing_and_syncs_nothing(
        monkeypatch):
    from deepspeed_tpu.utils import timer as timer_mod
    calls = []
    monkeypatch.setattr(timer_mod, "_device_synchronize",
                        lambda: calls.append(1))
    tracer = StepTracer(path=None, sync_spans=True)
    with tracer.span("serve_step", step=3, active=2) as sp:
        sp.set_metadata(rid=7)
    assert sp.duration == 0.0
    assert tracer.events == [] and not calls
    # the handle IS the profiler's annotation: nothing of ours in between
    assert isinstance(sp, jax.profiler.TraceAnnotation)


def test_an_enabled_tracer_records_the_chrome_event_and_the_annotation(
        tmp_path):
    tracer = StepTracer(path=str(tmp_path / "t.json"), sync_spans=False)
    with Capture(tmp_path / "prof") as cap:
        with tracer.span("submit", prompt_len=5) as sp:
            sp.set_metadata(rid=9)
    (event,) = [e for e in tracer.events if e.get("ph") == "X"]
    assert event["name"] == "submit"
    assert event["args"] == {"prompt_len": 5, "rid": 9}
    (span,) = named(cap.trace, "submit")
    assert span.stats == {"prompt_len": 5, "rid": 9}
    assert sp.duration > 0.0


def test_a_device_scope_is_one_of_the_table():
    with pytest.raises(KeyError, match="known"):
        device_scope("optimiser")
    assert all(" " not in name for name in DEVICE_SCOPES)

    @device_scope("optimizer")
    def f(x):
        return x * 2.0

    text = jax.jit(lambda x: f(x) + f(x + 1.0)).lower(
        jnp.ones(4)).compile().as_text()
    assert text.count(PREFIX + "optimizer/mul") >= 1


def test_the_annotation_lives_in_one_file_of_the_package():
    hits = []
    for path in glob.glob(os.path.join(REPO, "deepspeed_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            if "TraceAnnotation" in f.read():
                hits.append(os.path.relpath(path, REPO))
    assert hits == ["deepspeed_tpu/telemetry/tracer.py"]


# ---------------------------------------------------------------------------
# The trainer
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def train_capture(tmp_path_factory):
    engine = train_engine()
    rng = np.random.default_rng(0)
    batches = random_batches(rng, gas=2, batch_size=16)
    engine.train_batch(batches)                 # compile outside the capture
    first = engine.global_steps
    with Capture(tmp_path_factory.mktemp("train")) as cap:
        for _ in range(3):
            engine.train_batch(batches)
    return cap.trace, first


def test_a_train_batch_opens_its_four_spans_nested_and_numbered(
        train_capture):
    trace, first = train_capture
    outer = named(trace, "train_batch")
    assert [s.stats["step"] for s in outer] == [first, first + 1, first + 2]
    assert all(s.parent is None for s in outer)
    for name in ("dataloader", "train_step", "step_hooks"):
        inner = named(trace, name)
        assert len(inner) == 3, name
        assert {parent_name(trace, s) for s in inner} == {"train_batch"}
        # a step's spans share its identifier
        assert [s.stats["step"] for s in inner] == \
            [s.stats["step"] for s in outer]
    for s in outer:
        order = [trace.spans[c].name for c in s.children]
        assert order == ["dataloader", "train_step", "step_hooks"]
        assert 0.0 <= pt.self_seconds(trace, s) <= s.duration
    assert {s.name for s in trace.spans} == {
        "train_batch", "dataloader", "train_step", "step_hooks"}


def test_the_train_step_names_its_optimizer_and_its_accumulate():
    engine = train_engine(bf16={"enabled": True})   # so there is a cast
    batches = engine.put_batch(
        random_batches(np.random.default_rng(0), gas=2, batch_size=16),
        leading_gas_dim=True)
    text = engine._train_step.lower(
        engine.state, batches, jnp.float32(1e-2)).compile().as_text()
    for scope in ("optimizer", "accumulate", "cast_params"):
        assert f"/{PREFIX}{scope}/" in text, scope
    # forward and backward need no scope of ours: JAX names them
    assert "transpose(jvp(" in text and "/jvp(" in text


@pytest.mark.parametrize("extra,scopes", [
    ({"zero_optimization": {"stage": 2, "offload_optimizer":
                            {"device": "cpu"}}},
     ("accumulate",)),
    ({"bf16": {"enabled": True},
      "optimizer": {"type": "OneBitAdam",
                    "params": {"lr": 1e-2, "freeze_step": 2}}},
     ("accumulate", "grad_sync", "optimizer", "cast_params")),
], ids=["offload", "onebit"])
def test_the_other_step_builders_use_the_same_names(extra, scopes):
    engine = train_engine(**extra)
    batches = engine.put_batch(
        random_batches(np.random.default_rng(0), gas=2, batch_size=16),
        leading_gas_dim=True)
    if engine._train_step is None:
        fn, args = engine._offload_micro_scan, (
            engine._compute_params, engine.state.rng, batches,
            jnp.float32(1.0))
    else:
        fn, args = engine._train_step, (engine.state, batches,
                                        jnp.float32(1e-2))
    text = fn.lower(*args).as_text(debug_info=True)
    for scope in scopes:
        assert PREFIX + scope in text, scope


def test_the_hierarchical_builder_names_its_grad_sync(eight_devices):
    engine, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=mlp_loss_fn, params=mlp_params(),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "zero_optimization": {"stage": 1},
                "bf16": {"enabled": True},
                "comm": {"hierarchical": "on", "dcn_quant_bits": 8}},
        mesh=build_mesh(slices=2))
    batches = engine.put_batch(
        random_batches(np.random.default_rng(0), gas=2, batch_size=16),
        leading_gas_dim=True)
    text = engine._train_step.lower(
        engine.state, batches, jnp.float32(1e-2)).as_text(debug_info=True)
    for scope in ("grad_sync", "accumulate", "optimizer", "cast_params"):
        assert PREFIX + scope in text, scope


def test_the_gradient_norm_of_a_fused_step_is_kept():
    engine = train_engine()
    rng = np.random.default_rng(0)
    batches = random_batches(rng, gas=2, batch_size=16)
    # what the step should report: the norm of the mean gradient over the
    # micro-batches, at the weights the step started from
    params = jax.tree_util.tree_map(jnp.asarray, mlp_params())
    grads = [jax.grad(mlp_loss_fn)(
        params, {k: v[i] for k, v in batches.items()}, None)
        for i in range(2)]
    mean = jax.tree_util.tree_map(lambda a, b: (a + b) / 2, *grads)
    want = float(jnp.sqrt(sum(jnp.sum(g ** 2)
                              for g in jax.tree_util.tree_leaves(mean))))
    engine.train_batch(batches)
    assert isinstance(engine._fused_grad_norm, jax.Array)   # no fetch yet
    got = engine.get_global_grad_norm()
    assert got == pytest.approx(want, rel=1e-4) and got > 0.0
    # the forward()/backward() path reads the accumulators, as before
    loss = engine.forward(random_batch(rng, batch_size=16))
    engine.backward(loss)
    assert engine._fused_grad_norm is None
    assert engine.get_global_grad_norm() > 0.0


# ---------------------------------------------------------------------------
# The server
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_capture(gpt_setup, tmp_path_factory):
    """A whole tiny run inside one capture: five requests of mixed
    lengths, two arriving late, until the engine drains."""
    srv = serve_engine(gpt_setup)
    rng = np.random.default_rng(0)
    srv.submit(rng.integers(0, 100, 5).tolist(), 2)     # compile outside
    srv.run_until_complete()
    before = dict(srv.stats)
    rids, reports = [], []
    with Capture(tmp_path_factory.mktemp("serve")) as cap:
        for n, new in ((5, 6), (9, 4), (3, 8)):
            rids.append(srv.submit(rng.integers(0, 100, n).tolist(), new))
        for i in range(100):
            if i == 2:
                rids.append(srv.submit(rng.integers(0, 100, 7).tolist(), 5))
                rids.append(srv.submit(rng.integers(0, 100, 4).tolist(), 3))
            if srv.idle():
                break
            reports.append(srv.step())
    assert srv.idle()
    return cap, srv, before, rids, reports


def test_serve_spans_nest_and_carry_their_identifiers(serve_capture):
    cap, srv, _, rids, reports = serve_capture
    trace = cap.trace
    steps = named(trace, "serve_step")
    assert [s.stats["step"] for s in steps] == [r["step"] for r in reports]
    assert all(s.parent is None for s in steps)
    for s in steps:
        assert s.stats["blocks_total"] == srv.pool.capacity
        assert 0 <= s.stats["blocks_used"] <= s.stats["blocks_total"]
    # the first step found three requests queued and none running
    assert (steps[0].stats["queued"], steps[0].stats["active"]) == (3, 0)
    prefills = named(trace, "prefill")
    assert sorted(s.stats["rid"] for s in prefills) == sorted(rids)
    for s in prefills:
        assert parent_name(trace, s) == "serve_step"
        assert s.stats["step"] == trace.spans[s.parent].stats["step"]
        assert s.stats["bucket"] >= s.stats["prompt_len"] > 0
        assert s.stats["queue_wait_ms"] >= 0.0
    decodes = named(trace, "decode_step")
    assert len(decodes) == sum(1 for r in reports if r["active"])
    for s in decodes:
        assert parent_name(trace, s) == "serve_step"
        assert s.stats["step"] == trace.spans[s.parent].stats["step"]
        assert 0 < s.stats["active"] <= 4
        assert 0 < s.stats["live_positions"] <= s.stats["read_positions"]
        # what the program reads follows what is live: whole chunks of
        # the list of live blocks, 4 positions a block; and of a live
        # block at most its last 3 are not live
        assert s.stats["read_positions"] == (
            s.stats["chunks"] * srv.live_chunk_positions) > 0
        assert (s.stats["live_positions"]
                > 4 * (s.stats["live_blocks"] - s.stats["active"]))
        assert 4 * s.stats["live_blocks"] <= s.stats["read_positions"]
    assert {parent_name(trace, s) for s in named(trace, "admit")} == \
        {"serve_step"}
    submits = named(trace, "submit")
    assert sorted(s.stats["rid"] for s in submits) == sorted(rids)
    assert all(s.parent is None and s.stats["prompt_len"] > 0
               for s in submits)


def test_a_step_opens_few_spans_whatever_the_number_of_active_rows(
        serve_capture):
    trace = serve_capture[0].trace
    steps = named(trace, "serve_step")
    seen = set()
    for s in steps:
        opened = 1 + len(s.children)
        assert all(not trace.spans[c].children for c in s.children)
        assert opened <= 3 + 2 * MAX_PREFILLS, (s.stats, opened)
        seen.add(s.stats["active"])
    assert len(seen) > 1            # steps of several occupancies were seen


def test_the_useful_share_of_the_kv_read_is_the_ratio_of_the_totals(
        serve_capture):
    """The capture spans the whole run, so the reader's ratio over the
    decode spans is the ratio of the engine's own running totals."""
    from types import SimpleNamespace
    cap, srv, before, _, _ = serve_capture
    live = srv.stats["live_positions"] - before["live_positions"]
    read = srv.stats["read_positions"] - before["read_positions"]
    assert 0 < live < read
    run = SimpleNamespace(xplane=lambda: cap.path)
    got = load_module("layer_metrics", "serve.kv_read_useful_share").read(
        run, {}, None)
    assert got == 100.0 * live / read
    # and what was read is the chunks the decode programs walked
    assert srv.stats["chunks"] * srv.live_chunk_positions == \
        srv.stats["read_positions"]
    # on a CPU there is no device plane: the device readers say nothing,
    # the span readers read the host plane
    for name in ("serve.kv_gather_share", "serve.prefill_device_share"):
        assert load_module("layer_metrics", name).read(run, {}, None) is None
    for name in ("serve.prefill_ms_p50", "serve.decode_ms_p50",
                 "serve.host_ms_p50"):
        assert load_module("layer_metrics", name).read(run, {}, None) > 0.0


def test_the_serving_programs_name_their_parts(gpt_setup):
    srv = serve_engine(gpt_setup)
    srv.submit([1, 2, 3, 4, 5], 3)
    srv.run_until_complete()
    decode = srv._decode_jit
    nb, mb = srv.scfg.max_batch_size, srv.max_blocks
    text = decode.lower(
        srv.engine.params, srv._pools, jnp.zeros((nb, mb), jnp.int32),
        jnp.zeros((nb,), jnp.int32), jnp.zeros((nb,), jnp.int32),
        jax.random.PRNGKey(0),
        jnp.zeros((srv._live_chunks, srv.LIVE_CHUNK_RUNS,
                   srv.LIVE_RUN_BLOCKS + 2), jnp.int32),
        jnp.int32(1)).compile().as_text()
    for scope in ("decode", "kv_gather", "kv_write", "sample"):
        assert PREFIX + scope in text, scope
    assert f"{PREFIX}decode/" in text and f"/{PREFIX}kv_gather/" in text
    (prefill,) = srv._prefill_jit.values()
    text = prefill.lower(
        srv.engine.params, jnp.zeros((1, 8), jnp.int32),
        jnp.asarray(5, jnp.int32), jax.random.PRNGKey(0)).as_text(
            debug_info=True)
    assert PREFIX + "prefill" in text and PREFIX + "sample" in text
    text = srv._pack_jit.lower(
        srv._pools, jnp.zeros((2,), jnp.int32),
        *(jnp.zeros((srv.model_cfg.num_layers, 8, srv.model_cfg.num_heads,
                     srv.model_cfg.head_dim), jnp.float32),) * 2
    ).as_text(debug_info=True)
    assert PREFIX + "pack" in text


# ---------------------------------------------------------------------------
# Kernel names
# ---------------------------------------------------------------------------

KERNELS = {
    "ops/transformer/flash_attention.py": [
        "flash_fwd", "flash_bwd"],
    "ops/transformer/paged_attention.py": ["paged_attention"],
    "ops/sparse_attention/sparse_attention.py": [
        "sparse_attn_fwd", "sparse_attn_bwd_dq", "sparse_attn_bwd_dkv"],
    "ops/adam/fused_update.py": ["fused_adam"],
}


def pallas_calls(path):
    """``name=`` of every ``pallas_call(...)`` in a file, in order
    (``None`` where it has none)."""
    with open(path) as f:
        tree = ast.parse(f.read())
    out = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "pallas_call"):
            name = next((k.value.value for k in node.keywords
                         if k.arg == "name"
                         and isinstance(k.value, ast.Constant)), None)
            out.append((node.lineno, name))
    return [name for _, name in sorted(out)]


@pytest.mark.parametrize("site", [
    (path, i) for path, names in KERNELS.items() for i in range(len(names))],
    ids=[name for names in KERNELS.values() for name in names])
def test_every_pallas_call_has_its_name(site):
    path, i = site
    assert pallas_calls(os.path.join(REPO, "deepspeed_tpu", path))[i] == \
        KERNELS[path][i]


def test_the_table_of_kernels_misses_no_call():
    found = {}
    for path in glob.glob(os.path.join(REPO, "deepspeed_tpu", "**", "*.py"),
                          recursive=True):
        calls = pallas_calls(path)
        if calls:
            found[os.path.relpath(path, os.path.join(
                REPO, "deepspeed_tpu"))] = calls
    assert found == KERNELS


# ---------------------------------------------------------------------------
# tools/trace_report.py: the table adds up though spans nest
# ---------------------------------------------------------------------------

def test_the_trace_report_adds_up_now_that_spans_enclose_others():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "trace_report_under_test",
        os.path.join(REPO, "tools", "trace_report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    ev = lambda name, ts, dur, tid=1: {"name": name, "ph": "X", "pid": 1,
                                       "tid": tid, "ts": ts, "dur": dur}
    events = []
    for i in range(2):                      # two fused steps of 100 us
        t = 1000.0 * i
        events += [ev("train_batch", t, 100.0), ev("dataloader", t + 1, 9.0),
                   ev("train_step", t + 10, 60.0),
                   ev("step_hooks", t + 70, 30.0),
                   # inside step_hooks, and on the writer thread
                   ev("ckpt_snapshot", t + 80, 10.0),
                   ev("ckpt_write", t + 85, 200.0, tid=2)]
    summary = report.summarize(events)
    by = {r["name"]: r for r in summary["spans"]}
    assert by["train_batch"]["total_ms"] == pytest.approx(0.2)
    assert by["train_batch"]["self_ms"] == pytest.approx(0.002)
    assert by["step_hooks"]["self_ms"] == pytest.approx(0.04)
    assert by["ckpt_write"]["self_ms"] == pytest.approx(0.4)  # own thread
    assert sum(r["share"] for r in summary["spans"]) == pytest.approx(1.0)
    # self times add up to the time the spans cover: 2 x 100 + 2 x 200 us
    assert sum(r["self_ms"] for r in summary["spans"]) == pytest.approx(0.6)
    assert "self ms" in report.render(summary)
