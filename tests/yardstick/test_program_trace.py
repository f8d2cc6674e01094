"""``benchmarks/program_trace.py`` and the readers that use it: the
wire-format reader against ``ProfileData`` on a capture recorded on a v5e,
the scope rules on the names JAX writes, parent, self time and idle by
innermost span on hand-made streams, the cut of PR 24's own chip run
(``fixtures/v5e_pr24_*``), and the rules every ``per_layer`` entry of
``BENCHMARK.json`` keeps, so that a later PR can append an entry, or add
its cell to an entry's ``workloads``, and break nothing here."""

import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import program_trace as pt, trace_reduce  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import HERE, load_json, load_module  # noqa: E402
from benchmarks.program_trace import DeviceOp, ProgramTrace, Span  # noqa: E402

BENCH = load_json(ROOT, "BENCHMARK.json")
RECORDED = os.path.join(ROOT, "profiles", "gpt2", "plugins", "profile",
                        "2026_07_30_10_49_21", "vm.xplane.pb")
# PR 24's readers: twelve then; ``kernel.flash_dq_share`` and
# ``kernel.flash_dkv_share`` went with their kernels (PR 32) and
# ``kernel.flash_bwd_share`` reads the one that took their place (PR 34).
NEW = ["train.forward_share", "train.backward_share",
       "train.optimizer_share", "kernel.flash_fwd_share",
       "kernel.flash_bwd_share",
       "serve.prefill_ms_p50", "serve.decode_ms_p50", "serve.host_ms_p50",
       "serve.prefill_device_share", "serve.kv_gather_share",
       "serve.kv_read_useful_share"]
CELLS = [w["name"] for w in BENCH["workloads"]]
END_TO_END = {m["name"]: m for m in BENCH["end_to_end"]}
TRAIN_FIXTURE = os.path.join(HERE, "fixtures",
                             "v5e_pr24_gpt2m_train_boundary.json.gz")
SERVE_FIXTURE = os.path.join(HERE, "fixtures",
                             "v5e_pr24_gpt2m_serve_steps.json.gz")


def reader(name):
    return load_module("layer_metrics", name).read


# ---------------------------------------------------------------------------
# BENCHMARK.json: the rules of a per-layer entry (how a cell joins:
# benchmarks/layer_metrics/README.txt)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("entry", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_a_per_layer_entry_keeps_the_rules(entry):
    """Whatever a later PR appends: a reader that loads, a name of its
    own, an end-to-end metric to move, and only cells that report it."""
    assert callable(reader(entry["name"]))
    assert [m["name"] for m in BENCH["per_layer"]].count(entry["name"]) == 1
    moved = END_TO_END[entry["moves"]]
    cells = entry.get("workloads", CELLS)
    assert cells and set(cells) <= set(moved.get("workloads", CELLS))
    assert len(cells) == len(set(cells)) and set(cells) <= set(CELLS)
    assert set(entry) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


@pytest.mark.parametrize("name", NEW)
def test_an_entry_of_pr24_is_still_there_as_it_was(name):
    """Unit, source, layer and the metric it moves; its ``workloads`` may
    grow."""
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    if name.startswith("train."):
        assert {"gpt2m-train-s1024", "bert-large-train-s128"} <= set(
            entry["workloads"])
        assert (entry["source"], entry["layer"]) == ("device_trace",
                                                     "trainer")
    elif name.startswith("kernel."):
        assert "gpt2m-train-s1024" in entry["workloads"]
        assert (entry["source"], entry["layer"]) == ("device_trace",
                                                     "kernels")
    else:
        assert "gpt2m-serve-chat" in entry["workloads"]
        assert entry["layer"] == "serving" and entry["moves"] == "itl_ms_p95"
        assert entry["source"] == {
            "serve.prefill_ms_p50": "program_span",
            "serve.decode_ms_p50": "program_span",
            "serve.host_ms_p50": "program_span",
            "serve.kv_read_useful_share": "program_counter"}.get(
                name, "device_trace")
    assert entry["unit"] == ("ms" if name.endswith("_ms_p50") else "%")


# ---------------------------------------------------------------------------
# The wire reader on the capture recorded on a v5e (jax 0.9.0)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return pt.load(RECORDED)


def test_the_wire_reader_agrees_with_profile_data(recorded):
    from jax.profiler import ProfileData
    (plane,) = [p for p in ProfileData.from_file(RECORDED).planes
                if p.name == "/device:TPU:0"]
    (line,) = [l for l in plane.lines if l.name == trace_reduce.OP_LINE]
    theirs = [(e.start_ns, e.duration_ns, e.name) for e in line.events]
    (raw,) = [p for p in pt.read_xplane(RECORDED, pt._wanted)
              if p.name == "/device:TPU:0"]
    ((_, _, ours),) = raw.lines            # only the op line was wanted
    assert len(ours) == len(theirs) == 9663
    for (start_ns, duration_ns, name), ev in zip(theirs, ours):
        assert ev.name == name
        # ProfileData rounds to whole nanoseconds
        assert abs(ev.start * 1e9 - start_ns) < 1.0
        assert abs((ev.end - ev.start) * 1e9 - duration_ns) < 1.0
    # the event's own stats are all ProfileData shows; the metadata's are
    # what this reader is for
    assert set(ours[100].stats) >= {"device_offset_ps", "device_duration_ps"}
    assert set(ours[100].meta) >= {"hlo_category", "program_id", "flops",
                                   "bytes_accessed"}
    assert any("tf_op" in ev.meta for ev in ours)


def test_forward_backward_and_unscoped_make_up_all_op_time(recorded):
    ops = recorded.devices[0]
    assert len(ops) == 9663                 # this capture holds no container
    seconds = lambda pred: sum(o.end - o.start for o in ops if pred(o))
    everything = seconds(lambda o: True)
    forward, backward = seconds(pt.is_forward), seconds(pt.is_backward)
    unscoped = seconds(lambda o: not o.scope)
    assert forward + backward + unscoped == pytest.approx(everything,
                                                          rel=1e-12)
    assert 1.7 < backward / forward < 2.1
    assert 100 * backward / everything == pytest.approx(63.6, abs=0.1)
    assert 100 * forward / everything == pytest.approx(33.4, abs=0.1)
    # before this PR a kernel had no name: its path names the layer only
    kernels = {o.kernel for o in ops if o.kernel}
    assert kernels == {f"h_{i}" for i in range(12)}
    assert {o.category for o in ops if o.kernel} == {"custom-call"}
    # a capture of a program without our spans: no span, no window, and a
    # reader of a scope it does not name says nothing
    assert recorded.spans == [] and recorded.window is None


def test_the_parse_is_memoised():
    assert pt.load(RECORDED) is pt.load(RECORDED)


# ---------------------------------------------------------------------------
# Scope rules, on names as JAX writes them
# ---------------------------------------------------------------------------

def op(scope, start=0.0, end=1.0, name="fusion.1", program=0):
    return DeviceOp(name, start, end, scope, program_id=program,
                    kernel=pt.kernel_of(scope))


@pytest.mark.parametrize("scope,parts", [
    ("jit(train_step)/ds.optimizer/mul:",
     ["train_step", "ds.optimizer", "mul:"]),
    ("jit(_decode_impl)/ds.decode/GPT/h_3/ds.kv_gather/gather",
     ["_decode_impl", "ds.decode", "GPT", "h_3", "ds.kv_gather", "gather"]),
    ("jit(f)/transpose(jvp(ds.kv_gather))/mul",
     ["f", "ds.kv_gather", "mul"]),
    ("jit(train_step)/while/body/closed_call/jvp(GPT)/h_0/flash_fwd/"
     "pallas_call", ["train_step", "while", "body", "closed_call", "GPT",
                     "h_0", "flash_fwd", "pallas_call"]),
    ("", [""]),
])
def test_a_scope_is_matched_within_the_wrappers(scope, parts):
    assert list(pt.scope_parts(scope)) == parts


def test_scopes_kernels_and_modules_of_an_op():
    inside_vjp = op("jit(f)/transpose(jvp(ds.kv_gather))/mul")
    assert pt.in_scope(inside_vjp, "ds.kv_gather")
    assert pt.is_backward(inside_vjp) and not pt.is_forward(inside_vjp)
    assert pt.is_forward(op("jit(f)/jvp(GPT)/h_0/c_attn/dot_general"))
    update = op("jit(train_step)/ds.optimizer/mul")
    assert pt.in_scope(update, "ds.optimizer", "ds.cast_params")
    assert not pt.in_scope(update, "ds.accumulate")
    assert not pt.is_forward(update) and not pt.is_backward(update)
    # a name that merely contains the scope's is not under it
    assert not pt.in_scope(op("jit(f)/ds.optimizer_state/mul"),
                           "ds.optimizer")
    fwd = op("jit(train_step)/while/body/jvp(GPT)/h_7/flash_fwd/pallas_call:")
    dkv = op("jit(s)/transpose(jvp(GPT))/h_7/flash_bwd_dkv/pallas_call")
    assert (fwd.kernel, dkv.kernel) == ("flash_fwd", "flash_bwd_dkv")
    assert pt.kernel_of("jit(f)/jvp(GPT)/h_7/add") == ""
    assert pt.module_of(fwd.scope) == "block"
    assert pt.module_of("jit(f)/ds.decode/GPT/h_3/c_attn/dot_general") == \
        "c_attn"
    assert pt.module_of("jit(f)/ds.decode/GPT/ln_f/mul") == "ln_f"
    assert pt.module_of("jit(f)/ds.decode/ds.sample/argmax") == "other"
    assert pt.ds_scope_of("jit(f)/ds.decode/GPT/h_3/ds.kv_gather/gather") \
        == "ds.kv_gather"
    assert pt.ds_scope_of("jit(f)/jvp(GPT)/h_3/add") == "-"


# ---------------------------------------------------------------------------
# Hand-made streams: parent, self time, idle by innermost span
# ---------------------------------------------------------------------------

def serve_stream():
    """One device, a 10 s window. Two engine steps on the main thread:
    step 0 (0-5) admits (0-0.5), prefills (0.5-2) and decodes (2.5-4.5);
    step 1 (5-9) only decodes (5.5-8.5). A submit (9-9.5) lies outside any
    step, and another thread's span overlaps step 0 without being its
    child. The device runs 1-2 (the prefill program 1-1.7, then the pack
    program 1.7-2, most of which is the compiler's own copy of the pool,
    which bears the parameter's name and no scope), 3-4.5 and 6-8.5."""
    main, other = ("/host:CPU", "python#1"), ("/host:CPU", "python#2")
    spans = [
        Span("serve_step", 0.0, 5.0, {"step": 0, "active": 0}, main),
        Span("admit", 0.0, 0.5, {"step": 0}, main),
        Span("prefill", 0.5, 2.0, {"step": 0, "rid": 4}, main),
        Span("decode_step", 2.5, 4.5, {"step": 0, "live_positions": 30,
                                       "read_positions": 400}, main),
        Span("serve_step", 5.0, 9.0, {"step": 1, "active": 1}, main),
        Span("decode_step", 5.5, 8.5, {"step": 1, "live_positions": 50,
                                       "read_positions": 400}, main),
        Span("submit", 9.0, 9.5, {"rid": 5}, main),
        Span("ckpt_write", 1.0, 3.0, {}, other),
    ]
    ops = [op("jit(_prefill_impl)/ds.prefill/GPT/h_0/c_attn/dot", 1.0, 1.7,
              program=11),
           op("pools[0][0]:", 1.7, 1.9, name="copy.7", program=12),
           op("jit(pack_prefill)/ds.pack/scatter", 1.9, 2.0, program=12),
           op("jit(_decode_impl)/ds.decode/GPT/h_0/ds.kv_gather/gather",
              3.0, 4.0, program=13),
           op("jit(_decode_impl)/ds.decode/GPT/h_0/ds.kv_write/scatter",
              4.0, 4.5, program=13),
           op("jit(_decode_impl)/ds.decode/GPT/h_0/ds.kv_gather/gather",
              6.0, 8.0, program=13),
           op("", 8.0, 8.3, name="copy.106", program=13),
           op("jit(_decode_impl)/ds.decode/ds.sample/argmax", 8.3, 8.5,
              program=13)]
    trace = ProgramTrace({0: ops}, spans, (0.0, 10.0))
    pt.link_spans(trace.spans)
    return trace


def reduced_of(trace):
    """What ``trace_reduce.reduce`` makes of the same ops."""
    plain = trace_reduce.Trace(
        {d: [trace_reduce.Op(o.name, o.start, o.end, "fusion", "kLoop")
             for o in ops] for d, ops in trace.devices.items()},
        [trace_reduce.Op(trace_reduce.WINDOW, *trace.window)])
    return trace_reduce.reduce(plain)


def test_parent_is_the_innermost_span_of_the_same_thread():
    trace = serve_stream()
    names = lambda idx: [trace.spans[i].name for i in idx]
    step0, step1 = [s for s in trace.spans if s.name == "serve_step"]
    assert names(step0.children) == ["admit", "prefill", "decode_step"]
    assert names(step1.children) == ["decode_step"]
    for s in trace.spans:
        want = {"serve_step": None, "submit": None, "ckpt_write": None}.get(
            s.name, "serve_step")
        got = None if s.parent is None else trace.spans[s.parent].name
        assert got == want, s.name
    # a span of the next step that starts where the last one ended is its
    # sibling, not its child
    assert trace.spans[trace.spans.index(step1)].parent is None


def test_self_time_is_the_duration_less_what_children_cover():
    trace = serve_stream()
    step0, step1 = [s for s in trace.spans if s.name == "serve_step"]
    assert pt.self_seconds(trace, step0) == pytest.approx(5 - .5 - 1.5 - 2)
    assert pt.self_seconds(trace, step1) == pytest.approx(1.0)
    # the host's part of a step keeps the admit: only the children that
    # wait for the device are taken off
    work = ("prefill",) + pt.DECODE_SPANS
    assert pt.self_seconds(trace, step0, less=work) == pytest.approx(1.5)
    leaf = next(s for s in trace.spans if s.name == "prefill")
    assert pt.self_seconds(trace, leaf) == leaf.duration
    # overlapping children are covered once
    spans = [Span("a", 0.0, 10.0, {}, ("h", "t")),
             Span("b", 1.0, 4.0, {}, ("h", "t")),
             Span("c", 5.0, 12.0, {}, ("h", "t"))]     # runs past its parent
    pt.link_spans(spans)
    assert pt.self_seconds(ProgramTrace(spans=spans), spans[0]) == \
        pytest.approx(10 - 3 - 5)


def test_idle_seconds_go_to_the_innermost_span_open_at_the_time():
    trace = serve_stream()
    idle = pt.idle_by_span(trace, trace.window)
    # busy 1-2, 3-4.5, 6-8.5: idle 0-1, 2-3, 4.5-6, 8.5-10
    assert idle == pytest.approx({
        "admit": 0.5,               # 0-0.5
        "prefill": 0.5,             # 0.5-1 (the host builds the dispatch)
        "ckpt_write": 0.5,          # 2-2.5: starts later than serve_step
        "decode_step": 0.5 + 0.5,   # 2.5-3 and 5.5-6
        "serve_step": 0.5 + 0.5 + 0.5,   # 4.5-5, 5-5.5, 8.5-9
        "submit": 0.5, "unattributed": 0.5})
    assert sum(idle.values()) == pytest.approx(10 - 5.0)


def test_the_serve_readers_on_the_hand_made_stream(tmp_path, capsys):
    trace = serve_stream()
    red = reduced_of(trace)
    assert red.busy == {0: 5.0}
    share = lambda pred: pt.share_of_busy(trace, red, pred)
    assert share(lambda o: pt.in_scope(o, "ds.kv_gather")) == \
        pytest.approx(100 * 3.0 / 5.0)
    # by scope the pack program's copy of the pool is missed ...
    assert share(lambda o: pt.in_scope(o, "ds.prefill", "ds.pack")) == \
        pytest.approx(100 * 0.8 / 5.0)
    # ... by program it is not: an executable that holds the scope is the
    # program, whatever the compiler added to it
    programs = pt.programs_under(trace, "ds.prefill", "ds.pack", "ds.decode")
    assert programs == {11: "ds.prefill", 12: "ds.pack", 13: "ds.decode"}
    # a scope the program does not name reports nothing, as at the parent
    assert share(lambda o: pt.in_scope(o, "ds.optimizer")) is None
    assert pt.share_of_busy(None, red, pt.is_forward) is None
    assert pt.share_of_busy(trace, None, pt.is_forward) is None
    by = pt.seconds_by(trace, red, lambda o: (pt.ds_scope_of(o.scope),
                                              pt.module_of(o.scope)))
    assert by[("ds.kv_gather", "block")] == pytest.approx(3.0)
    assert by[("ds.prefill", "c_attn")] == pytest.approx(0.7)
    assert by[("-", "other")] == pytest.approx(0.2 + 0.3)
    assert sum(by.values()) == pytest.approx(5.0)
    assert pt.stem("copy.106") == pt.stem("copy") == "copy"
    assert pt.stem("multiply_reduce_fusion.12") == "multiply_reduce_fusion"
    # through the reader files, as run.py calls them
    pt.load.cache_clear()
    path = str(tmp_path / "fake.xplane.pb")
    run = SimpleNamespace(xplane=lambda: path)
    real = pt.load
    try:
        pt.load = lambda p: trace
        assert reader("serve.prefill_ms_p50")(run, {}, red) == 1500.0
        assert reader("serve.decode_ms_p50")(run, {}, red) == 2500.0
        assert reader("serve.host_ms_p50")(run, {}, red) == \
            pytest.approx(1250.0)           # median of 1.5 s and 1.0 s
        assert reader("serve.kv_read_useful_share")(run, {}, red) == \
            pytest.approx(100 * 80 / 800)
        assert reader("serve.kv_gather_share")(run, {}, red) == \
            pytest.approx(60.0)
        assert reader("serve.prefill_device_share")(run, {}, red) == \
            pytest.approx(20.0)
        said = capsys.readouterr().out
        assert "device idle seconds by innermost program span" in said
        assert "device seconds in the ds.decode program: 4.0000" in said
        assert "  under ds.kv_gather: 3.0000 (block 3.0000)" in said
        assert "  under -: 0.3000 (copy, unnamed 0.3000)" in said
        assert "device seconds in the ds.pack program: 0.3000" in said
        assert "  under -: 0.2000 (copy of pools 0.2000)" in said
        # a span that only touches the window is not of the stretch
        trace.window = (0.2, 10.0)
        assert reader("serve.host_ms_p50")(run, {}, None) == \
            pytest.approx(1000.0)
        # no capture at all
        none = SimpleNamespace(xplane=lambda: None)
        assert all(reader(n)(none, {}, None) is None
                   for n in NEW if n.startswith("serve."))
    finally:
        pt.load = real


def test_the_train_readers_on_a_hand_made_step():
    ops = [op("jit(train_step)/ds.cast_params/convert", 0.0, 0.5),
           op("jit(train_step)/while/body/jvp(GPT)/h_0/c_attn/dot", 0.5, 2.0),
           op("jit(train_step)/while/body/jvp(GPT)/h_0/flash_fwd/pallas_call",
              2.0, 3.0),
           op("jit(train_step)/while/body/transpose(jvp(GPT))/h_0/"
              "flash_bwd/pallas_call", 3.0, 6.0),
           op("jit(train_step)/while/body/ds.accumulate/add", 6.0, 6.5),
           op("jit(train_step)/ds.optimizer/mul", 6.5, 7.5),
           op("", 7.5, 8.0, name="copy.3")]
    trace = ProgramTrace({0: ops}, [], (0.0, 8.0))
    red = reduced_of(trace)
    real, pt.load = pt.load, lambda p: trace
    try:
        run = SimpleNamespace(xplane=lambda: "x")
        got = {n: reader(n)(run, {}, red) for n in NEW[:5]}
    finally:
        pt.load = real
    assert got == pytest.approx({
        "train.forward_share": 100 * 2.5 / 8,
        "train.backward_share": 100 * 3.5 / 8,      # with the accumulate
        "train.optimizer_share": 100 * 1.5 / 8,     # with the cast
        "kernel.flash_fwd_share": 100 * 1.0 / 8,
        "kernel.flash_bwd_share": 100 * 3.0 / 8})
    # the three parts and what no scope names make up the step
    assert sum(got[n] for n in NEW[:3]) == pytest.approx(100 * 7.5 / 8)


def test_a_cut_round_trips_through_json():
    trace = serve_stream()
    doc = json.loads(json.dumps(pt.to_json(trace, (2.0, 9.0))))
    back = pt.from_json(doc)
    assert back.window == (0.0, 7.0)
    assert len(back.devices[0]) == 5           # the ops of 1-2 are cut off
    assert [s.name for s in back.spans] == [
        "serve_step", "decode_step", "serve_step", "decode_step",
        "ckpt_write"]                          # what touches the cut
    kept = next(s for s in back.spans if s.name == "decode_step")
    assert kept.stats["read_positions"] == 400
    assert back.spans[kept.parent].name == "serve_step"
    assert back.devices[0][0].scope.endswith("ds.kv_gather/gather")
    assert len(doc["scopes"]) == 4             # each scope is stored once
    assert {o.program_id for o in back.devices[0]} == {13}


# ---------------------------------------------------------------------------
# The cut of this PR's own chip runs
# ---------------------------------------------------------------------------

def test_the_recorded_train_boundary_holds_scopes_kernels_and_spans():
    """``gpt2m-train-s1024`` on a v5e round the boundary between two
    optimizer steps (cut by ``program_trace.py`` from a ``--trace 1`` run
    of this PR): the end of a backward pass, the accumulate, the update,
    the cast and the start of the next forward pass."""
    trace = pt.load_fixture(TRAIN_FIXTURE)
    ops = trace.devices[0]
    scopes = {pt.ds_scope_of(o.scope) for o in ops}
    assert {"ds.optimizer", "ds.accumulate", "ds.cast_params"} <= scopes
    assert {o.kernel for o in ops if o.kernel} == {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    # the name comes with pallas_call(name=...) alone: no scope of ours
    # sits between the layer and the kernel
    fwd = next(o for o in ops if o.kernel == "flash_fwd")
    assert pt.scope_parts(fwd.scope)[-2] == "flash_fwd"
    assert pt.LAYER_RE.match(pt.scope_parts(fwd.scope)[-3])
    assert pt.is_forward(fwd)
    assert all(pt.is_backward(o) for o in ops
               if o.kernel.startswith("flash_bwd"))
    # every op is forward, backward, under one of our scopes, or unnamed
    named = lambda o: (pt.is_forward(o) or pt.is_backward(o)
                       or pt.ds_scope_of(o.scope) != "-")
    rest = {o.scope for o in ops if not named(o)}
    total = sum(o.end - o.start for o in ops)
    assert sum(o.end - o.start for o in ops if not named(o)) < 0.1 * total, \
        rest
    # host spans: the host runs two steps ahead of the device, so while
    # the device ends step 47 the host dispatches step 49
    (step,) = [s for s in trace.spans if s.name == "train_batch"]
    assert step.stats == {"step": 49}
    assert [(trace.spans[c].name, trace.spans[c].stats) for c in
            step.children] == [("dataloader", {"step": 49}),
                               ("train_step", {"step": 49}),
                               ("step_hooks", {"step": 49})]
    assert pt.self_seconds(trace, step) < 0.2 * step.duration


def test_the_recorded_serve_steps_hold_scopes_spans_and_counts():
    """``gpt2m-serve-chat`` on a v5e: a few engine steps, one of which
    admits a request."""
    trace = pt.load_fixture(SERVE_FIXTURE)
    ops = trace.devices[0]
    scopes = {pt.ds_scope_of(o.scope) for o in ops}
    assert {"ds.kv_gather", "ds.kv_write", "ds.decode"} <= scopes
    names = {s.name for s in trace.spans}
    assert {"serve_step", "decode_step", "prefill", "admit"} <= names
    for s in trace.spans:
        if s.name == "decode_step":
            assert s.stats["read_positions"] == 64 * 1024
            assert 0 < s.stats["live_positions"] < s.stats["read_positions"]
            assert 0 < s.stats["active"] <= 64
        if s.name == "prefill":
            assert s.stats["bucket"] >= s.stats["prompt_len"]
        if s.parent is not None:
            assert trace.spans[s.parent].name == "serve_step"
            assert trace.spans[s.parent].stats["step"] == s.stats["step"]
    # What this PR found (PERF.md, section 5): the gather is a seventh of
    # a decode step. More goes to the compiler's own copies of the WHOLE
    # pool, into another layout when a program starts (they bear the
    # parameter's name) and back when it ends (no name at all), in the
    # decode program and, 48 arrays each way, in the pack program, which
    # is why a step that admits a request is 70 ms longer.
    programs = pt.programs_under(trace, "ds.decode", "ds.pack", "ds.prefill")
    seconds = lambda pred: sum(o.end - o.start for o in ops if pred(o))
    of = lambda name: [p for p, n in programs.items() if n == name]
    (decode,), (pack,) = of("ds.decode"), of("ds.pack")
    total = seconds(lambda o: True)
    gather = seconds(lambda o: pt.in_scope(o, "ds.kv_gather"))
    pool_copies = lambda program: seconds(
        lambda o: o.program_id == program and pt.stem(o.name) == "copy"
        and pt.ds_scope_of(o.scope) == "-")
    assert 0.10 * total < gather < 0.20 * total
    assert pool_copies(decode) > 2 * gather
    assert pool_copies(pack) > 0.95 * seconds(
        lambda o: o.program_id == pack) > 0.060     # seconds, one admission
    copies_in = [o for o in ops if o.program_id == pack
                 and o.scope.startswith("pools[")]
    assert len(copies_in) == 48                     # K and V of 24 layers


# ---------------------------------------------------------------------------
# A traced rehearsal of the serving cell
# ---------------------------------------------------------------------------

def test_a_traced_rehearsal_counts_the_kv_read_and_nulls_the_spans(capsys):
    rc = bench_run.main(["--workload", "gpt2m-serve-chat", "--seed", "3",
                         "--seconds", "2", "--trace", "1", "--rehearsal"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True
    got = result["metrics"]
    assert 0 < got["serve.kv_read_useful_share"]["value"] <= 100
    sources = {m["name"]: m["source"] for m in BENCH["per_layer"]}
    spans = [n for n in got if sources[n] == "program_span"]
    assert sorted(spans) == ["serve.decode_ms_p50", "serve.host_ms_p50",
                             "serve.prefill_ms_p50"]
    assert all(got[n]["value"] is None for n in spans)
    # no device plane on a CPU: the device readers found nothing to read
    assert not [n for n in got if sources[n] == "device_trace"]
