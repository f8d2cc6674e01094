"""``benchmarks/host_idle.py`` and the six ``serve.idle_*`` readers: the
device's idle seconds of a traced stretch put down to the serving engine's
host path (``admit`` + ``batch_inputs``, ``put``, ``dispatch``, ``fetch``,
``post_step``) a decode step at a time, on a hand-made stream, on a program
that has none of those spans, and on a cut of a traced
``gpt2m-serve-chat`` run on a v5e; and their six entries of
``BENCHMARK.json``."""

import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import host_idle, trace_reduce  # noqa: E402
from benchmarks import program_trace as pt  # noqa: E402
from benchmarks.harness import HERE, load_json, load_module  # noqa: E402
from benchmarks.program_trace import DeviceOp, ProgramTrace, Span  # noqa: E402

BENCH = load_json(ROOT, "BENCHMARK.json")
METRICS = {"serve.idle_prep_ms": "ms", "serve.idle_put_ms": "ms",
           "serve.idle_dispatch_ms": "ms", "serve.idle_fetch_ms": "ms",
           "serve.idle_post_ms": "ms", "serve.idle_unnamed_share": "%"}
PER_STEP = [m for m in METRICS if m.endswith("_ms")]
FIXTURE = os.path.join(HERE, "fixtures", "v5e_gpt2m_serve_host_path")


def read_all(trace, reduced):
    """The six readers on ``trace`` as ``run.py`` calls them."""
    real = pt.load
    try:
        pt.load = lambda path: trace
        run = SimpleNamespace(xplane=lambda: "capture.xplane.pb")
        return {name: load_module("layer_metrics", name).read(run, {},
                                                              reduced)
                for name in METRICS}
    finally:
        pt.load = real


def reduced_of(trace):
    """What ``trace_reduce.reduce`` makes of the same ops."""
    plain = trace_reduce.Trace(
        {d: [trace_reduce.Op(o.name, o.start, o.end, "fusion", "kLoop")
             for o in ops] for d, ops in trace.devices.items()},
        [trace_reduce.Op(trace_reduce.WINDOW, *trace.window)])
    return trace_reduce.reduce(plain)


def stream(keep=lambda span: True):
    """One device, a 20 s window, the engine's main thread. Step 0 (0-10)
    admits (0-0.5), puts a prompt (0.5-1), prefills it (1-4: dispatch
    1-1.5, the pack's put 1.5-1.8, its dispatch 1.8-2, the first token's
    fetch 2-4), builds the batch (4-5, two spans), puts it (5-5.5),
    decodes (5.5-9: dispatch 5.5-6, fetch 6-8.8) and keeps its books
    (9-9.6, two spans). A submit (10-10.5) between steps; step 1 (11-18)
    only decodes; no span 18-20. The device runs the prefill program
    1.2-1.6, the pack 1.9-3.5 and the decodes 6.2-8.6 and 13.5-16.5."""
    main = ("/host:CPU", "python#1")
    rows = [("serve_step", 0, 10, {"step": 0}), ("admit", 0, .5, {}),
            ("put", .5, 1, {"rid": 4}), ("prefill", 1, 4, {"rid": 4}),
            ("dispatch", 1, 1.5, {"program": "prefill"}),
            ("put", 1.5, 1.8, {"rid": 4}),
            ("dispatch", 1.8, 2, {"program": "pack"}), ("fetch", 2, 4, {}),
            ("batch_inputs", 4, 4.5, {}), ("batch_inputs", 4.5, 5, {}),
            ("put", 5, 5.5, {}), ("decode_step", 5.5, 9, {}),
            ("dispatch", 5.5, 6, {"program": "decode"}),
            ("fetch", 6, 8.8, {}), ("post_step", 9, 9.3, {}),
            ("post_step", 9.3, 9.6, {}), ("submit", 10, 10.5, {}),
            ("serve_step", 11, 18, {"step": 1}),
            ("batch_inputs", 11, 11.5, {}), ("batch_inputs", 11.5, 12, {}),
            ("put", 12, 12.5, {}), ("decode_step", 12.5, 17, {}),
            ("dispatch", 12.5, 13, {"program": "decode"}),
            ("fetch", 13, 16.9, {}), ("post_step", 17, 17.5, {}),
            ("post_step", 17.5, 18, {})]
    spans = [Span(name, float(a), float(b), stats, main)
             for name, a, b, stats in rows]
    spans = [s for s in spans if keep(s)]
    ops = [DeviceOp("fusion.1", 1.2, 1.6, "jit(f)/ds.prefill/dot"),
           DeviceOp("copy.2", 1.9, 3.5, "jit(pack_prefill)/ds.pack/x"),
           DeviceOp("fusion.3", 6.2, 8.6, "jit(f)/ds.decode/dot"),
           DeviceOp("fusion.4", 13.5, 16.5, "jit(f)/ds.decode/dot")]
    trace = ProgramTrace({0: ops}, spans, (0.0, 20.0))
    pt.link_spans(trace.spans)
    return trace


def test_idle_goes_to_the_innermost_span_of_the_host_path():
    trace = stream()
    idle = pt.idle_by_span(trace, trace.window)
    # busy 1.2-1.6, 1.9-3.5, 6.2-8.6, 13.5-16.5: 7.4 s of 20
    assert sum(idle.values()) == pytest.approx(20 - 7.4)
    # 1.6-1.8: the pack's put, inside the prefill, inside the step
    put_in_prefill = next(s for s in trace.spans if s.name == "put"
                          and trace.spans[s.parent].name == "prefill")
    assert trace.spans[trace.spans[put_in_prefill.parent].parent].name == \
        "serve_step"
    assert idle["put"] == pytest.approx(0.5 + 0.2 + 0.5 + 0.5)
    assert idle == pytest.approx({
        "admit": 0.5, "batch_inputs": 2.0, "put": 1.7,
        "dispatch": 0.2 + 0.1 + 0.5 + 0.5,
        "fetch": 0.5 + 0.2 + 0.2 + 0.5 + 0.4, "post_step": 0.6 + 1.0,
        "decode_step": 0.2 + 0.1, "serve_step": 0.4, "submit": 0.5,
        "unattributed": 0.5 + 2.0})


def test_the_five_parts_and_the_unnamed_add_up_to_the_idle_a_step():
    trace = stream()
    got = read_all(trace, reduced_of(trace))
    steps = 2
    assert got == pytest.approx({
        "serve.idle_prep_ms": 1e3 * 2.5 / steps,
        "serve.idle_put_ms": 1e3 * 1.7 / steps,
        "serve.idle_dispatch_ms": 1e3 * 1.3 / steps,
        "serve.idle_fetch_ms": 1e3 * 1.8 / steps,
        "serve.idle_post_ms": 1e3 * 1.6 / steps,
        "serve.idle_unnamed_share": 100 * 3.7 / 12.6})
    unnamed_ms = got["serve.idle_unnamed_share"] / 100 * 12.6e3 / steps
    assert sum(got[m] for m in PER_STEP) + unnamed_ms == \
        pytest.approx(12.6e3 / steps)


def test_a_program_without_the_spans_reads_nothing_and_an_empty_part_0():
    parent = stream(keep=lambda s: s.name not in host_idle.HOST_PATH)
    assert set(read_all(parent, reduced_of(parent)).values()) == {None}
    # no post_step: its idle is the step's own, and the part reads 0.0
    trace = stream(keep=lambda s: s.name != "post_step")
    got = read_all(trace, reduced_of(trace))
    assert got["serve.idle_post_ms"] == 0.0
    assert got["serve.idle_unnamed_share"] == \
        pytest.approx(100 * (3.7 + 1.6) / 12.6)
    # no device trace (a rehearsal) or no capture
    assert set(read_all(trace, None).values()) == {None}
    run = SimpleNamespace(xplane=lambda: None)
    assert all(load_module("layer_metrics", m).read(
        run, {}, reduced_of(trace)) is None for m in METRICS)


@pytest.mark.parametrize("name", list(METRICS))
def test_the_entry_of_each_metric_is_as_it_came(name):
    """Its ``workloads`` may grow at the end, as a cell joins."""
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": METRICS[name], "better": "lower",
                     "source": "device_trace", "layer": "serving",
                     "moves": "itl_ms_p95",
                     "workloads": entry["workloads"]}
    assert entry["workloads"][:2] == ["gpt2m-serve-chat",
                                      "nemotron3s-serve-chat"]


def test_the_recorded_cut_reads_what_it_read_and_adds_up():
    """150 ms of a traced ``gpt2m-serve-chat`` run on a v5e (10 decode
    steps): the six readers give the values recorded beside the cut, the
    host path's spans nest as the engine opens them, and the five parts
    and the unnamed part make up the idle seconds a decode step."""
    trace = pt.load_fixture(FIXTURE + ".json.gz")
    expected = load_json(HERE, "fixtures",
                         "v5e_gpt2m_serve_host_path.expected.json")
    reduced = reduced_of(trace)
    got = read_all(trace, reduced)
    assert got == pytest.approx({m: expected[m] for m in METRICS})
    steps = len(pt.decode_spans(trace))
    idle = sum(pt.idle_by_span(trace, trace.window).values())
    assert steps == 10 and idle == pytest.approx(
        trace.window[1] - reduced.busy[0])
    unnamed_ms = got["serve.idle_unnamed_share"] / 100 * idle * 1e3 / steps
    assert sum(got[m] for m in PER_STEP) + unnamed_ms == \
        pytest.approx(idle * 1e3 / steps, rel=1e-9)
    # what the chip waited on, in order: the step's puts, the token's
    # fetch, the rest under a millisecond a step
    assert got["serve.idle_put_ms"] > got["serve.idle_fetch_ms"] > 1.0 > \
        max(got["serve.idle_prep_ms"], got["serve.idle_dispatch_ms"],
            got["serve.idle_post_ms"])
    assert got["serve.idle_unnamed_share"] < 15.0
    for s in trace.spans:
        if s.name in ("dispatch", "fetch") and s.parent is not None:
            assert trace.spans[s.parent].name in ("decode_step", "prefill")
        if s.name in ("batch_inputs", "post_step") and s.parent is not None:
            assert trace.spans[s.parent].name == "serve_step"
