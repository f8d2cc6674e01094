"""The ``setup.*`` readers (``benchmarks/layer_metrics/setup/``) and the
log of JAX's compile events they read (``harness.Compiles``,
``benchmarks/setup_log.py``): unions of nested spans on a hand-made log,
the cut at the window's start, the cases with nothing to read, the real
nesting of an inner ``jit`` inside its caller through ``jax.monitoring``
on the CPU, the set-up table, the entries in ``BENCHMARK.json``, and a
traced rehearsal of a training and a serving cell."""

import json
import os
import sys
import time
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run, setup_log  # noqa: E402
from benchmarks.harness import (CompileEvent, Compiles,  # noqa: E402
                                load_json, load_module, metrics_of)

BENCH = load_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
SETUP = ["setup.programs", "setup.cache_hit_share", "setup.trace_lower_s",
         "setup.compile_load_s", "setup.engine_s"]
HOST_CLOCK = ["setup.trace_lower_s", "setup.compile_load_s",
              "setup.engine_s"]
PHASES = [("imports and device start-up", 1.0), ("seeded weights", 0.2),
          ("engine construction", 0.25), ("warm-up step 1", 5.55)]


def reader(name):
    return load_module("layer_metrics", name).read


def hand_made_run(events, cache=(), window_start=10.0, phases=PHASES):
    """Set-up from 0 to ``window_start`` on the log's clock."""
    log = SimpleNamespace(events=[CompileEvent(*e) for e in events],
                          cache_events=list(cache),
                          count=sum(e[0] == "backend_compile"
                                    for e in events))
    return SimpleNamespace(compiles=log, t0=0.0, window_start=window_start,
                           phases=list(phases))


# a program traced with an inner jit inside it, lowered, compiled (a miss);
# then a second program, loaded from the cache (a hit), whose module JAX
# names <unknown> (a functools.partial)
LOG = [("trace", "inner", 2.0, 3.0), ("trace", "sin", 2.2, 2.4),
       ("trace", "outer", 1.5, 4.0), ("lower", "jit(outer)", 4.0, 4.5),
       ("backend_compile", "jit(outer)", 4.5, 6.5),
       ("trace", "_decode_impl", 7.0, 7.5),
       ("lower", "<unknown>", 7.5, 8.0),
       ("backend_compile", "<unknown>", 8.0, 8.25)]
CACHE = [("miss", 6.4), ("hit", 8.2)]


def read_all(run):
    return {name: reader(name)(run, {}, None) for name in SETUP}


def test_nested_trace_spans_read_as_their_union():
    got = read_all(hand_made_run(LOG, CACHE))
    # 1.5-4.0 and 4.0-4.5 and 7.0-8.0: the inner traces add nothing
    assert got["setup.trace_lower_s"] == pytest.approx(3.0 + 1.0)
    summed = sum(e[3] - e[2] for e in LOG if e[0] in ("trace", "lower"))
    assert summed == pytest.approx(5.2)
    assert got["setup.compile_load_s"] == pytest.approx(2.0 + 0.25)
    assert got["setup.programs"] == 2
    assert got["setup.cache_hit_share"] == pytest.approx(50.0)
    assert got["setup.engine_s"] == 0.25


@pytest.mark.parametrize("name, before, after", [
    ("setup.programs", 1, 2), ("setup.trace_lower_s", 3.0, 4.0),
    ("setup.compile_load_s", 2.0, 2.25),
    ("setup.cache_hit_share", 0.0, 50.0)])
def test_an_event_that_ends_after_the_window_opens_is_not_read(
        name, before, after):
    # the second program's trace ends at 7.5, its compile at 8.25, and
    # its hit comes at 8.2
    assert reader(name)(hand_made_run(LOG, CACHE, window_start=7.2), {},
                        None) == pytest.approx(before)
    assert reader(name)(hand_made_run(LOG, CACHE, window_start=8.3), {},
                        None) == pytest.approx(after)


def test_the_cache_share_is_none_where_no_request_used_the_cache():
    assert reader("setup.cache_hit_share")(hand_made_run(LOG), {},
                                           None) is None
    # hits and misses after the window are no requests of set-up
    run = hand_made_run(LOG, [("hit", 11.0), ("miss", 12.0)])
    assert reader("setup.cache_hit_share")(run, {}, None) is None
    run = hand_made_run(LOG, [("hit", 1.0)] * 3 + [("miss", 2.0)])
    assert reader("setup.cache_hit_share")(run, {}, None) == 75.0


def test_engine_s_is_none_without_its_phase():
    run = hand_made_run(LOG, phases=[p for p in PHASES
                                     if p[0] != "engine construction"])
    assert reader("setup.engine_s")(run, {}, None) is None
    assert reader("setup.engine_s")(SimpleNamespace(), {}, None) is None


@pytest.mark.parametrize("name", SETUP[:4])
def test_a_run_without_the_log_reads_nothing(name):
    """A ``Run`` that only replays a capture (``moe_trace.py``) keeps no
    log, and one whose window never opened has nothing cut at its
    start."""
    assert reader(name)(SimpleNamespace(compiles=None, window_start=1.0),
                        {}, None) is None
    run = hand_made_run(LOG, CACHE, window_start=None)
    assert reader(name)(run, {}, None) is None
    # nothing of its kind before the window
    assert reader(name)(hand_made_run([], [], window_start=1.0), {},
                        None) is None


def test_the_table_names_programs_tags_phases_and_places_hits(capsys):
    run = hand_made_run(LOG, CACHE)
    rows = {(r["phase"], r["program"]): r for r in setup_log.table(run)}
    outer = rows[("warm-up step 1", "outer")]
    assert (outer["executables"], outer["misses"], outer["hits"]) == (1, 1, 0)
    assert outer["trace_lower_s"] == pytest.approx(3.0)
    assert outer["compile_load_s"] == pytest.approx(2.0)
    # <unknown> takes the name of the trace before it
    decode = rows[("after the last mark", "_decode_impl")]
    assert (decode["executables"], decode["hits"]) == (1, 1)
    assert decode["trace_lower_s"] == pytest.approx(1.0)
    assert ("warm-up step 1", "inner") in rows
    assert list(rows)[0] == ("warm-up step 1", "outer")     # largest first
    setup_log.print_table(run)
    said = capsys.readouterr().out
    assert "warm-up step 1 | outer | 1 | 3.0000 | 2.0000 | 0 | 1" in said
    assert ("total | 2 executables | trace+lower 4.0000 s (union) | "
            "compile/load 2.2500 s (union) | 1 hits | 1 misses | of set-up "
            "10.0000 s; 0 more backend compiles") in said


def test_the_real_nesting_of_an_inner_jit_through_compiles():
    """On this process's CPU: JAX traces ``inner`` inside ``outer``'s
    trace, and the reader counts that time once. The log's times are on
    ``time.perf_counter``'s clock."""
    import jax
    import jax.numpy as jnp

    log = Compiles()
    t0 = time.perf_counter()

    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2.0

    @jax.jit
    def outer(x):
        return inner(x) + 1.0

    jax.block_until_ready(outer(jnp.ones(7)))
    run = SimpleNamespace(compiles=log, t0=t0,
                          window_start=time.perf_counter(),
                          phases=[("warm-up", time.perf_counter() - t0)])
    mine = [e for e in log.events if e.start >= t0]
    traces = [e for e in mine if e.kind == "trace"]
    (outer_trace,) = [e for e in traces if e.fun_name == "outer"]
    inner_traces = [e for e in traces if e.fun_name == "inner"]
    assert inner_traces
    assert all(outer_trace.start <= e.start <= e.end <= outer_trace.end
               for e in inner_traces)
    assert all(t0 <= e.start <= e.end <= run.window_start for e in mine)
    assert [e.fun_name for e in mine if e.kind == "backend_compile"][-1] \
        == "jit(outer)"
    union = reader("setup.trace_lower_s")(run, {}, None)
    summed = sum(e.end - e.start for e in log.events
                 if e.kind in setup_log.TRACE_LOWER)
    assert 0 < union < summed
    assert reader("setup.programs")(run, {}, None) == sum(
        e.kind == "backend_compile" for e in log.events)
    assert reader("setup.compile_load_s")(run, {}, None) > 0
    # the CPU keeps no persistent cache here: no request used one
    assert reader("setup.cache_hit_share")(run, {}, None) is None


def test_the_five_entries_stand_at_the_end_for_every_cell():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert set(SETUP) <= set(names)
    assert names.index(SETUP[0]) > names.index("serve.decode_hbm_roofline")
    for name in SETUP:
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert (m["layer"], m["moves"], m["workloads"]) == (
            "entry", "setup_s", CELLS)
        assert m["source"] == ("host_clock" if name in HOST_CLOCK
                               else "program_counter")
        assert m["unit"] == {"setup.programs": "executables",
                             "setup.cache_hit_share": "%"}.get(name, "s")


@pytest.mark.parametrize("cell, seconds", [("bert-large-train-s128", "0.3"),
                                           ("gpt2m-serve-chat", "2")])
def test_a_traced_rehearsal_reports_the_setup_metrics(cell, seconds, capsys):
    rc = bench_run.main(["--workload", cell, "--seed", "2147483909",
                         "--seconds", seconds, "--trace", "1",
                         "--rehearsal"])
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True, lines
    got = result["metrics"]
    assert set(SETUP) - {"setup.cache_hit_share"} <= set(got)
    assert set(got) <= {m["name"] for m in metrics_of(BENCH, "per_layer",
                                                      cell)}
    programs = got["setup.programs"]["value"]
    assert programs > 0
    # a CPU timing is not written under a metric's name
    assert all(got[name]["value"] is None for name in HOST_CLOCK)
    # the rehearsal keeps no persistent cache: no request used one
    assert "setup.cache_hit_share" not in got
    (total,) = [l for l in lines if l.startswith("  total | ")]
    assert total.startswith(f"  total | {programs} executables")
    assert any(l.startswith("set-up by program") for l in lines)
    # the weights' init, tagged with the driver's phase
    assert any(l.startswith("  seeded weights | ") for l in lines)
