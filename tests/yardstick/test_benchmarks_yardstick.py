"""The benchmark's data and arithmetic, checked without running a model:
``BENCHMARK.json`` against its contract, every file a cell names, the
required-FLOPs count against a hand count, the traffic generator's
determinism, and the trace reduction on a recorded TPU trace and on
hand-made streams."""

import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import flops, generate, trace_reduce  # noqa: E402
from benchmarks.harness import (HERE, find, load_json, load_module,  # noqa: E402
                                metrics_of, with_rehearsal_sizes)
from benchmarks.trace_reduce import Op, Trace  # noqa: E402

BENCH = load_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


# ---------------------------------------------------------------------------
# BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

def test_benchmark_json_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert all(any(arg.startswith(p + "/") for p in BENCH["paths"])
               or "/" not in arg for arg in BENCH["command"])
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [e["name"] for e in BENCH[k]]
        assert len(got) == len(set(got)), f"a name repeats in {k}"
    assert all(len(e["why"]) <= 200
               for e in BENCH["configs"] + BENCH["workloads"])


def test_every_config_is_used_and_lives_under_paths():
    used = {w["config"] for w in BENCH["workloads"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        doc = load_json(ROOT, c["file"])
        assert doc["source"] == c["source"]
        assert doc["reduced"] == c["reduced"]
        forbidden = re.compile(r"(_dim|_rank|_size|n_embd|n_inner|n_head)$")
        assert not [k for k in c["reduced"] if forbidden.search(k)
                    and k != "vocab_size"], "a width may never be reduced"


def test_cells_pair_once_and_four_chip_cells_are_a_quarter_at_most():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(pairs) // 4)


def test_metrics_are_well_formed_and_bounded():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["better"] in ("higher", "lower")
    for m in BENCH["per_layer"]:
        assert m["source"] in SOURCES and "bound" not in m
        assert m["moves"] in e2e
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        # a per-layer metric is reported only where the metric it moves is
        moved_in = set(e2e[m["moves"]].get("workloads", CELLS))
        assert set(m.get("workloads", CELLS)) <= moved_in, m["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", [])) <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_names_files_that_exist_and_reports_enough(cell):
    w = find(BENCH["workloads"], cell, "workload")
    config = load_json(ROOT, find(BENCH["configs"], w["config"],
                                  "config")["file"])
    traffic = load_json(HERE, "traffic", w["traffic"] + ".json")
    family = load_module("families", config["family"])
    driver = load_module("drivers", traffic["driver"])
    assert callable(driver.run) and callable(family.build_model)
    # the rehearsal sizes only override keys that exist
    for doc in (config, traffic):
        small = with_rehearsal_sizes(doc)
        assert set(small) == set(doc)
        for key, value in doc.get("rehearsal", {}).items():
            if isinstance(value, dict):
                assert set(value) <= set(doc[key]), key
    e2e = [m["name"] for m in metrics_of(BENCH, "end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = metrics_of(BENCH, "per_layer", cell)
    assert layer
    for m in layer:
        assert callable(load_module("layer_metrics", m["name"]).read)


def test_an_unknown_name_is_an_error_that_names_what_exists():
    with pytest.raises(KeyError, match="gpt2m-train-s1024"):
        find(BENCH["workloads"], "no-such-cell", "workload")
    with pytest.raises(FileNotFoundError):
        load_module("families", "no_such_family")
    with pytest.raises(KeyError, match="TPU v5 lite"):
        flops.load_peaks("cpu")


# ---------------------------------------------------------------------------
# flops.py against a hand count
# ---------------------------------------------------------------------------

def test_required_flops_of_gpt2_medium_match_a_hand_count():
    config = load_json(HERE, "configs", "gpt2-medium.json")
    family = load_module("families", "gpt2")
    # per layer: QKV 3*1024^2, proj 1024^2, MLP 2*1024*4096 multiply-adds
    dense = 2 * (3 * 1024 ** 2 + 1024 ** 2 + 2 * 1024 * 4096)
    assert dense == 25_165_824
    # causal attention at 1024: a query sees (1024+1)/2 keys on average,
    # 2*1024 FLOPs per key in QK^T and again in PV
    attention = 4 * 1024 * 1025 / 2
    head = 2 * 1024 * 50257
    by_hand = 24 * (dense + attention) + head
    got = family.forward_flops_per_token(config, {"seq_len": 1024})
    assert got == pytest.approx(by_hand, rel=1e-12)
    assert 3 * got == pytest.approx(2.2719e9, rel=1e-3)
    # the superseded count (bench.py) took the full square: 2x attention
    full = 24 * (dense + 2 * attention) + head
    assert full > got


def test_required_flops_of_bert_large_match_a_hand_count():
    config = load_json(HERE, "configs", "bert-large.json")
    family = load_module("families", "bert")
    dense = 2 * (4 * 1024 ** 2 + 2 * 1024 * 4096)
    attention = 4 * 1024 * 128                  # every key, no mask
    head = 0.15 * (2 * 1024 ** 2 + 2 * 1024 * 30522)   # masked positions
    by_hand = 24 * (dense + attention) + head
    got = family.forward_flops_per_token(
        config, {"seq_len": 128, "mask_rate": 0.15})
    assert got == pytest.approx(by_hand, rel=1e-12)
    assert 3 * got == pytest.approx(1.8789e9, rel=1e-3)


def test_mfu_and_the_flash_roofline_arithmetic():
    peaks = flops.load_peaks("TPU v5 lite")
    assert (peaks["bf16_tflops"], peaks["hbm_gbps"]) == (197.0, 819.0)
    assert flops.mfu(2.0e9, 49_250.0, 197.0) == pytest.approx(0.5)
    work = dict(batch=4, seq=1024, hidden=1024)
    f = flops.flash_train_flops(causal=True, **work)
    assert f == 7 * 2 * 4 * 1024 * 512.5 * 1024
    assert flops.flash_train_flops(causal=False, **work) == \
        pytest.approx(f * 1024 / 512.5)
    b = flops.flash_train_bytes(**work)
    assert b == 12 * 4 * 1024 * 1024 * 2
    least, bound = flops.roofline_seconds(f, b, peaks)
    assert bound == "compute" and least == pytest.approx(f / 197e12)
    assert flops.roofline_seconds(1.0, 819e9, peaks) == (1.0, "memory")


# ---------------------------------------------------------------------------
# generate.py: same seed, same inputs; the seed shuffles, it does not resize
# ---------------------------------------------------------------------------

def test_train_pool_is_a_function_of_the_seed():
    traffic = with_rehearsal_sizes(load_json(HERE, "traffic",
                                             "train-s1024.json"))
    a = generate.train_pool(traffic, 512, 2, seed=3)
    b = generate.train_pool(traffic, 512, 2, seed=3)
    c = generate.train_pool(traffic, 512, 2, seed=4)
    assert len(a) == traffic["pool_batches"]
    assert a[0].shape == (traffic["gradient_accumulation_steps"],
                          2 * traffic["micro_batch_per_chip"],
                          traffic["seq_len"])
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert a[0].dtype == np.int32 and 0 <= a[0].min() and a[0].max() < 512


def test_zipf_tokens_are_skewed():
    ids = generate.zipf_tokens(np.random.default_rng(0), 1000, 200_000)
    counts = np.sort(np.bincount(ids, minlength=1000))[::-1]
    # rank 1 about 1/H(1000) = 13% of tokens, rank 10 a tenth of that
    assert 0.11 < counts[0] / ids.size < 0.16
    assert 5 < counts[0] / counts[9] < 20


def test_open_loop_schedule_is_a_function_of_the_seed():
    traffic = load_json(HERE, "traffic", "chat-poisson.json")
    a = generate.open_loop_requests(traffic, 50257, seed=5, seconds=30)
    b = generate.open_loop_requests(traffic, 50257, seed=5, seconds=30)
    c = generate.open_loop_requests(traffic, 50257, seed=6, seconds=30)
    assert a == b and a != c
    prime = traffic["prime_seconds"]
    n = round(traffic["rate_per_s"] * (30 + prime))
    assert len(a) == len(c) == n
    due = [r["due"] for r in a]
    assert due == sorted(due) and -prime < due[0] and due[-1] < 30
    in_window = [r for r in a if r["due"] >= 0]
    assert len(in_window) == round(traffic["rate_per_s"] * 30)
    # the same work whatever the seed: the seed only shuffles
    lengths = lambda rs, k: sorted(len(r[k]) if k == "prompt" else r[k]
                                   for r in rs)
    assert lengths(a, "prompt") == lengths(c, "prompt")
    assert lengths(a, "max_new_tokens") == lengths(c, "max_new_tokens")
    p, o = traffic["prompt_len"], traffic["output_len"]
    assert all(p["min"] <= len(r["prompt"]) <= p["max"] for r in a)
    assert all(o["min"] <= r["max_new_tokens"] <= o["max"] for r in a)
    assert all(len(r["prompt"]) + r["max_new_tokens"]
               <= traffic["max_total_len"] for r in a)
    # the medians are the distributions'
    assert np.median(lengths(a, "prompt")) == pytest.approx(p["median"],
                                                            rel=0.05)
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(1 / traffic["rate_per_s"], rel=0.02)
    # exponential: the standard deviation is about the mean
    assert gaps.std() == pytest.approx(gaps.mean(), rel=0.1)


def test_every_stratum_holds_the_same_arrivals_and_lengths():
    """``stratum_seconds``: the seed shuffles WITHIN a stratum, so no
    stretch of the window is given more work than another."""
    traffic = load_json(HERE, "traffic", "chat-poisson.json")
    size, prime = traffic["stratum_seconds"], traffic["prime_seconds"]
    assert (30 / size).is_integer() and (prime / size).is_integer()
    per = round(traffic["rate_per_s"] * size)
    seen = set()
    for seed in (5, 6, 2**31 + 7):
        rs = generate.open_loop_requests(traffic, 50257, seed, 30)
        strata = {}
        for r in rs:
            strata.setdefault(int(np.floor(r["due"] / size)), []).append(r)
        assert sorted(strata) == list(range(-int(prime / size),
                                            int(30 / size)))
        pairs = {tuple(sorted((len(r["prompt"]), r["max_new_tokens"])
                              for r in s)) for s in strata.values()}
        assert all(len(s) == per for s in strata.values())
        # one multiset of (prompt, output) PAIRS: which prompt meets which
        # output is work (positions held in the cache), so it is the
        # stratum's and not the seed's
        assert len(pairs) == 1
        seen |= pairs
        # and the order inside a stratum is the seed's
        first = [len(r["prompt"]) for r in strata[0]]
        assert first != sorted(first)
    # lengths stay independent: prompt i meets output i * s mod n
    (pairs,) = seen
    p, o = np.array(pairs).T
    assert abs(np.corrcoef(p, o)[0, 1]) < 0.05
    assert len(seen) == 1               # the same pairs whatever the seed


def test_a_stratum_as_long_as_the_window_is_the_one_stratum():
    traffic = load_json(HERE, "traffic", "chat-poisson.json")
    traffic["stratum_seconds"] = 10
    rs = generate.open_loop_requests(traffic, 50257, 3, 10)
    rate, prime = traffic["rate_per_s"], traffic["prime_seconds"]
    assert sum(r["due"] < 0 for r in rs) == round(rate * prime)
    assert sum(r["due"] >= 0 for r in rs) == round(rate * 10)
    with pytest.raises(KeyError):       # the two keys are the schedule
        generate.open_loop_requests(
            {k: v for k, v in traffic.items() if k != "prime_seconds"},
            50257, 3, 10)


# sha256 of the JSON of the schedule that ``chat-poisson.json`` gives, by
# seed and window (PR 34). The schedule is what the serving cell times: a
# change to the generator that moves it is a change to every accepted
# number of the cell, and shows here first.
SCHEDULE = {(5, 30): "819f849b319462b3", (5, 2.0): "bdaec764ba66923b",
            (5, 0.3): "c1b218e784e0d5be",
            (2147483999, 30): "f8d94b209b2704d8",
            (2147483999, 2.0): "2d4055d2b1de79a7",
            (2147483999, 0.3): "e71f6eec0f204b1f"}


@pytest.mark.parametrize("seed,seconds", list(SCHEDULE))
def test_the_schedule_of_a_seed_is_the_one_the_cell_was_measured_on(
        seed, seconds):
    import hashlib
    traffic = load_json(HERE, "traffic", "chat-poisson.json")
    got = generate.open_loop_requests(traffic, 50257, seed, seconds)
    assert hashlib.sha256(json.dumps(got).encode()).hexdigest()[:16] \
        == SCHEDULE[(seed, seconds)]
    prime = traffic["prime_seconds"]
    assert sum(r["due"] < 0 for r in got) == round(
        traffic["rate_per_s"] * prime)


def test_percentile_helpers():
    assert generate.percentile([1, 2, 3, 4, 5], 50) == 3.0
    with pytest.raises(ValueError):
        generate.percentile([], 50)
    assert generate.tail_is_supported(200, 95)
    assert not generate.tail_is_supported(100, 95)


# ---------------------------------------------------------------------------
# trace_reduce.py on hand-made streams
# ---------------------------------------------------------------------------

def test_interval_arithmetic():
    assert trace_reduce.merge([(3, 4), (0, 1), (0.5, 2), (4, 4)]) == \
        [(0, 2), (3, 4)]
    assert trace_reduce.total([(0, 2), (3, 4)]) == 3
    assert trace_reduce.clip([(0, 2), (3, 5), (6, 7)], (1, 4)) == \
        [(1, 2), (3, 4)]
    assert trace_reduce.uncovered((0, 10), [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]


def two_stream_trace():
    """One device, a 10 s window. Compute runs 0-4 (a matmul fusion, then
    a Mosaic call) and 6-8. An all-gather is in flight 2-6: its start
    takes no time, and from 4 to 6 the core waits in its done, so half of
    the collective is hidden by nothing. Host: put until 1, dispatch 8-9,
    fetch 9-10."""
    ops = [Op("fusion.1", 0.0, 3.0, "fusion", "kOutput"),
           Op("h_0.3", 3.0, 4.0, "custom-call", "tpu_custom_call",
              "bf16[64,1024,64]"),
           Op("all-gather-start.7", 2.0, 2.0 + 1e-9, "all-gather-start"),
           Op("all-gather-start.7", 2.0, 6.0, "all-gather-start",
              in_flight=True),
           Op("all-gather-done.7", 4.0, 6.0, "all-gather-done"),
           Op("fusion.2", 6.0, 8.0, "fusion", "kLoop"),
           Op("fusion.9", 11.0, 12.0, "fusion", "kLoop")]      # outside
    host = [Op("bench.window", 0.0, 10.0), Op("bench.put", 0.0, 1.0),
            Op("bench.dispatch", 8.0, 9.0), Op("bench.fetch", 9.0, 10.0)]
    return Trace({0: ops}, host)


def test_hlo_text_is_parsed_as_the_trace_prints_it():
    parse = trace_reduce.parse_hlo
    assert parse("%fusion.5350 = s32[1,8,4,128]{3,2,1,0:T(4,128)S(1)} "
                 "fusion(s32[8,4,1024]{2,1,0:T(4,128)} %gte.21183), "
                 "kind=kLoop, calls=%fused_computation.2202.clone") == \
        ("fusion.5350", "fusion", "kLoop", "")
    assert parse(
        "%h_0.30 = (bf16[64,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, "
        "f32[64,1024,128]{2,1,0:T(8,128)}) custom-call(s32[1]{0:T(128)} "
        "%gte.21477, bf16[64,1024,64]{2,1,0} %bitcast.4027), "
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        "{s32[1]{0}}") == (
            "h_0.30", "custom-call", "tpu_custom_call",
            "(bf16[64,1024,64], f32[64,1024,128])")
    name, opcode, _, _ = parse(
        "%while.6 = (s32[]{:T(128)}, bf16[3072]{0:T(1024)(128)(2,1)}) "
        "while((s32[]{:T(128)}, bf16[3072]{0}) %tuple.1), "
        "condition=%cond, body=%body")
    assert (name, opcode) == ("while.6", "while")
    assert opcode in trace_reduce.CONTAINERS
    assert parse("bench.window") == ("bench.window", "", "", "")
    done = Op(*parse("%all-gather-done.3 = bf16[8]{0} all-gather-done("
                     "(bf16[2]{0}, bf16[8]{0}) %all-gather-start.3)")[:1],
              0, 1, "all-gather-done")
    assert trace_reduce.is_collective(done)
    wrapped = Op("all-to-all-start.2", 0, 1, "async-start")
    assert trace_reduce.is_collective(wrapped)
    assert not trace_reduce.is_collective(Op("copy-start.2", 0, 1,
                                             "copy-start"))
    assert trace_reduce.table_key(Op("bitcast_add_fusion.12", 0, 1,
                                     "fusion", "kOutput")) == \
        "fusion kOutput bitcast_add_fusion"


def test_a_half_hidden_collective_on_two_streams():
    red = trace_reduce.reduce(two_stream_trace())
    assert red.window == (0.0, 10.0) and red.window_s == 10.0
    assert red.busy == {0: 8.0} and red.busy_s == 8.0
    assert red.by_class["collective"] == {0: 4.0}
    assert red.exposed_collective == {0: 2.0}          # 4-6 only
    assert red.by_class["matmul"] == {0: 3.0}
    assert red.by_class["mosaic"] == {0: 1.0}
    assert red.share_of_busy("mosaic") == pytest.approx(1 / 8)
    # idle 8-10, labelled by what the host was doing then
    assert red.gaps == {"dispatch": 1.0, "fetch": 1.0}
    br = trace_reduce.breakdown(red)
    assert br["device_ops"][0] == ["fusion kOutput fusion", 3.0]
    # the span in flight is not an op of the core, fusion.9 is outside
    assert dict(br["device_ops"]) == {
        "fusion kOutput fusion": 3.0, "fusion kLoop fusion": 2.0,
        "all-gather-done all-gather-done": 2.0,
        "custom-call tpu_custom_call -> bf16[64,1024,64]": 1.0,
        "all-gather-start all-gather-start": pytest.approx(1e-9)}
    assert sorted(br["idle_gaps"]) == [["dispatch", 1.0], ["fetch", 1.0]]


def test_devices_are_averaged_and_a_silent_device_is_left_out():
    t = two_stream_trace()
    t.devices[1] = [Op("fusion.1", 0.0, 4.0, "fusion", "kOutput")]
    t.devices[2] = []
    red = trace_reduce.reduce(t)
    assert red.busy == {0: 8.0, 1: 4.0} and red.busy_s == 6.0
    assert red.mean(red.exposed_collective) == 1.0
    assert red.share_of_busy("matmul") == pytest.approx(7 / 12)
    # device 1 idles 4-10: 4 s unattributed, 1 dispatch, 1 fetch; the
    # table is the mean over the two devices
    assert red.gaps == {"dispatch": 1.0, "fetch": 1.0, "unattributed": 2.0}


def test_a_trace_without_the_window_annotation_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce(Trace({0: [Op("fusion.1", 0, 1, "fusion")]}, []))


def test_json_round_trip_cuts_to_the_window():
    t = two_stream_trace()
    doc = trace_reduce.to_json(t, (0.0, 10.0))
    assert len(doc["devices"]["0"]) == 6
    back = trace_reduce.from_json(json.loads(json.dumps(doc)))
    assert trace_reduce.reduce(back).busy == {0: 8.0}


# ---------------------------------------------------------------------------
# trace_reduce.py on the trace recorded on the chip
# ---------------------------------------------------------------------------

FIXTURE = os.path.join(HERE, "fixtures",
                       "v5e_gpt2m_train_step_boundary.json.gz")
EXPECTED = FIXTURE.replace(".json.gz", ".expected.json")


@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.load_fixture(FIXTURE)


def test_the_recorded_trace_reduces_to_the_numbers_counted_by_hand(recorded):
    """100 ms of one v5e round the boundary between two optimizer steps
    of ``gpt2m-train-s1024``. Busy is recounted here with a sort and a
    running maximum, the Mosaic and matmul shares as plain sums (the ops
    of one core do not overlap), and all of them are pinned in the
    ``.expected.json`` beside the fixture, which was written the same
    way and not by ``trace_reduce``."""
    w0, w1 = trace_reduce.window_of(recorded)
    core = sorted((max(o.start, w0), min(o.end, w1), o)
                  for o in recorded.devices[0]
                  if not o.in_flight and o.end > w0 and o.start < w1)
    busy, reach, gaps = 0.0, w0, []
    for s, e, _ in core:
        if e > reach:
            if s - reach > 1e-6:
                gaps.append(s - reach)
            busy += e - max(s, reach)
            reach = e
    mosaic = sum(e - s for s, e, o in core if trace_reduce.is_mosaic(o))
    matmul = sum(e - s for s, e, o in core if trace_reduce.is_matmul(o))
    with open(EXPECTED) as f:
        pinned = json.load(f)

    red = trace_reduce.reduce(recorded)
    assert red.window_s == pytest.approx(pinned["window_s"], rel=1e-9)
    for got in (red.busy[0], red.busy_s, busy):
        assert got == pytest.approx(pinned["busy_s"], rel=1e-9)
    assert 1 - red.busy_s / red.window_s == \
        pytest.approx(pinned["idle_share"], rel=1e-6)
    assert red.share_of_busy("mosaic") == pytest.approx(mosaic / busy)
    assert red.share_of_busy("mosaic") == \
        pytest.approx(pinned["mosaic_share"], rel=1e-9)
    assert red.share_of_busy("matmul") == pytest.approx(matmul / busy)
    assert red.share_of_busy("matmul") == \
        pytest.approx(pinned["matmul_share"], rel=1e-9)
    # three gaps over a microsecond; the longest, 60 us, is the one
    # between the two step programs, while the host sat in its fetch
    assert len(gaps) == len(pinned["gaps_over_1us"]) == 3
    assert max(gaps) == pytest.approx(59.826e-6, rel=1e-3)
    assert max(red.gaps, key=red.gaps.get) == "fetch"
    assert sum(red.gaps.values()) == \
        pytest.approx(red.window_s - red.busy_s, rel=1e-9)
    # one chip: no collective, nothing exposed
    assert sum(red.by_class["collective"].values()) == 0.0
    assert sum(red.exposed_collective.values()) == 0.0
    # the three flash kernels are told apart by their result shapes
    kernels = [k for k, _ in trace_reduce.top(red.ops, 40)
               if "tpu_custom_call" in k]
    assert len(kernels) == 3


FOUR_CHIPS = os.path.join(HERE, "fixtures",
                          "v5e_x4_gpt2xl_zero3_midstep.json.gz")


def test_collectives_of_the_recorded_four_chip_trace_counted_on_a_grid():
    """12 ms of a four-chip ZeRO-3 step. Every row is painted onto a grid
    of nanoseconds with numpy and the cells are counted: busy, collective
    time (ops and spans in flight) and the part of it no other op of that
    chip covers. The same counts are pinned in the ``.expected.json``
    beside the fixture."""
    trace = trace_reduce.load_fixture(FOUR_CHIPS)
    red = trace_reduce.reduce(trace)
    with open(FOUR_CHIPS.replace(".json.gz", ".expected.json")) as f:
        pinned = json.load(f)
    w0, w1 = red.window
    cells = round((w1 - w0) * 1e9)
    assert cells == 12_000_000 and sorted(red.busy) == [0, 1, 2, 3]
    for dev, ops in trace.devices.items():
        busy, compute, coll = (np.zeros(cells, bool) for _ in range(3))
        for o in ops:
            s = max(round((o.start - w0) * 1e9), 0)
            e = min(round((o.end - w0) * 1e9), cells)
            if not o.in_flight:
                busy[s:e] = True
                if not trace_reduce.is_collective(o):
                    compute[s:e] = True
            if trace_reduce.is_collective(o):
                coll[s:e] = True
        want = pinned["devices"][str(dev)]
        counted = {"busy_ns": busy.sum(), "collective_ns": coll.sum(),
                   "exposed_collective_ns": (coll & ~compute).sum()}
        got = {"busy_ns": red.busy[dev],
               "collective_ns": red.by_class["collective"][dev],
               "exposed_collective_ns": red.exposed_collective[dev]}
        for key in counted:
            assert counted[key] == want[key], (dev, key)
            assert got[key] * 1e9 == pytest.approx(want[key], abs=0.5)
    # the first chip alone shows what is in flight behind compute; what
    # is exposed reads alike on all four (within a quarter of a percent)
    assert red.spans_in_flight == [0]
    exposed = [red.exposed_collective[d] for d in range(4)]
    assert max(exposed) / min(exposed) < 1.0025
    assert red.by_class["collective"][0] > 1.6 * red.by_class["collective"][1]
    assert all(red.by_class["collective"][d] == pytest.approx(
        red.exposed_collective[d]) for d in (1, 2, 3))
    # the metric readers: total where spans exist, exposed as a mean
    from types import SimpleNamespace
    run = SimpleNamespace(chips=4)
    total_share = load_module("layer_metrics", "comm.collective_share").read(
        run, {}, red)
    exposed_share = load_module("layer_metrics", "comm.exposed_share").read(
        run, {}, red)
    assert total_share == pytest.approx(100 * 4654412 / 12e6)
    assert exposed_share == pytest.approx(
        100 * (2761958 + 2758525 + 2765172 + 2762723) / 4 / 12e6)
    assert load_module("layer_metrics", "comm.exposed_share").read(
        SimpleNamespace(chips=1), {}, red) is None
