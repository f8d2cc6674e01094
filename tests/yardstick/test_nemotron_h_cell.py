"""The cell ``nemotron3s-serve-chat`` as the benchmark has it: the
configuration against the catalog's keys, the cut against ISSUE 35's
table, parameters and bytes against a hand count (and against the
program's own model at the rehearsal's size), the traffic, the
``BENCHMARK.json`` entries against their files, the six new readers on a
hand-made traced stretch (and on one of a program that lacks the scopes,
as the parent does), the reference's independence, the step's cost model
under the open-loop simulator, and a traced rehearsal on the CPU."""

import json
import os
import re
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import flops, flops_nemotron_h as count  # noqa: E402
from benchmarks import program_trace as pt, trace_reduce  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import (HERE, find, load_json,  # noqa: E402
                                load_module, metrics_of, open_cell)
from benchmarks.program_trace import DeviceOp, ProgramTrace, Span  # noqa: E402

CELL = "nemotron3s-serve-chat"
BENCH = load_json(ROOT, "BENCHMARK.json")
CONFIG = load_json(ROOT, "benchmarks", "configs",
                   "nemotron-3-super-120b-a12b.json")
TRAFFIC = load_json(HERE, "traffic", "chat-poisson-2k.json")
NEW = ["serve.ssm_share", "serve.ssm_state_roofline", "serve.moe_share",
       "serve.moe_experts_roofline", "serve.moe_experts_touched",
       "serve.decode_hbm_roofline"]
JOINED = ["serve.late_ms_p99", "serve.step_ms_p50", "serve.batch_occupancy",
          "serve.ttft_ms_p50", "serve.ttft_ms_p90", "serve.prefill_ms_p50",
          "serve.decode_ms_p50", "serve.host_ms_p50",
          "serve.prefill_device_share", "serve.kv_gather_share",
          "serve.kv_read_useful_share"]

# the catalog's row (/opt/skills/guides/model-configs/architectures.jsonl,
# NVIDIA-Nemotron-3-Super-120B-A12B-BF16), every key of its ``config``
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern":
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False,
    "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8,
    "n_routed_experts": 512, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 22, "num_hidden_layers": 88,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False,
    "rope_theta": 10000, "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 11, "hybrid_override_pattern": "EMEMEMEMEM*",
           "n_routed_experts": 128, "vocab_size": 32768,
           "num_nextn_predict_layers": 0}


def reader(name):
    return load_module("layer_metrics", name).read


# ---------------------------------------------------------------------------
# The configuration, the cut and the traffic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_a_published_key_is_as_published_or_listed_as_reduced(key):
    if key in REDUCED:
        assert key in CONFIG["reduced"]
        assert CONFIG[key] == REDUCED[key]
        assert CONFIG["published"][key] == PUBLISHED[key]
    else:
        assert CONFIG[key] == PUBLISHED[key]


def test_the_cut_is_one_whole_period_and_names_no_width():
    entry = find(BENCH["configs"], "nemotron-3-super-120b-a12b", "config")
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"]) \
        == sorted(REDUCED)
    assert entry["source"] == CONFIG["source"]
    width = re.compile(r"(_dim|_rank|_size)$|^expand$|per_tok")
    assert not [k for k in CONFIG["reduced"]
                if width.search(k) and k != "vocab_size"]
    # layers 26-36 of the published string, the period that occurs four
    # times, every kind in its published ratio 40 : 40 : 8
    whole, cut = PUBLISHED["hybrid_override_pattern"], REDUCED[
        "hybrid_override_pattern"]
    assert whole[26:37] == cut and whole.count(cut) == 4
    assert len(whole) == 88 and len(cut) == CONFIG["num_hidden_layers"]
    assert [whole.count(k) for k in "ME*"] == [40, 40, 8]
    assert [cut.count(k) * 8 for k in "ME*"] == [40, 40, 8]
    for note in ("positions", "in_proj_order", "gate_before_norm",
                 "latent_projections", "router_input", "weights", "mtp"):
        assert len(CONFIG["assumed"][note]) > 40
    assert "four chips share each layer" in CONFIG["deployment"]


def test_the_parameters_held_are_the_issues_4648_million():
    p = count.parameters(CONFIG)
    assert p["mamba_layer"] == 109_640_064
    assert p["attention_layer"] == 35_655_680
    assert p["expert_layer_outside_routed"] == 54_530_560
    assert p["routed_expert"] == 5_505_024
    assert p["total"] == 4_648_163_712
    assert "4,648,163,712" in CONFIG["deployment"]
    # the whole published model, by the same count: "120B-A12B"
    whole = dict(CONFIG, **PUBLISHED, published=PUBLISHED)
    assert round(count.parameters(whole)["total"] / 1e9, 1) == 120.7
    assert count.state_bytes_per_slot_layer(CONFIG) == 4_255_744
    assert count.kv_bytes_per_position_layer(CONFIG) == 1024


def test_the_count_is_the_programs_own_model_at_the_rehearsals_size():
    import jax
    _, _, config, _ = open_cell(CELL, rehearsal=True)
    family = load_module("families", config["family"])
    model, _ = family.build_model(config)
    shapes = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, family.example_batch()))["params"]
    held = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert held == count.parameters(config)["total"]
    specs = model.serving_cache_spec()
    assert [s.kind for s in specs] == ["none", "recurrent", "kv"]
    state = sum(int(jax.numpy.dtype(dt).itemsize)
                * int(jax.numpy.prod(jax.numpy.asarray(shape)))
                for _, shape, dt in specs[1].shapes)
    # float32 here (the family builds bfloat16): the tail is 2 B a number
    assert state == count.state_bytes_per_slot_layer(config)
    assert (specs[2].heads, specs[2].head_dim) == (2, 16)


def test_the_traffic_is_the_issues():
    entry = find(BENCH["workloads"], CELL, "workload")
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "nemotron-3-super-120b-a12b", "chat-poisson-2k", 1)
    t = TRAFFIC
    assert t["driver"] == "serve_open_loop"
    assert t["prompt_len"] == {"dist": "lognormal", "median": 256,
                               "sigma": 0.9, "min": 32, "max": 1536}
    assert t["output_len"] == {"dist": "lognormal", "median": 128,
                               "sigma": 0.7, "min": 16, "max": 384}
    assert (t["max_total_len"], t["token_zipf_exponent"]) == (2048, 1.0)
    assert (t["check_requests"], t["trace_seconds"],
            t["drain_seconds"]) == (32, 1, 75)
    assert t["stratum_seconds"] == 2.5
    assert t["prime_seconds"] % 2.5 == 0 and t["prime_seconds"] >= 10
    per_stratum = t["rate_per_s"] * t["stratum_seconds"]
    assert per_stratum == int(per_stratum)
    s = CONFIG["serving"]
    assert s["max_model_len"] == t["max_total_len"]
    assert s["kv_num_blocks"] == s["max_batch_size"] * (
        s["max_model_len"] // s["kv_block_size"]) + 1
    # no data file carries a limit of the comparison
    assert not [k for k in list(t) + list(CONFIG) if "tol" in k.lower()]


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmarks", "reference",
                           "nemotron_h.py")) as f:
        lines = [l for l in f.read().splitlines()
                 if re.match(r"(import|from)\s+[A-Za-z_]", l)]
    assert lines == ["import jax", "import jax.numpy as jnp"]


# ---------------------------------------------------------------------------
# Bytes and operations against a hand count, at the rehearsal's size
# ---------------------------------------------------------------------------

def test_the_decode_steps_bytes_match_a_hand_count():
    _, _, c, _ = open_cell(CELL, rehearsal=True)
    # hidden 64, 8 Mamba heads x 16 (inner 128, 2 groups, state 16: conv
    # 192), 4 heads on 2 at 16, latent 32, experts 48 wide, shared 96,
    # router 8 wide, vocabulary 512; pattern EM*
    mamba = 64 * (128 + 192 + 8) + 5 * 192 + 3 * 8 + 128 + 128 * 64 + 64
    attention = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 64
    outside = 64 * 8 + 8 + 2 * 64 * 32 + 2 * 64 * 96 + 64
    p = count.parameters(c)
    assert (p["mamba_layer"], p["attention_layer"],
            p["expert_layer_outside_routed"],
            p["routed_expert"]) == (mamba, attention, outside, 2 * 32 * 48)
    state = 128 * 16 * 4 + 3 * 192 * 2
    assert count.state_bytes_per_slot_layer(c) == state
    assert count.ssm_state_step_bytes(c, 3) == 2 * 3 * state
    assert count.experts_step_flops(c, 5) == 5 * 2 * 2 * 32 * 48
    assert count.experts_step_bytes(c, 2, 5) == (2 * 2 * 32 * 48 * 2
                                                 + 5 * 2 * 32 * 2)
    kv = 2 * 2 * 16 * 2
    assert count.kv_bytes_per_position_layer(c) == kv
    got = count.decode_step_bytes(c, live_rows=3, experts_touched=2,
                                  held_assignments=5, live_positions=40)
    weights = (mamba + attention + outside + 512 * 64 + 64) * 2
    assert got == (weights + 3 * 64 * 2 + 2 * 2 * 32 * 48 * 2
                   + 5 * 2 * 32 * 2 + 2 * 3 * state + 43 * kv)


def test_the_real_cells_step_needs_what_the_issue_reckoned():
    # 60 rows alive, 92% of the held experts touched. ISSUE 35 reckoned
    # 5.2 GB of weights + 2.5 GB of state, 9.4 ms; it took an expert
    # layer's 705M held PARAMETERS for 705 MB: they are 1.41 GB. So: 1.98
    # GB outside the routed experts and the head, 6.5 GB of touched
    # experts, 2.55 GB of state both ways: 11 GB, 13.5 ms at 819 GB/s
    nbytes = count.decode_step_bytes(
        CONFIG, live_rows=60, experts_touched=0.92 * 128 * 5,
        held_assignments=60 * 5.5 * 5, live_positions=60 * 400)
    assert 10.8e9 < nbytes < 11.3e9
    least, bound = flops.roofline_seconds(0.0, nbytes,
                                          flops.load_peaks("TPU v5 lite"))
    assert bound == "memory" and 0.0130 < least < 0.0140


# ---------------------------------------------------------------------------
# BENCHMARK.json
# ---------------------------------------------------------------------------

def test_the_cell_is_appended_to_the_metrics_it_reports():
    end_to_end = {m["name"] for m in metrics_of(BENCH, "end_to_end", CELL)}
    assert end_to_end == {"itl_ms_p95", "setup_s"}
    listed = [m["name"] for m in metrics_of(BENCH, "per_layer", CELL)]
    assert listed == JOINED + NEW
    for name in JOINED + ["itl_ms_p95"]:
        entry = find(BENCH["per_layer"] + BENCH["end_to_end"], name, "metric")
        assert entry["workloads"][-1] == CELL
        assert entry["workloads"][0] == "gpt2m-serve-chat"


@pytest.mark.parametrize("name", NEW)
def test_a_reader_has_its_entry_at_the_end(name):
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-len(NEW):] == NEW
    entry = find(BENCH["per_layer"], name, "metric")
    assert entry["moves"] == "itl_ms_p95" and entry["workloads"] == [CELL]
    assert entry["unit"] == "%"
    assert callable(reader(name))


# ---------------------------------------------------------------------------
# The readers on a hand-made traced stretch
# ---------------------------------------------------------------------------

def op(scope, start, end, name="fusion.1", program=7):
    return DeviceOp(name, start, end, scope, program_id=program,
                    kernel=pt.kernel_of(scope))


DECODE = "jit(_decode_impl)/ds.decode/NemotronH/"
PREFILL = "jit(_prefill_impl)/ds.prefill/NemotronH/"


def traced_stretch(scopes=True):
    """Ten seconds of one device: two decode steps (program 7) and one
    prefill (program 8); ``scopes=False`` is a program that names none of
    this PR's scopes and hands its spans no counter (the parent's)."""
    ds = (lambda s: s) if scopes else (lambda s: "h")
    ops = [
        op(DECODE + f"{ds('ds.ssm')}/mixer_1/in_proj/dot", 0.0, 0.5),
        op(DECODE + f"{ds('ds.ssm')}/mixer_1/{ds('ds.ssm_conv')}/add",
           0.5, 0.75),
        op(DECODE + f"{ds('ds.ssm')}/mixer_1/{ds('ds.ssm_step')}/mul",
           0.75, 1.75),
        op(DECODE + f"mixer_0/{ds('ds.moe_route')}/top_k", 1.75, 2.0),
        op(DECODE + f"mixer_0/{ds('ds.moe_experts')}/mul", 2.0, 2.25),
        op(DECODE + f"mixer_0/{ds('ds.moe_shared')}/shared_up/dot",
           2.75, 3.0),
        op(DECODE + f"{ds('ds.attn')}/mixer_2/ds.kv_gather/gather",
           3.0, 3.5),
        op(DECODE + "lm_head/dot", 3.5, 4.0),
        op(PREFILL + f"{ds('ds.ssm')}/mixer_1/{ds('ds.ssm_scan')}/dot",
           4.0, 5.0, program=8),
        op(PREFILL + f"mixer_0/{ds('ds.moe_experts')}/mul", 5.0, 6.0,
           program=8),
        op(DECODE + f"{ds('ds.ssm')}/mixer_1/{ds('ds.ssm_step')}/mul",
           6.0, 7.0),
        op(DECODE + "lm_head/dot", 7.0, 10.0)]
    if scopes:      # the kernel exists only where the layer does
        ops.insert(5, op("ragged-dot-none", 2.25, 2.75,
                         name="ragged-dot-none.7"))
    counters = [{"state_slots_live": 40, "moe_experts_touched": 500,
                 "moe_held_assignments": 1000, "moe_held_rows_max": 20},
                {"state_slots_live": 60, "moe_experts_touched": 600,
                 "moe_held_assignments": 1600, "moe_held_rows_max": 30}]
    line = ("/host:CPU", "main#1")
    spans = [Span("decode_step", 0.0 + 6 * i, 4.0 + 6 * i,
                  {"step": i, "active": 40 + 20 * i,
                   "live_positions": 16000 + 8000 * i,
                   "read_positions": 262144, **(c if scopes else {})}, line)
             for i, c in enumerate(counters)]
    trace = ProgramTrace({0: ops}, spans, (0.0, 10.0))
    pt.link_spans(trace.spans)
    return trace


def reduced_of(trace):
    plain = trace_reduce.Trace(
        {d: [trace_reduce.Op(o.name, o.start, o.end, "fusion", "kLoop")
             for o in ops] for d, ops in trace.devices.items()},
        [trace_reduce.Op(trace_reduce.WINDOW, *trace.window)])
    return trace_reduce.reduce(plain)


def read_all(trace, monkeypatch, reduced=True):
    run = SimpleNamespace(
        xplane=lambda: "x", config=CONFIG, traffic=TRAFFIC,
        family=load_module("families", CONFIG["family"]),
        peaks=flops.load_peaks("TPU v5 lite"))
    monkeypatch.setattr(pt, "load", lambda path: trace)
    red = reduced_of(trace) if reduced else None
    return {name: reader(name)(run, {}, red) for name in NEW}


def test_the_six_readers_on_a_hand_made_stretch(monkeypatch, capsys):
    got = read_all(traced_stretch(), monkeypatch)
    busy = 10.0 - 0.0           # the ops leave no gap
    assert got["serve.ssm_share"] == pytest.approx(100 * 3.75 / busy)
    # route, experts (scope and kernel), shared, in both programs
    assert got["serve.moe_share"] == pytest.approx(100 * 2.25 / busy)
    assert got["serve.moe_experts_touched"] == pytest.approx(
        100 * 550 / (128 * 5))
    hbm = 819e9
    state = 2 * (40 + 60) * 5 * 4_255_744 / hbm
    assert got["serve.ssm_state_roofline"] == pytest.approx(
        100 * state / 2.0)
    # the decode program's experts alone: the prefill's second is not read
    experts = (1100 * 2 * 1024 * 2688 * 2 + 2600 * 2 * 1024 * 2) / hbm
    assert 2600 * 4 * 1024 * 2688 / 197e12 < experts
    assert got["serve.moe_experts_roofline"] == pytest.approx(
        100 * experts / 0.75)
    step = sum(count.decode_step_bytes(
        CONFIG, live_rows=r, experts_touched=t, held_assignments=a,
        live_positions=p) for r, t, a, p in (
            (40, 500, 1000, 16000), (60, 600, 1600, 24000))) / hbm
    assert got["serve.decode_hbm_roofline"] == pytest.approx(
        100 * step / 8.0)
    said = capsys.readouterr().out
    assert "ds.ssm_step 2.0000" in said and "ds.ssm_conv 0.2500" in said
    assert "50.0 rows alive a step" in said and "memory-bound" in said


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_program_without_the_scopes(
        name, monkeypatch):
    """As on the parent commit, where the benchmark's files are laid over
    a program that lacks what this PR adds: nothing is read, nothing
    raises, and the metric is left out of the line."""
    assert read_all(traced_stretch(scopes=False), monkeypatch)[name] is None
    monkeypatch.setattr(pt, "load", lambda path: None)
    run = SimpleNamespace(xplane=lambda: None, config=CONFIG,
                          traffic=TRAFFIC, peaks=None)
    assert reader(name)(run, {}, None) is None


# ---------------------------------------------------------------------------
# A traced rehearsal of the cell
# ---------------------------------------------------------------------------

def test_a_traced_rehearsal_ends_correct_with_its_metrics(capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", "2147485003",
                         "--seconds", "3", "--trace", "1", "--rehearsal"])
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0, lines
    assert result["compared"]["e_median"]["value"] < 0.088
    assert result["compared"]["compiles_in_replay"]["value"] == 0
    got = result["metrics"]
    wanted = {m["name"]: m for m in metrics_of(BENCH, "per_layer", CELL)}
    assert set(got) <= set(wanted)
    # no device plane on the CPU: what reads the program's counters reads
    assert 0 < got["serve.moe_experts_touched"]["value"] <= 100
    assert 0 < got["serve.kv_read_useful_share"]["value"] <= 100
    assert got["serve.batch_occupancy"]["value"] > 0
    assert "serve.decode_ms_p50" in got and "serve.prefill_ms_p50" in got


# ---------------------------------------------------------------------------
# The step's cost model under the open-loop simulator (PERF.md section 7,
# "how a serving cell is admitted", step 2). ``test_open_loop_simulator.py:
# COSTS`` is a file the benchmark already had, which this PR may not edit:
# the cell's entry lives here and runs that file's own simulator.
# ---------------------------------------------------------------------------

# (a, b, c, prefill) in ms and slots: what ``sweep_rate.py`` fitted on the
# chip on the finished program (my chip run, PR 35, call G): a decode-only
# step 38.44 ms + 0.061 ms a row alive, a step that admits a prompt 48.24 ms
# more
COST, SLOTS = (38.44, 0.061, 0.0, 48.24), 128


def test_the_cells_keys_hold_the_spread_of_p95_under_its_cost_model():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "open_loop_simulator", os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "test_open_loop_simulator.py"))
    sim = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sim)
    # the highest rate that leaves a handful waiting at the window's end
    # (a step of 80 ms takes in most of an arrival: the chip's own sweep
    # left 1 and 1 waiting at 8 and 9/s, then 21 and 59 at 10 and 11/s)
    waiting = {rate: sim.simulate(dict(TRAFFIC, rate_per_s=rate), 1, COST,
                                  SLOTS)["waiting_at_end"]
               for rate in (7.0, 8.0, 9.0, 10.0, 11.0, 12.0)}
    found = max(rate for rate, n in waiting.items() if n <= 5)
    assert 8.0 <= found <= 10.0 and waiting[12.0] > 20, waiting  # chip: 9
    assert TRAFFIC["rate_per_s"] == pytest.approx(0.8 * 9.0)
    spread, p95, rows = sim.spread_of_p95(TRAFFIC, COST, SLOTS)
    bare = sim.spread_of_p95(sim.without_keys(TRAFFIC), COST, SLOTS)
    print(f"{CELL}: simulated knee {found}/s (chip: 9), at "
          f"{TRAFFIC['rate_per_s']}/s itl_ms_p95 {p95:.2f} ms, {rows:.1f} "
          f"rows alive, spread over twelve seeds {spread:.2%} with the "
          f"keys; without them {bare[0]:.2%} (p95 {bare[1]:.2f} ms)")
    assert spread <= 0.025
    assert 50 < rows < 80 and 80 < p95 < 125


@pytest.mark.parametrize("name, by", [
    ("lost_state", "e_far_share"), ("one_slot_state", "e_far_share"),
    ("kv_heads_misordered", "e_median"),
    ("reference_fp8_e4m3", "e_median")])
def test_a_control_of_control_state_ends_not_correct(name, by, capsys):
    """``control.py``'s faults move a row's cache position, which this
    model barely feels (one attention layer, no positional encoding), and
    its control rounds into a float32 copy of the tree that the chip
    cannot hold; ``control_state.py`` has a fault of the key/value pool,
    two of the recurrent state and the control a matrix at a time, and
    the check has to see each, with every request still served."""
    from benchmarks import control_state
    rc = control_state.main(["--plant", name, "--workload", CELL, "--seed",
                             "2147485004", "--seconds", "3", "--trace", "0",
                             "--rehearsal"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is False and result["failed"] == 0
    held = result["compared"]
    assert held[by]["value"] > held[by]["limit"]
    assert not held["compiles_in_window"]["value"]
    assert not held["compiles_in_replay"]["value"]


def test_the_control_rounds_what_the_reference_widens_and_puts_it_back():
    """The reference a precision below holds no second copy of the tree:
    each matrix is rounded where the reference widens it, and the
    reference is its own again afterwards."""
    import jax.numpy as jnp
    import numpy as np
    from benchmarks import control, control_state
    from benchmarks.reference import nemotron_h as reference

    widen = reference.f32
    seen = []

    class Driver:
        @staticmethod
        def check_against_reference(run, params, sample, served, held):
            seen.append(served)
            held["e_median"] = [0.0, 1.0]

    class Family:
        pass

    Family.reference = reference

    def logits(config):
        def fn(params, ids):
            return reference.f32(params["w"])[ids]
        return fn

    Family.reference_logits = staticmethod(logits)

    class Run:
        family, config = Family, {}
        traffic = {"max_total_len": 4}

    control_state.reference_a_precision_below(Driver, "fp8_e4m3")
    w = jnp.asarray(np.random.default_rng(0).normal(size=(8, 16)),
                    jnp.bfloat16)
    sample = [{"prompt": [1], "tokens": [1, 2, 3]}]
    Driver.check_against_reference(Run, {"w": w}, sample, "served", {})
    assert reference.f32 is widen
    assert seen[0] == "served"          # the program's own, judged first
    rounded = np.asarray(control.rounded(w, "fp8_e4m3"))
    got = np.stack(seen[1][0])          # rows [1, 2) of request 0
    np.testing.assert_array_equal(got, rounded[[2]])
    assert np.abs(rounded - np.asarray(w, np.float32)).max() > 0
