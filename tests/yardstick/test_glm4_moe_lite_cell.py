"""The cell ``glm47flash-train-s4096`` as the benchmark has it: the
configuration against the published ``config.json``, the cut against the
issue's table, the required-FLOPs count against a hand count, the six
per-layer readers on a hand-made trace (and on one of a program that lacks
the scopes, as the parent does), the ``BENCHMARK.json`` entries against
their files, and a traced rehearsal on the CPU.

The six readers WAIT for their ``BENCHMARK.json`` entries: a pin of
``test_program_trace.py`` holds ``per_layer``'s last twelve names, and the
builder's contract takes an entry put before them as a change to what was
there, so only a ``benchmark`` PR can list them (PERF.md, section 7).
Until then they are run by hand (``benchmarks/moe_trace.py``)."""

import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import flops, flops_glm4_moe_lite as count  # noqa: E402
from benchmarks import moe_trace as mt  # noqa: E402
from benchmarks import program_trace as pt, trace_reduce  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import (find, load_json, load_module,  # noqa: E402
                                metrics_of, open_cell)
from benchmarks.program_trace import DeviceOp, ProgramTrace, Span  # noqa: E402

CELL = "glm47flash-train-s4096"
BENCH = load_json(ROOT, "BENCHMARK.json")
CONFIG = load_json(ROOT, "benchmarks", "configs", "glm-4.7-flash.json")
TRAFFIC = load_json(ROOT, "benchmarks", "traffic", "train-s4096.json")
NEW = ["moe.device_share", "moe.dispatch_share", "moe.experts_roofline",
       "moe.held_load_max_over_mean", "attn.mla_share", "train.mtp_share"]
APPENDED_TO = ["setup.compiles_in_window", "train.mfu",
               "train.dispatch_ms_p50", "model.matmul_share",
               "kernel.mosaic_share", "device.idle_share",
               "device.peak_hbm_gib"]

# https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json, the
# keys that say something of the language model's shape
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 8,
           "vocab_size": 19360}


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
        assert key not in CONFIG["reduced"]
        assert CONFIG[key] == PUBLISHED[key]


def test_the_cut_keeps_to_the_guides_floors_and_says_what_it_stands_for():
    assert sorted(CONFIG["reduced"]) == sorted(REDUCED)
    entry = find(BENCH["configs"], "glm-4.7-flash", "config")
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    # a whole period and four of the layers after the leading dense one,
    # eight routed experts, an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] - CONFIG["first_k_dense_replace"] >= 4
    assert CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert "eight chips share each layer" in CONFIG["deployment"]
    for name in ("mtp_loss_weight", "correction_bias", "layout", "weights",
                 "embedding_init_std", "dropout", "router"):
        assert name in CONFIG["assumed"]
    # gpt2-medium's engine as it is: ZeRO-2, Adam at 1e-4, bf16, bf16
    # accumulators
    theirs = load_json(ROOT, "benchmarks", "configs",
                       "gpt2-medium.json")["train_engine"]
    assert CONFIG["train_engine"] == theirs
    assert theirs["optimizer"]["params"]["lr"] == 1e-4


def test_the_wide_embedding_is_the_cells_and_not_the_programs():
    """The cell starts the embedding's rows at ``embedding_init_std``; the
    program's model knows one initialiser, the family's 0.02."""
    import dataclasses
    import jax
    import numpy as np
    from deepspeed_tpu.models import Glm4MoeLiteConfig, make_glm4_moe_lite
    assert not [f.name for f in dataclasses.fields(Glm4MoeLiteConfig)
                if "init" in f.name or "std" in f.name]
    family = load_module("families", CONFIG["family"])
    _, _, config, _ = open_cell(CELL, rehearsal=True)
    model, cfg = family.build_model(config)
    rngs, batch = {"params": jax.random.PRNGKey(5)}, family.example_batch()
    mine = model.init(rngs, batch)["params"]
    plain = make_glm4_moe_lite(cfg)[0].init(rngs, batch)["params"]
    scale = CONFIG["assumed"]["embedding_init_std"] / 0.02
    np.testing.assert_allclose(mine.pop("embed_tokens"),
                               plain.pop("embed_tokens") * scale, rtol=1e-6)
    jax.tree_util.tree_map(np.testing.assert_array_equal, mine, plain)


def test_the_parameters_held_are_the_issues_706_million():
    import jax
    import numpy as np
    family = load_module("families", CONFIG["family"])
    model, cfg = family.build_model(CONFIG)
    assert (cfg.n_routed_experts, cfg.n_held_experts) == (64, 8)
    shapes = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, family.example_batch())["params"])
    size = lambda tree: sum(int(np.prod(x.shape))
                            for x in jax.tree_util.tree_leaves(tree))
    assert size(shapes) == pytest.approx(706.5e6, rel=0.01)
    assert size(shapes["layers_0"]) == pytest.approx(84.68e6, rel=1e-3)
    assert size(shapes["layers_1"]) == pytest.approx(106.83e6, rel=1e-3)
    assert size(shapes["layers_1"]["self_attn"]) == pytest.approx(
        21.76e6, rel=1e-3)
    assert size(shapes["layers_1"]["mlp"]["router"]) == 2048 * 64
    assert shapes["lm_head"].shape == (19360, 2048)
    # 16 B a parameter: two thirds of the chip
    assert 0.6 < size(shapes) * 16 / (15.75 * 2 ** 30) < 0.7


def test_the_traffic_is_the_issues():
    want = {"driver": "train_steps", "seq_len": 4096,
            "micro_batch_per_chip": 1, "gradient_accumulation_steps": 8,
            "token_zipf_exponent": 1.0, "pool_batches": 8,
            "warmup_steps": 3, "reference_chunk_sequences": 1,
            # the issue's 2, and 2 more: a step's counters reach the
            # trace two steps later, and the experts' roofline reads the
            # steps that the window holds both ways
            "trace_steps": 4}
    assert {k: TRAFFIC[k] for k in want} == want
    cell = find(BENCH["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-4.7-flash", "train-s4096", 1)
    assert "1/8" in cell["why"] and "attention" in cell["why"]


# ---------------------------------------------------------------------------
# Required operations against a hand count
# ---------------------------------------------------------------------------

HEAD = 2 * 2048 * 19360
MLA = 2 * (2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048)
ATTENTION = 4 * 5120 * 4097 / 2
EXPERT = 2 * 3 * 2048 * 1536


@pytest.mark.parametrize("part,got,want", [
    ("mla projections", count.mla_projection_flops_per_token(CONFIG), MLA),
    ("held assignments a token", count.held_assignments_per_token(CONFIG),
     0.5),
    ("dense layer",
     count.forward_flops_per_token(
         {**CONFIG, "num_hidden_layers": 1, "num_nextn_predict_layers": 0},
         4096) - HEAD,
     MLA + ATTENTION + 2 * 3 * 2048 * 10240),
    ("expert layer",
     count.forward_flops_per_token(
         {**CONFIG, "num_hidden_layers": 2, "num_nextn_predict_layers": 0},
         4096)
     - count.forward_flops_per_token(
         {**CONFIG, "num_hidden_layers": 1, "num_nextn_predict_layers": 0},
         4096),
     MLA + ATTENTION + 2 * 2048 * 64 + 1.5 * EXPERT),
    ("mtp module",
     count.forward_flops_per_token(CONFIG, 4096)
     - count.forward_flops_per_token(
         {**CONFIG, "num_nextn_predict_layers": 0}, 4096),
     2 * 4096 * 2048 + MLA + ATTENTION + 2 * 2048 * 64 + 1.5 * EXPERT + HEAD),
    ("the whole forward pass", count.forward_flops_per_token(CONFIG, 4096),
     0.957e9)])
def test_required_flops_match_a_hand_count(part, got, want):
    assert got == pytest.approx(want, rel=1e-3), part


def test_the_family_hands_the_flash_roofline_its_width_and_six_layers():
    family = load_module("families", CONFIG["family"])
    assert family.hidden_layers_heads(CONFIG) == (5120, 6, 20)
    assert family.expert_layers(CONFIG) == 5 and family.CAUSAL
    assert family.forward_flops_per_token(CONFIG, TRAFFIC) == \
        count.forward_flops_per_token(CONFIG, 4096)
    # a step of 32,768 tokens is 94 TFLOP: 0.48 s at the v5e's peak
    step = 3 * count.forward_flops_per_token(CONFIG, 4096) * 32768
    assert step / 197e12 == pytest.approx(0.48, abs=0.005)


def test_the_experts_roofline_arithmetic():
    shape = dict(hidden=2048, intermediate=1536)
    peaks = flops.load_peaks("TPU v5 lite")

    def least(rows):
        ops = count.experts_train_flops(rows=rows, **shape)
        assert ops == 3 * rows * EXPERT
        nbytes = count.experts_train_bytes(rows=rows, held=8, passes=1,
                                           **shape)
        assert nbytes == (9 * 8 * 2048 * 1536 * 2
                          + rows * (5 * 2048 + 4 * 1536) * 2)
        return flops.roofline_seconds(ops, nbytes, peaks), ops, nbytes

    # the deployment's 2,048 rows an expert: the matmuls set the least time
    (seconds, bound), ops, _ = least(8 * 2048.0)
    assert bound == "compute" and seconds == pytest.approx(ops / 197e12)
    # this cell's 256 rows an expert: moving the weights does
    (seconds, bound), _, nbytes = least(8 * 256.0)
    assert bound == "memory" and seconds == pytest.approx(nbytes / 819e9)


# ---------------------------------------------------------------------------
# BENCHMARK.json: the entries this cell adds
# ---------------------------------------------------------------------------

def test_the_cell_is_appended_to_the_metrics_it_reports():
    e2e = find(BENCH["end_to_end"], "tokens_per_s_per_chip", "metric")
    assert e2e["workloads"][-1] == CELL
    for name in APPENDED_TO:
        assert find(BENCH["per_layer"], name, "metric")["workloads"][-1] \
            == CELL, name
    reported = [m["name"] for m in metrics_of(BENCH, "per_layer", CELL)]
    assert reported == APPENDED_TO + NEW
    # kernel.flash_roofline divides by ALL Mosaic time, and in this cell
    # XLA's grouped-matmul kernel is Mosaic time too: the cell stays off
    # it until its reader takes the flash kernels by name
    assert CELL not in find(BENCH["per_layer"], "kernel.flash_roofline",
                            "metric")["workloads"]


@pytest.mark.parametrize("name", NEW)
def test_a_reader_has_its_entry(name):
    """Listed since PR 34 (the pins that kept them out are rules now,
    ``test_program_trace.py``): the reader is a file the harness loads,
    the entry names this cell alone, and ``moe_trace.py`` still runs the
    six by hand on a capture."""
    assert callable(reader(name))
    assert name in mt.READERS
    entry = find(BENCH["per_layer"], name, "metric")
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "tokens_per_s_per_chip"
    assert entry["layer"] == ("experts" if name.startswith("moe.")
                              else "model")
    assert entry["source"] == ("program_counter" if name ==
                               "moe.held_load_max_over_mean"
                               else "device_trace")


# ---------------------------------------------------------------------------
# The readers on a hand-made traced step
# ---------------------------------------------------------------------------

def op(scope, start, end, name="fusion.1"):
    return DeviceOp(name, start, end, scope, kernel=pt.kernel_of(scope))


BODY = "jit(train_step)/while/body/"
FWD, BWD = BODY + "jvp(Glm4MoeLite)/", BODY + "transpose(jvp(Glm4MoeLite))/"


LAUNCHES = {0: [(0.0, 8.0), (8.0, 10.0)]}     # the line ``XLA Modules``


def traced_step(counters=True, scopes=True):
    """Ten seconds of one device, the launches of steps 9 and 10, and the
    counters of steps 7, 8 and 9; ``scopes=False`` is a program that
    names none of this PR's scopes (the parent's)."""
    ds = (lambda s: s) if scopes else (lambda s: "h")
    ops = [
        op(FWD + f"layers_1/{ds('ds.mla')}/self_attn/q_b_proj/dot", 0.0, 1.0),
        op(FWD + f"layers_1/{ds('ds.mla')}/self_attn/flash_fwd/pallas_call",
           1.0, 1.5),
        op(FWD + f"layers_1/mlp/{ds('ds.moe_route')}/top_k", 1.5, 1.75),
        op(FWD + f"layers_1/mlp/{ds('ds.moe_dispatch')}/gather", 1.75, 2.0),
        op(FWD + f"layers_1/mlp/{ds('ds.moe_experts')}/mul", 2.0, 2.25),
        op(FWD + f"layers_1/mlp/{ds('ds.moe_shared')}/shared_up/dot",
           2.75, 3.0),
        op(BWD + f"layers_1/mlp/{ds('ds.moe_combine')}/gather", 3.0, 3.5),
        op(BWD + f"{ds('ds.mtp')}/mtp_block/{ds('ds.mla')}/o_proj/dot",
           3.5, 4.0),
        op(BWD + f"{ds('ds.mtp')}/mtp_eh_proj/dot", 4.0, 5.0),
        op(BODY + "ds.accumulate/add", 5.0, 6.0),
        op("jit(train_step)/ds.optimizer/mul", 6.0, 8.0),
        # step 10's launch: its experts are not step 9's
        op(FWD + f"layers_1/mlp/{ds('ds.moe_experts')}/mul", 8.0, 8.5),
        op("jit(train_step)/ds.optimizer/mul", 8.5, 10.0)]
    if scopes:      # the kernel exists only where the layer does
        ops.insert(5, op("ragged-dot-none", 2.25, 2.75,
                         name="ragged-dot-none.7"))
    stats = [{"of_step": 7, "moe_held_assignments_per_token": 0.5,
              "moe_held_rows_max": 300.0, "moe_held_rows_mean": 250.0,
              "moe_no_held_expert_share": 0.6},
             {"of_step": 8, "moe_held_assignments_per_token": 0.75,
              "moe_held_rows_max": 450.0, "moe_held_rows_mean": 350.0,
              "moe_no_held_expert_share": 0.5},
             {"of_step": 9, "moe_held_assignments_per_token": 1.0,
              "moe_held_rows_max": 600.0, "moe_held_rows_mean": 300.0,
              "moe_no_held_expert_share": 0.4}]
    line = ("/host:CPU", "main#1")
    spans = [Span("train_step", 0.0, 0.1, {"step": 9}, line),
             Span("train_step", 4.0, 4.1, {"step": 10}, line)]
    if counters:
        spans += [Span("step_counters", 1.0 + 3 * i, 1.1 + 3 * i, s, line)
                  for i, s in enumerate(stats)]
        # the same step told twice counts once
        spans.append(Span("step_counters", 9.0, 9.1, stats[1], line))
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
    family = load_module("families", CONFIG["family"])
    run = SimpleNamespace(
        xplane=lambda: "x", config=CONFIG, family=family,
        traffic={**TRAFFIC, "trace_steps": 1, "seq_len": 4096,
                 "gradient_accumulation_steps": 1},
        peaks=flops.load_peaks("TPU v5 lite"))
    monkeypatch.setattr(pt, "load", lambda path: trace)
    monkeypatch.setattr(mt, "launches", lambda path: LAUNCHES)
    red = reduced_of(trace) if reduced else None
    return {name: reader(name)(run, {}, red) for name in NEW}


def test_the_six_readers_on_a_hand_made_step(monkeypatch, capsys):
    got = read_all(traced_step(), monkeypatch)
    busy = 10.0
    assert got["moe.device_share"] == pytest.approx(100 * 2.5 / busy)
    assert got["moe.dispatch_share"] == pytest.approx(100 * 1.0 / busy)
    assert got["attn.mla_share"] == pytest.approx(100 * 2.0 / busy)
    assert got["train.mtp_share"] == pytest.approx(100 * 1.5 / busy)
    assert got["moe.held_load_max_over_mean"] == pytest.approx(1350 / 900)
    # Step 9 alone ran in the window AND has its counters there: its 1.0
    # assignments a token over 4096 tokens and 5 layer passes, against
    # the expert ops of ITS launch (not step 10's, not the mean of 7-9)
    rows = 1.0 * 4096 * 5
    shape = dict(hidden=2048, intermediate=1536)
    least, _ = flops.roofline_seconds(
        count.experts_train_flops(rows=rows, **shape),
        count.experts_train_bytes(rows=rows, held=8, passes=5, **shape),
        flops.load_peaks("TPU v5 lite"))
    assert got["moe.experts_roofline"] == pytest.approx(
        100 * least / 0.75)         # the mul and the grouped matmul
    said = capsys.readouterr().out
    assert "ds.moe_experts (grouped matmul) 0.5000" in said
    assert "steps [9]: 20480 held assignments (1.0000 a token) over 5 " \
        "layer passes" in said
    assert "of_step 7" in said and "of_step 9" in said


@pytest.mark.parametrize("what", ["no counted step ran in the window",
                                  "launches and dispatches disagree"])
def test_the_roofline_reads_nothing_it_cannot_match(what, monkeypatch):
    trace = traced_step()
    if what.startswith("no counted"):
        trace.spans = [s for s in trace.spans
                       if s.stats.get("of_step") != 9]
    else:
        trace.spans = [s for s in trace.spans if s.stats.get("step") != 10]
    pt.link_spans(trace.spans)
    got = read_all(trace, monkeypatch)
    assert got["moe.experts_roofline"] is None
    assert got["moe.device_share"] == pytest.approx(25.0)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_program_without_the_scopes(
        name, monkeypatch):
    """As at the parent, which the driver runs with these files: the
    reader returns ``None`` and does not raise; the line leaves the metric
    out."""
    parent = traced_step(counters=False, scopes=False)
    assert read_all(parent, monkeypatch)[name] is None
    assert read_all(parent, monkeypatch, reduced=False)[name] is None
    monkeypatch.setattr(pt, "load", lambda path: None)
    run = SimpleNamespace(xplane=lambda: None, peaks=None)
    assert reader(name)(run, {}, None) is None


def test_counters_alone_give_the_load_and_no_device_share(monkeypatch):
    """A rehearsal: spans on the host plane, no device plane."""
    trace = traced_step()
    trace.devices = {}
    got = read_all(trace, monkeypatch, reduced=False)
    assert got.pop("moe.held_load_max_over_mean") == pytest.approx(1.5)
    assert set(got.values()) == {None}
    assert [c["of_step"] for c in mt.step_counters(trace)] == [7, 8, 9]


# ---------------------------------------------------------------------------
# A traced rehearsal of the cell
# ---------------------------------------------------------------------------

def test_a_traced_rehearsal_ends_correct_and_reports_the_counter(capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", "3", "--seconds",
                         "0.3", "--trace", "1", "--rehearsal"])
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0, lines
    assert any(l.startswith("reference check: step 1 loss engine")
               for l in lines)
    got = result["metrics"]
    wanted = {m["name"]: m for m in metrics_of(BENCH, "per_layer", CELL)}
    assert set(got) <= set(wanted)
    # no device plane on the CPU: only the counters are read
    assert not [n for n in got if wanted[n]["source"] == "device_trace"]
    assert got["setup.compiles_in_window"]["value"] == 0
    load = got["moe.held_load_max_over_mean"]["value"]
    assert load >= 1.0
    # the same six readers by hand, on the capture that run left. The
    # program emits a step's counters only once the device has finished
    # it and keeps two steps' worth (``_trace_step_counters``), so how
    # many of the traced steps' counters reach the capture follows the
    # machine's load: four on a quiet one (the two steps before the traced
    # ones and the first two of them), fewer or more under ``-n 6``, which
    # is what made this test unsteady in the driver's run of PR 32's tree.
    assert mt.main(["moe_trace.py", CELL, "--rehearsal"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    (told,) = [l for l in lines
               if l.startswith("step counters in the traced window")]
    assert 1 <= told.count("of_step") <= TRAFFIC["trace_steps"] + 2
    by_hand = dict(l.split(" ", 1) for l in lines[-len(mt.READERS):])
    assert list(by_hand) == list(mt.READERS)
    assert float(by_hand.pop("moe.held_load_max_over_mean")) == load
    assert set(by_hand.values()) == {"None"}
    # the rehearsal holds 4 of 8 experts, 2 a token
    _, _, config, _ = open_cell(CELL, rehearsal=True)
    assert count.held_assignments_per_token(config) == 1.0
