"""The benchmark's control flow, rehearsed on the CPU at the tiny sizes
the data files give: every cell runs end to end through ``run.py
--rehearsal``, the last line is the contract's object with no device
number in it, a run without a chip prints no result, and a cell, a
configuration, a traffic mix, a family and a per-layer metric can each be
added as new files without touching one that is there."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import load_json, metrics_of, open_cell  # noqa: E402

BENCH = load_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


RAN = {}       # what a test of this file already ran, for a later one


def rehearse(capsys, cell, trace, seconds="0.3", seed="1", fresh=False):
    key = (cell, trace, seconds, seed)
    if fresh or key not in RAN:
        rc = bench_run.main(["--workload", cell, "--seed", seed,
                             "--seconds", seconds, "--trace", str(trace),
                             "--rehearsal"])
        assert rc == 0
        RAN[key] = capsys.readouterr().out.strip().splitlines()
    return RAN[key], json.loads(RAN[key][-1])


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_rehearses_end_to_end(cell, capsys):
    lines, result = rehearse(capsys, cell, 0,
                             "2" if "serve" in cell else "0.3")
    assert set(result) - {"compared"} == RESULT_KEYS | {"rehearsal"}
    assert result["rehearsal"] is True and result["correct"] is True, lines
    # what correct held, each number beside its limit, comes last
    if "compared" in result:
        assert list(result)[-1] == "compared"
        assert all(set(c) == {"value", "limit"} and c["value"] <= c["limit"]
                   for c in result["compared"].values())
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    wanted = {m["name"]: m for m in metrics_of(BENCH, "end_to_end", cell)}
    assert set(result["metrics"]) == set(wanted)
    for name, metric in result["metrics"].items():
        assert metric["unit"] == wanted[name]["unit"]
        # a CPU timing is never written under a device metric's name
        assert metric["value"] is None
    assert all("REHEARSAL" in l for l in lines if "cell " in l[:80])


def test_a_traced_rehearsal_reports_counters_and_leaves_out_the_device(
        capsys):
    """The serving cell; a traced training cell runs in the last test."""
    cell = "gpt2m-serve-chat"
    lines, result = rehearse(capsys, cell, 1, "2")
    assert result["correct"] is True, lines
    wanted = {m["name"]: m for m in metrics_of(BENCH, "per_layer", cell)}
    got = result["metrics"]
    assert got and set(got) <= set(wanted)
    # no device trace on the CPU: its readers found nothing to read
    assert not [n for n in got if wanted[n]["source"] == "device_trace"]
    assert "busy_s" not in result["device"] and "breakdown" not in result
    counters = {n: v["value"] for n, v in got.items()
                if wanted[n]["source"] == "program_counter"}
    assert 0 < counters["serve.batch_occupancy"] <= 100
    assert all(v["value"] is None for n, v in got.items()
               if wanted[n]["source"] != "program_counter")
    # compiles in the window are counted in every cell, on an earlier line
    assert any(", 0 of them inside the window" in l for l in lines)


def test_the_serving_rehearsal_is_primed_stratified_and_replayed(capsys):
    """The driver's own path at the rehearsal's sizes: a priming stretch
    that is attempted and not measured, and after the window the sampled
    requests replayed with the decode's logits captured, compiling
    nothing and emitting the window's tokens again."""
    cell = "gpt2m-serve-chat"
    lines, result = rehearse(capsys, cell, 1, "2")
    _, _, _, traffic = open_cell(cell, rehearsal=True)
    assert traffic["prime_seconds"] and traffic["stratum_seconds"]
    in_window = round(traffic["rate_per_s"] * 2)
    primed = round(traffic["rate_per_s"] * traffic["prime_seconds"])
    assert result["attempted"] == in_window + primed
    assert any(l.startswith(f"window: {in_window} requests due in 2s")
               and f"({in_window + primed} attempted with it)" in l
               for l in lines)
    compared = result["compared"]
    assert list(compared) == [
        "failed", "compiles_in_window", "replay_requests_that_differ",
        "compiles_in_replay", "e_median", "e_far_share", "gap_far_share",
        "first_gap_far_share"]
    assert compared["replay_requests_that_differ"]["value"] == 0
    assert compared["compiles_in_replay"]["value"] == 0
    replay = next(l for l in lines if l.startswith("replay of "))
    assert f"replay of {traffic['check_requests']} sampled" in replay
    assert 0 < compared["e_median"]["value"] < compared["e_median"]["limit"]


def test_the_same_seed_reproduces_the_losses_and_another_does_not(capsys):
    pick = lambda lines: next(l for l in lines
                              if l.startswith("warm-up losses"))
    cell = "bert-large-train-s128"
    a = pick(rehearse(capsys, cell, 0)[0])
    b = pick(rehearse(capsys, cell, 0, fresh=True)[0])
    c = pick(rehearse(capsys, cell, 0, seed="2")[0])
    assert a == b and a != c


@pytest.mark.parametrize("cell", [c for c in CELLS if "train" in c])
def test_a_training_cell_is_held_to_the_reference_after_the_window(
        cell, capsys):
    """``correct`` rests on what ``train_batch()`` returned, and the
    reference is no part of set-up: its line follows the window's."""
    lines, result = rehearse(capsys, cell, 0)
    at = lambda start: next(i for i, l in enumerate(lines)
                            if l.startswith(start))
    assert at("window:") < at("reference check: step 1 loss engine")
    assert not [l for l in lines if "set-up phases" in l
                and "reference" in l]
    assert result["correct"] is True


def test_without_a_chip_the_command_prints_no_result(capsys):
    """The driver's command, as it is: on this CPU it must fail."""
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                        "1", "--trace", "0"])
    assert e.value.code not in (0, None) and "needs a TPU" in str(e.value)
    assert "{" not in capsys.readouterr().out


def test_an_unknown_cell_is_refused(capsys):
    with pytest.raises(KeyError, match="no-such-cell"):
        bench_run.main(["--workload", "no-such-cell", "--seed", "1",
                        "--seconds", "1", "--trace", "0", "--rehearsal"])


# ---------------------------------------------------------------------------
# Driven by data: add one of each in a copy, edit nothing that is there
# ---------------------------------------------------------------------------

def tree_digest(top):
    out = {}
    for base, dirs, files in os.walk(top):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def run_copy(copy, *args, pythonpath=ROOT):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    return subprocess.run(
        [sys.executable, os.path.join(copy, "benchmarks", "run.py"), *args],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600)


@pytest.fixture()
def copy(tmp_path):
    """A directory that holds only ``BENCHMARK.json`` and ``paths``."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return str(tmp_path)


def test_a_bare_checkout_of_the_benchmark_prints_no_result(copy):
    """Only ``BENCHMARK.json`` and the files under ``paths``: the system
    under test is missing, so the command fails and says nothing."""
    done = run_copy(copy, "--workload", CELLS[0], "--seed", "1",
                    "--seconds", "1", "--trace", "0", pythonpath=None)
    assert done.returncode != 0
    assert "{" not in done.stdout


def test_one_of_each_is_added_as_files_and_entries(copy):
    before = tree_digest(os.path.join(copy, "benchmarks"))
    here = os.path.join(copy, "benchmarks")
    write = lambda rel, text: (
        os.makedirs(os.path.dirname(os.path.join(here, rel)), exist_ok=True),
        open(os.path.join(here, rel), "w").write(text))

    config = load_json(here, "configs", "gpt2-medium.json")
    config.update(name="toy", family="toy")
    write("configs/toy.json", json.dumps(config))
    traffic = load_json(here, "traffic", "train-s1024.json")
    traffic["rehearsal"]["gradient_accumulation_steps"] = 1
    write("traffic/toy-steps.json", json.dumps(traffic))
    write("families/toy.py",
          "from benchmarks.families.gpt2 import *  # noqa: F401,F403\n"
          "TOY = True\n")
    write("layer_metrics/toy/answer.py",
          "def read(run, observed, reduced):\n"
          "    assert run.family.TOY and run.cell['name'] == 'toy-cell'\n"
          "    return 42 + observed['compiles_in_window']\n")
    write("layer_metrics/toy/silent.py",
          "def read(run, observed, reduced):\n    return None\n")

    bench = load_json(copy, "BENCHMARK.json")
    bench["configs"].append({"name": "toy", "source": config["source"],
                             "file": "benchmarks/configs/toy.json",
                             "reduced": [], "why": "a throw-away"})
    bench["workloads"].append({"name": "toy-cell", "config": "toy",
                               "traffic": "toy-steps", "chips": 1,
                               "why": "a throw-away"})
    for m in bench["end_to_end"]:
        if m["name"] == "tokens_per_s_per_chip":
            m["workloads"].append("toy-cell")
    for name in ("toy.answer", "toy.silent"):
        bench["per_layer"].append(
            {"name": name, "unit": "count", "better": "higher",
             "source": "program_counter", "layer": "toy",
             "moves": "tokens_per_s_per_chip", "workloads": ["toy-cell"]})
    with open(os.path.join(copy, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    done = run_copy(copy, "--workload", "toy-cell", "--seed", "3",
                    "--seconds", "0.3", "--trace", "1", "--rehearsal")
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    # the new reader ran, the silent one was left out, and the readers
    # that were there do not report in a cell that does not list them
    assert result["metrics"] == {"toy.answer": {"value": 42,
                                                "unit": "count"}}
    after = tree_digest(here)
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/toy.json", "families/toy.py",
        "layer_metrics/toy/answer.py", "layer_metrics/toy/silent.py",
        "traffic/toy-steps.json"]


PARKED = {
    "config": {"name": "gpt2-xl",
               "source": "https://huggingface.co/openai-community/gpt2-xl/"
                         "blob/main/config.json",
               "file": "benchmarks/configs/gpt2-xl.json",
               "reduced": ["n_layer"], "why": "parked (PERF.md, section 7)"},
    "workload": {"name": "gpt2xl-z3-s1024-4chip", "config": "gpt2-xl",
                 "traffic": "train-s1024-z3", "chips": 4,
                 "why": "parked (PERF.md, section 7)"},
    "per_layer": ["comm.collective_share", "comm.exposed_share"],
}


def test_the_parked_four_chip_cell_comes_back_as_entries(copy):
    """``gpt2xl-z3-s1024-4chip`` is not a cell of ``BENCHMARK.json`` until
    a benchmark PR has proven it on the chip (PERF.md, Open questions).
    Its configuration, traffic and readers are kept: adding the entries
    is all it takes, and the rehearsal runs it over four devices."""
    assert PARKED["workload"]["name"] not in CELLS
    before = tree_digest(os.path.join(copy, "benchmarks"))
    bench = load_json(copy, "BENCHMARK.json")
    bench["configs"].append(PARKED["config"])
    bench["workloads"].append(PARKED["workload"])
    cell = PARKED["workload"]["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "gpt2m-train-s1024" in m["workloads"]:
            m["workloads"].append(cell)
    for name in PARKED["per_layer"]:
        bench["per_layer"].append(
            {"name": name, "unit": "%", "better": "lower",
             "source": "device_trace", "layer": "communication",
             "moves": "tokens_per_s_per_chip", "workloads": [cell]})
    with open(os.path.join(copy, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    done = run_copy(copy, "--workload", cell, "--seed", "1", "--seconds",
                    "0.3", "--trace", "1", "--rehearsal")
    assert done.returncode == 0, done.stderr[-2000:]
    assert "on 4 of " in done.stdout
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["device"]["count"] >= 4
    # the CPU has no device trace: the comm readers found nothing to read
    got = result["metrics"]
    assert got["setup.compiles_in_window"]["value"] == 0
    assert not [n for n in got if n.startswith("comm.")]
    assert tree_digest(os.path.join(copy, "benchmarks")) == before
