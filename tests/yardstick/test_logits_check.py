"""What decides ``correct`` in a serving cell
(``drivers/serve_open_loop.py``): a median and three shares of far
positions over logits that no single position can decide; the control
that has to fail them (the reference a precision below in the program's
place, ``benchmarks/control.py``) and the program's own int8 path at a
size the CPU holds; and the rest of a run with each of ``control.py``'s
faults planted in the timed path underneath. On the chip the same plants
are run by hand at the cell's own size (PERF.md section 2 has the
readings the limits stand between); the benchmark's own runs never run
them."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import control  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import load_json, load_module  # noqa: E402

DRIVER = load_module("drivers", "serve_open_loop")
CELL = "gpt2m-serve-chat"
ARGS = ["--workload", CELL, "--seed", "2147483801", "--seconds", "2",
        "--trace", "0", "--rehearsal"]


def passes(stats):
    return all(value <= limit for value, limit in stats.values())


def test_a_flip_at_one_position_in_a_thousand_decides_nothing():
    """Reference logits with two of a thousand positions moved by 0.3 of a
    logit (half a standard deviation: a near-tie that flipped) pass; the
    same at 1% of the positions (one position a request of a hundred
    tokens: what a fault at the boundary of prefill and decode touches),
    or every position off by what a lower precision adds, do not."""
    rng = np.random.default_rng(0)
    spread = 0.6
    honest = rng.uniform(0.02, 0.03, 1000) / spread
    zeros = np.zeros(1000)
    assert passes(DRIVER.logit_statistics(honest, zeros, zeros[:32]))
    flipped = honest.copy()
    flipped[rng.choice(1000, 2, replace=False)] += 0.3 / spread
    stats = DRIVER.logit_statistics(flipped, zeros, zeros[:32])
    assert passes(stats) and stats["e_far_share"][0] == 0.002
    flipped = honest.copy()
    flipped[rng.choice(1000, 10, replace=False)] += 0.3 / spread
    stats = DRIVER.logit_statistics(flipped, zeros, zeros[:32])
    assert not passes(stats)
    assert stats["e_median"][0] <= stats["e_median"][1]     # not by this
    assert not passes(DRIVER.logit_statistics(2.5 * honest, zeros,
                                              zeros[:32]))
    # a token that is not the logits' choice, at 1% of the positions
    gap = zeros.copy()
    gap[:10] = 2.0
    stats = DRIVER.logit_statistics(honest, gap, zeros[:32])
    assert not passes(stats) and stats["gap_far_share"][0] == 0.01
    # one first token of 32 that is not the prefill's choice
    first = zeros[:32].copy()
    first[5] = 2.0
    assert not passes(DRIVER.logit_statistics(honest, zeros, first))


def test_the_limits_are_the_drivers_and_no_data_file_names_them():
    assert (DRIVER.E_MEDIAN_TOL, DRIVER.E_FAR, DRIVER.FAR_SHARE_TOL) == (
        0.088, 0.25, 0.002)
    bench = load_json(ROOT, "BENCHMARK.json")
    for cell in bench["workloads"]:
        traffic = load_json(ROOT, "benchmarks", "traffic",
                            cell["traffic"] + ".json")
        text = json.dumps(traffic).lower()
        assert "tol" not in text and "e_far" not in text


@pytest.mark.parametrize("scheme", list(control.SCHEMES))
def test_the_controls_rounding_is_the_formats(scheme):
    """``control.rounded`` against a cast by ``ml_dtypes`` (the float8s)
    and against ``round`` (the int8s), on the CPU, where a cast rounds: on
    the TPU a float8 cast there and back rounded nothing (PR 34, call H),
    so the control rounds by arithmetic on the exponent."""
    import jax.numpy as jnp
    import ml_dtypes
    rng = np.random.default_rng(0)
    w = (rng.standard_normal((256, 96)) * 0.02).astype(np.float32)
    w[0, 0], w[1, 1] = 0.0, 1e-7
    got = control.rounded({"kernel": jnp.asarray(w), "bias": jnp.ones(3)},
                          scheme)
    assert np.array_equal(got["bias"], np.ones(3))      # no matrix: as is
    top = control.SCHEMES[scheme][0]
    scale = np.abs(w).max(0 if scheme == "int8_column" else None,
                          keepdims=True) / np.float32(top)
    kind = {"fp8_e4m3": ml_dtypes.float8_e4m3fn,
            "fp8_e5m2": ml_dtypes.float8_e5m2}.get(scheme)
    want = ((w / scale).astype(kind).astype(np.float32) if kind
            else np.round(w / scale)) * scale
    step = np.abs(want - w).max()
    assert 0 < step < 0.3 * np.abs(w).max()
    assert np.abs(np.asarray(got["kernel"]) - want).max() < 1e-4 * step


def result_of(capsys, main):
    assert main(ARGS) == 0
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out


def test_the_controls_in_a_copy_as_deep_as_the_cell(tmp_path):
    """A copy whose rehearsal is as DEEP as the cell (24 layers, 64 wide:
    the error a precision adds grows with depth), each run a process of
    its own as on the chip: the honest run is correct; the control (the
    reference a precision below, in the program's place) serves nothing
    and fails by the median of ``e``; the program's own int8 path, the
    mildest step down, serves every request and reads twice the honest
    run's median (whether that is over the limit is the chip's to say:
    the limits are set from its readings, PERF.md section 2)."""
    import shutil
    import subprocess
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = tmp_path / "benchmarks" / "configs" / "gpt2-medium.json"
    config = json.loads(path.read_text())
    config["rehearsal"]["n_layer"] = config["n_layer"]
    path.write_text(json.dumps(config))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)

    def run(script, *plant):
        done = subprocess.run(
            [sys.executable, str(tmp_path / "benchmarks" / script), *plant,
             *ARGS],
            cwd=tmp_path, env=env, capture_output=True, text=True,
            timeout=900)
        assert done.returncode == 0, done.stderr[-2000:]
        return json.loads(done.stdout.strip().splitlines()[-1]), done

    honest, _ = run("run.py")
    assert honest["correct"] is True, honest["compared"]
    control_, done = run("control.py", "--plant",
                         "reference_" + control.CONTROL)
    assert control_["correct"] is False and control_["failed"] == 0
    over = [k for k, c in control_["compared"].items()
            if not c["value"] <= c["limit"]]
    assert "e_median" in over
    assert control_["compared"]["e_median"]["value"] > \
        3 * honest["compared"]["e_median"]["value"]
    # each number compared beside its limit: the last lines of stderr
    last = done.stderr.strip().splitlines()[-len(control_["compared"]):]
    assert all(l.startswith("compared ") and "(limit " in l for l in last)
    int8, _ = run("control.py", "--plant", "int8_path")
    assert int8["failed"] == 0
    assert int8["compared"]["e_median"]["value"] > \
        2 * honest["compared"]["e_median"]["value"]
    print({k: [r["compared"][n]["value"] for n in ("e_median",
                                                   "e_far_share")]
           for k, r in (("honest", honest), ("control", control_),
                        ("int8_path", int8))})


@pytest.mark.parametrize("fault,caught_by", [
    ("altered_token", "gap_far_share"),
    ("prefill_token", "first_gap_far_share"),
    ("replay_differs", "replay_requests_that_differ"),
    ("misplaced", "e_far_share"),
    ("boundary", "e_far_share"),
    ("one_slot", "e_far_share"),
])
def test_a_broken_timed_path_ends_not_correct(fault, caught_by, capsys):
    """The rest of a run (``--rehearsal`` skips the look for a chip) with
    one of ``control.py``'s faults planted in the engine underneath: every
    request still finishes with the tokens it asked for, and ``correct``
    comes out false by the number that is there to catch the fault."""
    result, _ = result_of(
        capsys, lambda args: control.main(["--plant", fault, *args]))
    assert result["failed"] == 0 and result["correct"] is False
    c = result["compared"][caught_by]
    assert not c["value"] <= c["limit"]
    from deepspeed_tpu.serving.engine import ServeEngine
    assert ServeEngine._decode.__module__.endswith("serving.engine")


def test_the_reference_a_precision_below_ends_not_correct(capsys):
    """The contract's control at a size the CPU holds: the reference in
    the program's place with every matrix rounded to ``control.CONTROL``
    fails by the median of ``e``, and the ladder prints every scheme."""
    result, out = result_of(
        capsys, lambda args: control.main(["--plant", "reference_ladder",
                                           *args]))
    assert result["correct"] is False and result["failed"] == 0
    c = result["compared"]["e_median"]
    assert not c["value"] <= c["limit"]
    told = [l for l in out.out.splitlines()
            if l.startswith("CONTROL reference at ")]
    assert [l.split()[3].rstrip(":") for l in told] == [
        s for s in control.SCHEMES if s != control.CONTROL] \
        + [control.CONTROL]
