"""The entry scripts' control flow, WITHOUT running any model: nothing
hides the device, and nothing turns a failure into success.

- ``bench.py`` / ``chip_smoke.py`` on a machine without a TPU exit
  non-zero naming the platform they found and print no result;
- a bench section fails ONCE, loudly (no retry, no "partial" row), the
  other sections still run, and any failed section makes ``main()`` exit
  non-zero;
- the compile-cache helper leaves JAX's config alone when
  ``JAX_COMPILATION_CACHE_DIR`` is set and otherwise yields the same
  in-checkout directory from every process;
- an unknown device kind has no peak, so no MFU.
"""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _load_bench(tmp_path, monkeypatch):
    spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench"] = mod
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "PARTIAL_PATH", str(tmp_path / "partial.json"))
    return mod


def _run_python(*argv, env=None):
    """``python *argv`` from the repo root on the CPU, with no compile
    cache directory inherited from the caller's environment (``env`` is
    laid over it)."""
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    return subprocess.run([sys.executable, *argv], cwd=REPO,
                          env={**base, "JAX_PLATFORMS": "cpu", **(env or {})},
                          capture_output=True, text=True, timeout=120)


class TestNoChipNoResult:
    @pytest.mark.parametrize("script", ["bench.py", "chip_smoke.py"])
    def test_exits_nonzero_naming_the_platform(self, script):
        proc = _run_python(script)
        assert proc.returncode != 0
        assert "'cpu'" in proc.stderr
        # no result line: nothing that parses as the scripts' JSON record
        assert not any(line.lstrip().startswith("{")
                       for line in proc.stdout.splitlines()), proc.stdout


class TestRunSection:
    def test_success_flushes_partial(self, tmp_path, monkeypatch):
        bench = _load_bench(tmp_path, monkeypatch)
        result = {"value": None}

        def section():
            result["value"] = 42.0

        assert bench.run_section("s", section, result)
        on_disk = json.loads((tmp_path / "partial.json").read_text())
        assert on_disk["value"] == 42.0

    @pytest.mark.parametrize("exc", [
        RuntimeError("UNAVAILABLE: connection reset"),   # once "transient"
        ValueError("shape mismatch (8192, 768) vs (8192, 770)"),
    ])
    def test_failure_is_recorded_once_and_not_retried(self, tmp_path,
                                                      monkeypatch, exc):
        bench = _load_bench(tmp_path, monkeypatch)
        result = {"value": 7.0}
        calls = []

        def dead():
            calls.append(1)
            raise exc

        assert not bench.run_section("dead", dead, result)
        # one multi-minute compile paid, not two; one error on the record
        assert len(calls) == 1 and len(result["errors"]) == 1
        assert "dead" in result["errors"][0]
        # rows of earlier sections survive on disk
        assert json.loads(
            (tmp_path / "partial.json").read_text())["value"] == 7.0

    def test_midwindow_failure_propagates_no_partial_row(self, tmp_path,
                                                         monkeypatch):
        bench = _load_bench(tmp_path, monkeypatch)

        class DiesInSecondWindow:
            calls = 0

            def train_batch(self, batches):
                self.calls += 1
                if self.calls > 3:      # warmup(1) + window 1 (2 steps) ok
                    raise RuntimeError("UNAVAILABLE: connection reset")
                return 0.5

        result = {}

        def section():
            bench.time_train_batches(DiesInSecondWindow(), {}, steps=2,
                                     warmup=1, windows=3)
            bench._section_rows(result, "s", samples_per_sec=1.0)

        assert not bench.run_section("s", section, result)
        assert "sections" not in result       # no row from a broken run

    def test_partial_flush_failure_does_not_kill_section(self, tmp_path,
                                                         monkeypatch):
        bench = _load_bench(tmp_path, monkeypatch)
        monkeypatch.setattr(bench, "PARTIAL_PATH", "/nonexistent-dir/x.json")
        result = {}

        def section():
            result["row"] = 1.0

        assert bench.run_section("s", section, result)


class _FakeTpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"


class TestMainExitCode:
    """main() on a (faked) one-chip TPU with every model-running function
    replaced: the exit code follows the sections, not the row count."""

    def _run_main(self, tmp_path, monkeypatch, capsys, gpt2_fails):
        bench = _load_bench(tmp_path, monkeypatch)
        monkeypatch.setattr(bench, "configure_compile_cache", lambda: "off")
        monkeypatch.setattr(bench.jax, "devices", lambda: [_FakeTpu()])

        def gpt2(steps, warmup, dropout_rate=0.0):
            if gpt2_fails:
                raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
            return 1000.0, 50.0, 990.0, 1e12, 1.0

        monkeypatch.setattr(bench, "bench_bert", lambda **kw: (
            100.0, 50.0, 335e6, 99.0, 1e12, 1.0))
        monkeypatch.setattr(bench, "bench_gpt2", gpt2)
        monkeypatch.setattr(bench, "bench_gpt2_long", lambda **kw: 10.0)
        monkeypatch.setattr(bench, "bench_inference", lambda **kw: 10.0)
        monkeypatch.setattr(bench, "bench_serving", lambda: (1.0,) * 7)
        monkeypatch.setattr(bench, "bench_serving_fastpath", lambda: {
            "decode_step_gather_ms": 1.0, "decode_step_kernel_ms": 1.0,
            "cold_ttft_ms": 1.0, "warm_ttft_p50_ms": 1.0,
            "spec_accept_rate": 1.0, "spec_tokens_per_step": 1.0})
        monkeypatch.setattr(bench, "bench_serving_overload", lambda: {
            "overload_shed_frac_off": 0.0, "overload_shed_frac_on": 0.5,
            "overload_admitted_ttft_p99_off_ms": 1.0,
            "overload_admitted_ttft_p99_on_ms": 1.0})
        monkeypatch.setattr(bench, "bench_serving_chunked", lambda: {
            "mixed_step_bucketed_ms": 1.0, "mixed_step_chunked_ms": 1.0,
            "ttft_p99_bucketed_ms": 1.0, "ttft_p99_chunked_ms": 1.0})
        monkeypatch.setattr(bench, "bench_fused_optimizer", lambda: {
            "optimizer_step_xla_ms": 1.0, "optimizer_step_fused_ms": 1.0})
        code = 0
        try:
            bench.main()
        except SystemExit as e:
            code = e.code
        return code, json.loads(capsys.readouterr().out.splitlines()[-1])

    def test_all_sections_green_exits_zero(self, tmp_path, monkeypatch,
                                           capsys):
        code, result = self._run_main(tmp_path, monkeypatch, capsys, False)
        assert code == 0 and "errors" not in result
        assert result["environment"]["platform"] == "tpu"
        assert result["environment"]["device_kind"] == "TPU v5 lite"

    def test_one_failed_section_exits_nonzero(self, tmp_path, monkeypatch,
                                              capsys):
        code, result = self._run_main(tmp_path, monkeypatch, capsys, True)
        assert code not in (0, None)
        # the surviving rows are still printed, the failures named
        assert result["value"] == 100.0 and "serving" in result["sections"]
        assert [e.split(":")[0] for e in result["errors"]] == [
            "gpt2", "gpt2_dropout"]

    def test_unknown_device_kind_is_an_error(self, tmp_path, monkeypatch):
        bench = _load_bench(tmp_path, monkeypatch)
        monkeypatch.setattr(bench, "configure_compile_cache", lambda: "off")

        class Unknown(_FakeTpu):
            device_kind = "TPU v99"

        monkeypatch.setattr(bench.jax, "devices", lambda: [Unknown()])
        with pytest.raises(SystemExit, match="TPU v99"):
            bench.main()


class TestCompileCachePlacement:
    _SNIPPET = ("import jax; "
                "from deepspeed_tpu.utils.compile_cache import "
                "configure_compile_cache as f; "
                "print(f()); print(jax.config.jax_compilation_cache_dir)")

    def test_unset_gives_the_fixed_in_checkout_path(self):
        from deepspeed_tpu.utils.compile_cache import DEFAULT_CACHE_DIR
        outs = [_run_python("-c", self._SNIPPET).stdout.split()
                for _ in range(2)]
        # same directory from two processes, returned AND configured
        assert outs[0] == outs[1] == [DEFAULT_CACHE_DIR, DEFAULT_CACHE_DIR]
        assert DEFAULT_CACHE_DIR == str(REPO / ".jax_cache")

    def test_env_var_set_leaves_config_untouched(self, tmp_path,
                                                 monkeypatch):
        import jax

        from deepspeed_tpu.utils import compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: updates.append(a))
        assert compile_cache.configure_compile_cache() == str(tmp_path)
        # where the cache lives is left alone; what it is keyed by is not
        # (ISSUE 24: a hit must carry this program's scopes)
        assert updates == [
            ("jax_compilation_cache_include_metadata_in_key", True)]


    def test_a_cache_hit_carries_this_programs_scopes(self, tmp_path):
        """Two programs that differ in a ``named_scope`` alone: with JAX's
        default key the second loads the first one's executable, names
        and all; after ``configure_compile_cache()`` it compiles its own."""
        snippet = (
            "import sys, jax, jax.numpy as jnp, jax.monitoring as mon\n"
            "if sys.argv[1] == 'ours':\n"
            "    from deepspeed_tpu.utils.compile_cache import "
            "configure_compile_cache as f\n"
            "    f()\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0.0)\n"
            "jax.config.update('jax_persistent_cache_min_entry_size_bytes',"
            " -1)\n"
            "seen = []\n"
            "mon.register_event_listener(lambda e, **kw: seen.append("
            "e.rsplit('/', 1)[-1]))\n"
            "def step(x):\n"
            "    return jnp.sin(x) * 2 + 1\n"
            "def scoped(x):\n"
            "    with jax.named_scope('ds.optimizer'):\n"
            "        return jnp.sin(x) * 2 + 1\n"
            "scoped.__name__ = 'step'\n"
            "for fn in (step, scoped):\n"
            "    seen.clear()\n"
            "    jax.jit(fn)(jnp.ones(8)).block_until_ready()\n"
            "    print('hit' if 'cache_hits' in seen else 'miss')\n")
        for keyed, want in (("default", ["miss", "hit"]),
                            ("ours", ["miss", "miss"])):
            env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / keyed)}
            out = _run_python("-c", snippet, keyed, env=env)
            assert out.stdout.split() == want, (keyed, out.stderr[-2000:])


class TestUnknownDeviceHasNoPeak:
    def test_cpu_gets_no_peak_and_no_mfu(self):
        from deepspeed_tpu.profiling import flops_profiler as fp
        assert fp.peak_tflops("cpu") is None
        assert fp.peak_hbm_gbps("cpu") is None
        assert fp.mfu(1e12, 1.0, device_kind="cpu") is None
        assert fp.peak_tflops("TPU v5 lite") == 197.0
