"""Doc-drift lint (tier-1): the metric tables in docs/OBSERVABILITY.md are
enforced against the code, not aspirational.

Statically scans every ``deepspeed_tpu/**/*.py`` for registry metric tag
literals — ``.gauge("…")`` / ``.counter("…")`` / ``.histogram("…")`` plus
the ``self._counter("…")`` wrappers — and asserts each emitted tag appears
in the doc. For the goodput surface the check runs in BOTH directions:
every ``goodput/*`` (and ``engine/mfu``) tag the accountant can emit must
be documented, and every goodput tag the doc names must be one the code
emits, so the doc cannot silently rot in either direction.

And the documents name only what exists: every file a document names in
backticks is in the repository (``test_documents_name_only_files_that_exist``),
which is the guard for a deletion that leaves its mention behind.

Pure text scanning, no jax import beyond the package's own — fast enough
for tier-1.
"""

import os
import re

import pytest

from deepspeed_tpu.autotuning.search import AUTOTUNE_METRIC_TAGS
from deepspeed_tpu.comm.grad_sync import COMM_PARAM_METRIC_TAGS
from deepspeed_tpu.resilience.elastic import ELASTIC_METRIC_TAGS
from deepspeed_tpu.serving.engine import SERVING_METRIC_TAGS
from deepspeed_tpu.telemetry.devicetime import DEVICETIME_METRIC_TAGS
from deepspeed_tpu.telemetry.fleet import FLEET_METRIC_TAGS
from deepspeed_tpu.telemetry.goodput import GOODPUT_METRIC_TAGS
from deepspeed_tpu.telemetry.memory import MEMORY_METRIC_TAGS
from deepspeed_tpu.telemetry.moe import MOE_METRIC_TAGS
from deepspeed_tpu.telemetry.numerics import NUMERICS_METRIC_TAGS
from deepspeed_tpu.telemetry.requests import (
    ENGINE_CATEGORIES,
    REQUEST_CATEGORIES,
    REQUEST_METRIC_TAGS,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "deepspeed_tpu")
DOC = os.path.join(REPO, "docs", "OBSERVABILITY.md")
SPAN_SECTION = "## Spans in the profiler's trace"

# telemetry.span("name", ...) / tracer.span(\n "name" / self._span("name"
_SPAN_CALL_RE = re.compile(r"\b_?span\(\s*\"([a-z_]+)\"")
_KERNEL_NAME_RE = re.compile(r"^\s*name=\"([a-z_]+)\",$", re.MULTILINE)

# .gauge("a/b") / .counter(f"a/{x}") / .histogram('a') / ._counter("a/b")
_METRIC_CALL_RE = re.compile(
    r"\.(?:gauge|counter|histogram|_counter)\(\s*(f?)([\"'])([^\"']+)\2")
_GOODPUT_TOKEN_RE = re.compile(r"goodput/[A-Za-z_]+")
_FLEET_TOKEN_RE = re.compile(r"fleet/[A-Za-z_]+")
_MEMORY_TOKEN_RE = re.compile(r"memory/[A-Za-z_]+")
_SERVING_TOKEN_RE = re.compile(r"serving/[A-Za-z_]+")
_DEVICETIME_TOKEN_RE = re.compile(r"devicetime/[A-Za-z_]+")
_NUMERICS_TOKEN_RE = re.compile(r"numerics/[A-Za-z_]+")
_COMM_PARAMS_TOKEN_RE = re.compile(r"comm/[A-Za-z_]+_params")
# \b so "elasticity/" (the package path) never false-positives
_ELASTIC_TOKEN_RE = re.compile(r"\belastic/[A-Za-z_]+")
# \b so "autotuning/" (the package path) never false-positives
_AUTOTUNE_TOKEN_RE = re.compile(r"\bautotune/[A-Za-z_]+")
# "moe/" is ALSO the package path (moe/layer.py, moe/dispatch.py), so a
# token followed by a dot/slash/word char (a file or module reference)
# is not a metric tag.
_MOE_TOKEN_RE = re.compile(r"\bmoe/[A-Za-z_]+(?![\w./])")
# the doc writes the templated "requests/engine_<category>_sec" — the
# (?![\w<]) lookahead (with backtracking blocked by \w) keeps the
# "requests/engine_" prefix of that placeholder from scanning as a tag
_REQUESTS_TOKEN_RE = re.compile(r"\brequests/[A-Za-z_]+(?![\w<])")


def _iter_py_files():
    for root, _dirs, files in os.walk(PKG):
        if "__pycache__" in root:
            continue
        for name in files:
            if name.endswith(".py"):
                yield os.path.join(root, name)


def _emitted_literals():
    """[(file, is_fstring, tag_literal)] for every metric-call literal in
    the package."""
    out = []
    for path in _iter_py_files():
        with open(path) as f:
            src = f.read()
        for m in _METRIC_CALL_RE.finditer(src):
            out.append((os.path.relpath(path, REPO), bool(m.group(1)),
                        m.group(3)))
    return out


def _doc_text():
    with open(DOC) as f:
        return f.read()


class TestDocDrift:
    def test_scan_finds_the_known_call_sites(self):
        """The regex must actually see the tree's emissions — if the scan
        collapses to nothing, the lint below would pass vacuously."""
        tags = {t for _, _, t in _emitted_literals()}
        assert "engine/hbm_peak_bytes" in tags
        assert "ckpt/write_latency_sec" in tags      # _counter/gauge wrappers
        assert "guardrails/rollbacks" in tags
        assert any(t.startswith("goodput/") for t in tags)
        assert len(tags) > 10

    def test_every_emitted_tag_is_documented(self):
        doc = _doc_text()
        missing = []
        for fname, is_fstring, tag in _emitted_literals():
            # f-strings contribute their static prefix (e.g.
            # f"guardrails/steps_{kind}" -> "guardrails/steps_", a
            # substring of the documented guardrails/steps_ok row).
            probe = tag.split("{")[0] if is_fstring else tag
            if not probe:
                continue
            if probe not in doc:
                missing.append(f"{fname}: {tag!r}")
        assert not missing, (
            "metric tags emitted but absent from docs/OBSERVABILITY.md "
            f"(add rows): {sorted(set(missing))}")

    def test_goodput_tags_documented_and_vice_versa(self):
        doc = _doc_text()
        # forward: everything the accountant can emit is in the doc
        undocumented = sorted(t for t in GOODPUT_METRIC_TAGS
                              if t not in doc)
        assert not undocumented, undocumented
        # reverse: every goodput/* token the doc names is really emitted
        doc_tokens = set(_GOODPUT_TOKEN_RE.findall(doc))
        phantom = sorted(t for t in doc_tokens
                         if t not in GOODPUT_METRIC_TAGS)
        assert not phantom, (
            f"docs/OBSERVABILITY.md names goodput tags the code never "
            f"emits: {phantom}")
        assert "engine/mfu" in doc

    def test_fleet_tags_documented_and_vice_versa(self):
        """The fleet surface (telemetry/fleet.py) is pinned in BOTH
        directions like goodput: every tag the aggregator can emit —
        the fleet/* gauges, the straggler instant and counter — must be
        in the doc, and every fleet/* token the doc names must be one
        the code emits."""
        doc = _doc_text()
        undocumented = sorted(t for t in FLEET_METRIC_TAGS if t not in doc)
        assert not undocumented, undocumented
        doc_tokens = set(_FLEET_TOKEN_RE.findall(doc))
        phantom = sorted(t for t in doc_tokens
                         if t not in FLEET_METRIC_TAGS)
        assert not phantom, (
            f"docs/OBSERVABILITY.md names fleet tags the code never "
            f"emits: {phantom}")
        # the device-time attribution gauge rides the same enforcement
        assert "comm/exposed_frac" in doc

    def test_memory_tags_documented_and_vice_versa(self):
        """The memory-observatory surface (telemetry/memory.py) is
        pinned in BOTH directions like goodput/fleet: every tag the
        observatory can emit — the xla_*/ledger_*/headroom gauges, the
        OOM counter and the instant names — must be in the doc, and
        every memory/* token the doc names must be one the code
        emits."""
        doc = _doc_text()
        undocumented = sorted(t for t in MEMORY_METRIC_TAGS if t not in doc)
        assert not undocumented, undocumented
        doc_tokens = set(_MEMORY_TOKEN_RE.findall(doc))
        phantom = sorted(t for t in doc_tokens
                         if t not in MEMORY_METRIC_TAGS)
        assert not phantom, (
            f"docs/OBSERVABILITY.md names memory tags the code never "
            f"emits: {phantom}")

    def test_devicetime_tags_documented_and_vice_versa(self):
        """The device-time surface (telemetry/devicetime.py) is pinned in
        BOTH directions like goodput/fleet/memory: every tag the
        observatory can emit — the per-category gauges, the capture
        counter, the divergence instant and the measured exposed-comm
        gauge — must be in the doc, and every devicetime/* token the doc
        names must be one the code emits."""
        doc = _doc_text()
        undocumented = sorted(t for t in DEVICETIME_METRIC_TAGS
                              if t not in doc)
        assert not undocumented, undocumented
        doc_tokens = set(_DEVICETIME_TOKEN_RE.findall(doc))
        phantom = sorted(t for t in doc_tokens
                         if t not in DEVICETIME_METRIC_TAGS)
        assert not phantom, (
            f"docs/OBSERVABILITY.md names devicetime tags the code never "
            f"emits: {phantom}")
        # the measured companion of comm/exposed_frac rides the same
        # enforcement (it is a DEVICETIME_METRIC_TAGS member)
        assert "comm/measured_exposed_frac" in DEVICETIME_METRIC_TAGS
        assert "comm/measured_exposed_frac" in doc

    def test_comm_param_tags_documented_and_vice_versa(self):
        """The ZeRO++ param-hop comm gauges (comm/grad_sync.py
        COMM_PARAM_METRIC_TAGS) are pinned in BOTH directions: every tag
        the ParamGatherPlan can emit must be in the doc, every
        comm/*_params token the doc names must be one the code emits,
        and every literal *_params emission in the tree is a declared
        tag — so fleet/devicetime dashboards can rely on the param-vs-
        grad traffic split staying documented."""
        doc = _doc_text()
        undocumented = sorted(t for t in COMM_PARAM_METRIC_TAGS
                              if t not in doc)
        assert not undocumented, undocumented
        doc_tokens = set(_COMM_PARAMS_TOKEN_RE.findall(doc))
        phantom = sorted(t for t in doc_tokens
                         if t not in COMM_PARAM_METRIC_TAGS)
        assert not phantom, (
            f"docs/OBSERVABILITY.md names comm param tags the code never "
            f"emits: {phantom}")
        emitted = {t for _, _, t in _emitted_literals()
                   if _COMM_PARAMS_TOKEN_RE.fullmatch(t)}
        assert emitted, "the scan must see the param-hop emissions"
        assert emitted <= COMM_PARAM_METRIC_TAGS, (
            emitted - COMM_PARAM_METRIC_TAGS)

    def test_elastic_tags_documented_and_vice_versa(self):
        """The live-elasticity surface (resilience/elastic.py) is pinned
        in BOTH directions like goodput/fleet: every tag the coordinator
        can emit — the elastic/* gauges plus the decision/event instants
        — must be in the doc, and every elastic/* token the doc names
        must be one the code emits."""
        doc = _doc_text()
        undocumented = sorted(t for t in ELASTIC_METRIC_TAGS
                              if t not in doc)
        assert not undocumented, undocumented
        doc_tokens = set(_ELASTIC_TOKEN_RE.findall(doc))
        phantom = sorted(t for t in doc_tokens
                         if t not in ELASTIC_METRIC_TAGS)
        assert not phantom, (
            f"docs/OBSERVABILITY.md names elastic tags the code never "
            f"emits: {phantom}")
        # every literal elastic/* emission in the tree is a declared tag
        emitted = {t for _, _, t in _emitted_literals()
                   if t.startswith("elastic/")}
        assert emitted, "the scan must see the elastic gauge emissions"
        assert emitted <= ELASTIC_METRIC_TAGS, (
            emitted - ELASTIC_METRIC_TAGS)
        # the reshard wall-clock category rides the goodput enforcement
        assert "goodput/elastic_reshard_sec" in GOODPUT_METRIC_TAGS
        assert "goodput/elastic_reshard_sec" in doc

    def test_numerics_tags_documented_and_vice_versa(self):
        """The numerics surface (telemetry/numerics.py) is pinned in
        BOTH directions like goodput/fleet/memory/devicetime: every tag
        the observatory surface can emit — the per-group gauges, the
        global grad norm, the DCN and KV quantization-error gauges —
        must be in the doc, and every numerics/* token the doc names
        must be one the code emits."""
        doc = _doc_text()
        undocumented = sorted(t for t in NUMERICS_METRIC_TAGS
                              if t not in doc)
        assert not undocumented, undocumented
        doc_tokens = set(_NUMERICS_TOKEN_RE.findall(doc))
        phantom = sorted(t for t in doc_tokens
                         if t not in NUMERICS_METRIC_TAGS)
        assert not phantom, (
            f"docs/OBSERVABILITY.md names numerics tags the code never "
            f"emits: {phantom}")
        # every literal numerics/* emission in the tree is a declared tag
        emitted = {t for _, _, t in _emitted_literals()
                   if t.startswith("numerics/")}
        assert emitted, "the scan must see the numerics emissions"
        assert emitted <= NUMERICS_METRIC_TAGS, (
            emitted - NUMERICS_METRIC_TAGS)

    def test_autotune_tags_documented_and_vice_versa(self):
        """The autotuner surface (autotuning/search.py) is pinned in BOTH
        directions like goodput/fleet/memory: every tag the search can
        emit — the autotune/* gauges plus the adoption instant — must be
        in the doc, and every autotune/* token the doc names must be one
        the code emits."""
        doc = _doc_text()
        undocumented = sorted(t for t in AUTOTUNE_METRIC_TAGS
                              if t not in doc)
        assert not undocumented, undocumented
        doc_tokens = set(_AUTOTUNE_TOKEN_RE.findall(doc))
        phantom = sorted(t for t in doc_tokens
                         if t not in AUTOTUNE_METRIC_TAGS)
        assert not phantom, (
            f"docs/OBSERVABILITY.md names autotune tags the code never "
            f"emits: {phantom}")
        # every literal autotune/* emission in the tree is a declared tag
        emitted = {t for _, _, t in _emitted_literals()
                   if t.startswith("autotune/")}
        assert emitted, "the scan must see the autotune gauge emissions"
        assert emitted <= AUTOTUNE_METRIC_TAGS, (
            emitted - AUTOTUNE_METRIC_TAGS)
        # the search-window wall-clock category rides the goodput
        # enforcement
        assert "goodput/autotune_search_sec" in GOODPUT_METRIC_TAGS
        assert "goodput/autotune_search_sec" in doc

    def test_moe_tags_documented_and_vice_versa(self):
        """The MoE observatory surface (telemetry/moe.py) is pinned in
        BOTH directions like goodput/fleet/numerics: every tag the
        monitor can emit — the four moe/* gauges — must be in the doc,
        and every moe/* metric token the doc names (file references like
        moe/layer.py are screened by the regex) must be one the code
        emits."""
        doc = _doc_text()
        undocumented = sorted(t for t in MOE_METRIC_TAGS if t not in doc)
        assert not undocumented, undocumented
        doc_tokens = set(_MOE_TOKEN_RE.findall(doc))
        phantom = sorted(t for t in doc_tokens
                         if t not in MOE_METRIC_TAGS)
        assert not phantom, (
            f"docs/OBSERVABILITY.md names moe tags the code never "
            f"emits: {phantom}")
        # the monitor's computed emission ("moe/" + aux suffix) must map
        # exactly onto the declared tag set — a renamed aux key would
        # silently drop a gauge otherwise
        from deepspeed_tpu.telemetry.moe import MOE_AUX_KEYS
        derived = {"moe/" + k[len("moe_"):] for k in MOE_AUX_KEYS}
        assert derived == set(MOE_METRIC_TAGS), (
            derived ^ set(MOE_METRIC_TAGS))

    def test_autotune_report_tags_in_sync(self):
        """tools/autotune_report.py is stdlib-only by design (no package
        import), so its private tag tuple is pinned here instead — every
        autotune/* literal the report reads must be one the search
        emits."""
        with open(os.path.join(REPO, "tools", "autotune_report.py")) as f:
            src = f.read()
        report_tags = set(re.findall(r'"(autotune/[A-Za-z_]+)"', src))
        assert report_tags, "scan must see autotune_report's tags"
        phantom = sorted(t for t in report_tags
                         if t not in AUTOTUNE_METRIC_TAGS)
        assert not phantom, (
            f"tools/autotune_report.py reads tags the code never emits: "
            f"{phantom} — keep it in sync with autotuning/search.py")

    def test_numerics_report_tags_in_sync(self):
        """tools/numerics_report.py is stdlib-only by design (no package
        import), so its private tag tuples are pinned here instead —
        every numerics/* literal the report reads must be one the
        observatory surface emits."""
        with open(os.path.join(REPO, "tools", "numerics_report.py")) as f:
            src = f.read()
        report_tags = set(re.findall(r'"(numerics/[A-Za-z_]+)"', src))
        assert report_tags, "scan must see numerics_report's tags"
        phantom = sorted(t for t in report_tags
                         if t not in NUMERICS_METRIC_TAGS)
        assert not phantom, (
            f"tools/numerics_report.py reads tags the code never emits: "
            f"{phantom} — keep it in sync with telemetry/numerics.py")

    def test_devicetime_report_tags_in_sync(self):
        """tools/devicetime_report.py is stdlib-only by design (it loads
        traceparse by file path, no package import), so its tag/key
        strings are pinned here instead — every devicetime/* literal the
        report names must be one the observatory emits."""
        with open(os.path.join(REPO, "tools",
                               "devicetime_report.py")) as f:
            src = f.read()
        report_tags = set(re.findall(r'"(devicetime/[A-Za-z_]+)"', src))
        phantom = sorted(t for t in report_tags
                         if t not in DEVICETIME_METRIC_TAGS)
        assert not phantom, (
            f"tools/devicetime_report.py reads tags the code never emits: "
            f"{phantom} — keep it in sync with telemetry/devicetime.py")

    def test_serving_tags_documented_and_vice_versa(self):
        """The serving SLO surface (serving/engine.py) is pinned in BOTH
        directions like goodput/fleet/memory: every tag in
        SERVING_METRIC_TAGS must be in the doc, and every serving/* token
        the doc names must be one the code emits."""
        doc = _doc_text()
        undocumented = sorted(t for t in SERVING_METRIC_TAGS
                              if t not in doc)
        assert not undocumented, undocumented
        doc_tokens = set(_SERVING_TOKEN_RE.findall(doc))
        phantom = sorted(t for t in doc_tokens
                         if t not in SERVING_METRIC_TAGS)
        assert not phantom, (
            f"docs/OBSERVABILITY.md names serving tags the code never "
            f"emits: {phantom}")
        # every literal serving/* emission in the tree is a declared tag
        emitted = {t for _, _, t in _emitted_literals()
                   if t.startswith("serving/")}
        assert emitted, "the scan must see the serving emissions"
        assert emitted <= SERVING_METRIC_TAGS, (
            emitted - SERVING_METRIC_TAGS)
        # the decode fast path's per-piece gauges ride this enforcement —
        # pin them explicitly so a rename can't silently drop a piece's
        # attribution (docs/SERVING.md "Decode fast path")
        assert {"serving/decode_attn_kernel", "serving/prefix_hits",
                "serving/prefix_blocks_reused", "serving/spec_accept_rate",
                "serving/spec_tokens_per_verify"} <= SERVING_METRIC_TAGS
        # the resilience layer's counters/gauge likewise (docs/SERVING.md
        # "Serving under failure")
        assert {"serving/shed_requests", "serving/deadline_expired",
                "serving/cancelled", "serving/retries",
                "serving/recoveries",
                "serving/degraded_level"} <= SERVING_METRIC_TAGS

    def test_request_tags_documented_and_vice_versa(self):
        """The request-observatory surface (telemetry/requests.py) is
        pinned in BOTH directions like goodput/fleet/serving: every tag
        in REQUEST_METRIC_TAGS must be in the doc, and every requests/*
        token the doc names must be one the accountant emits. The
        per-category gauges are f-string emissions
        (f"requests/{c}_sec"), so the literal-emission check covers the
        non-f-string tags and the tag set itself covers the rest."""
        doc = _doc_text()
        undocumented = sorted(t for t in REQUEST_METRIC_TAGS
                              if t not in doc)
        assert not undocumented, undocumented
        doc_tokens = set(_REQUESTS_TOKEN_RE.findall(doc))
        assert doc_tokens, "the scan must see the documented request tags"
        phantom = sorted(t for t in doc_tokens
                         if t not in REQUEST_METRIC_TAGS)
        assert not phantom, (
            f"docs/OBSERVABILITY.md names request tags the code never "
            f"emits: {phantom}")
        # every literal (non-f-string) requests/* emission in the tree
        # is a declared tag
        emitted = {t for _, is_f, t in _emitted_literals()
                   if not is_f and t.startswith("requests/")}
        assert emitted, "the scan must see the request emissions"
        assert emitted <= REQUEST_METRIC_TAGS, (
            emitted - REQUEST_METRIC_TAGS)
        # the derived per-category tags must map exactly onto the
        # declared set — a renamed category would silently drop a gauge
        derived = ({f"requests/{c}_sec" for c in REQUEST_CATEGORIES}
                   | {f"requests/engine_{c}_sec"
                      for c in ENGINE_CATEGORIES})
        assert derived <= REQUEST_METRIC_TAGS, (
            derived - REQUEST_METRIC_TAGS)
        # the rolling-window companion gauge rides the serving
        # enforcement
        assert "serving/tokens_per_sec_window" in SERVING_METRIC_TAGS
        assert "serving/tokens_per_sec_window" in doc

    def test_slo_report_tags_in_sync(self):
        """tools/slo_report.py is stdlib-only by design (no package
        import), so its private tag/category copies are pinned here
        instead — every requests/* literal the report reads must be one
        the accountant emits, and its category tuples must mirror
        telemetry/requests.py exactly."""
        with open(os.path.join(REPO, "tools", "slo_report.py")) as f:
            src = f.read()
        report_tags = set(re.findall(r'"(requests/[A-Za-z_]+)"', src))
        assert report_tags, "scan must see slo_report's tags"
        # trailing-underscore literals are startswith() prefix probes
        # (e.g. "requests/engine_"), not tags
        phantom = sorted(t for t in report_tags
                         if not t.endswith("_")
                         and t not in REQUEST_METRIC_TAGS)
        assert not phantom, (
            f"tools/slo_report.py reads tags the code never emits: "
            f"{phantom} — keep it in sync with telemetry/requests.py")
        for cat in REQUEST_CATEGORIES + ENGINE_CATEGORIES:
            assert f'"{cat}"' in src, (
                f"tools/slo_report.py category tuples are missing "
                f"{cat!r} — keep them in sync with telemetry/requests.py")

    def test_serving_report_tags_in_sync(self):
        """tools/serving_report.py is stdlib-only by design (no package
        import), so its private tag tuples are pinned here instead —
        every tag the report reads must be one the engine emits."""
        with open(os.path.join(REPO, "tools", "serving_report.py")) as f:
            src = f.read()
        report_tags = set(re.findall(r'"(serving/[A-Za-z_]+)"', src))
        assert report_tags, "scan must see serving_report's tags"
        phantom = sorted(t for t in report_tags
                         if t not in SERVING_METRIC_TAGS)
        assert not phantom, (
            f"tools/serving_report.py reads tags the code never emits: "
            f"{phantom} — keep it in sync with serving/engine.py")

    def test_memory_report_gauges_in_sync(self):
        """tools/memory_report.py is stdlib-only by design (no package
        import), so its private gauge lists are pinned here instead —
        every gauge the report reads must be one the code emits."""
        with open(os.path.join(REPO, "tools", "memory_report.py")) as f:
            src = f.read()
        report_tags = set(re.findall(r'"((?:memory|engine)/[A-Za-z_]+)"',
                                     src))
        known = MEMORY_METRIC_TAGS | {"engine/hbm_peak_bytes"}
        phantom = sorted(t for t in report_tags if t not in known)
        assert not phantom, (
            f"tools/memory_report.py reads gauges the code never emits: "
            f"{phantom} — keep it in sync with telemetry/memory.py")

    def test_goodput_report_categories_in_sync(self):
        """tools/goodput_report.py is stdlib-only by design (no package
        import), so its private copy of the category list is pinned here
        instead."""
        from deepspeed_tpu.telemetry.goodput import CATEGORIES
        with open(os.path.join(REPO, "tools", "goodput_report.py")) as f:
            src = f.read()
        for cat in CATEGORIES:
            assert f'"{cat}"' in src, (
                f"tools/goodput_report.py CATEGORIES is missing {cat!r} — "
                "keep it in sync with telemetry/goodput.py")


class TestSpanTable:
    """The section "Spans in the profiler's trace" against the code: every
    span name, device scope and kernel name the package emits is in it."""

    @staticmethod
    def _section():
        doc = _doc_text()
        start = doc.index(SPAN_SECTION)
        return doc[start:doc.index("\n## ", start + 1)]

    def test_every_span_name_is_in_the_table(self):
        names = set()
        for path in _iter_py_files():
            with open(path) as f:
                names.update(_SPAN_CALL_RE.findall(f.read()))
        # the scan sees the old call sites and the new ones
        assert {"dataloader", "train_step", "prefill", "decode_step",
                "train_batch", "step_hooks", "serve_step", "admit",
                "submit", "ckpt_write"} <= names
        section = self._section()
        missing = sorted(n for n in names if f"`{n}`" not in section)
        assert not missing, (
            f"span names emitted but absent from docs/OBSERVABILITY.md "
            f"({SPAN_SECTION!r}): {missing}")

    def test_every_device_scope_and_kernel_name_is_in_the_section(self):
        from deepspeed_tpu.telemetry.tracer import DEVICE_SCOPES, PREFIX
        section = self._section()
        missing = sorted(n for n in DEVICE_SCOPES
                         if f"`{PREFIX}{n}`" not in section)
        assert not missing, missing
        kernels = set()
        for path in _iter_py_files():
            with open(path) as f:
                src = f.read()
            if "pallas_call(" in src:
                kernels.update(_KERNEL_NAME_RE.findall(src))
        assert len(kernels) == 7, kernels
        assert not sorted(k for k in kernels if f"`{k}`" not in section)
        # the section says how to read them and what they cost
        assert "dump_xplane.py" in section and "sync_spans" in section


# a backticked token that names a file: no space, no placeholder or glob
# (`<dir>/info.json`, `step_*.json`, `{a,b}.py` name no one file)
_FILE_TOKEN_RE = re.compile(r"`([^`\s*<>{}]+\.(?:py|md|json|txt))`")
_FILE_ROOTS = ("", "deepspeed_tpu", "tools", "tests", "docs")
DOCUMENTS = ["README.md"] + sorted(
    os.path.join("docs", name) for name in os.listdir(
        os.path.join(REPO, "docs")) if name.endswith(".md"))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_documents_name_only_files_that_exist(document):
    """Every backticked token of the document that ends in ``.py``,
    ``.md``, ``.json`` or ``.txt`` resolves against the repository's root,
    ``deepspeed_tpu/``, ``tools/``, ``tests/`` or ``docs/``. A file a run
    writes, or one of the reference's, is written with its placeholder
    (``<dump>/info.json``, ``<deepspeed>/runtime/zero/stage3.py``)."""
    with open(os.path.join(REPO, document)) as f:
        tokens = set(_FILE_TOKEN_RE.findall(f.read()))
    assert tokens, f"{document} names no file: is the pattern still right?"
    missing = sorted(
        t for t in tokens if not any(
            os.path.exists(os.path.join(REPO, root, t))
            for root in _FILE_ROOTS))
    assert not missing, (
        f"{document} names files that are not in the repository: {missing}")
