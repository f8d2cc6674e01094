"""ServeEngine — continuous batching + SLO telemetry over the inference stack.

The production serving loop the ROADMAP's "millions of users" story needs,
layered on what the tree already has: the :class:`InferenceEngine` owns
params (TP sharding, int8 weights, dtype), ``serving/kv_cache.py`` owns KV
memory, ``serving/scheduler.py`` owns admission, and the telemetry stack
(registry/tracer/recompile detector) owns observability.

Execution model — **step-driven, three compiled programs, zero retraces
in steady state**:

- ``prefill`` (one program per power-of-two prompt **bucket**): a single
  sequence's prompt runs through the contiguous-cache forward, its first
  token is sampled in-program, and the per-layer K/V are scattered into
  the paged pool. Prefill and decode are **disaggregated**: a long prompt
  costs the decode batch at most ``max_prefills_per_step`` prefill
  dispatches per step boundary, never a retrace of the decode program.
- ``decode_step`` (ONE program, ever): the whole slot batch advances one
  token through the paged cache — fixed batch width, fixed block-table
  shape, per-row positions, and a fixed-length list of the batch's live
  KV blocks of which the program walks only the chunks that hold
  anything (a traced trip count). Sequences join/leave by editing
  host-side numpy inputs, which XLA never sees as a new signature.
- scheduling between steps is pure host python (microseconds).

**What the engine asks of a model** (GPT and Nemotron-H answer; any module
with a ``cfg`` that does can be served):

- ``model.serving_cache_spec()``: one ``LayerCacheSpec`` a layer
  (``serving/kv_cache.py``): ``kv(heads, head_dim)`` (a K and a V pool,
  sized by the layer's KEY/VALUE heads), ``recurrent(shapes)`` (state a
  slot, which does not grow with positions: a Mamba-2 layer's) or
  ``none()``;
- ``model.serve_prefill(params, ids [1, bucket], length)`` ->
  ``{"logits", "cache"[, "counters"]}``: per ``kv`` layer the prompt's
  ``(k, v)``, per ``recurrent`` layer the arrays the slot keeps, as they
  stand after the prompt's last real position;
- ``model.serve_decode(params, ids, pos_ids, cache[, live])`` ->
  the same keys, ``cache`` a view a layer (``PagedLayerCache``,
  ``RecurrentLayerState``, None).

What recurrent state cannot do yet is refused at construction, never
served wrong: the prefix cache (a prompt head's blocks say nothing of the
state behind them), speculative decoding and chunked prefill (both push
several positions a row through the decode-side program; a recurrent
layer's state advances one), and ``serving.resilience`` (its recovery
rebuilds blocks alone). Preemption is NOT refused: an evicted request
restarts from its prompt, whose prefill writes the slot's state whole.

SLO telemetry rides the established contract: metrics through the
``MetricsRegistry`` (no sinks -> no-ops), spans through the ``StepTracer``
(disabled -> reusable null span, zero device syncs), and
``tools/serving_report.py`` renders TTFT/throughput/occupancy percentiles
from the same metrics JSONL the training loop writes.
"""

import contextlib
import functools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.engine import (InferenceEngine, bucket_length,
                                            sample_logits)
from deepspeed_tpu.serving.kv_cache import (BlockPool, ChunkedLayerCache,
                                            PagedLayerCache,
                                            RecurrentLayerState,
                                            init_serving_state,
                                            live_block_list, pack_prefill)
from deepspeed_tpu.serving.scheduler import (PrefixCache, Scheduler,
                                             Sequence)
from deepspeed_tpu.telemetry.tracer import (device_scope,
                                            profiler_session_live)
from deepspeed_tpu.utils.logging import log_dist
from deepspeed_tpu.utils.platform import on_tpu

# Every metric tag the serving engine can emit — pinned against
# docs/OBSERVABILITY.md in both directions by tests/test_doc_lint.py.
SERVING_METRIC_TAGS = frozenset({
    "serving/ttft_ms",
    "serving/tokens_per_sec",
    # Rolling-window decode throughput (window: telemetry.requests.
    # window_sec) — emitted only when the request accountant is on, so
    # the tag set with telemetry.requests off stays byte-identical.
    "serving/tokens_per_sec_window",
    "serving/batch_occupancy",
    "serving/kv_blocks_in_use",
    "serving/queue_depth",
    "serving/preempted_seqs",
    "serving/requests_completed",
    # decode fast path (docs/SERVING.md "Decode fast path"): per-piece
    # attribution so each win is separately measurable.
    "serving/decode_attn_kernel",
    "serving/prefix_hits",
    "serving/prefix_blocks_reused",
    "serving/spec_accept_rate",
    "serving/spec_tokens_per_verify",
    # Serving resilience (docs/SERVING.md "Serving under failure"):
    # emitted only when serving.resilience is on, so the off tag set
    # stays byte-identical.
    "serving/shed_requests",
    "serving/deadline_expired",
    "serving/cancelled",
    "serving/recoveries",
    "serving/retries",
    "serving/degraded_level",
    # Chunked prefill (docs/SERVING.md "Chunked prefill admission"):
    # emitted only when serving.chunked_prefill is on, so the off tag
    # set stays byte-identical.
    "serving/chunked_tokens_per_step",
    "serving/prefill_chunks_in_flight",
})


def resolve_decode_attention(mode: str, tpu: bool, tiles: bool,
                             geometry: str = "", grouped: bool = False
                             ) -> str:
    """Which program decodes (docs/SERVING.md "Decode fast path"), from
    ``serving.decode_attention``, the platform and the kernel's gate
    (``paged_decode_ok``). ``"gather"``: the default decode, over the flat
    list of the batch's live blocks. ``"kernel"``: the Pallas paged
    decode-attention kernel over the table's width; the compiled kernel
    tiles only head_dim % 128 / block % 8 geometries, off the TPU the
    interpreter takes any. ``"auto"``: the kernel on a TPU where it tiles,
    else the default decode. ``grouped`` (a key/value head serves several
    query heads): both of those are written for one query head a
    key/value head, so the answer is ``"window"``, the model's own
    attention over each row's gathered table window
    (``PagedLayerCache.update``), and ``"kernel"`` is refused."""
    from deepspeed_tpu.config.config import ConfigError

    if grouped:
        if mode == "kernel":
            raise ConfigError(
                f"serving.decode_attention='kernel': the paged decode "
                f"kernel takes one query head a key/value head; this "
                f"model's attention is grouped ({geometry}) — use 'auto' "
                f"or 'gather'")
        return "window"
    if mode == "kernel":
        if tpu and not tiles:
            raise ConfigError(
                f"serving.decode_attention='kernel' cannot compile on "
                f"this TPU: {geometry} does not tile the paged "
                f"decode kernel (needs head_dim % 128 == 0 and "
                f"block_size % 8 == 0) — use 'auto' or 'gather'")
        return "kernel"
    if mode == "auto" and tpu and tiles:
        return "kernel"
    return "gather"


class ServeEngine:
    """Continuous-batching serving engine over an :class:`InferenceEngine`.

    ``engine``: an InferenceEngine wrapping a causal LM that answers the
    three questions of the module docstring (``serving_cache_spec``,
    ``serve_prefill``, ``serve_decode``: in-tree, GPT and Nemotron-H).
    ``config``: a parsed ``ServingConfig`` (or None
    for defaults). ``telemetry``: the run's ``Telemetry`` facade — omit it
    (or pass a disabled one) and the engine performs zero telemetry
    work beyond host float arithmetic.

    Thread model: **none required** — ``submit()`` + ``step()`` are plain
    calls (tier-1 drives them directly); ``serve_forever()`` is a thin
    loop for a dedicated serving process.
    """

    # The live-block list of the default decode (kv_cache.live_block_list,
    # PagedLayerCache.attend_live): a row's blocks are listed in runs of
    # LIVE_RUN_BLOCKS, the last one padded (larger: more padding read at
    # the end of every row); the program takes LIVE_CHUNK_RUNS runs a loop
    # iteration (larger: fewer iterations, a longer fold in each). PERF.md
    # section 6 (PR 27) has what the chip said of 4 x 8 to 16 x 4.
    LIVE_RUN_BLOCKS = 16
    LIVE_CHUNK_RUNS = 2

    def __init__(self, engine: InferenceEngine, config=None,
                 telemetry=None, capture_logits: bool = False,
                 measure_kv_quant_error: bool = False,
                 request_accountant=None, fault_plan=None):
        from deepspeed_tpu.config.config import ConfigError, ServingConfig
        from deepspeed_tpu.telemetry import null_telemetry

        asked = ("serving_cache_spec", "serve_prefill", "serve_decode")
        lacks = [name for name in asked
                 if not callable(getattr(engine.module, name, None))]
        if engine.model_cfg is None or lacks:
            raise ValueError(
                f"ServeEngine needs a cache-capable causal LM: a module "
                f"with a ``cfg`` that says what its layers cache and how "
                f"to run them through it "
                f"({', '.join(asked)}: models/gpt.py and "
                f"models/nemotron_h.py do); "
                f"{type(engine.module).__name__} lacks "
                f"{', '.join(lacks) or 'cfg'}")
        self.engine = engine
        self.module = engine.module
        self.model_cfg = engine.model_cfg
        self.scfg = config if config is not None else ServingConfig()
        self.telemetry = telemetry if telemetry is not None \
            else null_telemetry()
        self.capture_logits = bool(capture_logits)

        # What each layer keeps between steps, by the model's own word.
        self._specs = tuple(engine.module.serving_cache_spec())
        self._kinds = tuple(spec.kind for spec in self._specs)
        kv_specs = [spec for spec in self._specs if spec.kind == "kv"]
        if len({(sp.heads, sp.head_dim) for sp in kv_specs}) > 1:
            raise ValueError("the kv layers of a model share one block "
                             "table and so one (heads, head_dim)")
        self._stateful = "recurrent" in self._kinds
        self._all_kv = set(self._kinds) == {"kv"}
        if not self._all_kv:
            refused = [
                (self.scfg.prefix_cache, "serving.prefix_cache",
                 "a prompt head's KV blocks say nothing of the recurrent "
                 "state behind them"),
                (self.scfg.spec_decode, "serving.speculative",
                 "a verify chunk pushes several positions a row through "
                 "the decode program and a recurrent state advances one"),
                (self.scfg.chunked_prefill, "serving.chunked_prefill",
                 "the mixed program pushes a prompt through the "
                 "decode-side cache in chunks and a recurrent state "
                 "advances one position a step"),
                (self.scfg.resilience, "serving.resilience",
                 "its recovery rebuilds KV blocks alone")]
            for on, option, why in refused:
                if on:
                    raise ConfigError(
                        f"{option} cannot be served with layers that keep "
                        f"anything but keys and values: this model "
                        f"({type(engine.module).__name__}) has "
                        + ", ".join(f"{self._kinds.count(k)} {k}"
                                    for k in sorted(set(self._kinds)))
                        + f" layers; {why}")

        model_max = int(getattr(self.model_cfg, "max_seq_len"))
        self.max_model_len = min(self.scfg.max_model_len or model_max,
                                 model_max)
        bs = self.scfg.kv_block_size
        self.block_size = bs
        self.max_blocks = -(-self.max_model_len // bs)   # ceil
        # Prompt buckets must be BS multiples (whole blocks) and their
        # positions must exist in the model (wpe rows) AND in the block
        # table width.
        self.bucket_cap = min(self.max_blocks * bs, (model_max // bs) * bs)
        if self.bucket_cap < bs:
            raise ValueError(
                f"serving.kv_block_size={bs} exceeds the usable context "
                f"({model_max}) — no prompt bucket fits")

        self.pool = BlockPool(self.scfg.kv_num_blocks)
        self.prefix_cache = (PrefixCache(self.pool, bs)
                             if self.scfg.prefix_cache else None)
        self.sched = Scheduler(self.scfg.max_batch_size, self.pool, bs,
                               prefix_cache=self.prefix_cache)
        self._dtype = engine.config.dtype
        self._dtype_name = jnp.dtype(self._dtype).name
        self._pools = init_serving_state(
            self._specs, self.scfg.kv_num_blocks, bs,
            self.scfg.max_batch_size, int8=self.scfg.int8_kv_cache,
            dtype=self._dtype)

        self._prefill_jit: Dict[int, Any] = {}
        # -- decode fast path (docs/SERVING.md "Decode fast path") ------
        # ONE decode program an engine: the default decode over the flat
        # list of the batch's live blocks (_dispatch_live), or the paged
        # kernel over the table's width (_dispatch_batch). The speculative
        # verify chunk, several queries a row, reads the table's width
        # either way.
        from deepspeed_tpu.ops.transformer.paged_attention import \
            paged_decode_ok
        mode = self.scfg.decode_attention
        tpu = on_tpu()
        head_dim = kv_specs[0].head_dim if kv_specs \
            else self.model_cfg.head_dim
        tiles = paged_decode_ok(head_dim, bs)
        geometry = f"head_dim={head_dim}, block_size={bs}"
        q_heads = int(getattr(self.model_cfg, "num_heads", 0))
        grouped = bool(kv_specs) and q_heads != kv_specs[0].heads
        if grouped:
            geometry = (f"{q_heads} query heads on {kv_specs[0].heads} "
                        f"key/value heads, {geometry}")
        self._attn_impl = resolve_decode_attention(mode, tpu, tiles,
                                                   geometry, grouped)
        log_dist(f"serving: decode_attention={mode!r} resolved to "
                 f"{self._attn_impl!r} ({geometry}, platform "
                 f"{jax.devices()[0].platform})", ranks=[0])
        self._decode_jit = None
        # Length of the live-block list, in chunks: every slot at the
        # table's width, in whole runs (a block shared through the prefix
        # cache is listed once per row that reads it, so the pool's block
        # count is no bound). Fixed, so the decode program has one
        # signature.
        runs = (self.scfg.max_batch_size
                * -(-self.max_blocks // self.LIVE_RUN_BLOCKS))
        self._live_chunks = -(-runs // self.LIVE_CHUNK_RUNS)
        self._tail_prefill_jit: Dict[int, Any] = {}
        # -- speculative decoding ---------------------------------------
        self._spec_k = 0
        self._spec_jit = None
        if self.scfg.spec_decode:
            self._init_speculative()
        # -- chunked prefill (docs/SERVING.md "Chunked prefill
        # admission"): the third admission mode. Decode tokens and
        # prefill CHUNKS of admitted prompts share ONE ragged mixed
        # program (ops/transformer/chunked_prefill.py), bounded by a
        # per-step token budget — no per-bucket prefill compiles, no
        # head-of-line full-prompt stall, one compile ever. Off (the
        # default) keeps every hook a single attribute check and the
        # lowered bucketed programs + emitted tag set byte-identical.
        self._chunked = bool(self.scfg.chunked_prefill)
        self._chunk_budget = int(self.scfg.chunked_token_budget)
        self._mixed_jit = None
        self._chunk_tokens_last = 0
        if self._chunked:
            if tpu and not tiles:
                # The user asked for this admission path: an engine that
                # quietly served bucketed instead would report success
                # for a path that never ran.
                raise ConfigError(
                    f"serving.chunked_prefill cannot compile on this TPU: "
                    f"{geometry} does not tile the ragged-prefill kernel "
                    f"(needs head_dim % 128 == 0 and block_size % 8 == 0) "
                    f"— turn chunked_prefill off for this model")
            log_dist(
                f"serving: chunked prefill on — token budget "
                f"{self._chunk_budget}/step, one mixed program",
                ranks=[0])
        # Request observatory (telemetry/requests.py): per-request SLO
        # ledger + engine serving-time partition. None (the default and
        # the telemetry.requests=off state) keeps every hook a single
        # attribute check and the emitted tag set byte-identical.
        self._req_acc = request_accountant
        if self._req_acc is not None:
            self._req_acc.spec_k = self._spec_k
            self.sched.accountant = self._req_acc
        # Serving resilience (serving/resilience.py; docs/SERVING.md
        # "Serving under failure"): deadlines + cancellation, SLO-aware
        # load shedding, in-flight recovery, degradation ladder. None
        # (the serving.resilience=off default) keeps every hook a single
        # attribute check and the lowered decode program + emitted tag
        # set byte-identical. Chaos (``fault_plan``) is independent: an
        # injected serve fault with resilience off crashes the loop —
        # the failure mode the manager exists to absorb.
        self._fault = fault_plan
        self._dispatch_attempts = 0      # decode dispatches, fault-keyed
        self._storm_template = None      # last submit args, for storms
        if self.scfg.resilience:
            from deepspeed_tpu.serving.resilience import ResilienceManager
            self._resil = ResilienceManager(self)
        else:
            self._resil = None
        # Numerics observatory surface (telemetry/numerics.py): with the
        # int8 KV cache AND the numerics opt-in on
        # (``telemetry.numerics.enabled`` — init_serving plumbs it;
        # telemetry-only deployments must not pay a per-prefill measure
        # inside the TTFT span), each prefill measures the RTNE
        # round-trip error of the K/V it just quantized into the pool
        # (one jitted measure per bucket, real positions only) — the
        # serving analogue of the DCN grad gauge.
        self._measure_kv = (bool(measure_kv_quant_error)
                            and bool(self.scfg.int8_kv_cache)
                            and self.telemetry.enabled)
        self._kv_err_jit: Dict[int, Any] = {}
        # Donate the pools: decode/pack rewrite them functionally, and
        # without donation XLA double-buffers the whole KV cache (2x HBM)
        # and copies it per token (same rationale as the training
        # engine's donated TrainState). Backends without donation (CPU
        # tier-1) just warn and copy.
        self._pack_jit = jax.jit(pack_prefill, static_argnames=("kinds",),
                                 donate_argnums=(0,))
        # The names of the int32 counters a model's serving programs
        # return beside the token (Nemotron-H: its expert layers'), which
        # ride the decode and prefill spans; fetched after the token, and
        # only while a profiler session records.
        self._counter_names = tuple(
            getattr(engine.module, "SERVING_COUNTERS", ()))
        self._base_key = jax.random.PRNGKey(self.scfg.seed)
        self._step_count = 0
        # Cumulative decode work behind the throughput gauge: a
        # token-weighted rate (total tokens / total decode seconds) —
        # a mean over per-step instantaneous rates would overweight
        # fast steps and overstate throughput exactly when straggler
        # steps appear.
        self._decode_tokens = 0
        self._decode_sec = 0.0
        self.results: Dict[int, Dict[str, Any]] = {}
        # Host-side aggregates, kept regardless of telemetry (floats and
        # ints only — the SLO gauges are derived from these).
        # ``read_positions``: what the decode programs read (the
        # default decode: chunks walked x chunk x block size; the kernel
        # decode and a speculative round: table rows x columns x block
        # size whatever is live);
        # ``live_positions``: of those, the positions of active rows that
        # hold KV. Their ratio is the useful share of the KV read.
        # ``live_blocks``/``chunks``: entries of the default decode's
        # live lists and the chunks it walked.
        self.stats = {"decode_steps": 0, "occupancy_sum": 0.0,
                      "slot_assignments": {}, "kernel_steps": 0,
                      "live_positions": 0, "read_positions": 0,
                      "live_blocks": 0, "chunks": 0,
                      "spec_rounds": 0, "spec_proposed": 0,
                      "spec_accepted": 0, "spec_new_tokens": 0}
        log_dist(
            f"serving: {self.scfg.max_batch_size} slots, KV pool "
            f"{self.pool.capacity}x{bs} positions "
            f"({'int8' if self.scfg.int8_kv_cache else self._dtype_name}), "
            f"max_model_len {self.max_model_len}", ranks=[0])

    # ------------------------------------------------------------------
    # submission / retrieval
    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int,
               eos_token_id: Optional[int] = None,
               deadline_ms: Optional[float] = None) -> int:
        """Queue one request; returns its request id. Never blocks —
        admission happens at the next ``step()`` boundary.

        ``deadline_ms`` (requires ``serving.resilience``): wall-clock
        budget from submission; past it the request is aborted at the
        next step boundary with status ``deadline_expired`` and whatever
        tokens it produced. With resilience on, the admission gate may
        also refuse the request outright — the returned rid then maps to
        a terminal ``results`` record with status ``shed``."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        with self.telemetry.span("submit", prompt_len=len(prompt)) as sp:
            rid = self._submit(prompt, max_new_tokens, eos_token_id,
                               deadline_ms)
            sp.set_metadata(rid=rid)
        return rid

    def _submit(self, prompt: List[int], max_new_tokens: int,
                eos_token_id: Optional[int],
                deadline_ms: Optional[float]) -> int:
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        if len(prompt) > self.bucket_cap:
            raise ValueError(
                f"prompt ({len(prompt)}) exceeds the largest prefill "
                f"bucket ({self.bucket_cap})")
        if len(prompt) + int(max_new_tokens) > self.max_model_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_model_len "
                f"({self.max_model_len})")
        bs = self.block_size
        # Lifetime KV need: the LAST sampled token's KV is never written
        # (the run ends on it), so the highest write position is
        # prompt + max_new_tokens - 2.
        need = max(self._bucket_of(len(prompt)) // bs,
                   -(-(len(prompt) + int(max_new_tokens) - 1) // bs))
        if need > self.pool.capacity:
            raise ValueError(
                f"request needs {need} KV blocks but the pool holds "
                f"{self.pool.capacity} — it could never be admitted; "
                f"raise serving.kv_num_blocks")
        if deadline_ms is not None:
            if self._resil is None:
                raise ValueError(
                    "deadline_ms requires serving.resilience.enabled "
                    "(docs/SERVING.md 'Serving under failure')")
            if deadline_ms <= 0:
                raise ValueError(
                    f"deadline_ms must be > 0, got {deadline_ms}")
        eos = eos_token_id if eos_token_id is not None \
            else self.scfg.eos_token_id
        if self._fault is not None:
            self._storm_template = (list(prompt), int(max_new_tokens),
                                    eos_token_id, deadline_ms)
        if self._resil is not None:
            reason = self._resil.admission_gate(prompt,
                                                int(max_new_tokens))
            if reason is not None:
                return self._resil.shed(prompt, int(max_new_tokens),
                                        eos, reason)
        rid = self.sched.submit(prompt, int(max_new_tokens), eos)
        req = self.sched.waiting[-1]
        if self._resil is not None:
            dl = (deadline_ms if deadline_ms is not None
                  else self.scfg.resil_default_deadline_ms)
            if dl is not None:
                req.deadline = req.arrival + dl / 1e3
        if self._req_acc is not None:
            self._req_acc.on_submit(req)
        return rid

    def cancel(self, rid: int) -> bool:
        """Flag a submitted request for cancellation; it is resolved at
        the next step boundary — dropped from the queue, or aborted with
        its partial output and terminal status ``cancelled``. Returns
        False when the rid is unknown or already terminal. Requires
        ``serving.resilience``."""
        if self._resil is None:
            raise RuntimeError(
                "cancel() requires serving.resilience.enabled "
                "(docs/SERVING.md 'Serving under failure')")
        return self._resil.request_cancel(rid)

    def idle(self) -> bool:
        return self.sched.idle()

    # ------------------------------------------------------------------
    # the serving step
    # ------------------------------------------------------------------
    def step(self) -> Dict[str, Any]:
        """One engine iteration: admit+prefill (bounded), then advance the
        whole decode batch one token. Returns a step report
        (``finished``/``prefilled`` request ids, ``active`` count...)."""
        with self.telemetry.span(
                "serve_step", step=self._step_count,
                queued=self.sched.queue_depth,
                active=len(self.sched.running),
                blocks_used=self.pool.used_blocks,
                blocks_total=self.pool.capacity):
            return self._step()

    def _step(self) -> Dict[str, Any]:
        info: Dict[str, Any] = {"step": self._step_count, "prefilled": [],
                                "finished": [], "active": 0}
        # Engine serving-time partition (telemetry/requests.py): the
        # accountant's single cursor is advanced at each phase boundary,
        # so the step's wall clock lands in exactly one category. A step
        # that grew a jit cache files its dispatch under "compile" (the
        # first trace dominates that step's wall time).
        acc = self._req_acc
        if acc is not None:
            acc.engine_mark("host_idle")    # since the previous step

        # -- resilience boundary: deadlines/cancellations resolve, then
        # any scheduled chaos storm joins the queue (through submit(),
        # i.e. through the shed gate) ----------------------------------
        if self._resil is not None:
            self._resil.process_boundary()
        if self._fault is not None \
                and self._fault.should_serve_storm(self._step_count):
            self._inject_storm()

        # -- admission + prefill (the in-flight batching half) ----------
        for _ in range(self.scfg.max_prefills_per_step):
            with self.telemetry.span("admit", step=self._step_count,
                                     queued=self.sched.queue_depth):
                seq = self.sched.try_admit(self._bucket_of,
                                           self._step_count)
            if seq is None:
                break
            if self._chunked:
                # Chunked admission: no prefill dispatch here — the
                # prompt enters the mixed program in budget-bounded
                # chunks starting at the adopted prefix head. First
                # token, prefix registration and the ``prefilled``
                # report land when the LAST chunk completes
                # (_mixed_round).
                seq.pos = seq.prefilled = seq.shared_len
                if acc is not None:
                    acc.engine_mark("scheduler_admission")
                self.stats["slot_assignments"].setdefault(seq.slot, 0)
                self.stats["slot_assignments"][seq.slot] += 1
                continue
            if acc is not None:
                acc.engine_mark("scheduler_admission")
                n_jits = len(self._prefill_jit) + len(self._tail_prefill_jit)
            self._prefill(seq)
            if acc is not None:
                grew = (len(self._prefill_jit)
                        + len(self._tail_prefill_jit)) > n_jits
                acc.engine_mark("compile" if grew else "prefill")
                acc.on_prefilled(seq)
            self.sched.register_prefix(seq, self._step_count)
            info["prefilled"].append(seq.request.rid)
            self.stats["slot_assignments"].setdefault(seq.slot, 0)
            self.stats["slot_assignments"][seq.slot] += 1
            if seq.finished():      # max_new_tokens == 1 / instant EOS
                self._finish(seq, info)

        # -- decode one token for every running sequence ----------------
        # (a speculative round writes k+1 positions, so capacity is
        # ensured with that lookahead — capped at each row's lifetime)
        with self.telemetry.span("batch_inputs", step=self._step_count,
                                 active=len(self.sched.running)):
            active = self.sched.active
            for seq in list(active):
                if self.sched.running.get(seq.slot) is seq:
                    self.sched.ensure_capacity(seq, lookahead=self._spec_k)
            active = self.sched.active      # preemption may have evicted
        info["active"] = len(active)
        if acc is not None:
            acc.engine_mark("scheduler_admission")
        dt_decode = 0.0
        n_tokens = 0
        if active:
            if acc is not None:
                n_djits = self._decode_programs()
            if self._resil is not None:
                n_tokens, dt_decode, active = self._resil.run_decode(
                    active, info)
            else:
                n_tokens, dt_decode = self._decode_round(active, info)
        with self._post_span(info):
            if active:
                if self._resil is not None:
                    self._resil.note_step(dt_decode)
                if acc is not None:
                    grew = self._decode_programs() > n_djits
                    acc.engine_mark("compile" if grew else "decode")
                    still = [s for s in active
                             if self.sched.running.get(s.slot) is s]
                    acc.on_decode_step(still, dt_decode, self._step_count)
                self.stats["decode_steps"] += 1
                self.stats["occupancy_sum"] += \
                    len(active) / self.scfg.max_batch_size
                # Cumulative decode rate lives OUTSIDE the telemetry
                # gate: the admission gate's projected-wait fallback needs
                # it even on a telemetry-free engine (two host floats, no
                # syncs).
                if n_tokens and dt_decode > 0:
                    self._decode_tokens += n_tokens
                    self._decode_sec += dt_decode
            # Gauges carry the SAME step index as this iteration's TTFT/
            # completion rows (emitted above) — increment only afterwards.
            self._emit_step_metrics(len(active), dt_decode, n_tokens)
        self._step_count += 1
        return info

    def run_until_complete(self, max_steps: int = 100_000,
                           timeout_sec: Optional[float] = None
                           ) -> Dict[int, Any]:
        """Drive ``step()`` until every submitted request has finished;
        returns the results map (rid -> record). ``timeout_sec`` is a
        wall-clock bound: a wedged loop (a straggling dispatch, a stuck
        backend) raises loudly with queue/active diagnostics instead of
        spinning toward the step bound at whatever pace the wedge
        allows."""
        steps = 0
        t0 = time.monotonic()
        while not self.idle():
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(
                    f"serving did not drain in {max_steps} steps "
                    f"(queue={self.sched.queue_depth}, "
                    f"running={len(self.sched.running)})")
            if timeout_sec is not None \
                    and time.monotonic() - t0 > timeout_sec:
                waiting = [r.rid for r in self.sched.waiting]
                running = {s.slot: s.request.rid
                           for s in self.sched.running.values()}
                raise RuntimeError(
                    f"serving wall-clock timeout: not drained after "
                    f"{timeout_sec:.3f}s ({steps} steps, "
                    f"queue={self.sched.queue_depth} "
                    f"rids={waiting[:8]}, running={running})")
        return self.results

    def serve_forever(self, should_stop=None, idle_sleep: float = 0.002):
        """Loop ``step()`` until ``should_stop()`` returns True, sleeping
        briefly when there is no work. The step-driven core stays
        single-threaded; callers submit from other threads freely (the
        scheduler's deque append is atomic)."""
        while should_stop is None or not should_stop():
            if self.idle():
                if should_stop is None:
                    return          # nothing queued and no stop predicate
                time.sleep(idle_sleep)
                continue
            self.step()

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------
    def _bucket_of(self, t: int) -> int:
        if self._chunked:
            # Chunked admission sizes exactly (whole blocks, no pow2
            # rounding): there are no per-bucket compiles to amortize —
            # the ragged program takes any length — so neither KV
            # blocks nor prefill compute ever pay bucket rounding.
            return min(-(-t // self.block_size) * self.block_size,
                       self.bucket_cap)
        b = bucket_length(t, cap=self.bucket_cap)
        b = -(-b // self.block_size) * self.block_size   # whole blocks
        return min(max(b, -(-t // self.block_size) * self.block_size),
                   self.bucket_cap)

    @property
    def live_chunk_positions(self) -> int:
        """Key positions the default decode reads a loop iteration."""
        return (self.LIVE_CHUNK_RUNS * self.LIVE_RUN_BLOCKS
                * self.block_size)

    @property
    def mean_occupancy(self) -> float:
        n = self.stats["decode_steps"]
        return self.stats["occupancy_sum"] / n if n else 0.0

    def _result_record(self, seq: Sequence, status: str) -> Dict[str, Any]:
        """Terminal record for an ADMITTED sequence — shared by the
        happy path (``finished``) and the resilience terminals
        (``deadline_expired``/``cancelled``/``aborted``), so the record
        shape cannot drift between them. Latency fields are stamped
        unconditionally — host floats the caller gets without telemetry
        enabled."""
        req = seq.request
        now = time.monotonic()
        return {
            "tokens": list(seq.tokens),
            "prompt_len": len(req.prompt),
            "status": status,
            "slot": seq.slot,
            "finish_step": self._step_count,
            "ttft_ms": (req.first_token_time - req.arrival) * 1e3
            if req.first_token_time else None,
            "finish_time": now,
            "e2e_ms": (now - req.arrival) * 1e3,
            "queue_wait_ms": (req.admitted_time - req.arrival) * 1e3
            if req.admitted_time is not None else None,
            "preempted_count": req.preempted_count,
        }

    def _queue_record(self, req, status: str,
                      reason: Optional[str] = None) -> Dict[str, Any]:
        """Terminal record for a request that was NEVER admitted (shed,
        cancelled/expired in the queue, torn down with the engine):
        ``tokens`` is just the prompt, TTFT/queue-wait never existed."""
        now = time.monotonic()
        rec = {
            "tokens": list(req.prompt),
            "prompt_len": len(req.prompt),
            "status": status,
            "slot": None,
            "finish_step": self._step_count,
            "ttft_ms": None,
            "finish_time": now,
            "e2e_ms": (now - req.arrival) * 1e3,
            "queue_wait_ms": None,
            "preempted_count": req.preempted_count,
        }
        if reason is not None:
            rec["shed_reason"] = reason
        return rec

    def _finish(self, seq: Sequence, info: Dict[str, Any]) -> None:
        rid = seq.request.rid
        self.sched.finish(seq)
        self.results[rid] = self._result_record(seq, "finished")
        info["finished"].append(rid)
        tel = self.telemetry
        if tel.enabled:
            tel.registry.counter("serving/requests_completed").inc(
                step=self._step_count)
        if self._req_acc is not None:
            slo = self._req_acc.on_finish(seq, self._step_count)
            if slo is not None:
                self.results[rid]["slo"] = slo

    # -- prefill --------------------------------------------------------
    def _prefill(self, seq: Sequence) -> None:
        if seq.shared_len:
            # Warm prompt head (prefix cache hit): the adopted blocks
            # already hold positions [0, shared_len) — only the tail is
            # computed, through the paged cache (TTFT collapses to the
            # unshared remainder).
            self._prefill_tail(seq)
            return
        t = len(seq.request.prompt)
        bucket = seq.bucket
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :t] = seq.request.prompt          # right-pad: causal masking
        with self._put_span(ids, np.int32(t), **self._prompt_ids(seq)):
            dev_ids = jnp.asarray(ids)
            length = jnp.asarray(t, jnp.int32)   # keeps pads invisible
            rng = jax.random.fold_in(self._base_key,
                                     2 * seq.request.rid + 1)
            # Per-bucket detector scope: each bucket's one compile is the
            # expected first trace, so a healthy engine never warns — a
            # retrace under any of these names is a real bug.
            self.engine.recompile_detector.check(
                f"serving.prefill_b{bucket}", dev_ids, length)
        with self._prefill_span(seq, bucket, t) as span:
            tok, counters = self._prefill_and_pack(
                seq, bucket, dev_ids, length, rng, measure=self._measure_kv)
            (first,) = self._fetch(tok, **self._prompt_ids(seq))
            self._note_counters(span, counters)
        self._record_first_token(seq, int(first))

    def _note_counters(self, span, counters) -> None:
        """A model's own counters onto the span of the dispatch that made
        them, fetched only while a profiler session records (a trace is
        all that reads them); called after the token's fetch, so they are
        ready and cost no wait of their own."""
        if counters is not None and profiler_session_live():
            span.set_metadata(**dict(zip(
                self._counter_names, map(int, np.asarray(counters)))))

    # -- the host path of a dispatch, in spans ---------------------------
    # Round each serving program's call: ``batch_inputs`` (the host
    # arrays of a decode-side step), ``put`` (their transfers, the step's
    # key and the recompile detector's check of what the program
    # receives), ``dispatch`` (the jitted call), ``fetch`` (what the host
    # waits for before it can go on) and ``post_step`` (what the host
    # does with it). Spans of one step share ``step``, those of one prompt
    # ``rid``.
    def _prompt_ids(self, seq: Sequence) -> Dict[str, int]:
        return {"step": self._step_count, "rid": seq.request.rid}

    def _put_span(self, *host, **ids):
        """The ``put`` span round the transfers of ``host`` (numpy arrays
        and scalars, as the program receives them)."""
        return self.telemetry.span(
            "put", arrays=len(host),
            bytes=int(sum(np.asarray(a).nbytes for a in host)), **ids)

    def _fetch(self, *devs, **ids):
        """``devs`` to the host under a ``fetch`` span: the blocking
        transfer of what the host needs before it can go on."""
        with self.telemetry.span("fetch", bytes=sum(d.nbytes for d in devs),
                                 **ids):
            return [np.asarray(d) for d in devs]

    def _prefill_and_pack(self, seq: Sequence, bucket: int, dev_ids, length,
                          rng, measure: bool = False):
        """Dispatch the bucket's prefill program and the pack that puts
        what it made where the slot keeps it: the prompt's keys and values
        into the sequence's blocks, a recurrent layer's state into row
        ``seq.slot`` of that layer's arrays, whole (so a slot's earlier
        tenant leaves nothing behind). Returns the sampled token and the
        model's counters (None where it has none), both still on the
        device."""
        if bucket not in self._prefill_jit:
            self._prefill_jit[bucket] = jax.jit(functools.partial(
                self._prefill_impl, bucket=bucket))
        ids = self._prompt_ids(seq)
        with self.telemetry.span("dispatch", program="prefill", **ids):
            tok, _logits, ks, vs, states, counters = \
                self._prefill_jit[bucket](self.engine.params, dev_ids,
                                          length, rng)
        if measure:
            self._emit_kv_quant_error(ks, vs, length, bucket)
        table = np.asarray(seq.block_table, np.int32)
        slot = np.int32(seq.slot)
        with self._put_span(table, slot, **ids):
            table, slot = jnp.asarray(table), jnp.asarray(slot)
        with self.telemetry.span("dispatch", program="pack", **ids):
            self._pools = self._pack_jit(self._pools, table, ks, vs, slot,
                                         states, kinds=self._kinds)
        return tok, counters

    def _prefill_tail(self, seq: Sequence) -> None:
        """Prefill only the unshared prompt tail through the paged cache:
        the tail chunk (right-padded to a block-multiple bucket) runs one
        multi-token paged forward at per-row position ``shared_len`` —
        writes land past the adopted (immutable) head blocks, attention
        sees head + causal tail, and the first token samples from the
        last REAL tail position. The int8 KV quant-error gauge is NOT
        measured here: the adopted head blocks were measured at their
        cold prefill, and the tail's K/V never leave the jitted program
        as stacks (docs/SERVING.md "Current limits")."""
        t = len(seq.request.prompt)
        sl = seq.shared_len
        tail = t - sl                           # >= 1 (match is capped)
        mb_positions = self.max_blocks * self.block_size
        tb = min(self._bucket_of(tail), mb_positions - sl)
        ids = np.zeros((1, tb), np.int32)
        ids[0, :tail] = seq.request.prompt[sl:]
        bt = np.zeros((1, self.max_blocks), np.int32)
        bt[0, :len(seq.block_table)] = seq.block_table
        ids_of = self._prompt_ids(seq)
        with self._put_span(ids, bt, np.int32([sl]), np.int32(tail),
                            **ids_of):
            dev_ids, dev_bt = jnp.asarray(ids), jnp.asarray(bt)
            start = jnp.asarray([sl], jnp.int32)
            length = jnp.asarray(tail, jnp.int32)
            rng = jax.random.fold_in(self._base_key,
                                     2 * seq.request.rid + 1)
            self.engine.recompile_detector.check(
                f"serving.prefill_tail_b{tb}", dev_ids, dev_bt, start,
                length)
        if tb not in self._tail_prefill_jit:
            self._tail_prefill_jit[tb] = jax.jit(functools.partial(
                self._prefill_tail_impl, tail_bucket=tb),
                donate_argnums=(1,))
        with self._prefill_span(seq, tb, t, shared_len=sl):
            with self.telemetry.span("dispatch", program="prefill_tail",
                                     **ids_of):
                tok, self._pools = self._tail_prefill_jit[tb](
                    self.engine.params, self._pools, dev_ids, dev_bt, start,
                    length, rng)
            (first,) = self._fetch(tok, **ids_of)
        self._record_first_token(seq, int(first))

    def _prefill_span(self, seq: Sequence, bucket: int, prompt_len: int,
                      **ids):
        """The ``prefill`` span of one request: dispatch, pack and the
        first token's fetch, with how long the request queued (admitted -
        arrival, this engine's clock)."""
        req = seq.request
        wait_ms = ((req.admitted_time - req.arrival) * 1e3
                   if req.admitted_time is not None else 0.0)
        return self.telemetry.span(
            "prefill", rid=req.rid, bucket=bucket, prompt_len=prompt_len,
            step=self._step_count, queue_wait_ms=wait_ms, **ids)

    def _record_first_token(self, seq: Sequence, first: int) -> None:
        """Append the prefill's sampled token and record TTFT — on the
        request's FIRST prefill only: a preemption restart (cold or
        warm) must not add a second (optimistically small) TTFT
        observation."""
        now = time.monotonic()
        seq.tokens.append(first)
        if seq.request.first_token_time is None:
            seq.request.first_token_time = now
            if self.telemetry.enabled:
                self.telemetry.registry.histogram(
                    "serving/ttft_ms").observe(
                    (now - seq.request.arrival) * 1e3,
                    step=self._step_count)

    def _replay_prefill(self, seq: Sequence, replay: List[int]) -> None:
        """Recovery replay (serving/resilience.py): rebuild ``seq``'s KV
        ``[0, pos)`` in the fresh pools by prefilling its recorded
        ``tokens[:-1]`` — through the SAME per-bucket prefill programs
        as a cold/warm admission (pure functions, kept across the
        rebuild). The sampled token is discarded: under greedy it equals
        the already-recorded ``tokens[-1]``, whose KV is written by the
        next decode step as usual. No TTFT observation, no token
        append, no quant-error measure — the request already paid its
        real prefill."""
        if self._chunked:
            self._replay_chunked(seq, replay)
            return
        t = len(replay)
        rng = jax.random.fold_in(self._base_key, 2 * seq.request.rid + 1)
        if seq.shared_len:
            sl = seq.shared_len
            tail = t - sl
            mb_positions = self.max_blocks * self.block_size
            tb = min(self._bucket_of(tail), mb_positions - sl)
            ids = np.zeros((1, tb), np.int32)
            ids[0, :tail] = replay[sl:]
            bt = np.zeros((1, self.max_blocks), np.int32)
            bt[0, :len(seq.block_table)] = seq.block_table
            dev_ids, dev_bt = jnp.asarray(ids), jnp.asarray(bt)
            start = jnp.asarray([sl], jnp.int32)
            length = jnp.asarray(tail, jnp.int32)
            self.engine.recompile_detector.check(
                f"serving.prefill_tail_b{tb}", dev_ids, dev_bt, start,
                length)
            if tb not in self._tail_prefill_jit:
                self._tail_prefill_jit[tb] = jax.jit(functools.partial(
                    self._prefill_tail_impl, tail_bucket=tb),
                    donate_argnums=(1,))
            with self._prefill_span(seq, tb, t, replay=1):
                _tok, self._pools = self._tail_prefill_jit[tb](
                    self.engine.params, self._pools, dev_ids, dev_bt,
                    start, length, rng)
            return
        bucket = seq.bucket
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :t] = replay
        dev_ids = jnp.asarray(ids)
        length = jnp.asarray(t, jnp.int32)
        self.engine.recompile_detector.check(
            f"serving.prefill_b{bucket}", dev_ids, length)
        with self._prefill_span(seq, bucket, t, replay=1):
            self._prefill_and_pack(seq, bucket, dev_ids, length, rng)

    def _replay_chunked(self, seq: Sequence, replay: List[int]) -> None:
        """Chunked-mode replay: rebuild ``[shared_len, len(replay))`` in
        the fresh pools through the SAME mixed program as live traffic —
        no per-bucket replay variants to compile. Samples are discarded
        (greedy: they equal the recorded tokens); the seq's cursors
        already reflect its pre-crash state, only pool contents need
        rebuilding. Resilience only routes fully-prefilled sequences
        here (a mid-prefill seq is cold-requeued instead)."""
        t0, total = seq.shared_len, len(replay)
        while t0 < total:
            c = min(self._chunk_budget, total - t0)
            rows = [(seq.slot, replay[t0 + i], t0 + i) for i in range(c)]
            with self._prefill_span(seq, seq.bucket, total, replay=1):
                self._mixed_dispatch(*self._mixed_inputs([seq], rows, 1),
                                     len(rows))
            t0 += c

    @device_scope("prefill")
    def _prefill_tail_impl(self, params, pools, ids, bt, start, length,
                           rng, *, tail_bucket: int):
        # The tail writes [start, start + tail_bucket) — block-aligned
        # start, so adopted head blocks are never touched; pad positions
        # past the allocated blocks hit zero table entries (scratch).
        cache = tuple(
            PagedLayerCache(*pools[i], bt, start, self.block_size,
                            self._dtype_name)
            for i in range(self.model_cfg.num_layers))
        pos_ids = jnp.minimum(start[:, None] + jnp.arange(tail_bucket),
                              self.model_cfg.max_seq_len - 1)
        out = self.module.serve_decode(
            self.engine._materialized(params), ids, pos_ids, cache)
        last = jax.lax.dynamic_index_in_dim(out["logits"], length - 1,
                                            axis=1, keepdims=False)  # [1,V]
        tok = sample_logits(last.astype(jnp.float32), rng,
                            self.scfg.temperature, self.scfg.top_k)[0]
        return tok, tuple(c.pools for c in out["cache"])

    @device_scope("prefill")
    def _prefill_impl(self, params, ids, length, rng, *, bucket: int):
        out = self.module.serve_prefill(
            self.engine._materialized(params), ids, length,
            dtype=self._dtype)
        # Right-padded prompt: causality alone keeps pad positions out of
        # every real token's attention, so the last REAL position's logits
        # are exact; pad-position K/V are garbage the position mask hides
        # (and a recurrent layer hands back its state as it stood after
        # position ``length - 1``: the model's business).
        last = jax.lax.dynamic_index_in_dim(out["logits"], length - 1,
                                            axis=1, keepdims=False)  # [1,V]
        tok = sample_logits(last.astype(jnp.float32), rng,
                            self.scfg.temperature, self.scfg.top_k)[0]
        of_kind = lambda kind: [c for c, k in zip(out["cache"], self._kinds)
                                if k == kind]
        kvs = of_kind("kv")
        k_stack = jnp.stack([c[0][0] for c in kvs]) if kvs else None
        v_stack = jnp.stack([c[1][0] for c in kvs]) if kvs else None
        # k_stack, v_stack: [kv layers, Tb, H, D]; a model with no
        # recurrent layer and no counters adds no output to the program
        return (tok, last, k_stack, v_stack, tuple(of_kind("recurrent")),
                out.get("counters"))

    # -- decode ---------------------------------------------------------
    def _decode_round(self, active: List[Sequence],
                      info: Dict[str, Any]):
        """One decode (or speculative) round for the batch: dispatch,
        append accepted tokens, finish rows that completed. Returns
        ``(n_tokens, dt_decode)`` — the dispatch+fetch wall seconds the
        throughput gauge and the accountant both key on. Host-side
        extraction of the step() decode block (the lowered programs are
        untouched); the resilience manager wraps THIS boundary, where a
        failed dispatch has mutated nothing."""
        t_dec = time.perf_counter()
        if self._chunked:
            # The mixed ragged program serves every round that has a
            # prefill chunk in flight — and, without speculative
            # decoding, every round (the all-decode batch is just the
            # degenerate ragged case; one program ever). With spec on,
            # rounds with no chunk in flight fall through to the
            # speculative path (greedy-identical either way).
            prefilling = any(s.prefilled < len(s.request.prompt)
                             for s in active)
            if prefilling or not self._spec_k:
                n_tokens = self._mixed_round(active, info)
                return n_tokens, time.perf_counter() - t_dec
        if self._spec_k:
            n_tokens = self._spec_round(active, info)
            dt_decode = time.perf_counter() - t_dec
        else:
            toks, logits = self._decode(active)
            dt_decode = time.perf_counter() - t_dec
            n_tokens = len(active)
            with self._post_span(info):
                for seq, tok in zip(active, toks):
                    seq.tokens.append(int(tok))
                    seq.pos += 1
                    if seq.finished():
                        self._finish(seq, info)
                if self.capture_logits:
                    info["logits"] = logits
                    info["slots"] = {s.slot: s.request.rid for s in active}
        return n_tokens, dt_decode

    @contextlib.contextmanager
    def _post_span(self, info: Dict[str, Any]):
        """The ``post_step`` span round the host's bookkeeping after a
        step's fetch; ``finished``, the requests the step has finished so
        far, is set as it closes."""
        with self.telemetry.span("post_step", step=self._step_count) as sp:
            yield
            sp.set_metadata(finished=len(info["finished"]))

    def _inject_storm(self) -> None:
        """FaultPlan request storm: a burst of duplicates of the last
        submitted request, through the normal ``submit()`` path — i.e.
        through the shed gate when resilience is on (the overload
        scenario the admission controller exists for)."""
        if self._storm_template is None:
            return
        prompt, max_new, eos, dl = self._storm_template
        n = self._fault.serve_storm_requests
        log_dist(f"serving: FaultPlan request storm — {n} burst "
                 f"submissions at step {self._step_count}", ranks=[0])
        for _ in range(n):
            if self._resil is not None:
                self.submit(prompt, max_new, eos, deadline_ms=dl)
            else:
                self.submit(prompt, max_new, eos)

    def _fault_hook(self) -> None:
        """Serving chaos rides the decode DISPATCH attempt counter:
        monotonic across steps AND retries, so a fault window of width k
        is consumed by k dispatch attempts (a transient fault heals
        under retry; a wider window forces the rebuild path). Raising
        here mutates nothing — pools are only donated by a dispatch
        that actually runs. Shared by the bucketed/spec dispatch prep
        and the chunked mixed dispatch, so chaos covers all three."""
        if self._fault is None:
            return
        self._dispatch_attempts += 1
        if self._fault.should_serve_decode_fault(self._dispatch_attempts):
            self._fault.serve_decode_fault(self._dispatch_attempts)
        if self._fault.should_serve_slow_step(self._dispatch_attempts):
            self._fault.serve_slow_step()

    def _batch_inputs(self, active: List[Sequence]):
        """Host-side decode batch matrices (inactive rows -> scratch)."""
        nb, mb = self.scfg.max_batch_size, self.max_blocks
        bt = np.zeros((nb, mb), np.int32)
        pos = np.zeros((nb,), np.int32)
        toks = np.zeros((nb,), np.int32)
        for seq in active:
            s = seq.slot
            bt[s, :len(seq.block_table)] = seq.block_table
            pos[s] = seq.pos
            toks[s] = seq.tokens[-1]
        return bt, pos, toks

    def _table_inputs(self, active: List[Sequence], chunk: int):
        """Host inputs of the programs that read the table's width (the
        kernel and window decodes, every speculative round): the fault
        hook, the batch matrices and the position counts."""
        self._fault_hook()
        bt, pos, toks = self._batch_inputs(active)
        if self._attn_impl == "kernel":
            self.stats["kernel_steps"] += 1
        # once this dispatch has written, row r holds pos[r] + chunk
        ids = self._count_positions(
            len(active), live=int(pos.sum()) + len(active) * chunk,
            read=bt.size * self.block_size)
        return (bt, pos, toks), ids

    def _live_inputs(self, active: List[Sequence]):
        """Host inputs of the default decode: the batch matrices and,
        beside them, the flat list of the batch's live blocks
        (kv_cache.live_block_list) that the program attends over. One
        signature whatever is live."""
        self._fault_hook()
        bt, pos, toks = self._batch_inputs(active)
        live, n_live, n_chunks = live_block_list(
            ((s.slot, s.block_table, s.pos) for s in active),
            self.block_size, self.LIVE_RUN_BLOCKS, self.LIVE_CHUNK_RUNS,
            self._live_chunks)
        # once this dispatch has written, row r holds pos[r] + 1
        ids = self._count_positions(
            len(active), live=int(pos.sum()) + len(active),
            read=n_chunks * self.live_chunk_positions,
            live_blocks=n_live, chunks=n_chunks)
        return (bt, pos, toks, live, np.int32(n_chunks)), ids

    def _decode_programs(self) -> int:
        """How many of the decode-side programs (decode, speculative,
        mixed) this engine has built."""
        return sum(jit is not None for jit in (
            self._decode_jit, self._spec_jit, self._mixed_jit))

    def _count_positions(self, active: int, live: int, read: int,
                         **more: int) -> Dict[str, int]:
        """Add one decode dispatch to the running totals of positions read
        and of those that are live (and of whatever else it counts), and
        return what its span carries."""
        counts = dict(live_positions=live, read_positions=read, **more)
        for name, n in counts.items():
            self.stats[name] += n
        return {"step": self._step_count, "active": active, **counts}

    def _decode(self, active: List[Sequence]):
        step = self._step_count
        with self.telemetry.span("batch_inputs", step=step,
                                 active=len(active)):
            if self._attn_impl in ("kernel", "window"):
                host, ids = self._table_inputs(active, 1)
            else:
                host, ids = self._live_inputs(active)
            more = {}
            if not self._all_kv:
                # which rows hold a request: a dead slot's recurrent state
                # stays as it is and its row is routed to no expert
                alive = np.zeros((self.scfg.max_batch_size,), bool)
                alive[[s.slot for s in active]] = True
                more["alive"] = alive
                if self._stateful:
                    ids["state_slots_live"] = len(active)
        with self._put_span(*host, *more.values(), step=step):
            bt, pos, toks, *live = map(jnp.asarray, host)
            more = {k: jnp.asarray(v) for k, v in more.items()}
            rng = jax.random.fold_in(self._base_key, 2 * step)
            self.engine.recompile_detector.check(
                "serving.decode_step", bt, pos, toks, *live)
        if self._decode_jit is None:
            self._decode_jit = jax.jit(
                functools.partial(self._decode_impl,
                                  attn_impl=self._attn_impl),
                donate_argnums=(1,))
        with self.telemetry.span("decode_step", **ids) as span:
            with self.telemetry.span("dispatch", program="decode",
                                     step=step):
                tok_dev, logits, self._pools, counters = self._decode_jit(
                    self.engine.params, self._pools, bt, pos, toks, rng,
                    *live, **more)
            # host fetch: finish checks
            (tok_host,) = self._fetch(tok_dev, step=step)
            self._note_counters(span, counters)
        logits_host = np.asarray(logits) if self.capture_logits else None
        return [int(tok_host[s.slot]) for s in active], logits_host

    @device_scope("decode")
    def _decode_impl(self, params, pools, bt, pos, toks, rng, live=None,
                     n_chunks=None, alive=None, *,
                     attn_impl: str = "gather"):
        def view(kind, pool):
            if kind == "kv":
                return PagedLayerCache(*pool, bt, pos, self.block_size,
                                       self._dtype_name, attn_impl,
                                       live=live, n_chunks=n_chunks)
            return (RecurrentLayerState(pool, alive)
                    if kind == "recurrent" else None)

        cache = tuple(map(view, self._kinds, pools))
        out = self.module.serve_decode(
            self.engine._materialized(params), toks[:, None], pos[:, None],
            cache, **({} if alive is None else {"live": alive}))
        logits = out["logits"][:, -1].astype(jnp.float32)
        tok = sample_logits(logits, rng, self.scfg.temperature,
                            self.scfg.top_k)
        kept = tuple(None if c is None else c.pools for c in out["cache"])
        return tok, logits, kept, out.get("counters")

    # -- chunked prefill (the mixed ragged round) -----------------------
    def _mixed_round(self, active: List[Sequence],
                     info: Dict[str, Any]) -> int:
        """One mixed step: every decoding sequence advances one token
        AND waiting prompts prefill in chunks, all through ONE ragged
        program. Rows: decode tokens first (each decoding slot must
        advance — the token budget is validated >= max_batch_size),
        then prefill chunks FCFS by admission until the budget is full.
        A prompt whose last chunk lands this step samples its first
        token from that chunk's final row — exactly the logits the
        bucketed prefill samples from, so outputs are token-identical.
        Returns the number of tokens appended."""
        if self.capture_logits:
            raise ValueError(
                "capture_logits is not supported with chunked prefill — "
                "a mixed step has no per-slot logits row to expose "
                "(docs/SERVING.md)")
        step = self._step_count
        with self.telemetry.span("batch_inputs", step=step,
                                 active=len(active)):
            decoding = [s for s in active
                        if s.prefilled >= len(s.request.prompt)]
            prefilling = sorted(
                (s for s in active if s.prefilled < len(s.request.prompt)),
                key=lambda s: (s.admitted_step, s.request.rid))
            self._fault_hook()   # live rounds only — replay never injects
            rows = [(s.slot, s.tokens[-1], s.pos) for s in decoding]
            chunks = []                          # (seq, first_row, count)
            for s in prefilling:
                if len(rows) >= self._chunk_budget:
                    break
                t0 = s.prefilled
                c = min(len(s.request.prompt) - t0,
                        self._chunk_budget - len(rows))
                chunks.append((s, len(rows), c))
                rows.extend((s.slot, s.request.prompt[t0 + i], t0 + i)
                            for i in range(c))
            host, ids = self._mixed_inputs(active, rows, len(active))
        tok_host = self._mixed_dispatch(host, ids, len(rows))
        self._chunk_tokens_last = len(rows)
        appended = 0
        with self._post_span(info):
            for r, seq in enumerate(decoding):
                seq.tokens.append(int(tok_host[r]))
                seq.pos += 1
                appended += 1
                if seq.finished():
                    self._finish(seq, info)
            for seq, r0, c in chunks:
                seq.prefilled += c
                seq.pos = seq.prefilled
                if seq.prefilled == len(seq.request.prompt):
                    # Prompt complete: the chunk's final row sits at the
                    # last prompt position — its sampled token is the
                    # first generated token (TTFT lands here).
                    self._record_first_token(seq,
                                             int(tok_host[r0 + c - 1]))
                    appended += 1
                    if self._req_acc is not None:
                        self._req_acc.on_prefilled(seq)
                    self.sched.register_prefix(seq, self._step_count)
                    info["prefilled"].append(seq.request.rid)
                    if seq.finished():   # max_new_tokens == 1 / instant EOS
                        self._finish(seq, info)
        return appended

    def _mixed_inputs(self, table_seqs: List[Sequence], rows,
                      n_active: int):
        """Host inputs of one ragged token batch. ``rows``: ``(slot,
        token, position)`` triples (decode rows then chunk rows); the
        batch is padded to the token budget with scratch rows — slot
        ``max_batch_size`` maps to the spare all-zeros table row, so pad
        writes land in the reserved scratch block and pad reads stay
        masked."""
        nb, mb = self.scfg.max_batch_size, self.max_blocks
        bt = np.zeros((nb + 1, mb), np.int32)    # row nb: pad/scratch row
        toks = np.zeros((self._chunk_budget,), np.int32)
        pos = np.zeros((self._chunk_budget,), np.int32)
        slots = np.full((self._chunk_budget,), nb, np.int32)
        for seq in table_seqs:
            bt[seq.slot, :len(seq.block_table)] = seq.block_table
        for r, (sl, tk, p) in enumerate(rows):
            slots[r], toks[r], pos[r] = sl, tk, p
        # every token of the budget reads its row of the table; a real
        # token at position p sees p + 1 keys
        ids = self._count_positions(
            n_active, live=int(pos.sum()) + len(rows),
            read=self._chunk_budget * mb * self.block_size)
        return (bt, pos, toks, slots), ids

    def _mixed_dispatch(self, host, ids: Dict[str, int], n_rows: int):
        """Dispatch one ragged token batch (``_mixed_inputs``). ONE
        detector scope, ONE jit entry, ever: every mixed step has the same
        signature regardless of the decode/prefill mix."""
        step = self._step_count
        with self._put_span(*host, step=step):
            bt, pos, toks, slots = map(jnp.asarray, host)
            self.engine.recompile_detector.check("serving.mixed_step", toks,
                                                 pos, slots, bt)
            rng = jax.random.fold_in(self._base_key, 2 * step)
        if self._mixed_jit is None:
            self._mixed_jit = jax.jit(self._mixed_impl,
                                      donate_argnums=(1,))
        with self.telemetry.span("mixed_step", tokens=n_rows, **ids):
            with self.telemetry.span("dispatch", program="mixed",
                                     step=step):
                tok_dev, self._pools = self._mixed_jit(
                    self.engine.params, self._pools, bt, pos, slots, toks,
                    rng)
            # host fetch: finish checks
            (tok_host,) = self._fetch(tok_dev, step=step)
        return tok_host

    @device_scope("decode")
    def _mixed_impl(self, params, pools, bt, pos, slots, toks, rng):
        max_pos = self.model_cfg.max_seq_len - 1
        cache = tuple(
            ChunkedLayerCache(*pools[i], bt, slots, pos, self.block_size,
                              self._dtype_name)
            for i in range(self.model_cfg.num_layers))
        out = self.module.serve_decode(
            self.engine._materialized(params), toks[None, :],
            jnp.minimum(pos, max_pos)[None, :], cache)
        logits = out["logits"][0].astype(jnp.float32)      # [T, V]
        tok = sample_logits(logits, rng, self.scfg.temperature,
                            self.scfg.top_k)
        return tok, tuple(c.pools for c in out["cache"])

    # -- speculative decoding -------------------------------------------
    def _init_speculative(self) -> None:
        """Draft model = a truncated-layer view of the target (the
        config-named default): the first ``draft_layers`` blocks plus the
        shared embeddings/final-LN/head, applied with the SAME params by
        top-level key. Because the draft's layer stack IS the target's
        prefix, its per-layer K/V are identical to the target's for the
        same inputs — so the draft reads and writes the target's own
        pools for its layers: no second KV cache, no draft prefill, and
        the verify step's rewrites are bit-identical no-ops for accepted
        tokens."""
        from dataclasses import replace as dc_replace

        cfg = self.model_cfg
        if self.scfg.temperature != 0.0:
            raise ValueError("speculative decoding requires greedy "
                             "sampling (serving.temperature == 0)")
        dl = (self.scfg.spec_draft_layers
              if self.scfg.spec_draft_layers is not None
              else max(1, cfg.num_layers // 2))
        if not 1 <= dl < cfg.num_layers:
            raise ValueError(
                f"serving.speculative.draft_layers must be in "
                f"[1, {cfg.num_layers - 1}] for a {cfg.num_layers}-layer "
                f"target, got {dl}")
        self._spec_k = int(self.scfg.spec_k)
        self._draft_layers = dl
        self._draft_module = type(self.module)(
            dc_replace(cfg, num_layers=dl))
        keys = ["wte", "wpe", "ln_f"] + [f"h_{i}" for i in range(dl)]
        if not getattr(cfg, "tie_embeddings", True):
            keys.append("lm_head")
        self._draft_param_keys = tuple(keys)
        log_dist(f"serving: speculative decode on — draft = first {dl}/"
                 f"{cfg.num_layers} layers, k={self._spec_k}", ranks=[0])

    def _spec_round(self, active: List[Sequence],
                    info: Dict[str, Any]) -> int:
        """One speculative round for the whole batch: the draft proposes
        ``k`` tokens (one jitted scan — its writes land in the shared
        pools), ONE target verification scores all ``k+1`` positions
        through the paged cache, and the standard greedy accept rule
        keeps outputs token-identical to non-speculative decode: a draft
        token is kept iff it equals the target's greedy choice at that
        position, and the first disagreement is replaced by the target's
        own token. Rejected positions simply stay behind the write
        cursor (``seq.pos``) — masked now, overwritten by the next
        round's chunk. Returns the number of tokens appended."""
        if self.capture_logits:
            raise ValueError(
                "capture_logits is not supported with speculative "
                "decoding — a spec round has no single per-step logits "
                "row to expose (docs/SERVING.md)")
        k = self._spec_k
        step = self._step_count
        with self.telemetry.span("batch_inputs", step=step,
                                 active=len(active)):
            host, ids = self._table_inputs(active, k + 1)
        with self._put_span(*host, step=step):
            bt, pos, toks = map(jnp.asarray, host)
            self.engine.recompile_detector.check("serving.spec_step", bt,
                                                 pos, toks)
        if self._spec_jit is None:
            self._spec_jit = jax.jit(
                functools.partial(self._spec_impl, k=k,
                                  attn_impl=self._attn_impl),
                donate_argnums=(1,))
        with self.telemetry.span("spec_step", k=k, **ids):
            with self.telemetry.span("dispatch", program="spec",
                                     step=step):
                chunk_dev, greedy_dev, self._pools = self._spec_jit(
                    self.engine.params, self._pools, bt, pos, toks)
            # [B, k+1] verify inputs, [B, k+1] target argmax
            chunk, greedy = self._fetch(chunk_dev, greedy_dev, step=step)
        appended = 0
        with self._post_span(info):
            for seq in active:
                s = seq.slot
                drafted = chunk[s, 1:]           # d_1..d_k
                target = greedy[s]               # g_1..g_{k+1}
                accept = 0
                while accept < k and \
                        int(drafted[accept]) == int(target[accept]):
                    accept += 1
                self.stats["spec_proposed"] += k
                self.stats["spec_accepted"] += accept
                # d_1..d_a are the target's own greedy tokens (they
                # matched); g_{a+1} is the correction/bonus — every
                # appended token is exactly what greedy non-speculative
                # decode would emit.
                for tok in list(drafted[:accept]) + [target[accept]]:
                    seq.tokens.append(int(tok))
                    seq.pos += 1
                    appended += 1
                    if seq.finished():
                        self._finish(seq, info)
                        break
            self.stats["spec_rounds"] += 1
            self.stats["spec_new_tokens"] += appended
        return appended

    @device_scope("decode")
    def _spec_impl(self, params, pools, bt, pos, toks, *, k: int,
                   attn_impl: str):
        """Draft scan (k+1 single-token steps — the extra step pre-writes
        the full-accept position so the draft cache never lags) + ONE
        multi-query target verification over the chunk ``[t0, d_1..d_k]``
        at positions ``pos..pos+k``. Writes are clamp-guarded: lookahead
        past a row's allocated blocks lands in scratch."""
        p = self.engine._materialized(params)
        dp = {key: p[key] for key in self._draft_param_keys}
        dl = self._draft_layers
        nl = self.model_cfg.num_layers
        bs = self.block_size
        max_pos = self.model_cfg.max_seq_len - 1

        def draft_step(carry, j):
            pools_c, cur = carry
            cache = tuple(
                PagedLayerCache(*pools_c[i], bt, pos + j, bs,
                                self._dtype_name, attn_impl,
                                clamp_writes=True)
                for i in range(dl))
            out = self._draft_module.serve_decode(
                dp, cur[:, None], jnp.minimum(pos + j, max_pos)[:, None],
                cache)
            nxt = jnp.argmax(out["logits"][:, -1].astype(jnp.float32),
                             axis=-1).astype(jnp.int32)
            new_pools = tuple(out["cache"][i].pools if i < dl else pools_c[i]
                              for i in range(nl))
            return (new_pools, nxt), cur

        (pools, _), inputs = jax.lax.scan(draft_step, (pools, toks),
                                          jnp.arange(k + 1))
        chunk = inputs.T                              # [B, k+1] t0,d_1..d_k
        pos_ids = jnp.minimum(pos[:, None] + jnp.arange(k + 1), max_pos)
        cache = tuple(
            PagedLayerCache(*pools[i], bt, pos, bs, self._dtype_name,
                            attn_impl, clamp_writes=True)
            for i in range(nl))
        out = self.module.serve_decode(p, chunk, pos_ids, cache)
        greedy = jnp.argmax(out["logits"].astype(jnp.float32),
                            axis=-1).astype(jnp.int32)   # [B, k+1]
        return chunk, greedy, tuple(c.pools for c in out["cache"])

    # -- telemetry ------------------------------------------------------
    def _emit_kv_quant_error(self, ks, vs, length, bucket: int) -> None:
        """``numerics/kv_quant_rel_err`` / ``_max_abs_err``: RTNE
        round-trip error of the per-(token, head) int8 quantization the
        pool stores (block = head_dim, the quantize_chunk layout),
        measured over the REAL prompt positions (pads are masked to
        zero, and zero blocks round-trip exactly — they contribute
        nothing to either norm). One jitted measure per prompt bucket
        and ONE device_get for both scalars, on the prefill path that
        already pays a first-token fetch; gated on the numerics opt-in
        (``_measure_kv``). The measured evidence behind the int8-KV
        accuracy/bandwidth trade (docs/OBSERVABILITY.md "Numerics
        observatory")."""
        from deepspeed_tpu.comm.quantize import roundtrip_error

        if bucket not in self._kv_err_jit:
            def measure(ks_, vs_, length_):
                # ks_/vs_: [L, bucket, H, D]; mask pad positions.
                mask = (jnp.arange(ks_.shape[1]) < length_)[None, :, None,
                                                            None]
                kz = jnp.where(mask, ks_.astype(jnp.float32), 0.0)
                vz = jnp.where(mask, vs_.astype(jnp.float32), 0.0)
                head_dim = kz.shape[-1]
                rk, mk = roundtrip_error(kz, 8, head_dim)
                rv, mv = roundtrip_error(vz, 8, head_dim)
                return jnp.maximum(rk, rv), jnp.maximum(mk, mv)

            self._kv_err_jit[bucket] = jax.jit(measure)
        rel, mab = jax.device_get(self._kv_err_jit[bucket](ks, vs, length))
        reg = self.telemetry.registry
        reg.gauge("numerics/kv_quant_rel_err").set(
            float(rel), step=self._step_count, bucket=bucket)
        reg.gauge("numerics/kv_quant_max_abs_err").set(
            float(mab), step=self._step_count, bucket=bucket)

    def _emit_step_metrics(self, n_active: int, dt_decode: float,
                           n_tokens: int) -> None:
        """``dt_decode``: wall seconds of the decode dispatch+fetch only —
        the throughput gauge means DECODE tokens/s, so prefill/admission
        time on the same step must not dilute it. ``n_tokens``: tokens
        appended this step (== active rows, except speculative rounds
        append up to k+1 per row)."""
        tel = self.telemetry
        if not tel.enabled:
            return
        reg = tel.registry
        step = self._step_count
        reg.gauge("serving/batch_occupancy").set(
            n_active / self.scfg.max_batch_size, step=step)
        reg.gauge("serving/kv_blocks_in_use").set(self.pool.used_blocks,
                                                  step=step)
        reg.gauge("serving/queue_depth").set(self.sched.queue_depth,
                                             step=step)
        if n_tokens and dt_decode > 0:
            reg.gauge("serving/tokens_per_sec").set(
                self._decode_tokens / self._decode_sec, step=step)
        # Request observatory rides here (only when the accountant is on,
        # so the telemetry.requests=off tag set stays byte-identical):
        # the rolling-window throughput gauge — responsive under changing
        # load where the cumulative mean above goes stale — plus the
        # requests/* category + engine-partition gauges.
        acc = self._req_acc
        if acc is not None:
            if n_tokens and dt_decode > 0:
                acc.rolling_add(n_tokens, dt_decode)
            rate = acc.rolling_rate()
            if rate is not None:
                reg.gauge("serving/tokens_per_sec_window").set(rate,
                                                               step=step)
            acc.emit(step)
        pre = self.sched.preempted_total
        ctr = reg.counter("serving/preempted_seqs")
        if pre > ctr.total:
            ctr.inc(pre - ctr.total, step=step)
        # -- fast-path attribution (only when the piece is on: the tag
        # set a disabled engine emits stays what it was) ----------------
        if self.scfg.decode_attention != "gather" and n_active:
            reg.gauge("serving/decode_attn_kernel").set(
                1.0 if self._attn_impl == "kernel" else 0.0, step=step)
        if self.prefix_cache is not None:
            for tag, total in (
                    ("serving/prefix_hits", self.prefix_cache.hits),
                    ("serving/prefix_blocks_reused",
                     self.prefix_cache.blocks_reused)):
                ctr = reg.counter(tag)
                if total > ctr.total:
                    ctr.inc(total - ctr.total, step=step)
        if self._spec_k and self.stats["spec_rounds"]:
            reg.gauge("serving/spec_accept_rate").set(
                self.stats["spec_accepted"]
                / max(1, self.stats["spec_proposed"]), step=step)
            reg.gauge("serving/spec_tokens_per_verify").set(
                self.stats["spec_new_tokens"] / self.stats["spec_rounds"],
                step=step)
        # -- resilience transitions (only when the manager exists: the
        # serving.resilience=off tag set stays byte-identical) ----------
        if self._resil is not None:
            reg.gauge("serving/degraded_level").set(
                self._resil.degraded_level, step=step)
            c = self._resil.counters
            for tag, total in (
                    ("serving/shed_requests", c["shed_requests"]),
                    ("serving/deadline_expired", c["deadline_expired"]),
                    ("serving/cancelled", c["cancelled"]),
                    ("serving/recoveries", c["recoveries"]),
                    ("serving/retries", c["retries"])):
                ctr = reg.counter(tag)
                if total > ctr.total:
                    ctr.inc(total - ctr.total, step=step)
        # -- chunked-prefill admission (only when the mode is on: the
        # serving.chunked_prefill=off tag set stays byte-identical) -----
        if self._chunked:
            reg.gauge("serving/chunked_tokens_per_step").set(
                self._chunk_tokens_last, step=step)
            reg.gauge("serving/prefill_chunks_in_flight").set(
                sum(1 for s in self.sched.running.values()
                    if s.prefilled < len(s.request.prompt)), step=step)

    def close(self) -> None:
        """Flush AND close the telemetry this engine drives (sink file
        handles, tracer, request records) — init_serving hands the
        engine ownership. Any request still in flight or queued gets a
        terminal ``aborted`` record first: every submitted rid resolves
        through ``results``, even through a teardown."""
        for seq in list(self.sched.running.values()):
            rid = seq.request.rid
            self.sched.abort(seq)
            self.results[rid] = self._result_record(seq, "aborted")
            if self._req_acc is not None:
                slo = self._req_acc.on_finish(seq, self._step_count,
                                              status="aborted")
                if slo is not None:
                    self.results[rid]["slo"] = slo
        while self.sched.waiting:
            req = self.sched.waiting.popleft()
            self.results[req.rid] = self._queue_record(req, "aborted")
            if self._req_acc is not None:
                self._req_acc.on_drop(req, "aborted", self._step_count)
        if self._req_acc is not None:
            self._req_acc.close()
        self.telemetry.close()
