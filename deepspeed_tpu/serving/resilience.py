"""Serving resilience — deadlines, admission control, in-flight recovery.

The serving-side counterpart of the training stack's guardrails +
elasticity (PRs 3/13): the ServeEngine owns exactly one
:class:`ResilienceManager` (or ``None`` — the ``serving.resilience`` off
state, which keeps every engine hook a single attribute check and the
emitted tag set + lowered decode program byte-identical). Four composable
pieces, all driven at decode-step boundaries:

- **Deadlines + cancellation** — ``submit(deadline_ms=...)`` stamps an
  absolute monotonic deadline on the request; ``cancel(rid)`` flags one
  for removal. Both resolve at the next step boundary: a queued request
  is dropped without admission, a running sequence is aborted with its
  partial output kept, KV blocks and prefix-cache refs released exactly
  once (``Scheduler.abort`` → ``BlockPool.release``, whose refcounts
  raise on double-free — the leak assertion is structural). Terminal
  statuses: ``deadline_expired`` / ``cancelled``.
- **SLO-aware admission control + load shedding** — at submit time the
  projected queue wait (pending decode tokens over the RequestAccountant
  rolling tokens/s window, falling back to the engine's cumulative rate)
  is compared against ``max_queue_wait_ms``; past it the request is
  **shed**: it gets a real rid, a terminal ``results[rid]`` record with
  status ``shed`` and the gate's reason, and a requests.jsonl record —
  but never a queue slot, so admitted requests keep their p99.
  ``max_queue_depth`` is the hard backstop when no rate evidence exists
  yet.
- **Recovery from a failed decode dispatch** — an exception out of the
  decode/spec dispatch first retries through the shared
  ``guardrails/retry.py`` exponential backoff (transient faults heal
  in-place: nothing was mutated, the pools donate only on a successful
  dispatch entry). On exhaustion the manager **rebuilds in-process**:
  fresh BlockPool + paged device pools + prefix cache, decode jit caches
  dropped, and every live sequence **replayed** from its recorded
  prompt+generated tokens — a prefill over ``tokens[:-1]`` reconstructs
  KV ``[0, pos)`` exactly (the sampled token is discarded; under greedy
  it equals the already-recorded ``tokens[-1]``), warm-started through
  the fresh prefix cache as earlier replays populate it. A sequence that
  cannot replay (pool too tight) cold-requeues via the scheduler's
  always-correct preemption path. A fault that persists past the rebuild
  propagates loudly — recovery never loops.
- **Degradation ladder** — every anomaly (a recovery event, or a decode
  step slower than ``slow_step_ms``) feeds an escalating ladder, one rung
  per ``degrade_after`` anomalies: (1) speculative decoding off, (2)
  decode attention kernel → gather fallback, (3) admission batch cap
  halved (``Scheduler.slot_cap`` — no program recompile, capped slots are
  padding-masked like any idle slot). Rungs never un-climb within a
  process; the ``serving/degraded_level`` gauge is the operator's signal
  to rotate the replica.

Chaos comes from the same :class:`~deepspeed_tpu.resilience.fault.FaultPlan`
the training loop uses — ``serve_decode_fault_at_step`` /
``serve_slow_step_at_step`` (keyed on the engine's monotonic decode
dispatch-attempt counter, so retries consume the fault window) and
``serve_storm_at_step`` (a burst of duplicate submissions through the
normal ``submit`` path, i.e. through the shed gate). Injection is
independent of this manager: a fault with resilience OFF crashes the
serve loop — the motivating failure this module exists to absorb.

Every transition lands as ``serving/{shed_requests,deadline_expired,
cancelled,recoveries,retries,degraded_level}`` (emitted only when the
manager exists) and as a terminal ``status`` on the request record.
docs/SERVING.md "Serving under failure" is the operator story.
"""

import collections
import time
from typing import Any, Dict, List, Optional

from deepspeed_tpu.guardrails.retry import retry_call
from deepspeed_tpu.serving.scheduler import Request, Sequence
from deepspeed_tpu.utils.logging import logger

# Terminal statuses a request record can carry ("finished" is the happy
# path stamped by the engine itself).
TERMINAL_STATUSES = ("finished", "shed", "deadline_expired", "cancelled",
                     "aborted")


class ResilienceManager:
    """Per-engine serving resilience policy (docs/SERVING.md
    "Serving under failure").

    Host-side python only — admission math, deque surgery, counters.
    The single device-facing action is the rebuild path, which reuses
    the engine's own prefill programs to replay live sequences.
    """

    def __init__(self, engine):
        self.engine = engine
        self.cfg = engine.scfg
        self.counters: Dict[str, int] = {
            "shed_requests": 0, "deadline_expired": 0, "cancelled": 0,
            "recoveries": 0, "retries": 0,
        }
        self.degraded_level = 0
        self.anomalies = 0
        self._cancel_pending: set = set()

    # ------------------------------------------------------------------
    # admission control / load shedding
    # ------------------------------------------------------------------
    def _projected_wait_ms(self) -> Optional[float]:
        """Pending decode tokens over the measured decode rate: the
        rolling accountant window when the observatory is on (responsive
        under changing load), else the engine's cumulative token-weighted
        rate. None before any decode evidence — a cold engine never
        sheds on projection."""
        eng = self.engine
        rate = None
        if eng._req_acc is not None:
            rate = eng._req_acc.rolling_rate()
        if rate is None and eng._decode_sec > 0:
            rate = eng._decode_tokens / eng._decode_sec
        if not rate or rate <= 0:
            return None
        sched = eng.sched
        pending = sum(r.max_new_tokens for r in sched.waiting)
        pending += sum(
            max(0, s.request.max_new_tokens - s.generated)
            for s in sched.running.values())
        return pending / rate * 1e3

    def admission_gate(self, prompt: List[int],
                       max_new_tokens: int) -> Optional[str]:
        """Returns a shed reason, or None to admit to the queue."""
        depth = self.cfg.resil_max_queue_depth
        if depth is not None and self.engine.sched.queue_depth >= depth:
            return (f"queue depth {self.engine.sched.queue_depth} >= "
                    f"max_queue_depth {depth}")
        wait_ms = self.cfg.resil_max_queue_wait_ms
        if wait_ms is not None:
            projected = self._projected_wait_ms()
            if projected is not None and projected > wait_ms:
                return (f"projected queue wait {projected:.0f}ms > "
                        f"max_queue_wait_ms {wait_ms:.0f}ms")
        return None

    def shed(self, prompt: List[int], max_new_tokens: int,
             eos_token_id: Optional[int], reason: str) -> int:
        """Terminal-record a request WITHOUT queueing it. It still draws
        a real rid so every submission resolves through ``results``."""
        eng = self.engine
        rid = eng.sched.reserve_rid()
        req = Request(rid, list(prompt), int(max_new_tokens), eos_token_id)
        self.counters["shed_requests"] += 1
        eng.results[rid] = eng._queue_record(req, "shed", reason=reason)
        if eng._req_acc is not None:
            eng._req_acc.on_drop(req, "shed", eng._step_count)
        logger.warning("serving: shed request %d (%s)", rid, reason)
        return rid

    # ------------------------------------------------------------------
    # deadlines + cancellation (step-boundary resolution)
    # ------------------------------------------------------------------
    def request_cancel(self, rid: int) -> bool:
        eng = self.engine
        if rid in eng.results:
            return False
        known = any(r.rid == rid for r in eng.sched.waiting) or any(
            s.request.rid == rid for s in eng.sched.running.values())
        if not known:
            return False
        self._cancel_pending.add(rid)
        return True

    def process_boundary(self) -> None:
        """Resolve pending cancellations and expired deadlines — called
        once at the top of every ``step()``. Queue first (a queued drop
        never touches the pool), then running sequences (aborted with
        partial output; blocks released exactly once via
        ``Scheduler.abort``)."""
        eng = self.engine
        sched = eng.sched
        # A cancel that raced a natural finish is already terminal.
        self._cancel_pending -= set(eng.results)
        if not self._cancel_pending and not any(
                r.deadline is not None for r in sched.waiting) and not any(
                s.request.deadline is not None
                for s in sched.running.values()):
            return
        now = time.monotonic()
        if sched.waiting:
            keep: collections.deque = collections.deque()
            for req in sched.waiting:
                if req.rid in self._cancel_pending:
                    self._cancel_pending.discard(req.rid)
                    self._drop_queued(req, "cancelled")
                elif req.deadline is not None and now >= req.deadline:
                    self._drop_queued(req, "deadline_expired")
                else:
                    keep.append(req)
            sched.waiting = keep
        for seq in list(sched.running.values()):
            rid = seq.request.rid
            if rid in self._cancel_pending:
                self._cancel_pending.discard(rid)
                self._abort(seq, "cancelled")
            elif (seq.request.deadline is not None
                  and now >= seq.request.deadline):
                self._abort(seq, "deadline_expired")

    def _drop_queued(self, req: Request, status: str) -> None:
        eng = self.engine
        self.counters[status] += 1
        eng.results[req.rid] = eng._queue_record(req, status)
        if eng._req_acc is not None:
            eng._req_acc.on_drop(req, status, eng._step_count)

    def _abort(self, seq: Sequence, status: str) -> None:
        """Terminal-abort a RUNNING sequence: slot + KV blocks released
        exactly once (pool refcounts raise on a double release), partial
        output kept in the record."""
        eng = self.engine
        eng.sched.abort(seq)
        self.counters[status] += 1
        eng.results[seq.request.rid] = eng._result_record(seq, status)
        if eng._req_acc is not None:
            slo = eng._req_acc.on_finish(seq, eng._step_count,
                                         status=status)
            if slo is not None:
                eng.results[seq.request.rid]["slo"] = slo

    # ------------------------------------------------------------------
    # decode recovery + degradation ladder
    # ------------------------------------------------------------------
    def run_decode(self, active: List[Sequence], info: Dict[str, Any]):
        """The guarded decode round: dispatch, and on failure retry →
        rebuild+replay → one final unguarded dispatch (a persistent
        fault propagates loudly). Returns ``(n_tokens, dt_decode,
        active)`` — recovery can shrink the live set (cold requeues)."""
        eng = self.engine
        try:
            n_tokens, dt = eng._decode_round(active, info)
            return n_tokens, dt, active
        except Exception as e:  # noqa: BLE001 — the recovery entry point
            logger.warning("serving: decode dispatch failed (%s); "
                           "entering recovery", e)

        if self.cfg.resil_max_retries > 0:
            def _attempt():
                self.counters["retries"] += 1
                return eng._decode_round(active, info)

            try:
                n_tokens, dt = retry_call(
                    _attempt,
                    max_retries=self.cfg.resil_max_retries - 1,
                    base=self.cfg.resil_retry_base_sec, jitter=0.0,
                    retry_on=(Exception,),
                    describe="serving decode dispatch")
                self.note_anomaly()
                return n_tokens, dt, active
            except Exception:  # noqa: BLE001 — exhausted: rebuild next
                logger.warning(
                    "serving: decode retries exhausted (%d); rebuilding "
                    "decode state in-process",
                    self.cfg.resil_max_retries)

        self.counters["recoveries"] += 1
        self.note_anomaly()
        self._rebuild_and_replay()
        # Mirror the step boundary's capacity pass against the FRESH
        # block tables (a replay bucket may sit exactly at the next
        # write position), then dispatch unguarded.
        sched = eng.sched
        for seq in list(sched.active):
            if sched.running.get(seq.slot) is seq:
                sched.ensure_capacity(seq, lookahead=eng._spec_k)
        active = sched.active
        if not active:
            return 0, 0.0, active
        n_tokens, dt = eng._decode_round(active, info)
        return n_tokens, dt, active

    def note_step(self, dt_decode: float) -> None:
        """Slow-step anomaly: a decode dispatch past ``slow_step_ms``
        feeds the ladder (the straggler-step signal — on real pods a
        wedged core shows up exactly here)."""
        th = self.cfg.resil_slow_step_ms
        if th is not None and dt_decode * 1e3 > th:
            logger.warning("serving: slow decode step (%.1fms > %.1fms)",
                           dt_decode * 1e3, th)
            self.note_anomaly()

    def note_anomaly(self) -> None:
        self.anomalies += 1
        while (self.degraded_level < 3
               and self.anomalies >= self.cfg.resil_degrade_after
               * (self.degraded_level + 1)):
            self._escalate()

    def _escalate(self) -> None:
        """One ladder rung: trade throughput features for stability.
        Rungs never un-climb — a replica that had to degrade is a
        replica the operator should rotate, and flapping features back
        on under the same anomaly source would thrash."""
        eng = self.engine
        self.degraded_level += 1
        lvl = self.degraded_level
        if lvl == 1:
            eng._spec_k = 0
            if eng._req_acc is not None:
                eng._req_acc.spec_k = 0
            action = "speculative decoding off"
        elif lvl == 2:
            eng._attn_impl = "gather"
            eng._decode_jit = eng._spec_jit = None
            # expected: the default decode takes the live-block list
            eng.engine.recompile_detector.forget("serving.decode_step")
            action = "decode attention kernel -> gather"
        else:
            eng.sched.slot_cap = max(1, eng.scfg.max_batch_size // 2)
            action = (f"admission batch cap -> {eng.sched.slot_cap} "
                      f"slots")
        logger.warning("serving: degradation ladder -> level %d (%s) "
                       "after %d anomalies", lvl, action, self.anomalies)

    # ------------------------------------------------------------------
    # rebuild + replay
    # ------------------------------------------------------------------
    def _rebuild_and_replay(self) -> None:
        """Rebuild the KV substrate in-process and replay live
        sequences. The failed pool's device state is unrecoverable
        (donated buffers), so every block reference is dropped and a
        fresh BlockPool + paged pools + prefix cache replace it; decode
        jit caches are dropped (prefill programs are pure functions of
        their inputs and are kept). Sequences replay oldest-first so
        the fresh prefix cache warms later replays of a shared head."""
        from deepspeed_tpu.serving.kv_cache import BlockPool, \
            init_paged_pools
        from deepspeed_tpu.serving.scheduler import PrefixCache

        eng = self.engine
        sched = eng.sched
        live = sorted(sched.running.values(),
                      key=lambda s: (s.admitted_step, s.request.rid))
        for seq in live:
            seq.block_table = []
        pool = BlockPool(eng.scfg.kv_num_blocks)
        eng.pool = pool
        sched.pool = pool
        if eng.prefix_cache is not None:
            eng.prefix_cache = PrefixCache(pool, eng.block_size)
            sched.prefix_cache = eng.prefix_cache
        eng._pools = init_paged_pools(
            eng.model_cfg, eng.scfg.kv_num_blocks, eng.block_size,
            int8=eng.scfg.int8_kv_cache, dtype=eng._dtype)
        # they donate the pools — the old programs are dead
        eng._decode_jit = eng._spec_jit = eng._mixed_jit = None
        replayed = requeued = 0
        for seq in live:
            if self._replay(seq):
                replayed += 1
            else:
                # Cold requeue through the scheduler's always-correct
                # preemption path: restart from the prompt (greedy
                # decoding regenerates the same tokens).
                sched.preempt(seq)
                requeued += 1
        logger.warning(
            "serving: rebuilt KV pools + decode programs in-process "
            "(%d sequences replayed, %d requeued cold)",
            replayed, requeued)

    def _replay(self, seq: Sequence) -> bool:
        """Reconstruct ``seq``'s KV ``[0, pos)`` in the fresh pool by
        prefilling its recorded ``tokens[:-1]`` (prompt + generated so
        far, minus the last sampled token — whose KV was never written).
        Warm through the fresh prefix cache when the head matches an
        earlier replay. False → caller cold-requeues."""
        eng = self.engine
        sched = eng.sched
        replay = seq.tokens[:-1]
        if not replay or len(replay) > eng.bucket_cap:
            return False
        if eng._chunked and seq.prefilled < len(seq.request.prompt):
            # Mid-prefill chunked sequence: its prompt KV is only
            # partially written and it has sampled nothing, so a
            # tokens[:-1] replay can't express it. Cold requeue
            # restarts the prompt — always correct.
            return False
        bucket = eng._bucket_of(len(replay))
        shared: List[int] = []
        if sched.prefix_cache is not None:
            shared = sched.prefix_cache.match(replay, eng._step_count)
        n_shared = len(shared)
        blocks = eng.pool.alloc(bucket // eng.block_size - n_shared)
        if blocks is None:
            if shared:
                eng.pool.release(shared)
            return False
        if sched.prefix_cache is not None:
            sched.prefix_cache.commit_hit(n_shared)
        seq.bucket = bucket
        seq.block_table = shared + blocks
        seq.shared_len = n_shared * eng.block_size
        eng._replay_prefill(seq, replay)
        if sched.prefix_cache is not None:
            sched.prefix_cache.insert(replay, seq.block_table,
                                      eng._step_count)
        return True
