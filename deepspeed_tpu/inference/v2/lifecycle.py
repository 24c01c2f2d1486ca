"""Serving request lifecycle: admission, deadlines, cancellation,
KV-pressure preemption, and the decode watchdog.

The :class:`ContinuousBatcher` (engine_v2.py) drives a CLOSED set of
requests to completion; a server faces an OPEN stream where requests die
mid-flight: clients disconnect, deadlines pass, the KV pool saturates, a
decode window hangs or goes NaN.  :class:`LifecycleScheduler` owns that
survivability layer on top of the engine primitives:

  * **Bounded admission + overload shedding** — ``submit`` rejects when the
    waiting queue is full (or the server is draining) and computes a
    ``Retry-After`` from the decode roofline's predicted drain rate, so an
    overloaded server answers in O(1) instead of queueing unboundedly
    (``serving/shed``).
  * **Deadlines and TTFT timeouts** — checked every scheduler iteration,
    which is at most one bounded decode window (``window_steps`` tokens)
    long: an expired request is flushed and its KV blocks reclaimed at the
    next window boundary — mid-stream, never "after it finishes"
    (``serving/deadline_expired``, ``serving/ttft_timeout``).
  * **One decode window ahead** — at most TWO windows are ever committed to
    the device: the one whose tokens the host is about to drain and, where
    the scheduler observes that it may (``_may_run_ahead``), the next one,
    dispatched BEFORE that drain so that the fetch, the bookkeeping, the
    lifecycle passes and the next dispatch all run while the device decodes.
    It may where the next window's riders are exactly the ones in flight
    (nobody reaches ``max_new_tokens`` inside the window in flight — the
    host knows by count — nothing queued or prefilling, no drafter, no
    cancellation or deadline due) and every row of the decode batch is
    taken (``max_seqs`` live sequences; with recurrent state, every state
    slot).  Everything else — a free row, a queued request, a finisher, a
    drain, an error — drains first, and the schedule is the one of a
    scheduler that drains every window.  **What it costs**: with recurrent
    state a full pool admits nobody, so nothing; a paged family admits on
    free BLOCKS, so a request that arrives while the batch is full and
    blocks are free is started after the window in flight — one window (at
    most ``window_steps`` steps) later than it would have been, never two.
    A queue that is not empty keeps every window drained: a server with a
    backlog runs the drained schedule.  A rider that ends where the host
    could not foresee it (EOS, a non-finite row) is found at the drain of
    window i while window i+1 carries it: that row of i+1 is dropped and
    its blocks go back when i+1 has drained.
  * **Cancellation** — ``cancel(uid)`` (client disconnect) flushes the
    sequence and returns its blocks to the pool; the freed blocks are
    immediately re-admittable (``serving/cancelled``).
  * **KV-pressure preemption** — when the pool is above the high watermark
    and the queue head cannot reserve blocks, the lowest-priority decoding
    request is preempted: its generated tokens are spilled host-side (they
    already live there), its blocks are flushed, and it re-queues for
    **prefill recompute** — the resume prompt is ``prompt + produced[:-1]``
    and the next decode seed is ``produced[-1]``, which rebuilds exactly
    the KV state the interrupted stream had, so greedy decode continues
    bit-identically (``serving/preempted``; test-asserted under both attn
    impls).
  * **Decode watchdog** — every drained window reports per-sequence
    non-finite flags (model_runner.build_decode_loop): poisoned requests
    are flushed ALONE (kernel-level NaN isolation extended to the
    scheduler, ``serving/nan_isolated``) and a window whose wall time blows
    the hang deadline raises a ``serving_window_hang`` incident — both
    reported through the PR-5 anomaly/event path and reflected in
    ``/healthz`` as ``degraded``.

Whole-lifetime block reservation at admission (as in ContinuousBatcher)
means a live request can never hit out-of-blocks mid-flight; the only
allocation point is admission, which is exactly where the ``kv_alloc``
fault-injection site fires.

Thread safety: ``submit``/``cancel`` are called from HTTP handler threads,
``step``/``drain`` from the driver thread; all state is guarded by one
reentrant lock.  Request callbacks (``on_event``) run inline under that
lock and must only hand off (enqueue) — the HTTP server's callbacks do.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from enum import Enum
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ...telemetry.goodput import (get_goodput_ledger, goodput_residual,
                                  record_goodput)
from ...telemetry.hub import get_telemetry
from ...telemetry.trace import get_tracer
from ...telemetry.tracing import (FLAG_BY_REASON, flag_trace,
                                  get_trace_store, record_span, trace_id_of)
from ...utils.logging import logger
from .engine_v2 import InferenceEngineV2

#: ``serve/*`` spans: the process-global ring and a profiler session's host
#: events; a request with a ``trace`` feeds the store from the same reads
_TRACER = get_tracer()


class RequestState(Enum):
    QUEUED = "queued"          # admitted to the waiting queue
    PREFILL = "prefill"        # holds KV blocks, prompt chunks in flight
    DECODE = "decode"          # generating
    FINISHED = "finished"      # terminal: completed normally
    CANCELLED = "cancelled"    # terminal: client cancelled / disconnected
    EXPIRED = "expired"        # terminal: deadline / TTFT timeout / drain
    SHED = "shed"              # terminal: rejected at admission (overload)
    FAILED = "failed"          # terminal: poisoned window, engine error

TERMINAL_STATES = (RequestState.FINISHED, RequestState.CANCELLED,
                   RequestState.EXPIRED, RequestState.SHED,
                   RequestState.FAILED)


@dataclasses.dataclass
class ServeRequest:
    """One request's full lifecycle record.

    ``deadline_s`` / ``ttft_timeout_s`` are RELATIVE seconds at submit time
    and converted to absolute monotonic deadlines on admission.  ``priority``
    is higher-wins (preemption victims are picked lowest-priority first).
    ``on_event(event, request)`` fires on: ``tokens`` (new tokens appended —
    the streaming hook), ``finished``, ``cancelled``, ``expired``,
    ``preempted``, ``failed``.
    """

    uid: int
    prompt: List[int]
    max_new_tokens: int = 32
    priority: int = 0
    deadline_s: Optional[float] = None
    ttft_timeout_s: Optional[float] = None
    on_event: Optional[Callable[[str, "ServeRequest"], None]] = None
    #: per-request speculative-decoding override (``speculative: {mode,
    #: k}`` on `/v1/generate`): ``spec_mode`` None inherits the scheduler
    #: default, "off" disables, any other mode enables the scheduler's
    #: drafter; ``spec_k`` is this request's draft length.
    spec_mode: Optional[str] = None
    spec_k: Optional[int] = None
    #: QoS attribution (serving/fleet/qos): the admission class the
    #: router charged; stamped so every shed/latency record downstream
    #: names its tenant.  None = direct traffic, accounted as "default".
    tenant: Optional[str] = None
    #: disaggregated prefill (serving/fleet): ``prefill_only`` requests
    #: stop at prefill completion and export their KV rows into
    #: ``kv_shipment`` (a kv_ship.KVShipment) instead of decoding;
    #: ``kv_import`` carries a shipment produced elsewhere — its rows are
    #: grafted at admission so only the unshipped prompt tail (>= 1 token)
    #: prefills locally and the stream continues bit-exactly.
    prefill_only: bool = False
    kv_import: Optional[object] = None
    #: fleet-wide request-trace context (telemetry/tracing): when set, the
    #: scheduler appends typed spans (``store.SPAN_KINDS``) under this trace
    #: id to the process-global store, and ``trace_result`` carries the
    #: finished local trace for in-band return to the router
    trace: Optional[object] = None
    trace_result: Optional[dict] = None

    # -- runtime state (scheduler-owned) --
    state: RequestState = RequestState.QUEUED
    kv_shipment: Optional[object] = None     # prefill_only export result
    prefix_hit_tokens: int = 0               # prompt tokens grafted, not run
    produced: List[int] = dataclasses.field(default_factory=list)
    finish_reason: Optional[str] = None
    arrival_t: float = 0.0
    first_token_t: Optional[float] = None
    finished_t: Optional[float] = None
    preempt_count: int = 0
    deadline_t: Optional[float] = None       # absolute, from deadline_s
    ttft_deadline_t: Optional[float] = None  # absolute, from ttft_timeout_s
    _admit_order: int = 0
    _prefill_pos: int = 0
    _resume_seed: Optional[int] = None       # set while resuming a preempt
    _prefix_counted: bool = False            # hit/miss recorded once
    #: on the scheduler's ``clock``: queued (again after a preemption),
    #: first admitted; and the prompt chunks run so far
    _queue_t: float = 0.0
    _admit_t: Optional[float] = None
    _chunks: int = 0
    _import_s: float = 0.0                   # kv_ship_import wall inside
    #                                          the last _reserve_for call

    @property
    def remaining(self) -> int:
        return self.max_new_tokens - len(self.produced)

    @property
    def resume_prompt(self) -> List[int]:
        """Tokens to (re)prefill: the original prompt, plus — after a
        preemption — every produced token except the last, which becomes
        the decode seed instead (rebuilding the exact pre-preemption KV
        state)."""
        if self._resume_seed is None:
            return self.prompt
        return self.prompt + self.produced[:-1]

    def ttft_s(self) -> Optional[float]:
        if self.first_token_t is None:
            return None
        return self.first_token_t - self.arrival_t

    def tpot_s(self) -> Optional[float]:
        """Time per output token over the decode phase."""
        if self.first_token_t is None or self.finished_t is None \
                or len(self.produced) < 2:
            return None
        return (self.finished_t - self.first_token_t) / \
            (len(self.produced) - 1)

    def _fire(self, event: str) -> None:
        if self.on_event is not None:
            try:
                self.on_event(event, self)
            except Exception as e:  # noqa: BLE001 — a sink bug must not kill scheduling
                logger.warning(f"request {self.uid} on_event({event}) "
                               f"failed: {e!r}")


@dataclasses.dataclass
class AdmissionVerdict:
    admitted: bool
    reason: Optional[str] = None       # "queue_full" | "draining"
    retry_after_s: Optional[float] = None


@dataclasses.dataclass
class _InFlight:
    """A fused decode window dispatched and not yet drained."""

    window: object                   # the engine's DecodeWindow
    uids: List[int]                  # its riders, in row order
    steps: int
    index: int                       # engine.decode_windows_dispatched
    #: riders retired since the dispatch (EOS or a non-finite row found at
    #: the previous window's drain, a deadline): their rows are dropped at
    #: this window's drain and their blocks released after it
    dropped: set = dataclasses.field(default_factory=set)


class LifecycleScheduler:
    """Open-world serving scheduler over :class:`InferenceEngineV2`.

    One ``step()`` runs either a mixed prefill/admission forward (``put``)
    or dispatches one bounded fused decode window, after processing
    cancellations and deadline expiries — so no request ever waits more than
    one window for its lifecycle events to take effect.  A window is drained
    in the step that dispatched it, unless the next step may dispatch its
    successor first (the module docstring's "one decode window ahead").
    """

    def __init__(self, engine: InferenceEngineV2, max_queue: int = 64,
                 window_steps: int = 8, kv_high_watermark: float = 0.9,
                 preempt: bool = True, hang_deadline_s: float = 30.0,
                 eos_token_id: Optional[int] = None,
                 fallback_tok_per_s: float = 32.0,
                 degraded_window_s: float = 60.0,
                 speculative=None, drafter=None,
                 clock: Callable[[], float] = time.monotonic):
        self.eng = engine
        self.max_queue = int(max_queue)
        self.window_steps = int(window_steps)
        self.kv_high_watermark = float(kv_high_watermark)
        self.preempt_enabled = bool(preempt)
        self.hang_deadline_s = float(hang_deadline_s)
        self.eos_token_id = eos_token_id
        self.fallback_tok_per_s = float(fallback_tok_per_s)
        self.degraded_window_s = float(degraded_window_s)
        self.clock = clock
        #: speculative decoding (SpeculativeConfig + drafter): when armed,
        #: decode windows become VERIFY windows — the drafter proposes K
        #: candidates per stream, the engine scores seed+K in one ragged
        #: pass, and the longest greedy-matching prefix is accepted.
        #: Greedy streams stay bit-exact; only tok/s changes.  A drafter
        #: instance may be handed in (draft_model mode needs its engine);
        #: otherwise it is built from the config.
        self.spec = speculative
        self.drafter = drafter
        if (speculative is not None or drafter is not None) \
                and getattr(engine, "latent_kv", False):
            raise ValueError(
                "speculative= / drafter= (verify windows) are not supported "
                "with latent (MLA) pages: build the scheduler without them")
        if (speculative is not None or drafter is not None) \
                and getattr(engine, "state_pool", None) is not None:
            raise NotImplementedError(
                "speculative= / drafter= (verify windows) are not supported "
                "with recurrent state: a rejected candidate cannot be taken "
                "back out of a state without a snapshot (ROADMAP R5)")
        if self.spec is not None and self.drafter is None:
            from .speculative import make_drafter

            self.drafter = make_drafter(self.spec)

        #: component label on spans this scheduler records (the serving
        #: server overwrites it with ``serve:<port>`` at start so fleet
        #: waterfalls name the replica, even in-process)
        self.trace_component = "serve"
        self._lock = threading.RLock()
        self._reqs: Dict[int, ServeRequest] = {}
        self._waiting: "collections.deque[int]" = collections.deque()
        self._prefilling: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        self._decodes: "collections.OrderedDict[int, int]" = \
            collections.OrderedDict()          # uid -> next seed token
        self._cancel_requested: set = set()
        #: the decode window dispatched and not yet drained, if any
        self._inflight: Optional[_InFlight] = None
        #: what ``_may_run_ahead`` last said held the next window ("" = go)
        self._held = ""
        #: why the next window finds nothing in flight (its ``held_by``):
        #: the reason of the FIRST drain since the last window went out,
        #: ``first`` where none was in flight since the start or an idle
        #: scheduler, "" while the last window is still in flight
        self._boundary = "first"
        self._admit_seq = 0
        self.draining = False
        self.counters: "collections.Counter[str]" = collections.Counter()
        self.last_incident_t: Optional[float] = None
        self.last_incident_kind: Optional[str] = None
        self.last_shed_t: Optional[float] = None

    # Request tracing: un-traced request / no store = one check per site
    def _tspan(self, req: ServeRequest, kind: str, t0: float, dur_s: float,
               **attrs) -> None:
        """``t0``: a span's own (``perf_counter``); the store keeps unix."""
        if req.trace is not None:
            record_span(req.trace, kind, t0=_TRACER.wall(t0), dur_s=dur_s,
                        component=self.trace_component, uid=req.uid, **attrs)

    def _trace_finish(self, req: ServeRequest, flag=None) -> None:
        store = get_trace_store()
        if store is None or req.trace is None:
            return
        if req.preempt_count > 0:
            store.flag(req.trace.trace_id, "preempted")
        req.trace_result = store.finish(
            req.trace.trace_id, flag=flag,
            wall_s=max(self.clock() - req.arrival_t, 0.0))

    # ------------------------------------------------------------------ #
    # Ingress (HTTP handler threads)
    # ------------------------------------------------------------------ #
    def submit(self, req: ServeRequest) -> AdmissionVerdict:
        """Admit to the bounded queue, or shed with a Retry-After."""
        with self._lock:
            t_shed0 = time.perf_counter()
            now = self.clock()
            req.arrival_t = req._queue_t = now
            if req.deadline_s is not None:
                req.deadline_t = now + req.deadline_s
            if req.ttft_timeout_s is not None:
                req.ttft_deadline_t = now + req.ttft_timeout_s
            if req.uid in self._reqs:
                raise ValueError(f"uid {req.uid} already submitted")
            if not req.prompt:
                # nothing to condition on: trivially complete
                req.state = RequestState.FINISHED
                req.finish_reason = "empty_prompt"
                req.finished_t = now
                self._reqs[req.uid] = req
                self._trace_finish(req)
                req._fire("finished")
                return AdmissionVerdict(True)
            full = len(self._waiting) >= self.max_queue
            if self.draining or full:
                reason = "draining" if self.draining else "queue_full"
                tenant = req.tenant or "default"
                if not self.draining:
                    self.last_shed_t = now
                self._tspan(req, "admission", t0=t_shed0, dur_s=0.0,
                            shed=reason, tenant=tenant)
                self._retire(req, RequestState.SHED, reason, "serving_shed",
                             "serving/shed", tenant=tenant,
                             queue_depth=len(self._waiting))
                record_goodput("shed", time.perf_counter() - t_shed0,
                               tenant=tenant)
                return AdmissionVerdict(
                    False, reason, self.predicted_drain_s()
                    if self.draining else self.retry_after_s())
            self._reqs[req.uid] = req
            self._waiting.append(req.uid)
            self._count("serving/requests")
            self._publish_gauges()
            return AdmissionVerdict(True)

    def cancel(self, uid: int) -> bool:
        """Request cancellation (client disconnect); takes effect at the
        next scheduler iteration — at most one decode window away."""
        with self._lock:
            if uid not in self._reqs or \
                    self._reqs[uid].state in TERMINAL_STATES:
                return False
            self._cancel_requested.add(uid)
            return True

    def request(self, uid: int) -> Optional[ServeRequest]:
        with self._lock:
            return self._reqs.get(uid)

    @property
    def pending(self) -> int:
        """Live (non-terminal) request count."""
        with self._lock:
            return (len(self._waiting) + len(self._prefilling)
                    + len(self._decodes))

    # ------------------------------------------------------------------ #
    # Load prediction (Retry-After / drain estimates)
    # ------------------------------------------------------------------ #
    def predicted_tok_per_s(self) -> float:
        """Decode drain rate from the last clean decode-window roofline;
        the configured fallback before any window has been measured."""
        r = self.eng.last_decode_roofline
        if r and not r.get("compile_polluted") and \
                r.get("decode_tok_per_s", 0) > 0:
            return float(r["decode_tok_per_s"])
        return self.fallback_tok_per_s

    def outstanding_tokens(self) -> int:
        with self._lock:
            return sum(self._reqs[u].remaining
                       for bucket in (self._waiting, self._prefilling,
                                      self._decodes)
                       for u in bucket)

    def retry_after_s(self) -> float:
        """Seconds until one queue slot is predicted to free: the whole
        backlog's remaining tokens over the predicted drain rate, scaled to
        one slot."""
        backlog = self.outstanding_tokens()
        slots = max(len(self._waiting) + len(self._prefilling)
                    + len(self._decodes), 1)
        per_slot = backlog / slots / self.predicted_tok_per_s()
        return float(min(max(per_slot, 1.0), 120.0))

    def predicted_drain_s(self) -> float:
        """Predicted seconds to drain every live request (the Retry-After
        while draining, and the basis for drain-deadline sizing)."""
        return float(min(max(
            self.outstanding_tokens() / self.predicted_tok_per_s(),
            1.0), 600.0))

    # ------------------------------------------------------------------ #
    # Lifecycle passes
    # ------------------------------------------------------------------ #
    def _retire(self, req: ServeRequest, state: RequestState, reason: str,
                event: str, counter: Optional[str] = None,
                holds_blocks: bool = False, **fields) -> None:
        """Move a request to a terminal state, reclaiming its KV blocks: the
        one way out, for ``submit``'s sheds too (never queued, nothing to
        reclaim, no callback).  Any end but ``finished`` says why."""
        uid = req.uid
        if state is not RequestState.FINISHED:
            self._say_why(uid, state.value, reason, len(req.produced))
        holds_blocks |= uid in self._prefilling or uid in self._decodes
        self._waiting = collections.deque(
            u for u in self._waiting if u != uid)
        self._prefilling.pop(uid, None)
        self._decodes.pop(uid, None)
        self._release(uid, flush=holds_blocks)
        req.state = state
        req.finish_reason = reason
        if req.finished_t is None:
            req.finished_t = self.clock()
        if counter:
            self._count(counter)
        self._event(event, uid=uid, reason=reason, produced=len(req.produced),
                    trace=trace_id_of(req.trace), **fields)
        self._trace_finish(req, flag=FLAG_BY_REASON.get(reason))
        if state is not RequestState.SHED:
            req._fire(event.replace("serving_", ""))
        self._publish_gauges()

    def _say_why(self, uid, state: str, reason: str, produced: int,
                 **attrs) -> None:
        """A request that does not finish leaves its reason in the ring (a
        ``serve/retire`` span) and in one warning line, hub or no hub."""
        attrs.update(waiting=len(self._waiting),
                     kv_used=round(self.eng.kv_used_fraction(), 4))
        with _TRACER.span("serve/retire", uid=uid, state=state, reason=reason,
                          produced=produced, **attrs):
            logger.warning(f"serve/retire uid={uid} state={state} "
                           f"reason={reason} produced={produced} " + " ".join(
                               f"{k}={v}" for k, v in attrs.items()))

    def _release(self, uid: int, flush: bool = True) -> None:
        """A request is over: its KV blocks, drafter state, parked rows."""
        fl = self._inflight
        if flush and fl is not None and uid in fl.uids:
            # its row of the window in flight is still decoding into those
            # blocks: they go back when that window has drained (_drain)
            fl.dropped.add(uid)
        elif flush:
            self.eng.flush([uid])
        if self.drafter is not None:
            self.drafter.flush(uid)
        ksw = getattr(self.eng, "kv_swap", None)
        if ksw is not None:
            ksw.drop(uid)

    def _process_cancellations(self) -> List[int]:
        done = []
        for uid in sorted(self._cancel_requested):
            req = self._reqs.get(uid)
            if req is not None and req.state not in TERMINAL_STATES:
                self._retire(req, RequestState.CANCELLED, "cancelled",
                             "serving_cancelled", "serving/cancelled")
                done.append(uid)
        self._cancel_requested.clear()
        return done

    def _process_expiries(self) -> List[int]:
        now = self.clock()
        done = []
        for req in list(self._reqs.values()):
            if req.state in TERMINAL_STATES:
                continue
            if req.deadline_t is not None and now >= req.deadline_t:
                reason, counter = "deadline", "serving/deadline_expired"
            elif (req.ttft_deadline_t is not None
                    and req.first_token_t is None
                    and now >= req.ttft_deadline_t):
                reason, counter = "ttft_timeout", "serving/ttft_timeout"
            else:
                continue
            self._retire(req, RequestState.EXPIRED, reason,
                         "serving_expired", counter)
            done.append(req.uid)
        return done

    # ------------------------------------------------------------------ #
    # KV-pressure preemption
    # ------------------------------------------------------------------ #
    def _maybe_preempt_for(self, head: ServeRequest) -> bool:
        """Preempt the lowest-priority decoding request so ``head`` can be
        admitted — only above the KV high watermark, and never a victim
        with strictly higher priority than the starved head."""
        if not self.preempt_enabled or not self._decodes:
            return False
        if self.eng.kv_used_fraction() < self.kv_high_watermark:
            return False
        victims = [self._reqs[u] for u in self._decodes]
        # anti-ping-pong: among equal priorities, a head preempted N times
        # may only evict victims preempted >= N times, so two requests never
        # evict each other in a cycle (observed livelock: a 3-block and an
        # 8-block request alternating forever on a 10-block pool)
        victims = [v for v in victims
                   if v.priority < head.priority
                   or (v.priority == head.priority
                       and v.preempt_count >= head.preempt_count)]
        if not victims:
            return False
        # lowest priority first; among equals the latest-admitted loses
        # (least work thrown away for FIFO arrival orders)
        victim = min(victims, key=lambda r: (r.priority, -r._admit_order))
        uid = victim.uid
        # host tier on: park the victim's coldest contiguous page-prefix
        # BEFORE the flush (a pure read of still-live pages), so resume is a
        # swap-in, not a prefill recompute; 0 tokens spilled = recompute
        swapped = 0
        ksw = getattr(self.eng, "kv_swap", None)
        if ksw is not None and victim.produced:
            swapped = ksw.spill(
                uid, victim.prompt + victim.produced[:-1])
        del self._decodes[uid]
        self.eng.flush([uid])                 # spill: produced stays host-side
        victim.state = RequestState.QUEUED
        victim.preempt_count += 1
        victim._resume_seed = victim.produced[-1]
        victim._prefill_pos = 0
        self._waiting.append(uid)             # re-admitted behind the head
        self._count("serving/preempted")
        if swapped:
            self._count("serving/swap_out")
            self._count("serving/swap_out_tokens", swapped)
        self._event("serving_preempted", uid=uid, for_uid=head.uid,
                    produced=len(victim.produced), swapped=swapped,
                    kv_used=round(self.eng.kv_used_fraction(), 4),
                    trace=trace_id_of(victim.trace))
        victim._queue_t = self.clock()        # the next queue_wait span
        self._tspan(victim, "preempt", t0=victim._queue_t, dur_s=0.0,
                    for_uid=head.uid, produced=len(victim.produced))
        victim._fire("preempted")
        logger.info(f"KV pressure: preempted uid {uid} "
                    f"({len(victim.produced)} tokens spilled) to admit "
                    f"uid {head.uid}")
        return True

    # ------------------------------------------------------------------ #
    # Scheduling
    # ------------------------------------------------------------------ #
    def _room_for(self, need_blocks: int) -> bool:
        """Evict prefix-cache slack; are ``need_blocks`` free then?"""
        sm = self.eng.state_manager
        if need_blocks > sm.allocator.free_blocks and \
                sm.prefix_cache is not None:
            sm.prefix_cache.evict(need_blocks - sm.allocator.free_blocks)
        return need_blocks <= sm.allocator.free_blocks

    def _reserve_for(self, req: ServeRequest) -> Optional[bool]:
        """Whole-lifetime KV reservation for admission.  Returns True on
        success, False on transient exhaustion (backpressure), None when
        the request can never fit (rejected).

        Before reserving, two graft paths may pre-seed the sequence's KV:

          * a ``kv_import`` shipment (disaggregated prefill handoff) is
            validated against the request's own prompt and scattered into
            freshly-allocated pages — only the unshipped tail prefills;
          * otherwise the radix prefix cache is consulted and the longest
            committed prefix is grafted (shared full pages + a CoW'd
            partial tail).

        Either way ``_prefill_pos`` advances past the grafted rows and the
        reservation covers only the remainder.  On a FAILED reservation
        the graft is fully released (flush) so a waiting queue head holds
        zero blocks — grafted pages stay evictable in the trie, and the
        retry re-grafts for a few microseconds of host work."""
        c = self.eng.config
        need, need_blocks = self.eng.lifetime_reservation(
            len(req.resume_prompt), req.remaining)
        if (len(req.resume_prompt) > c.max_ctx
                or (self.eos_token_id is None
                    and len(req.resume_prompt) + req.remaining > c.max_ctx)
                or need_blocks > self.eng.kv.config.num_blocks):
            # impossible under ANY load (an eos can cut a long generation
            # short, so only the eos-less overrun is deterministic): reject
            # now instead of wedging the queue head
            return None
        sm = self.eng.state_manager
        ksw = getattr(self.eng, "kv_swap", None)
        swapped_in = False
        if sm.get_sequence(req.uid) is None:
            req._prefill_pos = 0
            if (ksw is not None and req._resume_seed is not None
                    and ksw.entry(req.uid) is not None):
                # swap-in resume: the preempt path parked this uid's rows
                # host-side, and they cover MORE than any original
                # kv_import shipment (prompt + produced so far), so this
                # branch wins.  Same cheap feasibility gate as kv_import.
                if not self._room_for(need_blocks):
                    return False
                with _TRACER.span("serve/kv_swap_in", uid=req.uid) as sp:
                    n = ksw.restore(req.uid, req.resume_prompt)
                if n:
                    req._import_s = sp.dur_s
                    self._tspan(req, "kv_swap_in", t0=sp.t0,
                                dur_s=sp.dur_s, tokens=n)
                    req._prefill_pos = n
                    swapped_in = True
                    self._count("serving/swap_in")
                    self._count("serving/swap_in_tokens", n)
                elif ksw.entry(req.uid) is not None:
                    return False    # transient: rows stay parked, retry
                else:   # rows evicted / failed re-attestation: recompute
                    self._count("serving/swap_miss")
            elif req.kv_import is not None:
                ship = req.kv_import
                attested = [int(t) for t in
                            req.resume_prompt[:ship.n_tokens]]
                if (ship.n_tokens > len(req.resume_prompt) - 1
                        or list(ship.tokens) != attested):
                    # wrong conversation's KV: no retry can fix this
                    return None
                # feasibility gate BEFORE the device write: a blocked head
                # retries every pass, and importing (pages scatter + decode-
                # state invalidation) only to flush would repeat per window
                if not self._room_for(need_blocks):
                    return False
                from .kv_ship import import_kv

                with _TRACER.span("serve/kv_import", uid=req.uid) as sp:
                    imported = import_kv(self.eng, ship, req.uid)
                if not imported:
                    return False           # transient exhaustion
                req._import_s = sp.dur_s
                self._tspan(req, "kv_ship_import", t0=sp.t0,
                            dur_s=sp.dur_s, tokens=ship.n_tokens)
                req._prefill_pos = ship.n_tokens
            elif self.eng.prefix_cache is not None:
                matched = self.eng.graft_prefix(req.uid, req.resume_prompt)
                if matched:
                    req._prefill_pos = matched
        seq = sm.get_or_create_sequence(req.uid)
        # tenant label rides the reservation so the memory plane can
        # attribute this uid's KV pages fractionally per tenant
        self.eng.set_tenant(req.uid, req.tenant or "default")
        if not sm.maybe_allocate_kv(seq, need - seq.seen_tokens):
            # roll back so a retry starts clean: grafted / imported blocks
            # go (shared pages survive in the trie), an empty descriptor too
            if seq.blocks or seq.seen_tokens:
                sm.flush_sequence(req.uid)
            else:
                sm._seqs.pop(req.uid, None)
            req._prefill_pos = 0
            return False
        # count the graft ONLY on a successful reservation: a blocked head
        # re-grafts every pass, and those retries would inflate the hit
        # stats (why cache.note_hit/note_miss exist; match() only looks up)
        cache = self.eng.prefix_cache
        if swapped_in:
            pass    # swap-in counters were recorded in the branch above
        elif req.kv_import is not None and req._prefill_pos:
            self._count("serving/kv_import")
            self._count("serving/kv_import_tokens", req._prefill_pos)
        elif cache is not None and req.prefix_hit_tokens == 0 \
                and not req._prefix_counted:
            req._prefix_counted = True
            if req._prefill_pos:
                req.prefix_hit_tokens = req._prefill_pos
                cache.note_hit(req._prefill_pos)
                self._count("serving/prefix_hits")
                self._count("serving/prefix_hit_tokens", req._prefill_pos)
            else:
                cache.note_miss()
        return True

    def _build_prefill_batch(self, sp) -> List[Tuple[int, List[int]]]:
        """Chunks for one ``put``: in-flight prefills first, then admit from
        the queue head (with preemption when starved under pressure).  The
        counts go on ``sp``, the ``serve/admit`` span."""
        c = self.eng.config
        budget = c.max_tokens
        admitted = 0
        prefix_tokens = prompt_tokens = 0   # of the requests admitted here
        picked: List[Tuple[int, List[int]]] = []
        for uid in list(self._prefilling):
            if budget <= 0 or len(picked) >= c.max_seqs:
                break
            req = self._reqs[uid]
            chunk = req.resume_prompt[req._prefill_pos:
                                      req._prefill_pos + budget]
            picked.append((uid, chunk))
            budget -= len(chunk)
        preempted_this_pass = False
        sm = self.eng.state_manager
        stateful = sm.free_slots is not None     # recurrent state: slots
        state_blocked = False
        while self._waiting and budget > 0 and len(picked) < c.max_seqs:
            head = self._reqs[self._waiting[0]]
            t_try = self.clock()
            head._import_s = 0.0
            with _TRACER.span("serve/reserve", uid=head.uid) as rsp:
                verdict = self._reserve_for(head)
                if stateful and verdict is True:
                    # its slot of the state pool came with its pages
                    rsp.set(slot=sm.get_sequence(head.uid).slot)
            if verdict is True:
                # admitted: close the queue_wait segment (re-opened by a
                # preemption); the reservation / graft work is the admission
                # segment MINUS the KV import, which has its own span
                # (segments stay disjoint or the decomposition sums lie)
                waited = max(t_try - head._queue_t, 0.0)
                _TRACER.record("serve/queue_wait", head._queue_t, waited,
                               uid=head.uid, prompt_tokens=len(head.prompt),
                               preempted=head.preempt_count)
                self._tspan(head, "queue_wait", t0=head._queue_t,
                            dur_s=waited)
                if head._admit_t is None:
                    head._admit_t = t_try
                # tenant rides the admission span so a recorded
                # traces.jsonl stays convertible into a replayable
                # workload even without a router in front
                self._tspan(head, "admission", t0=rsp.t0,
                            dur_s=max(rsp.dur_s - head._import_s, 0.0),
                            prefix_hit=head._prefill_pos
                            if head.kv_import is None else 0,
                            tenant=head.tenant or "default")
            if verdict is None:
                self._waiting.popleft()
                self._retire(head, RequestState.FAILED, "impossible",
                             "serving_rejected", "serving/rejected")
                continue
            if verdict is False:
                # (with recurrent state the head may wait for a SLOT while
                # pages are free: every slot is owned by a live sequence)
                state_blocked = stateful and sm.free_slots == 0
                # backpressure: try one preemption, then re-check; a
                # second failure this pass means the pool genuinely cannot
                # host the head yet — it keeps its place in the queue
                if not preempted_this_pass and self._maybe_preempt_for(head):
                    preempted_this_pass = True
                    continue
                break
            self._waiting.popleft()
            head.state = RequestState.PREFILL
            self._prefilling[head.uid] = None
            admitted += 1
            prompt_tokens += len(head.resume_prompt)
            if head.kv_import is None:
                prefix_tokens += head._prefill_pos      # grafted, not run
            self._admit_seq += 1
            head._admit_order = self._admit_seq
            # _prefill_pos may start past 0: grafted prefix / imported KV
            # rows are already cached, so only the remainder runs
            chunk = head.resume_prompt[head._prefill_pos:
                                       head._prefill_pos + budget]
            picked.append((head.uid, chunk))
            budget -= len(chunk)
        # a head still waiting with budget left is one the pool cannot host
        sp.set(admitted=admitted, preempted=int(preempted_this_pass),
               blocked=int(bool(self._waiting) and budget > 0
                           and len(picked) < c.max_seqs),
               tokens=c.max_tokens - budget, prompt_tokens=prompt_tokens,
               prefix_tokens=prefix_tokens)
        if stateful:
            sp.set(state_blocked=int(state_blocked))
        return picked

    def _run_prefill(self, batch: List[Tuple[int, List[int]]]) -> List[int]:
        with _TRACER.span("serve/prefill", n_seqs=len(batch)) as sp:
            logits = self.eng.put([u for u, _ in batch],
                                  [t for _, t in batch])
            put_s = time.perf_counter() - sp.t0
            ledger = get_goodput_ledger()
            if ledger is not None and put_s > 0.0:
                # split by chunk size: replaying a preemption victim's KV
                # is waste (``preempt_recompute``), the rest useful prefill
                total_toks = sum(len(t) for _, t in batch) or 1
                redo = put_s / total_toks * sum(
                    len(t) for u, t in batch
                    if self._reqs[u]._resume_seed is not None)
                if redo:
                    ledger.add("preempt_recompute", redo)
                ledger.add("compute", put_s - redo)
            # rows whose prompt ends here and that go on to decode need
            # their next token now: the one host sync of a prefill step
            seeds: Dict[int, int] = {}
            need = [row for row, (r, chunk) in enumerate(
                (self._reqs[u], t) for u, t in batch)
                if r._prefill_pos + len(chunk) >= len(r.resume_prompt)
                and not r.prefill_only and r._resume_seed is None]
            if need:
                with _TRACER.span("serve/logits_fetch", rows=len(need)):
                    for row in need:
                        seeds[row] = int(np.argmax(np.asarray(logits[row])))
            with _TRACER.span("serve/prefill_apply"):
                return self._apply_prefill(batch, seeds, sp.t0, put_s)

    def _apply_prefill(self, batch, seeds: Dict[int, int], t0: float,
                       put_s: float) -> List[int]:
        finished: List[int] = []
        now = self.clock()
        for row, (uid, chunk) in enumerate(batch):
            req = self._reqs[uid]
            # the whole forward's wall is attributed to every rider: the
            # request really did spend that time inside this batch
            self._tspan(req, "prefill", t0=t0, dur_s=put_s,
                        tokens=len(chunk), batch=len(batch),
                        resume=req._resume_seed is not None)
            req._prefill_pos += len(chunk)
            req._chunks += 1
            if req._prefill_pos < len(req.resume_prompt):
                continue                       # mid-prompt; logits unused
            # prefill complete: commit the full prompt pages to the radix
            # cache NOW (not at retirement) so concurrent staggered
            # requests sharing the prefix hit while this one still decodes
            self.eng.commit_prefix(uid, req.resume_prompt)
            if req.prefill_only:
                # disaggregated-prefill producer: export the rows (a pure
                # read), finish without decoding (_retire pops the
                # prefilling entry and reclaims the blocks)
                from .kv_ship import export_kv

                with _TRACER.span("serve/kv_export", uid=uid) as esp:
                    req.kv_shipment = export_kv(self.eng, uid,
                                                req.resume_prompt)
                self._tspan(req, "kv_ship_encode", t0=esp.t0,
                            dur_s=esp.dur_s,
                            tokens=req.kv_shipment.n_tokens)
                self._count("serving/completed")
                self._retire(req, RequestState.FINISHED, "prefill_done",
                             "serving_finished", "serving/prefill_exported")
                finished.append(uid)
                continue
            del self._prefilling[uid]
            req.state = RequestState.DECODE
            if req._resume_seed is not None:
                # preemption resume: KV is rebuilt, the next decode seed is
                # the spilled stream's last token, NOT a fresh argmax
                seed = int(req._resume_seed)
                req._resume_seed = None
                self._tspan(req, "resume", t0=t0 + put_s, dur_s=0.0,
                            produced=len(req.produced))
            else:
                seed = seeds[row]
                req.produced.append(seed)
                req.first_token_t = now
                _TRACER.record("serve/first_token", req._admit_t,
                               now - req._admit_t, uid=uid,
                               prompt_tokens=len(req.prompt),
                               chunks=req._chunks,
                               preempted=req.preempt_count)
                self._observe("serving/ttft_s", req.ttft_s(), req)
                req._fire("tokens")
                if self._finished_by(req, seed):
                    self._finish(req)
                    finished.append(uid)
                    continue
            self._decodes[uid] = seed
        self._publish_gauges()
        return finished

    def _finished_by(self, req: ServeRequest, tok: int) -> bool:
        return ((self.eos_token_id is not None and tok == self.eos_token_id)
                or req.remaining <= 0)

    def _finish(self, req: ServeRequest) -> None:
        # the tail prompt page goes quiet forever now — commit it too
        # (allow_partial), so sub-page prefixes become reusable; full pages
        # were committed at prefill completion
        self.eng.commit_prefix(req.uid, req.prompt, allow_partial=True)
        req.finished_t = self.clock()
        self._observe("serving/tpot_s", req.tpot_s(), req)
        eos = self.eos_token_id is not None and req.produced \
            and req.produced[-1] == self.eos_token_id
        self._retire(req, RequestState.FINISHED, "eos" if eos else "length",
                     "serving_finished", "serving/completed",
                     holds_blocks=True)

    def _run_decode_window(self, sp) -> List[int]:
        """One bounded fused decode window (``sp``: its ``serve/window`` span)
        over up to max_seqs decoding requests (round-robin rotated), with
        watchdog + NaN isolation at drain."""
        c = self.eng.config
        n = min(len(self._decodes), c.max_seqs, c.max_tokens)
        uids = []
        for _ in range(n):
            uid, seed = self._decodes.popitem(last=False)
            uids.append(uid)
            self._decodes[uid] = seed          # rotate to the back
        # context-cap guard (eos-expected requests reserve less than
        # prompt+max_new): a sequence with no KV room left cannot decode —
        # retire it instead of wedging the window
        room = {}
        for uid in list(uids):
            seq = self.eng.state_manager.get_sequence(uid)
            room[uid] = c.max_ctx - seq.seen_tokens
            if room[uid] <= 0:
                uids.remove(uid)
                self._retire(self._reqs[uid], RequestState.FAILED,
                             "ctx_overflow", "serving_rejected",
                             "serving/rejected")
        if not uids:
            return []
        if self.drafter is not None and \
                any(self._spec_k_for(self._reqs[u]) > 0 for u in uids):
            sp.set(n_seqs=len(uids), steps=1, verify=True, ahead=0,
                   held_by="drafter")      # it needs the tokens, every time
            return self._run_verify_window(uids, room)
        # the window in flight (its riders are these: _settle saw to it)
        # has not given its tokens yet; what it will give is known by count
        prev = self._inflight
        owed = prev.steps if prev is not None else 0
        steps = min(self.window_steps,
                    min(self._reqs[u].remaining for u in uids) - owed,
                    min(room[u] for u in uids))
        if steps > 2:       # pow2 quantize: one compiled loop per window size
            steps = 1 << (steps.bit_length() - 1)
        sp.set(n_seqs=len(uids), steps=steps, ahead=int(prev is not None),
               held_by="" if prev is not None else self._boundary)
        self._boundary = ""
        # (ahead of an undrained window these seeds are stale and advisory:
        # the true ones are on the device, decode_batch_async)
        seeds = [self._decodes[u] for u in uids]
        window = self.eng.decode_batch_async(uids, seeds, steps)
        self._inflight = _InFlight(window, uids, steps,
                                   self.eng.decode_windows_dispatched)
        finished = [] if prev is None else self._drain(prev)
        return finished + self._settle()

    def _free_slots(self) -> int:
        """Rows of the decode batch not taken: the engine's ``max_seqs`` less
        the live sequences and, with recurrent state, the state pool's free
        slots if fewer.  (Only the state pool GATES admission; a paged
        family admits beyond ``max_seqs`` while blocks are free, and its
        decode set then rotates.)"""
        free = self.eng.config.max_seqs - len(self._prefilling) \
            - len(self._decodes)
        slots = self.eng.state_manager.free_slots
        return free if slots is None else min(free, slots)

    def _may_run_ahead(self) -> bool:
        """May the next decode window be dispatched BEFORE the one in flight
        is drained?  From what the host can observe: (a) the next window's
        riders are exactly the ones in flight — nobody reaches
        ``max_new_tokens`` or the context cap inside that window (known by
        count), nothing queued, prefilling, cancelled, due or draining, no
        drafter (it needs the tokens), the engine's device state still
        describes them; and (b) no row of the decode batch is free
        (``_free_slots``): with a free row an arrival is the common case and
        would wait a second window for its first token.  With recurrent
        state (b) means nobody could be admitted anyway.  A paged family
        still admits an arrival on free blocks: it is started when the
        window in flight has drained, one window later than under a
        scheduler that drains every window (the module docstring's "what it
        costs").  ``_held`` says which condition said no, the first in
        the order they are tested ("" = it may)."""
        self._held = self._holds_next_window()
        return not self._held

    def _holds_next_window(self) -> str:
        fl = self._inflight
        if fl.dropped:
            return "dropped"
        if self.drafter is not None:
            return "drafter"
        if self.draining:
            return "draining"
        if self._waiting:
            return "queued"
        if self._prefilling:
            return "prefilling"
        if self._cancel_requested:
            return "cancel"
        if self._free_slots() > 0:
            return "free_row"
        if list(self._decodes) != fl.uids:
            return "rotation"
        if not self.eng.decode_chains(fl.uids):
            return "chains"
        now = self.clock()
        cap = self.eng.config.max_ctx
        sm = self.eng.state_manager
        for uid in fl.uids:
            req = self._reqs[uid]
            if req.remaining <= fl.steps:
                return "finisher"
            if sm.get_sequence(uid).seen_tokens >= cap:
                return "ctx_cap"
            if req.deadline_t is not None and now >= req.deadline_t:
                return "deadline"
        return ""

    def _settle(self) -> List[int]:
        """Drain the window in flight unless the next may run ahead of it:
        whatever touches its riders or could start a request finds the
        schedule as it was before windows ran ahead."""
        if self._inflight is None or self._may_run_ahead():
            return []
        fl, self._inflight = self._inflight, None
        return self._drained(fl, self._held)

    def _drained(self, fl: _InFlight, why: str) -> List[int]:
        """Drain ``fl`` with nothing left in flight, and keep ``why`` for
        the next ``serve/window`` if it is the root of this boundary."""
        self._boundary = self._boundary or why
        finished = self._drain(fl)
        if not self.pending:            # idle: the next window is a first
            self._boundary = "first"
        return finished

    def _drain(self, fl: _InFlight) -> List[int]:
        """Wait for a window's tokens and apply them (watchdog + NaN
        isolation); the rows of riders retired since its dispatch are
        dropped and their blocks released, now that nothing writes them."""
        toks = fl.window.tokens()
        with _TRACER.span("serve/window_apply"):
            columns = toks.T.tolist()
            rows = [(u, columns[col]) for col, u in enumerate(fl.uids)
                    if u not in fl.dropped]
            finished = self._apply_window_results(
                [u for u, _ in rows], [stream for _, stream in rows],
                set(fl.window.nonfinite_uids()) - fl.dropped,
                wall_s=fl.window.duration_s, compiled=fl.window.compiled,
                window_index=fl.index)
            if fl.dropped:
                self.eng.flush(sorted(fl.dropped))
        return finished

    def _apply_window_results(self, uids: List[int],
                              streams: List[List[int]], poisoned: set,
                              wall_s: float, compiled: bool,
                              span_kind: str = "decode_window",
                              span_wall_s: Optional[float] = None,
                              window_index: Optional[int] = None
                              ) -> List[int]:
        """Shared tail of fused-decode and verify windows: post-hoc hang
        detection, per-request NaN isolation, eos truncation, finish /
        rotate bookkeeping.  ``streams[i]`` is uid i's newly produced
        tokens (ignored for poisoned uids).  ``span_wall_s`` narrows the
        recorded span below the hang-check wall when part of the wall is
        attributed elsewhere (verify windows: drafting has its own
        span)."""
        finished: List[int] = []
        # goodput: the window wall is attributed ONCE (not per rider) — a
        # first-use window is XLA compilation, a drained one useful decode
        # (a verify window's draft time included: its tokens were accepted)
        record_goodput("compile" if compiled else "compute", wall_s)
        # window span per rider; a first-use window is typed ``compile``, so
        # the decode_window decomposition stays clean like the roofline's
        span_s = wall_s if span_wall_s is None else span_wall_s
        t0 = time.perf_counter() - span_s
        kind = "compile" if compiled else span_kind
        if window_index is None:
            window_index = self.eng.decode_windows_dispatched
        for uid, stream in zip(uids, streams):
            self._tspan(self._reqs[uid], kind, t0=t0, dur_s=span_s,
                        n_seqs=len(uids), tokens=len(stream),
                        window=window_index)
        if not compiled and wall_s > self.hang_deadline_s:
            # post-hoc hang detection: the window drained, but took longer
            # than the deadline — a stuck DMA / pathological host stall.
            self.last_incident_t = self.clock()
            self.last_incident_kind = "window_hang"
            self._count("serving/window_hang")
            self._event("serving_window_hang", uids=list(uids),
                        duration_s=round(wall_s, 3),
                        deadline_s=self.hang_deadline_s,
                        traces=[trace_id_of(self._reqs[u].trace)
                                for u in uids])
            for u in uids:
                flag_trace(self._reqs[u].trace, "window_hang")

        if poisoned:
            self.last_incident_t = self.clock()
            self.last_incident_kind = "nan"
        for uid, stream in zip(uids, streams):
            req = self._reqs[uid]
            if uid in poisoned:
                # flush ONLY the poisoned request; batchmates are clean by
                # the kernel-level isolation property and keep decoding
                self._count("serving/nan_isolated")
                self._retire(req, RequestState.FAILED, "nan",
                             "serving_nan_isolated")
                finished.append(uid)
                continue
            stream = list(stream)
            if self.eos_token_id is not None and \
                    self.eos_token_id in stream:
                stream = stream[:stream.index(self.eos_token_id) + 1]
            req.produced.extend(stream)
            req._fire("tokens")
            if self._finished_by(req, req.produced[-1]):
                self._finish(req)
                finished.append(uid)
            else:
                self._decodes[uid] = req.produced[-1]
        self._publish_gauges()
        return finished

    # ------------------------------------------------------------------ #
    # Speculative decoding (verify windows)
    # ------------------------------------------------------------------ #
    def _spec_k_for(self, req: ServeRequest) -> int:
        """Effective draft length for a request: the per-request override
        (``speculative: {mode, k}`` on ``/v1/generate``) on top of the
        scheduler default.  A request's ``spec_mode`` acts as a toggle for
        the SERVER-configured drafter — a single scheduler runs one
        drafter, so requesting a different mode than the server's enables
        that drafter rather than building another."""
        if self.drafter is None:
            return 0
        mode = req.spec_mode if req.spec_mode is not None else \
            (self.spec.mode if self.spec else "off")
        if mode == "off":
            return 0
        k = req.spec_k if req.spec_k is not None else \
            (self.spec.k if self.spec else 0)
        return max(int(k), 0)

    def _run_verify_window(self, uids: List[int],
                           room: Dict[int, int]) -> List[int]:
        """One speculative verify window over the rotated decode set.

        Per stream the drafter proposes up to ``spec_k`` candidates —
        capped at ``remaining - 1`` and ``room - 1`` so the speculative
        append never outgrows the whole-lifetime block reservation or the
        context cap (live requests still never allocate KV mid-flight), and
        at the engine's flat token budget: the window packs ``sum(1 + k_i)``
        tokens into one ragged batch, so what ``max_tokens`` leaves after
        the one-token-per-stream rows is dealt out in rotation order (late
        streams draft less this window; the rotation moves the allowance
        around).  A stream with nothing to draft rides along (a 1-token
        verify is one vanilla decode step).  Greedy bit-exactness, watchdog/
        NaN isolation, eos handling and preemption mirror the fused path."""
        budget = self.eng.config.max_tokens - len(uids)   # draft allowance
        seeds, drafts = [], []
        with _TRACER.span("serve/draft") as dsp:
            for u in uids:
                req = self._reqs[u]
                cap = max(0, min(self._spec_k_for(req), req.remaining - 1,
                                 room[u] - 1, budget))
                d = []
                if cap > 0:
                    d = [int(t) for t in self.drafter.draft(
                        u, req.prompt + req.produced, cap)][:cap]
                budget -= len(d)
                drafts.append(d)
                seeds.append(self._decodes[u])
        draft_s = dsp.dur_s
        for u, d in zip(uids, drafts):
            self._tspan(self._reqs[u], "draft", t0=dsp.t0, dur_s=draft_s,
                        k=len(d))
        result = self.eng.verify_decode(uids, seeds, drafts,
                                        draft_wall_s=draft_s)
        with _TRACER.span("serve/window_apply"):
            self._count("serving/spec_windows")
            if result.drafted:
                self._count("serving/spec_drafted", result.drafted)
            if result.accepted_draft:
                self._count("serving/spec_accepted", result.accepted_draft)
            return self._apply_window_results(
                uids, result.accepted, set(result.nonfinite_uids),
                wall_s=result.duration_s + draft_s,
                compiled=result.compiled,
                span_kind="verify", span_wall_s=result.duration_s)

    def step(self) -> List[int]:
        """One scheduler iteration; returns uids that reached a terminal
        state.  Lifecycle passes (cancel, expiry) run FIRST, so no request
        outlives its deadline by more than one bounded window."""
        with self._lock, _TRACER.step_span(
                name="serve/step", waiting=len(self._waiting),
                prefilling=len(self._prefilling),
                decoding=len(self._decodes)) as sp:
            # a window in flight is drained first, unless this step may
            # dispatch its successor ahead of that: the passes below, and
            # admission, see its riders drained (a finisher's slot free)
            done = self._settle()
            with _TRACER.span("serve/lifecycle"):
                done += self._process_cancellations()
                done += self._process_expiries()
            done += self._settle()      # a deadline that passed in between
            # prefill/admission first — finishing prefills frees the decode
            # path to run fused windows over the full live set.  A BLOCKED
            # queue head (no reservation, no preemption victim) yields an
            # empty batch: the decode window runs, draining the live set
            # toward the capacity the head is waiting for.
            batch = []
            if self._prefilling or self._waiting:
                with _TRACER.span("serve/admit") as asp:
                    batch = self._build_prefill_batch(asp)
            kind = "prefill" if batch else "decode" if self._decodes else "idle"
            sp.set(kind=kind)                       # what the step DID
            try:
                if batch:
                    done += self._run_prefill(batch)
                elif self._decodes:
                    with _TRACER.span("serve/window") as wsp:
                        done += self._run_decode_window(wsp)
            except Exception as e:  # said, then the caller's: requests stay
                self._say_why(None, "error", kind, 0, error=type(e).__name__)
                self._settle_safely()
                raise
            return done

    def _settle_safely(self) -> None:
        """Drain what is in flight whatever may come next, and never raise:
        for a step that has already failed (its riders get their tokens if
        the device still answers) and for the drain's mop-up."""
        fl, self._inflight = self._inflight, None
        if fl is None:
            return
        try:
            self._drained(fl, "draining" if self.draining else "error")
        except Exception as e:  # noqa: BLE001 — the first error is the caller's
            logger.error(f"window in flight lost after a failed step: {e!r}")
            if fl.dropped:
                self.eng.flush(sorted(fl.dropped))

    def run_until_idle(self, max_iters: int = 10_000) -> None:
        """Drive until no live work remains (tests / batch mode)."""
        idle_guard = 0
        for _ in range(max_iters):
            if not self.pending:
                return
            before = self._progress_mark()
            self.step()
            idle_guard = idle_guard + 1 \
                if self._progress_mark() == before else 0
            if idle_guard > 3:
                raise RuntimeError(
                    f"scheduler made no progress ({self.pending} pending)")
        raise RuntimeError(f"not idle after {max_iters} iterations")

    def _progress_mark(self) -> Tuple[int, int]:
        return (sum(len(r.produced) for r in self._reqs.values())
                + sum(r._prefill_pos for r in self._reqs.values()),
                self.pending)

    # ------------------------------------------------------------------ #
    # Drain (SIGTERM path)
    # ------------------------------------------------------------------ #
    def start_drain(self) -> None:
        # the flag goes up BEFORE the lock is asked for: a driver thread
        # that steps back to back re-takes the lock the moment it lets go
        # of it (Python's locks are not fair), and a drain that waits for
        # the lock is not seen by ``/healthz`` or by ``submit`` until the
        # last request is through (tools/check_serving_smoke.py, drain)
        first, self.draining = not self.draining, True
        if first:
            with self._lock:
                self._event("serving_drain_start",
                            pending=self.pending,
                            predicted_s=self.predicted_drain_s())

    def drain(self, deadline_s: float = 30.0) -> Dict[str, int]:
        """Stop admitting, finish in-flight work bounded by the deadline;
        whatever is still live at the deadline is expired and flushed.
        Returns {completed, expired} counts for this drain."""
        self.start_drain()
        # goodput: the drain envelope is a residual — the windows it runs
        # attribute their own walls (compute/compile), only the loop's
        # remaining wall (scheduling, expiry mop-up) lands in ``drain``
        with goodput_residual("drain"):
            t_end = self.clock() + deadline_s
            completed = 0
            while self.pending and self.clock() < t_end:
                try:
                    finished = self.step()
                except Exception as e:  # noqa: BLE001 — a raising step
                    # must not wedge the drain: whatever is still live gets
                    # expired and flushed by the mop-up below, and the
                    # server still exits
                    logger.error(f"drain step failed: {e!r}")
                    break
                for uid in finished:
                    if self._reqs[uid].state == RequestState.FINISHED:
                        completed += 1
            expired = 0
            with self._lock:
                self._settle_safely()      # nothing stays in flight
                for req in list(self._reqs.values()):
                    if req.state not in TERMINAL_STATES:
                        self._retire(req, RequestState.EXPIRED,
                                     "drain_deadline", "serving_expired",
                                     "serving/drain_expired")
                        expired += 1
                self._event("serving_drain_done", completed=completed,
                            expired=expired)
        return {"completed": completed, "expired": expired}

    # ------------------------------------------------------------------ #
    # Health / telemetry plumbing
    # ------------------------------------------------------------------ #
    def health_state(self) -> Tuple[str, List[str]]:
        """Serving status for /healthz: ``draining`` > ``degraded``
        (recent NaN/hang incident) > ``saturated`` (queue full or recent
        shed) > ``healthy``."""
        if self.draining:       # without the lock: see start_drain
            live = (len(self._waiting) + len(self._prefilling)
                    + len(self._decodes))
            return "draining", [f"{live} request(s) in flight"]
        with self._lock:
            now = self.clock()
            if self.last_incident_t is not None and \
                    now - self.last_incident_t <= self.degraded_window_s:
                return "degraded", [
                    f"{self.last_incident_kind} incident "
                    f"{now - self.last_incident_t:.0f}s ago"]
            reasons = []
            if len(self._waiting) >= self.max_queue:
                reasons.append(f"queue full ({len(self._waiting)}"
                               f"/{self.max_queue})")
            if self.last_shed_t is not None and \
                    now - self.last_shed_t <= self.degraded_window_s:
                reasons.append(
                    f"shed traffic {now - self.last_shed_t:.0f}s ago")
            if reasons:
                return "saturated", reasons
            return "healthy", []

    def _count(self, name: str, n: int = 1) -> None:
        self.counters[name] += n
        tel = get_telemetry()
        if tel is not None:
            tel.metrics.counter(name).inc(n)

    def _observe(self, name: str, value, req: ServeRequest) -> None:
        """A latency into its histogram and the store's exemplar slots."""
        if value is None:
            return
        tel = get_telemetry()
        if tel is not None:
            tel.metrics.histogram(name).observe(float(value))
        store = get_trace_store()
        if store is not None and req.trace is not None:
            store.note_exemplar(name.rsplit("/", 1)[1], value,
                                req.trace.trace_id)

    def _event(self, kind: str, **fields) -> None:
        tel = get_telemetry()
        if tel is not None:
            tel.event(kind, **fields)

    def _publish_gauges(self) -> None:
        tel = get_telemetry()
        if tel is None:
            return
        m = tel.metrics
        m.gauge("serving/queue_depth").set(len(self._waiting))
        m.gauge("serving/active_seqs").set(
            len(self._prefilling) + len(self._decodes))
        m.gauge("serving/kv_pressure").set(
            round(self.eng.kv_used_fraction(), 4))
        cache = self.eng.prefix_cache
        if cache is not None:
            total = cache.hits + cache.misses
            m.gauge("serving/prefix_hit_rate").set(
                round(cache.hits / total, 4) if total else 0.0)
            m.gauge("serving/prefix_cached_pages").set(cache.nodes)
