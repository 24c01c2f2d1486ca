"""Request-trace store: bounded ring + JSONL + tail-based sampling.

Every hop that owns a :class:`~.context.TraceContext` appends TYPED spans
here — ``queue_wait``, ``admission``, ``compile``, ``prefill``,
``kv_ship_{encode,wire,import}``, ``decode_window``, ``preempt``,
``resume``, ``reroute``, ``draft``, ``verify``, ``route`` — each carrying
the request uid, a wall-clock ``t0`` (unix seconds, so spans from
different processes merge onto one timeline) and a duration.  The store is
process-global (:func:`install_trace_store` / :func:`get_trace_store`),
mirroring the telemetry hub's install pattern: ``None`` IS the disabled
fast path, every instrumentation site guards with one global read.

Merging: a replica returns its spans IN-BAND with the HTTP response
(``trace`` field on ``/v1/generate`` / ``/v1/prefill`` bodies and terminal
SSE events); the router :meth:`merge`\\ s them into its own store, so
host-0/the router owns the fleet-merged view.  Spans dedupe by a per-span
``sid``, which makes merging idempotent — including the in-process fleet
harness where router and replicas share one global store.

Tail-based sampling (the keep/drop decision runs at trace COMPLETION,
when the interesting-ness is known):

  * always keep FLAGGED traces — shed / preempted / rerouted /
    nan_isolated / deadline_expired / drain_expired / mid_stream_error /
    window_hang;
  * always keep traces holding a TTFT/TPOT exemplar slot (the histogram
    tail must link to retrievable traces);
  * keep the slow cohort — wall time at or above the rolling p99 of
    recently finished traces (armed once enough walls are seen);
  * sample the steady-state remainder 1-in-``sample_every``.

Kept traces land in the bounded in-memory ring (the ``/traces`` live
endpoint and ``dstpu-trace``'s live views) and are written through to
``traces.jsonl`` (rotation-capable EventLog, ``kind: "trace"`` lines) for
the offline CLI; dropped traces are discarded wholesale, so steady-state
overhead stays bounded no matter the request rate.  Per-segment duration
aggregates (and the ``serving/trace_segment_s`` registry histogram behind
the ``dstpu-telemetry`` TTFT-decomposition section) are updated for EVERY
span, sampled out or not — the percentiles describe all traffic, the ring
holds the interesting subset.
"""
from __future__ import annotations

import collections
import itertools
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .. import get_telemetry

#: canonical span kinds (attrs may refine; kinds stay closed so the
#: decomposition tables and the waterfall renderer have a stable axis)
SPAN_KINDS = (
    "queue_wait",      # submit → admission (per admission; resets on preempt)
    "admission",       # reservation + prefix/KV graft work at the queue head
    "compile",         # a first-use decode/verify window (wall = XLA compile)
    "prefill",         # one put() forward covering this request's chunk
    "kv_ship_encode",  # disagg producer: KV pages → canonical rows
    "kv_ship_wire",    # router-measured ship leg (HTTP minus replica time)
    "kv_ship_import",  # disagg consumer: rows → local page geometry
    "decode_window",   # one drained fused decode window
    "preempt",         # KV-pressure eviction marker
    "resume",          # preempted request back to DECODE after recompute
    "reroute",         # router moved zero-token work off a dead replica
    "draft",           # speculative drafter host time for one verify window
    "verify",          # one speculative verify window
    "route",           # router wrapper: admission → final forwarded byte
)

#: segment kinds whose p95s sum into the TTFT estimate (time queued plus
#: prompt service — the part of TTFT fleet capacity actually controls);
#: canonical here, the fleet controller imports it
TTFT_SEGMENTS = ("queue_wait", "prefill")

#: flags that force tail-sampling to KEEP a trace.  ``exemplar`` is set
#: by :meth:`RequestTraceStore.note_exemplar` itself: a flag rides the
#: in-band payload, so the ROUTER's independently-sampled merged copy is
#: kept too and the histogram-tail link resolves fleet-wide (a slot later
#: stolen by a larger value leaves the flag — a small over-keep bias on
#: exactly the traces worth keeping)
ALWAYS_KEEP_FLAGS = ("shed", "preempted", "rerouted", "nan_isolated",
                     "deadline_expired", "drain_expired",
                     "mid_stream_error", "window_hang",
                     "prefill_fallback", "exemplar")

#: retirement reason → trace flag (satellite: incidents name the victim)
FLAG_BY_REASON = {
    "nan": "nan_isolated",
    "deadline": "deadline_expired",
    "ttft_timeout": "deadline_expired",
    "drain_deadline": "drain_expired",
    "queue_full": "shed",
    "draining": "shed",
}


# span ids: a per-process random prefix + a counter — unique across the
# fleet for merge dedupe, ~10x cheaper than a uuid4 per span (spans are
# recorded inside the decode window hot path)
_SID_PREFIX = os.urandom(4).hex()
_SID_COUNTER = itertools.count()


def _sid() -> str:
    return f"{_SID_PREFIX}{next(_SID_COUNTER):x}"


class RequestTraceStore:
    """One process's view of request traces (see module docstring)."""

    def __init__(self, jsonl_path: Optional[str] = None,
                 max_traces: int = 256, max_spans_per_trace: int = 512,
                 sample_every: int = 10, slow_quantile: float = 0.99,
                 slow_min_samples: int = 32, wall_window: int = 512,
                 exemplar_k: int = 4, segment_window: int = 512,
                 segment_window_s: float = 60.0,
                 jsonl_max_mb: float = 64.0, clock=time.monotonic):
        self.sample_every = max(int(sample_every), 1)
        self.slow_quantile = float(slow_quantile)
        self.slow_min_samples = int(slow_min_samples)
        self.max_traces = max(int(max_traces), 1)
        self.max_spans_per_trace = int(max_spans_per_trace)
        self.exemplar_k = int(exemplar_k)
        self._lock = threading.RLock()
        #: trace_id → record; records carry done/kept marks and stay in
        #: this one ordered map so late spans (amend semantics) and
        #: re-finishes (router after replica, in-process) just work
        self._traces: "collections.OrderedDict[str, Dict[str, Any]]" = \
            collections.OrderedDict()
        #: sid tombstones of traces evicted while still ACTIVE (> max
        #: concurrent in-flight): a late span/merge for such a trace must
        #: neither re-count trace/started nor re-observe merged segments
        self._evicted_seen: "collections.OrderedDict[str, set]" = \
            collections.OrderedDict()
        self._walls: "collections.deque[float]" = collections.deque(
            maxlen=int(wall_window))
        self._segments: Dict[str, "collections.deque[float]"] = {}
        self._segment_window = int(segment_window)
        #: TIME-windowed (ts, dur) pairs per kind: the count-bounded deque
        #: above keeps stale breaches alive forever under low traffic, so
        #: the rolling p95 the fleet controller trusts (``p95_window_s``)
        #: only sees the last ``segment_window_s`` seconds
        self._seg_recent: Dict[str,
                               "collections.deque[Tuple[float, float]]"] = {}
        self.segment_window_s = float(segment_window_s)
        self.clock = clock
        self._seg_totals: Dict[str, Tuple[int, float]] = {}
        self._exemplars: Dict[str, List[Tuple[float, str]]] = {}
        self._finish_seq = 0
        self.counters: "collections.Counter[str]" = collections.Counter()
        self._log = None
        if jsonl_path:
            from ..events import EventLog

            self._log = EventLog(
                jsonl_path, max_bytes=int(jsonl_max_mb * 1024 * 1024))

    # ---------------------------------------------------------------- #
    # Recording
    # ---------------------------------------------------------------- #
    def _record(self, trace_id: str) -> Dict[str, Any]:
        rec = self._traces.get(trace_id)
        if rec is None:
            was_evicted = self._evicted_seen.pop(trace_id, None)
            rec = self._traces[trace_id] = {
                "trace": trace_id, "uid": None, "t_start": time.time(),
                "spans": [], "flags": [], "wall_s": None,
                "done": False, "kept": False,
                # every sid ever appended — survives a sampling drop as
                # a tombstone so a later merge() (in-process shared
                # store) cannot re-observe the same spans
                "_seen": was_evicted if was_evicted is not None else set(),
            }
            if was_evicted is None:
                self.counters["trace/started"] += 1
                self._count_registry("trace/started")
            self._evict_locked()
        return rec

    def _evict_locked(self) -> None:
        while len(self._traces) > self.max_traces:
            # oldest DONE trace first; else the oldest of anything (an
            # abandoned active trace must not pin the ring forever)
            victim = next((t for t, r in self._traces.items() if r["done"]),
                          next(iter(self._traces)))
            rec = self._traces.pop(victim)
            if not rec["done"]:
                # still in flight (> max_traces concurrent): stash the
                # sid tombstones so a late span/merge neither double-
                # counts trace/started nor re-observes segments
                self._evicted_seen[victim] = rec["_seen"]
                while len(self._evicted_seen) > self.max_traces:
                    self._evicted_seen.popitem(last=False)
            self.counters["trace/evicted"] += 1

    def add_span(self, trace_id: str, kind: str, t0: float, dur_s: float,
                 component: str = "serve", uid: Optional[int] = None,
                 **attrs) -> Optional[Dict[str, Any]]:
        span = {"sid": _sid(), "kind": str(kind), "component": str(component),
                "uid": uid, "t0": float(t0), "dur_s": float(dur_s)}
        if attrs:
            span["attrs"] = attrs
        with self._lock:
            rec = self._record(trace_id)
            if uid is not None:
                rec["uid"] = uid
            if len(rec["spans"]) >= self.max_spans_per_trace:
                self.counters["trace/spans_dropped"] += 1
                return None
            rec["spans"].append(span)
            rec["_seen"].add(span["sid"])
            self._observe_segment_locked(kind, dur_s)
        return span

    def merge(self, trace_id: str, payload: Optional[Dict[str, Any]]) -> int:
        """Fold a remote hop's trace payload (``{"trace", "spans",
        "flags", ...}`` — a :meth:`finish` return or response field) into
        this store.  Spans dedupe by ``sid``; segment aggregates only
        count genuinely-new spans, so the in-process fleet harness (one
        shared store) never double-counts.  Returns spans added."""
        if not payload or not isinstance(payload, dict):
            return 0
        spans = payload.get("spans") or []
        added = 0
        with self._lock:
            rec = self._record(trace_id)
            seen = rec["_seen"]
            # dedupe STORAGE against what the record currently holds, and
            # AGGREGATES against every sid ever observed: a span whose
            # sid is tombstoned but no longer stored (its first finish
            # sampled the trace out before this hop flagged it worth
            # keeping) is restored to the record without re-counting its
            # segment into the histograms
            stored = {s.get("sid") for s in rec["spans"]}
            for span in spans:
                if not isinstance(span, dict):
                    continue
                sid = span.get("sid") or _sid()
                if sid in stored:
                    continue
                if len(rec["spans"]) >= self.max_spans_per_trace:
                    self.counters["trace/spans_dropped"] += 1
                    break
                observe = sid not in seen
                span = dict(span)
                span["sid"] = sid
                rec["spans"].append(span)
                stored.add(sid)
                seen.add(sid)
                added += 1
                if observe:
                    try:
                        self._observe_segment_locked(
                            str(span.get("kind", "?")),
                            float(span.get("dur_s", 0.0)))
                    except (TypeError, ValueError):
                        pass
                if rec["uid"] is None and span.get("uid") is not None:
                    rec["uid"] = span["uid"]
            for fl in payload.get("flags") or []:
                if fl not in rec["flags"]:
                    rec["flags"].append(str(fl))
        return added

    def flag(self, trace_id: str, reason: str) -> None:
        with self._lock:
            rec = self._record(trace_id)
            if reason not in rec["flags"]:
                rec["flags"].append(str(reason))

    # ---------------------------------------------------------------- #
    # Exemplars (histogram tail → trace id links)
    # ---------------------------------------------------------------- #
    def note_exemplar(self, metric: str, value: float,
                      trace_id: str) -> bool:
        """Offer ``(value, trace_id)`` as a tail exemplar for ``metric``
        (``ttft_s`` / ``tpot_s``).  The top-``exemplar_k`` largest values
        win; a trace holding a slot is force-kept at finish so the link
        always resolves.  Returns True when the offer entered the set."""
        value = float(value)
        with self._lock:
            ex = self._exemplars.setdefault(metric, [])
            if any(t == trace_id for _, t in ex):
                return False
            if len(ex) >= self.exemplar_k and value <= min(ex)[0]:
                return False
            ex.append((value, trace_id))
            ex.sort(reverse=True)
            del ex[self.exemplar_k:]
            # the keep decision must travel with the trace (see
            # ALWAYS_KEEP_FLAGS): flag under the same lock hold
            rec = self._record(trace_id)
            if "exemplar" not in rec["flags"]:
                rec["flags"].append("exemplar")
        tel = get_telemetry()
        if tel is not None:
            tel.event("trace_exemplar", metric=metric,
                      value=round(value, 6), trace=trace_id)
        return True

    def exemplars(self) -> Dict[str, List[Dict[str, Any]]]:
        with self._lock:
            return {m: [{"value": v, "trace": t} for v, t in ex]
                    for m, ex in self._exemplars.items()}

    def _is_exemplar_locked(self, trace_id: str) -> bool:
        return any(t == trace_id
                   for ex in self._exemplars.values() for _, t in ex)

    # ---------------------------------------------------------------- #
    # Completion + tail sampling
    # ---------------------------------------------------------------- #
    def _slow_threshold_locked(self) -> Optional[float]:
        if len(self._walls) < self.slow_min_samples:
            return None
        svals = sorted(self._walls)
        from ..metrics import _percentile

        return _percentile(svals, self.slow_quantile * 100.0)

    def finish(self, trace_id: str, flag: Optional[str] = None,
               wall_s: Optional[float] = None) -> Dict[str, Any]:
        """Seal a trace and run the tail-sampling keep/drop decision;
        returns the FULL record either way (in-band propagation to the
        next hop is never subject to local sampling).  Re-finishing an
        already-done trace (the router finishes after the replica did, on
        a shared in-process store) updates flags/wall and re-evaluates
        keep — a drop can upgrade to keep, never the reverse."""
        with self._lock:
            rec = self._record(trace_id)
            if flag and flag not in rec["flags"]:
                rec["flags"].append(str(flag))
            if wall_s is not None:
                rec["wall_s"] = float(wall_s)
            elif rec["wall_s"] is None:
                rec["wall_s"] = max(time.time() - rec["t_start"], 0.0)
            first_finish = not rec["done"]
            rec["done"] = True
            if first_finish:
                self._finish_seq += 1
                self.counters["trace/finished"] += 1
                self._count_registry("trace/finished")
                self._walls.append(rec["wall_s"])
            keep = bool(rec["flags"]) \
                or self._is_exemplar_locked(trace_id)
            if not keep and first_finish:
                # probabilistic keeps are decided ONCE, at the first
                # finish: a re-finish (router after replica on a shared
                # store) may only upgrade for DETERMINISTIC reasons
                # (flags/exemplar) — re-rolling the sampling counter
                # against a trace whose spans were already discarded
                # would keep nondeterministic, span-less records.
                # STRICTLY above the rolling p99: under perfectly uniform
                # walls nothing qualifies as "slow", so steady state
                # still samples 1-in-N instead of keeping everything
                thresh = self._slow_threshold_locked()
                keep = (thresh is not None and rec["wall_s"] > thresh) \
                    or (self._finish_seq - 1) % self.sample_every == 0
            newly_kept = keep and not rec["kept"]
            rec["kept"] = rec["kept"] or keep
            if rec["flags"] and not rec.get("_flag_counted"):
                rec["_flag_counted"] = True
                self.counters["trace/flagged"] += 1
                self._count_registry("trace/flagged")
            if first_finish:
                self.counters["trace/kept" if keep else "trace/dropped"] += 1
                self._count_registry(
                    "trace/kept" if keep else "trace/dropped")
            elif newly_kept:
                # drop→keep upgrade on a re-finish (a flag arrived after
                # the first finish, e.g. the router flagging a replica-
                # finished trace on a shared store): MOVE the snapshot
                # count so kept+dropped keeps agreeing with the ring/
                # jsonl, but keep the EXPORTED registry counters
                # monotonic (a scraper rate()s them; a decrement reads
                # as a counter reset) — upgrades get their own counter,
                # so scraped dropped-minus-upgraded matches the ring
                self.counters["trace/dropped"] -= 1
                self.counters["trace/kept"] += 1
                self.counters["trace/upgraded"] += 1
                self._count_registry("trace/kept")
                self._count_registry("trace/upgraded")
            if not rec["kept"]:
                # discard the span payload, keep a sid tombstone: a later
                # merge() of the same spans (in-process shared store, or
                # a retried in-band payload) must dedupe, not re-observe
                # the segment aggregates.  The tombstone is a few sids,
                # ring-bounded like everything else.
                out = dict(rec, spans=list(rec["spans"]),
                           flags=list(rec["flags"]))
                for k in ("_seen", "_flag_counted"):
                    out.pop(k, None)
                rec["spans"] = []
                return out
            if self._log is not None:
                # every finish of a kept trace re-emits: a re-finish
                # (router after replica on a shared store) carries spans
                # and the true end-to-end wall the first emit predates —
                # the loader takes the newest line per trace id
                self._log.emit("trace",
                               **{k: v for k, v in rec.items()
                                  if k not in ("done", "kept", "_seen",
                                               "_flag_counted")})
            return rec

    # ---------------------------------------------------------------- #
    # Reads (live /traces endpoint, dstpu-trace, tests)
    # ---------------------------------------------------------------- #
    @staticmethod
    def _copy(rec: Dict[str, Any]) -> Dict[str, Any]:
        out = dict(rec, spans=list(rec["spans"]), flags=list(rec["flags"]))
        for k in ("_seen", "_flag_counted"):
            out.pop(k, None)
        return out

    def get(self, trace_id: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            rec = self._traces.get(trace_id)
            if rec is None or (rec["done"] and not rec["kept"]):
                return None                    # unknown or sampled out
            return self._copy(rec)

    def traces(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [self._copy(r) for r in self._traces.values()
                    if not (r["done"] and not r["kept"])]

    def slowest(self, n: int = 10) -> List[Dict[str, Any]]:
        done = [r for r in self.traces() if r["done"]]
        done.sort(key=lambda r: r.get("wall_s") or 0.0, reverse=True)
        return done[:max(int(n), 0)]

    def segment_summary(self) -> Dict[str, Dict[str, Any]]:
        """Per-segment duration stats over EVERY observed span (kept and
        sampled-out alike): count/total plus p50/p95 from the bounded
        recent window — the live TTFT/TPOT decomposition."""
        from ..metrics import _percentile

        out: Dict[str, Dict[str, Any]] = {}
        with self._lock:
            now = self.clock()
            for kind, window in self._segments.items():
                count, total = self._seg_totals.get(kind, (0, 0.0))
                svals = sorted(window)
                recent = self._seg_recent.get(kind)
                rvals = []
                if recent is not None:
                    self._expire_recent_locked(recent, now)
                    rvals = sorted(d for _, d in recent)
                out[kind] = {
                    "count": count, "total_s": total,
                    "mean_s": total / count if count else 0.0,
                    "p50_s": _percentile(svals, 50) if svals else None,
                    "p95_s": _percentile(svals, 95) if svals else None,
                    # rolling TIME window (last segment_window_s seconds):
                    # None once traffic goes quiet — a stale breach must
                    # age out of the controller's overload signal
                    "p95_window_s": _percentile(rvals, 95) if rvals
                    else None,
                }
        return out

    def ttft_p95_window_s(self) -> Optional[float]:
        """Rolling-window TTFT p95 estimate: the sum of the time-windowed
        segment p95s over the TTFT segments (queue_wait + prefill); None
        when the window holds no recent traffic."""
        summary = self.segment_summary()
        parts = [row.get("p95_window_s") for kind, row in summary.items()
                 if kind in TTFT_SEGMENTS
                 and row.get("p95_window_s") is not None]
        return float(sum(parts)) if parts else None

    def _expire_recent_locked(self, recent, now: float) -> None:
        horizon = now - self.segment_window_s
        while recent and recent[0][0] < horizon:
            recent.popleft()

    def _observe_segment_locked(self, kind: str, dur_s: float) -> None:
        win = self._segments.get(kind)
        if win is None:
            win = self._segments[kind] = collections.deque(
                maxlen=self._segment_window)
        win.append(dur_s)
        recent = self._seg_recent.get(kind)
        if recent is None:
            recent = self._seg_recent[kind] = collections.deque(
                maxlen=self._segment_window)
        now = self.clock()
        recent.append((now, dur_s))
        self._expire_recent_locked(recent, now)
        count, total = self._seg_totals.get(kind, (0, 0.0))
        self._seg_totals[kind] = (count + 1, total + dur_s)
        tel = get_telemetry()
        if tel is not None:
            tel.metrics.histogram("serving/trace_segment_s").observe(
                dur_s, segment=kind)

    def _count_registry(self, name: str) -> None:
        tel = get_telemetry()
        if tel is not None:
            tel.metrics.counter(name).inc()

    def flush(self) -> None:
        if self._log is not None:
            self._log.flush()

    def close(self) -> None:
        if self._log is not None:
            self._log.close()


# --------------------------------------------------------------------- #
# Process-global instance (telemetry-hub install pattern)
# --------------------------------------------------------------------- #
_GLOBAL: Optional[RequestTraceStore] = None
_GLOBAL_LOCK = threading.Lock()


def install_trace_store(store: Optional[RequestTraceStore]
                        ) -> Optional[RequestTraceStore]:
    """Install (or clear, with None) the process-global trace store."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        previous, _GLOBAL = _GLOBAL, store
    return previous


def get_trace_store() -> Optional[RequestTraceStore]:
    return _GLOBAL


def add_trace_cli_args(parser) -> None:
    """The tracing flags shared by ``dstpu-serve`` and ``dstpu-router``."""
    parser.add_argument("--no-trace", action="store_true",
                        help="disable request tracing (spans, /traces, "
                             "traces.jsonl)")
    parser.add_argument("--trace-sample", type=int, default=10,
                        help="tail-sampling rate: keep 1-in-N steady-state "
                             "traces (flagged/slow/exemplar traces are "
                             "always kept); 1 keeps everything")


def install_trace_store_from_cli(args,
                                 telemetry_dir: str
                                 ) -> Optional[RequestTraceStore]:
    """Build + install the process store from :func:`add_trace_cli_args`
    flags; ``--no-trace`` installs nothing (the disabled fast path)."""
    if getattr(args, "no_trace", False):
        return None
    store = RequestTraceStore(
        jsonl_path=os.path.join(telemetry_dir, "traces.jsonl"),
        sample_every=args.trace_sample)
    install_trace_store(store)
    return store


# --------------------------------------------------------------------- #
# Shared recording helpers: the store-None/trace-None disabled fast path
# every recorder (LifecycleScheduler, FleetRouter, servers) needs.  One
# copy here so a change to the disabled-path contract happens once.
# --------------------------------------------------------------------- #
def trace_id_of(trace) -> Optional[str]:
    """The trace id for event/log payloads, or None when untraced."""
    return trace.trace_id if trace is not None else None


def record_span(trace, kind: str, t0: float, dur_s: float,
                component: str, **attrs) -> None:
    """Append a typed span for ``trace`` to the installed store; no-op
    when tracing is disabled or the request is untraced."""
    store = get_trace_store()
    if store is None or trace is None:
        return
    store.add_span(trace.trace_id, kind, t0=t0, dur_s=dur_s,
                   component=component, **attrs)


def merge_trace(trace, body) -> None:
    """Merge an in-band span payload (``body["trace"]``) from a
    downstream hop's response into ``trace``; no-op when disabled,
    untraced, or the body carries no payload."""
    store = get_trace_store()
    if store is None or trace is None or not isinstance(body, dict):
        return
    store.merge(trace.trace_id, body.get("trace"))


def flag_trace(trace, reason: str) -> None:
    """Attach an always-keep flag to ``trace``; no-op when disabled or
    untraced."""
    store = get_trace_store()
    if store is not None and trace is not None:
        store.flag(trace.trace_id, reason)


# --------------------------------------------------------------------- #
# Shared helpers (coverage math + /traces endpoint payload)
# --------------------------------------------------------------------- #
def span_coverage(spans: Sequence[Dict[str, Any]], t0: float, t1: float,
                  exclude: Tuple[str, ...] = ("route",)) -> float:
    """Fraction of ``[t0, t1]`` covered by the UNION of span intervals.
    Wrapper spans (``route`` — the router leg that by construction covers
    nearly the whole request) are excluded by default so the number
    reflects attributed WORK segments, not envelopes."""
    if t1 <= t0:
        return 0.0
    ivals = []
    for s in spans:
        if s.get("kind") in exclude:
            continue
        a = max(float(s.get("t0", 0.0)), t0)
        b = min(float(s.get("t0", 0.0)) + float(s.get("dur_s", 0.0)), t1)
        if b > a:
            ivals.append((a, b))
    ivals.sort()
    covered = 0.0
    cur_a = cur_b = None
    for a, b in ivals:
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                covered += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        covered += cur_b - cur_a
    return covered / (t1 - t0)


def traces_endpoint_payload(query: Dict[str, Any]
                            ) -> Tuple[int, Dict[str, Any]]:
    """The ``GET /traces`` body shared by dstpu-serve, dstpu-router and
    the live observability server.  ``query`` is a parse_qs dict:
    ``?request=<trace_id>`` → one full trace (404 when unknown/sampled
    out); ``?slowest=N`` → the N slowest; default → summary (segment
    decomposition, counters, exemplars, slowest few)."""
    store = get_trace_store()
    if store is None:
        return 404, {"error": "request tracing disabled "
                              "(no trace store installed)"}

    def _q(name):
        v = query.get(name)
        return v[0] if isinstance(v, (list, tuple)) and v else v

    want = _q("request") or _q("trace")
    if want:
        rec = store.get(str(want))
        if rec is None:
            return 404, {"error": f"unknown trace {want} "
                                  f"(never seen, evicted, or sampled out)"}
        rec.pop("done", None)
        rec.pop("kept", None)
        return 200, rec
    try:
        n = int(_q("slowest") or 5)
    except (TypeError, ValueError):
        n = 5
    slow = []
    for rec in store.slowest(n):
        by_kind: Dict[str, float] = {}
        for s in rec["spans"]:
            # merge() stores in-band spans verbatim — a version-skewed
            # replica's span may lack keys; the live endpoint must not
            # 500 on it
            kind = str(s.get("kind", "?"))
            try:
                dur = float(s.get("dur_s") or 0.0)
            except (TypeError, ValueError):
                dur = 0.0
            by_kind[kind] = by_kind.get(kind, 0.0) + dur
        slow.append({"trace": rec["trace"], "uid": rec["uid"],
                     "wall_s": rec["wall_s"], "flags": rec["flags"],
                     "n_spans": len(rec["spans"]),
                     "segments_s": {k: round(v, 6)
                                    for k, v in sorted(by_kind.items())}})
    return 200, {
        "segments": store.segment_summary(),
        "ttft_p95_window_s": store.ttft_p95_window_s(),
        "ttft_window_s": store.segment_window_s,
        "counters": dict(store.counters),
        "exemplars": store.exemplars(),
        "slowest": slow,
    }
