"""Span-based structured tracer.

``tracer.span("fwd")`` nests (thread-local stack), records wall-clock
durations, optionally fences on a JAX value (``block_until_ready``) so the
measured time covers device execution instead of dispatch, and mirrors every
span into ``jax.profiler.TraceAnnotation`` so spans line up with XLA ops when
an xprof/jax profile is active.  ``step_span`` is the
``StepTraceAnnotation`` analogue that delimits whole training steps.

Export: :meth:`Tracer.to_chrome_trace` renders the recorded spans as a
Chrome-trace/Perfetto-compatible JSON object (``ph: "X"`` complete events,
microsecond timestamps) so a run can be dropped into ``chrome://tracing`` or
https://ui.perfetto.dev with no conversion step.

Disabled cost: a disabled tracer hands back one shared no-op span object —
no allocation, no locking — so instrumentation can stay in the hot path
unconditionally.

One process-global tracer always exists (:func:`get_tracer`).  The layer
boundaries of the served and the trained path (``serve/*``, ``engine/*``)
record on it whether or not a ``Telemetry`` hub is installed: two clock
reads, one ``TraceAnnotation`` (a flag check while no profiler session
runs, a host event on the device trace's clock while one does) and one
append to the bounded ring — no file, socket, thread or sync.  A hub adopts
this tracer and adds its exporters; a profiler session sees the spans in
its xplane.

The account of a step.  The two spans that delimit a step (``step_span``:
``serve/step``, ``engine/train_batch``) also say what the host did with the
thread meanwhile, from ONE ``getrusage(RUSAGE_THREAD)`` call an edge —
``cpu_s`` (the thread's CPU time, user + system), ``nvcsw`` / ``nivcsw``
(its voluntary / involuntary context switches) — so that a step that took
seconds can be told apart afterwards: descheduled (wall far over CPU,
involuntary switches), blocked below Python (no CPU) or running Python that
long (CPU = wall).  Under gVisor, the chip's host, the switches read 0 and
``cpu_s`` has a 10 ms grain: "ran" against "did not run" is what it tells
there.  And every pause of Python's
collector is an ``engine/host_gc`` span (``generation``, ``collected``)
under whatever span it interrupted.  No other span pays for either.
"""
from __future__ import annotations

import collections
import functools
import gc
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

try:
    import resource

    _RUSAGE_THREAD: Optional[int] = resource.RUSAGE_THREAD
except (ImportError, AttributeError):       # not Linux: no host facts
    _RUSAGE_THREAD = None

#: ring capacity of a tracer nobody configured (the hub's ``max_spans``
#: default is the same number)
DEFAULT_MAX_SPANS = 100_000


class _NullSpan:
    """Shared do-nothing span for disabled telemetry (zero per-call cost)."""

    __slots__ = ()
    t0 = dur_s = 0.0

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def set(self, **attrs):
        return self

    def fence_on(self, value):
        return self


NULL_SPAN = _NullSpan()


class SpanRecord:
    __slots__ = ("name", "start_s", "dur_s", "depth", "parent", "tid",
                 "attrs", "error")

    def __init__(self, name: str, start_s: float, dur_s: float, depth: int,
                 parent: Optional[str], tid: int,
                 attrs: Optional[Dict[str, Any]], error: Optional[str]):
        self.name = name
        self.start_s = start_s      # seconds since tracer epoch
        self.dur_s = dur_s
        self.depth = depth
        self.parent = parent
        self.tid = tid
        self.attrs = attrs
        self.error = error

    def to_dict(self) -> Dict[str, Any]:
        d = {"name": self.name, "start_s": round(self.start_s, 9),
             "dur_s": round(self.dur_s, 9), "depth": self.depth,
             "parent": self.parent, "tid": self.tid}
        if self.attrs:
            d["attrs"] = self.attrs
        if self.error:
            d["error"] = self.error
        return d


_ANNOTATIONS: Optional[tuple] = None


def _annotations() -> tuple:
    """(TraceAnnotation, StepTraceAnnotation), looked up once; (None, None)
    where jax cannot be imported."""
    global _ANNOTATIONS
    if _ANNOTATIONS is None:
        try:
            import jax

            _ANNOTATIONS = (jax.profiler.TraceAnnotation,
                            jax.profiler.StepTraceAnnotation)
        except Exception:
            _ANNOTATIONS = (None, None)
    return _ANNOTATIONS


class _Span:
    __slots__ = ("_tracer", "name", "_attrs", "_sync", "_t0", "_annotation",
                 "_step_num", "dur_s", "_stack")

    def __init__(self, tracer: "Tracer", name: str, sync: Any,
                 attrs: Optional[Dict[str, Any]], step_num: Optional[int] = None):
        self._tracer = tracer
        self.name = name
        self._attrs = attrs
        self._sync = sync
        self._t0 = 0.0
        self._annotation = None
        self._step_num = step_num
        self.dur_s = 0.0           # set at exit

    @property
    def t0(self) -> float:
        """``time.perf_counter()`` at entry: with ``dur_s`` (after exit) a
        second sink can be fed from the same two clock reads."""
        return self._t0

    def set(self, **attrs) -> "_Span":
        """Attach attributes after entry (e.g. values known only mid-span)."""
        if self._attrs is None:
            self._attrs = {}
        self._attrs.update(attrs)
        return self

    def fence_on(self, value) -> "_Span":
        """Fence span exit on ``value`` (``jax.block_until_ready``) — for
        sync targets that only exist mid-span, e.g. the step's loss."""
        self._sync = value
        return self

    def __enter__(self) -> "_Span":
        tracer = self._tracer
        stack = self._stack = tracer._stack()
        stack.append(self)
        if tracer.jax_annotations:
            trace_ann, step_ann = _annotations()
            if trace_ann is not None:
                try:
                    if self._step_num is not None:
                        self._annotation = step_ann(
                            self.name, step_num=self._step_num)
                    else:
                        self._annotation = trace_ann(self.name)
                    self._annotation.__enter__()
                except Exception:
                    self._annotation = None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        tracer = self._tracer
        end = time.perf_counter()
        try:
            if self._sync is not None and exc_type is None:
                try:
                    import jax

                    jax.block_until_ready(self._sync)
                    end = time.perf_counter()
                except Exception:
                    pass
            if self._annotation is not None:
                try:
                    self._annotation.__exit__(exc_type, exc, tb)
                except Exception:
                    pass
        finally:
            stack = self._stack
            depth = len(stack) - 1
            if stack and stack[-1] is self:
                stack.pop()
            else:  # unbalanced exit — drop up to and including this span
                while stack:
                    if stack.pop() is self:
                        break
            parent = stack[-1].name if stack else None
            self.dur_s = end - self._t0
            tracer._record(SpanRecord(
                self.name, self._t0 - tracer._epoch, self.dur_s,
                max(depth, 0), parent, threading.get_ident(), self._attrs,
                exc_type.__name__ if exc_type is not None else None))
        return False  # never swallow the exception


def _host_facts() -> Optional[Tuple[float, int, int]]:
    """The calling thread's CPU seconds (user + system) and its voluntary /
    involuntary context switches so far, from ONE ``getrusage`` call; None
    where the platform does not count them by thread."""
    if _RUSAGE_THREAD is None:
        return None
    usage = resource.getrusage(_RUSAGE_THREAD)
    return usage.ru_utime + usage.ru_stime, usage.ru_nvcsw, usage.ru_nivcsw


class _StepSpan(_Span):
    """A span that delimits one step: besides its wall time, what the
    thread got of the host inside it (``cpu_s``, ``nvcsw``, ``nivcsw``)."""

    __slots__ = ("_facts",)

    def __enter__(self) -> "_StepSpan":
        super().__enter__()
        self._facts = _host_facts()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        then, now = self._facts, _host_facts()
        if then is not None and now is not None:
            self.set(cpu_s=now[0] - then[0], nvcsw=now[1] - then[1],
                     nivcsw=now[2] - then[2])
        return super().__exit__(exc_type, exc, tb)


class Tracer:
    """Records nested spans; exports Chrome-trace JSON.

    Parameters
    ----------
    enabled: disabled tracers return the shared :data:`NULL_SPAN`.
    max_spans: ring-buffer cap — the newest spans win, and a dropped-span
        counter records how many fell off (no silent truncation).
    jax_annotations: mirror spans into ``jax.profiler.TraceAnnotation``.
    """

    def __init__(self, enabled: bool = True,
                 max_spans: int = DEFAULT_MAX_SPANS,
                 jax_annotations: bool = True):
        self.enabled = enabled
        self.max_spans = max(int(max_spans), 1)
        self.jax_annotations = jax_annotations
        self.dropped = 0
        self.total_recorded = 0   # monotonic; never decreases on eviction
        self._epoch = time.perf_counter()
        self._epoch_unix = time.time()
        # re-entrant: the collector's hook (``_on_gc``) records a span at
        # whatever bytecode the collection ran, ``_record``'s own included
        self._lock = threading.RLock()
        self._spans: "collections.deque[SpanRecord]" = collections.deque(
            maxlen=self.max_spans)
        self._tls = threading.local()

    # ---------------------------------------------------------------- #
    @property
    def epoch(self) -> float:
        """``time.perf_counter()`` at ``start_s`` = 0: a record's start on
        the host's clock is ``epoch + start_s``."""
        return self._epoch

    def wall(self, t: float) -> float:
        """Unix seconds of ``t``, a ``perf_counter``/``monotonic`` reading
        (spans from several processes merge on that clock)."""
        return self._epoch_unix + (t - self._epoch)

    def configure(self, max_spans: Optional[int] = None,
                  jax_annotations: Optional[bool] = None,
                  drop_recorded: bool = False) -> None:
        """Resize the ring (the newest spans are kept, or none with
        ``drop_recorded``) and/or switch the profiler mirror;
        ``total_recorded`` stays monotonic."""
        with self._lock:
            if max_spans is not None:
                self.max_spans = max(int(max_spans), 1)
            if drop_recorded or self._spans.maxlen != self.max_spans:
                self._spans = collections.deque(
                    () if drop_recorded else self._spans,
                    maxlen=self.max_spans)
                self.dropped = 0
            if jax_annotations is not None:
                self.jax_annotations = bool(jax_annotations)

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _record(self, rec: SpanRecord) -> None:
        with self._lock:
            if len(self._spans) == self.max_spans:
                self.dropped += 1   # deque(maxlen) evicts the oldest in O(1)
            self._spans.append(rec)
            self.total_recorded += 1

    # ---------------------------------------------------------------- #
    def span(self, name: str, sync: Any = None, **attrs):
        """Context manager for one timed span.

        ``sync``: a JAX value to ``block_until_ready`` at span exit, so the
        span covers device time, not just Python dispatch.
        """
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, sync, attrs or None)

    def step_span(self, step_num: Optional[int] = None,
                  name: str = "train_step", sync: Any = None, **attrs):
        """Step-delimiting span: it also records what the thread got of the
        host inside it (``cpu_s``, ``nvcsw``, ``nivcsw``; Linux).  With a
        ``step_num`` it carries ``step`` and emits ``StepTraceAnnotation``,
        so an active JAX profile groups device ops per training step."""
        if not self.enabled:
            return NULL_SPAN
        if step_num is not None:
            attrs["step"] = step_num = int(step_num)
        return _StepSpan(self, name, sync, attrs or None, step_num=step_num)

    def record(self, name: str, start: float, dur_s: float,
               **attrs) -> None:
        """A span measured elsewhere (``start`` on the ``perf_counter`` /
        ``monotonic`` clock): the ring only, no profiler event — it is
        already over.  Its parent is whatever span is open on this thread."""
        if not self.enabled:
            return
        stack = self._stack()
        self._record(SpanRecord(
            name=name, start_s=start - self._epoch, dur_s=dur_s,
            depth=len(stack), parent=stack[-1].name if stack else None,
            tid=threading.get_ident(), attrs=attrs or None, error=None))

    # ---------------------------------------------------------------- #
    def records(self) -> List[SpanRecord]:
        with self._lock:
            return list(self._spans)

    def snapshot(self) -> Tuple[List[SpanRecord], int]:
        """(buffered records, total ever recorded) read atomically — the
        incremental-export bookkeeping in ``Telemetry.flush`` needs both from
        the same instant or ring eviction between the two reads skews it."""
        with self._lock:
            return list(self._spans), self.total_recorded

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0
            self.total_recorded = 0

    def to_chrome_trace(self) -> Dict[str, Any]:
        """Chrome-trace JSON object (``chrome://tracing`` / Perfetto)."""
        events = []
        for rec in self.records():
            ev = {
                "name": rec.name,
                "ph": "X",
                "ts": rec.start_s * 1e6,     # µs
                "dur": rec.dur_s * 1e6,
                "pid": 0,
                "tid": rec.tid,
                "args": dict(rec.attrs or {}),
            }
            if rec.error:
                ev["args"]["error"] = rec.error
            if rec.parent:
                ev["args"]["parent"] = rec.parent
            events.append(ev)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "metadata": {"epoch_unix_s": self._epoch_unix,
                         "dropped_spans": self.dropped},
        }

    def export_chrome_trace(self, path: str) -> str:
        import json
        import os

        from ..runtime.fault.atomic import atomic_write_text

        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        atomic_write_text(path, json.dumps(self.to_chrome_trace()))
        return path


def own_times(records: Iterable[SpanRecord]) -> Dict[str, float]:
    """Seconds by span name less the time of the spans directly inside
    (same thread, one level deeper, within the interval)."""
    out: Dict[str, float] = {}
    open_: Dict[int, List[SpanRecord]] = {}
    for rec in sorted(records, key=lambda r: (r.tid, r.start_s, -r.dur_s)):
        out[rec.name] = out.get(rec.name, 0.0) + rec.dur_s
        stack = open_.setdefault(rec.tid, [])
        while stack and (stack[-1].depth >= rec.depth or stack[-1].start_s
                         + stack[-1].dur_s < rec.start_s + rec.dur_s):
            stack.pop()
        if stack and stack[-1].depth == rec.depth - 1:
            out[stack[-1].name] -= rec.dur_s
        stack.append(rec)
    return out


# --------------------------------------------------------------------- #
# The process-global tracer
# --------------------------------------------------------------------- #
_TRACER = Tracer()
_GC_SPAN = NULL_SPAN    # the collection under way (one at a time, per process)


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    """``gc.callbacks`` hook: a pause of Python's collector is one
    ``engine/host_gc`` span under whatever span the thread has open."""
    global _GC_SPAN
    if phase == "start":
        _GC_SPAN = _TRACER.span("engine/host_gc",
                                generation=info["generation"])
        _GC_SPAN.__enter__()
    else:
        span, _GC_SPAN = _GC_SPAN, NULL_SPAN
        span.set(collected=info["collected"])
        span.__exit__(None, None, None)


gc.callbacks.append(_on_gc)


def get_tracer() -> Tracer:
    """The tracer every layer boundary records on; a ``Telemetry`` hub
    adopts it rather than building its own."""
    return _TRACER


def traced(name: str):
    """Decorator: each call of the function is one ``name`` span on the
    process-global tracer (``engine/init`` around an engine's
    construction)."""
    def decorate(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with _TRACER.span(name):
                return fn(*args, **kwargs)
        return call
    return decorate
