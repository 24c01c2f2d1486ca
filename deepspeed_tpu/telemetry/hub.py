"""Telemetry hub: one object bundling tracer + metrics + events + memory.

The engine builds a :class:`Telemetry` from ``config.telemetry`` and installs
it process-globally (``set_telemetry``) so module-level instrumentation sites
— the comm facade, the monitor fan-out, fault counters, the checkpoint
engine — can reach it without threading a handle through every call chain.
``get_telemetry()`` returning ``None`` IS the disabled fast path: every site
guards with one attribute load + ``is None``.

Outputs (all under ``output_dir``):
  * ``events.jsonl``  — structured events, written through as they happen;
    spans and metric snapshots are appended at ``flush()``;
  * ``trace.json``    — Chrome-trace/Perfetto view of the recorded spans;
  * ``metrics.prom``  — Prometheus text-exposition snapshot.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional, Tuple

from .events import EventLog
from .memory import MemorySampler
from .metrics import MetricsRegistry
from .trace import DEFAULT_MAX_SPANS, NULL_SPAN, get_tracer

EVENTS_FILE = "events.jsonl"
TRACE_FILE = "trace.json"
PROM_FILE = "metrics.prom"


class Telemetry:
    def __init__(self, output_dir: str = "telemetry", jsonl: bool = True,
                 chrome_trace: bool = True, prometheus: bool = True,
                 fence: bool = False, memory_interval: int = 1,
                 max_spans: int = 100_000, histogram_max_samples: int = 4096,
                 jax_annotations: bool = True, events_max_mb: float = 0.0,
                 events_keep: int = 3):
        self.output_dir = os.path.abspath(output_dir)
        self.chrome_trace = bool(chrome_trace)
        self.prometheus = bool(prometheus)
        #: fence spans with block_until_ready on the value handed to span(sync=)
        self.fence = bool(fence)
        # the process-global tracer, which the serve and train paths record
        # on with or without a hub: the hub sizes its ring, starts it empty
        # (what came before is not this run's) and exports it
        self.tracer = get_tracer()
        self.tracer.configure(max_spans=max_spans,
                              jax_annotations=jax_annotations,
                              drop_recorded=True)
        self.metrics = MetricsRegistry(
            histogram_max_samples=histogram_max_samples)
        self.events = EventLog(
            path=os.path.join(self.output_dir, EVENTS_FILE) if jsonl else None,
            max_bytes=int(float(events_max_mb) * 1024 * 1024),
            keep=events_keep)
        self.memory = MemorySampler(self.metrics, self.events,
                                    interval=memory_interval)
        self._flush_lock = threading.Lock()
        # spans recorded before this hub existed are not its run's
        self._spans_flushed = self.tracer.total_recorded
        self._closed = False
        # Run delimiter: events.jsonl is append-mode, so re-using an
        # output_dir accumulates runs — this marker lets the summarizer
        # isolate the latest run (matching trace.json, which is overwritten).
        self.events.emit("run_start", pid=os.getpid(),
                         output_dir=self.output_dir)

    @classmethod
    def from_config(cls, tcfg) -> "Telemetry":
        """Build from a ``TelemetryConfig`` block (runtime/config.py)."""
        return cls(
            output_dir=tcfg.output_dir,
            jsonl=tcfg.jsonl,
            chrome_trace=tcfg.chrome_trace,
            prometheus=tcfg.prometheus,
            fence=tcfg.fence,
            memory_interval=tcfg.memory_interval,
            max_spans=tcfg.max_spans,
            histogram_max_samples=tcfg.histogram_max_samples,
            jax_annotations=tcfg.jax_annotations,
            events_max_mb=getattr(tcfg, "events_max_mb", 0.0),
            events_keep=getattr(tcfg, "events_keep", 3),
        )

    # ---------------------------------------------------------------- #
    # Convenience instrumentation entry points
    # ---------------------------------------------------------------- #
    def span(self, name: str, sync: Any = None, **attrs):
        return self.tracer.span(name, sync=sync if self.fence else None,
                                **attrs)

    def event(self, kind: str, **fields) -> None:
        self.events.emit(kind, **fields)

    def record_comm_op(self, op_name: str, size_bytes: int,
                       duration_s: Optional[float], n_ranks: int,
                       algbw_gbps: float, busbw_gbps: float) -> None:
        """Per-collective aggregation: message sizes, latency, and bandwidth
        estimates, labelled by op (upgraded ``comms_logging`` path).

        ``duration_s=None`` marks a trace-time (in-jit) record: message size
        and call count are real, but there is no transfer to time — those
        land in ``comm/traced_calls`` and stay out of the latency/bandwidth
        histograms."""
        m = self.metrics
        m.counter("comm/calls").inc(op=op_name)
        m.histogram("comm/bytes").observe(size_bytes, op=op_name)
        if duration_s is None:
            m.counter("comm/traced_calls").inc(op=op_name)
        else:
            m.histogram("comm/latency_s").observe(duration_s, op=op_name)
            if algbw_gbps > 0:
                m.histogram("comm/algbw_gbps").observe(algbw_gbps, op=op_name)
            if busbw_gbps > 0:
                m.histogram("comm/busbw_gbps").observe(busbw_gbps, op=op_name)
        m.gauge("comm/ranks").set(n_ranks, op=op_name)

    def record_monitor_events(self, event_list) -> None:
        """Mirror monitor scalar events (label, value, step) into telemetry
        so TB/W&B/CSV writers and telemetry can never drift apart: gauges
        hold last/min/max per label, and one compact ``scalars`` JSONL event
        per batch keeps the full per-step history recoverable even with
        every writer disabled."""
        values = {}
        last_step = None
        for label, value, step in event_list:
            try:
                value = float(value)
                # a label colliding with a non-gauge metric name raises
                # TypeError — skip that scalar, never break the fan-out
                self.metrics.gauge(str(label)).set(value)
            except (TypeError, ValueError):
                continue
            values[str(label)] = value
            last_step = step
        if values:
            try:
                self.metrics.gauge("monitor/last_step").set(float(last_step))
            except (TypeError, ValueError):
                pass
            self.events.emit("scalars", step=last_step, values=values)

    # ---------------------------------------------------------------- #
    def flush(self) -> Dict[str, str]:
        """Write every export: new spans + a metric snapshot into the JSONL,
        the Chrome trace, and the Prometheus snapshot.  Idempotent and safe
        to call mid-run.  Returns {artifact: path}."""
        out: Dict[str, str] = {}
        with self._flush_lock:
            # _spans_flushed counts against the tracer's MONOTONIC total, not
            # the ring buffer length — ring eviction must not re-export old
            # spans or silently skip new ones.
            records, total = self.tracer.snapshot()
            unseen = max(total - self._spans_flushed, 0)
            missed = max(unseen - len(records), 0)
            if missed:   # evicted before this flush could export them
                self.events.emit("spans_dropped", count=missed,
                                 ring_capacity=self.tracer.max_spans)
            for rec in records[len(records) - min(unseen, len(records)):]:
                self.events.emit("span", **rec.to_dict())
            self._spans_flushed = total
            for row in self.metrics.snapshot():
                self.events.emit("metric", **row)
            self.events.flush()
            if self.events.path:
                out["events"] = self.events.path
            if self.chrome_trace:
                out["trace"] = self.tracer.export_chrome_trace(
                    os.path.join(self.output_dir, TRACE_FILE))
            if self.prometheus:
                from ..runtime.fault.atomic import atomic_write_text

                os.makedirs(self.output_dir, exist_ok=True)
                prom = os.path.join(self.output_dir, PROM_FILE)
                atomic_write_text(prom, self.metrics.prometheus_text())
                out["prometheus"] = prom
        return out

    def close(self) -> Dict[str, str]:
        if self._closed:
            return {}
        out = self.flush()
        self.events.close()
        self._closed = True
        # the tracer outlives the hub: back to what nobody configured
        self.tracer.configure(max_spans=DEFAULT_MAX_SPANS,
                              jax_annotations=True)
        return out


# --------------------------------------------------------------------- #
# Process-global instance
# --------------------------------------------------------------------- #
_GLOBAL: Optional[Telemetry] = None
_GLOBAL_LOCK = threading.Lock()


def set_telemetry(tel: Optional[Telemetry]) -> Optional[Telemetry]:
    """Install (or clear, with None) the process-global telemetry hub."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        previous, _GLOBAL = _GLOBAL, tel
    return previous


def get_telemetry() -> Optional[Telemetry]:
    return _GLOBAL


def telemetry_enabled() -> bool:
    return _GLOBAL is not None


def span(name: str, sync: Any = None, **attrs):
    """Module-level span against the global hub; NULL_SPAN when disabled."""
    tel = _GLOBAL
    if tel is None:
        return NULL_SPAN
    return tel.span(name, sync=sync, **attrs)


def emit_event(kind: str, **fields) -> None:
    """Fire-and-forget structured event against the global hub."""
    tel = _GLOBAL
    if tel is not None:
        tel.event(kind, **fields)
