"""Unified telemetry: structured tracing, metrics, events, memory sampling.

Enable via the ``telemetry`` config block (see ``runtime/config.py``):

    {"telemetry": {"enabled": true, "output_dir": "telemetry_out"}}

then summarize a finished run with ``bin/dstpu-telemetry <output_dir>``,
compare it against bench history with ``dstpu-telemetry <run> --compare``,
or watch it live via the ``telemetry.live`` HTTP plane
(``deepspeed_tpu/telemetry/live/``).
"""
from .events import EventLog, read_event_segments, read_jsonl
from .goodput import (GOODPUT_CATEGORIES, GoodputLedger, get_goodput_ledger,
                      goodput_residual, install_goodput_ledger,
                      record_goodput, rollup_goodput)
from .hub import (Telemetry, emit_event, get_telemetry, set_telemetry, span,
                  telemetry_enabled)
from .memory import (MEM_BUCKETS, MemoryLedger, MemorySampler,
                     get_memory_ledger, install_memory_ledger, rollup_memory)
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .trace import NULL_SPAN, SpanRecord, Tracer, get_tracer

__all__ = [
    "Counter", "EventLog", "GOODPUT_CATEGORIES", "Gauge", "GoodputLedger",
    "Histogram", "MEM_BUCKETS", "MemoryLedger", "MemorySampler",
    "MetricsRegistry", "NULL_SPAN", "SpanRecord", "Telemetry", "Tracer",
    "emit_event", "get_goodput_ledger", "get_memory_ledger", "get_telemetry",
    "get_tracer",
    "goodput_residual", "install_goodput_ledger", "install_memory_ledger",
    "read_event_segments", "read_jsonl",
    "record_goodput", "rollup_goodput", "rollup_memory", "set_telemetry",
    "span", "telemetry_enabled",
]
