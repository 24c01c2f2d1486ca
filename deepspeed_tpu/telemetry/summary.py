"""Run-summary: turn a telemetry output directory into a human report.

Backs the ``bin/dstpu-telemetry`` CLI.  Reads the ``events.jsonl`` written by
a run (spans, metric snapshots, structured events) — with ``trace.json`` as a
span fallback for logs that predate the JSONL span mirror — and prints:

  * a step-phase time breakdown (count / total / mean / p50 / p95 per span);
  * a per-collective communication table (calls, bytes, latency, alg/bus
    bandwidth estimates);
  * performance attribution: the profiler's per-module cost tree
    (``profile_report`` events), the roofline/MFU line (``roofline/*``
    gauges), and a device-time breakdown parsed from the captured xprof
    trace (``xprof_trace`` events / ``--xprof``);
  * memory high-water marks (live jax.Arrays + device allocator stats);
  * an incident digest (faults, watchdog timeouts, stragglers, checkpoint
    lifecycle).

Everything is computed into a plain dict first (``summarize_run``) so tests
and downstream tooling can consume the numbers without scraping text.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .events import read_event_segments
from .metrics import _percentile

EVENT_KINDS_INCIDENT = ("fault", "watchdog_timeout", "elastic_worker_failure",
                        "elastic_restart", "elastic_reshape", "straggler",
                        "anomaly", "anomaly_checkpoint_failed",
                        "checkpoint_reshard_fallback",
                        "serving_nan_isolated", "serving_window_hang",
                        "fleet_replica_lost", "fleet_mid_stream_error",
                        "fleet_prefill_fallback", "fleet_tenant_shed",
                        "fleet_scale_up", "fleet_scale_down", "fleet_heal",
                        "fleet_controller_crash", "mem_unattributed")

#: request-tracing counters (telemetry/tracing/store.py mirrors these)
TRACE_COUNTERS = ("trace/started", "trace/finished", "trace/kept",
                  "trace/dropped", "trace/upgraded", "trace/flagged")

#: goodput-ledger category order for the rendered table (telemetry/goodput.py
#: is canonical; imported lazily in goodput_summary so a partial install of
#: the telemetry package still summarizes everything else)
GOODPUT_SCALARS = ("wall_s", "goodput_fraction", "overcommit_s")

#: roofline table columns, shared between the section renderer and --help
ROOFLINE_COLUMNS = (
    ("achieved_tflops", "achieved TFLOP/s per chip (step flops / step time)"),
    ("peak_tflops", "device bf16 peak TFLOP/s (profiling/roofline.py table)"),
    ("mfu", "model flops utilization = achieved / peak"),
    ("hbm_gbps", "achieved HBM bandwidth, GB/s per chip"),
    ("hbm_utilization", "achieved / peak HBM bandwidth"),
    ("arithmetic_intensity", "flops per byte accessed; above the ridge "
                             "point the step is compute-bound"),
)


def _fmt_bytes(n: float) -> str:
    n = float(n)
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024.0 or unit == "TB":
            return f"{n:.2f} {unit}" if unit != "B" else f"{n:.0f} B"
        n /= 1024.0
    return f"{n:.2f} TB"


def _fmt_ms(seconds: float) -> str:
    return f"{seconds * 1e3:.2f}"


# --------------------------------------------------------------------- #
# Loaders
# --------------------------------------------------------------------- #
def load_run(events_path: Optional[str],
             trace_path: Optional[str] = None) -> Dict[str, Any]:
    """Parse the raw artifacts into {spans, metrics, events}.

    ``metrics``: metric snapshots are cumulative, so only the LAST snapshot
    row per (name, labelset) counts.
    """
    spans: List[Dict[str, Any]] = []
    metrics: Dict[tuple, Dict[str, Any]] = {}
    events: List[Dict[str, Any]] = []
    runs = 0
    if events_path:
        # rotation-aware: a size-rotated log's oldest events live in
        # events.jsonl.N segments — walk them oldest-first so the stream
        # (and the latest run_start marker) reads exactly as written
        for rec in read_event_segments(events_path):
            kind = rec.get("kind")
            if kind == "run_start":
                # append-mode log: summarize only the LATEST run, consistent
                # with trace.json (which the last run overwrote)
                runs += 1
                spans.clear()
                metrics.clear()
                events.clear()
                continue
            if kind == "span":
                spans.append(rec)
            elif kind == "metric":
                labels = rec.get("labels") or {}
                key = (rec.get("name"),
                       tuple(sorted((str(k), str(v))
                                    for k, v in labels.items())))
                metrics[key] = rec
            else:
                events.append(rec)
    if not spans and trace_path and os.path.exists(trace_path):
        try:
            with open(trace_path) as f:
                trace = json.load(f)
            for ev in trace.get("traceEvents", []):
                if ev.get("ph") != "X":
                    continue
                spans.append({
                    "name": ev.get("name", "?"),
                    "start_s": float(ev.get("ts", 0.0)) / 1e6,
                    "dur_s": float(ev.get("dur", 0.0)) / 1e6,
                    "depth": 0,
                    "parent": (ev.get("args") or {}).get("parent"),
                })
        except (OSError, json.JSONDecodeError, ValueError):
            pass
    return {"spans": spans, "metrics": list(metrics.values()),
            "events": events, "runs_in_log": max(runs, 1)}


# --------------------------------------------------------------------- #
# Sections
# --------------------------------------------------------------------- #
def step_breakdown(spans: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    groups: Dict[str, List[float]] = {}
    errors: Dict[str, int] = {}
    for s in spans:
        name = s.get("name", "?")
        groups.setdefault(name, []).append(float(s.get("dur_s", 0.0)))
        if s.get("error"):
            errors[name] = errors.get(name, 0) + 1
    rows = []
    for name, durs in groups.items():
        durs_sorted = sorted(durs)
        total = sum(durs)
        rows.append({
            "phase": name,
            "count": len(durs),
            "total_s": total,
            "mean_s": total / len(durs),
            "p50_s": _percentile(durs_sorted, 50),
            "p95_s": _percentile(durs_sorted, 95),
            "max_s": durs_sorted[-1],
            "errors": errors.get(name, 0),
        })
    rows.sort(key=lambda r: r["total_s"], reverse=True)
    return rows


def _metric_map(metrics: Sequence[Dict[str, Any]],
                name: str) -> Dict[tuple, Dict[str, Any]]:
    out = {}
    for m in metrics:
        if m.get("name") == name:
            labels = m.get("labels") or {}
            out[tuple(sorted(labels.items()))] = m
    return out


def comm_table(metrics: Sequence[Dict[str, Any]],
               device_kind: Optional[str] = None) -> List[Dict[str, Any]]:
    calls = _metric_map(metrics, "comm/calls")
    sizes = _metric_map(metrics, "comm/bytes")
    lats = _metric_map(metrics, "comm/latency_s")
    algbw = _metric_map(metrics, "comm/algbw_gbps")
    busbw = _metric_map(metrics, "comm/busbw_gbps")
    ranks = _metric_map(metrics, "comm/ranks")
    # per-collective bandwidth roofline: achieved bus bandwidth vs the
    # device kind's aggregate interconnect peak (profiling/roofline.py)
    ici_peak_gbps = None
    if device_kind:
        try:
            from ..profiling.roofline import interconnect_peak

            peak = interconnect_peak(device_kind)
            ici_peak_gbps = peak / 1e9 if peak > 0 else None
        except Exception:  # noqa: BLE001 — table degrades, never dies
            ici_peak_gbps = None
    ops = sorted({k for k in list(calls) + list(sizes)})
    rows = []
    for key in ops:
        op = dict(key).get("op", "?")
        size = sizes.get(key, {})
        lat = lats.get(key, {})
        bus = busbw.get(key, {}).get("mean")
        pct_peak = None
        if bus and ici_peak_gbps:
            pct_peak = 100.0 * float(bus) / ici_peak_gbps
        rows.append({
            "op": op,
            "calls": int(calls.get(key, {}).get("value", 0)),
            "bytes_total": size.get("sum", 0),
            "bytes_mean": size.get("mean", 0),
            "bytes_max": size.get("max", 0),
            "latency_total_s": lat.get("sum", 0),
            "latency_mean_s": lat.get("mean", 0),
            "algbw_mean_gbps": algbw.get(key, {}).get("mean"),
            "busbw_mean_gbps": bus,
            "busbw_pct_peak": pct_peak,
            "ici_peak_gbps": ici_peak_gbps,
            "ranks": ranks.get(key, {}).get("value"),
        })
    rows.sort(key=lambda r: r["bytes_total"] or 0, reverse=True)
    return rows


#: collective algorithm/wire selection gauges (overlap manager,
#: runtime/comm/hierarchical.py) — exact names, distinct from the
#: labelled per-op comm facade series (comm/calls, comm/bytes, …)
COMM_SELECTION_GAUGES = ("comm/algo_2hop", "comm/wire_bits",
                         "comm/predicted_exchange_ms",
                         "comm/predicted_wire_bytes")


def overlap_summary(metrics: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """The ``overlap/*`` gauges (comm/compute overlap subsystem): exposed
    comm fraction, deferred-reduction activity, bucket shape — plus the
    collective algorithm/wire selection (``comm/*`` gauges) under
    ``comm_selection``."""
    out: Dict[str, Any] = {}
    comm: Dict[str, Any] = {}
    for m in metrics:
        name = str(m.get("name", ""))
        if name.startswith("overlap/"):
            key = name.split("/", 1)[1]
            out[key] = m.get("value", m.get("count"))
        elif name in COMM_SELECTION_GAUGES:
            comm[name.split("/", 1)[1]] = m.get("value")
    if comm:
        out["comm_selection"] = comm
    return out


#: request-lifecycle counters surfaced in the serving section / incident
#: digest (LifecycleScheduler mirrors these into the registry)
SERVING_LIFECYCLE_COUNTERS = (
    "serving/requests", "serving/completed", "serving/shed",
    "serving/preempted", "serving/cancelled", "serving/deadline_expired",
    "serving/ttft_timeout", "serving/nan_isolated", "serving/window_hang",
    "serving/rejected", "serving/drain_expired",
    "serving/spec_windows", "serving/spec_drafted", "serving/spec_accepted",
    "serving/prefix_hits", "serving/prefix_hit_tokens",
    "serving/kv_import", "serving/kv_import_tokens",
    "serving/prefill_exported")

#: serving latency histograms: TTFT (arrival → first generated token) and
#: TPOT (decode-phase seconds per output token)
SERVING_LATENCY_HISTOGRAMS = ("serving/ttft_s", "serving/tpot_s")


def serving_summary(metrics: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """The ``serving/*`` series: decode-HBM-roofline gauges (published per
    drained decode window by ``InferenceEngineV2._account_decode_window``)
    with the per-kernel %-of-peak breakdown, plus the request-lifecycle
    layer — shed/preempt/cancel/expiry counters and TTFT/TPOT percentiles
    (published by ``LifecycleScheduler``)."""
    out: Dict[str, Any] = {}
    kernels: Dict[str, Dict[str, Any]] = {}
    lifecycle: Dict[str, float] = {}
    latency: Dict[str, Dict[str, Any]] = {}
    for m in metrics:
        name = str(m.get("name", ""))
        if not name.startswith("serving/"):
            continue
        key = name.split("/", 1)[1]
        labels = m.get("labels") or {}
        if labels.get("device"):
            out["device_kind"] = labels["device"]
        if name in SERVING_LIFECYCLE_COUNTERS:
            lifecycle[key] = m.get("value")
        elif name in SERVING_LATENCY_HISTOGRAMS:
            if m.get("count"):
                latency[key] = {k: m.get(k) for k in
                                ("count", "mean", "p50", "p90", "p95",
                                 "p99", "max")}
        elif key.startswith("kernel_"):
            kname = labels.get("kernel", "?")
            kernels.setdefault(kname, {})[key[len("kernel_"):]] = \
                m.get("value")
        else:
            out[key] = m.get("value")
    if kernels:
        out["kernels"] = kernels
    if lifecycle:
        out["lifecycle"] = lifecycle
    if latency:
        out["latency"] = latency
    return out


def kernels_summary(metrics: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """The ``kernels/*`` series: per-kernel %-of-peak rooflines
    (``profiling/roofline.py publish_kernel_gauges`` — published from the
    engine per decode window like the ``serving/*`` gauges, and by the
    ``kernel_sweep`` bench).  One row per kernel label."""
    out: Dict[str, Dict[str, Any]] = {}
    for m in metrics:
        name = str(m.get("name", ""))
        if not name.startswith("kernels/"):
            continue
        key = name.split("/", 1)[1]
        labels = m.get("labels") or {}
        kname = labels.get("kernel")
        if not kname:
            continue
        row = out.setdefault(kname, {})
        row[key] = m.get("value")
        if labels.get("device"):
            row["device_kind"] = labels["device"]
    # "bound" is a string the numeric gauges can't carry — reconstruct it
    # from the published arithmetic intensity vs the device's ridge
    for row in out.values():
        ai = row.get("arithmetic_intensity")
        if isinstance(ai, (int, float)) and row.get("device_kind"):
            from ..profiling.roofline import spec_for_kind

            ridge = spec_for_kind(row["device_kind"]).ridge_intensity
            row["bound"] = "compute" if ai >= ridge else "memory"
    return out


#: fleet-tier counters (dstpu-router) surfaced in the fleet section
FLEET_COUNTERS = (
    "fleet/routed", "fleet/rerouted", "fleet/shed", "fleet/replica_shed",
    "fleet/replica_lost", "fleet/mid_stream_error",
    "fleet/prefill_disagg", "fleet/prefill_fallback",
    "fleet/kv_ship_bytes",
    # per-tenant QoS + the autoscaling controller (dstpu-fleet)
    "fleet/tenant_shed",
    "fleet/controller_scale_ups", "fleet/controller_scale_downs",
    "fleet/controller_heals", "fleet/controller_crashes",
    "fleet/controller_scrape_failures", "fleet/controller_spawn_failures")


def fleet_summary(metrics: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """The ``fleet/*`` series published by ``dstpu-router``: fleet size /
    routability, routed/rerouted/shed/replica-lost counters, the
    aggregated prefix-cache hit rate, per-replica queue depth + KV
    pressure (labelled gauges), and disaggregated-prefill KV-ship
    volume/latency."""
    out: Dict[str, Any] = {}
    counters: Dict[str, float] = {}
    replicas: Dict[str, Dict[str, Any]] = {}
    tenants: Dict[str, Dict[str, Any]] = {}
    for m in metrics:
        name = str(m.get("name", ""))
        if not name.startswith("fleet/"):
            continue
        key = name.split("/", 1)[1]
        labels = m.get("labels") or {}
        if labels.get("tenant"):
            tenants.setdefault(labels["tenant"], {})[
                key.replace("tenant_", "")] = m.get("value")
        elif name in FLEET_COUNTERS:
            counters[key] = m.get("value")
        elif labels.get("replica"):
            replicas.setdefault(labels["replica"], {})[
                key.replace("replica_", "")] = m.get("value")
        else:
            out[key] = m.get("value")
    if counters:
        out["counters"] = counters
    if replicas:
        out["replicas"] = replicas
    if tenants:
        out["tenants"] = tenants
    return out


def tracing_summary(metrics: Sequence[Dict[str, Any]],
                    events: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """The request-tracing plane (telemetry/tracing): per-segment
    TTFT/TPOT decomposition percentiles from the
    ``serving/trace_segment_s`` histogram (one labelset per span kind),
    the tail-sampling counters, and the exemplar links from the TTFT/TPOT
    histogram tails to the trace ids that populated them
    (``trace_exemplar`` events; the ids resolve via ``dstpu-trace
    --request`` or ``GET /traces?request=``)."""
    out: Dict[str, Any] = {}
    segments: Dict[str, Dict[str, Any]] = {}
    counters: Dict[str, float] = {}
    for m in metrics:
        name = str(m.get("name", ""))
        if name == "serving/trace_segment_s" and m.get("count"):
            seg = (m.get("labels") or {}).get("segment", "?")
            segments[seg] = {k: m.get(k) for k in
                            ("count", "sum", "mean", "p50", "p95")}
        elif name in TRACE_COUNTERS:
            counters[name.split("/", 1)[1]] = m.get("value")
    # newest exemplar offer per trace id wins; keep the largest few
    exemplars: Dict[str, Dict[str, float]] = {}
    for e in events:
        if e.get("kind") != "trace_exemplar":
            continue
        metric, trace = str(e.get("metric")), str(e.get("trace"))
        try:
            exemplars.setdefault(metric, {})[trace] = float(e.get("value"))
        except (TypeError, ValueError):
            continue
    if segments:
        out["segments"] = segments
    if counters:
        out["counters"] = counters
    if exemplars:
        out["exemplars"] = {
            m: [{"trace": t, "value": v} for t, v in
                sorted(vals.items(), key=lambda kv: kv[1],
                       reverse=True)[:4]]
            for m, vals in exemplars.items()}
    return out


def goodput_summary(metrics: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """The goodput ledger's ``goodput/*`` gauges (telemetry/goodput.py):
    ledger wall, per-category seconds + fractions-of-wall, the goodput
    scalar (compute / wall), the conservation detector (``overcommit_s``
    — attributed beyond wall means a double-counting seam) and the
    per-tenant shed attribution."""
    from .goodput import GOODPUT_CATEGORIES

    out: Dict[str, Any] = {}
    cats: Dict[str, float] = {}
    tenants: Dict[str, float] = {}
    for m in metrics:
        name = str(m.get("name", ""))
        if not name.startswith("goodput/"):
            continue
        key = name.split("/", 1)[1]
        labels = m.get("labels") or {}
        if key == "tenant_shed_s" and labels.get("tenant"):
            tenants[labels["tenant"]] = m.get("value")
        elif key.endswith("_s") and key[:-2] in GOODPUT_CATEGORIES:
            cats[key[:-2]] = m.get("value")
        elif key in GOODPUT_SCALARS:
            out[key] = m.get("value")
    if cats:
        out["categories"] = cats
        wall = float(out.get("wall_s") or 0.0)
        if wall > 0:
            out["fractions"] = {c: round((v or 0.0) / wall, 6)
                                for c, v in cats.items()}
    if tenants:
        out["tenant_shed_s"] = tenants
    return out


def memory_summary(metrics: Sequence[Dict[str, Any]],
                   events: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for name in ("memory/live_array_bytes", "memory/live_array_count",
                 "memory/device_bytes_in_use",
                 "memory/device_peak_bytes_in_use"):
        for m in metrics:
            if m.get("name") == name and m.get("count"):
                out[name.split("/", 1)[1] + "_max"] = m.get("max")
    # which step hit the live-bytes peak (from per-step memory events)
    peak, peak_step = -1.0, None
    for e in events:
        if e.get("kind") != "memory":
            continue
        v = e.get("live_array_bytes")
        if v is not None and float(v) > peak:
            peak, peak_step = float(v), e.get("step")
    if peak_step is not None:
        out["live_array_bytes_peak_step"] = peak_step
    # HBM occupancy ledger (``mem/*`` gauges, telemetry/memory.py): bucket
    # bytes, the conservation detector and the KV heat cold-set view
    from .memory import MEM_BUCKETS

    buckets: Dict[str, Any] = {}
    kv: Dict[str, Any] = {}
    cold: Dict[str, Any] = {}
    tenants: Dict[str, Any] = {}
    for m in metrics:
        name = str(m.get("name", ""))
        if not name.startswith("mem/"):
            continue
        key = name.split("/", 1)[1]
        labels = m.get("labels") or {}
        if key.endswith("_bytes") and key[:-6] in MEM_BUCKETS:
            buckets[key[:-6]] = m.get("value")
        elif key == "kv_cold_pages" and labels.get("age_windows"):
            cold[labels["age_windows"]] = m.get("value")
        elif key == "tenant_kv_bytes" and labels.get("tenant"):
            tenants[labels["tenant"]] = m.get("value")
        elif key in ("live_bytes", "unattributed_bytes",
                     "unattributed_frac", "conserved"):
            out[key] = m.get("value")
        elif key in ("kv_live_pages", "kv_peak_pages", "kv_used_bytes",
                     "prefix_shared_bytes_saved"):
            kv[key] = m.get("value")
    if buckets:
        out["buckets"] = buckets
    if cold:
        kv["cold_pages"] = cold
    if tenants:
        kv["tenants"] = tenants
    if kv:
        out["kv"] = kv
    return out


def profile_summary(events: Sequence[Dict[str, Any]],
                    metrics: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Performance attribution: the last ``profile_report`` event (module
    rows + roofline snapshot at profile time) plus the latest ``roofline/*``
    gauges (steady-state MFU, updated every roofline_interval steps)."""
    out: Dict[str, Any] = {}
    for e in events:
        if e.get("kind") == "profile_report":
            out["report"] = {k: v for k, v in e.items() if k != "kind"}
    gauges: Dict[str, Any] = {}
    for m in metrics:
        name = str(m.get("name", ""))
        if name.startswith("roofline/"):
            gauges[name.split("/", 1)[1]] = m.get("value")
            labels = m.get("labels") or {}
            if labels.get("device"):
                gauges["device_kind"] = labels["device"]
    if gauges:
        out["roofline_gauges"] = gauges
    return out


def xprof_summary(events: Sequence[Dict[str, Any]],
                  explicit_dir: Optional[str] = None) -> Optional[Dict[str, Any]]:
    """Device-time attribution from the captured xprof trace: ``--xprof``
    wins, else the engine's ``xprof_trace`` breadcrumb event."""
    candidates = [explicit_dir] if explicit_dir else []
    for e in events:
        if e.get("kind") == "xprof_trace" and e.get("dir"):
            candidates.append(str(e["dir"]))
    for path in candidates:
        if not path or not os.path.exists(path):
            continue
        try:
            from ..profiling.xprof_parse import attribute_device_time

            report = attribute_device_time(path)
        except Exception:  # noqa: BLE001 — a bad trace must not kill the CLI
            continue
        if report["files"]:
            report["source"] = path
            return report
    return None


def incident_summary(events: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    counts: Dict[str, int] = {}
    for e in events:
        counts[e.get("kind", "?")] = counts.get(e.get("kind", "?"), 0) + 1
    incidents = [e for e in events if e.get("kind") in EVENT_KINDS_INCIDENT]
    checkpoints = [e for e in events
                   if str(e.get("kind", "")).startswith("checkpoint")]
    return {"event_counts": counts,
            "incidents": incidents[-20:],
            "checkpoints": checkpoints[-20:]}


def summarize_run(events_path: Optional[str],
                  trace_path: Optional[str] = None,
                  xprof_dir: Optional[str] = None) -> Dict[str, Any]:
    run = load_run(events_path, trace_path)
    profile = profile_summary(run["events"], run["metrics"])
    # device kind recorded by the roofline gauges keys the per-collective
    # bandwidth roofline in the comm table
    device_kind = (profile.get("roofline_gauges") or {}).get("device_kind")
    return {
        "sources": {"events": events_path, "trace": trace_path,
                    "xprof": xprof_dir},
        "runs_in_log": run["runs_in_log"],
        "n_spans": len(run["spans"]),
        "step_breakdown": step_breakdown(run["spans"]),
        "comm": comm_table(run["metrics"], device_kind=device_kind),
        "overlap": overlap_summary(run["metrics"]),
        "kernels": kernels_summary(run["metrics"]),
        "serving": serving_summary(run["metrics"]),
        "fleet": fleet_summary(run["metrics"]),
        "goodput": goodput_summary(run["metrics"]),
        "tracing": tracing_summary(run["metrics"], run["events"]),
        "profile": profile,
        "xprof": xprof_summary(run["events"], explicit_dir=xprof_dir),
        "memory": memory_summary(run["metrics"], run["events"]),
        "incidents": incident_summary(run["events"]),
    }


# --------------------------------------------------------------------- #
# Rendering
# --------------------------------------------------------------------- #
def format_summary(s: Dict[str, Any]) -> str:
    lines: List[str] = []
    add = lines.append
    add("=== dstpu telemetry run summary ===")
    add(f"sources: events={s['sources']['events']} "
        f"trace={s['sources']['trace']}")
    if s.get("runs_in_log", 1) > 1:
        add(f"note: log contains {s['runs_in_log']} runs — summarizing the "
            f"latest only")
    add("")

    add("--- step-phase breakdown ---")
    rows = s["step_breakdown"]
    if rows:
        add(f"{'phase':<32}{'count':>7}{'total(ms)':>12}{'mean(ms)':>11}"
            f"{'p50(ms)':>11}{'p95(ms)':>11}{'max(ms)':>11}{'err':>5}")
        for r in rows:
            add(f"{r['phase']:<32}{r['count']:>7}{_fmt_ms(r['total_s']):>12}"
                f"{_fmt_ms(r['mean_s']):>11}{_fmt_ms(r['p50_s']):>11}"
                f"{_fmt_ms(r['p95_s']):>11}{_fmt_ms(r['max_s']):>11}"
                f"{r['errors']:>5}")
    else:
        add("(no spans recorded)")
    add("")

    add("--- communication ---")
    rows = s["comm"]
    if rows:
        peak = next((r["ici_peak_gbps"] for r in rows
                     if r.get("ici_peak_gbps")), None)
        if peak:
            add(f"interconnect peak: {peak:.0f} GB/s/chip (aggregate ICI; "
                f"%peak = achieved busbw vs this)")
        add(f"{'op':<22}{'calls':>7}{'total':>12}{'mean msg':>12}"
            f"{'lat(ms)':>10}{'algbw(GB/s)':>13}{'busbw(GB/s)':>13}"
            f"{'%peak':>8}")
        for r in rows:
            alg = f"{r['algbw_mean_gbps']:.2f}" if r.get("algbw_mean_gbps") \
                else "-"
            bus = f"{r['busbw_mean_gbps']:.2f}" if r.get("busbw_mean_gbps") \
                else "-"
            pct = f"{r['busbw_pct_peak']:.1f}%" \
                if r.get("busbw_pct_peak") is not None else "-"
            add(f"{r['op']:<22}{r['calls']:>7}"
                f"{_fmt_bytes(r['bytes_total'] or 0):>12}"
                f"{_fmt_bytes(r['bytes_mean'] or 0):>12}"
                f"{_fmt_ms(r['latency_mean_s'] or 0):>10}{alg:>13}{bus:>13}"
                f"{pct:>8}")
    else:
        add("(no collectives recorded)")
    ov = s.get("overlap") or {}
    if ov:
        frac = ov.get("exposed_comm_fraction")
        exposed = f"{float(frac) * 100:.1f}% of device time" \
            if frac is not None else "n/a (no xprof capture)"
        bits = [f"exposed comm: {exposed}"]
        if ov.get("deferred") is not None:
            steps = int(ov.get("deferred_steps") or 0)
            bits.append(f"deferred reduction "
                        f"{'on' if ov['deferred'] else 'off'}"
                        f" ({steps} steps)")
        if ov.get("bucket_count"):
            bits.append(f"buckets {int(ov['bucket_count'])}"
                        f" @ {_fmt_bytes(ov.get('bucket_bytes') or 0)} target")
        if ov.get("prefetch_reuse"):
            bits.append(f"prefetch reuse {int(ov['prefetch_reuse'])}")
        cs = ov.get("comm_selection") or {}
        if cs:
            wb = int(cs.get("wire_bits") or 0)
            bits.append(
                f"collectives "
                f"{'2-hop' if cs.get('algo_2hop') else 'flat'}/"
                f"{f'int{wb}' if wb else 'fp'}")
        add("overlap: " + " · ".join(bits))
    add("")

    add("--- performance attribution ---")
    prof = s.get("profile") or {}
    gauges = prof.get("roofline_gauges")
    report = prof.get("report")
    roof = (report or {}).get("roofline") or gauges
    if roof:
        dev = roof.get("device_kind", "?")
        mfu = roof.get("mfu")
        line = f"roofline [{dev}]: "
        if roof.get("achieved_tflops") is not None:
            line += f"{roof['achieved_tflops']:.1f}"
            if roof.get("peak_tflops"):
                line += f"/{roof['peak_tflops']:.0f}"
            line += " TFLOP/s/chip"
        if mfu is not None:
            line += f" (MFU {mfu * 100:.1f}%)"
        if roof.get("hbm_gbps") is not None:
            line += f", HBM {roof['hbm_gbps']:.0f} GB/s"
            if roof.get("hbm_utilization") is not None:
                line += f" ({roof['hbm_utilization'] * 100:.1f}%)"
        add(line + "  [source: flops profiler]")
    if report:
        add(f"profile @ step {report.get('step')}: "
            f"flops/step={report.get('flops', 0):.3e} "
            f"params={report.get('params', 0):.3e} "
            f"latency={report.get('latency_s', 0):.3f}s")
        rows = report.get("module_rows") or []
        if rows:
            add(f"{'module':<34}{'params':>12}{'flops':>12}{'AI':>8}"
                f"{'%flops':>8}")
            for r in rows:
                label = "  " * int(r.get("depth", 0)) + str(r.get("module"))
                add(f"{label:<34}{r.get('params', 0):>12.3g}"
                    f"{r.get('flops', 0):>12.3g}"
                    f"{r.get('arithmetic_intensity', 0):>8.1f}"
                    f"{r.get('pct_flops', 0):>7.1f}%")
    if not roof and not report:
        add("(no profile_report events — enable config.profiling)")
    xp = s.get("xprof")
    if xp:
        add("")
        add(f"--- device-time breakdown (xprof: {xp.get('source')}) ---")
        from ..profiling.xprof_parse import format_device_table

        for line in format_device_table(xp):
            add(line)
    add("")

    kr = s.get("kernels") or {}
    if kr:
        add("--- kernels (%-of-peak rooflines) ---")
        from ..profiling.roofline import format_kernel_table

        dev = next((row.get("device_kind") for row in kr.values()
                    if row.get("device_kind")), "?")
        add(f"device: {dev}")
        rows = [dict(row, kernel=kname) for kname, row in sorted(
            kr.items(), key=lambda kv: kv[1].get("pct_peak_flops") or 0,
            reverse=True)]
        for line in format_kernel_table(rows):
            add(line)
        add("")

    srv = s.get("serving") or {}
    if srv:
        add("--- serving (decode HBM roofline) ---")
        dev = srv.get("device_kind", "?")
        line = f"decode [{dev}]: "
        if srv.get("decode_tok_per_s") is not None:
            line += f"{srv['decode_tok_per_s']:.1f} tok/s"
        if srv.get("decode_hbm_gbps") is not None:
            line += f", HBM {srv['decode_hbm_gbps']:.1f}"
            if srv.get("peak_hbm_gbps"):
                line += f"/{srv['peak_hbm_gbps']:.0f}"
            line += " GB/s"
            if srv.get("decode_hbm_pct_peak") is not None:
                line += f" ({srv['decode_hbm_pct_peak']:.1f}% of peak)"
        add(line)
        kernels = srv.get("kernels") or {}
        if kernels:
            add(f"{'kernel':<22}{'HBM(GB/s)':>12}{'%peak':>8}")
            for kname in sorted(kernels,
                                key=lambda k: kernels[k].get("hbm_gbps")
                                or 0, reverse=True):
                row = kernels[kname]
                gbps = f"{row['hbm_gbps']:.1f}" \
                    if row.get("hbm_gbps") is not None else "-"
                pct = f"{row['hbm_pct_peak']:.1f}%" \
                    if row.get("hbm_pct_peak") is not None else "-"
                add(f"{kname:<22}{gbps:>12}{pct:>8}")
        if srv.get("acceptance_rate") is not None or \
                srv.get("effective_tok_per_s") is not None:
            # speculative decoding gauges (engine._record_verify_window)
            line = "spec-dec: "
            parts = []
            if srv.get("acceptance_rate") is not None:
                parts.append(f"acceptance {srv['acceptance_rate']:.2f}")
            if srv.get("effective_tok_per_s") is not None:
                parts.append(
                    f"effective {srv['effective_tok_per_s']:.1f} tok/s")
            if srv.get("draft_overhead_frac") is not None:
                parts.append(
                    f"draft overhead "
                    f"{100 * srv['draft_overhead_frac']:.1f}%")
            add(line + ", ".join(parts))
        lat = srv.get("latency") or {}
        for hname, label in (("ttft_s", "TTFT"), ("tpot_s", "TPOT")):
            row = lat.get(hname)
            if row:
                add(f"{label}: p50 {_fmt_ms(row.get('p50') or 0)}ms, "
                    f"p95 {_fmt_ms(row.get('p95') or 0)}ms, "
                    f"p99 {_fmt_ms(row.get('p99') or 0)}ms "
                    f"(n={int(row.get('count') or 0)})")
        lc = srv.get("lifecycle") or {}
        if lc:
            parts = [f"{k}={int(v)}" for k, v in sorted(lc.items())
                     if v]
            if parts:
                add("lifecycle: " + ", ".join(parts))
        add("")

    tr = s.get("tracing") or {}
    if tr:
        add("--- request tracing (TTFT/TPOT decomposition) ---")
        segs = tr.get("segments") or {}
        if segs:
            from .tracing.cli import segment_table_lines

            rows = [{"segment": seg, "count": row.get("count"),
                     "total_s": row.get("sum"), "p50_s": row.get("p50"),
                     "p95_s": row.get("p95")}
                    for seg, row in segs.items()]
            rows.sort(key=lambda r: r["total_s"] or 0, reverse=True)
            for line in segment_table_lines(rows):
                add(line)
        tc = tr.get("counters") or {}
        if tc:
            add("sampling: " + ", ".join(
                f"{k}={int(v)}" for k, v in sorted(tc.items())
                if v is not None))
        for metric, label in (("ttft_s", "TTFT"), ("tpot_s", "TPOT")):
            ex = (tr.get("exemplars") or {}).get(metric)
            if ex:
                add(f"{label} tail exemplars: " + ", ".join(
                    f"{e['trace'][:12]}… ({_fmt_ms(e['value'])}ms)"
                    for e in ex) +
                    "  [dstpu-trace --request <id> / GET /traces]")
        add("")

    fl = s.get("fleet") or {}
    if fl:
        add("--- serving fleet (dstpu-router) ---")
        line = (f"replicas: {int(fl.get('replicas_routable') or 0)}"
                f"/{int(fl.get('replicas_registered') or 0)} routable")
        if fl.get("replicas_saturated"):
            line += f" ({int(fl['replicas_saturated'])} saturated)"
        if fl.get("prefix_hit_rate") is not None:
            line += (f" · prefix-cache hit rate "
                     f"{100 * fl['prefix_hit_rate']:.1f}%"
                     f" ({int(fl.get('prefix_hit_tokens') or 0)} tokens"
                     f" reused)")
        add(line)
        fc = fl.get("counters") or {}
        if fc:
            parts = [f"{k}={int(v)}" for k, v in sorted(fc.items())
                     if v and k != "kv_ship_bytes"]
            if parts:
                add("routing: " + ", ".join(parts))
        if fc.get("kv_ship_bytes") or fl.get("kv_ship_ms") is not None:
            line = "kv ship: " + _fmt_bytes(int(fc.get("kv_ship_bytes")
                                                or 0))
            if fl.get("kv_ship_ms") is not None:
                line += f", last {fl['kv_ship_ms']:.1f}ms"
            if fl.get("kv_ship_tokens"):
                line += f" ({int(fl['kv_ship_tokens'])} tokens)"
            add(line)
        reps = fl.get("replicas") or {}
        if reps:
            add(f"{'replica':<28}{'queue':>7}{'pending':>9}"
                f"{'kv_pressure':>13}{'tok/s pred':>12}")
            for rname in sorted(reps):
                row = reps[rname]
                add(f"{rname:<28}{int(row.get('queue_depth') or 0):>7}"
                    f"{int(row.get('pending') or 0):>9}"
                    f"{(row.get('kv_pressure') or 0):>13.3f}"
                    f"{(row.get('predicted_tok_per_s') or 0):>12.1f}")
        tens = fl.get("tenants") or {}
        if tens:
            add(f"{'tenant':<20}{'admitted':>10}{'shed':>8}"
                f"{'shed rate':>11}{'inflight':>10}")
            for tname in sorted(tens):
                row = tens[tname]
                add(f"{tname:<20}{int(row.get('admitted') or 0):>10}"
                    f"{int(row.get('sheds') or 0):>8}"
                    f"{100 * (row.get('shed_rate') or 0):>10.1f}%"
                    f"{int(row.get('inflight') or 0):>10}")
        if fl.get("controller_replicas") is not None:
            line = (f"controller: {int(fl['controller_replicas'])} live"
                    f" / {int(fl.get('controller_routable') or 0)} routable"
                    f", drain est {fl.get('controller_drain_s') or 0:.2f}s")
            if fl.get("controller_ttft_p95_s") is not None:
                line += f", ttft p95 est {fl['controller_ttft_p95_s']:.2f}s"
            acts = [f"{k.replace('controller_', '')}={int(v)}"
                    for k, v in sorted((fl.get('counters') or {}).items())
                    if k.startswith("controller_") and v]
            if acts:
                line += "  [" + ", ".join(acts) + "]"
            add(line)
        add("")

    gp = s.get("goodput") or {}
    if gp.get("categories"):
        add("--- goodput ledger (every wall-second attributed) ---")
        wall = float(gp.get("wall_s") or 0.0)
        line = f"wall: {wall:.2f}s"
        if gp.get("goodput_fraction") is not None:
            line += f" · goodput {100 * gp['goodput_fraction']:.1f}%"
        over = float(gp.get("overcommit_s") or 0.0)
        line += (f" · overcommit {over:.3f}s"
                 + (" (NOT conserved — double-counted seam?)"
                    if wall > 0 and over > 0.01 * wall else ""))
        add(line)
        cats = gp["categories"]
        fracs = gp.get("fractions") or {}
        add(f"{'category':<20}{'seconds':>12}{'% wall':>9}")
        for cat in sorted(cats, key=lambda c: cats[c] or 0, reverse=True):
            if not cats[cat]:
                continue
            pct = f"{100 * fracs[cat]:.1f}%" if cat in fracs else "-"
            add(f"{cat:<20}{cats[cat]:>12.3f}{pct:>9}")
        tens = gp.get("tenant_shed_s") or {}
        if tens:
            add("shed by tenant: " + ", ".join(
                f"{t}={v:.3f}s" for t, v in sorted(tens.items())))
        add("")

    add("--- memory high-water marks ---")
    mem = s["memory"]
    if mem:
        if "live_array_bytes_max" in mem:
            step = mem.get("live_array_bytes_peak_step")
            at = f" (at step {step})" if step is not None else ""
            add(f"live jax.Arrays: {_fmt_bytes(mem['live_array_bytes_max'])}"
                f"{at}, count max "
                f"{int(mem.get('live_array_count_max') or 0)}")
        if "device_peak_bytes_in_use_max" in mem:
            add(f"device allocator peak: "
                f"{_fmt_bytes(mem['device_peak_bytes_in_use_max'])} "
                f"(in_use max {_fmt_bytes(mem.get('device_bytes_in_use_max') or 0)})")
        buckets = mem.get("buckets") or {}
        if buckets:
            live = float(mem.get("live_bytes") or 0.0)
            line = f"occupancy ledger: live {_fmt_bytes(live)}"
            if mem.get("conserved") is not None:
                ok = bool(mem["conserved"])
                una = float(mem.get("unattributed_bytes") or 0.0)
                line += (f" · unattributed {_fmt_bytes(abs(una))}"
                         + ("" if ok else " (NOT conserved)"))
            add(line)
            add(f"{'bucket':<20}{'bytes':>12}{'% live':>9}")
            for b in sorted(buckets, key=lambda b: buckets[b] or 0,
                            reverse=True):
                v = float(buckets[b] or 0.0)
                if not v:
                    continue
                pct = f"{100 * v / live:.1f}%" if live > 0 else "-"
                add(f"{b:<20}{_fmt_bytes(v):>12}{pct:>9}")
        kv = mem.get("kv") or {}
        if kv:
            line = (f"kv heat: live pages "
                    f"{int(kv.get('kv_live_pages') or 0)} "
                    f"(peak {int(kv.get('kv_peak_pages') or 0)}), used "
                    f"{_fmt_bytes(kv.get('kv_used_bytes') or 0)}")
            saved = float(kv.get("prefix_shared_bytes_saved") or 0.0)
            if saved:
                line += f", prefix sharing saves {_fmt_bytes(saved)}"
            add(line)
            cold = kv.get("cold_pages") or {}
            if cold:
                add("cold pages by age: " + ", ".join(
                    f">{thr}w={int(n)}" for thr, n in
                    sorted(cold.items(), key=lambda kv_: int(kv_[0]))))
            tens = kv.get("tenants") or {}
            if tens:
                add("kv by tenant: " + ", ".join(
                    f"{t}={_fmt_bytes(v)}"
                    for t, v in sorted(tens.items())))
    else:
        add("(no memory samples)")
    add("")

    inc = s["incidents"]
    add("--- events ---")
    add("counts: " + json.dumps(inc["event_counts"], sort_keys=True))
    for e in inc["checkpoints"]:
        dur = e.get("duration_s")
        dur_txt = f" in {dur:.3f}s" if isinstance(dur, (int, float)) else ""
        add(f"  {e.get('kind')}: tag={e.get('tag')}{dur_txt}")
    for e in inc["incidents"]:
        add("  INCIDENT " + json.dumps(
            {k: v for k, v in e.items() if k != "thread_stacks"},
            sort_keys=True, default=str))
    return "\n".join(lines)


# --------------------------------------------------------------------- #
# Postmortem bundle (--bundle)
# --------------------------------------------------------------------- #
def make_bundle(out_path: str,
                events_path: Optional[str] = None,
                trace_path: Optional[str] = None,
                extra_dir: Optional[str] = None,
                summary: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """One-file postmortem: every rotation segment of the event log, the
    request-trace log (``traces.jsonl[.N]`` beside it), the chrome trace,
    any ``*config*.json`` echo files in the telemetry dir, plus a
    generated ``summary.json`` (the final metric snapshot, digested) and
    a ``manifest.json`` — packed into ``out_path`` (tar.gz).  Returns the
    manifest.  Missing artifacts are skipped, never fatal: a postmortem
    of a half-dead run is exactly when this gets used."""
    import tarfile
    import time as _time

    from .events import event_segments

    files: List[str] = []
    if events_path:
        files.extend(event_segments(events_path))
        # the request-trace log lives beside events.jsonl in the same
        # telemetry dir (tracing/store.py default wiring)
        files.extend(event_segments(
            os.path.join(os.path.dirname(os.path.abspath(events_path)),
                         "traces.jsonl")))
    if trace_path and os.path.exists(trace_path):
        files.append(trace_path)
    if extra_dir and os.path.isdir(extra_dir):
        for fn in sorted(os.listdir(extra_dir)):
            if "config" in fn and fn.endswith(".json"):
                files.append(os.path.join(extra_dir, fn))
    seen: set = set()
    files = [f for f in files
             if os.path.exists(f) and not (f in seen or seen.add(f))]
    manifest: Dict[str, Any] = {
        "created_unix": _time.time(),
        "sources": {"events": events_path, "trace": trace_path},
        "files": [{"name": os.path.basename(f),
                   "bytes": os.path.getsize(f)} for f in files],
    }
    with tarfile.open(out_path, "w:gz") as tar:
        for f in files:
            tar.add(f, arcname=os.path.join("bundle", os.path.basename(f)))

        def _add_json(name: str, obj: Any) -> None:
            import io

            data = json.dumps(obj, indent=2, sort_keys=True,
                              default=str).encode()
            info = tarfile.TarInfo(os.path.join("bundle", name))
            info.size = len(data)
            info.mtime = int(_time.time())
            tar.addfile(info, io.BytesIO(data))

        if summary is not None:
            _add_json("summary.json", summary)
            manifest["files"].append({"name": "summary.json",
                                      "generated": True})
        _add_json("manifest.json", manifest)
    return manifest


# --------------------------------------------------------------------- #
# CLI
# --------------------------------------------------------------------- #
def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    import sys

    roofline_doc = "\n".join(f"  {name:<22}{desc}"
                             for name, desc in ROOFLINE_COLUMNS)
    parser = argparse.ArgumentParser(
        prog="dstpu-telemetry",
        formatter_class=argparse.RawDescriptionHelpFormatter,
        description="Summarize a deepspeed_tpu telemetry output directory "
                    "(step-phase breakdown, comm bandwidth, performance "
                    "attribution, memory high-water marks).",
        epilog="roofline columns (the 'performance attribution' section, "
               "from roofline/* gauges\nand profile_report events):\n"
               + roofline_doc +
               "\n\nThe per-module cost tree attributes analytic "
               "flops/bytes to jax.named_scope\nmodules (fwd+bwd), anchored "
               "to XLA cost analysis of the compiled step; the\ndevice-time "
               "breakdown parses the xprof trace captured at "
               "comms_logger.xprof_step\ninto compute / communication / "
               "host-transfer buckets.")
    parser.add_argument("path",
                        help="telemetry output dir (containing events.jsonl/"
                             "trace.json) or a path to an events.jsonl")
    parser.add_argument("--trace", default=None,
                        help="explicit trace.json path (default: "
                             "<dir>/trace.json)")
    parser.add_argument("--xprof", default=None,
                        help="xprof trace dir/file for the device-time "
                             "breakdown (default: the run's xprof_trace "
                             "event, if any)")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="emit the summary as JSON instead of text")
    parser.add_argument("--bundle", default=None, metavar="OUT.tar.gz",
                        help="pack a postmortem bundle: events.jsonl[.N] "
                             "+ traces.jsonl[.N] + trace.json + config "
                             "echoes + generated summary.json + manifest, "
                             "as one tar.gz")
    parser.add_argument("--compare", nargs="?", const=".", default=None,
                        metavar="HISTORY_DIR",
                        help="cross-run regression check: diff this run "
                             "(a telemetry dir or a bench JSON) against the "
                             "BENCH_r*.json history in HISTORY_DIR (default "
                             "'.'); exits 3 when a metric regressed past "
                             "the threshold, 2 when either side has "
                             "nothing comparable")
    parser.add_argument("--compare-threshold", type=float, default=0.15,
                        help="relative worsening vs the history median that "
                             "counts as a regression (default 0.15)")
    parser.add_argument("--compare-pattern", default=None,
                        help="history filename glob (default BENCH_r*.json)")
    args = parser.parse_args(argv)

    if args.compare is not None:
        rc, text = _run_compare(args)
        try:
            print(text)
        except BrokenPipeError:
            try:
                sys.stdout.close()
            except BrokenPipeError:
                pass
        return rc

    path = args.path
    if os.path.isdir(path):
        events_path = os.path.join(path, "events.jsonl")
        trace_path = args.trace or os.path.join(path, "trace.json")
    else:
        events_path = path
        trace_path = args.trace
    from .events import event_segments

    # rotation-aware: after a crash mid-rotation the live events.jsonl may
    # be missing while the .N segments hold the whole pre-crash history
    if not event_segments(events_path) and not (
            trace_path and os.path.exists(trace_path)):
        print(f"dstpu-telemetry: no events.jsonl[.N] or trace.json at {path}")
        return 2

    summary = summarize_run(events_path, trace_path, xprof_dir=args.xprof)
    if args.bundle:
        manifest = make_bundle(
            args.bundle, events_path=events_path, trace_path=trace_path,
            extra_dir=path if os.path.isdir(path) else
            os.path.dirname(os.path.abspath(events_path)),
            summary=summary)
        print(f"dstpu-telemetry: bundle {args.bundle} "
              f"({len(manifest['files'])} files)")
        return 0
    try:
        if args.as_json:
            print(json.dumps(summary, indent=2, sort_keys=True, default=str))
        else:
            print(format_summary(summary))
    except BrokenPipeError:   # e.g. piped into `head`
        try:
            sys.stdout.close()
        except BrokenPipeError:
            pass
    return 0


def _run_compare(args) -> Tuple[int, str]:
    """``--compare`` mode: (exit_code, report_text) — 3 on a regression
    (so CI gates on the exit code alone), 2 when there is nothing to
    compare on EITHER side: an unusable current run, or no usable history
    (a mistyped HISTORY_DIR must not read as a green gate).  ``main`` owns
    the printing."""
    from .regression import (DEFAULT_PATTERN, VERDICT_NO_HISTORY,
                             VERDICT_REGRESSION, compare_runs,
                             current_metrics_from_path, format_compare,
                             load_history)

    try:
        current = current_metrics_from_path(args.path)
    except (OSError, json.JSONDecodeError) as e:
        return 2, (f"dstpu-telemetry --compare: cannot read current run "
                   f"{args.path}: {e}")
    if not current:
        return 2, (f"dstpu-telemetry --compare: no comparable metrics in "
                   f"{args.path} (need a bench JSON or a telemetry dir "
                   f"with engine/train_batch spans)")
    history = load_history(args.compare,
                           args.compare_pattern or DEFAULT_PATTERN,
                           exclude=args.path)
    report = compare_runs(current, history,
                          threshold=args.compare_threshold)
    report["current_run"] = args.path
    if args.as_json:
        text = json.dumps(report, indent=2, sort_keys=True, default=str)
    else:
        text = format_compare(report, history_dir=args.compare)
    if report["verdict"] == VERDICT_REGRESSION:
        return 3, text
    if report["verdict"] == VERDICT_NO_HISTORY:
        return 2, text
    return 0, text


if __name__ == "__main__":
    import sys

    sys.exit(main())
