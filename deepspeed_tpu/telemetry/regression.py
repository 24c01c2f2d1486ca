"""Cross-run performance regression tracking against BENCH history.

Nothing in the repo writes a ``BENCH_r*.json`` (a step-time / MFU /
tokens-per-chip record of one run) any more: the benchmark is
``benchmark/run.py`` and its history ``PERF_LEDGER.jsonl`` (ROADMAP D16).
This module turns an archive of such files into a regression gate: extract
the comparable
metrics from the current run (a bench JSON *or* a telemetry output dir),
take the median of the history as the baseline (median, not mean — one
broken historical run must not move the bar), and flag any metric that
moved past ``threshold`` in its *bad* direction.  Step time and exposed
comm regress upward; MFU and throughput regress downward.

Consumed by ``dstpu-telemetry --compare`` (exit code 3 on a regression so
CI can gate without parsing output) and by ``tools/check_telemetry_cli.py``.
"""
from __future__ import annotations

import glob
import json
import math
import os
import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

DEFAULT_PATTERN = "BENCH_r*.json"

#: metric key → (direction, description); direction +1 = higher is better
METRICS: Dict[str, Tuple[int, str]] = {
    "step_time_s": (-1, "mean optimizer-step wall time"),
    "mfu": (+1, "model flops utilization"),
    "tokens_per_sec_per_chip": (+1, "training throughput per chip"),
    "exposed_comm_fraction": (-1, "device time exposed on communication"),
}

VERDICT_REGRESSION = "regression"
VERDICT_OK = "ok"
VERDICT_NO_HISTORY = "no-history"


# ------------------------------------------------------------------- #
# Extraction
# ------------------------------------------------------------------- #
def extract_bench_metrics(doc: Dict[str, Any]) -> Dict[str, float]:
    """Comparable metrics from one BENCH_r*.json (or a bare bench ``parsed``
    payload).  Runs that never produced numbers (``parsed: null`` — e.g. no
    accelerator that day) extract to {} and are skipped upstream."""
    parsed = doc.get("parsed") if isinstance(doc.get("parsed"), dict) else (
        doc if "metric" in doc else None)
    if not parsed:
        return {}
    out: Dict[str, float] = {}
    extra = parsed.get("extra") or {}
    if isinstance(extra.get("step_time_s"), (int, float)):
        out["step_time_s"] = float(extra["step_time_s"])
    if isinstance(extra.get("mfu"), (int, float)):
        out["mfu"] = float(extra["mfu"])
    if isinstance(extra.get("exposed_comm_fraction"), (int, float)):
        out["exposed_comm_fraction"] = float(extra["exposed_comm_fraction"])
    unit = str(parsed.get("unit", ""))
    if isinstance(parsed.get("value"), (int, float)) and \
            unit.startswith("tokens/s"):
        out["tokens_per_sec_per_chip"] = float(parsed["value"])
    return out


def extract_run_metrics(summary: Dict[str, Any]) -> Dict[str, float]:
    """Comparable metrics from a ``summarize_run`` digest (a telemetry
    output dir): step time from the ``engine/train_batch`` span row, MFU
    from the roofline gauges, exposed comm from the overlap gauges."""
    out: Dict[str, float] = {}
    for row in summary.get("step_breakdown") or []:
        if row.get("phase") == "engine/train_batch" and row.get("count"):
            out["step_time_s"] = float(row["mean_s"])
            break
    prof = summary.get("profile") or {}
    roof = (prof.get("report") or {}).get("roofline") or \
        prof.get("roofline_gauges") or {}
    if isinstance(roof.get("mfu"), (int, float)):
        out["mfu"] = float(roof["mfu"])
    ov = summary.get("overlap") or {}
    if isinstance(ov.get("exposed_comm_fraction"), (int, float)):
        out["exposed_comm_fraction"] = float(ov["exposed_comm_fraction"])
    return out


def load_history(history_dir: str, pattern: str = DEFAULT_PATTERN,
                 exclude: Optional[str] = None) -> List[Dict[str, Any]]:
    """Every readable history entry, sorted by filename (run order):
    ``[{"file", "metrics"}, ...]``; entries with no numbers keep ``metrics:
    {}`` so callers can report how much history was unusable.  ``exclude``
    drops one path — the run UNDER comparison often sits in the same dir
    (the history is one flat directory), and letting it join its own
    baseline dilutes the median toward itself, masking the regression."""
    entries: List[Dict[str, Any]] = []
    skip = os.path.abspath(exclude) if exclude else None
    for path in sorted(glob.glob(os.path.join(history_dir, pattern))):
        if skip and os.path.abspath(path) == skip:
            continue
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            entries.append({"file": path, "metrics": {}, "unreadable": True})
            continue
        entries.append({"file": path, "metrics": extract_bench_metrics(doc)})
    return entries


def current_metrics_from_path(path: str) -> Dict[str, float]:
    """The current run's metrics from either source: a bench JSON file, or
    a telemetry output dir (events.jsonl summarized on the spot)."""
    if os.path.isfile(path) and path.endswith(".json"):
        with open(path) as f:
            return extract_bench_metrics(json.load(f))
    from .summary import summarize_run

    events_path = os.path.join(path, "events.jsonl") \
        if os.path.isdir(path) else path
    trace_path = os.path.join(path, "trace.json") \
        if os.path.isdir(path) else None
    return extract_run_metrics(summarize_run(events_path, trace_path))


# ------------------------------------------------------------------- #
# Comparison
# ------------------------------------------------------------------- #
def compare_runs(current: Dict[str, float],
                 history: Sequence[Dict[str, Any]],
                 threshold: float = 0.15,
                 min_history: int = 1) -> Dict[str, Any]:
    """Verdict over every metric present in both the current run and at
    least ``min_history`` usable history entries.  ``delta`` is signed so a
    +0.30 on ``step_time_s`` reads as "30% slower"."""
    usable = [h for h in history if h.get("metrics")]
    rows: Dict[str, Dict[str, Any]] = {}
    regressions: List[str] = []
    for name, (direction, desc) in METRICS.items():
        if name not in current:
            continue
        past = [h["metrics"][name] for h in usable if name in h["metrics"]]
        if len(past) < min_history:
            continue
        baseline = statistics.median(past)
        cur = float(current[name])
        if baseline:
            delta = (cur - baseline) / abs(baseline)
        else:
            # a zero baseline (e.g. exposed_comm_fraction fully overlapped
            # in every prior run) must still flag ANY move off it — delta 0
            # here would make the one regression this metric can have
            # structurally invisible to the gate
            delta = math.inf if cur > 0 else 0.0
        # positive worsening: how far the metric moved in its bad direction
        worsening = -delta if direction > 0 else delta
        regressed = worsening > threshold
        rows[name] = {
            "current": cur,
            "baseline": baseline,
            "n_history": len(past),
            # an infinite delta (off a zero baseline) would serialize as
            # the non-standard JSON token Infinity and break strict --json
            # consumers (jq, JSON.parse); null keeps the report parseable
            # while "regressed" still carries the verdict
            "delta": None if math.isinf(delta) else round(delta, 4),
            "worsening": None if math.isinf(worsening)
            else round(worsening, 4),
            "regressed": regressed,
            "description": desc,
        }
        if regressed:
            regressions.append(name)
    if not rows:
        verdict = VERDICT_NO_HISTORY
    elif regressions:
        verdict = VERDICT_REGRESSION
    else:
        verdict = VERDICT_OK
    return {
        "verdict": verdict,
        "threshold": threshold,
        "regressions": regressions,
        "metrics": rows,
        "history_total": len(history),
        "history_usable": len(usable),
    }


def format_compare(report: Dict[str, Any],
                   history_dir: Optional[str] = None) -> str:
    lines: List[str] = []
    add = lines.append
    add("=== dstpu cross-run regression check ===")
    if history_dir:
        add(f"history: {report['history_usable']}/{report['history_total']} "
            f"usable run(s) under {history_dir}")
    add(f"threshold: {report['threshold'] * 100:.0f}% vs history median")
    rows = report["metrics"]
    if rows:
        add(f"{'metric':<26}{'current':>12}{'baseline':>12}{'delta':>9}"
            f"{'n':>4}  verdict")
        for name, r in rows.items():
            verdict = "REGRESSED" if r["regressed"] else "ok"
            delta = "inf%" if r["delta"] is None \
                else f"{r['delta'] * 100:.1f}%"
            add(f"{name:<26}{r['current']:>12.4g}{r['baseline']:>12.4g}"
                f"{delta:>9}{r['n_history']:>4}  {verdict}")
    else:
        add("(no comparable metrics between the current run and history)")
    add(f"verdict: {report['verdict'].upper()}")
    if report["regressions"]:
        add("regressed: " + ", ".join(
            f"{n} ({rows[n]['description']})" for n in report["regressions"]))
    return "\n".join(lines)
