"""What the program says about itself, for the readers that wrap nothing.

Two sources, each loaded once per process (one run is one process):

* ``ring(run)`` — the spans the program recorded on its process-global
  tracer (``deepspeed_tpu.telemetry.get_tracer()``): ``serve/*`` from the
  scheduler, ``engine/*`` from the engines, as ``(name, t0, dur, attrs,
  tid)`` with ``t0`` on ``time.perf_counter()``, the clock ``run["window"]``
  is on.  None where the program has no such tracer.
* ``xplane(run)`` — from the run's own profile (the newest ``.xplane.pb``
  under ``<checkout>/.bench_trace``), what ``lib/trace.load_xplane`` leaves
  out: the program's host events (``serve/*``, ``engine/*``, which a span
  mirrors into the profile through ``TraceAnnotation``) and each device's
  "XLA Modules" line, on the clock ``run["trace"]`` is on (ns from the start
  of the profile).  None for a run that was not traced.

A traced run also leaves ``program_breakdown.json`` beside its profile: the
device's idle time by program span and, where the program registered its
compiled step (``profiling/xprof_parse.register_step_text``), device time by
name scope — the tables PERF.md §5 is written from.

Nothing here imports ``lib/serve_system`` or reads a ``bench/`` span, and
nothing raises where the program lacks a span, a counter or the tracer: the
reader then returns None and the line leaves the metric out.
"""
from __future__ import annotations

import bisect
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from lib import trace as trace_lib

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: host events of the program (what ``lib/trace.py`` drops)
PROGRAM_SPAN = re.compile(r"^(serve|engine)/")

_RING: Optional[list] = None
_XPLANE: Optional[dict] = None
_SCOPES: Optional[dict] = None
_LOADED = {"ring": False, "xplane": False, "scopes": False}


def preload(ring=None, xplane=None, scopes=None) -> None:
    """Stand in for the live sources (a recorded sample, a test)."""
    global _RING, _XPLANE, _SCOPES
    _RING, _XPLANE, _SCOPES = ring, xplane, scopes
    for key in _LOADED:
        _LOADED[key] = True


def ring(run) -> Optional[List[tuple]]:
    """[(name, t0, dur, attrs, tid)] of the program's tracer, oldest first."""
    global _RING
    if not _LOADED["ring"]:
        _LOADED["ring"] = True
        try:
            from deepspeed_tpu.telemetry import get_tracer
        except ImportError:
            return None
        tracer = get_tracer()
        epoch = tracer.epoch
        _RING = sorted(
            ((r.name, epoch + r.start_s, r.dur_s, r.attrs or {}, r.tid)
             for r in tracer.records()), key=lambda sp: sp[1])
    return _RING


def in_window(spans: Sequence[tuple], lo: float, hi: float, names=None
              ) -> List[tuple]:
    """Spans that END inside [lo, hi) (a span recorded with an explicit
    start, such as a queue wait, may have begun before it)."""
    return [sp for sp in spans if lo <= sp[1] + sp[2] < hi
            and (names is None or sp[0] in names)]


def xplane(run) -> Optional[Dict[str, object]]:
    """``{"host": [(name, start_ns, dur_ns)], "modules": {plane: [(module,
    start_ns, dur_ns)]}}`` of the run's profile."""
    global _XPLANE
    if run.get("trace") is None:
        return None
    if not _LOADED["xplane"]:
        _LOADED["xplane"] = True
        path = trace_lib.find_xplane(os.path.join(ROOT, ".bench_trace"))
        if path is None:
            return None
        import jax

        data = jax.profiler.ProfileData.from_file(path)
        host: List[tuple] = []
        modules: Dict[str, List[tuple]] = {}
        for plane in data.planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        name = ev.name.split("#", 1)[0]
                        if PROGRAM_SPAN.match(name):
                            host.append((name, float(ev.start_ns),
                                         float(ev.duration_ns)))
            elif plane.name.startswith("/device:TPU:"):
                for line in plane.lines:
                    if line.name == "XLA Modules":
                        modules[plane.name] = sorted(
                            (ev.name.split("(", 1)[0], float(ev.start_ns),
                             float(ev.duration_ns)) for ev in line.events)
        host.sort(key=lambda sp: sp[1])
        _XPLANE = {"host": host, "modules": modules, "dir":
                   os.path.dirname(path)}
        _write_breakdown(run)
    return _XPLANE


def scopes() -> Optional[Dict[str, Dict[str, str]]]:
    """{XLA module: {instruction: name scope}} of the programs that
    registered their compiled text; None where there is none to ask."""
    global _SCOPES
    if not _LOADED["scopes"]:
        _LOADED["scopes"] = True
        try:
            from deepspeed_tpu.profiling.xprof_parse import registered_scopes
        except ImportError:
            return None
        try:
            _SCOPES = registered_scopes() or None
        except Exception as exc:  # noqa: BLE001 — a metric less, not a run
            print(f"program_trace: no scopes: {exc!r}", flush=True,
                  file=sys.stderr)
            _SCOPES = None
    return _SCOPES


# ---- idle time by program span --------------------------------------------
def idle_by_span(trace: Dict[str, object], host: Sequence[tuple]
                 ) -> Optional[Dict[str, object]]:
    """The first device's idle time inside the traced slice
    (``lib/trace.window_of``) split among the program's host spans that
    cover it, innermost first; ``""`` is what no program span covers.
    ``leaf`` is the part claimed by a span with no span inside it."""
    win = trace_lib.window_of(trace)
    devices = [ops for _, ops in sorted(trace["device"].items()) if ops]
    if win is None or not devices:
        return None
    lo, hi = win
    gaps = trace_lib.subtract(
        [(lo, hi)], trace_lib.clip(trace_lib.op_intervals(devices[0]),
                                   lo, hi))
    spans = [sp for sp in host if sp[1] + sp[2] > lo and sp[1] < hi]
    starts = sorted(sp[1] for sp in spans)
    by_name: Dict[str, float] = {}
    leaf = 0.0
    left = gaps
    # innermost wins: shortest first, each claims what is still unclaimed
    for name, start, dur in sorted(spans, key=lambda sp: sp[2]):
        if not left:
            break
        span = [(start, start + dur)]
        rest = trace_lib.subtract(left, span)
        claimed = trace_lib.total(left) - trace_lib.total(rest)
        if claimed > 0:
            by_name[name] = by_name.get(name, 0.0) + claimed
            # a leaf holds no other span's start (its own start aside)
            i = bisect.bisect_right(starts, start)
            if i >= len(starts) or starts[i] >= start + dur:
                leaf += claimed
            left = rest
    by_name[""] = trace_lib.total(left)
    return {"slice_ns": hi - lo, "idle_ns": trace_lib.total(gaps),
            "by_span_ns": by_name, "leaf_ns": leaf}


# ---- device time by name scope ---------------------------------------------
def ops_with_scope(run) -> Optional[Dict[str, List[tuple]]]:
    """Per device, the operations inside the traced slice as (scope, opcode,
    own_ns, label); the scope is None for an operation of a program that
    registered no text.  None when no program registered any."""
    table = scopes()
    extra = xplane(run)
    if table is None or extra is None:
        return None
    trace = run["trace"]
    win = trace_lib.window_of(trace)
    if win is None:
        return None
    lo, hi = win
    out: Dict[str, List[tuple]] = {}
    for plane, ops in trace["device"].items():
        mods = extra["modules"].get(plane, [])
        mod_starts = [m[1] for m in mods]
        rows = []
        for name, start, dur, label, opcode, own in ops:
            if own <= 0 or start < lo or start + dur > hi:
                continue
            i = bisect.bisect_right(mod_starts, start) - 1
            module = mods[i][0] if i >= 0 and \
                start < mods[i][1] + mods[i][2] else None
            known = table.get(module)
            rows.append((known.get(name, "") if known is not None else None,
                         opcode, own, label))
        out[plane] = rows
    return out


def _write_breakdown(run) -> None:
    """``program_breakdown.json`` beside the profile; never raises."""
    try:
        out: Dict[str, object] = {}
        idle = idle_by_span(run["trace"], _XPLANE["host"])
        if idle is not None:
            out["idle"] = {
                "slice_s": idle["slice_ns"] / 1e9,
                "idle_s": idle["idle_ns"] / 1e9,
                "leaf_s": idle["leaf_ns"] / 1e9,
                "by_span_s": {k or "_none_": v / 1e9 for k, v in sorted(
                    idle["by_span_ns"].items(), key=lambda kv: -kv[1])}}
        per_dev = ops_with_scope(run)
        if per_dev:
            with open(os.path.join(_XPLANE["dir"], "scopes.json"), "w") as f:
                json.dump(scopes(), f)
            acc: Dict[str, float] = {}
            coll: Dict[str, float] = {}
            for rows in per_dev.values():
                for scope, opcode, own, label in rows:
                    key = "_other_program_" if scope is None \
                        else (scope or "_none_")
                    acc[key] = acc.get(key, 0.0) + own
                    if trace_lib.COLLECTIVE.match(opcode):
                        coll[key] = coll.get(key, 0.0) + own
            n = len(per_dev)
            top = lambda d: {k: v / n / 1e9 for k, v in sorted(  # noqa: E731
                d.items(), key=lambda kv: -kv[1])[:60]}
            out["device_s_by_scope"] = top(acc)
            out["collective_s_by_scope"] = top(coll)
        spans = ring(run)
        if spans is not None:
            lo, hi = run["window"]
            by: Dict[str, List[float]] = {}
            for name, t0, dur, _, _ in in_window(spans, lo, hi):
                rec = by.setdefault(name, [0, 0.0])
                rec[0] += 1
                rec[1] += dur
            out["host_spans_in_window"] = {
                k: {"n": n_, "s": s_} for k, (n_, s_) in sorted(by.items())}
        with open(os.path.join(_XPLANE["dir"], "program_breakdown.json"),
                  "w") as f:
            json.dump(out, f, indent=1)
    except Exception as exc:  # noqa: BLE001
        print(f"program_trace: no breakdown written: {exc!r}", flush=True,
              file=sys.stderr)
