"""``scope_time_share`` for a cell that runs SEVERAL compiled programs in
its traced slice (a serving cell: prefill buckets and decode windows).

Own device time, inside the traced slice, of the operations whose
``jax.named_scope`` path matches ``scope``, as a share of the device's busy
time, mean over devices.  An operation's scope comes from the text of the
program it ran in, which the serving engine registers per program
(``profiling/xprof_parse.register_step_text``); the program is the "XLA
Modules" event that covers the operation's start.
``lib/program_trace.ops_with_scope`` finds that event by bisecting a list it
sorted by module NAME, which is right for a cell with one program (the
training cells) and wrong here, so this reader sorts the events by start
itself.  A traced run also leaves ``serve_scopes.json`` beside its profile:
device seconds by scope, the table PERF.md section 5 is written from.
args: scope.  A program that registered no text: no value."""
import bisect
import json
import os
import re

from lib import program_trace, trace

_ROWS = None


def rows_by_device(run):
    """Per device [(scope or None, own_ns)] of the operations in the
    slice; None when no program registered its text."""
    global _ROWS
    if _ROWS is not None:
        return _ROWS or None
    _ROWS = {}
    table = program_trace.scopes()
    extra = program_trace.xplane(run)
    win = trace.window_of(run["trace"]) if run.get("trace") else None
    if table is None or extra is None or win is None:
        return None
    lo, hi = win
    for plane, ops in run["trace"]["device"].items():
        mods = sorted(extra["modules"].get(plane, []), key=lambda m: m[1])
        starts = [m[1] for m in mods]
        rows = []
        for name, start, dur, _, _, own in ops:
            if own <= 0 or start < lo or start + dur > hi:
                continue
            i = bisect.bisect_right(starts, start) - 1
            known = table.get(mods[i][0]) if i >= 0 and \
                start < mods[i][1] + mods[i][2] else None
            rows.append((known.get(name, "") if known is not None else None,
                         own))
        _ROWS[plane] = rows
    try:
        acc = {}
        for rows in _ROWS.values():
            for scope, own in rows:
                key = "_other_program_" if scope is None else scope or "_none_"
                acc[key] = acc.get(key, 0.0) + own / len(_ROWS) / 1e9
        seen = sorted({m[0] for mods in extra["modules"].values()
                       for m in mods})
        with open(os.path.join(extra["dir"], "serve_scopes.json"), "w") as f:
            json.dump({"device_s_by_scope": dict(sorted(
                acc.items(), key=lambda kv: -kv[1])[:80]),
                "programs_in_the_slice": seen,
                "programs_with_text": sorted(table)}, f, indent=1)
    except Exception:  # noqa: BLE001 — a table less, not a run
        pass
    return _ROWS or None


def read(run, args):
    busy = trace.busy(run["trace"]) if run.get("trace") else None
    per_dev = rows_by_device(run)
    if not per_dev or busy is None or busy["busy_s"] <= 0:
        return None
    if not any(scope is not None for rows in per_dev.values()
               for scope, _ in rows):
        return None
    scope = re.compile(args["scope"])
    total = sum(own for rows in per_dev.values() for op_scope, own in rows
                if op_scope is not None and scope.search(op_scope))
    return total / len(per_dev) / 1e9 / busy["busy_s"]
