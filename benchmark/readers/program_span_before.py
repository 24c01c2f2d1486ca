"""Set-up by what the program says it did: the sum in seconds (``stat``
"sum") or the count ("count") of the ring's records named in ``spans`` that
END before the window starts (``run["window"][0]``), kept where the
attribute ``where.key`` is one of ``where.in``, or is not one of
``where.not_in``.  The ring is ``deepspeed_tpu.telemetry.get_tracer()``'s:
``compile/trace``, ``compile/lower`` and ``compile/backend`` come from
``jax.monitoring`` (``deepspeed_tpu/utils/compile_cache.py``), one a phase
and outermost only, so their seconds add up to no more than ``setup_s``;
``engine/init`` spans each engine's construction.

No record of those names anywhere in the ring: no value (a program without
the listeners).  A tracer that dropped records: no value either, not a short
sum.  A traced run also leaves ``setup_account.json`` beside its profile:
``compile_account()``'s row a program (before, in and after the window; the
rows' ``cache`` = ``written`` are what ``cache_entries_added`` counts from
outside), the seconds of set-up under each top-level ``engine/*`` and ``serve/*`` span, and
the benchmark's own ``bench/setup_*`` spans beside them — the table PERF.md section 5 is written
from."""
import json
import os
import sys

from lib import program_trace

_WRITTEN = False


def dropped() -> int:
    try:
        from deepspeed_tpu.telemetry import get_tracer
    except ImportError:
        return 0
    return get_tracer().dropped


def kept(attrs, where) -> bool:
    if not where:
        return True
    value = attrs.get(where["key"])
    if "in" in where:
        return value in where["in"]
    return value not in where["not_in"]


def write_account(run) -> None:
    """``setup_account.json`` beside the profile, once; never raises."""
    global _WRITTEN
    if _WRITTEN:
        return
    _WRITTEN = True
    try:
        from deepspeed_tpu.telemetry import get_tracer
        from deepspeed_tpu.utils.compile_cache import compile_account

        extra = program_trace.xplane(run)
        if extra is None:
            return
        tracer = get_tracer()
        records = tracer.records()
        lo, hi = (t - tracer.epoch for t in run["window"])
        when = {"before_the_window": [], "in_the_window": [],
                "after_the_window": []}
        for r in records:
            end = r.start_s + r.dur_s
            when["before_the_window" if end < lo else "in_the_window"
                 if end < hi else "after_the_window"].append(r)
        top = {}
        for r in when["before_the_window"]:
            if r.parent is None and r.name.startswith(("engine/", "serve/")):
                top[r.name] = top.get(r.name, 0.0) + r.dur_s
        out = {
            "facts": {k: run["facts"].get(k) for k in (
                "setup_seconds", "client_seconds", "cache_entries_new")},
            "window_s": [lo, hi], "ring_dropped": tracer.dropped,
            "programs": {k: compile_account(v) for k, v in when.items()},
            "top_level_spans_before_the_window_s": top,
            "bench_spans_before_the_window_s": run["spans"].by_name(
                float("-inf"), run["window"][0])}
        with open(os.path.join(extra["dir"], "setup_account.json"), "w") as f:
            json.dump(out, f, indent=1)
    except Exception as exc:  # noqa: BLE001 — a table less, not a run
        print(f"program_span_before: no setup_account.json: {exc!r}",
              flush=True, file=sys.stderr)


def read(run, args):
    spans = program_trace.ring(run)
    if spans is None:
        return None
    names = set(args["spans"])
    if not any(sp[0] in names for sp in spans):
        return None                 # the program records no such span
    if dropped():
        return None
    write_account(run)
    lo = run["window"][0]
    where = args.get("where")
    found = [sp[2] for sp in spans if sp[0] in names and sp[1] + sp[2] < lo
             and kept(sp[3], where)]
    return float(len(found) if args["stat"] == "count" else sum(found))
