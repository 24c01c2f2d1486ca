"""Host time inside the window that the program spent in its own spans
``spans`` (``deepspeed_tpu.telemetry.get_tracer()``'s ring, recorded where
the work happens), less the time of the spans under them whose name starts
with one of ``minus``, as a share of the window.  args: spans, minus."""
from lib import program_trace, trace


def read(run, args):
    spans = program_trace.ring(run)
    if spans is None:
        return None
    lo, hi = run["window"]
    minus = tuple(args.get("minus", []))
    inside, under = {}, {}          # per thread
    for name, t0, dur, _, tid in spans:
        if t0 + dur <= lo or t0 >= hi:
            continue
        if name in args["spans"]:
            inside.setdefault(tid, []).append((t0, t0 + dur))
        elif minus and name.startswith(minus):
            under.setdefault(tid, []).append((t0, t0 + dur))
    if not inside:
        return None
    own = 0.0
    for tid, intervals in inside.items():
        mine = trace.clip(trace.union(intervals), lo, hi)
        own += trace.total(trace.subtract(
            mine, trace.union(under.get(tid, []))))
    return own / (hi - lo)
