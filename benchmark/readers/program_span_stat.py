"""A statistic over the program's own spans named ``span`` that end inside
the window (``deepspeed_tpu.telemetry.get_tracer()``'s ring): of their
duration in milliseconds (``value`` absent or "dur_ms") or of the attribute
``value`` they carry.  ``stat`` is "mean", "sum", "count" or a percentile (a
number).  A span that lacks the attribute counts as 0.  No such span: no
value, except that "sum" and "count" are then 0 if the program records the
span ``beside`` (default: ``span`` itself) — a span the program writes only
when something goes wrong, such as ``serve/retire``, counts 0 in a run that
recorded its steps and nothing wrong."""
from lib import program_trace, stats


def read(run, args):
    spans = program_trace.ring(run)
    if spans is None:
        return None
    name = args["span"]
    beside = args.get("beside", name)
    if not any(sp[0] == beside for sp in spans):
        return None                 # the program has no such span
    lo, hi = run["window"]
    value = args.get("value", "dur_ms")
    values = [sp[2] * 1e3 if value == "dur_ms" else float(sp[3].get(value, 0))
              for sp in program_trace.in_window(spans, lo, hi, {name})]
    stat = args["stat"]
    if stat == "count":
        return float(len(values))
    if stat == "sum":
        return float(sum(values))
    if not values:
        return None
    if stat == "mean":
        return sum(values) / len(values)
    return stats.percentile(values, float(stat))
