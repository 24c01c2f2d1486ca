"""The share of the program's own spans named ``span`` that end inside the
window (``deepspeed_tpu.telemetry.get_tracer()``'s ring) whose attribute
``attr`` is one of ``equals``: ``window_held_share.*`` = the share of the
window's ``serve/window`` spans whose ``held_by`` names that reason for
finding no window in flight.  No such span in the window, or none of them
carries the attribute (a program that does not say): no value."""
from lib import program_trace


def read(run, args):
    spans = program_trace.ring(run)
    if spans is None:
        return None
    lo, hi = run["window"]
    attr = args["attr"]
    found = [sp[3][attr] for sp in
             program_trace.in_window(spans, lo, hi, {args["span"]})
             if attr in sp[3]]
    if not found:
        return None
    equals = args["equals"]
    return sum(1 for value in found if value in equals) / len(found)
