"""Device idle time of the traced slice (first device; slice as
``lib/trace.window_of``) that lies under the program's host spans named in
``spans`` — the innermost span covering an instant owns it — as a share of
the slice.  With ``"unowned": true``: the idle time under no ``serve/`` or
``engine/`` span at all (the client's time).  The program's spans are read
from the profile itself, where ``TraceAnnotation`` put them on the device's
clock.  args: spans | unowned."""
from lib import program_trace


def read(run, args):
    extra = program_trace.xplane(run)
    if extra is None or not extra["host"]:
        return None                 # not traced, or a program with no spans
    idle = program_trace.idle_by_span(run["trace"], extra["host"])
    if idle is None or idle["slice_ns"] <= 0:
        return None
    by = idle["by_span_ns"]
    if args.get("unowned"):
        return by.get("", 0.0) / idle["slice_ns"]
    return sum(by.get(name, 0.0) for name in args["spans"]) \
        / idle["slice_ns"]
