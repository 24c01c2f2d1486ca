"""Host time inside the window spent in the benchmark's spans ``spans``,
less the time in ``minus`` (their children), as a share of the window."""


def read(run, args):
    lo, hi = run["window"]
    spans = run["spans"]
    inside = sum(spans.total(n, lo, hi) for n in args["spans"])
    if inside <= 0:
        return None
    inside -= sum(spans.total(n, lo, hi) for n in args.get("minus", []))
    return inside / (hi - lo)
