"""Time a collective runs while no compute runs on that device (own time of
collective operations and of the waits for them), as a share of the traced
slice, mean over devices."""
from lib import trace


def read(run, args):
    if run["trace"] is None:
        return None
    out = trace.collective_exposed(run["trace"])
    if out is None or out["window_s"] <= 0:
        return None
    return out["exposed_s"] / out["window_s"]
