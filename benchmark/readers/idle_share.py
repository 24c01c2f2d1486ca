"""1 - union of device-operation intervals / traced slice."""
from lib import trace


def read(run, args):
    if run["trace"] is None:
        return None
    busy = trace.busy(run["trace"])
    if busy is None or busy["window_s"] <= 0:
        return None
    return 1.0 - busy["busy_s"] / busy["window_s"]
