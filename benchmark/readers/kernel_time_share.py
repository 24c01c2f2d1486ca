"""Device time of the operations matching ``pattern`` (kernel name, name
scope or HLO name) as a share of the device's busy time in the slice."""
from lib import trace


def read(run, args):
    if run["trace"] is None:
        return None
    kernel = trace.kernel_seconds(run["trace"], args["pattern"])
    busy = trace.busy(run["trace"])
    if kernel is None or busy is None or busy["busy_s"] <= 0:
        return None
    return kernel["seconds"] / busy["busy_s"]
