"""A percentile (or the mean) of per-request or per-step samples.
args: samples, q (a number, or "mean"), scale."""
from lib import stats


def read(run, args):
    values = run["samples"].get(args["samples"]) or []
    if not values:
        return None
    scale = args.get("scale", 1.0)
    if args["q"] == "mean":
        return sum(values) / len(values) * scale
    return stats.percentile(values, float(args["q"])) * scale
