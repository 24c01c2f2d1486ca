"""A count over the window's wall time.  args: key, per_chip (bool)."""


def read(run, args):
    count = run["facts"].get(args["key"])
    if count is None or run["window_s"] <= 0:
        return None
    chips = run["facts"].get("chips", 1) if args.get("per_chip") else 1
    return float(count) / run["window_s"] / chips
