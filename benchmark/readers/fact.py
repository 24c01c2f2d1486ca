"""A counter the generator reports, times ``scale``.  args: key, scale."""


def read(run, args):
    value = run["facts"].get(args["key"])
    return None if value is None else float(value) * args.get("scale", 1.0)
