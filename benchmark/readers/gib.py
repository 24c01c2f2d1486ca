"""Peak device memory on the fullest chip, in GiB."""


def read(run, args):
    peak = run.get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
