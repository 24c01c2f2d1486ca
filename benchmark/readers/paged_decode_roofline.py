"""Share of the chip's HBM bandwidth the paged decode kernel reaches in the
traced slice: the K and V rows every call must read (one layer, the summed
contexts of the sequences in the window, from the benchmark's own log of
decode windows) over peak bytes/s, over the kernel's device time."""
from lib import flops, trace


def read(run, args):
    if run["trace"] is None or run["peaks"] is None:
        return None
    kernel = trace.kernel_seconds(run["trace"], args["pattern"])
    lo, hi = run["slice"]
    ctx = [c for t, _, c in run["samples"].get("decode_log", [])
           if lo <= t < hi]
    if kernel is None or not ctx:
        return None
    one_layer = dict(run["sizes"], num_hidden_layers=1)
    per_call = flops.decode_attention_bytes(one_layer, sum(ctx) / len(ctx))
    least = kernel["calls"] * per_call / run["peaks"].hbm_bytes_per_s
    return 100.0 * least / kernel["seconds"]
