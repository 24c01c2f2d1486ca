"""Share of its roofline the differential decode reads reach in the traced
slice: the least time the K/V decode kernel's calls can take (the larger of
their bytes over the chip's HBM bandwidth and their FLOPs over the bf16
peak; at 3 FLOP a byte the bytes bound them) over the kernel's device time.
The rows the calls must read are the program's own counters on
``engine/window_account`` (``window_rows_read``: every rider's ``min(ctx,
window)`` a step, one window layer's; ``page_rows_read``: every rider's
context a step, one reader's), times the layers of each kind
(``lib/flops_phi4flash.layer_counts``); bytes a row from the published
widths (5,120 B: the 16-in-10 padding of the pool is not counted).  A
program without the counters, or a configuration that is not this family's:
no value."""
from lib import flops_phi4flash, program_trace, trace


def read(run, args):
    if run.get("trace") is None or run.get("peaks") is None \
            or "sliding_window" not in run["sizes"] \
            or "mb_per_layer" not in run["sizes"]:
        return None
    kernel = trace.kernel_seconds(run["trace"], args["pattern"])
    spans = program_trace.ring(run)
    if kernel is None or spans is None:
        return None
    lo, hi = run["slice"]
    # the windows drained inside the slice (one that began before it counts
    # whole, one that ends after it not at all: a window in ~40)
    accounts = [sp[3] for sp in program_trace.in_window(
        spans, lo, hi, {"engine/window_account"})
        if "window_rows_read" in sp[3] and "page_rows_read" in sp[3]]
    if not accounts or kernel["seconds"] <= 0:
        return None
    layers = flops_phi4flash.layer_counts(run["sizes"])
    rows = sum(layers["window"] * float(a["window_rows_read"])
               + (layers["full"] + layers["cross"])
               * float(a["page_rows_read"]) for a in accounts)
    least = max(
        flops_phi4flash.diff_decode_bytes(run["sizes"], rows)
        / run["peaks"].hbm_bytes_per_s,
        flops_phi4flash.diff_decode_flops(run["sizes"], rows)
        / run["peaks"].bf16_flops)
    return 100.0 * least / kernel["seconds"]
