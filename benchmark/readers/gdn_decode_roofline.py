"""Share of its roofline the Gated DeltaNet decode kernel reaches in the
traced slice: the least time one call can take (the larger of its bytes over
the chip's HBM bandwidth and its FLOPs over the bf16 peak; at under one FLOP
a byte the bytes bound it) over the kernel's device time per call.  Bytes
and FLOPs come from ``lib/flops_gdn`` and the benchmark's own log of decode
windows (the sequences in a window), one call being one layer of one step.
A program with no such kernel, or a configuration with no DeltaNet layer:
no value."""
from lib import flops_gdn, trace


def read(run, args):
    if run["trace"] is None or run["peaks"] is None:
        return None
    kernel = trace.kernel_seconds(run["trace"], args["pattern"])
    lo, hi = run["slice"]
    rows = [n for t, n, _ in run["samples"].get("decode_log", [])
            if lo <= t < hi]
    if kernel is None or not rows \
            or "linear_num_value_heads" not in run["sizes"]:
        return None
    mean_rows = sum(rows) / len(rows)
    least = max(
        flops_gdn.gdn_decode_bytes(run["sizes"], mean_rows)
        / run["peaks"].hbm_bytes_per_s,
        flops_gdn.gdn_decode_flops(run["sizes"], mean_rows)
        / run["peaks"].bf16_flops)
    return 100.0 * kernel["calls"] * least / kernel["seconds"]
