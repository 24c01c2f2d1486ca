"""Share of its roofline the Mamba-2 (state-space dual) decode kernel reaches
in the traced slice: the least time one call can take (the larger of its
bytes over the chip's HBM bandwidth and its FLOPs over the bf16 peak; at
0.6 FLOP a byte the bytes bound it) over the kernel's device time per call.
Bytes and FLOPs come from ``lib/flops_ssd`` and the benchmark's own log of
decode windows (the sequences in a window), one call being one layer of one
step; the convolution's carry moves in a kernel of its own and is counted in
neither.  A program with no such kernel, or a configuration with no Mamba-2
layer: no value."""
from lib import flops_ssd, trace


def read(run, args):
    if run["trace"] is None or run["peaks"] is None:
        return None
    kernel = trace.kernel_seconds(run["trace"], args["pattern"])
    lo, hi = run["slice"]
    rows = [n for t, n, _ in run["samples"].get("decode_log", [])
            if lo <= t < hi]
    if kernel is None or not rows or kernel["seconds"] <= 0 \
            or "mamba_num_heads" not in run["sizes"]:
        return None
    mean_rows = sum(rows) / len(rows)
    least = max(
        flops_ssd.ssd_decode_bytes(run["sizes"], mean_rows)
        / run["peaks"].hbm_bytes_per_s,
        flops_ssd.ssd_decode_flops(run["sizes"], mean_rows)
        / run["peaks"].bf16_flops)
    return 100.0 * kernel["calls"] * least / kernel["seconds"]
