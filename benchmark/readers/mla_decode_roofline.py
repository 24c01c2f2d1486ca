"""Share of its roofline the latent decode kernel reaches in the traced
slice: the least time one call can take (the larger of its bytes over the
chip's HBM bandwidth and its FLOPs over the bf16 peak; at 60 FLOP/byte the
bytes bound it on a v5e) over the kernel's device time per call.  Bytes and
FLOPs come from ``lib/flops_mla`` and the benchmark's own log of decode
windows (the summed contexts of the sequences in a window), one call being
one layer of one step."""
from lib import flops_mla, trace


def read(run, args):
    if run["trace"] is None or run["peaks"] is None:
        return None
    kernel = trace.kernel_seconds(run["trace"], args["pattern"])
    lo, hi = run["slice"]
    ctx = [c for t, _, c in run["samples"].get("decode_log", [])
           if lo <= t < hi]
    if kernel is None or not ctx or "kv_lora_rank" not in run["sizes"]:
        return None
    mean_ctx = sum(ctx) / len(ctx)
    least = max(
        flops_mla.mla_decode_bytes(run["sizes"], mean_ctx)
        / run["peaks"].hbm_bytes_per_s,
        flops_mla.mla_decode_flops(run["sizes"], mean_ctx)
        / run["peaks"].bf16_flops)
    return 100.0 * kernel["calls"] * least / kernel["seconds"]
