"""Share of their roofline the flash-attention kernels reach in the traced
slice at q/k width 192 and v width 128: the least time the chip could take
for the calls made (the larger of FLOPs / peak FLOP/s and bytes / peak
bytes/s, per call, from ``lib/flops_mla_train``) over their device time.  A
forward call is one layer's causal attention of this device's micro-batch
(remat's second forward is a call like any other); a backward is counted
once per dq-kernel call, with the algorithm's five matmuls."""
from lib import flops_mla_train as flops
from lib import trace


def read(run, args):
    if run["trace"] is None or run["peaks"] is None:
        return None
    fwd = trace.kernel_seconds(run["trace"], args["fwd"])
    bwd = [trace.kernel_seconds(run["trace"], p) for p in args["bwd"]]
    if fwd is None or any(b is None for b in bwd):
        return None
    facts, z, peaks = run["facts"], run["sizes"], run["peaks"]
    rows = facts["global_batch"] // facts["chips"]
    seq = facts["seq_len"]
    t_fwd = max(flops.flash_fwd_flops(z, rows, seq) / peaks.bf16_flops,
                flops.flash_bytes(z, rows, seq) / peaks.hbm_bytes_per_s)
    t_bwd = max(flops.flash_bwd_flops(z, rows, seq) / peaks.bf16_flops,
                flops.flash_bytes(z, rows, seq, backward=True)
                / peaks.hbm_bytes_per_s)
    least = fwd["calls"] * t_fwd + bwd[0]["calls"] * t_bwd
    spent = fwd["seconds"] + sum(b["seconds"] for b in bwd)
    return 100.0 * least / spent if spent > 0 else None
