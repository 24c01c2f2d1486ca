"""Share of its roofline the flash-attention kernels reach in the traced
slice: least time the chip could take for the calls made (the larger of
FLOPs / peak FLOP/s and bytes / peak bytes/s, per call) over their device
time.  A forward call is one layer's causal attention of this device's
micro-batch (remat's second forward is a call like any other); a backward
is counted once per dq-kernel call, with the algorithm's five matmuls
against the forward's two, whatever the kernels recompute."""
from lib import flops, trace


def read(run, args):
    if run["trace"] is None or run["peaks"] is None:
        return None
    fwd = trace.kernel_seconds(run["trace"], args["fwd"])
    bwd = [trace.kernel_seconds(run["trace"], p) for p in args["bwd"]]
    if fwd is None or any(b is None for b in bwd):
        return None
    facts, sizes, peaks = run["facts"], run["sizes"], run["peaks"]
    one_layer = dict(sizes, num_hidden_layers=1)
    rows = facts["global_batch"] // facts["chips"]
    seq = facts["seq_len"]
    f_fwd = flops.flash_fwd_flops(one_layer, rows, seq)
    b_fwd = flops.flash_bytes(one_layer, rows, seq)
    t_fwd = max(f_fwd / peaks.bf16_flops, b_fwd / peaks.hbm_bytes_per_s)
    t_bwd = max(flops.flash_bwd_flops(one_layer, rows, seq)
                / peaks.bf16_flops,
                flops.flash_bytes(one_layer, rows, seq, passes=2.0)
                / peaks.hbm_bytes_per_s)
    least = fwd["calls"] * t_fwd + bwd[0]["calls"] * t_bwd
    spent = fwd["seconds"] + sum(b["seconds"] for b in bwd)
    return 100.0 * least / spent if spent > 0 else None
