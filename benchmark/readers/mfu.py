"""Model FLOP/s utilisation: FLOPs/token the model requires (forward +
backward, recompute not counted, active experts only) x tokens/s over
chips x the chip's published bf16 peak."""
from lib import flops


def read(run, args):
    facts = run["facts"]
    if not facts.get("tokens") or run["window_s"] <= 0 \
            or run["peaks"] is None:
        return None
    per_token = flops.train_flops_per_token(run["sizes"], facts["seq_len"])
    rate = facts["tokens"] / run["window_s"]
    return per_token * rate / (facts["chips"] * run["peaks"].bf16_flops)
