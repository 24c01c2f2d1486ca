"""The whole step's share of the chip's bf16 peak in the latent-attention
training cell: FLOPs a trained token requires HERE (MLA projections,
attention at q/k 192 and v 128 causal, the dense and the shared SwiGLU, the
(token, choice) pairs this chip actually computed — from the program's
counter ``train/model_state.moe_pairs_held_share`` — ``W_eh`` and both heads;
forward + backward, remat not counted) x tokens/s over chips x peak.  A
program without the counter: no value."""
from lib import flops_mla_train as flops
from lib.program_state import last_record_attr


def read(run, args):
    facts, z = run["facts"], run["sizes"]
    if not facts.get("tokens") or run["window_s"] <= 0 \
            or run["peaks"] is None:
        return None
    share = last_record_attr(run, "train/model_state",
                             "moe_pairs_held_share")
    if share is None:
        return None
    per_token = flops.train_flops_per_token(
        z, facts["seq_len"], z["num_experts_per_tok"] * share)
    rate = facts["tokens"] / run["window_s"]
    return per_token * rate / (facts["chips"] * run["peaks"].bf16_flops)
