"""Operations and bytes of latent (MLA) attention in the absorbed form, from
shapes.  As ``lib/flops.py``: what the ALGORITHM needs — the zero padding of
a cached row to whole lanes is the program's, and is not counted."""
from __future__ import annotations

from typing import Dict


def latent_row_values(model: Dict) -> int:
    """Values one token holds in one layer's cache: ``c_kv`` and the one
    ``k_rope`` all heads share."""
    return model["kv_lora_rank"] + model["qk_rope_head_dim"]


def mla_decode_bytes(model: Dict, ctx_tokens_total: float,
                     itemsize: int = 2) -> float:
    """HBM bytes the decode kernel must read in ONE call (one layer, one
    step) in which the sequences' contexts sum to ``ctx_tokens_total``:
    every cached latent row, once.  One row feeds all heads."""
    return float(ctx_tokens_total) * latent_row_values(model) * itemsize


def mla_decode_flops(model: Dict, ctx_tokens_total: float) -> float:
    """FLOPs of the same call: per cached token and head, the score against
    the whole row (``R + rd`` multiply-adds) and the weighted sum of its
    ``c_kv`` part (``R``)."""
    r = model["kv_lora_rank"]
    return float(ctx_tokens_total) * model["num_attention_heads"] \
        * 2.0 * (latent_row_values(model) + r)


def mla_prefill_flops(model: Dict, q_tokens: float,
                      mean_ctx_attended: float) -> float:
    """FLOPs of a prefill call (one layer): ``q_tokens`` query tokens, each
    attending ``mean_ctx_attended`` cached tokens on average."""
    return q_tokens * mla_decode_flops(model, mean_ctx_attended)
