"""Operations and bytes of the latent-attention TRAINING cell, from shapes.
Every function takes the configuration's sizes (the published keys, with
``num_hidden_layers`` the main model's layers held and ``n_routed_experts``
the experts held) and returns what the ALGORITHM needs: recomputation,
padding and the rows a grouped matmul skips do not count."""
from __future__ import annotations

from typing import Dict


def mla_params(z: Dict) -> int:
    """Weights of one layer's latent attention a token is multiplied by:
    ``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb``, ``W_o``."""
    d, heads = z["hidden_size"], z["num_attention_heads"]
    qk = z["qk_nope_head_dim"] + z["qk_rope_head_dim"]
    return (d * z["q_lora_rank"] + z["q_lora_rank"] * heads * qk
            + d * (z["kv_lora_rank"] + z["qk_rope_head_dim"])
            + z["kv_lora_rank"] * heads
            * (z["qk_nope_head_dim"] + z["v_head_dim"])
            + heads * z["v_head_dim"] * d)


def expert_params(z: Dict) -> int:
    """One expert's (or the shared expert's) SwiGLU."""
    return 3 * z["hidden_size"] * z["moe_intermediate_size"]


def layers_of(z: Dict):
    """(dense layers, expert layers of the main model, MTP modules)."""
    dense = min(z["first_k_dense_replace"], z["num_hidden_layers"])
    return dense, z["num_hidden_layers"] - dense, \
        z.get("num_nextn_predict_layers", 0)


def matmul_params_active(z: Dict, pairs_per_token: float) -> float:
    """Weights a token is multiplied by in one forward pass HERE:
    ``pairs_per_token`` is the (token, choice) pairs a token has computed on
    this chip per expert layer (8 x the share of the pairs whose expert is
    held: from the program's counter, not assumed).  The embedding is a
    lookup and is not counted; the head is used twice with an MTP module."""
    d = z["hidden_size"]
    dense, moe, mtp = layers_of(z)
    expert_layer = mla_params(z) + d * z["router_outputs"] \
        + expert_params(z) * z["n_shared_experts"] \
        + expert_params(z) * pairs_per_token
    return (dense * (mla_params(z) + 3 * d * z["intermediate_size"])
            + (moe + mtp) * expert_layer
            + mtp * 2 * d * d
            + (1 + mtp) * d * z["vocab_size"])


def attention_flops_per_token(z: Dict, seq_len: int) -> float:
    """Forward q·kᵀ (192 wide) and p·v (128 wide) of causal attention, all
    layers that attend: a token attends to (seq_len + 1) / 2 positions on
    average, 2 FLOPs a multiply-add."""
    dense, moe, mtp = layers_of(z)
    width = z["qk_nope_head_dim"] + z["qk_rope_head_dim"] + z["v_head_dim"]
    return (dense + moe + mtp) * 2.0 * z["num_attention_heads"] * width \
        * (seq_len + 1) / 2.0


def train_flops_per_token(z: Dict, seq_len: int,
                          pairs_per_token: float) -> float:
    """Forward + backward: 2 FLOPs a weight forward, twice that backward,
    plus attention; remat is the system's choice and is not counted."""
    return 3.0 * (2.0 * matmul_params_active(z, pairs_per_token)
                  + attention_flops_per_token(z, seq_len))


def flash_fwd_flops(z: Dict, batch: int, seq_len: int) -> float:
    """One layer's causal flash forward over [batch, seq_len]: q·kᵀ at the
    q/k width and p·v at v's."""
    width = z["qk_nope_head_dim"] + z["qk_rope_head_dim"] + z["v_head_dim"]
    return 2.0 * z["num_attention_heads"] * width * (seq_len + 1) / 2.0 \
        * batch * seq_len


def flash_bwd_flops(z: Dict, batch: int, seq_len: int) -> float:
    """The backward's five matmuls: s = q·kᵀ again, dq = ds·k, dk = dsᵀ·q
    (each at the q/k width) and dp = do·vᵀ, dv = pᵀ·do (each at v's)."""
    qk = z["qk_nope_head_dim"] + z["qk_rope_head_dim"]
    return 2.0 * z["num_attention_heads"] * (3 * qk + 2 * z["v_head_dim"]) \
        * (seq_len + 1) / 2.0 * batch * seq_len


def flash_bytes(z: Dict, batch: int, seq_len: int, itemsize: int = 2,
                backward: bool = False) -> float:
    """HBM bytes one layer's pass must move at least.  Forward: q and k
    (192 a head) and v (128) read, o (128) written.  Backward: q, k, v, o's
    cotangent read and dq, dk, dv written (the kernels read more: the dq and
    dkv kernels each read all four)."""
    qk = z["qk_nope_head_dim"] + z["qk_rope_head_dim"]
    dv = z["v_head_dim"]
    widths = 2 * qk + 2 * dv if not backward else 4 * qk + 3 * dv
    return float(batch * seq_len * z["num_attention_heads"] * widths
                 * itemsize)


def param_count(z: Dict) -> int:
    """All parameters held: embedding, head, every layer, the MTP module,
    norms."""
    d = z["hidden_size"]
    dense, moe, mtp = layers_of(z)
    norms = 2 * d + z["q_lora_rank"] + z["kv_lora_rank"]
    expert_layer = mla_params(z) + norms + d * z["router_outputs"] \
        + expert_params(z) * (z["n_shared_experts"] + z["n_routed_experts"])
    return (dense * (mla_params(z) + norms + 3 * d * z["intermediate_size"])
            + (moe + mtp) * expert_layer + mtp * (2 * d * d + 3 * d)
            + d + 2 * d * z["vocab_size"])
