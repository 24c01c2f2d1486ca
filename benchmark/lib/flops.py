"""Operations and bytes from shapes.  Every function takes the model's
published sizes (the configuration file's keys) and returns what the
ALGORITHM needs — recomputation, padding and capacity slack do not count."""
from __future__ import annotations

from typing import Dict


def _sizes(model: Dict):
    d = model["hidden_size"]
    heads = model["num_attention_heads"]
    kv = model["num_key_value_heads"]
    hd = model.get("head_dim") or d // heads
    return (d, model["intermediate_size"], heads, kv, hd,
            model["num_hidden_layers"], model["vocab_size"],
            model.get("num_local_experts", 1),
            model.get("num_experts_per_tok", 1))


def matmul_params_active(model: Dict) -> int:
    """Weights a token is multiplied by in one forward pass: attention
    projections, the MLP of each ACTIVE expert, the router, the head.  The
    embedding is a lookup and is not counted."""
    d, f, heads, kv, hd, layers, vocab, experts, top_k = _sizes(model)
    attn = d * (heads + 2 * kv) * hd + heads * hd * d
    mlp = 3 * d * f * (top_k if experts > 1 else 1)
    router = d * experts if experts > 1 else 0
    return layers * (attn + mlp + router) + d * vocab


def attention_flops_per_token(model: Dict, seq_len: int) -> float:
    """Forward QK^T and PV FLOPs per token of causal attention over
    sequences of ``seq_len``: each token attends to (seq_len + 1) / 2
    positions on average; 2 matmuls x 2 FLOPs per multiply-add."""
    d, f, heads, kv, hd, layers, *_ = _sizes(model)
    return layers * 4.0 * heads * hd * (seq_len + 1) / 2.0


def train_flops_per_token(model: Dict, seq_len: int) -> float:
    """Forward + backward FLOPs one trained token requires: 2 FLOPs per
    active matmul weight forward, twice that backward, plus causal
    attention.  Recompute (remat) is work the system chose, not work the
    model requires, and is not counted."""
    fwd = 2.0 * matmul_params_active(model) \
        + attention_flops_per_token(model, seq_len)
    return 3.0 * fwd


def flash_fwd_flops(model: Dict, batch: int, seq_len: int) -> float:
    """Causal flash attention forward over [batch, seq_len], all layers."""
    return attention_flops_per_token(model, seq_len) * batch * seq_len


def flash_bwd_flops(model: Dict, batch: int, seq_len: int) -> float:
    """Backward needs dV, dP, dQ, dK (4 matmuls) and recomputes S = QK^T
    inside the kernel as the algorithm prescribes (5 in all, against the
    forward's 2)."""
    return 2.5 * flash_fwd_flops(model, batch, seq_len)


def flash_bytes(model: Dict, batch: int, seq_len: int, itemsize: int = 2,
                passes: float = 1.0) -> float:
    """HBM bytes a flash pass must move at least: Q, K, V read and O written
    once per layer (forward); ``passes`` scales it for the backward."""
    d, f, heads, kv, hd, layers, *_ = _sizes(model)
    per_layer = batch * seq_len * hd * itemsize * (2 * heads + 2 * kv)
    return passes * layers * per_layer


def kv_row_bytes(model: Dict, itemsize: int = 2) -> int:
    """Bytes of K and V one token holds in one layer."""
    d, f, heads, kv, hd, *_ = _sizes(model)
    return 2 * kv * hd * itemsize


def decode_attention_bytes(model: Dict, ctx_tokens_total: float,
                           itemsize: int = 2) -> float:
    """HBM bytes the decode attention kernel must read for ONE decode step
    in which the sequences' contexts sum to ``ctx_tokens_total``: every
    cached K and V row of every layer, once."""
    layers = model["num_hidden_layers"]
    return float(layers) * ctx_tokens_total * kv_row_bytes(model, itemsize)


def param_count(model: Dict) -> int:
    """All parameters: embedding, every expert, norms, head."""
    d, f, heads, kv, hd, layers, vocab, experts, top_k = _sizes(model)
    attn = d * (heads + 2 * kv) * hd + heads * hd * d
    mlp = 3 * d * f * experts
    router = d * experts if experts > 1 else 0
    tied = model.get("tie_word_embeddings", False)
    return layers * (attn + mlp + router + 2 * d) + d \
        + vocab * d * (1 if tied else 2)
