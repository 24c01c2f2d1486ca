"""Operations and bytes of learned sparse attention (an indexer that picks
``topk`` cached tokens a query), from shapes.  As ``lib/flops.py``: what the
ALGORITHM needs, whatever implements it — a query must read every cached
index key of its sequence once (its score of each is a function of the
query) and the K/V rows of the tokens it keeps; a score matrix written and
read back, a gathered copy of the rows, a page table, are the program's."""
from __future__ import annotations

from typing import Dict


def index_row_bytes(model: Dict, itemsize: int = 2) -> int:
    """Bytes of the index key one token holds in one layer."""
    return model["indexer_head_dim"] * itemsize


def kv_row_bytes(model: Dict, itemsize: int = 2) -> int:
    """Bytes of K and V of one token in one layer, all K/V heads."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] * itemsize


def selected(model: Dict, ctx: float) -> float:
    return min(ctx, model["indexer_topk"])


def sparse_query_bytes(model: Dict, ctx: float, itemsize: int = 2) -> float:
    """HBM bytes ONE query at a context of ``ctx`` cached tokens must move
    in ONE page layer: ``ctx`` index keys and ``min(ctx, topk)`` K/V rows."""
    return ctx * index_row_bytes(model, itemsize) \
        + selected(model, ctx) * kv_row_bytes(model, itemsize)


def sparse_bytes(model: Dict, tokens_scored: float, tokens_selected: float,
                 itemsize: int = 2) -> float:
    """The same summed over queries and layers, from the program's counters
    (``engine/window_account``: ``sparse_tokens_scored``, ``_selected``)."""
    return tokens_scored * index_row_bytes(model, itemsize) \
        + tokens_selected * kv_row_bytes(model, itemsize)


def index_flops(model: Dict, ctx: float) -> float:
    """FLOPs of one query's scores in one layer: per cached token and index
    head a dot of ``indexer_head_dim`` and the weighted sum."""
    return ctx * model["indexer_num_heads"] \
        * (2.0 * model["indexer_head_dim"] + 2.0)


def core_flops(model: Dict, ctx: float) -> float:
    """FLOPs of one query's attention over its set in one layer: per kept
    token and query head, QK and PV."""
    return selected(model, ctx) * model["num_attention_heads"] \
        * 4.0 * model["head_dim"]
