"""Operations and bytes of Phi-4-mini-flash's decode reads (differential
attention over a window or over the whole context) and of its selective-scan
update, from shapes.  As ``lib/flops.py``: what the ALGORITHM needs, whatever
implements it — the heads a pool pads a token's rows to (10 pairs stored in
16), the rows a compiled bucket pads its batch to, a gathered copy of a
state, are the program's and are not counted, so they lower a share."""
from __future__ import annotations

from typing import Dict


def layer_counts(model: Dict) -> Dict[str, int]:
    """How many layers of each kind: ``M = L // 2``; scan layers at the even
    indices up to ``M``, window attention at the odd ones below it, the full
    layer at ``M + 1``, then (memory unit, cross attention) pairs."""
    L = model["num_hidden_layers"]
    M = L // 2
    cross = (L - M - 2) // 2
    return {"scan": M // 2 + 1, "window": M // 2, "full": 1,
            "memory": cross, "cross": cross}


def row_bytes(model: Dict, itemsize: int = 2) -> int:
    """Bytes of one cached token of one attention layer: ``KV / 2`` K rows
    ``[k1 | k2]`` and as many V rows ``[v1 | v2]`` of ``2 hd`` values."""
    hd = model["hidden_size"] // model["num_attention_heads"]
    return 2 * (model["num_key_value_heads"] // 2) * 2 * hd * itemsize


def diff_decode_bytes(model: Dict, rows_read: float, itemsize: int = 2
                      ) -> float:
    """HBM bytes of differential decode reads of ``rows_read`` cached tokens
    in all (a window layer reads ``min(ctx, window)`` a sequence a step, the
    full and the cross layers ``ctx``); the queries and outputs, 15 KB a
    sequence, are left out."""
    return float(rows_read) * row_bytes(model, itemsize)


def diff_decode_flops(model: Dict, rows_read: float) -> float:
    """FLOPs of the same reads: a cached token costs a pair of query heads
    ``(q1_i, q2_i)`` two scores of ``hd`` and two weighted sums of ``2 hd``."""
    hd = model["hidden_size"] // model["num_attention_heads"]
    return float(rows_read) * (model["num_attention_heads"] // 2) \
        * 2.0 * (2 * hd + 2 * 2 * hd)


def scan_state_values(model: Dict, expand: int = 2, d_state: int = 16) -> int:
    """Values of one sequence's selective state in one layer."""
    return expand * model["hidden_size"] * d_state


def ssm_decode_bytes(model: Dict, rows: float, state_itemsize: int = 4
                     ) -> float:
    """HBM bytes ONE scan layer's one-token update must move for ``rows``
    live sequences: every state read once and written once."""
    return float(rows) * 2 * scan_state_values(model) * state_itemsize


def ssm_decode_flops(model: Dict, rows: float) -> float:
    """FLOPs of the same update, a state value: the decay's product and
    exponential (2), the decay applied and the input added (3), the
    read-out (2)."""
    return float(rows) * 7.0 * scan_state_values(model)
