"""Operations and bytes of the Gated DeltaNet one-token update (the
``gdn_decode`` kernel), from shapes.  As ``lib/flops.py``: what the ALGORITHM
needs — the rows a compiled bucket pads its batch to, which update the trash
slot, are the program's, and are not counted."""
from __future__ import annotations

from typing import Dict


def state_values(model: Dict) -> int:
    """Values of one sequence's delta-rule state in one layer:
    ``[value heads, key_dim, value_dim]``."""
    return model["linear_num_value_heads"] * model["linear_key_head_dim"] \
        * model["linear_value_head_dim"]


def gdn_decode_bytes(model: Dict, rows: float, state_itemsize: int = 4,
                     itemsize: int = 4) -> float:
    """HBM bytes ONE call (one layer, one step, ``rows`` live sequences) must
    move: every sequence's state read once and written once, its q, k, v and
    the two gates read, its output written."""
    heads = model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    vectors = heads * (2 * dk + 2 * dv + 2) * itemsize
    return float(rows) * (2 * state_values(model) * state_itemsize + vectors)


def gdn_decode_flops(model: Dict, rows: float) -> float:
    """FLOPs of the same call, a head: the decay (dk*dv), ``S^T k`` (2 dk*dv),
    the rank-one update (2 dk*dv), ``S^T q`` (2 dk*dv), and 3 dv for the
    delta."""
    heads = model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    return float(rows) * heads * (7.0 * dk * dv + 3.0 * dv)
