"""Operations and bytes of the Mamba-2 (state-space dual) one-token update
(the ``ssd_decode`` kernel), from shapes.  As ``lib/flops.py``: what the
ALGORITHM needs — the rows a compiled bucket pads its batch to, which update
the trash slot, and a decay or a ``delta x`` broadcast along a head's lanes
before the kernel, are the program's, and are not counted."""
from __future__ import annotations

from typing import Dict


def state_values(model: Dict) -> int:
    """Values of one sequence's state in one layer: ``[mamba_num_heads,
    mamba_head_dim, ssm_state_size]``."""
    return model["mamba_num_heads"] * model["mamba_head_dim"] \
        * model["ssm_state_size"]


def conv_channels(model: Dict) -> int:
    """Channels of the causal convolution: ``x | B | C``."""
    return model["mamba_num_heads"] * model["mamba_head_dim"] \
        + 2 * model["n_groups"] * model["ssm_state_size"]


def ssd_decode_bytes(model: Dict, rows: float, state_itemsize: int = 4,
                     itemsize: int = 4) -> float:
    """HBM bytes ONE call (one layer, one step, ``rows`` live sequences) must
    move: every sequence's state read once and written once, its ``x``, the
    head's ``dt``, the groups' ``B`` and ``C`` read, its output written.
    NOT the convolution's carry (``conv_kernel - 1`` inputs over
    :func:`conv_channels`, read and written: 123 KB a sequence beside 8.4
    MB): the program runs the convolution step as a kernel of its own, whose
    time is not ``ssd_decode``'s."""
    heads, hd = model["mamba_num_heads"], model["mamba_head_dim"]
    vectors = (2 * heads * hd + heads
               + 2 * model["n_groups"] * model["ssm_state_size"]) * itemsize
    return float(rows) * (2 * state_values(model) * state_itemsize + vectors)


def ssd_decode_flops(model: Dict, rows: float) -> float:
    """FLOPs of the same call, a head of ``hd`` x ``N``: the decay (hd*N),
    the rank-one input (2 hd*N), the readout ``S C`` (2 hd*N), and 3 hd for
    ``delta x`` and the ``D`` skip."""
    heads, hd = model["mamba_num_heads"], model["mamba_head_dim"]
    return float(rows) * heads * (5.0 * hd * model["ssm_state_size"]
                                  + 3.0 * hd)
