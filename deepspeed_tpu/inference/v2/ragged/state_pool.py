"""Recurrent state beside the pages: one pool for the layers of a family
that keep a per-sequence state instead of cached rows (``models/serving.py``:
``ServingFamily.state``).

Storage is one array per thing a slot holds (for a Gated DeltaNet layer the
delta-rule state and the convolution's carry), each
``[state_layers * slots + 1, ...]``: state layer ``l``'s slot ``s`` is row
``l * slots + s`` (plain arithmetic on the batch's metadata, as a layer's
page table is), and the FINAL row is the trash slot that padded sequence
rows read and write.  A live sequence owns one slot; ``DSStateManager``
hands it out with the sequence's first pages and takes it back at flush.  A
slot is never cleared: the first chunk of a sequence (position 0) starts
from zeros ON THE DEVICE whatever the last owner left (``kernels/gdn_ops``).
"""
from __future__ import annotations

import jax.numpy as jnp


class StatePool:
    def __init__(self, kind, slots: int, dtype=jnp.bfloat16):
        self.kind = kind
        self.slots = int(slots)
        rows = kind.num_layers * self.slots + 1
        self.arrays = tuple(jnp.zeros((rows,) + shape, dt)
                            for shape, dt in kind.arrays(dtype))

    @property
    def pad_slot(self) -> int:
        """Sentinel the batch wrapper gives padded sequence rows (any value
        >= slots routes to the trash row on device)."""
        return self.slots

    def update(self, arrays) -> None:
        self.arrays = tuple(arrays)

    def mem_bytes(self) -> int:
        return sum(a.size * a.dtype.itemsize for a in self.arrays)
