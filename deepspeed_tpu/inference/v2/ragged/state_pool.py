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

What the pool HOLDS is not what its values add up to: the device lays an
array out in tiles of its two minor axes (a float32 ``[96, 192]`` state as
``[96, 256]``, a bfloat16 ``[3, C]`` carry in tiles of more rows than 3), so
``mem_bytes`` asks the arrays (``on_device_size_in_bytes``) and
``pad_share`` says what share of that is padding.  How a state is stored is
the state kind's (``GatedDeltaState.arrays``).

A slot may hold SEVERAL kinds of thing, each over its own number of layers
(a selective-scan state in 9 layers and a window ring in 8:
``ServingFamily.slot_kinds``): the pool is then the kinds' arrays one after
the other, each ``[its layers * slots + 1, ...]``, under the one slot id.
"""
from __future__ import annotations

import jax.numpy as jnp


class StatePool:
    def __init__(self, kinds, slots: int, dtype=jnp.bfloat16):
        """``kinds``: what a slot holds, in the pool's order
        (``ServingFamily.slot_kinds``)."""
        self.kinds = tuple(kinds)
        self.slots = int(slots)
        self.arrays = tuple(
            jnp.zeros((k.num_layers * self.slots + 1,) + shape, dt)
            for k in self.kinds for shape, dt in k.arrays(dtype))
        #: bytes of the values, and bytes the device holds for them (shapes
        #: never change, so both are read once)
        self.value_bytes = sum(a.size * a.dtype.itemsize for a in self.arrays)
        self.held_bytes = sum(held_bytes(a) for a in self.arrays)

    @property
    def pad_slot(self) -> int:
        """Sentinel the batch wrapper gives padded sequence rows (any value
        >= slots routes to the trash row on device)."""
        return self.slots

    def update(self, arrays) -> None:
        self.arrays = tuple(arrays)

    def mem_bytes(self) -> int:
        """Bytes the device holds for the pool, tile padding included."""
        return self.held_bytes

    @property
    def pad_share(self) -> float:
        return 1.0 - self.value_bytes / self.held_bytes


def held_bytes(array) -> int:
    """What the device holds for ``array`` (its values' bytes where the
    backend does not say)."""
    try:
        return int(array.on_device_size_in_bytes())
    except Exception:  # noqa: BLE001 — a backend without the call
        return array.size * array.dtype.itemsize


def slot_held_bytes(kind, dtype=jnp.bfloat16) -> int:
    """Bytes the device holds for ONE slot of ``kind`` over its state
    layers, read from a one-row probe of each array (padding is of the two
    minor axes, so a pool's rows cost this each): what a caller sizing the
    pools BEFORE building them reckons with."""
    return kind.num_layers * sum(
        held_bytes(jnp.zeros((1,) + shape, dt))
        for shape, dt in kind.arrays(dtype))
