"""Blocked (paged) KV cache on TPU HBM (reference: inference/v2/ragged/kv_cache.py:40).

Storage is ONE flat page pool shared by every layer:
``[num_layers * num_blocks + 1, block_size, 2 * kv_heads, head_dim]`` —
K heads at ``[..., :KV, :]``, V heads at ``[..., KV:, :]``.  Layer ``l``'s
view of logical page ``p`` is physical page ``l * num_blocks + p``, so a
per-layer page table is plain metadata arithmetic (``table + l * num_blocks``)
and the paged-attention kernel needs no in-kernel layer index.  One page
fetch carries K AND V for every kv head — a single contiguous DMA feeds all
heads' compute (see kernels/ragged_ops.py).  A latent pool
(``KVCacheConfig.token_shape`` of one axis) keeps the same page arithmetic
with pages of ``[block_size, width]``.

The FINAL page (index ``num_layers * num_blocks``) is a shared trash page
that padded tokens write into, keeping the append a single dense scatter
(no predication).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp


@dataclasses.dataclass
class KVCacheConfig:
    num_layers: int
    num_blocks: int              # logical pages per layer
    block_size: int              # tokens per page
    #: what one token holds in one layer's page, as the model's family says
    #: (models/serving.py): ``(2 * kv_heads, head_dim)`` for K/V rows, or
    #: ``(width,)`` for a LATENT pool (multi-head latent attention: one row
    #: a token, no K/V pair and no heads; kernels/mla_ops.py)
    token_shape: tuple
    dtype: object = jnp.bfloat16

    @property
    def total_pages(self) -> int:
        """Physical pages including the trailing shared trash page."""
        return self.num_layers * self.num_blocks + 1

    @property
    def pad_page_flag(self) -> int:
        """Layer-relative sentinel the batch wrapper marks padded tokens
        with (any value >= num_blocks routes to the trash page on device)."""
        return self.num_blocks


class BlockedKVCache:
    def __init__(self, config: KVCacheConfig):
        self.config = config
        c = config
        self.pages = jnp.zeros(
            (c.total_pages, c.block_size) + c.token_shape, c.dtype)

    def update(self, pages) -> None:
        self.pages = pages

    def mem_bytes(self) -> int:
        return self.pages.size * self.pages.dtype.itemsize
