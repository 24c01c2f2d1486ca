"""Blocked (paged) KV cache on TPU HBM (reference: inference/v2/ragged/kv_cache.py:40).

Storage is ONE flat page pool shared by every layer:
``[num_layers * num_blocks + 1, block_size, 2 * kv_heads, head_dim]`` —
K heads at ``[..., :KV, :]``, V heads at ``[..., KV:, :]``.  Layer ``l``'s
view of logical page ``p`` is physical page ``l * num_blocks + p``, so a
per-layer page table is plain metadata arithmetic (``table + l * num_blocks``)
and the paged-attention kernel needs no in-kernel layer index.  One page
fetch carries K AND V for every kv head — a single contiguous DMA feeds all
heads' compute (see kernels/ragged_ops.py).  A latent pool
(``KVCacheConfig.latent_row``) keeps the same page arithmetic with pages of
``[block_size, latent_row]``.

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
    num_kv_heads: int
    head_dim: int
    dtype: object = jnp.bfloat16
    #: > 0: a LATENT pool (multi-head latent attention).  A token's cache is
    #: one row of ``latent_row`` values a layer, with no K/V pair and no
    #: heads (``num_kv_heads`` / ``head_dim`` are then unused); pages are
    #: ``[block_size, latent_row]`` (kernels/mla_ops.py).
    latent_row: int = 0

    @property
    def token_shape(self) -> tuple:
        """What one token holds in one layer's page."""
        if self.latent_row:
            return (self.latent_row,)
        return (2 * self.num_kv_heads, self.head_dim)

    @property
    def total_pages(self) -> int:
        """Physical pages including the trailing shared trash page."""
        return self.num_layers * self.num_blocks + 1

    @property
    def trash_page(self) -> int:
        """Physical index of the shared trash page."""
        return self.num_layers * self.num_blocks

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
