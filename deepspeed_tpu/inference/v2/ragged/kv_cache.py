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

A K/V row that carries an INDEX KEY (``KVCacheConfig.index_dim``: learned
sparse attention, ``models/serving.IndexKey``) gets a second array under the
same page ids, ``[total_pages, block_size // 2, 2 * index_dim]``: token ``t``
of a page is row ``t % (block_size // 2)``, values ``(t // (block_size // 2))
* index_dim`` onwards — two 64-value keys fill a row of 128 lanes, where one a
row would be stored in twice its bytes.  ``pages`` is then the PAIR (K/V
array, index array): what shares, frees or copies a block by id carries
both.  A pool without index keys is the one array it always was.

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
    #: values of the index key a token holds beside its row (0: none)
    index_dim: int = 0

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
        if c.index_dim:
            if c.block_size % 2:
                raise ValueError(
                    f"index keys are stored two a row: block_size "
                    f"{c.block_size} must be even")
            self.pages = (self.pages, jnp.zeros(
                (c.total_pages, c.block_size // 2, 2 * c.index_dim), c.dtype))

    def update(self, pages) -> None:
        self.pages = pages

    def arrays(self) -> tuple:
        """The pool's arrays: one, or (K/V rows, index keys)."""
        return self.pages if isinstance(self.pages, tuple) else (self.pages,)

    def mem_bytes(self) -> int:
        return sum(a.size * a.dtype.itemsize for a in self.arrays())
