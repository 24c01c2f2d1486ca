"""What a model tells the paged serving path about itself.

``inference/v2`` serves any model object whose ``serving_family()`` returns a
:class:`ServingFamily`; the runner (``model_runner.ragged_forward``) owns
everything about pages and composes the family's pieces around them.  Nothing
in ``models/`` imports from ``inference/``.  The pieces, all on the flat token
axis ``[T, ...]``:

``embed(params, ids, pos, valid) -> (x, ctx)``: the tokens and their
    absolute positions ``[T]``; ``valid()`` makes the ``[T]`` mask that is
    False on the batch's padding (a call: few families need it).  ``x`` is
    the residual carry; ``ctx`` is whatever the bodies need that is made
    once a forward (rotary tables, the mask).
``body(x, lp, layer, cache, ctx) -> x``, or ``(x, counts)`` (``counts`` may
    be None) where the family has ``counts``: one layer, ``lp`` its slice of
    the stack's parameters, ``layer`` its (traced) index in the model.
    ``cache(q, *rows, **attn)`` appends the new tokens' rows to the layer's
    pages and attends ``q [T, H, d]`` to every sequence's cached context →
    ``[T, H, d']``; ``cache.append`` / ``cache.attend`` are the halves, for
    a body that scopes them apart.  ``attn`` is the attention's own
    arithmetic (``scale``; K/V rows also ``alibi``, ``alibi_scaled``).  A
    body never sees a page table.
``head(params, x, pick) -> logits``: the final norm, ``pick`` (the rows that
    need logits: each sequence's last, or all in a verify window), the head.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterable, Optional, Tuple, Union


@dataclasses.dataclass(frozen=True)
class KVRow:
    """A cached token of one layer is K and V for ``num_kv_heads`` heads."""

    num_kv_heads: int
    head_dim: int
    latent = False

    @property
    def token_shape(self) -> Tuple[int, ...]:
        return (2 * self.num_kv_heads, self.head_dim)

    @property
    def read_values(self) -> int:       # attention reads, a token a layer
        return 2 * self.num_kv_heads * self.head_dim

    @property
    def attn_flops(self) -> float:      # QK + PV a cached token a query head
        return 4.0 * self.head_dim


@dataclasses.dataclass(frozen=True)
class LatentRow:
    """A cached token of one layer is one latent (MLA) row: ``rank`` values
    of compressed K/V, the shared rotary key up to ``dim``, zero padding up
    to ``width``.  No K/V pair and no heads, so what is built on K/V rows is
    refused by name: speculative verify windows, the host tier and
    ``kv_ship`` shipments, a pool replicated under tensor-parallel params."""

    width: int
    dim: int
    rank: int
    latent = True

    @property
    def token_shape(self) -> Tuple[int, ...]:
        return (self.width,)

    @property
    def read_values(self) -> int:
        return self.dim

    @property
    def attn_flops(self) -> float:
        return 2.0 * (self.dim + self.rank)     # the absorbed form


@dataclasses.dataclass(frozen=True)
class ExpertPairs:
    """What a routed family's step returns beside logits and pages: int32
    ``[num_experts]``, the (token, choice) pairs each expert computed, summed
    over the expert layers; one live token makes ``per_token`` of them a
    step (expert layers × choices)."""

    num_experts: int
    per_token: int


@dataclasses.dataclass(frozen=True)
class LayerStack:
    """Layers that are alike, scanned as one: their stacked parameters, their
    indices in the model, the body, the name scope of the scan (None: none)."""

    params: Any
    layers: range
    body: Callable
    scope: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class ServingFamily:
    num_layers: int
    num_heads: int
    row: Union[KVRow, LatentRow]
    embed: Callable[..., Tuple[Any, Any]]
    stacks: Callable[[Any], Iterable[LayerStack]]    # may yield lazily
    head: Callable[[Any, Any, Callable], Any]
    counts: Optional[ExpertPairs] = None
