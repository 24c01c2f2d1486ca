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
    body never sees a page table.  ``cache`` is page layer ``layer``; a
    body that owns SEVERAL page layers (two attention blocks in one layer:
    ``page_layer_count`` > ``num_layers``) takes each from the same handle,
    ``cache.at(page_layer)``, and the pool is threaded through all their
    appends.
``head(params, x, pick) -> logits``: the final norm, ``pick`` (the rows that
    need logits: each sequence's last, or all in a verify window), the head.

A K/V row may carry an INDEX KEY beside it (``KVRow.index``,
:class:`IndexKey`: learned sparse attention).  The body then appends
``cache.append(k, v, k_index)`` and attends ``cache.attend(q, q_index,
w_index, scale=...)``: the cache scores every cached index key of the
query's sequence, keeps the ``topk`` best and attends to those rows only.

A family whose layers (some of them) keep a per-sequence RECURRENT STATE
instead of cached rows says so with ``state`` (:class:`GatedDeltaState`): the
engine then holds a state pool beside the page pool, a sequence owns one slot
of it, and a body is called ``body(x, lp, layer, cache, ctx, state)``.
``state(state_layer, *inputs)`` runs the state kind's update for the new
tokens through every sequence's slot (``kernels/gdn_ops.gdn_mix``) and
returns its output; ``state_layer`` counts the state-holding layers only, as
``layer`` given to ``cache`` counts the page-owning ones (``page_layers``).
A body never sees a slot.  The state kind names its recurrence
(``recurrence``): ``"gated_delta"`` (:class:`GatedDeltaState`),
``"selective"`` (:class:`SelectiveScanState`, ``kernels/ssm_ops.ssm_mix``) or
``"ssd"`` (:class:`SSDState`, ``kernels/ssd_ops.ssd_mix``).

A family some of whose attention layers read only the last ``window`` tokens
says so with ``window`` (:class:`WindowRing`): those layers own NO page
layer; a sequence holds a ring of ``window`` rows of the family's row kind
in each of them, in its slot of the state pool, whatever its length.  A body
takes such a layer from the same handle, ``cache.window(window_layer)``,
which appends and attends like a page layer's view
(``kernels/window_ops``).

Layers may SHARE a page layer: a body that calls ``cache.at(page_layer)
.attend(q, ...)`` and appends nothing reads what another layer's body wrote
(cross-layer K/V sharing).  ``attend_pair(q1, q2, ...)`` of either view is
the DIFFERENTIAL read of a K/V row kind whose heads are pairs (``K`` row
``[k1 | k2]``, ``V`` row ``[v1 | v2]``): two score sets over one value pair,
each with its own softmax, returned apart.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterable, Optional, Tuple, Union


@dataclasses.dataclass(frozen=True)
class IndexKey:
    """What a cached token holds BESIDE its K/V row for a learned sparse-
    attention indexer: one key of ``dim`` values.  A query brings ``heads``
    index queries of ``dim`` and a weight a head; its score of cached token
    ``s`` is ``sum_j w[j] * relu(q[j] . key[s])`` in float32, and it attends
    to the ``topk`` cached tokens of largest score (all of them while there
    are no more; ties to the lower position).  The keys live in a second
    array of the page pool under the same block ids
    (``ragged/kv_cache.py``), so what shares, frees or copies a block
    carries both; what ships K/V rows WITHOUT them is refused by name:
    ``kv_ship``, the host tier, speculative verify windows."""

    dim: int
    heads: int
    topk: int


@dataclasses.dataclass(frozen=True)
class KVRow:
    """A cached token of one layer is K and V for ``num_kv_heads`` heads.

    How a token is STORED is the row kind's to say, and the page operations
    read it off the pool's shape (``kernels/ragged_ops``), so the model's
    mathematics never knows.  Three forms:

    - as it is: a page is ``[page_size, 2 * num_kv_heads, head_dim]``, K
      heads first.  Right wherever the combined 16-bit rows tile the chip's
      sublanes (2, 4, 8 or a multiple of 16 of them);
    - padded (:meth:`tiled`; ``stored_kv_heads``): a head count whose
      combined rows do not tile (30 heads: 60 rows) is stored in one that
      does (32), and the heads past the model's are zeros that no query
      head reads — and that every read streams;
    - heads along the lanes (:meth:`packed`; ``lane_heads``): ``lane_heads``
      heads lie side by side in one row, a page is ``[page_size, 2 *
      num_kv_heads / lane_heads, lane_heads * head_dim]`` (10 heads of 128:
      2 K rows and 2 V rows of 640, heads 0-4 in the first of each and 5-9
      in the second — a reshape of ``k [T, 10, 128]``), which tiles as
      stored and pads nothing: a token takes exactly ``read_values``
      values.

    ``read_values`` and ``attn_flops`` stay the model's: a padding is the
    program's cost."""

    num_kv_heads: int
    head_dim: int
    stored_kv_heads: Optional[int] = None
    #: an index key beside the K/V row (None: attention is dense)
    index: Optional[IndexKey] = None
    #: heads side by side along the lanes of one stored row
    lane_heads: int = 1
    latent = False

    def __post_init__(self):
        if self.stored_kv_heads is not None \
                and self.stored_kv_heads < self.num_kv_heads:
            raise ValueError(
                f"stored_kv_heads {self.stored_kv_heads} < num_kv_heads "
                f"{self.num_kv_heads}")
        if self.lane_heads > 1 and (
                self.stored != self.num_kv_heads or self.index is not None
                or self.num_kv_heads % self.lane_heads):
            raise ValueError(
                f"lane_heads {self.lane_heads}: the heads of a row must "
                f"divide num_kv_heads {self.num_kv_heads}, with no padded "
                f"head and no index key")

    @classmethod
    def tiled(cls, num_kv_heads: int, head_dim: int) -> "KVRow":
        """The row kind of a model with ``num_kv_heads`` heads, stored in
        :func:`tiling_kv_heads` of them."""
        return cls(num_kv_heads, head_dim, tiling_kv_heads(num_kv_heads))

    @classmethod
    def packed(cls, num_kv_heads: int, head_dim: int) -> "KVRow":
        """The row kind of a model with ``num_kv_heads`` heads, stored in
        its own bytes: as it is where the head count tiles, else with the
        fewest heads along the lanes whose row count does
        (:func:`tiling_kv_heads`; 10 heads: 5 a row, 2 + 2 rows)."""
        lanes = min(n for n in range(1, num_kv_heads + 1)
                    if num_kv_heads % n == 0
                    and tiling_kv_heads(num_kv_heads // n)
                    == num_kv_heads // n)
        return cls(num_kv_heads, head_dim, lane_heads=lanes)

    @property
    def stored(self) -> int:
        return self.stored_kv_heads or self.num_kv_heads

    @property
    def token_shape(self) -> Tuple[int, ...]:
        return (2 * self.stored // self.lane_heads,
                self.lane_heads * self.head_dim)

    @property
    def read_values(self) -> int:       # attention reads, a token a layer
        return 2 * self.num_kv_heads * self.head_dim

    @property
    def attn_flops(self) -> float:      # QK + PV a cached token a query head
        return 4.0 * self.head_dim


def tiling_kv_heads(num_kv_heads: int) -> int:
    """The least count >= ``num_kv_heads`` of 16-bit K (and as many V) rows
    a token that tile the sublanes of a page as they are: 1, 2, 4 or a
    multiple of 8 (a token's combined rows 2, 4, 8 or a multiple of 16 —
    what the chip's compiler lays out without padding and the decode
    kernel's pair load takes).  :meth:`KVRow.tiled` stores a token in that
    many heads; :meth:`KVRow.packed` lays heads along the lanes until the
    rows that are left are such a count."""
    for n in (1, 2, 4):
        if num_kv_heads <= n:
            return n
    return -(-num_kv_heads // 8) * 8


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
    index = None

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
class GatedDeltaState:
    """What a sequence owns in each of ``num_layers`` Gated DeltaNet layers:
    the delta-rule state ``[num_heads, key_dim, value_dim]`` (float32) and
    the causal convolution's last ``conv_kernel - 1`` inputs over its
    ``conv_channels`` (in the serving dtype).

    The state is STORED with ``lane_heads`` heads side by side along the
    minor axis (``arrays``): 1, the plain ``[H, dk, dv]``, where
    ``value_dim`` is whole 128-lane tiles; 2 (``state_layout`` ``"pairs"``)
    where a single head's values would be padded (192 -> 256 lanes, a third
    more bytes held and moved) and a pair's are whole tiles (384).
    ``kernels/gdn_ops`` reads the layout off the pool's shape.

    A state can be restored only
    at the token it was saved at, so what re-reads or ships cached tokens is
    refused by name: the prefix cache, speculative verify windows, the host
    tier, ``kv_ship`` (ROADMAP R5)."""

    num_layers: int
    num_heads: int          # value heads: one state a head
    num_key_heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int
    recurrence = "gated_delta"
    #: the delta rule's ``beta`` lies in (0, ``beta_max``): 1, or 2 where
    #: ``I - beta k k^T`` may have eigenvalue -1.  The kernels take ``beta``
    #: as it comes; the chunked form inverts ``I + A`` by squarings only
    #: where ``beta`` stays within 1 (``kernels/gdn_ops._chunk_update``)
    beta_max: float = 1.0

    @property
    def conv_channels(self) -> int:
        return 2 * self.num_key_heads * self.key_dim \
            + self.num_heads * self.value_dim

    @property
    def lane_heads(self) -> int:
        """Heads stored side by side in a row of the state."""
        if self.value_dim % 128 and self.num_heads % 2 == 0 \
                and (2 * self.value_dim) % 128 == 0:
            return 2
        return 1

    @property
    def state_layout(self) -> str:
        return "pairs" if self.lane_heads == 2 else "plain"

    def arrays(self, dtype) -> Tuple[Tuple[Tuple[int, ...], Any], ...]:
        """(shape, dtype) of what one slot holds in one layer."""
        import jax.numpy as jnp

        P = self.lane_heads
        return (((self.num_heads // P, self.key_dim, P * self.value_dim),
                 jnp.float32),
                ((self.conv_kernel - 1, self.conv_channels), dtype))

    def slot_bytes(self, dtype) -> int:
        """Bytes of the values a sequence owns, all state layers (what the
        device HOLDS for them, tile padding included, is the pool's to say:
        ``ragged/state_pool.StatePool.mem_bytes``)."""
        import math

        import jax.numpy as jnp

        return self.num_layers * sum(
            math.prod(shape) * jnp.dtype(dt).itemsize
            for shape, dt in self.arrays(dtype))


@dataclasses.dataclass(frozen=True)
class SelectiveScanState:
    """What a sequence owns in each of ``num_layers`` selective-scan
    (Mamba-1) layers: the state ``[state_dim, channels]`` (float32; every
    channel has ``state_dim`` values, each with its own decay) and the
    causal convolution's last ``conv_kernel - 1`` inputs over the channels
    (in the serving dtype).  The state is STORED channels-minor: ``[16,
    5120]`` float32 is whole (8, 128) tiles, ``[5120, 16]`` would be padded
    eightfold along the lanes.

    As a :class:`GatedDeltaState`, it can be restored only at the token it
    was saved at: the prefix cache, speculative verify windows, the host
    tier and ``kv_ship`` are refused by name (ROADMAP R5)."""

    num_layers: int
    channels: int
    state_dim: int
    conv_kernel: int
    recurrence = "selective"

    def arrays(self, dtype) -> Tuple[Tuple[Tuple[int, ...], Any], ...]:
        """(shape, dtype) of what one slot holds in one layer."""
        import jax.numpy as jnp

        return (((self.state_dim, self.channels), jnp.float32),
                ((self.conv_kernel - 1, self.channels), dtype))

    slot_bytes = GatedDeltaState.slot_bytes


@dataclasses.dataclass(frozen=True)
class SSDState:
    """What a sequence owns in each of ``num_layers`` Mamba-2 (state-space
    dual) layers: a matrix A HEAD, ``[heads, head_dim, state_dim]`` values in
    float32 — ONE scalar decay a head a token, and the input and output
    maps ``B`` / ``C`` ``[groups, state_dim]`` shared by the ``heads /
    groups`` heads of a group — and the causal convolution's last
    ``conv_kernel - 1`` inputs over ``x | B | C`` (in the serving dtype).

    The state is STORED with the state values along the sublanes and
    ``lane_heads`` heads side by side along the lanes (``arrays``:
    ``[heads / P, state_dim, P * head_dim]``): a head of 64 alone would be
    padded to 128 lanes, twice the bytes held and moved; two fill a tile,
    and a head's input ``x`` and output ``y`` are then ROWS of the update,
    ``B`` / ``C`` columns (``kernels/ssd_ops`` reads the layout off the
    pool's shape).  The heads of a stored row share a group.

    ``chunk``: tokens the chunked prefill form takes at a time (the
    published ``chunk_size``).  As the other state kinds, it can be restored
    only at the token it was saved at: the prefix cache, speculative verify
    windows, the host tier and ``kv_ship`` are refused by name (ROADMAP
    R5)."""

    num_layers: int
    heads: int
    head_dim: int
    state_dim: int
    groups: int
    conv_kernel: int
    chunk: int = 128
    recurrence = "ssd"

    def __post_init__(self):
        if self.heads % self.groups:
            raise ValueError(f"heads {self.heads} are not whole groups of "
                             f"{self.groups}")

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_channels(self) -> int:
        return self.inner + 2 * self.groups * self.state_dim

    @property
    def lane_heads(self) -> int:
        """Heads stored side by side in a row of the state: as many as fill
        a 128-lane tile, where a group's heads are whole such rows."""
        P = 128 // self.head_dim if 128 % self.head_dim == 0 else 1
        return P if P > 1 and (self.heads // self.groups) % P == 0 else 1

    def arrays(self, dtype) -> Tuple[Tuple[Tuple[int, ...], Any], ...]:
        """(shape, dtype) of what one slot holds in one layer."""
        import jax.numpy as jnp

        P = self.lane_heads
        return (((self.heads // P, self.state_dim, P * self.head_dim),
                 jnp.float32),
                ((self.conv_kernel - 1, self.conv_channels), dtype))

    slot_bytes = GatedDeltaState.slot_bytes


@dataclasses.dataclass(frozen=True)
class WindowRing:
    """``num_layers`` attention layers read the last ``window`` tokens only
    (a token attends itself and the ``window - 1`` before it).  A sequence
    holds, in each, a RING of ``window`` rows of the family's row kind in
    its state-pool slot: the row of position ``p`` lies at ``p % window``,
    so a new token's row replaces the one that just left the window and the
    ring never holds more, however long the sequence.  Valid only where
    attention is a function of the SET of rows (no positional term inside
    the scores): the ring forgets which row is which position.  What ships
    or re-reads cached tokens is refused as for a recurrent state: the ring
    at an earlier token is gone."""

    num_layers: int
    window: int
    #: rows of a page of the ring as the decode kernel walks it (the ring is
    #: ``window // page`` pages of its own; no table, no allocator)
    page: int = 64

    def __post_init__(self):
        if self.window % self.page:
            raise ValueError(f"window {self.window} is not whole pages of "
                             f"{self.page} rows")


@dataclasses.dataclass(frozen=True)
class _RingOf:
    """A :class:`WindowRing` of a row kind, as the state pool takes a slot
    holder: ``num_layers`` and ``arrays(dtype)``."""

    ring: WindowRing
    row: Any

    @property
    def num_layers(self) -> int:
        return self.ring.num_layers

    def arrays(self, dtype):
        return (((self.ring.window,) + self.row.token_shape, dtype),)


@dataclasses.dataclass(frozen=True)
class ExpertPairs:
    """What a routed family's step returns beside logits and pages: int32
    ``[num_experts]``, the (token, choice) pairs each expert computed, summed
    over the expert layers; one live token makes ``per_token`` of them a
    step (expert layers × choices).  ``elsewhere``: the ``num_experts``
    experts are a chip's share of an expert-parallel layer; the pairs routed
    to the experts of other chips are neither computed nor dropped here, and
    the vector has one more entry behind the experts' that counts them.
    ``identity``: the router also scores experts WITHOUT weights (zero-
    computation identity experts: the pair adds ``g·h``); their pairs cost
    no matmul row and are held on no chip, and one more entry, the last,
    counts them apart."""

    num_experts: int
    per_token: int
    elsewhere: bool = False
    identity: bool = False

    @property
    def size(self) -> int:
        return self.num_experts + int(self.elsewhere) + int(self.identity)


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
    #: per-sequence recurrent state of some layers (None: every layer caches
    #: rows); bodies then take a ``state`` handle after ``ctx``
    state: Union[GatedDeltaState, SelectiveScanState, SSDState, None] = None
    #: attention layers that read a bounded window and keep a ring of rows
    #: in the sequence's slot instead of pages (None: none)
    window: Optional[WindowRing] = None
    #: layer bodies that READ each page layer a forward (None: one each, the
    #: body that appends to it): ``(8,)`` where seven later layers attend
    #: the rows one layer wrote.  What the engine accounts, not what it runs
    page_readers: Optional[Tuple[int, ...]] = None
    #: page layers of the pool (None: one a layer, ``num_layers``): fewer
    #: where some layers keep state instead, more where a body owns several
    page_layer_count: Optional[int] = None

    @property
    def page_layers(self) -> int:
        return self.num_layers if self.page_layer_count is None \
            else self.page_layer_count

    @property
    def slot_kinds(self) -> Tuple[Any, ...]:
        """What a sequence's slot of the state pool holds, in the pool's
        order: the recurrent state's arrays, then the window ring."""
        kinds = () if self.state is None else (self.state,)
        if self.window is not None:
            kinds += (_RingOf(self.window, self.row),)
        return kinds
