"""Learned sparse attention inside the paged cache: a K/V row that carries an
INDEX KEY (``models/serving.IndexKey``).  A query brings, beside ``q``, the
indexer's queries ``qi [Hi, di]`` and head weights ``w [Hi]``; its score of
cached token ``s`` of its own sequence is

    I[s] = sum_j w[j] * relu(qi[j] . key[s])                     (float32)

and it attends to the ``topk`` causal tokens of largest ``I`` — all of them
while there are no more, ties to the LOWER position — and to no other.

The pool is the pair ``(kv, ix)`` under one set of page ids
(``ragged/kv_cache.py``): ``kv [N, ps, 2·KV, hd]`` as every K/V pool, ``ix
[N, ps/2, 2·di]`` with token ``t`` of a page at row ``t % (ps/2)``, values
``(t // (ps/2))·di`` onwards: two 64-value keys fill a 128-lane row.  A
page of index keys is scored as it lies, against the block-diagonal ``[2·di,
2·Hi]`` of the query (``_index_scores``), so no key is moved within a row.

The four steps, each under its own name scope:

``attention/index_score``  every cached index key of the sequence.  Decode,
    on the chip: a Pallas walk (``index_score_paged``) over each sequence's
    OWN pages by its page table — a page is one contiguous copy into VMEM,
    a chunk of pages is scored while the next one lands, and only the
    float32 scores are written.  A sequence's chunk of prefill queries, and
    decode wherever the kernel does not serve (``_walk_serves``: off the
    chip, or a pool whose rows do not tile): XLA's gather of a ``[S, pages]``
    rectangle a pass (``_index_scores``), bounded by the longest REAL
    context of the batch;
``attention/index_select`` the exact set.  ``lax.top_k`` of 2,048 from 66k
    sorts the row; here the ``topk``-th largest score is found by a radix
    select on the order-preserving unsigned image of the float32 scores
    (``_kth_largest``: 32 / ``_RADIX_BITS`` counting passes), ties go to the
    lower position by a prefix count, and a decode query's set is laid out
    as ``topk`` ascending positions by two-level counting — a block's count,
    then a one-hot matmul that fetches the block's bits (``_compact``): no
    sort, no scatter, no element gather;
``attention/sparse_read``  decode: the chosen tokens' rows, one contiguous
    ``[2·KV, hd]`` row each, by page and offset;
``attention/sparse_core``  decode: softmax attention over the ``topk`` rows.
    Prefill (a chunk of queries, each with its own set): reading ``topk``
    rows a QUERY would move far more than the context; the sequence's pages
    are walked densely, a chunk a pass with a running softmax, and the set
    is a mask on the scores — the dense walk's arithmetic, exactly the
    set's result.

Which path a batch takes follows from its context lengths alone: while no
sequence of it holds more than ``topk`` tokens the set is the whole causal
context and the batch takes the K/V kernels that exist
(``ragged_ops.decode_attention`` / ``ragged_paged_attention``); otherwise
every sequence of it is scored and selected (for a short one the set comes
out as its whole context).  ``sparse_attend_dense`` is the oracle
(``attn_impl="gather"``): the padded context, ``lax.top_k``.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ....telemetry.trace import get_tracer
from .mla_ops import _dot_nt
from .ragged_ops import (_interpret, decode_attention, paged_kv_append,
                         ragged_paged_attention)

_RADIX_BITS = 4         # bits settled a counting pass of the radix select
_LANES = 128            # block of the two-level counts
_SCORE_PAGES = 64       # pages of index keys scored a pass
#: which axis of the score tile the cached tokens lie along, ``"lanes"`` or
#: ``"rows"``, for decode batches and for a sequence's chunk of queries
#: (read on the chip, PERF.md section 6, PR 46: a 20-wide decode call 877
#: against 1,001 us, a 512-query chunk 8.5 against 4.4 ms; since PR 47 the
#: chip's decode batches take ``index_score_paged``)
_SCORE_FORM = {"decode": "lanes", "chunk": "rows"}
_WALK_PAGES = 16        # pages of K/V rows a pass of the masked walk
_KERNEL_PAGES = 32      # pages of index keys a chunk of the score kernel
_MASKED = -1e30


def _cdiv(a, b):
    return -(-a // b)


# --------------------------------------------------------------------- #
# Append
# --------------------------------------------------------------------- #
def indexed_append(pools, k, v, ki, page_of_token, off_of_token,
                   replicate=None):
    """K/V rows as ``paged_kv_append``; index keys ``ki [T, di]`` into their
    half of a row of ``ix`` (a window scatter: ``di`` values at a lane
    offset, in place in a donated pool)."""
    if replicate is not None:
        raise NotImplementedError(
            "index keys beside the K/V rows (sparse attention): "
            "tensor-parallel params are not supported")
    kv, ix = pools
    kv = paged_kv_append(kv, k, v, page_of_token, off_of_token)
    half, di = ix.shape[1], ix.shape[2] // 2
    where = jnp.stack([page_of_token, off_of_token % half,
                       (off_of_token // half) * di], axis=1)
    ix = lax.scatter(
        ix, where.astype(jnp.int32), ki.astype(ix.dtype),
        lax.ScatterDimensionNumbers(
            update_window_dims=(1,), inserted_window_dims=(0, 1),
            scatter_dims_to_operand_dims=(0, 1, 2)),
        mode="promise_in_bounds")
    return kv, ix


# --------------------------------------------------------------------- #
# Score
# --------------------------------------------------------------------- #
def _query_blocks(qi, w, dtype):
    """``qi [..., Hi, di]``, ``w [..., Hi]`` → the block-diagonal ``[...,
    2·di, 2·Hi]`` a row of two keys is multiplied by (column ``h·Hi + j``:
    head ``j`` against the row's key ``h``) and the weights twice over."""
    qt = jnp.swapaxes(qi, -1, -2).astype(dtype)             # [..., di, Hi]
    zero = jnp.zeros_like(qt)
    q2 = jnp.concatenate([jnp.concatenate([qt, zero], -1),
                          jnp.concatenate([zero, qt], -1)], -2)
    return q2, jnp.concatenate([w, w], -1).astype(jnp.float32)


def _token_order(s):
    """``[..., P, half, 2]`` (page, row, key of the row) → ``[..., P·ps]``."""
    s = jnp.swapaxes(s, -1, -2)
    return s.reshape(s.shape[:-3] + (-1,))


def _index_scores(qi, w, ix, page_table, ctx_max):
    """``I`` of every query against its sequence's cached index keys.

    decode: ``qi [S, Hi, di]``, ``w [S, Hi]``, ``page_table [S, NB]`` → ``[S,
    C]``; a sequence's chunk of queries: ``qi [T, Hi, di]``, ``page_table
    [NB]`` → ``[T, C]``.  ``C`` is ``NB·ps`` rounded up to whole passes and
    whole 128-blocks; passes beyond ``ctx_max`` tokens are not made
    (zeros)."""
    per_seq = page_table.ndim == 2
    Hi = qi.shape[-2]
    half = ix.shape[1]
    ps = 2 * half
    NB = page_table.shape[-1]
    P = min(_SCORE_PAGES, NB)
    n_pass = _cdiv(NB, P)
    pt = jnp.pad(page_table, [(0, 0)] * (page_table.ndim - 1)
                 + [(0, n_pass * P - NB)])
    q2, w2 = _query_blocks(qi, w, ix.dtype)
    rows = qi.shape[0]

    def one(c, out):
        pids = lax.dynamic_slice_in_dim(pt, c * P, P, axis=-1)
        keys = ix[pids]                             # [(S,) P, half, 2·di]
        if _SCORE_FORM["decode" if per_seq else "chunk"] == "lanes":
            keys = keys.reshape(keys.shape[:-3] + (P * half, keys.shape[-1]))
            s = jnp.einsum("slj,snl->sjn" if per_seq else "tlj,nl->tjn",
                           q2, keys, preferred_element_type=jnp.float32)
            s = jax.nn.relu(s) * w2[:, :, None]
            s = s.reshape(rows, 2, Hi, P, half).sum(2)  # [rows, 2, P, half]
            s = jnp.swapaxes(s, 1, 2).reshape(rows, P * ps)
        else:
            s = jnp.einsum("sprl,slj->sprj" if per_seq else "prl,tlj->tprj",
                           keys, q2, preferred_element_type=jnp.float32)
            s = jax.nn.relu(s) * w2[:, None, None, :]
            s = _token_order(s.reshape(s.shape[:-1] + (2, Hi)).sum(-1))
        return lax.dynamic_update_slice_in_dim(out, s, c * P * ps, axis=1)

    # (whole blocks of the select's two-level counts)
    out = jnp.zeros((rows, _cdiv(n_pass * P * ps, _LANES) * _LANES),
                    jnp.float32)
    return lax.fori_loop(0, _cdiv(ctx_max, P * ps), one, out)


# --------------------------------------------------------------------- #
# Score, decode: a page walk (Pallas)
# --------------------------------------------------------------------- #
def _score_chunk(q, w, keys, Hi: int):
    """``q [2·Hi, 2·di]`` (the transposed block-diagonal: row ``h·Hi + j`` is
    head ``j`` against a row's key ``h``), ``w [2·Hi, 1]``, ``keys [rows,
    2·di]`` → ``[2, rows]`` float32: ``I`` of each row's two keys, the rows
    along the lanes.  The arithmetic of ``_index_scores``."""
    t = jnp.maximum(_dot_nt(q, keys), 0.0) * w
    return (jnp.sum(t[:Hi], axis=0, keepdims=True),
            jnp.sum(t[Hi:], axis=0, keepdims=True))


def _index_score_kernel(kvl_ref, pt_ref, q_ref, w_ref, ix_ref, o_ref,
                        bufs, sems, rows, carry, *, ps, P, NB, Hi):
    """One grid step = one decoding sequence: its indexer query against its
    own pages of index keys, ``P`` pages a chunk, two chunks' buffers; the
    walk and the ``carry`` of ``mla_ops._mla_decode_kernel`` (the first
    chunk of the NEXT sequence is started behind this one's last compute;
    the grid runs in order on one core: never ``parallel``).

    The copies' issue bounds the walk (~35 ns a page for a start and a wait
    behind their ``pl.when``), so a chunk that lies WHOLE below the context
    — all but a sequence's last — starts its pages unguarded and waits
    once: a buffer's copies signal one semaphore, and one descriptor of the
    buffer's size waits for all of their bytes.

    A page's rows hold tokens ``r`` and ``half + r``, so 128 rows (``G``
    pages) score into two 128-lane rows of the output, a page's two halves
    side by side.  The pages of a group are PLACED in the buffer so that no
    lane moves further than ``half``: the group's first ``G/2`` pages (the
    first output row) at even places, the others at odd ones; then the first
    row is key 0 where it lies and key 1 rolled up by ``half``, the second
    key 1 where it lies and key 0 rolled down."""
    s, S = pl.program_id(0), pl.num_programs(0)
    kvl = kvl_ref[s]
    half = ps // 2
    G = _LANES // half                  # pages a group: 128 rows of keys
    CH = P * ps
    R = CH // _LANES                    # output rows a chunk
    nch = _cdiv(kvl, CH)

    def place(p):
        g, i = divmod(p, G)
        return g * G + (2 * i if i < G // 2 else 2 * (i - G // 2) + 1)

    def whole(seq, c):
        return (c + 1) * CH <= kvl_ref[seq]

    def page_dma(seq, c, slot, p):
        pid = pt_ref[seq, jnp.minimum(c * P + p, NB - 1)]
        return pltpu.make_async_copy(
            ix_ref.at[pid], bufs.at[slot, place(p)], sems.at[slot])

    def pages_below_context(seq, c, do):
        for p in range(P):
            pl.when((c * P + p) * ps < kvl_ref[seq])(partial(do, p))

    def start_chunk(seq, c, slot):
        def start(p):
            page_dma(seq, c, slot, p).start()

        @pl.when(whole(seq, c))
        def _():
            for p in range(P):
                start(p)

        @pl.when(jnp.logical_not(whole(seq, c)))
        def _():
            pages_below_context(seq, c, start)

    def wait_chunk(seq, c, slot):
        @pl.when(whole(seq, c))
        def _():                        # the bytes of all P copies at once
            pltpu.make_async_copy(ix_ref.at[pl.ds(0, P)], bufs.at[slot],
                                  sems.at[slot]).wait()

        @pl.when(jnp.logical_not(whole(seq, c)))
        def _():
            pages_below_context(
                seq, c, lambda p: page_dma(seq, c, slot, p).wait())

    o_ref[...] = jnp.zeros_like(o_ref)      # chunks past the context: zeros

    @pl.when(s == 0)
    def _():
        carry[0] = 0
    fetched = carry[0] == 1
    slot0 = jnp.where(fetched, carry[1], 0)
    carry[0] = 0

    @pl.when(kvl > 0)
    def _walk():
        @pl.when(jnp.logical_not(fetched))
        def _():
            start_chunk(s, 0, slot0)

        lane = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
        even = (lane // half) % 2 == 0
        token = lax.broadcasted_iota(jnp.int32, (R, _LANES), 0) * _LANES \
            + lax.broadcasted_iota(jnp.int32, (R, _LANES), 1)

        def compute(c, slot):
            keys = bufs[slot].reshape(P * half, bufs.shape[-1])
            k0, k1 = _score_chunk(q_ref[0], w_ref[0], keys, Hi)
            for g in range(P // G):
                a = k0[:, g * _LANES:(g + 1) * _LANES]
                b = k1[:, g * _LANES:(g + 1) * _LANES]
                rows[2 * g:2 * g + 1] = jnp.where(
                    even, a, pltpu.roll(b, half, 1))
                rows[2 * g + 1:2 * g + 2] = jnp.where(
                    even, pltpu.roll(a, _LANES - half, 1), b)
            # pages past the context were never fetched, and a page's tail
            # holds whatever the pool does: zeros
            o_ref[0, pl.ds(pl.multiple_of(c * R, R), R), :] = jnp.where(
                c * CH + token < kvl, rows[...], 0.0)

        def body(state):
            c, slot = state

            @pl.when(c + 1 < nch)
            def _prefetch():
                start_chunk(s, c + 1, 1 - slot)

            # behind this sequence's LAST compute: the next grid step's
            # first chunk, into the buffer the walk has just left
            nxt = jnp.minimum(s + 1, S - 1)

            @pl.when((c + 1 == nch) & (s + 1 < S) & (kvl_ref[nxt] > 0))
            def _next_seq():
                start_chunk(nxt, 0, 1 - slot)
                carry[0] = 1
                carry[1] = 1 - slot

            wait_chunk(s, c, slot)
            compute(c, slot)
            return c + 1, 1 - slot

        lax.while_loop(lambda st: st[0] < nch, body, (jnp.int32(0), slot0))


def _walk_serves(ix, Hi: int) -> bool:
    """Whether the page walk is compiled for this pool: the chip, whole
    groups of 128 rows, whole sublane tiles of heads."""
    half = ix.shape[1]
    return not _interpret() and _LANES % (2 * half) == 0 and Hi % 8 == 0 \
        and ix.shape[2] % _LANES == 0


def index_score_paged(qi, w, ix, ctx_len, page_table):
    """``_index_scores``' decode form as a walk over each sequence's OWN
    pages: ``qi [S, Hi, di]``, ``w [S, Hi]``, ``ix`` left in HBM, ``ctx_len
    [S]`` (0: a padding row, no page visited), ``page_table [S, NB]`` → ``[S,
    C]`` float32 in token order, ``C`` whole chunks; zeros from ``ctx_len``
    on.  A chunk's pages are copied one ``make_async_copy`` each, the next
    chunk in flight while one is scored; only the scores leave VMEM."""
    S, Hi, _ = qi.shape
    _, half, L = ix.shape
    ps = 2 * half
    NB = page_table.shape[1]
    G = _LANES // half
    P = G * max(1, _KERNEL_PAGES // G)
    C = _cdiv(NB, P) * P * ps
    q2, w2 = _query_blocks(qi, w, ix.dtype)
    out = pl.pallas_call(
        partial(_index_score_kernel, ps=ps, P=P, NB=NB, Hi=Hi),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[
                pl.BlockSpec((1, 2 * Hi, L), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec((1, 2 * Hi, 1), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, C // _LANES, _LANES),
                                   lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, P, half, L), ix.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((P * ps // _LANES, _LANES), jnp.float32),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, C // _LANES, _LANES),
                                       jnp.float32),
        interpret=_interpret(),
        name="index_score_paged",
    )(ctx_len.astype(jnp.int32), page_table.astype(jnp.int32),
      jnp.swapaxes(q2, 1, 2), w2[:, :, None], ix)
    return out.reshape(S, C)


# --------------------------------------------------------------------- #
# Select
# --------------------------------------------------------------------- #
def _ordered(x, valid):
    """float32 → uint32 whose unsigned order is the floats' (``-0.0`` taken
    as ``0.0``: ``w·relu`` makes both); 0 where not ``valid``, below the
    image of every number."""
    x = jnp.where(x == 0, 0.0, x)
    b = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    u = jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))
    return jnp.where(valid, u, jnp.uint32(0))


def _kth_largest(u, k: int):
    """Per row of ``u [..., C]`` (uint32): the ``k``-th largest value and
    how many of the values EQUAL to it the ``k`` largest hold.  A radix
    select from the top bits down: a pass counts, among the values that
    share the bits settled so far, those whose next ``_RADIX_BITS`` bits are
    at least ``v`` for every ``v``, and settles those bits."""
    lead = u.shape[:-1]
    prefix = jnp.zeros(lead, jnp.uint32)
    need = jnp.full(lead, k, jnp.int32)
    bits, levels = _RADIX_BITS, 1 << _RADIX_BITS
    for shift in range(32 - bits, -1, -bits):
        if shift + bits < 32:
            settled = (u >> (shift + bits)) == \
                (prefix >> (shift + bits))[..., None]
        else:
            settled = jnp.ones(u.shape, jnp.bool_)
        digit = (u >> shift) & jnp.uint32(levels - 1)
        # at_least[v]: candidates whose digit is >= v (v = 1 .. levels-1)
        at_least = jnp.stack(
            [jnp.sum(settled & (digit >= v), axis=-1, dtype=jnp.int32)
             for v in range(1, levels)], axis=-1)           # decreasing in v
        pick = jnp.sum(at_least >= need[..., None], axis=-1)  # the digit
        above = jnp.take_along_axis(
            jnp.pad(at_least, [(0, 0)] * len(lead) + [(0, 1)]),
            pick[..., None], axis=-1)[..., 0]       # candidates of digit > pick
        need = need - above
        prefix = prefix | (pick.astype(jnp.uint32) << shift)
    return prefix, need


def _blocks(x):
    """``[..., C]`` → ``[..., C / 128, 128]``."""
    return x.reshape(x.shape[:-1] + (x.shape[-1] // _LANES, _LANES))


def _tri(inclusive: bool):
    r = jnp.arange(_LANES)
    return ((r[:, None] <= r[None, :]) if inclusive
            else (r[:, None] < r[None, :])).astype(jnp.bfloat16)


def _before(flags):
    """Per element of ``flags [..., C]`` (bool, ``C`` whole blocks): how many
    flags are set at lower indices of its row.  Within a block by a
    triangular matmul (0/1 in bfloat16, sums in float32: exact), the blocks
    by a cumulative sum of their counts."""
    f = _blocks(flags).astype(jnp.bfloat16)
    within = jnp.einsum("...bl,lm->...bm", f, _tri(False),
                        preferred_element_type=jnp.float32)
    count = jnp.sum(f, axis=-1, dtype=jnp.float32)
    earlier = jnp.cumsum(count, axis=-1) - count
    return (within + earlier[..., None]).reshape(flags.shape).astype(jnp.int32)


def _select(u, k: int):
    """The set as a mask ``[..., C]``: the ``k`` largest of each row of
    ``u``, ties to the lower index; never an element whose image is 0."""
    thr, need = _kth_largest(u, min(k, u.shape[-1]))
    thr, need = thr[..., None], need[..., None]
    tie = u == thr
    return (u != 0) & ((u > thr) | (tie & (_before(tie) < need)))


def _compact(chosen, k: int):
    """``chosen [..., C]`` (bool, at most ``k`` set a row, ``C`` whole
    blocks) → the set positions ascending ``[..., k]`` (int32; slots past
    the count hold an in-range position) and the count.  Slot ``j`` lies in
    the block whose running count first passes ``j``; the block's flags are
    fetched by a one-hot matmul, and the slot's place in the block is where
    the flags' running count reaches its rank."""
    C = chosen.shape[-1]
    f = _blocks(chosen).astype(jnp.bfloat16)                # [..., nb, 128]
    nb = f.shape[-2]
    upto = jnp.cumsum(jnp.sum(f, axis=-1, dtype=jnp.float32),
                      axis=-1).astype(jnp.int32)            # [..., nb]
    slot = jnp.arange(k, dtype=jnp.int32)
    done = upto[..., None, :] <= slot[:, None]              # [..., k, nb]
    block = jnp.sum(done, axis=-1, dtype=jnp.int32)
    earlier = jnp.max(jnp.where(done, upto[..., None, :], 0), axis=-1)
    onehot = (block[..., None] == jnp.arange(nb)).astype(jnp.bfloat16)
    flags = jnp.einsum("...kb,...bl->...kl", onehot, f,
                       preferred_element_type=jnp.float32)
    running = jnp.einsum("...kl,lm->...km", flags.astype(jnp.bfloat16),
                         _tri(True), preferred_element_type=jnp.float32)
    rank = (slot - earlier).astype(jnp.float32)
    place = jnp.sum(running <= rank[..., None], axis=-1, dtype=jnp.int32)
    pos = jnp.minimum(block * _LANES + place, C - 1)
    return pos, upto[..., -1]


# --------------------------------------------------------------------- #
# Decode: one query a sequence
# --------------------------------------------------------------------- #
def _layout_record(index, pools, score: str, select: str, read: str) -> None:
    """Trace time only: what the compiled sparse path is made of."""
    kv, ix = pools
    item = jnp.dtype(kv.dtype).itemsize
    get_tracer().record(
        "attn/sparse_layout", time.perf_counter(), 0.0, topk=index.topk,
        index_heads=index.heads, index_dim=index.dim,
        index_row_bytes=index.dim * jnp.dtype(ix.dtype).itemsize,
        kv_row_bytes=kv.shape[2] * kv.shape[3] * item, score=score,
        select=select, read=read, page_size=kv.shape[1])


def _stored(kv, num_kv_heads):
    """(heads stored, the model's) of a K/V pool."""
    return kv.shape[2] // 2, num_kv_heads or kv.shape[2] // 2


def _decode_sparse(q, qi, w, kv, ix, ctx_len, page_table, *, scale,
                   num_kv_heads, topk):
    S, H, hd = q.shape
    ps = kv.shape[1]
    stored, KV = _stored(kv, num_kv_heads)
    with jax.named_scope("attention/index_score"):
        if _walk_serves(ix, qi.shape[1]):
            scores = index_score_paged(qi, w, ix, ctx_len, page_table)
        else:
            scores = _index_scores(qi, w, ix, page_table, jnp.max(ctx_len))
    with jax.named_scope("attention/index_select"):
        C = scores.shape[-1]
        live = jnp.arange(C)[None, :] < ctx_len[:, None]
        chosen = _select(_ordered(scores, live), topk)
        pos, count = _compact(chosen, min(topk, C))             # [S, k]
        held = jnp.arange(pos.shape[-1])[None, :] < count[:, None]
    with jax.named_scope("attention/sparse_read"):
        page = jnp.take_along_axis(page_table, pos // ps, axis=1)
        rows = kv[page, pos % ps]                               # [S,k,2KV,hd]
    with jax.named_scope("attention/sparse_core"):
        k_sel = rows[:, :, :KV]
        # select before multiply: a slot past the count reads SOME row
        v_sel = jnp.where(held[:, :, None, None],
                          rows[:, :, stored:stored + KV], 0)
        qg = q.reshape(S, KV, H // KV, hd)
        s = jnp.einsum("sngd,sknd->sngk", qg, k_sel,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(held[:, None, None, :], s, _MASKED)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.where(held[:, None, None, :], jnp.exp(s - m), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("sngk,sknd->sngd", p.astype(v_sel.dtype), v_sel,
                       preferred_element_type=jnp.float32)
        o = o / jnp.where(l > 0, l, 1.0)
        return o.reshape(S, H, hd).astype(q.dtype)


def sparse_decode_attention(qs, pools, ctx_len, page_table, *, index,
                            num_kv_heads=None, scale=None,
                            pages_per_chunk: int = 8):
    """``qs = (q [S, H, hd], qi [S, Hi, di], w [S, Hi])``, one query a
    sequence → ``[S, H, hd]``.  Rows with ``ctx_len`` 0 are padding and
    yield zeros."""
    q, qi, w = qs
    kv, ix = pools
    if scale is None:
        scale = q.shape[-1] ** -0.5
    _layout_record(
        index, pools, select=f"radix{_RADIX_BITS}+onehot", read="xla_gather",
        score="pallas_walk" if _walk_serves(ix, qi.shape[1]) else "xla_gather")
    dense = partial(decode_attention, q, kv, ctx_len, page_table,
                    num_kv_heads=num_kv_heads, scale=scale,
                    pages_per_chunk=pages_per_chunk)
    if page_table.shape[1] * kv.shape[1] <= index.topk:
        return dense()          # no context this program serves selects
    sparse = partial(_decode_sparse, q, qi, w, kv, ix, ctx_len, page_table,
                     scale=scale, num_kv_heads=num_kv_heads, topk=index.topk)
    return lax.cond(jnp.max(ctx_len) > index.topk, sparse, dense)


# --------------------------------------------------------------------- #
# Prefill: a chunk of queries a sequence, each with its own set
# --------------------------------------------------------------------- #
def _masked_walk(q, kv, page_table, ctx, chosen, *, scale, num_kv_heads):
    """Softmax attention of ``q [T, H, hd]`` over one sequence's pages with
    ``chosen [T, C]`` as the mask: ``_WALK_PAGES`` pages a pass, a running
    softmax in float32, bounded by the real context."""
    T, H, hd = q.shape
    ps = kv.shape[1]
    stored, KV = _stored(kv, num_kv_heads)
    G = H // KV
    NB = page_table.shape[0]
    P = min(_WALK_PAGES, NB)
    pt = jnp.pad(page_table, (0, _cdiv(NB, P) * P - NB))
    CH = P * ps
    width = _cdiv(NB, P) * CH            # (the scores' width is the scorer's)
    chosen = jnp.pad(chosen, ((0, 0), (0, max(width - chosen.shape[1], 0)))
                     )[:, :width]
    qg = q.reshape(T, KV, G, hd)

    def one(c, carry):
        m, l, acc = carry
        rows = kv[lax.dynamic_slice_in_dim(pt, c * P, P)]   # [P,ps,2KV,hd]
        rows = rows.reshape(CH, rows.shape[2], hd)
        inside = (c * CH + jnp.arange(CH)) < ctx
        k_c = rows[:, :KV]
        v_c = jnp.where(inside[:, None, None], rows[:, stored:stored + KV], 0)
        keep = lax.dynamic_slice_in_dim(chosen, c * CH, CH,
                                        axis=1)[:, None, None, :]
        s = jnp.einsum("tngd,cnd->tngc", qg, k_c,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(keep, s, _MASKED)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(keep, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "tngc,cnd->tngd", p.astype(v_c.dtype), v_c,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((T, KV, G), _MASKED, jnp.float32)
    l0 = jnp.zeros((T, KV, G), jnp.float32)
    a0 = jnp.zeros((T, KV, G, hd), jnp.float32)
    _, l, acc = lax.fori_loop(0, _cdiv(ctx, CH), one, (m0, l0, a0))
    o = acc / jnp.where(l > 0, l, 1.0)[..., None]
    return o.reshape(T, H, hd)


def _ragged_sparse(q, qi, w, kv, ix, ctx_len, page_table, cu_q_lens, *,
                   scale, num_kv_heads, topk):
    """Sequence by sequence (those without queries are skipped): all ``T``
    flat tokens stand as the sequence's queries, those of other sequences
    with an empty causal range, and the sequence's own rows are kept."""
    T = q.shape[0]
    S = ctx_len.shape[0]
    tok = jnp.arange(T, dtype=jnp.int32)
    ps, NB = kv.shape[1], page_table.shape[1]
    widths = sorted({min(_cdiv(NB * part, 3 * _SCORE_PAGES) * _SCORE_PAGES,
                         NB) for part in (1, 2, 3)})

    def one(s, out):
        lo, hi = cu_q_lens[s], cu_q_lens[s + 1]

        def attend(out, pages):
            """Over the first ``pages`` of the sequence's table: enough for
            its context (the select's passes run over the width given)."""
            ctx = ctx_len[s]
            table = page_table[s, :pages]
            mine = (tok >= lo) & (tok < hi)
            # the query's own position: the last cached token it may see
            at = jnp.where(mine, ctx - (hi - lo) + (tok - lo), -1)
            with jax.named_scope("attention/index_score"):
                scores = _index_scores(qi, w, ix, table, ctx)
            with jax.named_scope("attention/index_select"):
                C = scores.shape[-1]
                causal = jnp.arange(C)[None, :] <= at[:, None]
                chosen = _select(_ordered(scores, causal), topk)
            with jax.named_scope("attention/sparse_core"):
                o = _masked_walk(q, kv, table, ctx, chosen,
                                 scale=scale, num_kv_heads=num_kv_heads)
            return jnp.where(mine[:, None, None], o.astype(out.dtype), out)

        # a third, two thirds or all of the table: a document's early chunks
        # do not pay the select over the longest context's width
        branch = sum((ctx_len[s] > n * ps).astype(jnp.int32)
                     for n in widths[:-1])
        return lax.cond(
            hi > lo, lambda out: lax.switch(
                branch, [partial(attend, pages=n) for n in widths], out),
            lambda out: out, out)

    return lax.fori_loop(0, S, one, jnp.zeros(q.shape, q.dtype))


def sparse_ragged_attention(qs, pools, ctx_len, page_table, cu_q_lens, *,
                            index, num_kv_heads=None, scale=None,
                            block_q: int = 128, pages_per_chunk: int = 8):
    """``qs = (q [T, H, hd], qi [T, Hi, di], w [T, Hi])`` on the flat token
    axis (sequence ``s`` owns tokens ``cu_q_lens[s]:cu_q_lens[s+1]``, the
    last of its ``ctx_len[s]`` cached ones) → ``[T, H, hd]``."""
    q, qi, w = qs
    kv, ix = pools
    if scale is None:
        scale = q.shape[-1] ** -0.5
    _layout_record(index, pools, score="xla_gather",
                   select=f"radix{_RADIX_BITS}+mask", read="masked_walk")
    dense = partial(ragged_paged_attention, q, kv, ctx_len, page_table,
                    cu_q_lens, num_kv_heads=num_kv_heads, scale=scale,
                    block_q=block_q, pages_per_chunk=pages_per_chunk)
    if page_table.shape[1] * kv.shape[1] <= index.topk:
        return dense()
    sparse = partial(_ragged_sparse, q, qi, w, kv, ix, ctx_len, page_table,
                     cu_q_lens, scale=scale, num_kv_heads=num_kv_heads,
                     topk=index.topk)
    return lax.cond(jnp.max(ctx_len) > index.topk, sparse, dense)


# --------------------------------------------------------------------- #
# The oracle
# --------------------------------------------------------------------- #
def sparse_attend_dense(q_seq, pools, page_table, q_len, ctx_len, *, index,
                        num_kv_heads=None, scale=None):
    """The padded context of every sequence, ``lax.top_k`` (equal scores:
    the lower index first), a dense masked softmax in float32.  ``q_seq =
    (q [S, mq, H, hd], qi [S, mq, Hi, di], w [S, mq, Hi])`` → ``[S, mq, H,
    hd]`` float32."""
    q, qi, w = q_seq
    kv, ix = pools
    S, mq, H, hd = q.shape
    if scale is None:
        scale = hd ** -0.5
    ps, half = kv.shape[1], ix.shape[1]
    stored, KV = _stored(kv, num_kv_heads)
    NB = page_table.shape[1]
    C = NB * ps
    pos = jnp.arange(C, dtype=jnp.int32)
    page = jnp.take_along_axis(page_table,
                               jnp.broadcast_to(pos // ps, (S, C)), axis=1)
    off = jnp.broadcast_to(pos % ps, (S, C))
    rows = kv[page, off].astype(jnp.float32)                # [S,C,2KV,hd]
    di = index.dim
    keys = ix[page, off % half].astype(jnp.float32)         # [S, C, 2·di]
    keys = jnp.where((off // half == 0)[..., None], keys[..., :di],
                     keys[..., di:])
    hi = jax.lax.Precision.HIGHEST
    I = jnp.einsum("sqjd,scd->sqjc", qi.astype(jnp.float32), keys,
                   precision=hi)
    I = jnp.sum(w.astype(jnp.float32)[..., None] * jax.nn.relu(I), axis=2)
    I = jnp.where(I == 0, 0.0, I)
    q_pos = (ctx_len - q_len)[:, None] + jnp.arange(mq)[None, :]
    causal = (pos[None, None, :] <= q_pos[:, :, None]) \
        & (pos[None, None, :] < ctx_len[:, None, None]) \
        & (jnp.arange(mq)[None, :] < q_len[:, None])[:, :, None]
    _, best = lax.top_k(jnp.where(causal, I, -jnp.inf), min(index.topk, C))
    chosen = jnp.zeros((S, mq, C), jnp.bool_).at[
        jnp.arange(S)[:, None, None], jnp.arange(mq)[None, :, None],
        best].set(True) & causal
    k_ctx = rows[:, :, :KV]
    v_ctx = jnp.where((pos[None, :] < ctx_len[:, None])[:, :, None, None],
                      rows[:, :, stored:stored + KV], 0)
    qg = q.astype(jnp.float32).reshape(S, mq, KV, H // KV, hd)
    s = jnp.einsum("sqngd,scnd->sqngc", qg, k_ctx, precision=hi) * scale
    s = jnp.where(chosen[:, :, None, None, :], s, _MASKED)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(chosen[:, :, None, None, :], p, 0.0)
    o = jnp.einsum("sqngc,scnd->sqngd", p, v_ctx, precision=hi)
    return o.reshape(S, mq, H, hd)
