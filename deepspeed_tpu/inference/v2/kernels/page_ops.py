"""What the paged forward asks of a cache kind, for each kind of cached row
(``models/serving.py``): append the new tokens' rows, and attend through the
decode kernel, the ragged prefill kernel, the verify-window kernel, or the
dense page-gather oracle.  ``model_runner._LayerCache`` dispatches among the
four and owns the page arithmetic: ``pages`` is always the FULL multi-layer
pool and ``page_table`` rows are ABSOLUTE physical page ids.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import gdn_ops, mla_ops, sparse_ops, ssd_ops, ssm_ops, window_ops
from .ragged_ops import (_stored_heads, _token_heads, decode_attention,
                         paged_kv_append, ragged_paged_attention,
                         verify_window_attention)


def _attend_gather(q_seq, kv_pages, page_table, q_len, ctx_len,
                   scale, alibi=None, alibi_scaled=False, num_kv_heads=None):
    """Dense page-gather reference attention (the numerics oracle).

    Gathers the full padded context per sequence straight from the page pool
    and runs masked softmax attention.  ``alibi`` ([H] slopes) adds the
    position bias (bloom semantics; the falcon ``alibi_scaled`` variant
    computes bf16(slope·pos) pre-scaling).

    q_seq: [S, mq, H, hd]; kv_pages: [NP_total, ps, 2KV, hd] (or several
    heads a row: ``ragged_ops._stored_heads``); page_table: [S, NB] →
    output [S, mq, H, hd] (f32).  ``num_kv_heads``: the model's, where the
    pool stores a token in more (None: the pool's).
    """
    H_model = q_seq.shape[2]
    _, ps, ckv, hd_k = kv_pages.shape
    q_seq, KV, alibi = _stored_heads(
        q_seq, kv_pages,
        num_kv_heads or ckv // 2 * (hd_k // q_seq.shape[-1]), alibi)
    S, mq, H, hd = q_seq.shape
    NB = page_table.shape[1]
    C = NB * ps
    ctx_pos = jnp.arange(C, dtype=jnp.int32)
    pg = jnp.take_along_axis(
        page_table, (ctx_pos // ps)[None, :].repeat(S, 0), axis=1)   # [S, C]
    off = jnp.broadcast_to((ctx_pos % ps)[None, :], (S, C))
    ctx = _token_heads(kv_pages[pg, off], hd)         # [S, C, 2KV, hd]
    k_ctx, v_ctx = ctx[..., :KV, :], ctx[..., KV:, :]
    # zero V at out-of-context columns: masked scores become -1e30 (so K
    # garbage can't leak) but probs*V still multiplies 0-weight columns —
    # and 0*NaN = NaN.  A sequence's UNUSED block-table slots are 0 and
    # alias page 0, so a NaN-poisoned page 0 would contaminate every
    # sequence through its padding columns without this (same hardening
    # the dense decode lowering already has).  Select-BEFORE-multiply is
    # the contract dstpu-check's masked-nan-propagation pass enforces.
    valid_col = ctx_pos[None, :] < ctx_len[:, None]   # [S, C]
    v_ctx = jnp.where(valid_col[:, :, None, None], v_ctx, 0)
    if KV != H:
        k_ctx = jnp.repeat(k_ctx, H // KV, axis=2)
        v_ctx = jnp.repeat(v_ctx, H // KV, axis=2)

    q_pos = ctx_len[:, None] - q_len[:, None] + jnp.arange(mq)[None, :]
    q_mask = jnp.arange(mq)[None, :] < q_len[:, None]
    attn_mask = (ctx_pos[None, None, :] <= q_pos[:, :, None]) & \
        (ctx_pos[None, None, :] < ctx_len[:, None, None]) & q_mask[:, :, None]

    scores = jnp.einsum("sqhd,schd->shqc", q_seq.astype(jnp.float32),
                        k_ctx.astype(jnp.float32)) * scale
    if alibi is not None:
        slopes = jnp.asarray(alibi, jnp.float32)              # [H]
        if alibi_scaled:
            bias = (slopes[:, None].astype(jnp.bfloat16) *
                    ctx_pos[None, :].astype(jnp.bfloat16)
                    ).astype(jnp.float32) * scale             # [H, C]
        else:
            bias = slopes[:, None] * ctx_pos[None, :].astype(jnp.float32)
        scores = scores + bias[None, :, None, :]
    scores = jnp.where(attn_mask[:, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("shqc,schd->sqhd", probs, v_ctx.astype(jnp.float32))
    return out if H == H_model else out[:, :, :H_model]


class PageOps(NamedTuple):
    """One cache kind.  ``attn`` is the attention's own arithmetic as the
    layer body gave it (``scale``; K/V also ``alibi``, ``alibi_scaled``)."""

    append: Callable    # (pages, *rows, page_of_token, off_of_token) → pages
    decode: Callable    # (q [S,H,d], pages, ctx_len, page_table, *,
    #                      pages_per_chunk, **attn)
    ragged: Callable    # (q [T,H,d], pages, ctx_len, page_table, cu_q_lens,
    #                      *, block_q, pages_per_chunk, **attn)
    verify: Optional[Callable]   # as ragged, for verify windows; None: none
    dense: Callable     # (q_seq [S,mq,H,d], pages, page_table, q_len,
    #                      ctx_len, **attn) → f32


def page_ops(row, replicate=None) -> PageOps:
    """The operations of the cache kind that holds ``row``.  ``replicate`` (a
    replicated NamedSharding) must be given when the parameters are
    tensor-parallel: see :func:`paged_kv_append`."""
    if row.latent:
        # pages [ps, width]: a body appends rows [T, width] and attends
        # absorbed queries [T, H, width] → [T, H, rank]
        if replicate is not None:
            raise NotImplementedError(
                "latent (MLA) pages: tensor-parallel params are not "
                "supported")
        kw = dict(rank=row.rank)
        return PageOps(
            append=mla_ops.latent_append,
            decode=partial(mla_ops.mla_decode_attention, **kw),
            ragged=lambda *a, block_q, **k: mla_ops.mla_ragged_prefill(
                *a, block_q=min(block_q, 16), **kw, **k),
            verify=None,
            dense=partial(mla_ops.mla_attend_dense, **kw))
    if row.index is not None:
        # the pair (K/V pages, index-key pages): a body appends (k, v, ki)
        # and attends (q, qi, w): score, select, read (kernels/sparse_ops)
        kw = dict(num_kv_heads=row.num_kv_heads, index=row.index)
        return PageOps(
            append=partial(sparse_ops.indexed_append, replicate=replicate),
            decode=partial(sparse_ops.sparse_decode_attention, **kw),
            ragged=partial(sparse_ops.sparse_ragged_attention, **kw),
            verify=None,
            dense=partial(sparse_ops.sparse_attend_dense, **kw))
    # pages [ps, *row.token_shape]: ``row.stored`` heads (the model's, or
    # more where the row kind pads them), one a row [2*stored, hd] or
    # ``row.lane_heads`` side by side [2*stored/HL, HL*hd] — the operations
    # read the form off the pool (``ragged_ops._stored_heads``); a body
    # appends (k, v) [T, KV, hd] and attends q [T, H, hd] → [T, H, hd]
    kw = dict(num_kv_heads=row.num_kv_heads)

    def ragged(q, *a, pages_per_chunk, **k):
        # the ragged kernel's VMEM estimate does not see what Mosaic keeps
        # on its stack (a chunk loaded whole and copied by the V select),
        # which grows with the query rows a kv head brings: from 64 padded
        # query heads of 128 up (16 stored kv heads under a group of 4) a
        # 32-token bucket of 4-page chunks ran out of scoped VMEM on the
        # chip (PR 55) where every bucket of 2-page chunks compiles; pools
        # of fewer padded heads keep their chunks
        if row.stored * (q.shape[1] // row.num_kv_heads) > 32:
            pages_per_chunk = min(pages_per_chunk, 2)
        return ragged_paged_attention(q, *a, pages_per_chunk=pages_per_chunk,
                                      **kw, **k)

    return PageOps(
        append=partial(paged_kv_append, replicate=replicate),
        decode=partial(decode_attention, **kw),
        ragged=ragged,
        verify=partial(verify_window_attention, **kw),
        dense=partial(_attend_gather, **kw))


def state_ops(state) -> Callable:
    """The update of the recurrent-state kind ``state`` (``models/
    serving.py``): ``(*inputs, pool, rows, mode=, batch=, valid=) → (out,
    pool)``, ``mode`` one of ``"decode"``, ``"ragged"``, ``"oracle"``
    (``model_runner._LayerState`` picks it as ``_LayerCache`` picks among a
    cache kind's operations).  The inputs are the recurrence's own, one of
    three kinds: the gated delta rule (``gdn_ops.gdn_mix``: a ``[dk, dv]``
    matrix a head, rank-one corrected), the selective scan (``ssm_ops.
    ssm_mix``: ``[N, C]``, a decay a value) and the state-space dual
    (``ssd_ops.ssd_mix``: a ``[hd, N]`` matrix a head, one decay a head)."""
    mix = {"gated_delta": gdn_ops.gdn_mix,
           "selective": ssm_ops.ssm_mix,
           "ssd": ssd_ops.ssd_mix}[state.recurrence]
    return partial(mix, kind=state)


def window_op(row, ring) -> Callable:
    """The windowed read of K/V rows ``row`` kept in a ring (``models/
    serving.WindowRing``): ``(q, k, v, ring_pool, rows, *, mode, batch,
    valid, scale, pages_per_chunk) → (out, ring_pool)``."""
    if row.latent or row.index is not None:
        raise NotImplementedError(
            f"a window ring of {type(row).__name__} rows"
            + (" with index keys" if not row.latent else ""))
    return partial(window_ops.window_attention,
                   num_kv_heads=row.num_kv_heads, page=ring.page)


def pair_queries(q1, q2, pairs: int):
    """The DIFFERENTIAL read as the K/V operations take it.  A cached K row
    of pair ``j`` is ``[k1_j | k2_j]`` and its V row ``[v1_j | v2_j]``, each
    ``hd`` wide; ``q1``, ``q2`` [T, Hp, hd / 2] are the two query sets,
    head ``i`` of either reading pair ``i // (Hp / pairs)``.  ``q1`` padded
    with zeros behind and ``q2`` in front score ``q1 . k1`` and ``q2 . k2``
    against the SAME row, so a pair's group of query heads is ``[q1 heads |
    q2 heads]``, each with its own softmax over the one value pair → q [T,
    2 Hp, hd] grouped by pair (:func:`pair_outputs` takes it apart)."""
    T, Hp, half = q1.shape

    def grouped(q, pad):
        return jnp.pad(q, ((0, 0), (0, 0), pad)).reshape(
            T, pairs, Hp // pairs, 2 * half)

    return jnp.concatenate([grouped(q1, (0, half)), grouped(q2, (half, 0))],
                           axis=2).reshape(T, 2 * Hp, 2 * half)


def pair_outputs(out, pairs: int):
    """[T, 2 Hp, hd] grouped by pair → (A1, A2), each [T, Hp, hd]."""
    T, H2, hd = out.shape
    out = out.reshape(T, pairs, 2, H2 // (2 * pairs), hd)
    return (out[:, :, 0].reshape(T, H2 // 2, hd),
            out[:, :, 1].reshape(T, H2 // 2, hd))
