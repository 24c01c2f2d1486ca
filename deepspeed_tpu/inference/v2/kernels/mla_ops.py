"""Pallas attention over a LATENT page pool (multi-head latent attention in
the absorbed form).

A page is ``[page_size, W]``: one row a token, ``W = latent_row`` lanes
holding ``c_kv`` (``R = kv_lora_rank`` values), the shared ``k_rope`` and
zero padding to whole 128-lane tiles — no K/V pair and no heads.  The pool
is ``[num_layers·pages + 1, page_size, W]`` with the same layer arithmetic
and trash page as the K/V pool (``ragged/kv_cache.py``).

The query arrives absorbed (``models/xing4.mla_absorb_query``): ``[.., H,
W]`` with ``q_nope·W_UKᵀ`` against ``c_kv``, ``q_rope`` against ``k_rope``
and zeros against the padding.  Every head attends the SAME row, so one page
fetch feeds all heads: ``scores = q̃·rowᵀ``, ``o = softmax(scores)·row[:R]``;
the caller goes back to head space through ``W_UV``.  Per cached token the
decode kernel reads ``2·W`` bytes and computes ``H·(W + R)·2`` FLOP: below
the v5e's ridge, so its roof is the HBM bandwidth.

Both kernels follow ``ragged_ops.py`` (flat-token grid, in-kernel context
walk, double-buffered page DMA steered by the scalar-prefetched page table);
operands go to the MXU in the pool's dtype, accumulation is float32.

Double-buffered page DMA: while a chunk of ``pages_per_chunk`` pages is
scored out of one VMEM buffer, the next chunk's copies land in the other.
The ragged kernel walks every sequence of a query block inside ONE grid
step, so its prefetch crosses sequences by itself.  The decode kernel runs
one grid step a sequence, and a step would begin with nothing in flight:
so the walk's LAST compute has the next sequence's first chunk started
behind it, into the buffer it has just left, and two SMEM words (``carry``:
a flag and that buffer's index) tell the next grid step to wait on those
copies and not to start its own.  A ``kv_lens == 0`` row is never handed a
chunk and hands none on.  This rests on the grid running in order on one
core (scratch and DMA semaphores persist from step to step), which is the
default and what ``ragged_ops._decode_paged_kernel`` relies on too: the
decode grid's axis is never ``parallel``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ragged_ops import _NEG_INF, _cdiv, _interpret

_VMEM_LIMIT = 64 * 1024 * 1024


def _dot_nt(a, b):
    """a [M, K] · b [N, K]ᵀ → [M, N] float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


# ===================================================================== #
# Decode: one query token a sequence
# ===================================================================== #
def _mla_decode_kernel(kvl_ref, pt_ref, q_ref, pages_ref, o_ref,
                       bufs, sems, acc, m_scr, l_scr, carry,
                       *, scale, ps, P, NB, R):
    """One grid step = one decoding sequence: its H absorbed queries
    against its latent pages, ``P`` pages a compute step.

    ``carry`` (two SMEM words) hands a sequence's FIRST chunk across grid
    steps, as ``ragged_ops._decode_paged_kernel`` does: ``carry[0] == 1``
    says the previous grid step already started THIS sequence's chunk 0,
    into buffer ``carry[1]``, behind its own last compute; the walk then
    starts in that buffer and waits on the very same copies (same page
    ids, same buffer slot, same semaphores).  Grid step 0 clears it, every
    step reads it and clears it.  A ``kv_lens == 0`` row (bucket padding)
    starts nothing and is handed nothing: it writes zeros, and the row
    after it fetches its own chunk 0.  Scratch and semaphores persist
    over grid steps only because the grid runs IN ORDER on one core: never
    mark its axis ``parallel``.
    """
    s, S = pl.program_id(0), pl.num_programs(0)
    kvl = kvl_ref[s]
    CH = P * ps
    nch = _cdiv(kvl, CH)
    H = q_ref.shape[1]

    def page_needed(seq, page_idx):
        return page_idx * ps < kvl_ref[seq]

    def chunk_dma(seq, c, slot, p):
        pid = pt_ref[seq, jnp.minimum(c * P + p, NB - 1)]
        return pltpu.make_async_copy(
            pages_ref.at[pid], bufs.at[slot, p], sems.at[slot, p])

    def start_chunk(seq, c, slot):
        for p in range(P):
            @pl.when(page_needed(seq, c * P + p))
            def _():
                chunk_dma(seq, c, slot, p).start()

    def wait_chunk(seq, c, slot):
        for p in range(P):
            @pl.when(page_needed(seq, c * P + p))
            def _():
                chunk_dma(seq, c, slot, p).wait()

    acc[:] = jnp.zeros_like(acc)
    m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)

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

        def compute(c, slot):
            k_pos = c * CH + jax.lax.broadcasted_iota(jnp.int32, (H, CH), 1)
            mask = k_pos < kvl
            col_ok = jax.lax.broadcasted_iota(
                jnp.int32, (CH, 1), 0) + c * CH < kvl
            # rows past the context were never fetched: zero them before
            # they meet a probability of 0 (0·NaN), the select-before-
            # multiply contract of ragged_ops
            rows = jnp.where(col_ok, bufs[slot].reshape(CH, -1), 0)
            s_mat = _dot_nt(q_ref[0], rows) * scale          # [H, CH]
            s_mat = jnp.where(mask, s_mat, _NEG_INF)
            m_prev = m_scr[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s_mat, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p_mat = jnp.exp(s_mat - m_new)
            l_scr[:] = jnp.broadcast_to(
                alpha * l_scr[:, :1] + jnp.sum(p_mat, axis=1, keepdims=True),
                l_scr.shape)
            acc[:] = acc[:] * alpha + jnp.dot(
                p_mat.astype(rows.dtype), rows[:, :R],
                preferred_element_type=jnp.float32)
            m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

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

        jax.lax.while_loop(lambda st: st[0] < nch, body,
                           (jnp.int32(0), slot0))

    l = l_scr[:, :1]
    o_ref[0] = (acc[:] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def mla_paged_decode(q, pages, kv_lens, page_table, *, rank: int,
                     scale: float, pages_per_chunk: int = 8,
                     interpret: Optional[bool] = None):
    """``q`` [S, H, W] absorbed queries (row s = sequence s's one token),
    ``pages`` [NP, ps, W], ``kv_lens`` [S] (0 = padding row → zeros),
    ``page_table`` [S, NB] absolute page ids → [S, H, rank]."""
    S, H, W = q.shape
    _, ps, W_p = pages.shape
    assert W == W_p, f"latent row mismatch {W} vs {W_p}"
    NB = page_table.shape[1]
    P = min(pages_per_chunk, NB)
    kernel = functools.partial(_mla_decode_kernel, scale=scale, ps=ps, P=P,
                               NB=NB, R=rank)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[
                pl.BlockSpec((1, H, W), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, rank), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, P, ps, W), pages.dtype),
                pltpu.SemaphoreType.DMA((2, P)),
                pltpu.VMEM((H, rank), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, H, rank), q.dtype),
        interpret=_interpret() if interpret is None else interpret,
        name="mla_paged_decode",
    )(kv_lens.astype(jnp.int32), page_table.astype(jnp.int32), q, pages)


# ===================================================================== #
# Ragged prefill: any mix of sequence chunks on the flat token axis
# ===================================================================== #
def _mla_ragged_kernel(kvl_ref, pt_ref, cu_ref, q_ref, pages_ref, o_ref,
                       bufs, sems, acc, m_scr, l_scr,
                       *, scale, ps, P, H, BQ, S, NB, R, use_refs=True):
    """One grid step = one BQ-token block of the flat query axis, all H
    heads of a token as H rows of one tile (``ragged_ops._ragged_paged_
    kernel`` with one shared latent "KV head")."""
    qb = pl.program_id(0)
    blk_start = qb * BQ
    blk_end = blk_start + BQ
    CH = P * ps
    rows = BQ * H

    if use_refs:
        def cu(i):
            return cu_ref[jnp.minimum(i, S)]

        def kvl_at(s):
            return kvl_ref[s]
    else:
        cu_v, kvl_v = cu_ref[...], kvl_ref[...]

        def cu(i):
            return cu_v[jnp.minimum(i, S)]

        def kvl_at(s):
            return kvl_v[s]

    def seq_valid(s):
        s_c = jnp.minimum(s, S - 1)
        return (s < S) & (cu(s_c + 1) > cu(s_c)) & (cu(s_c) < blk_end) & \
            (cu(s_c + 1) > blk_start)

    def next_valid(s):
        return jax.lax.while_loop(
            lambda t: (t < S) & (cu(jnp.minimum(t, S - 1)) < blk_end)
            & ~seq_valid(t),
            lambda t: t + 1, s)

    def eff_kvl(s):
        """Causal bound of this block's last row of sequence s: chunks past
        it are neither fetched nor computed."""
        s_c = jnp.minimum(s, S - 1)
        kvl = kvl_at(s_c)
        q1 = cu(s_c + 1)
        t_max = jnp.minimum(blk_end, q1) - 1
        return jnp.clip(kvl - q1 + t_max + 1, 0, kvl)

    def page_needed(s, page_idx):
        return page_idx * ps < eff_kvl(s)

    def chunk_dma(s, c, slot, p):
        pid = pt_ref[jnp.minimum(s, S - 1), jnp.minimum(c * P + p, NB - 1)]
        return pltpu.make_async_copy(
            pages_ref.at[pid], bufs.at[slot, p], sems.at[slot, p])

    def start_chunk(s, c, slot):
        for p in range(P):
            @pl.when(page_needed(s, c * P + p))
            def _():
                chunk_dma(s, c, slot, p).start()

    def wait_chunk(s, c, slot):
        for p in range(P):
            @pl.when(page_needed(s, c * P + p))
            def _():
                chunk_dma(s, c, slot, p).wait()

    acc[:] = jnp.zeros_like(acc)
    m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)

    s0 = next_valid(jnp.int32(0))

    @pl.when(seq_valid(s0))
    def _warmup():
        start_chunk(s0, 0, 0)

    def compute(s, c, slot):
        kvl = kvl_at(jnp.minimum(s, S - 1))
        q0 = cu(s)
        q1 = cu(s + 1)
        chunk_base = c * CH
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, CH), 0)
        t = blk_start + r // H
        k_pos = chunk_base + jax.lax.broadcasted_iota(
            jnp.int32, (rows, CH), 1)
        q_pos = kvl - (q1 - q0) + (t - q0)
        mask = (t >= q0) & (t < q1) & (k_pos <= q_pos) & (k_pos < kvl)
        # rows of other sequences keep their softmax state untouched (the
        # per-sequence NaN isolation of ragged_ops)
        row_ok = (t[:, :1] >= q0) & (t[:, :1] < q1)
        col_ok = jax.lax.broadcasted_iota(
            jnp.int32, (CH, 1), 0) + chunk_base < eff_kvl(s)
        kv = jnp.where(col_ok, bufs[slot].reshape(CH, -1), 0)
        s_mat = _dot_nt(q_ref[...].reshape(rows, -1), kv) * scale
        s_mat = jnp.where(mask, s_mat, _NEG_INF)
        m_prev = m_scr[:, :1]
        m_cand = jnp.maximum(m_prev, jnp.max(s_mat, axis=1, keepdims=True))
        m_new = jnp.where(row_ok, m_cand, m_prev)
        alpha = jnp.exp(m_prev - m_new)
        p_mat = jnp.exp(s_mat - m_new)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, :1] + jnp.where(
                row_ok, jnp.sum(p_mat, axis=1, keepdims=True), 0.0),
            l_scr.shape)
        acc[:] = acc[:] * alpha + jnp.where(
            row_ok, jnp.dot(p_mat.astype(kv.dtype), kv[:, :R],
                            preferred_element_type=jnp.float32), 0.0)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    def body(state):
        s, c, slot = state
        nch = _cdiv(eff_kvl(s), CH)
        s_next, c_next = jax.lax.cond(
            c + 1 < nch,
            lambda: (s, c + 1),
            lambda: (next_valid(s + 1), jnp.int32(0)))

        @pl.when(seq_valid(s_next))
        def _prefetch():
            start_chunk(s_next, c_next, 1 - slot)

        wait_chunk(s, c, slot)
        compute(s, c, slot)
        return s_next, c_next, 1 - slot

    jax.lax.while_loop(lambda st: seq_valid(st[0]), body,
                       (s0, jnp.int32(0), jnp.int32(0)))

    l = l_scr[:, :1]
    o = acc[:] / jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = o.reshape(BQ, H, R).astype(o_ref.dtype)


def mla_ragged_prefill(q, pages, kv_lens, page_table, cu_q_lens, *,
                       rank: int, scale: float, block_q: int = 16,
                       pages_per_chunk: int = 8,
                       interpret: Optional[bool] = None):
    """``q`` [T, H, W] absorbed queries, sequence-major on the flat token
    axis (sequence s at ``[cu_q_lens[s], cu_q_lens[s+1])``); the latent rows
    of the chunk itself are already in the pool → [T, H, rank]."""
    T, H, W = q.shape
    _, ps, W_p = pages.shape
    assert W == W_p, f"latent row mismatch {W} vs {W_p}"
    S, NB = page_table.shape
    assert cu_q_lens.shape == (S + 1,)
    BQ = max(8, min(block_q, T))
    T_pad = _cdiv(T, BQ) * BQ
    if T_pad != T:
        q = jnp.pad(q, ((0, T_pad - T), (0, 0), (0, 0)))
    P = min(pages_per_chunk, NB)
    interp = _interpret() if interpret is None else interpret
    kernel = functools.partial(
        _mla_ragged_kernel, scale=scale, ps=ps, P=P, H=H, BQ=BQ, S=S, NB=NB,
        R=rank, use_refs=not interp)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(T_pad // BQ,),
            in_specs=[
                pl.BlockSpec((BQ, H, W), lambda qb, *_: (qb, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((BQ, H, rank), lambda qb, *_: (qb, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, P, ps, W), pages.dtype),
                pltpu.SemaphoreType.DMA((2, P)),
                pltpu.VMEM((BQ * H, rank), jnp.float32),
                pltpu.VMEM((BQ * H, 128), jnp.float32),
                pltpu.VMEM((BQ * H, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((T_pad, H, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interp,
        name="mla_ragged_prefill",
    )(kv_lens.astype(jnp.int32), page_table.astype(jnp.int32),
      cu_q_lens.astype(jnp.int32), q, pages)
    return out[:T]


# ===================================================================== #
# Plain XLA forms (the numerics oracle, and the decode path off the TPU)
# ===================================================================== #
def mla_attend_dense(q_seq, pages, page_table, q_len, ctx_len, *, rank: int,
                     scale: float):
    """Dense page-gather attention in the absorbed form.  ``q_seq`` [S, mq,
    H, W]; row j of sequence s sits at absolute position ``ctx_len[s] -
    q_len[s] + j`` → [S, mq, H, rank] float32."""
    S, mq, H, W = q_seq.shape
    ps = pages.shape[1]
    C = page_table.shape[1] * ps
    ctx_pos = jnp.arange(C, dtype=jnp.int32)
    pg = jnp.take_along_axis(
        page_table, (ctx_pos // ps)[None, :].repeat(S, 0), axis=1)
    off = jnp.broadcast_to((ctx_pos % ps)[None, :], (S, C))
    rows = pages[pg, off]                                   # [S, C, W]
    valid = ctx_pos[None, :] < ctx_len[:, None]
    rows = jnp.where(valid[:, :, None], rows, 0).astype(jnp.float32)
    q_pos = ctx_len[:, None] - q_len[:, None] + jnp.arange(mq)[None, :]
    mask = (ctx_pos[None, None, :] <= q_pos[:, :, None]) \
        & valid[:, None, :] & (jnp.arange(mq)[None, :]
                               < q_len[:, None])[:, :, None]
    scores = jnp.einsum("sqhw,scw->sqhc", q_seq.astype(jnp.float32),
                        rows) * scale
    scores = jnp.where(mask[:, :, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.any(mask, axis=-1)[:, :, None, None], probs, 0.0)
    return jnp.einsum("sqhc,scr->sqhr", probs, rows[..., :rank])


def mla_decode_attention(q, pages, kv_lens, page_table, *, rank: int,
                         scale: float, pages_per_chunk: int = 8,
                         impl: Optional[str] = None):
    """Decode dispatch: the Pallas kernel on the TPU, the dense form
    elsewhere (as ``ragged_ops.decode_attention``)."""
    if impl is None:
        impl = "dense" if _interpret() else "pallas"
    if impl == "pallas":
        return mla_paged_decode(q, pages, kv_lens, page_table, rank=rank,
                                scale=scale, pages_per_chunk=pages_per_chunk)
    out = mla_attend_dense(q[:, None], pages, page_table,
                           jnp.minimum(kv_lens, 1), kv_lens, rank=rank,
                           scale=scale)
    return out[:, 0].astype(q.dtype)


def latent_append(pages, rows, page_of_token, off_of_token):
    """Scatter the new latent rows [T, W] into their pages (padded tokens
    target the trash page); in place on a donated / loop-carried pool."""
    return pages.at[page_of_token, off_of_token].set(rows.astype(pages.dtype))
