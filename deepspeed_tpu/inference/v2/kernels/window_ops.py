"""Attention over a bounded window of K/V rows kept in a per-sequence RING
(``models/serving.WindowRing``): a token attends itself and the ``W - 1``
tokens before it, and a sequence holds ``W`` rows a window layer in its slot
of the state pool, whatever its length.

The ring pool is ``[window_layers * slots + 1, W, *token_shape]`` (the last
row the trash ring of padded rows), a row laid out as a page's, in whichever
form the family's row kind stores a token (``KVRow.token_shape``: K heads
first, then V, ``stored`` of each, one head a row ``[2 * stored, hd]`` or
``lane_heads`` of them side by side; the decode kernel and the append read
the form off the pool, the ragged and oracle forms see a ring's rows through
``ragged_ops._token_heads``, one head a row either way).  The row of position
``p`` lies at ``p % W``: a new token's row replaces the one that has just
left the window.  Attention has no positional term inside the scores here,
so it is a function of the SET of rows and the ring need not know their
order; which rows are live is arithmetic on the sequence's length.

Three forms (:func:`window_attention` dispatches, as the page kinds do):

``decode``   one token a sequence: the row is written, then the ring IS the
             window, and it is read by the K/V decode kernel
             (``ragged_ops.decode_attention``) as ``W / page`` pages of the
             sequence's own with ``min(ctx, W)`` live rows — no table in
             memory, no lower bound in the kernel.
``ragged``   a ragged batch of chunks (SplitFuse), ``jax.numpy``: a loop
             over the batch's real (sequence, tile of :data:`TILE` queries)
             pairs; a tile attends the ring AS IT WAS before the batch (the
             tokens before the chunk) and the batch's own rows of its
             sequence, under the band ``0 <= q_pos - k_pos < W``; then the
             last ``W`` tokens of every chunk are written.  No row older
             than the window is read: the ring holds none.
``oracle``   token by token: write the row, attend the ring's live rows
             (``attn_impl="gather"``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from ....telemetry import get_tracer
from .ragged_ops import (_row_bytes, _token_heads, decode_attention,
                         paged_kv_append)

#: queries a step of the ragged form
TILE = 128
_NEG = -1e30


def _as_pages(ring, page: int):
    """The ring pool as the page pool the K/V operations take (a reshape of
    leading axes: no copy)."""
    return ring.reshape((-1, page) + ring.shape[2:])


def _write(ring, k, v, ring_row, pos, *, page: int):
    """Rows ``k``/``v`` [T, KV, hd] to ring ``ring_row`` [T] at ``pos % W``
    (a trash ``ring_row`` for tokens that write nothing)."""
    shape = ring.shape
    W = shape[1]
    at = pos % W
    return paged_kv_append(
        _as_pages(ring, page), k, v, ring_row * (W // page) + at // page,
        at % page).reshape(shape)


def _decode(q, k, v, ring, rows, ctx_len, *, page, pages_per_chunk, **attn):
    W = ring.shape[1]
    P = W // page
    ring = _write(ring, k, v, rows, jnp.maximum(ctx_len - 1, 0), page=page)
    table = rows[:, None] * P + jnp.arange(P, dtype=rows.dtype)[None, :]
    out = decode_attention(q, _as_pages(ring, page), jnp.minimum(ctx_len, W),
                           table.astype(jnp.int32),
                           pages_per_chunk=pages_per_chunk, **attn)
    return out, ring


def _ragged(q, k, v, ring, rows, *, cu_q_lens, q_len, ctx_len, num_kv_heads,
            scale, tile: int = TILE):
    T, H, hd = q.shape
    W = ring.shape[1]
    KV, G = num_kv_heads, q.shape[1] // num_kv_heads
    dtype = ring.dtype
    per_seq = -(-q_len // tile)                                   # [S]
    ends = jnp.cumsum(per_seq)
    first_pos = ctx_len - q_len             # a chunk's first position
    qp = jnp.pad(q, ((0, tile), (0, 0), (0, 0))).astype(dtype)
    kp = jnp.pad(k, ((W, tile), (0, 0), (0, 0))).astype(dtype)
    vp = jnp.pad(v, ((W, tile), (0, 0), (0, 0))).astype(dtype)
    lane = jnp.arange(tile)
    back = jnp.arange(W + tile)
    ring_at = jnp.arange(W)

    def one(c, out):
        s = jnp.searchsorted(ends, c, side="right").astype(jnp.int32)
        i0 = (c - (ends[s] - per_seq[s])) * tile   # offset in the chunk
        start = cu_q_lens[s] + i0                  # the tile's flat index
        live = lane < q_len[s] - i0
        qt = jax.lax.dynamic_slice_in_dim(qp, start, tile, axis=0)
        # the batch's own rows: flat indices start - W .. start + tile - 1
        kb = jax.lax.dynamic_slice_in_dim(kp, start, W + tile, axis=0)
        vb = jax.lax.dynamic_slice_in_dim(vp, start, W + tile, axis=0)
        k_idx, q_idx = start - W + back, start + lane
        mine = (k_idx >= cu_q_lens[s]) & (k_idx < cu_q_lens[s] + q_len[s])
        ok_b = mine[None, :] & (k_idx[None, :] <= q_idx[:, None]) \
            & (q_idx[:, None] - k_idx[None, :] < W)
        # the ring before the batch: slot r holds the last position
        # congruent to r below the chunk's first (none: below 0)
        p0 = first_pos[s]
        held = p0 - 1 - jnp.mod(p0 - 1 - ring_at, W)
        hist = _token_heads(ring[rows[s]], hd)     # [W, 2 stored, hd]
        stored = hist.shape[1] // 2
        q_pos = p0 + i0 + lane
        ok_h = (held >= 0)[None, :] & (q_pos[:, None] - held[None, :] < W)
        keys = jnp.concatenate([hist[:, :KV], kb], axis=0)
        # select before multiply: a row no query may read is zeros
        vals = jnp.concatenate([
            jnp.where((held >= 0)[:, None, None],
                      hist[:, stored:stored + KV], 0),
            jnp.where(mine[:, None, None], vb, 0)], axis=0)
        ok = jnp.concatenate([ok_h, ok_b], axis=1) & live[:, None]
        sc = jnp.einsum("qkgd,ckd->kgqc", qt.reshape(tile, KV, G, hd), keys,
                        preferred_element_type=jnp.float32) * scale
        pr = jax.nn.softmax(jnp.where(ok[None, None], sc, _NEG), axis=-1)
        o = jnp.einsum("kgqc,ckd->qkgd", pr.astype(dtype), vals,
                       preferred_element_type=jnp.float32
                       ).reshape(tile, H, hd)
        old = jax.lax.dynamic_slice_in_dim(out, start, tile, axis=0)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(live[:, None, None], o, old), start, axis=0)

    out = jax.lax.fori_loop(0, ends[-1], one,
                            jnp.zeros((T + tile, H, hd), jnp.float32))
    return out[:T]


def _oracle(q, k, v, ring, ring_row, pos, *, num_kv_heads, scale, page):
    T, H, hd = q.shape
    W = ring.shape[1]
    KV, G = num_kv_heads, H // num_kv_heads
    qf = q.astype(jnp.float32).reshape(T, KV, G, hd)

    def token(t, carry):
        out, ring = carry
        ring = _write(ring, k[t][None], v[t][None], ring_row[t][None],
                      pos[t][None], page=page)
        held = _token_heads(ring[ring_row[t]], hd).astype(jnp.float32)
        stored = held.shape[1] // 2
        ok = jnp.arange(W) <= pos[t]            # every slot once p >= W - 1
        sc = jnp.einsum("kgd,ckd->kgc", qf[t], held[:, :KV]) * scale
        pr = jax.nn.softmax(jnp.where(ok[None, None], sc, _NEG), axis=-1)
        o = jnp.einsum("kgc,ckd->kgd", pr, jnp.where(
            ok[:, None, None], held[:, stored:stored + KV], 0.0))
        return out.at[t].set(o.reshape(H, hd)), ring

    return jax.lax.fori_loop(
        0, T, token, (jnp.zeros((T, H, hd), jnp.float32), ring))


def window_attention(q, k, v, ring, rows, *, mode: str, batch, valid,
                     num_kv_heads: int, scale: float, page: int,
                     pages_per_chunk: int = 8):
    """Append the new tokens' rows to their sequences' rings and attend each
    query to its window.  ``q`` [T, H, hd], ``k``/``v`` [T, KV, hd],
    ``ring`` the pool, ``rows`` [S] absolute ring rows (a padded row's is the
    trash ring), ``mode`` ``"decode"`` (row-major one-token rows),
    ``"ragged"`` or ``"oracle"`` → (out [T, H, hd], ring)."""
    T = q.shape[0]
    q_len, ctx_len = batch["q_len"], batch["ctx_len"]
    trash = ring.shape[0] - 1
    # trace time only: the form a ring's rows are stored and read in
    get_tracer().record(
        "attn/window_layout", time.perf_counter(), 0.0, form=mode,
        window=ring.shape[1], page=page, kv_heads=num_kv_heads,
        dtype=jnp.dtype(ring.dtype).name,
        **_row_bytes(ring, num_kv_heads, q.shape[-1]))
    if mode == "decode":
        R = min(rows.shape[0], T)
        out, ring = _decode(q[:R], k[:R], v[:R], ring, rows[:R], ctx_len[:R],
                            page=page, pages_per_chunk=pages_per_chunk,
                            num_kv_heads=num_kv_heads, scale=scale)
        if T > R:
            out = jnp.pad(out, ((0, T - R), (0, 0), (0, 0)))
        return out, ring
    seq, pos = batch["seq_of_token"], batch["pos_of_token"]
    if mode == "oracle":
        return _oracle(q, k, v, ring, jnp.where(valid, rows[seq], trash),
                       pos, num_kv_heads=num_kv_heads, scale=scale,
                       page=page)
    out = _ragged(q, k, v, ring, rows, cu_q_lens=batch["cu_q_lens"],
                  q_len=q_len, ctx_len=ctx_len, num_kv_heads=num_kv_heads,
                  scale=scale)
    # after the read: the last W tokens of every chunk take their slots
    W = ring.shape[1]
    keeps = valid & (pos >= ctx_len[seq] - W)
    ring = _write(ring, k, v, jnp.where(keeps, rows[seq], trash), pos,
                  page=page)
    return out.astype(q.dtype), ring
