"""Pallas ragged/paged serving attention — the FastGen ``blocked_flash``
equivalent on TPU.

The context walk runs INSIDE the kernel (an earlier design walked
``max_blocks`` grid steps per (atom, kv-head) with one ``[rows, block_size]``
tile each, and grid-step overhead swamped decode):

  * the grid is ``(num_q_blocks,)`` over the FLAT token axis — no atom
    packing, no per-sequence padding; a 64-seq decode batch is ONE grid step.
  * each grid step walks its sequences' KV pages with a dynamic
    ``lax.while_loop`` bounded by each sequence's REAL context length
    (``kv_lens``), not the ``max_blocks`` compile-time budget.
  * pages are fetched by double-buffered manual DMA
    (``pltpu.make_async_copy`` steered by the scalar-prefetched page table),
    ``pages_per_chunk`` pages per compute step — wide
    ``[rows, pages·page_size]`` MXU tiles instead of one page-size sliver,
    with the next chunk's DMA in flight behind the current matmul.
  * K and V for ALL kv heads ride ONE page fetch: a page is stored
    ``[page_size, 2·KV, hd]`` (K heads first, V heads second), so one
    contiguous copy per page feeds every head's compute.

Reference analogues (cited for parity, re-designed for TPU):
  - ``deepspeed/inference/v2/kernels/ragged_ops/blocked_flash/`` — ragged
    flash attention over paged KV blocks.
  - ``deepspeed/inference/v2/kernels/ragged_ops/atom_builder/`` — REPLACED:
    the flat-token grid + in-kernel sequence walk makes host-side atom
    packing unnecessary (atoms bounded work per CTA; here the while-loop
    bounds work per sequence).
  - ``deepspeed/inference/v2/kernels/ragged_ops/linear_blocked_kv_rotary/``
    — KV append into paged blocks (here: a donated-buffer XLA scatter,
    which Mosaic/XLA already performs in place on TPU).

Multi-layer caches need NO in-kernel layer index: the cache is one
``[num_layers·pages + 1, page_size, 2·KV, hd]`` buffer and layer ``l``'s
page table is ``table + l·pages`` — plain metadata arithmetic outside the
kernel (the final page is the shared trash page padded tokens write into).

HBM traffic is O(tokens actually cached) and walk length O(real context),
making 32k+ contexts servable at decode cost, not prefill cost.

What the chip read (TPU v5e, 819 GB/s; PERF.md section 6, PR 29: the decode
kernel alone at the Mistral-7B serving shapes, 32/8 heads of 128, bf16 pool,
64-token pages, a layer's call).  With a head taken by a value slice of the
chunk (``kv[:, :, h, :]`` on ``[P, ps, 2KV, hd]``, the 16 combined heads
being the packed sublane axis) and float32 operands: 1,517 us for 64
sequences of 128-1,280 tokens (15% of the HBM roof), 1,786 us for 28 of
2-3k (21%).  The slice alone was two thirds of that (503 / 523 us with a
same-sized contiguous read in its place); the float32 casts and dots cost
nothing measurable, and bf16 operands WITH the slice were 1.6x slower
still.  So ``decode_paged_attention`` reads a chunk's heads in pairs with a
sublane-strided ref load of 32-bit words (``_decode_paged_kernel``), in the
pool's dtype, and hands each sequence's first chunk to the DMA engine
behind the previous sequence's last compute: 308 us (74%) and 428 us (86%),
which is what the same walk takes with its compute removed.  The ragged
(prefill / verify) kernel below still slices and casts per head.

PR 33 (PERF.md section 6; the kernel alone at Qwen3-Next's serving shape,
16/2 heads of 256, 64 sequences of 0.3-3.1k tokens): with 4 combined rows
a token the value slice was 86% of the kernel (2,425 us, 9% of the roof;
335 us with a contiguous read in its place, 264 us with no compute).  Few
kv heads never stood in the strided load's way: Mosaic lowers a sublane-
strided load whenever the BASE memref is one lane tile (128 lanes) wide,
whatever its sublane tiling, and refuses it on a 256-wide one.  So the
shape of the chunk buffer follows the lane tile, not the head count: a
page lands by ``hd // 128`` copies, one a 128-lane tile, and the pair
load reads each tile's buffer (283 us, 77%; at 128-wide heads one copy a
page as before).  ``_decode_head_load`` says which pools get it.

PR 37 (PERF.md section 6; the kernel alone at Olmo-Hybrid's serving shape,
128 sequences of 600-1,700 tokens, 30 multi-head-attention heads stored in
32, 1 MiB pages: 2,741 us at the roof on the model's bytes).  At ONE query
row a kv head the compute of a chunk, not the DMA, was what the walk
waited for: 5,209 us (52.6%) with 4,911 us of compute on a resident chunk
and 3,287 us (83.4%) of DMA starts and waits alone.  A pass is a serial
chain whose latencies are paid once whatever its rows, and a pair pass
filled 2 of the 8 sublanes of every score vreg, 16 passes a chunk.  So a
pass scores as many pairs as fill the tile (``_pairs_per_pass``: 4 at one
row a head, 1 from a group of 4 up, where the program is PR 29's): 4,030
us (68.0%) at the 4 pages a chunk the VMEM budget gave.  What was left was
a per-BYTE cost of the chunk's size: a pass costs ~0.15 us + 0.05 us a
64 KB pair-page at 1 or 2 pages a chunk and 0.085 us at 4 (the same passes
over half a 4-page chunk at a time get most of it back; the DMA alone
takes the same 3.29 ms at 1, 2, 4 and 8 pages).  Mistral's pool (8 pages
of 256 KB) and a ``KV`` 16 x group 2 pool (4 of 512 KB) agree: the best
chunk is 2 MiB a buffer, which ``decode_paged_attention`` now holds a chunk
to — 2 pages at Olmo's shape: 3,402 us, 80.6%, the DMA walk again.  (8
pairs a pass, two sublane tiles of rows, bought 1 point more and stay out.)
"""
from __future__ import annotations

import functools
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ....telemetry import get_tracer

_NEG_INF = -1e30
# The most a chunk (one of the two page buffers) may hold, in both kernels:
# the ragged kernel loads a chunk whole onto Mosaic's stack (PR 34), and the
# decode kernel's passes cost 1.7 x more a byte on a 4 MiB chunk than on a
# 2 MiB one, in every pool measured (PR 37; module docstring)
_CHUNK_LIMIT = 2 * 1024 * 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _cdiv(a, b):
    return (a + b - 1) // b


def _lane_heads(hd: int, kv_pages) -> int:
    """Heads side by side along the lanes of a stored row
    (``models/serving.KVRow.lane_heads``), read off the pool: its rows are
    ``lane_heads * hd`` wide where the queries' heads are ``hd``."""
    HL = kv_pages.shape[-1] // hd
    assert kv_pages.shape[-1] == HL * hd, \
        f"head_dim mismatch {hd} vs {kv_pages.shape[-1]}"
    return HL


def _token_heads(rows, hd: int):
    """Cached rows ``[..., 2 * stored / HL, HL * hd]`` as ``[..., 2 * stored,
    hd]``, K heads first (a row's heads are consecutive: a reshape; a pool
    of one head a row comes back as it came)."""
    if rows.shape[-1] == hd:
        return rows
    return rows.reshape(rows.shape[:-2] + (-1, hd))


def _stored_heads(q, kv_pages, num_kv_heads: int, alibi=None):
    """How many heads a pool STORES a token in, which its shape says
    (``models/serving.KVRow``): ``[pages, page_size, 2 * stored / HL, HL *
    hd]``, the K heads first, ``HL`` heads along the lanes of a row
    (:func:`_lane_heads`; 1 in most pools).  A pool may store MORE heads
    than the model has (``KVRow.tiled``): the queries then get zero heads
    behind their own (one group a padded kv head) and the operation runs on
    the stored count; its caller cuts the output back to the model's heads,
    so no padded head's output reaches the model, and a padded head's rows
    are zeros (:func:`paged_kv_append`).  A pool with heads along its lanes
    (``KVRow.packed``) stores the model's own count and pads nothing.  →
    (q, stored, alibi); a pool of the model's own count comes back as it
    came."""
    stored = kv_pages.shape[2] // 2 * _lane_heads(q.shape[-1], kv_pages)
    assert kv_pages.shape[2] % 2 == 0 and stored >= num_kv_heads, \
        f"kv_pages combined-head dim {kv_pages.shape[2]} is not 2 x " \
        f"(>= {num_kv_heads}) heads"
    if stored == num_kv_heads:
        return q, stored, alibi
    extra = (stored - num_kv_heads) * (q.shape[-2] // num_kv_heads)
    q = jnp.pad(q, [(0, 0)] * (q.ndim - 2) + [(0, extra), (0, 0)])
    if alibi is not None:
        alibi = tuple(alibi) + (0.0,) * extra
    return q, stored, alibi


def _row_bytes(kv_pages, num_kv_heads: int, hd: int) -> dict:
    """What a layout record says of the stored form: the bytes a token
    takes in the pool and the bytes of it the model reads (equal but for a
    padded head count)."""
    itemsize = jnp.dtype(kv_pages.dtype).itemsize
    return dict(row_bytes=math.prod(kv_pages.shape[2:]) * itemsize,
                read_bytes=2 * num_kv_heads * hd * itemsize)


def _ragged_paged_kernel(kvl_ref, pt_ref, cu_ref,        # scalar prefetch
                         q_ref, pages_ref, o_ref,        # VMEM block / HBM
                         kv_bufs, sems, acc, m_scr, l_scr,
                         *, scale, ps, P, KV, G, BQ, S, NB,
                         alibi, alibi_scaled, use_refs=True, HL=1):
    """One grid step = one BQ-token block of the flat query axis.

    Walks the sequences whose tokens fall in this block; per sequence,
    walks its context in chunks of P pages with double-buffered DMA.
    Online-softmax state lives in VMEM scratch per (kv head, query row).

    ``use_refs=False`` (interpret mode) hoists the scalar-prefetched
    metadata into values once up front: jax 0.4.x cannot discharge a
    while-loop/cond whose predicate reads a Ref, so the CPU interpreter
    needs every control-flow decision made on VALUES.  On TPU the per-
    element SMEM reads stay (whole-array SMEM loads are not a Mosaic
    vector op).

    ``HL`` heads lie along the lanes of a stored row (``_lane_heads``):
    combined head ``h`` is row ``h // HL``, lanes ``h % HL`` of ``hd``.
    """
    qb = pl.program_id(0)
    blk_start = qb * BQ
    blk_end = blk_start + BQ
    CH = P * ps                      # context tokens per compute chunk
    rows = BQ * G

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
        """Sequence s exists, has query tokens, and overlaps this block's
        token span."""
        s_c = jnp.minimum(s, S - 1)
        return (s < S) & (cu(s_c + 1) > cu(s_c)) & (cu(s_c) < blk_end) & \
            (cu(s_c + 1) > blk_start)

    def next_valid(s):
        """First sequence >= s that overlaps this block.  Zero-q-len rows
        (cu(s+1) == cu(s)) are SKIPPED, not treated as terminators, so an
        interior empty row cannot hide later sequences; the walk still
        terminates at the first sequence starting at/after blk_end (the
        wrapper keeps sequences flat-token-ordered)."""
        return jax.lax.while_loop(
            lambda t: (t < S) & (cu(jnp.minimum(t, S - 1)) < blk_end)
            & ~seq_valid(t),
            lambda t: t + 1, s)

    def eff_kvl(s):
        """Causal context bound for THIS query block: the highest query row
        of sequence s in the block attends keys up to its own absolute
        position, so chunks past it are fully masked — skip their DMA and
        compute entirely (the flash-attention causal skip, per sequence).
        Decode (q_len 1) reduces to kvl; prefill blocks early in a long
        prompt walk only their causal prefix (~2x less work overall)."""
        s_c = jnp.minimum(s, S - 1)
        kvl = kvl_at(s_c)
        q1 = cu(s_c + 1)
        t_max = jnp.minimum(blk_end, q1) - 1          # last query row here
        p_max = kvl - q1 + t_max                      # its absolute position
        return jnp.clip(p_max + 1, 0, kvl)

    def page_needed(s, page_idx):
        return page_idx * ps < eff_kvl(s)

    def chunk_dma(s, c, slot, p):
        page_idx = c * P + p
        pid = pt_ref[jnp.minimum(s, S - 1), jnp.minimum(page_idx, NB - 1)]
        return pltpu.make_async_copy(
            pages_ref.at[pid], kv_bufs.at[slot, p], sems.at[slot, p])

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

    # ---- init softmax state -------------------------------------------- #
    acc[:] = jnp.zeros_like(acc)
    m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)

    # ---- find the first sequence overlapping this block ----------------- #
    s0 = next_valid(jnp.int32(0))

    @pl.when(seq_valid(s0))
    def _warmup():
        start_chunk(s0, 0, 0)

    # ---- compute on chunk (s, c) from buffer `slot` --------------------- #
    def compute(s, c, slot):
        kvl = kvl_at(jnp.minimum(s, S - 1))
        q0 = cu(s)
        q1 = cu(s + 1)
        chunk_base = c * CH
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, CH), 0)
        t = blk_start + r // G                       # flat token index
        k_pos = chunk_base + \
            jax.lax.broadcasted_iota(jnp.int32, (rows, CH), 1)
        q_pos = kvl - (q1 - q0) + (t - q0)           # absolute position
        mask = (t >= q0) & (t < q1) & (k_pos <= q_pos) & (k_pos < kvl)
        # rows OUTSIDE sequence s must treat s's chunks as exact no-ops.
        # Masked scores alone don't achieve that: a fully-masked row has
        # m = -NEG_INF so p = exp(-1e30 - -1e30) = 1, and its acc picks up
        # a 1-weighted sum of s's V values.  Finite garbage washes out
        # later (the row's own chunk rescales by alpha ≈ 0) — but
        # alpha·NaN STICKS, so one NaN-poisoned sequence would
        # contaminate every batchmate sharing its query block.  Gate the
        # accumulator updates on row ownership instead (the per-sequence
        # NaN-isolation contract the dense/decode lowerings already
        # enforce by construction).
        row_ok = (t[:, :1] >= q0) & (t[:, :1] < q1)  # [rows, 1]
        kv = kv_bufs[slot]                    # [P, ps, 2KV / HL, HL·hd]

        def head(h):                          # combined head h, [CH, hd]
            if HL == 1:
                return kv[:, :, h, :].reshape(CH, -1)
            hd = kv.shape[-1] // HL
            return kv[:, :, h // HL, h % HL * hd:(h % HL + 1) * hd] \
                .reshape(CH, -1)
        # pages past this block's CAUSAL bound (eff_kvl <= kv_len) are never
        # DMA'd — their buffer rows hold stale / uninitialized data.  Scores
        # there are masked, but V must be zeroed too: softmax weights for
        # REAL rows are exactly 0 on those columns and 0·garbage(NaN) would
        # still poison the accumulate.
        col_ok = jax.lax.broadcasted_iota(
            jnp.int32, (CH, 1), 0) + chunk_base < eff_kvl(s)
        for h in range(KV):
            qh = q_ref[:, h * G:(h + 1) * G, :].reshape(rows, -1) \
                .astype(jnp.float32)
            kh = head(h).astype(jnp.float32)
            vh = jnp.where(col_ok, head(KV + h), 0.0).astype(jnp.float32)
            s_mat = jnp.dot(qh, kh.T,
                            preferred_element_type=jnp.float32) * scale
            if alibi is not None:
                slope = jnp.zeros((rows, CH), jnp.float32)
                for g in range(G):                   # static per-head slope
                    slope = jnp.where(r % G == g,
                                      jnp.float32(alibi[h * G + g]), slope)
                if alibi_scaled:
                    # falcon: bias = bf16(slope·pos), added pre-1/sqrt(hd)
                    bias = (slope.astype(jnp.bfloat16) *
                            k_pos.astype(jnp.bfloat16)
                            ).astype(jnp.float32) * scale
                else:                  # bloom: unscaled f32 bias post-scale
                    bias = slope * k_pos.astype(jnp.float32)
                s_mat = s_mat + bias
            s_mat = jnp.where(mask, s_mat, _NEG_INF)

            m_prev = m_scr[h][:, :1]
            m_cand = jnp.maximum(m_prev,
                                 jnp.max(s_mat, axis=1, keepdims=True))
            # foreign rows keep their softmax state: m frozen ⇒ alpha = 1
            # ⇒ acc/l untouched, and their (possibly NaN) chunk
            # contribution is dropped below
            m_new = jnp.where(row_ok, m_cand, m_prev)
            alpha = jnp.exp(m_prev - m_new)
            p_mat = jnp.exp(s_mat - m_new)
            l_scr[h] = jnp.broadcast_to(
                alpha * l_scr[h][:, :1] +
                jnp.where(row_ok,
                          jnp.sum(p_mat, axis=1, keepdims=True), 0.0),
                l_scr[h].shape)
            acc[h] = acc[h] * alpha + \
                jnp.where(row_ok,
                          jnp.dot(p_mat.astype(vh.dtype), vh,
                                  preferred_element_type=jnp.float32), 0.0)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr[h].shape)

    # ---- main walk: (sequence, chunk) pairs, double-buffered ------------ #
    def body(state):
        s, c, slot = state
        nch = _cdiv(eff_kvl(s), CH)
        has_next = c + 1 < nch
        # ADVICE r5: only run the O(S) next_valid scan when the walk
        # actually leaves the current sequence — steady-state chunk
        # iterations on a long context stay on the cheap branch
        s_next, c_next = jax.lax.cond(
            has_next,
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

    # ---- finalize ------------------------------------------------------- #
    for h in range(KV):
        l = l_scr[h][:, :1]
        o = acc[h] / jnp.where(l == 0.0, 1.0, l)
        o_ref[:, h * G:(h + 1) * G, :] = o.reshape(BQ, G, -1).astype(o_ref.dtype)


def ragged_paged_attention(q: jnp.ndarray, kv_pages: jnp.ndarray,
                           kv_lens: jnp.ndarray, page_table: jnp.ndarray,
                           cu_q_lens: jnp.ndarray, *,
                           num_kv_heads: int,
                           scale: Optional[float] = None,
                           alibi=None, alibi_scaled: bool = False,
                           block_q: int = 128, pages_per_chunk: int = 8,
                           interpret: Optional[bool] = None) -> jnp.ndarray:
    """Ragged attention over a paged KV cache, flat-token layout.

    Args:
      q:          [T, H, hd] flat query tokens, sequence-major (sequence
                  s's tokens at [cu_q_lens[s], cu_q_lens[s+1])).
      kv_pages:   [num_pages_total, page_size, 2*KV, hd] combined page pool
                  (K heads at [:KV], V heads at [KV:]; or ``HL`` heads a
                  row, [.., 2*KV/HL, HL*hd]: ``_stored_heads``).  For stacked
                  multi-layer caches pass the full buffer and a per-layer
                  ``page_table + layer*pages`` — no in-kernel layer index.
      kv_lens:    [S] total context span per sequence (seen + in-flight).
      page_table: [S, NB] int32 physical page ids per sequence.
      cu_q_lens:  [S+1] exclusive prefix sum of per-sequence query counts.
    Returns [T, H, hd].
    """
    T, H_model, hd = q.shape
    _, ps, ckv, hd_k = kv_pages.shape
    HL = _lane_heads(hd, kv_pages)
    assert H_model % num_kv_heads == 0, \
        "query heads must be a multiple of kv heads"
    if alibi is not None:
        import numpy as np

        alibi = tuple(np.asarray(alibi, np.float32).tolist())   # static const
        assert len(alibi) == H_model, "alibi slopes must be per query head"
    q, KV, alibi = _stored_heads(q, kv_pages, num_kv_heads, alibi)
    H = q.shape[1]
    G = H // KV
    S, NB = page_table.shape
    assert cu_q_lens.shape == (S + 1,)
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    # never walk chunks past the page-table budget
    BQ, P0 = max(8, min(block_q, T)), min(pages_per_chunk, NB)

    # ---- VMEM budget: scratch must fit alongside the q/o blocks --------- #
    # kv_bufs double-buffer 2*P pages of [ps, 2KV, hd]; softmax state is
    # f32 [KV, BQ*G, hd|128] x3; q/o blocks are [BQ, H, hd].  Mosaic fails
    # with an opaque error past ~16MB, so shrink P first (fewer pages per
    # chunk costs DMA overlap, not correctness); a pool of so many heads that
    # one page a chunk is still too much (32 stored heads of 128: the
    # softmax state and the q/o blocks are 10 MB at 128 rows) halves the
    # query block and tries again; then fail loudly.
    VMEM_BUDGET = 12 * 1024 * 1024
    kv_itemsize = jnp.dtype(kv_pages.dtype).itemsize

    def _vmem_bytes(p, bq):
        kv_bufs = 2 * p * ps * ckv * hd_k * kv_itemsize
        softmax = KV * (bq * G) * (hd + 2 * 128) * 4
        # Pallas double-buffers the streamed q/o blocks across grid steps
        qo = 2 * 2 * bq * H * hd * jnp.dtype(q.dtype).itemsize
        # live f32 temporaries per compute step scale with the chunk width:
        # s_mat/p_mat [rows, P*ps] plus mask/iota registers of the same shape
        temps = 3 * (bq * G) * (p * ps) * 4
        return kv_bufs + softmax + qo + temps

    # A chunk is also LOADED whole (``kv_bufs[slot]``) and copied once more
    # by the per-head V select: values Mosaic keeps on its stack beside the
    # scratch above, inside the 4 MB between the budget and its limit as
    # long as a chunk is no more than 2 MiB (8 pages of 8 kv heads of 128: 2
    # MiB; of 32 stored heads: 8, and a 16-row bucket ran out of VMEM on
    # the chip, PR 34)
    while P0 > 1 and P0 * ps * ckv * hd_k * kv_itemsize > _CHUNK_LIMIT:
        P0 //= 2
    while True:
        P = P0
        while P > 1 and _vmem_bytes(P, BQ) > VMEM_BUDGET:
            P //= 2
        if _vmem_bytes(P, BQ) <= VMEM_BUDGET or BQ <= 8:
            break
        BQ //= 2
    if _vmem_bytes(P, BQ) > VMEM_BUDGET:
        raise ValueError(
            f"ragged_paged_attention VMEM budget exceeded even at "
            f"pages_per_chunk=1 and block_q=8: "
            f"{_vmem_bytes(P, BQ)/2**20:.1f}MB > "
            f"{VMEM_BUDGET/2**20:.0f}MB — reduce "
            f"page_size ({ps}), or kv heads x head_dim ({KV}x{hd})")
    T_pad = _cdiv(T, BQ) * BQ
    if T_pad != T:
        q = jnp.pad(q, ((0, T_pad - T), (0, 0), (0, 0)))

    # trace time only: what a run says about the kernel it compiled
    get_tracer().record(
        "attn/ragged_layout", time.perf_counter(), 0.0, P=P, block_q=BQ,
        dtype=jnp.dtype(kv_pages.dtype).name, kv_heads=num_kv_heads,
        stored_kv_heads=KV, group=G, lane_heads=HL,
        **_row_bytes(kv_pages, num_kv_heads, hd))

    interp = _interpret() if interpret is None else interpret
    kernel = functools.partial(
        _ragged_paged_kernel, scale=scale, ps=ps, P=P, KV=KV, G=G, BQ=BQ,
        S=S, NB=NB, alibi=alibi, alibi_scaled=alibi_scaled,
        use_refs=not interp, HL=HL)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(T_pad // BQ,),
            in_specs=[
                pl.BlockSpec((BQ, H, hd), lambda qb, *_: (qb, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((BQ, H, hd), lambda qb, *_: (qb, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, P, ps, ckv, hd_k), kv_pages.dtype),
                pltpu.SemaphoreType.DMA((2, P)),
                pltpu.VMEM((KV, BQ * G, hd), jnp.float32),
                pltpu.VMEM((KV, BQ * G, 128), jnp.float32),
                pltpu.VMEM((KV, BQ * G, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((T_pad, H, hd), q.dtype),
        interpret=interp,
        name="ragged_prefill",
    )(kv_lens.astype(jnp.int32), page_table.astype(jnp.int32),
      cu_q_lens.astype(jnp.int32), q, kv_pages)
    return out[:T] if H == H_model else out[:T, :H_model]


# ===================================================================== #
# Decode-specialized paged attention (the serving fast path)
# ===================================================================== #
def _decode_head_load(dtype, KV: int, hd: int, ps: int) -> str:
    """Which load a chunk's heads get — decided from what the pool shows,
    and it says what Mosaic lowers (``test_chip_compile.py`` compiles both
    answers for a described v5e).

    ``"strided"``: the pool's dtype packs two rows into a 32-bit word
    (bf16), so that a word row is a PAIR of combined heads; ``KV`` is even
    (a pair is two K heads or two V heads, never one of each); the ``KV``
    word rows of a token tile the sublanes without padding (1, 2, 4 or 8
    of them, or a multiple of 8 — 6 are padded to 8 and the flattened view
    is refused); ``hd`` is whole lane tiles and a page whole sublane tiles.
    The number of kv heads is NOT the obstacle (PR 33: 4 and 8 combined
    rows a token lower as they are), and neither is a 256-wide head: the
    kernel lands a page one 128-lane tile at a time, because the base
    memref of a sublane-strided load must be one lane tile wide.  Anything
    else — float32 pools, ``KV`` 1, 3 or 6, narrow heads — is
    ``"general"``.  ``KV`` counts the K ROWS a token is stored in: the
    heads, or fewer where several heads lie along the lanes of a row
    (``_lane_heads``: 10 heads in 2 rows of five, which tile where 10 rows
    do not).
    """
    packing = 4 // jnp.dtype(dtype).itemsize
    if packing == 2 and (KV in (2, 4) or KV % 8 == 0) and hd % 128 == 0 \
            and ps % 8 == 0:
        return "strided"
    return "general"


def _pairs_per_pass(KV: int, G: int) -> int:
    """How many head PAIRS one pass of the strided load scores: the largest
    divisor ``m`` of ``KV / 2`` whose ``2·m·G`` query rows fit the float32
    sublane tile (8 rows), at least 1 — read off the stored head count and
    the group size, nothing else.  A pass is a serial chain (strided load,
    ``q·Kᵀ``, lane max, ``exp``, lane sum, ``p·V``) whose latencies are
    paid once whatever its rows, and a ``[R <= 8, W]`` float32 tile costs
    ``W / 128`` vregs whatever ``R`` is.  A group of 4 or more query heads
    a kv head fills the tile with one pair (Mistral, Qwen3-Next: ``m`` = 1,
    PR 29's pass); multi-head attention takes 4 pairs a pass (Olmo-Hybrid:
    32 stored heads, 4 passes a chunk where one pair a pass made 16)."""
    return max(m for m in range(1, max(KV // 2, 1) + 1)
               if (KV // 2) % m == 0 and (m == 1 or 2 * m * G <= 8))


def _decode_paged_kernel(kvl_ref, pt_ref,                # scalar prefetch
                         q_ref, pages_ref, o_ref,        # VMEM block / HBM
                         kv_bufs, sems, acc, m_scr, l_scr, carry,
                         *, scale, ps, P, KV, G, NB, alibi, alibi_scaled, hpg,
                         pairs, HL=1):
    """One grid step = ONE decoding sequence's single query token.

    The ragged kernel spends a ``[block_q·G, chunk]`` MXU tile per chunk even
    when only one row is a real decode query — ~``block_q``× wasted compute
    per sequence.  Here the walk covers ONLY this sequence's pages, there is
    no in-kernel sequence scan, and a page's K and V for every kv head ride
    one double-buffered fetch (``[ps, 2KV, hd]``).

    How a chunk's heads reach the MXU (PR 29; the figures are in the module
    docstring).  Operands go in the POOL's dtype, accumulation and the
    softmax state are float32.  ``hpg`` is the number of kv heads scored a
    pass.  With ``hpg == 2`` (the strided load) the chunk buffer is read as
    32-bit words: word row ``t·KV + j`` holds combined heads
    ``2j`` (low half) and ``2j+1`` (high half) of token ``t``, so ONE
    sublane-strided ref load (start ``j``, stride ``KV``) yields heads
    ``2j, 2j+1`` of all ``CH`` tokens, each vreg of the buffer read once,
    and its bitcast back to the pool's dtype is the ``[2·CH, lanes]`` matrix
    whose even rows are head ``2j`` and odd rows head ``2j+1``.  That pair
    is scored in one pass against the ``2·G`` query rows of both heads;
    the columns of the other head's parity are masked like columns past
    the context, so their probabilities are exactly 0 and the same
    interleaved V pair sums each head's own rows.  No per-head sublane
    gather, no unpacking, no cast.  ``hpg == 1`` (the general load) scores
    one head a pass from a value slice of the chunk: the same body with no
    parity mask.

    How many pairs a pass scores (PR 37).  ``pairs`` (``_pairs_per_pass``)
    strided loads are laid one under the other, ``[pairs·2·CH, lanes]``,
    and scored against the pass's ``R = 2·pairs·G`` query rows: one
    ``[R, pairs·2·CH]`` score tile, one mask (a column's head is its load's
    pair and its parity), ONE max / ``exp`` / sum chain, one ``p · V`` over
    the V pairs laid the same way, one ``[R, hd]`` accumulator and ``m`` /
    ``l`` block a pass, ``KV / (2·pairs)`` passes a chunk.  Another head's
    probabilities are exactly 0, as the other parity's are, so the products
    and their precision are those of one pair a pass; a row's ``sum(p)``
    adds its terms in another order.  With ``pairs == 1`` the body is PR
    29's, equation for equation.

    How a chunk's pages land (PR 33).  Mosaic lowers a strided load only
    from a base memref that is ONE lane tile (128 lanes) wide — then any
    sublane tiling of it is plain rows of 128 words.  That, and not the
    number of kv heads, decides the buffer's shape: ``kv_bufs`` is
    ``[2, LT, P, ps, 2·KV, hd/LT]`` and a page is fetched by ``LT`` copies,
    one a lane tile (``LT = hd // 128`` for the strided load: 1 at 128-wide
    heads, which is PR 29's kernel; 2 at Qwen3-Next's 256; 1 copy of the
    whole page for the general load).  A pass sums ``q_t · K_tᵀ`` over the
    lane tiles in float32 and writes ``p · V_t`` into the accumulator's own
    columns: the same products in the same precision.

    Heads along the lanes (PR 59; ``HL``, ``_lane_heads``).  A pool whose
    head count tiles no sublane tile stores ``HL`` heads side by side in a
    row, ``KV / HL`` K rows and as many V rows a token (10 heads of 128: 2
    + 2 rows of 640), so that a token takes its own bytes and no more.  The
    page lands a lane tile at a time as above, and the pair load of word
    row ``j`` from the tiles of lane slot ``c`` is the pair of heads ``2j ·
    HL + c`` and ``(2j + 1) · HL + c``: a lane slot is a pair OF ITS OWN —
    its own scores, softmax state and accumulator, nothing summed across
    slots — and a chunk takes ``HL`` passes for each one a row pair takes
    (5 where the same heads padded to 16 rows took 8).  The wrapper hands
    the queries over in pass order and takes the outputs back.  ``KV``
    stays the number of heads; with ``HL == 1`` the body is the parent's,
    equation for equation.
    """
    s, S = pl.program_id(0), pl.num_programs(0)
    kvl = kvl_ref[s]
    CH = P * ps                               # context tokens per chunk
    nch = _cdiv(kvl, CH)
    KVr = KV // HL                            # K rows a token
    HP, PW = hpg * pairs, hpg * CH            # rows a pass, columns a load
    NG, R, W = KVr // HP, HP * G, pairs * PW  # row passes, query rows, columns
    dtype, LT, LW = kv_bufs.dtype, kv_bufs.shape[1], kv_bufs.shape[-1]
    QW = LW // HL if hpg == 1 else LW         # columns a dot takes of a head

    def page_needed(seq, page_idx):
        return page_idx * ps < kvl_ref[seq]

    def page_dmas(seq, c, slot, p):           # one copy a lane tile
        page_idx = c * P + p
        pid = pt_ref[seq, jnp.minimum(page_idx, NB - 1)]
        page = pages_ref.at[pid]               # one tile: the whole page
        return [pltpu.make_async_copy(
            page if LT == 1 else page.at[:, :, pl.ds(t * LW, LW)],
            kv_bufs.at[slot, t, p], sems.at[slot, t, p]) for t in range(LT)]

    def start_chunk(seq, c, slot):
        for p in range(P):
            @pl.when(page_needed(seq, c * P + p))
            def _():
                for dma in page_dmas(seq, c, slot, p):
                    dma.start()

    def wait_chunk(seq, c, slot):
        for p in range(P):
            @pl.when(page_needed(seq, c * P + p))
            def _():
                for dma in page_dmas(seq, c, slot, p):
                    dma.wait()

    acc[:] = jnp.zeros_like(acc)
    m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)

    # carry[0] = 1: the previous grid step already started THIS sequence's
    # first chunk, into slot carry[1].  Scratch and semaphores persist over
    # grid steps, and the grid runs in order on one core.
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
            col = jax.lax.broadcasted_iota(jnp.int32, (R, W), 1)
            # column -> context position: a load's PW columns are its CH
            # tokens with the pair's two heads interleaved, and a pass lays
            # its loads side by side
            k_pos = c * CH + (col if pairs == 1 else col % PW) // hpg
            mask = k_pos < kvl                 # decode: attend all cached ctx
            if hpg > 1:                        # ... of the row's own head:
                row = jax.lax.broadcasted_iota(jnp.int32, (R, W), 0)
                row_head = row // G
                col_head = col % hpg           # its parity in the pair
                if pairs > 1:                  # and which pair of the pass
                    col_head = col // PW * hpg + col_head
                mask = mask & (row_head == col_head)
            # never-DMA'd tokens hold stale data: scores there are masked,
            # but V rows must be zeroed so 0·garbage(NaN) cannot poison the
            # accumulate (select-before-multiply — the
            # masked-nan-propagation pass contract)
            tok_ok = jax.lax.broadcasted_iota(
                jnp.int32, (CH, 1), 0) + c * CH < kvl
            if hpg == 2:
                words = [kv_bufs.at[slot, t].reshape(CH * 2 * KVr, LW)
                         .bitcast(jnp.uint32)  # [CH·KVr, 128] a lane tile
                         for t in range(LT)]

                def pair(t, j, keep=None):     # combined rows 2j, 2j+1
                    w = words[t][pl.ds(j, CH, stride=KVr), :]
                    if keep is not None:       # a word row is one token
                        w = jnp.where(keep, w, jnp.uint32(0))
                    return pltpu.bitcast(w, dtype)        # [2·CH, 128]

                def load(t, first, keep=None):  # a pass's pairs, one
                    got = [pair(t, first + i, keep)         # under the other
                           for i in range(pairs)]
                    return got[0] if pairs == 1 else jnp.concatenate(got)

                def load_k(g, t):
                    return load(t, g * pairs)

                def load_v(g, t):
                    return load(t, KVr // 2 + g * pairs, tok_ok)
            else:
                kv = kv_bufs[slot, 0]          # [P, ps, 2KVr, HL·hd]

                def load_k(row, t):            # lane slot t of a row
                    lanes = slice(None) if HL == 1 \
                        else slice(t * QW, (t + 1) * QW)
                    return kv[:, :, row, lanes].reshape(CH, QW)

                def load_v(g, t):
                    return jnp.where(tok_ok, load_k(KVr + g, t), 0.0)

            # pass g = (row pass rp, lane slot c), over the slot's own tiles
            for g in range(NG * HL):
                rp, c = divmod(g, HL)
                tiles = range(c * LT // HL, (c + 1) * LT // HL) \
                    if hpg == 2 else [c]
                s_mat = functools.reduce(jnp.add, [jax.lax.dot_general(
                    q_ref[0, g * R:(g + 1) * R, i * QW:(i + 1) * QW]
                    .astype(dtype), load_k(rp, t), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                    for i, t in enumerate(tiles)]) * scale
                if alibi is not None:
                    r = jax.lax.broadcasted_iota(jnp.int32, (R, W), 0)
                    slope = jnp.zeros((R, W), jnp.float32)
                    for i in range(R):         # static per-head slope
                        slope = jnp.where(r == i,
                                          jnp.float32(alibi[g * R + i]),
                                          slope)
                    if alibi_scaled:           # falcon: bf16 pre-scale bias
                        bias = (slope.astype(jnp.bfloat16) *
                                k_pos.astype(jnp.bfloat16)
                                ).astype(jnp.float32) * scale
                    else:                      # bloom: unscaled f32 bias
                        bias = slope * k_pos.astype(jnp.float32)
                    s_mat = s_mat + bias
                s_mat = jnp.where(mask, s_mat, _NEG_INF)

                m_prev = m_scr[g][:, :1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s_mat, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p_mat = jnp.exp(s_mat - m_new)
                l_scr[g] = jnp.broadcast_to(
                    alpha * l_scr[g][:, :1] +
                    jnp.sum(p_mat, axis=1, keepdims=True), l_scr[g].shape)
                for i, t in enumerate(tiles):  # the tile's own columns
                    cols = slice(i * QW, (i + 1) * QW)
                    acc[g, :, cols] = acc[g, :, cols] * alpha + \
                        jnp.dot(p_mat.astype(dtype), load_v(rp, t),
                                preferred_element_type=jnp.float32)
                m_scr[g] = jnp.broadcast_to(m_new, m_scr[g].shape)

        def body(state):
            c, slot = state

            @pl.when(c + 1 < nch)
            def _prefetch():
                start_chunk(s, c + 1, 1 - slot)

            # behind this sequence's LAST compute: the next grid step's
            # first chunk, into the buffer the walk has just left (a
            # kv_lens == 0 row starts nothing and is handed nothing)
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

    for g in range(NG * HL):
        l = l_scr[g][:, :1]
        o = acc[g] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, g * R:(g + 1) * R, :] = o.astype(o_ref.dtype)


def decode_paged_attention(q: jnp.ndarray, kv_pages: jnp.ndarray,
                           kv_lens: jnp.ndarray, page_table: jnp.ndarray, *,
                           num_kv_heads: int, scale: Optional[float] = None,
                           alibi=None, alibi_scaled: bool = False,
                           pages_per_chunk: int = 8,
                           interpret: Optional[bool] = None) -> jnp.ndarray:
    """Paged attention for pure-decode batches: ONE query token per sequence.

    Args:
      q:          [S, H, hd] — sequence s's single new-token query at row s.
      kv_pages:   [num_pages_total, page_size, 2*KV, hd] page pool (the
                  multi-layer layout of :func:`ragged_paged_attention`; or
                  ``HL`` heads a row, [.., 2*KV/HL, HL*hd]).
      kv_lens:    [S] context length per sequence (seen + the in-flight
                  token, i.e. the query's own position is kv_lens-1).
                  Rows with kv_lens == 0 are padding and yield zeros.
      page_table: [S, NB] int32 physical page ids.
    Returns [S, H, hd].

    The MXU sees ``q``, K, V and the probabilities in the POOL's dtype and
    accumulates in float32 (a float32 pool rounds nothing).  Every traced
    call leaves one ring-only ``attn/decode_layout`` record saying which
    head load the compiled kernel got (:func:`_decode_head_load`) and in
    how many ``lane_tiles`` a page lands (``hd // 128`` for the strided
    load, 1 otherwise), how many head pairs a pass scores and how many
    passes a chunk takes (``pairs_per_pass``, ``passes_per_chunk``), and
    the STORED form: ``lane_heads`` (heads along the lanes of a row),
    ``row_bytes`` (what a token takes in the pool) and ``read_bytes`` (what
    the model reads of it: less only where a head count is padded).
    ``pages_per_chunk`` is an upper bound: a chunk is held to 2 MiB a
    buffer and to the VMEM budget.
    """
    S, H_model, hd = q.shape
    _, ps, ckv, hd_k = kv_pages.shape
    HL = _lane_heads(hd, kv_pages)
    assert H_model % num_kv_heads == 0, \
        "query heads must be a multiple of kv heads"
    if alibi is not None:
        import numpy as np

        alibi = tuple(np.asarray(alibi, np.float32).tolist())
        assert len(alibi) == H_model, "alibi slopes must be per query head"
    q, KV, alibi = _stored_heads(q, kv_pages, num_kv_heads, alibi)
    H = q.shape[1]
    G = H // KV
    S_t, NB = page_table.shape
    assert S_t == S and kv_lens.shape == (S,)
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    P = min(pages_per_chunk, NB)
    # the load, the pairs and the passes follow the ROWS a token is stored
    # in: ``KV`` of them, or ``KV / HL`` with ``HL`` heads a row
    load = _decode_head_load(kv_pages.dtype, KV // HL, hd, ps)
    hpg, LT = (2, hd_k // 128) if load == "strided" else (1, 1)
    pairs = _pairs_per_pass(KV // HL, G) if load == "strided" else 1
    NG, R = KV // (hpg * pairs), hpg * pairs * G   # passes a chunk, rows a pass
    by_pass = HL > 1 and hpg * pairs > 1
    if by_pass:
        # a pass of the strided load scores lane slot c of ``hpg · pairs``
        # rows, heads ``row · HL + c``: the queries (and the slopes) go in
        # pass order, the outputs come back in the model's
        def swap(x, a, b):          # [.., a, b, G, ..] -> [.., b, a, G, ..]
            return x.reshape(x.shape[:1] + (NG // HL, a, b, G)
                             + x.shape[2:]).swapaxes(2, 3).reshape(x.shape)

        q = swap(q, R // G, HL)
        if alibi is not None:
            import numpy as np

            alibi = tuple(swap(np.asarray(alibi)[None], R // G, HL)[0]
                          .tolist())

    # same VMEM accounting as the ragged kernel, with the
    # [R, pairs·hpg·chunk] score tile, and the same limit on a chunk
    VMEM_BUDGET = 12 * 1024 * 1024
    kv_itemsize = jnp.dtype(kv_pages.dtype).itemsize
    page_bytes = ps * ckv * hd_k * kv_itemsize

    def _vmem_bytes(p):
        kv_bufs = 2 * p * ps * ckv * hd_k * kv_itemsize
        softmax = KV * G * (hd + 2 * 128) * 4
        qo = 2 * 2 * H * hd * jnp.dtype(q.dtype).itemsize
        temps = 3 * R * (pairs * hpg * p * ps) * 4
        return kv_bufs + softmax + qo + temps

    while P > 1 and (P * page_bytes > _CHUNK_LIMIT
                     or _vmem_bytes(P) > VMEM_BUDGET):
        P //= 2
    if _vmem_bytes(P) > VMEM_BUDGET:
        raise ValueError(
            f"decode_paged_attention VMEM budget exceeded even at "
            f"pages_per_chunk=1: {_vmem_bytes(P)/2**20:.1f}MB > "
            f"{VMEM_BUDGET/2**20:.0f}MB — reduce page_size ({ps}) or "
            f"kv heads x head_dim ({KV}x{hd})")

    # trace time only: what a run says about the kernel it compiled
    get_tracer().record(
        "attn/decode_layout", time.perf_counter(), 0.0, load=load, P=P,
        dtype=jnp.dtype(kv_pages.dtype).name, kv_heads=num_kv_heads,
        stored_kv_heads=KV, group=G, lane_tiles=LT, pairs_per_pass=pairs,
        passes_per_chunk=NG, lane_heads=HL,
        **_row_bytes(kv_pages, num_kv_heads, hd))

    kernel = functools.partial(
        _decode_paged_kernel, scale=scale, ps=ps, P=P, KV=KV, G=G, NB=NB,
        alibi=alibi, alibi_scaled=alibi_scaled, hpg=hpg, pairs=pairs, HL=HL)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[
                pl.BlockSpec((1, H, hd), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, hd), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, LT, P, ps, ckv, hd_k // LT), kv_pages.dtype),
                pltpu.SemaphoreType.DMA((2, LT, P)),
                pltpu.VMEM((NG, R, hd), jnp.float32),
                pltpu.VMEM((NG, R, 128), jnp.float32),
                pltpu.VMEM((NG, R, 128), jnp.float32),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, H, hd), q.dtype),
        interpret=_interpret() if interpret is None else interpret,
        name="paged_decode",
    )(kv_lens.astype(jnp.int32), page_table.astype(jnp.int32), q, kv_pages)
    if by_pass:
        out = swap(out, HL, R // G)
    return out if H == H_model else out[:, :H_model]


def verify_window_attention(q: jnp.ndarray, kv_pages: jnp.ndarray,
                            kv_lens: jnp.ndarray, page_table: jnp.ndarray,
                            cu_q_lens: jnp.ndarray, *,
                            num_kv_heads: int,
                            scale: Optional[float] = None,
                            alibi=None, alibi_scaled: bool = False,
                            block_q: int = 128, pages_per_chunk: int = 8,
                            interpret: Optional[bool] = None) -> jnp.ndarray:
    """Speculative-decoding verify windows: score a short multi-token row
    per sequence (the seed token plus K draft candidates) in ONE pass.

    This is the ragged prefill kernel's multi-row scoring reused — a verify
    window IS a ragged batch whose rows are all K+1 tokens or shorter — but
    dispatched through its own seam so the query tile is sized to the
    window: a verify window is ``S·(K+1)`` flat tokens (tens, not
    hundreds), and the prefill default ``block_q=128`` would burn a
    mostly-padding MXU tile per grid step.  Clamping the tile to the flat
    token budget keeps the whole window in one grid step, which is also
    what makes verify cheaper than K+1 sequential decode steps: one page
    walk per sequence scores every candidate position.

    Layout contract (what the engine's verify bucket builds): sequence s's
    ``q_len[s] = 1 + len(draft_s)`` query tokens sit contiguously at flat
    indices ``[cu_q_lens[s], cu_q_lens[s+1])``; ``kv_lens`` counts seen +
    in-flight (so the KV append for the window has already happened);
    causal masking inside the kernel gives draft position j visibility of
    the real context plus drafts ``< j`` — exactly the state vanilla decode
    would have when it reached that position, which is why the greedy
    argmax chain is stream-identical to vanilla decode.
    """
    T = q.shape[0]
    return ragged_paged_attention(
        q, kv_pages, kv_lens, page_table, cu_q_lens,
        num_kv_heads=num_kv_heads, scale=scale, alibi=alibi,
        alibi_scaled=alibi_scaled, block_q=min(block_q, T),
        pages_per_chunk=pages_per_chunk, interpret=interpret)


def decode_attend_dense(q: jnp.ndarray, kv_pages: jnp.ndarray,
                        kv_lens: jnp.ndarray, page_table: jnp.ndarray, *,
                        num_kv_heads: int, scale: Optional[float] = None,
                        alibi=None, alibi_scaled: bool = False) -> jnp.ndarray:
    """Decode attention with q_len=1 semantics in plain XLA — the off-TPU
    lowering of :func:`decode_paged_attention` (bit-compatible numerics).

    Unlike the prefill-shaped gather oracle this never materialises a
    ``[S, max_q, H, ctx]`` score tensor — scores are ``[S, H, ctx]`` — so
    even the interpreter-free CPU sim sees the decode win.  ``kv_lens == 0``
    rows (bucket padding) produce zeros.
    """
    S, H_model, hd = q.shape
    _, ps, ckv, _ = kv_pages.shape
    q, KV, alibi = _stored_heads(q, kv_pages, num_kv_heads, alibi)
    H = q.shape[1]
    G = H // KV
    NB = page_table.shape[1]
    C = NB * ps
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    ctx_pos = jnp.arange(C, dtype=jnp.int32)
    pg = jnp.take_along_axis(page_table,
                             (ctx_pos // ps)[None, :].repeat(S, 0), axis=1)
    off = jnp.broadcast_to((ctx_pos % ps)[None, :], (S, C))
    ctx = _token_heads(kv_pages[pg, off], hd)            # [S, C, 2KV, hd]
    k_ctx, v_ctx = ctx[..., :KV, :], ctx[..., KV:, :]
    # out-of-context columns may hold never-written garbage: scores there
    # are masked to -inf, but V must be zeroed too so 0·garbage(NaN)
    # cannot poison the weighted sum (mirrors the Pallas kernel's col_ok;
    # select-before-multiply — the masked-nan-propagation pass contract)
    valid = (ctx_pos[None, :] < kv_lens[:, None])[:, :, None, None]
    v_ctx = jnp.where(valid, v_ctx, 0.0)
    if KV != H:
        k_ctx = jnp.repeat(k_ctx, G, axis=2)
        v_ctx = jnp.repeat(v_ctx, G, axis=2)
    scores = jnp.einsum("shd,schd->shc", q.astype(jnp.float32),
                        k_ctx.astype(jnp.float32)) * scale
    if alibi is not None:
        slopes = jnp.asarray(alibi, jnp.float32)          # [H]
        if alibi_scaled:
            bias = (slopes[:, None].astype(jnp.bfloat16) *
                    ctx_pos[None, :].astype(jnp.bfloat16)
                    ).astype(jnp.float32) * scale
        else:
            bias = slopes[:, None] * ctx_pos[None, :].astype(jnp.float32)
        scores = scores + bias[None, :, :]
    mask = ctx_pos[None, None, :] < kv_lens[:, None, None]
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # fully-masked (padding) rows: softmax over all -inf is uniform garbage
    probs = jnp.where(jnp.any(mask, axis=-1, keepdims=True), probs, 0.0)
    out = jnp.einsum("shc,schd->shd", probs, v_ctx.astype(jnp.float32))
    out = out.astype(q.dtype)
    return out if H == H_model else out[:, :H_model]


def decode_attention(q: jnp.ndarray, kv_pages: jnp.ndarray,
                     kv_lens: jnp.ndarray, page_table: jnp.ndarray, *,
                     num_kv_heads: int, scale: Optional[float] = None,
                     alibi=None, alibi_scaled: bool = False,
                     pages_per_chunk: int = 8,
                     impl: Optional[str] = None) -> jnp.ndarray:
    """Decode fast-path dispatch: the Pallas kernel on TPU, the dense
    q_len=1 XLA path elsewhere (interpreter-mode Pallas is a correctness
    tool, not a CPU serving path).  ``impl`` forces ``"pallas"`` /
    ``"dense"`` for tests."""
    if impl is None:
        impl = "dense" if _interpret() else "pallas"
    if impl == "pallas":
        return decode_paged_attention(
            q, kv_pages, kv_lens, page_table, num_kv_heads=num_kv_heads,
            scale=scale, alibi=alibi, alibi_scaled=alibi_scaled,
            pages_per_chunk=pages_per_chunk)
    return decode_attend_dense(
        q, kv_pages, kv_lens, page_table, num_kv_heads=num_kv_heads,
        scale=scale, alibi=alibi, alibi_scaled=alibi_scaled)


# ===================================================================== #
# Paged KV append (linear_blocked_kv_rotary's cache-update half)
# ===================================================================== #
def paged_kv_append(kv_pages: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    page_of_token: jnp.ndarray,
                    off_of_token: jnp.ndarray, replicate=None) -> jnp.ndarray:
    """Scatter new K/V rows into their cache pages.

    kv_pages: [num_pages_total, page_size, 2*KV, hd] (or ``HL`` heads a
    row, [.., 2*KV/HL, HL*hd]: ``_stored_heads``); k/v: [T, KV, hd] (or
    fewer heads than the pool stores); page_of_token/off_of_token: [T]
    (padded tokens target the trash page).
    A row scatter into a donated / loop-carried buffer lowers to an
    in-place dynamic-update on TPU — the idiomatic equivalent of the
    reference's pointer-chasing CUDA append.  Writing the combined
    [T, 2KV, hd] rows costs O(T) HBM regardless of cache size.

    ``replicate`` (a replicated ``NamedSharding``) pins the scatter's
    operands and result when the surrounding program carries TP-sharded
    params: without the constraint GSPMD rewrites this row-set into a
    scatter applied per replica group and SUMS the groups' contributions,
    multiplying every cached K/V row by the group count (observed 4x on a
    dp4×tp2 mesh — serving under a TP mesh produced garbage logits).  Pass
    it whenever any model param is non-trivially sharded.
    """
    HL = _lane_heads(k.shape[2], kv_pages)
    stored = kv_pages.shape[2] // 2 * HL
    if stored != k.shape[1]:
        # a pool that stores a token in more heads than the model has
        # (_stored_heads): the heads past the model's are written as zeros
        pad = ((0, 0), (0, stored - k.shape[1]), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    comb = jnp.concatenate([k, v], axis=1).astype(kv_pages.dtype)
    if HL > 1:              # HL heads a row, as they follow one another
        comb = comb.reshape((-1,) + kv_pages.shape[2:])
    if replicate is not None:
        comb = jax.lax.with_sharding_constraint(comb, replicate)
        kv_pages = jax.lax.with_sharding_constraint(kv_pages, replicate)
    out = kv_pages.at[page_of_token, off_of_token].set(comb)
    if replicate is not None:
        out = jax.lax.with_sharding_constraint(out, replicate)
    return out
