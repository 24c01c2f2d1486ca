"""Ragged-batch model execution (reference: inference/v2/model_implementations/
inference_transformer_base.py:48 + the ragged_ops kernel chain in §3.4:
qkv → linear_blocked_kv_rotary (paged KV append) → blocked_flash → logits_gather).

One jitted step serves ANY mix of prefill and decode under fixed budgets
(max_tokens/max_seqs/max_blocks), with the paged KV cache donated through the
call so the update is in-place in HBM.

This file owns everything about pages and nothing about a model.  What a
model is comes as its :class:`~deepspeed_tpu.models.serving.ServingFamily`
(``model.serving_family()``): the cached row, the embedding, the layer stacks
and their bodies, the head.  :func:`ragged_forward` is the one forward:
unpack → the family's embedding → one ``lax.scan`` per layer stack → the
family's final norm and head on each sequence's last token (logits_gather).
A layer body is handed a :class:`_LayerCache` to append to and attend
through; it never sees a page table.

Cache layout (see ragged/kv_cache.py): ONE flat page pool
``[L*num_blocks + 1, page_size, *row]`` shared by all layers — layer l's
page table is ``block_table + l*num_blocks`` (plain metadata arithmetic, no
in-kernel layer index), and the final page is the shared trash page padded
tokens write into.

Two attention impls, for every cache kind (kernels/page_ops.py):
  "paged"  — Pallas ragged paged-attention kernels; flat-token grid,
             in-kernel context walk, double-buffered page DMA; HBM traffic
             O(cached tokens).
  "gather" — dense page-gather reference path (O(S·C) HBM per layer); kept
             as the numerics oracle for kernel tests.
"""
from __future__ import annotations

import contextlib
import copy
import math
from functools import partial

import jax
import jax.numpy as jnp

from ...models.serving import ServingFamily
from .kernels.page_ops import (PageOps, page_ops, pair_outputs, pair_queries,
                               state_ops, window_op)
from .ragged.ragged_wrapper import pack_layout


def _unpack_batch(batch, max_q, max_seqs, max_blocks, state_slot=False):
    """Packed int32 metadata vector → field dict via static on-device
    slices (one H2D transfer per forward; see ragged_wrapper.pack_layout)."""
    return {name: batch[off:off + math.prod(shape)].reshape(shape)
            for name, (off, shape)
            in pack_layout(max_q, max_seqs, max_blocks, state_slot).items()
            if name != "_total"}


def _layer_pages(page_of_token, layer, num_blocks, trash_page):
    """Layer-relative token pages → absolute pool pages; the wrapper's
    pad sentinel (>= num_blocks) routes to the shared trash page."""
    return jnp.where(page_of_token < num_blocks,
                     page_of_token + layer * num_blocks, trash_page)


def _slot_rows(batch, layer, slots, trash_row):
    """Each sequence row's row of a slot pool ``[layers * slots + 1, ...]``
    in ``layer``; the wrapper's pad sentinel (>= slots) routes to the
    trailing trash row."""
    slot = batch["state_slot"]
    return jnp.where(slot < slots, slot + layer * slots, trash_row)


class _LayerCache:
    """One layer's view of the page pool, as a layer body gets it:
    ``append`` the new tokens' rows, ``attend`` to every sequence's cached
    context; called, it does both.

    ``pages`` is the FULL multi-layer page pool; ``layer`` (traced) picks
    this layer's pages via table arithmetic — no per-layer slice
    materialization.  ``at(page_layer)`` is the same pool seen as another
    page layer, for a body that owns several: the views share the pool, so
    an append through one is seen by the others and by the runner.
    ``attend`` dispatches among the cache kind's
    operations (``ops``): the flat-token ragged kernel; the dense
    page-gather oracle; with ``decode_mode`` — the row-major decode layout
    (sequence i's single query token at flat index i, rows past n_seqs
    padded with ctx_len 0: the fused decode loop's batches by construction)
    — the one-token-per-sequence kernel instead of a full ``block_q`` query
    tile per decoding sequence; with ``verify_mode`` (mutually exclusive),
    the speculative-decoding seam — rows are short multi-token windows
    (seed + K draft candidates) — the kind's verify-window operation.

    A row kind with an index key (``KVRow.index``): ``pages`` is the pair
    (K/V array, index array), ``append`` takes the index key as a third
    row, and ``attend`` takes, after ``q``, the indexer's queries and head
    weights ``[T, ...]``; they ride with ``q`` as one tuple through the same
    dispatch, and the kind's operations score, select and read.

    ``attend_pair(q1, q2, pairs=)`` is the differential read of a row kind
    whose heads are pairs (``page_ops.pair_queries``); it appends nothing,
    like ``attend``, so a body that only reads another layer's page layer
    calls it on ``at(that layer)``.  ``window(window_layer)`` is a layer
    that keeps a ring of rows in the sequence's slot instead of pages
    (:class:`_WindowView`; families with ``ServingFamily.window``).
    """

    def __init__(self, pages, layer, *, ops: PageOps, batch, attn_impl,
                 num_blocks, max_q, block_q, pages_per_chunk, decode_mode,
                 verify_mode, rings=None):
        self.ops, self.batch, self.layer = ops, batch, layer
        self._pool = [pages]            # shared by every view (``at``)
        self.rings = rings
        self.paged, self.num_blocks, self.max_q = \
            attn_impl == "paged", num_blocks, max_q
        self.tile = dict(block_q=block_q, pages_per_chunk=pages_per_chunk)
        self.decode_mode, self.verify_mode = decode_mode, verify_mode

    @property
    def pages(self):
        return self._pool[0]

    @pages.setter
    def pages(self, pages) -> None:
        self._pool[0] = pages

    def at(self, page_layer) -> "_LayerCache":
        view = copy.copy(self)          # the same ``_pool`` list
        view.layer = page_layer
        return view

    def append(self, *rows) -> None:
        b = self.batch
        self.pages = self.ops.append(
            self.pages, *rows,
            _layer_pages(b["page_of_token"], self.layer, self.num_blocks,
                         jax.tree.leaves(self.pages)[0].shape[0] - 1),
            b["off_of_token"])

    def attend(self, q, *index, **attn):
        """q [T, H, d] → [T, H, d']; ``index``: what an indexed row kind's
        queries bring beside ``q``, each ``[T, ...]``."""
        ops, pages, b, max_q = self.ops, self.pages, self.batch, self.max_q
        T = q.shape[0]
        if index:
            q = (q,) + index
        q_len, ctx_len = b["q_len"], b["ctx_len"]
        pt_l = b["block_table"] + self.layer * self.num_blocks     # [S, NB]
        if self.paged and self.verify_mode:
            return ops.verify(q, pages, ctx_len, pt_l, b["cu_q_lens"],
                              **self.tile, **attn)
        if self.paged and self.decode_mode:
            SW = min(q_len.shape[0], T)
            out = ops.decode(
                jax.tree.map(lambda a: a[:SW], q), pages, ctx_len[:SW],
                pt_l[:SW], pages_per_chunk=self.tile["pages_per_chunk"],
                **attn)
            return jnp.pad(out, ((0, T - SW), (0, 0), (0, 0))) \
                if T > SW else out
        if self.paged:
            return ops.ragged(q, pages, ctx_len, pt_l, b["cu_q_lens"],
                              **self.tile, **attn)
        q_idx = jnp.clip(b["q_offset"][:, None] + jnp.arange(max_q)[None, :],
                         0, T - 1)
        q_seq = jax.tree.map(
            lambda a: jnp.take(a, q_idx.reshape(-1), axis=0).reshape(
                (-1, max_q) + a.shape[1:]), q)            # [S, mq, H, d]
        o_seq = ops.dense(q_seq, pages, pt_l, q_len, ctx_len,
                          **attn).astype(jax.tree.leaves(q)[0].dtype)
        within = jnp.clip(
            jnp.arange(T) - jnp.take(b["q_offset"], b["seq_of_token"]),
            0, max_q - 1)
        return o_seq[b["seq_of_token"], within]

    def __call__(self, q, *rows, **attn):
        self.append(*rows)
        return self.attend(q, **attn)

    def attend_pair(self, q1, q2, *, pairs: int, **attn):
        """→ (A1, A2) [T, Hp, hd]: ``q1 . k1`` and ``q2 . k2`` over the one
        value pair, each with its own softmax."""
        return pair_outputs(
            self.attend(pair_queries(q1, q2, pairs), **attn), pairs)

    def window(self, window_layer) -> "_WindowView":
        return _WindowView(self.rings, window_layer,
                           self.tile["pages_per_chunk"])


class _Rings:
    """The window layers' ring pool (``kernels/window_ops``) for one layer
    step: ``[window_layers * slots + 1, W, *row]``, a sequence's ring of
    window layer ``w`` at row ``w * slots + slot``, the last row the trash
    ring.  Shared by the step's views as the page pool is."""

    def __init__(self, pool, *, op, batch, slots, mode, valid):
        self.pool, self.op, self.batch = pool, op, batch
        self.slots, self.mode, self.valid = slots, mode, valid


class _WindowView:
    """One window layer as a body gets it: called with ``(q, k, v,
    **attn)`` it appends the new tokens' rows to their rings and attends
    each query to its window (the halves cannot be had apart: a prefill
    chunk reads the ring as it was BEFORE the chunk); ``pair`` is the
    differential form of the same (``_LayerCache.attend_pair``)."""

    def __init__(self, rings: _Rings, window_layer, pages_per_chunk):
        self.rings, self.layer, self.ppc = rings, window_layer, \
            pages_per_chunk

    def __call__(self, q, k, v, **attn):
        r = self.rings
        rows = _slot_rows(r.batch, self.layer, r.slots, r.pool.shape[0] - 1)
        out, r.pool = r.op(q, k, v, r.pool, rows, mode=r.mode, batch=r.batch,
                           valid=r.valid, pages_per_chunk=self.ppc, **attn)
        return out.astype(q.dtype)

    def pair(self, q1, q2, k, v, *, pairs: int, **attn):
        return pair_outputs(self(pair_queries(q1, q2, pairs), k, v, **attn),
                            pairs)


class _LayerState:
    """The state pool as a layer body gets it: ``state(state_layer,
    *inputs)`` runs the state kind's update for the batch's new tokens
    through every sequence's slot and returns its output.

    ``pool`` is the FULL pool ``[state_layers * slots + 1, ...]`` (a tuple of
    arrays); row ``state_layer * slots + slot`` is a sequence's, the last is
    the trash row of padded sequence rows (the wrapper's sentinel ``>=
    slots``).  The mode follows the attention dispatch: the one-token kernel
    for ``decode_mode`` batches, the chunked form for ragged ones, the
    token-by-token oracle with ``attn_impl="gather"``."""

    def __init__(self, pool, *, update, batch, slots, mode, valid):
        self.pool, self.update, self.batch = pool, update, batch
        self.slots, self.mode, self.valid = slots, mode, valid

    def __call__(self, state_layer, *inputs):
        rows = _slot_rows(self.batch, state_layer, self.slots,
                          self.pool[0].shape[0] - 1)
        out, self.pool = self.update(*inputs, self.pool, rows,
                                     mode=self.mode, batch=self.batch,
                                     valid=self.valid)
        return out


def ragged_forward(params, kv_pages: jnp.ndarray, batch,
                   family: ServingFamily, max_q: int, num_blocks: int,
                   attn_impl: str = "paged", max_seqs: int = 0,
                   max_blocks: int = 0, block_q: int = 128,
                   pages_per_chunk: int = 8, decode_mode: bool = False,
                   verify_mode: bool = False, kv_replicate=None):
    """→ (last-token logits [max_seqs, V], new kv_pages), and where the
    family counts (``family.counts``) its counts as a third.

    A family with recurrent state (``family.state``): ``kv_pages`` is the
    pair ``(page pool, state pool)``, donated and returned as one, and the
    batch carries each row's slot.

    ``decode_mode`` dispatches the one-token-per-sequence decode attention
    (requires row-major decode batches).  ``verify_mode`` dispatches the
    spec-dec verify-window path (short multi-token rows) and the logits are
    ALL positions' ``[max_q, V]``: the verify pass needs the target's greedy
    argmax at every window position (:func:`build_verify_step` wraps the
    argmax/accept).  ``kv_replicate`` (replicated NamedSharding) must be
    passed when params are TP-sharded — see ``ragged_ops.paged_kv_append``."""
    assert attn_impl in ("paged", "gather"), \
        f"attn_impl must be 'paged' or 'gather', got {attn_impl!r}"
    assert not (decode_mode and verify_mode), \
        "decode_mode and verify_mode are mutually exclusive dispatches"
    ops = page_ops(family.row, kv_replicate)
    if verify_mode and ops.verify is None:
        raise NotImplementedError(
            f"speculative verify windows are not supported with "
            f"{type(family.row).__name__} pages"
            + (" that carry index keys (sparse attention)"
               if family.row.index is not None else ""))
    state_pool = rings = None
    has_slot = bool(family.slot_kinds)
    if has_slot:
        if verify_mode:
            raise NotImplementedError(
                "speculative verify windows are not supported with "
                "recurrent state: a rejected candidate cannot be taken back "
                "out of a state (ROADMAP R5)")
        kv_pages, state_pool = kv_pages
        if family.window is not None:
            # the window layers' rings lie behind the state's arrays
            state_pool, rings = tuple(state_pool[:-1]), state_pool[-1]
    batch = _unpack_batch(batch, max_q, max_seqs, max_blocks, has_slot)
    layer_cache = partial(
        _LayerCache, ops=ops, batch=batch, attn_impl=attn_impl,
        num_blocks=num_blocks, max_q=max_q, block_q=block_q,
        pages_per_chunk=pages_per_chunk, decode_mode=decode_mode,
        verify_mode=verify_mode)
    # valid(): padded tokens carry the pad-page sentinel
    x, ctx = family.embed(
        params, batch["tokens"], batch["pos_of_token"],
        lambda: batch["page_of_token"] < num_blocks)
    counts = jnp.zeros((family.counts.size,), jnp.int32) \
        if family.counts else None
    # (made only for a family with a slot: a program without one keeps the
    # parent's text, operation for operation)
    slot_mode = dict(
        batch=batch, valid=batch["page_of_token"] < num_blocks,
        mode="oracle" if attn_impl != "paged"
        else "decode" if decode_mode else "ragged") if has_slot else {}
    layer_state = None if not state_pool else partial(
        _LayerState, update=state_ops(family.state),
        slots=(state_pool[0].shape[0] - 1) // family.state.num_layers,
        **slot_mode)
    layer_rings = None if rings is None else partial(
        _Rings, op=window_op(family.row, family.window),
        slots=(rings.shape[0] - 1) // family.window.num_layers, **slot_mode)

    for stack in family.stacks(params):
        def layer_step(carry, inputs, body=stack.body):
            # The FULL page pool rides the carry: the append is an in-place
            # scatter of T rows and the paged kernel reads pages straight
            # from the pool.  Scanning the cache as xs/ys instead would
            # slice-copy one full layer per iteration AND restack the whole
            # cache per forward — O(cache) HBM per decode step.
            x, pages, counts, state_pool, rings = carry
            lp, l_idx = inputs
            # the rings ride the carry too, shared by the step's views
            cache = layer_cache(pages, l_idx, rings=layer_rings
                                and layer_rings(rings))
            if layer_state is None:
                out = body(x, lp, l_idx, cache, ctx)
            else:
                # the state pool rides the carry beside the page pool
                state = layer_state(state_pool)
                out = body(x, lp, l_idx, cache, ctx, state)
                state_pool = state.pool
            x, c = out if family.counts else (out, None)
            if c is not None:
                counts = counts + c
            return (x, cache.pages, counts, state_pool,
                    cache.rings and cache.rings.pool), None

        with jax.named_scope(stack.scope) if stack.scope \
                else contextlib.nullcontext():
            (x, kv_pages, counts, state_pool, rings), _ = jax.lax.scan(
                layer_step, (x, kv_pages, counts, state_pool, rings),
                (stack.params, jnp.arange(
                    stack.layers.start, stack.layers.stop, dtype=jnp.int32)))

    # verify_mode: the accept test compares the target's greedy chain with
    # the draft candidates position by position: no last-token gather
    logits = family.head(
        params, x, (lambda x: x) if verify_mode
        else (lambda x: jnp.take(x, batch["logit_idx"], axis=0)))    # [S, V]
    logits = logits.astype(jnp.float32)
    if has_slot:
        kv_pages = (kv_pages, tuple(state_pool or ())
                    + (() if rings is None else (rings,)))
    return (logits, kv_pages, counts) if family.counts \
        else (logits, kv_pages)


def build_ragged_step(family: ServingFamily, *, jit: bool = True, **step):
    """Jitted step with a donated page pool (the CUDA-graph analogue: one
    compiled program reused for every batch; reference engine.py:494
    _create_cuda_graph).  ``jit=False`` returns the raw traceable fn (for
    embedding in the fused decode loop); ``step`` is what
    :func:`ragged_forward` takes."""
    fn = partial(ragged_forward, family=family, **step)
    return jax.jit(fn, donate_argnums=(1,)) if jit else fn


def build_verify_step(family: ServingFamily, *, max_q: int, num_blocks: int,
                      max_seqs: int = 0, max_blocks: int = 0,
                      jit: bool = True, **step):
    """Spec-dec verify pass: score a ragged window of (seed + K draft)
    tokens per sequence and return the target model's greedy argmax at
    EVERY flat position, plus per-sequence non-finite flags.

    The device→host transfer is two small int/bool vectors, not a
    ``[T, vocab]`` logits tensor: the host-side accept test only needs the
    argmax chain (greedy spec-dec is exact by construction — the argmax at
    the seed position IS the token vanilla decode would have produced, and
    each accepted draft position extends the chain under the identical
    causal context), and the non-finite flags feed the serving decode
    watchdog so a NaN-poisoned sequence is isolated in verify windows
    exactly as in fused decode windows.

    Returns jitted ``(params, kv_pages, packed_meta) →
    (greedy [max_q] int32, nonfinite [max_seqs] bool, kv_pages)``; ``step``
    is the rest of what :func:`build_ragged_step` takes.
    """
    step_fn = build_ragged_step(family, max_q=max_q, num_blocks=num_blocks,
                                max_seqs=max_seqs, max_blocks=max_blocks,
                                jit=False, verify_mode=True, **step)
    layout = pack_layout(max_q, max_seqs, max_blocks)

    def field(meta, name):
        off, shape = layout[name]
        return meta[off:off + math.prod(shape)]

    def step(params, kv_pages, meta):
        logits, new_pages = step_fn(params, kv_pages, meta)   # [T, V]
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # per-sequence poison flag over REAL tokens only: padded rows carry
        # the pad-page sentinel and alias seq_of_token to the last row, so
        # an unmasked scatter would blame row max_seqs-1 for pad garbage
        valid = field(meta, "page_of_token") < num_blocks
        bad_tok = ~jnp.all(jnp.isfinite(logits), axis=-1) & valid
        bad_seq = jnp.zeros(max_seqs, jnp.bool_).at[
            field(meta, "seq_of_token")].max(bad_tok)
        return greedy, bad_seq, new_pages

    return jax.jit(step, donate_argnums=(1,)) if jit else step


def sample_tokens(logits, rng, temperature: float = 0.0, top_k: int = 0):
    """On-device token selection: argmax, temperature, or top-k sampling.
    ``logits`` [S, V] → int32 [S].  ``rng`` may be None for greedy."""
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / temperature
    if top_k and top_k > 0:
        vals, idx = jax.lax.top_k(scaled, top_k)
        choice = jax.random.categorical(rng, vals, axis=-1)
        return jnp.take_along_axis(idx, choice[:, None],
                                   axis=-1)[:, 0].astype(jnp.int32)
    return jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)


def build_decode_loop(family: ServingFamily, *, max_q: int, max_seqs: int,
                      max_blocks: int, block_size: int, num_blocks: int,
                      steps: int, temperature: float = 0.0, top_k: int = 0,
                      jit: bool = True, **step):
    """Fused multi-step greedy/sampling decode: ``steps`` forward+select
    iterations in ONE compiled program (lax.scan), with the batch metadata
    advanced on device between iterations.

    Why: the host-driven put()/argmax loop pays a host↔device round trip per
    token — the kernel-launch overhead the reference kills with CUDA graphs
    (engine.py:494).  Here the whole decode window is
    device-resident: token i+1's embedding lookup consumes the sampled token
    of step i without ever leaving HBM, selection (argmax / temperature /
    top-k — :func:`sample_tokens`) runs on device, and the advanced metadata
    is RETURNED so the engine can chain the next window off the device state
    without a host repack (continuous decode).

    Requires a DECODE-ONLY batch laid out row-major (sequence i's single
    query token at flat index i — what RaggedBatchWrapper.finalize produces
    for 1-token-per-seq batches), with KV pages pre-allocated for the full
    window so the block table is static across the loop; only tokens /
    page_of / off_of / positions / ctx lengths advance, and those are
    recomputed from the block table on device.

    Returns jitted (params, kv_pages, packed_meta, rng) →
    (tokens [steps, max_seqs] int32, kv_pages, advanced_meta,
    nonfinite [max_seqs] bool), and where the family counts
    (``family.counts``) the window's summed counts as a fifth: they come
    back with the window's tokens, no new sync.  ``nonfinite[i]`` is True
    when sequence i's logits went non-finite at ANY step of the window —
    the signal the serving decode watchdog uses to flush ONLY the poisoned
    requests
    (kernel-level NaN isolation guarantees a poisoned sequence cannot
    contaminate its batchmates; this flag extends the isolation to the
    scheduler, which would otherwise keep decoding garbage).  ``step`` is
    the rest of what :func:`build_ragged_step` takes."""
    step_fn = build_ragged_step(family, max_q=max_q, num_blocks=num_blocks,
                                max_seqs=max_seqs, max_blocks=max_blocks,
                                jit=False, decode_mode=True, **step)
    # (a family with state: its rows' slots ride behind the block table and
    # do not advance; ``kv_pages`` below is the pair of pools)
    layout = pack_layout(max_q, max_seqs, max_blocks,
                         bool(family.slot_kinds))
    NB, bs = max_blocks, block_size
    S = max_seqs
    # A decode row costs one flat token, so at most min(max_seqs, max_q)
    # rows can be live — and the per-token fields are only max_q long.
    # Writing S values past a shorter field would silently corrupt the
    # adjacent packed metadata (rows >= SW can never be admitted: the
    # wrapper's can_fit caps tokens at max_q).
    SW = min(S, max_q)
    pad_page = num_blocks                       # wrapper's pad sentinel

    def field(meta, name, n):
        off = layout[name][0]
        return jax.lax.dynamic_slice_in_dim(meta, off, n)

    def set_field(meta, name, vals):
        off = layout[name][0]
        return jax.lax.dynamic_update_slice_in_dim(meta, vals, off, axis=0)

    def advance(meta, new_toks):
        """Next step's metadata: row i's token advances to position pos+1;
        its cache page/offset are re-derived from the (static) block table."""
        q_len = field(meta, "q_len", SW)
        active = (q_len > 0).astype(jnp.int32)            # [SW]
        pos = field(meta, "pos_of_token", SW) + active
        ctx = field(meta, "ctx_len", SW) + active
        bt = field(meta, "block_table", S * NB).reshape(S, NB)[:SW]
        blk = jnp.take_along_axis(bt, (pos // bs)[:, None], axis=1)[:, 0]
        page = jnp.where(active == 1, blk, pad_page)
        off = jnp.where(active == 1, pos % bs, 0)
        tok = jnp.where(active == 1, new_toks[:SW], 0)
        meta = set_field(meta, "tokens", tok)
        meta = set_field(meta, "page_of_token", page)
        meta = set_field(meta, "off_of_token", off)
        meta = set_field(meta, "pos_of_token", pos)
        meta = set_field(meta, "ctx_len", ctx)
        return meta

    counts = family.counts

    def loop(params, kv_pages, meta, rng):
        stats0 = jnp.zeros((counts.size,), jnp.int32) \
            if counts else None

        def body(carry, _):
            pages, meta, rng, bad, stats = carry
            if counts:
                logits, pages, step_counts = step_fn(params, pages, meta)
                stats = stats + step_counts
            else:
                logits, pages = step_fn(params, pages, meta)
            # per-sequence poison flag: a NaN/Inf logit row marks ONLY its
            # own sequence (sticky across the window's steps)
            bad = bad | ~jnp.all(jnp.isfinite(logits), axis=-1)
            if temperature > 0:
                rng, sub = jax.random.split(rng)
            else:
                sub = rng
            toks = sample_tokens(logits, sub, temperature=temperature,
                                 top_k=top_k)
            meta = advance(meta, toks)
            return (pages, meta, rng, bad, stats), toks

        bad0 = jnp.zeros(max_seqs, jnp.bool_)
        (kv_pages, meta, _, bad, stats), toks = jax.lax.scan(
            body, (kv_pages, meta, rng, bad0, stats0), None, length=steps)
        if stats0 is None:
            return toks, kv_pages, meta, bad
        return toks, kv_pages, meta, bad, stats

    return jax.jit(loop, donate_argnums=(1,)) if jit else loop
