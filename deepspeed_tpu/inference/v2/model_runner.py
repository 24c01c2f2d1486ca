"""Ragged-batch model execution (reference: inference/v2/model_implementations/
inference_transformer_base.py:48 + the ragged_ops kernel chain in §3.4:
qkv → linear_blocked_kv_rotary (paged KV append) → blocked_flash → logits_gather).

One jitted step serves ANY mix of prefill and decode under fixed budgets
(max_tokens/max_seqs/max_blocks), with the paged KV cache donated through the
call so the update is in-place in HBM.

Cache layout (see ragged/kv_cache.py): ONE flat page pool
``[L*num_blocks + 1, page_size, 2*KV, hd]`` shared by all layers — layer l's
page table is ``block_table + l*num_blocks`` (plain metadata arithmetic, no
in-kernel layer index), and the final page is the shared trash page padded
tokens write into.

Pipeline per layer over the flat token axis [T]:
  rmsnorm → qkv proj → RoPE (per-token absolute positions) → paged KV append
  → Pallas paged attention over the sequence's page table → o proj → MLP.
Logits are computed only for each sequence's last token (logits_gather).

Two attention impls:
  "paged"  — Pallas ragged paged-attention kernel (kernels/ragged_ops.py);
             flat-token grid, in-kernel context walk, double-buffered page
             DMA; HBM traffic O(cached tokens).
  "gather" — dense page-gather reference path (O(S·C) HBM per layer); kept
             as the numerics oracle for kernel tests.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ...models.transformer import TransformerConfig, rms_norm
from .kernels.ragged_ops import (
    decode_attention,
    paged_kv_append,
    ragged_paged_attention,
    verify_window_attention,
)
from .ragged.ragged_wrapper import pack_layout


def _rope_at(pos, rotary_dim, theta):
    """cos/sin tables gathered at arbitrary positions [T] → [T, rd/2]."""
    inv = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                           / rotary_dim))
    freqs = pos.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(freqs), jnp.sin(freqs)


def _apply_rope_flat(x, cos, sin, rotary_dim=None, style="neox"):
    """x [T, H, hd] with per-token tables [T, rd/2]; partial rotary (phi)
    and interleaved-pair style (gptj) supported, mirroring
    families._rope_partial for the flat serving token axis."""
    hd = x.shape[-1]
    rd = hd if rotary_dim is None else rotary_dim
    rot, passthrough = x[..., :rd], x[..., rd:]
    c = cos[:, None, :].astype(x.dtype)
    s = sin[:, None, :].astype(x.dtype)
    if style == "gptj":
        x1, x2 = rot[..., 0::2], rot[..., 1::2]
        rot = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c],
                        axis=-1).reshape(rot.shape)
    else:
        x1, x2 = jnp.split(rot, 2, axis=-1)
        rot = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([rot, passthrough], axis=-1) if rd < hd else rot


def _attend_gather(q_seq, kv_pages, page_table, q_len, ctx_len,
                   scale, alibi=None, alibi_scaled=False):
    """Dense page-gather reference attention (the numerics oracle).

    Gathers the full padded context per sequence straight from the page pool
    (``page_table`` rows are ABSOLUTE physical page ids — for a multi-layer
    pool pass ``block_table + layer*num_blocks``) and runs masked softmax
    attention.  ``alibi`` ([H] slopes) adds the position bias (bloom
    semantics; the falcon ``alibi_scaled`` variant computes bf16(slope·pos)
    pre-scaling).

    q_seq: [S, mq, H, hd]; kv_pages: [NP_total, ps, 2KV, hd];
    page_table: [S, NB] → output [S, mq, H, hd] (f32).
    """
    S, mq, H, hd = q_seq.shape
    _, ps, ckv, _ = kv_pages.shape
    KV = ckv // 2
    NB = page_table.shape[1]
    C = NB * ps
    ctx_pos = jnp.arange(C, dtype=jnp.int32)
    pg = jnp.take_along_axis(
        page_table, (ctx_pos // ps)[None, :].repeat(S, 0), axis=1)   # [S, C]
    off = jnp.broadcast_to((ctx_pos % ps)[None, :], (S, C))
    ctx = kv_pages[pg, off]                           # [S, C, 2KV, hd]
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
    return jnp.einsum("shqc,schd->sqhd", probs, v_ctx.astype(jnp.float32))


def _unpack_batch(batch, max_q, max_seqs, max_blocks):
    """Packed int32 metadata vector → field dict via static on-device
    slices (one H2D transfer per forward; see ragged_wrapper.pack_layout)."""
    layout = pack_layout(max_q, max_seqs, max_blocks)
    packed = batch
    batch = {}
    for name, (off, shape) in layout.items():
        if name == "_total":
            continue
        n = 1
        for d in shape:
            n *= d
        batch[name] = packed[off:off + n].reshape(shape)
    return batch


def _ragged_attend(q, kv_pages, batch, *, attn_impl, layer, num_blocks,
                   max_q, scale, alibi=None, alibi_scaled=False,
                   block_q=128, pages_per_chunk=8, decode_mode=False,
                   verify_mode=False):
    """Shared ragged attention dispatch: the flat-token Pallas paged kernel,
    the decode-specialized fast path, the spec-dec verify-window path, or
    the dense page-gather oracle.  q: [T, H, hd] → [T, H*hd].

    ``kv_pages`` is the FULL multi-layer page pool; ``layer`` (traced) picks
    this layer's pages via table arithmetic — no per-layer slice
    materialization.

    ``decode_mode`` asserts the row-major decode layout (sequence i's single
    query token at flat index i, rows past n_seqs padded with ctx_len 0 —
    what the fused decode loop's batches look like by construction) and
    dispatches the one-token-per-sequence kernel instead of burning a full
    ``block_q`` query tile per decoding sequence.

    ``verify_mode`` (mutually exclusive with ``decode_mode``) is the
    speculative-decoding seam: rows are short multi-token windows
    (seed + K draft candidates) and dispatch goes through
    :func:`verify_window_attention`, the ragged prefill kernel's multi-row
    scoring with the query tile clamped to the window's flat token budget.
    """
    assert not (decode_mode and verify_mode), \
        "decode_mode and verify_mode are mutually exclusive dispatches"
    T, H, hd = q.shape
    KV = kv_pages.shape[2] // 2
    q_len, ctx_len = batch["q_len"], batch["ctx_len"]
    pt_l = batch["block_table"] + layer * num_blocks          # [S, NB]
    if attn_impl == "paged" and verify_mode:
        out = verify_window_attention(
            q, kv_pages, ctx_len, pt_l, batch["cu_q_lens"],
            num_kv_heads=KV, scale=scale, alibi=alibi,
            alibi_scaled=alibi_scaled, block_q=block_q,
            pages_per_chunk=pages_per_chunk)
        return out.reshape(T, H * hd)
    if attn_impl == "paged" and decode_mode:
        S = q_len.shape[0]
        SW = min(S, T)
        out = decode_attention(
            q[:SW], kv_pages, ctx_len[:SW], pt_l[:SW], num_kv_heads=KV,
            scale=scale, alibi=alibi, alibi_scaled=alibi_scaled,
            pages_per_chunk=pages_per_chunk)
        if T > SW:
            out = jnp.pad(out, ((0, T - SW), (0, 0), (0, 0)))
        return out.reshape(T, H * hd)
    if attn_impl == "paged":
        out = ragged_paged_attention(
            q, kv_pages, ctx_len, pt_l, batch["cu_q_lens"],
            num_kv_heads=KV, scale=scale, alibi=alibi,
            alibi_scaled=alibi_scaled, block_q=block_q,
            pages_per_chunk=pages_per_chunk)
        return out.reshape(T, H * hd)
    q_idx = jnp.clip(batch["q_offset"][:, None] + jnp.arange(max_q)[None, :],
                     0, T - 1)
    q_seq = jnp.take(q.reshape(T, -1), q_idx.reshape(-1), axis=0
                     ).reshape(-1, max_q, H, hd)             # [S, mq, H, hd]
    o_seq = _attend_gather(q_seq, kv_pages, pt_l, q_len, ctx_len, scale,
                           alibi=alibi, alibi_scaled=alibi_scaled
                           ).astype(q.dtype)
    within = jnp.clip(
        jnp.arange(T) - jnp.take(batch["q_offset"], batch["seq_of_token"]),
        0, max_q - 1)
    return o_seq[batch["seq_of_token"], within].reshape(T, H * hd)


def _layer_pages(page_of_token, layer, num_blocks, trash_page):
    """Layer-relative token pages → absolute pool pages; the wrapper's
    pad sentinel (>= num_blocks) routes to the shared trash page."""
    return jnp.where(page_of_token < num_blocks,
                     page_of_token + layer * num_blocks, trash_page)


def ragged_forward(params: Dict, kv_pages: jnp.ndarray, batch,
                   cfg: TransformerConfig, max_q: int, num_blocks: int,
                   attn_impl: str = "paged", max_seqs: int = 0,
                   max_blocks: int = 0, block_q: int = 128,
                   pages_per_chunk: int = 8, decode_mode: bool = False,
                   verify_mode: bool = False,
                   kv_replicate=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """→ (last-token logits [max_seqs, V], new kv_pages); with
    ``verify_mode`` → (ALL-position logits [max_q, V], new kv_pages) — the
    spec-dec verify pass needs the target's greedy argmax at every window
    position, not just each sequence's last token."""
    batch = _unpack_batch(batch, max_q, max_seqs, max_blocks)
    tokens = batch["tokens"]              # [T]
    page_of = batch["page_of_token"]      # [T] layer-relative
    off_of = batch["off_of_token"]        # [T]
    pos = batch["pos_of_token"]           # [T]
    logit_idx = batch["logit_idx"]        # [S]

    T = tokens.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dtype = params["layers"]["q_proj"]["kernel"].dtype
    scale = 1.0 / math.sqrt(hd)
    trash_page = kv_pages.shape[0] - 1

    x = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(dtype)  # [T, D]
    cos, sin = _rope_at(pos, hd, cfg.rope_theta)

    # ragged-padding mask: padded tokens carry the pad-page sentinel
    batch_valid = page_of < num_blocks

    def layer_step(carry, inputs):
        # The FULL page pool rides the carry: the append is an in-place
        # scatter of T rows and the paged kernel reads pages straight from
        # the pool.  Scanning the cache as xs/ys instead would slice-copy
        # one full layer per iteration AND restack the whole cache per
        # forward — O(cache) HBM per decode step.
        x, kv_pages = carry
        lp, l_idx = inputs
        h = rms_norm(x, lp["attn_norm"]["scale"], cfg.norm_eps)

        def proj(p, n):
            y = h @ p["kernel"]
            if "bias" in p:
                y = y + p["bias"]
            return y.reshape(T, n, hd)

        q = proj(lp["q_proj"], H)
        k = proj(lp["k_proj"], KV)
        v = proj(lp["v_proj"], KV)
        q = _apply_rope_flat(q, cos, sin)
        k = _apply_rope_flat(k, cos, sin)
        kv_pages = paged_kv_append(
            kv_pages, k, v,
            _layer_pages(page_of, l_idx, num_blocks, trash_page), off_of,
            replicate=kv_replicate)

        o_flat = _ragged_attend(q, kv_pages, batch, attn_impl=attn_impl,
                                layer=l_idx, num_blocks=num_blocks,
                                max_q=max_q, scale=scale, block_q=block_q,
                                pages_per_chunk=pages_per_chunk,
                                decode_mode=decode_mode,
                                verify_mode=verify_mode).astype(dtype)
        x = x + o_flat @ lp["o_proj"]["kernel"]
        h = rms_norm(x, lp["mlp_norm"]["scale"], cfg.norm_eps)
        if cfg.num_experts > 1:
            # MoE serving (moe_gather/moe_scatter analogue): sparse-slot
            # dispatch over flat ragged tokens; padded tokens (pad-page
            # sentinel) are excluded from expert capacity.
            from ...moe.sharded_moe import moe_mlp_block

            mlp_out, _ = moe_mlp_block(
                lp, h, k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                dispatch_impl="sparse", valid=batch_valid)
            x = x + mlp_out
        else:
            gate = jax.nn.silu(h @ lp["gate_proj"]["kernel"])
            up = h @ lp["up_proj"]["kernel"]
            x = x + (gate * up) @ lp["down_proj"]["kernel"]
        return (x, kv_pages), None

    (x, new_pages), _ = jax.lax.scan(
        layer_step, (x, kv_pages),
        (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))

    x = rms_norm(x, params["norm_f"]["scale"], cfg.norm_eps)
    # verify_mode: every window position needs its argmax (the spec-dec
    # accept test compares the target's greedy chain against the draft
    # candidates position by position), so skip the last-token gather
    last = x if verify_mode else jnp.take(x, logit_idx, axis=0)    # [S, D]
    if cfg.tie_embeddings:
        logits = last @ params["embed"]["embedding"].T
    else:
        logits = last @ params["lm_head"]["kernel"]
    return logits.astype(jnp.float32), new_pages


def ragged_forward_universal(params: Dict, kv_pages: jnp.ndarray, batch, cfg,
                             max_q: int, num_blocks: int,
                             attn_impl: str = "paged", max_seqs: int = 0,
                             max_blocks: int = 0, block_q: int = 128,
                             pages_per_chunk: int = 8,
                             decode_mode: bool = False,
                             verify_mode: bool = False, kv_replicate=None
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Paged ragged serving for the universal (ArchConfig) families —
    gpt2/gptj/opt/bloom/falcon/phi serve through the SAME put/query/flush
    engine and Pallas paged kernel as the native families (reference:
    inference/v2/model_implementations/{falcon,phi,opt}/ per-arch ragged
    models).  Arch knobs handled on the flat token axis: learned positions
    (+opt's offset), ALiBi inside the kernel (bloom + falcon-scaled
    variants), partial/interleaved rotary, parallel-attn, dual-LN,
    LayerNorm-with-bias, gelu/relu/glu MLPs, lm-head bias."""
    from ...models.families import ArchConfig, alibi_slopes, layer_norm

    assert isinstance(cfg, ArchConfig)
    batch = _unpack_batch(batch, max_q, max_seqs, max_blocks)
    tokens = batch["tokens"]
    page_of = batch["page_of_token"]
    off_of = batch["off_of_token"]
    pos = batch["pos_of_token"]
    logit_idx = batch["logit_idx"]

    T = tokens.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dtype = params["layers"]["q_proj"]["kernel"].dtype
    scale = 1.0 / math.sqrt(hd)
    trash_page = kv_pages.shape[0] - 1

    def norm(x, p):
        if cfg.norm == "rmsnorm":
            return rms_norm(x, p["scale"], cfg.norm_eps)
        return layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)

    def proj(h, p, n):
        y = h @ p["kernel"]
        if "bias" in p:
            y = y + p["bias"]
        return y.reshape(T, n, hd)

    x = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(dtype)
    if cfg.pos == "learned":
        x = x + jnp.take(params["pos_embed"]["embedding"],
                         pos + cfg.pos_offset, axis=0).astype(dtype)
    if cfg.embed_layernorm:
        x = norm(x, params["embed_ln"])

    cos = sin = None
    if cfg.pos == "rope":
        cos, sin = _rope_at(pos, cfg.rotary_dim, cfg.rope_theta)
    alibi = alibi_slopes(H) if cfg.pos == "alibi" else None

    def layer_step(carry, inputs):
        # page-pool carry: see ragged_forward.layer_step
        x, kv_pages = carry
        lp, l_idx = inputs
        h_attn_in = norm(x, lp["ln1"])
        q = proj(h_attn_in, lp["q_proj"], H)
        k = proj(h_attn_in, lp["k_proj"], KV)
        v = proj(h_attn_in, lp["v_proj"], KV)
        if cfg.pos == "rope":
            q = _apply_rope_flat(q, cos, sin, cfg.rotary_dim, cfg.rope_style)
            k = _apply_rope_flat(k, cos, sin, cfg.rotary_dim, cfg.rope_style)
        kv_pages = paged_kv_append(
            kv_pages, k, v,
            _layer_pages(page_of, l_idx, num_blocks, trash_page), off_of,
            replicate=kv_replicate)

        o_flat = _ragged_attend(q, kv_pages, batch, attn_impl=attn_impl,
                                layer=l_idx, num_blocks=num_blocks,
                                max_q=max_q, scale=scale, alibi=alibi,
                                alibi_scaled=cfg.alibi_scaled,
                                block_q=block_q,
                                pages_per_chunk=pages_per_chunk,
                                decode_mode=decode_mode,
                                verify_mode=verify_mode).astype(dtype)
        attn_out = o_flat @ lp["o_proj"]["kernel"]
        if "bias" in lp["o_proj"]:
            attn_out = attn_out + lp["o_proj"]["bias"]

        if cfg.parallel_attn:
            h_mlp_in = norm(x, lp["ln2"]) if cfg.dual_ln else h_attn_in
        else:
            x = x + attn_out
            h_mlp_in = norm(x, lp["ln2"])

        if cfg.mlp == "silu_glu":
            gate = jax.nn.silu(h_mlp_in @ lp["gate_proj"]["kernel"])
            up = h_mlp_in @ lp["up_proj"]["kernel"]
            mlp_out = (gate * up) @ lp["down_proj"]["kernel"]
        else:
            act = (lambda y: jax.nn.gelu(y, approximate=not cfg.gelu_exact)) \
                if cfg.mlp == "gelu" else jax.nn.relu
            h1 = h_mlp_in @ lp["fc1"]["kernel"]
            if "bias" in lp["fc1"]:
                h1 = h1 + lp["fc1"]["bias"]
            mlp_out = act(h1) @ lp["fc2"]["kernel"]
            if "bias" in lp["fc2"]:
                mlp_out = mlp_out + lp["fc2"]["bias"]

        x = x + attn_out + mlp_out if cfg.parallel_attn else x + mlp_out
        return (x, kv_pages), None

    (x, new_pages), _ = jax.lax.scan(
        layer_step, (x, kv_pages),
        (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))

    x = norm(x, params["norm_f"])
    # verify_mode: all-position logits (see ragged_forward)
    last = x if verify_mode else jnp.take(x, logit_idx, axis=0)
    if cfg.tie_embeddings:
        logits = last @ params["embed"]["embedding"].T
    else:
        logits = last @ params["lm_head"]["kernel"]
        if "bias" in params["lm_head"]:
            logits = logits + params["lm_head"]["bias"]
    return logits.astype(jnp.float32), new_pages


def ragged_forward_xing(params: Dict, kv_pages: jnp.ndarray, batch, cfg,
                        max_q: int, num_blocks: int,
                        attn_impl: str = "paged", max_seqs: int = 0,
                        max_blocks: int = 0, block_q: int = 128,
                        pages_per_chunk: int = 8, decode_mode: bool = False,
                        verify_mode: bool = False, kv_replicate=None):
    """Paged ragged serving for the Xing4 family (models/xing4.py): latent
    (MLA) pages, sigmoid-routed experts beside a shared expert, and
    hyper-connection residual streams.  → (last-token logits [max_seqs, V],
    new pages, pairs per expert [E] int32 summed over the expert layers).

    Two scans, because the layers are not all alike: the leading dense
    stack, then the expert stack.  The carry is ``[T, hc_mult, D]`` float32.  The
    pool is ``[L·num_blocks + 1, page_size, latent_row]``; attention runs in
    the absorbed form against it (kernels/mla_ops.py) for prefill chunks and
    decode alike."""
    from ...models import xing4 as X
    from ...moe.dropless import sigmoid_moe_block
    from .kernels import mla_ops

    if verify_mode or kv_replicate is not None:
        raise NotImplementedError(
            "xing4 serving: speculative verify windows and tensor-parallel "
            "params are not supported with latent pages")
    batch = _unpack_batch(batch, max_q, max_seqs, max_blocks)
    tokens = batch["tokens"]
    page_of = batch["page_of_token"]
    off_of = batch["off_of_token"]
    pos = batch["pos_of_token"]
    q_len, ctx_len = batch["q_len"], batch["ctx_len"]
    T = tokens.shape[0]
    R, scale = cfg.kv_lora_rank, cfg.softmax_scale
    dtype = params["embed"]["embedding"].dtype
    trash_page = kv_pages.shape[0] - 1
    batch_valid = page_of < num_blocks
    E = cfg.n_routed_experts

    with jax.named_scope("embed"):
        x = jnp.take(params["embed"]["embedding"], tokens, axis=0
                     ).astype(jnp.float32)
        # the embedding is copied into every residual stream; the carry
        # is float32 (models/xing4.hc_sublayer)
        x = jnp.broadcast_to(x[:, None, :], (T, cfg.hc_mult, x.shape[-1]))
    cos, sin = X.rope_at(pos, cfg)

    def attend(q_abs, pages, l_idx):
        pt_l = batch["block_table"] + l_idx * num_blocks
        if attn_impl == "paged" and decode_mode:
            SW = min(q_len.shape[0], T)
            out = mla_ops.mla_decode_attention(
                q_abs[:SW], pages, ctx_len[:SW], pt_l[:SW], rank=R,
                scale=scale, pages_per_chunk=pages_per_chunk)
            return jnp.pad(out, ((0, T - SW), (0, 0), (0, 0)))
        if attn_impl == "paged":
            return mla_ops.mla_ragged_prefill(
                q_abs, pages, ctx_len, pt_l, batch["cu_q_lens"], rank=R,
                scale=scale, block_q=min(block_q, 16),
                pages_per_chunk=pages_per_chunk)
        q_idx = jnp.clip(batch["q_offset"][:, None]
                         + jnp.arange(max_q)[None, :], 0, T - 1)
        q_seq = jnp.take(q_abs, q_idx.reshape(-1), axis=0).reshape(
            (-1, max_q) + q_abs.shape[1:])
        o_seq = mla_ops.mla_attend_dense(q_seq, pages, pt_l, q_len, ctx_len,
                                         rank=R, scale=scale).astype(dtype)
        within = jnp.clip(
            jnp.arange(T) - jnp.take(batch["q_offset"],
                                     batch["seq_of_token"]), 0, max_q - 1)
        return o_seq[batch["seq_of_token"], within]

    def layer_step(moe):
        def step(carry, inputs):
            xs, pages, pairs = carry
            lp, l_idx = inputs

            def attention(h):
                with jax.named_scope("attention/mla_q"):
                    q_nope, q_rope = X.mla_query(h, lp, cos, sin, cfg)
                    q_abs = X.mla_absorb_query(q_nope, q_rope, lp, cfg)
                with jax.named_scope("attention/mla_kv"):
                    rows = X.mla_latent(h, lp, cos, sin, cfg)
                    new_pages = mla_ops.latent_append(
                        pages, rows,
                        _layer_pages(page_of, l_idx, num_blocks, trash_page),
                        off_of)
                with jax.named_scope("attention/mla_core"):
                    o_lat = attend(q_abs, new_pages, l_idx).astype(dtype)
                    return X.mla_output(o_lat, lp, cfg), new_pages

            xs, pages = X.hc_sublayer(xs, lp["hc_attn"],
                                      lp["attn_norm"]["scale"], attention,
                                      cfg, dtype)

            def mlp(h):
                if not moe:
                    with jax.named_scope("mlp"):
                        return X.dense_mlp(
                            h, lp["gate_proj"]["kernel"],
                            lp["up_proj"]["kernel"],
                            lp["down_proj"]["kernel"]), None
                # the experts' stack rides the closure, not the scan: see
                # moe/dropless.dropless_experts
                return sigmoid_moe_block(
                    h, lp, k=cfg.num_experts_per_tok,
                    scaling=cfg.routed_scaling_factor,
                    renormalise=cfg.norm_topk_prob, valid=batch_valid,
                    experts=params["moe_layers"]["experts"],
                    layer=l_idx - cfg.num_dense_layers)

            xs, layer_pairs = X.hc_sublayer(xs, lp["hc_mlp"],
                                            lp["mlp_norm"]["scale"], mlp,
                                            cfg, dtype)
            if moe:
                pairs = pairs + layer_pairs
            return (xs, pages, pairs), None

        return step

    carry = (x, kv_pages, jnp.zeros((E,), jnp.int32))
    with jax.named_scope("layers"):
        Ld = cfg.num_dense_layers
        if Ld:
            carry, _ = jax.lax.scan(
                layer_step(False), carry,
                (params["dense_layers"], jnp.arange(Ld, dtype=jnp.int32)))
        if cfg.num_moe_layers:
            carry, _ = jax.lax.scan(
                layer_step(True), carry,
                ({k: v for k, v in params["moe_layers"].items()
                  if k != "experts"},
                 jnp.arange(Ld, cfg.num_layers, dtype=jnp.int32)))
    x, new_pages, pairs = carry

    with jax.named_scope("final_norm"):
        # the streams are summed before the final norm
        x = rms_norm(jnp.sum(x, axis=1),
                     params["norm_f"]["scale"].astype(jnp.float32),
                     cfg.norm_eps).astype(dtype)
    with jax.named_scope("lm_head"):
        last = jnp.take(x, batch["logit_idx"], axis=0)
        if cfg.tie_embeddings:
            logits = last @ params["embed"]["embedding"].T
        else:
            logits = last @ params["lm_head"]["kernel"]
    return logits.astype(jnp.float32), new_pages, pairs


def build_ragged_step(cfg, max_q: int, num_blocks: int,
                      attn_impl: str = "paged", max_seqs: int = 0,
                      max_blocks: int = 0, block_q: int = 128,
                      pages_per_chunk: int = 8, jit: bool = True,
                      decode_mode: bool = False, verify_mode: bool = False,
                      kv_replicate=None):
    """Jitted step with a donated page pool (the CUDA-graph analogue: one
    compiled program reused for every batch; reference engine.py:494
    _create_cuda_graph).  Dispatches on the config type: TransformerConfig →
    native llama-family runner; ArchConfig → universal per-arch runner.
    ``jit=False`` returns the raw traceable fn (for embedding in the fused
    decode loop); ``decode_mode=True`` dispatches the one-token-per-sequence
    decode attention path (requires row-major decode batches);
    ``verify_mode=True`` dispatches the spec-dec verify-window path (short
    multi-token rows, ALL-position logits — see :func:`build_verify_step`
    for the argmax/accept wrapper); ``kv_replicate`` (replicated
    NamedSharding) must be passed when params are TP-sharded — see
    :func:`paged_kv_append`."""
    from ...models.families import ArchConfig
    from ...models.xing4 import Xing4Config

    assert attn_impl in ("paged", "gather"), \
        f"attn_impl must be 'paged' or 'gather', got {attn_impl!r}"
    body = ragged_forward_universal if isinstance(cfg, ArchConfig) \
        else ragged_forward_xing if isinstance(cfg, Xing4Config) \
        else ragged_forward
    fn = partial(body, cfg=cfg, max_q=max_q, num_blocks=num_blocks,
                 attn_impl=attn_impl, max_seqs=max_seqs,
                 max_blocks=max_blocks, block_q=block_q,
                 pages_per_chunk=pages_per_chunk, decode_mode=decode_mode,
                 verify_mode=verify_mode, kv_replicate=kv_replicate)
    return jax.jit(fn, donate_argnums=(1,)) if jit else fn


def build_verify_step(cfg, *, max_q: int, num_blocks: int,
                      attn_impl: str = "paged", max_seqs: int = 0,
                      max_blocks: int = 0, block_q: int = 128,
                      pages_per_chunk: int = 8, jit: bool = True,
                      kv_replicate=None):
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
    (greedy [max_q] int32, nonfinite [max_seqs] bool, kv_pages)``.
    """
    step_fn = build_ragged_step(cfg, max_q=max_q, num_blocks=num_blocks,
                                attn_impl=attn_impl, max_seqs=max_seqs,
                                max_blocks=max_blocks, block_q=block_q,
                                pages_per_chunk=pages_per_chunk, jit=False,
                                verify_mode=True, kv_replicate=kv_replicate)
    layout = pack_layout(max_q, max_seqs, max_blocks)

    def field(meta, name):
        off, shape = layout[name]
        n = 1
        for d in shape:
            n *= d
        return meta[off:off + n]

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


def build_decode_loop(cfg, *, max_q: int, max_seqs: int, max_blocks: int,
                      block_size: int, num_blocks: int, attn_impl: str,
                      steps: int, temperature: float = 0.0,
                      block_q: int = 128, pages_per_chunk: int = 8,
                      top_k: int = 0, jit: bool = True, kv_replicate=None):
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
    nonfinite [max_seqs] bool).  ``nonfinite[i]`` is True when sequence
    i's logits went non-finite at ANY step of the window — the signal the
    serving decode watchdog uses to flush ONLY the poisoned requests
    (kernel-level NaN isolation guarantees a poisoned sequence cannot
    contaminate its batchmates; this flag extends the isolation to the
    scheduler, which would otherwise keep decoding garbage)."""
    step_fn = build_ragged_step(cfg, max_q=max_q, num_blocks=num_blocks,
                                attn_impl=attn_impl, max_seqs=max_seqs,
                                max_blocks=max_blocks, block_q=block_q,
                                pages_per_chunk=pages_per_chunk, jit=False,
                                decode_mode=True, kv_replicate=kv_replicate)
    layout = pack_layout(max_q, max_seqs, max_blocks)
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

    #: per-expert pair counts ride the window's carry for a family that
    #: routes (they come back with the window's tokens: no new sync)
    n_experts = getattr(cfg, "n_routed_experts", 0)

    def loop(params, kv_pages, meta, rng):
        stats0 = jnp.zeros((n_experts,), jnp.int32) if n_experts else None

        def body(carry, _):
            pages, meta, rng, bad, stats = carry
            logits, pages, *extra = step_fn(params, pages, meta)
            if extra:       # xing4: (token, choice) pairs per expert
                stats = stats + extra[0]
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
