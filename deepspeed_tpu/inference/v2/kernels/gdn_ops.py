"""Gated DeltaNet over per-sequence recurrent state (Qwen3-Next's linear
attention layers), on the paged serving path's flat token axis.

A sequence owns, in each state layer, one SLOT of the state pool
(``ragged/state_pool.py``): the delta-rule state ``S [H, dk, dv]`` float32
and the causal convolution's last ``K - 1`` inputs ``[K - 1, C]``.  The pool
is ``[state_layers * slots + 1, ...]``; its last row is the trash slot that
padded rows read and write.  ``rows`` below are ABSOLUTE pool rows, one per
sequence row of the batch (``model_runner._LayerState`` makes them).

Per head, with ``a_t = exp(g_t)``:
``S_t = a_t S_{t-1} + k_t (x) b_t (v_t - (a_t S_{t-1})^T k_t)``,
``o_t = S_t^T q_t``.

Three forms of the same recurrence (:func:`gdn_mix` dispatches):

``gdn_decode``         one token a sequence: ONE Pallas kernel a layer, the
                       state read once and written once IN PLACE
                       (``input_output_aliases``), a grid step a (sequence,
                       block of heads).  The update is ~110 vector
                       operations a head on a ``[dk, dv]`` tile; nothing
                       goes through the MXU (every (sequence, head) has its
                       own state, so a matmul would have one row).
``gdn_chunk_prefill``  a ragged batch of chunks (SplitFuse): the chunked
                       (WY) form, 64 tokens at a time, ``jax.numpy`` under
                       its own name scope: a ``while`` over the batch's real
                       chunks, each reading its sequence's state from the
                       slot (zeros at position 0) and leaving the state after
                       its last token there.  A chunk is any length; decode
                       rows riding in the batch are chunks of one token.
``gdn_recurrent``      token by token over the flat batch: the numerics
                       oracle (``attn_impl="gather"``).

The convolution with its carry (:func:`causal_conv_ragged`) is ``jax.numpy``
in all three: 48 KB a sequence a layer against the state's 2 MB.
"""
from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ....telemetry.trace import get_tracer
from .ragged_ops import _interpret

_HI = jax.lax.Precision.HIGHEST
CHUNK = 64


def l2norm(x):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


# --------------------------------------------------------------------- #
# Causal depthwise convolution with a per-sequence carry
# --------------------------------------------------------------------- #
def causal_conv_ragged(x, conv_w, carry_pool, rows, *, seq_of_token,
                       q_offset, q_len, fresh):
    """``x`` [T, C] (the flat batch's new inputs), ``conv_w`` [K, C]
    (``out_t = sum_j conv_w[j] * x_{t-(K-1)+j}``), ``carry_pool`` [N, K-1,
    C] (a sequence's last K-1 inputs, oldest first), ``rows`` [S] pool rows,
    ``fresh`` [S] (the sequence's first token is at position 0: its carry is
    zeros whatever the slot holds) → (out [T, C] float32, new carry_pool).

    Rows with ``q_len == 0`` are the batch's padding and come with the
    trash row."""
    T, C = x.shape
    K = conv_w.shape[0]
    xf = x.astype(jnp.float32)
    carry = jnp.where(fresh[:, None, None], 0,
                      carry_pool[rows]).astype(jnp.float32)   # [S, K-1, C]
    within = jnp.arange(T) - q_offset[seq_of_token]             # [T]
    w = conv_w.astype(jnp.float32)
    out = w[K - 1][None, :] * xf
    for d in range(1, K):
        # the input d tokens back: in the batch, or in the carry
        back = jnp.take(xf, jnp.clip(jnp.arange(T) - d, 0, T - 1), axis=0)
        old = carry[seq_of_token, jnp.clip(K - 1 + within - d, 0, K - 2)]
        out = out + w[K - 1 - d][None, :] * jnp.where(
            (within >= d)[:, None], back, old)
    # the new carry: the sequence's last K-1 inputs after this batch
    rel = q_len[:, None] - (K - 1) + jnp.arange(K - 1)[None, :]  # [S, K-1]
    from_x = jnp.take(xf, jnp.clip(q_offset[:, None] + rel, 0, T - 1),
                      axis=0)                                    # [S,K-1,C]
    from_old = jnp.take_along_axis(
        carry, jnp.clip(rel + K - 1, 0, K - 2)[:, :, None], axis=1)
    new = jnp.where((rel >= 0)[:, :, None], from_x, from_old)
    return out, carry_pool.at[rows].set(new.astype(carry_pool.dtype))


# --------------------------------------------------------------------- #
# The recurrence, token by token (the oracle)
# --------------------------------------------------------------------- #
def _token_update(S, q, k, v, g, beta):
    """One token of every head: ``S`` [H, dk, dv], ``q``/``k`` [H, dk],
    ``v`` [H, dv], ``g``/``beta`` [H] → (S', o [H, dv])."""
    S = S * jnp.exp(g)[:, None, None]
    delta = (v - jnp.sum(S * k[:, :, None], axis=1)) * beta[:, None]
    S = S + k[:, :, None] * delta[:, None, :]
    return S, jnp.sum(S * q[:, :, None], axis=1)


def gdn_recurrent(q, k, v, g, beta, state_pool, rows, *, seq_of_token,
                  pos_of_token, valid):
    """Flat tokens in order, each through its sequence's slot.  ``q``/``k``
    [T, H, dk], ``v`` [T, H, dv], ``g``/``beta`` [T, H] float32 → (o [T, H,
    dv] float32, new state_pool)."""
    T = q.shape[0]
    trash = state_pool.shape[0] - 1
    row_of = jnp.where(valid, rows[seq_of_token], trash)

    def token(t, carry):
        out, pool = carry
        S = jnp.where(pos_of_token[t] == 0, 0.0, pool[row_of[t]])
        S, o = _token_update(S, q[t], k[t], v[t], g[t], beta[t])
        return out.at[t].set(o), pool.at[row_of[t]].set(S)

    return jax.lax.fori_loop(
        0, T, token, (jnp.zeros(v.shape, jnp.float32), state_pool))


# --------------------------------------------------------------------- #
# The chunked (WY) form over a ragged batch
# --------------------------------------------------------------------- #
def _chunk_update(S0, q, k, v, g, beta):
    """One chunk of one sequence, every head.  ``S0`` [H, dk, dv]; ``q``/
    ``k`` [C, H, dk], ``v`` [C, H, dv], ``g``/``beta`` [C, H]; positions past
    the chunk's length come zeroed (``g`` 0 too).  → (S', o [C, H, dv]).

    With ``G_i = sum_{j<=i} g_j``, ``A = strictly_lower(beta_i k_i.k_j
    exp(G_i - G_j))`` and ``T = (I + A)^-1``: ``u = T (beta v)``, ``w = T
    (beta exp(G) k)``, ``v' = u - w S0``, ``o = exp(G) q S0 +
    lower(q.k exp(G_i - G_j)) v'``, ``S' = exp(G_C) S0 + sum_i exp(G_C -
    G_i) k_i (x) v'_i``.  ``A`` is nilpotent, so ``T = prod_n (I + (-A)^(2^n))``:
    six squarings instead of a row-by-row substitution."""
    C = q.shape[0]
    dot = functools.partial(jnp.einsum, precision=_HI,
                            preferred_element_type=jnp.float32)
    G = jnp.cumsum(g, axis=0)                                    # [C, H]
    decay = jnp.exp(jnp.minimum(G[:, None, :] - G[None, :, :], 0.0))
    i, j = jnp.arange(C)[:, None, None], jnp.arange(C)[None, :, None]
    kb = k * beta[:, :, None]
    A = jnp.where(i > j, dot("ihd,jhd->ijh", kb, k) * decay, 0.0)
    neg = jnp.moveaxis(-A, -1, 0)                                # [H, C, C]
    eye = jnp.eye(C, dtype=jnp.float32)[None]
    Tm, power = eye + neg, neg
    for _ in range(max(math.ceil(math.log2(C)) - 1, 0)):
        power = dot("hij,hjk->hik", power, power)
        Tm = dot("hij,hjk->hik", Tm, eye + power)
    u = dot("hij,jhd->ihd", Tm, v * beta[:, :, None])            # [C, H, dv]
    w = dot("hij,jhd->ihd", Tm, kb * jnp.exp(G)[:, :, None])     # [C, H, dk]
    v_new = u - dot("chk,hkd->chd", w, S0)
    qk = jnp.where(i >= j, dot("ihd,jhd->ijh", q, k) * decay, 0.0)
    o = dot("chk,hkd->chd", q * jnp.exp(G)[:, :, None], S0) \
        + dot("ijh,jhd->ihd", qk, v_new)
    tail = jnp.exp(G[-1][None, :] - G)                           # [C, H]
    S = S0 * jnp.exp(G[-1])[:, None, None] \
        + dot("chk,chd->hkd", k * tail[:, :, None], v_new)
    return S, o


def gdn_chunk_prefill(q, k, v, g, beta, state_pool, rows, *, cu_q_lens,
                      q_len, fresh, chunk: int = CHUNK):
    """The ragged batch's chunks, in order.  Shapes as :func:`gdn_recurrent`;
    ``cu_q_lens`` [S+1], ``q_len`` [S], ``fresh`` [S].  Tokens of no
    sequence (the batch's padding) get zeros."""
    T = q.shape[0]
    per_seq = -(-q_len // chunk)                                 # [S]
    ends = jnp.cumsum(per_seq)
    pad = lambda x: jnp.pad(x, ((0, chunk),) + ((0, 0),) * (x.ndim - 1))  # noqa: E731
    qp, kp, vp, gp, bp = (pad(x) for x in (q, k, v, g, beta))
    lane = jnp.arange(chunk)

    def one(c, carry):
        out, pool = carry
        s = jnp.searchsorted(ends, c, side="right").astype(jnp.int32)
        n = c - (ends[s] - per_seq[s])           # chunk n of sequence s
        start = cu_q_lens[s] + n * chunk
        live = lane < jnp.minimum(chunk, q_len[s] - n * chunk)   # [chunk]

        def cut(x):
            x = jax.lax.dynamic_slice_in_dim(x, start, chunk, axis=0)
            return jnp.where(live.reshape((chunk,) + (1,) * (x.ndim - 1)),
                             x, 0)

        S0 = jnp.where(fresh[s] & (n == 0), 0.0, pool[rows[s]])
        S, o = _chunk_update(S0, cut(qp), cut(kp), cut(vp), cut(gp), cut(bp))
        old = jax.lax.dynamic_slice_in_dim(out, start, chunk, axis=0)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(live[:, None, None], o, old), start, axis=0)
        return out, pool.at[rows[s]].set(S)

    out, pool = jax.lax.fori_loop(
        0, ends[-1], one,
        (jnp.zeros((T + chunk,) + v.shape[1:], jnp.float32), state_pool))
    return out[:T], pool


# --------------------------------------------------------------------- #
# One token a sequence: the Pallas kernel
# --------------------------------------------------------------------- #
def _gdn_decode_kernel(rows_ref, q_ref, k_ref, v_ref, a_ref, b_ref, s_ref,
                       o_ref, s_out_ref, *, hb: int):
    """One grid step = ``hb`` heads of one sequence.  ``q``/``k``/``v``/
    ``a``/``b`` blocks are [1, hb, 128]-ish rows (``a``/``b`` broadcast
    along the lanes); ``s`` [1, hb, dk, dv]."""
    del rows_ref
    kT = k_ref[0].T                                  # [dk, hb]
    qT = q_ref[0].T
    for h in range(hb):
        a = a_ref[0, h:h + 1, :]                     # [1, dv]
        # a decay of exactly 0: the row starts from zeros (a reused slot's
        # last owner may have left anything there, NaN included)
        S = jnp.where(a > 0.0, s_ref[0, h] * a, 0.0)  # [dk, dv]
        kc = kT[:, h:h + 1]                          # [dk, 1]
        delta = (v_ref[0, h:h + 1, :]
                 - jnp.sum(S * kc, axis=0, keepdims=True)) \
            * b_ref[0, h:h + 1, :]                   # [1, dv]
        S = S + kc * delta
        s_out_ref[0, h] = S
        o_ref[0, h:h + 1, :] = jnp.sum(S * qT[:, h:h + 1], axis=0,
                                       keepdims=True)


def gdn_decode(q, k, v, alpha, beta, state_pool, rows, *,
               heads_per_step: int = 16, interpret=None):
    """``q``/``k`` [R, H, dk], ``v`` [R, H, dv], ``alpha`` (= ``exp(g)``; 0:
    start from zeros) and ``beta`` [R, H] float32, ``rows`` [R] → (o [R, H,
    dv] float32, state_pool updated in place).  Several rows may name the
    trash row: one after the other, and never read."""
    R, H, dk = q.shape
    dv = v.shape[-1]
    hb = math.gcd(H, heads_per_step)
    lanes = lambda x: jnp.broadcast_to(  # noqa: E731
        x.astype(jnp.float32)[:, :, None], (R, H, dv))
    row_block = lambda d: pl.BlockSpec(  # noqa: E731
        (1, hb, d), lambda r, j, rows: (r, j, 0))
    state_block = pl.BlockSpec((1, hb, dk, dv),
                               lambda r, j, rows: (rows[r], j, 0, 0))
    o, pool = pl.pallas_call(
        functools.partial(_gdn_decode_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R, H // hb),
            in_specs=[row_block(dk), row_block(dk), row_block(dv),
                      row_block(dv), row_block(dv), state_block],
            out_specs=[row_block(dv), state_block]),
        out_shape=[jax.ShapeDtypeStruct((R, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state_pool.shape, state_pool.dtype)],
        # operands count the scalar prefetch: the pool is operand 6
        input_output_aliases={6: 1},
        interpret=_interpret() if interpret is None else interpret,
        name="gdn_decode",
    )(rows.astype(jnp.int32), q.astype(jnp.float32), k.astype(jnp.float32),
      v.astype(jnp.float32), lanes(alpha), lanes(beta), state_pool)
    return o, pool


# --------------------------------------------------------------------- #
# What a layer body calls (through model_runner._LayerState)
# --------------------------------------------------------------------- #
def gdn_mix(mixed, g, beta, conv_w, pool, rows, *, kind, mode: str, batch,
            valid):
    """Everything of a Gated DeltaNet mixer between its input projections and
    its gated norm.  ``mixed`` [T, C] is ``[q | k | v]`` before the
    convolution, ``g``/``beta`` [T, H] float32, ``pool`` = (state_pool,
    carry_pool), ``rows`` [S].  ``mode``: ``"decode"`` (row-major one-token
    rows), ``"ragged"`` or ``"oracle"``.  → (o [T, H, dv] float32, pool)."""
    state_pool, carry_pool = pool
    T = mixed.shape[0]
    S = rows.shape[0]
    Hk, Hv, dk, dv = kind.num_key_heads, kind.num_heads, kind.key_dim, \
        kind.value_dim
    q_len, ctx_len = batch["q_len"], batch["ctx_len"]
    fresh = ctx_len == q_len
    with jax.named_scope("attention/gdn_conv"):
        x, carry_pool = causal_conv_ragged(
            mixed, conv_w, carry_pool, rows,
            seq_of_token=batch["seq_of_token"], q_offset=batch["q_offset"],
            q_len=q_len, fresh=fresh)
        x = jax.nn.silu(x)
    with jax.named_scope("attention/gdn_core"):
        Kd = Hk * dk
        q = l2norm(x[:, :Kd].reshape(T, Hk, dk)) / math.sqrt(dk)
        k = l2norm(x[:, Kd:2 * Kd].reshape(T, Hk, dk))
        q = jnp.repeat(q, Hv // Hk, axis=1)
        k = jnp.repeat(k, Hv // Hk, axis=1)
        v = x[:, 2 * Kd:].reshape(T, Hv, dv)
        g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
        # trace time only: what a run says about the form it compiled
        get_tracer().record(
            "attn/gdn_layout", time.perf_counter(), 0.0,
            rows=min(S, T) if mode == "decode" else T, heads=Hv, chunk=CHUNK,
            state_dtype=jnp.dtype(state_pool.dtype).name, form=mode,
            impl="kernel" if mode == "decode" else "xla")
        if mode == "decode":
            R = min(S, T)
            # a fresh or padded row starts from zeros whatever the slot
            # holds: the kernel reads a decay of exactly 0 as "no state"
            alpha = jnp.where((q_len[:R] > 0)[:, None] & ~fresh[:R, None],
                              jnp.exp(g[:R]), 0.0)
            live = (q_len[:R] > 0)[:, None]
            o, state_pool = gdn_decode(
                q[:R], k[:R], v[:R], alpha, jnp.where(live, beta[:R], 0.0),
                state_pool, rows[:R])
            if T > R:
                o = jnp.pad(o, ((0, T - R), (0, 0), (0, 0)))
        elif mode == "ragged":
            o, state_pool = gdn_chunk_prefill(
                q, k, v, g, beta, state_pool, rows,
                cu_q_lens=batch["cu_q_lens"], q_len=q_len, fresh=fresh)
        else:
            o, state_pool = gdn_recurrent(
                q, k, v, g, beta, state_pool, rows,
                seq_of_token=batch["seq_of_token"],
                pos_of_token=batch["pos_of_token"], valid=valid)
    return o, (state_pool, carry_pool)
