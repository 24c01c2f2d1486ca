"""The selective scan (Mamba-1) over per-sequence state, on the paged serving
path's flat token axis.  Nothing here is a model's: the widths come with the
arrays (``C`` channels, ``N`` state values a channel, a convolution of ``K``
taps).

A sequence owns, in each scan layer, one SLOT of the state pool
(``ragged/state_pool.py``): the state ``[N, C]`` float32, channels along the
lanes (``models/serving.SelectiveScanState``), and the causal convolution's
last ``K - 1`` inputs ``[K - 1, C]``.  ``rows`` below are ABSOLUTE pool rows,
one per sequence row of the batch (``model_runner._LayerState`` makes them);
the pool's last row is the trash slot of padded rows.  A slot is never
cleared: a sequence whose first token is at position 0 starts from zeros on
the device whatever the slot holds.

Per channel ``c`` and state value ``n``, with ``x = SiLU(conv(u) + bias)``
and ``(delta, B, C) = proj(x)`` (the layer's own projections, handed in as a
function because they lie BETWEEN the convolution and the recurrence):

``S_t[n, c] = exp(delta_t[c] A[n, c]) S_{t-1}[n, c] + delta_t[c] x_t[c] B_t[n]``,
``y_t[c] = sum_n S_t[n, c] C_t[n] + D[c] x_t[c]``.

Three forms of the same recurrence (:func:`ssm_mix` dispatches), all
``jax.numpy`` under their own name scopes:

``decode``   one token a sequence: the rows' states gathered, updated and
             scattered back into the (donated, loop-carried) pool.
``ragged``   a ragged batch of chunks (SplitFuse): the flat batch is ONE
             linear recurrence ``S_t = a_t S_{t-1} + b_t`` once a sequence's
             first token takes its slot's state into ``b`` and a decay of 0
             (:func:`_fold_starts`), so no chunk boundary is left; it is
             scanned in blocks of :data:`BLOCK` tokens — the blocks side by
             side token after token, then the blocks' ends by an
             associative scan, then every token's state from its block's
             start.  Products of decays only, never a quotient: a decay
             that underflows is a state forgotten, not an overflow.
``oracle``   token by token over the flat batch, each through its
             sequence's slot: the numerics oracle (``attn_impl="gather"``).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from .gdn_ops import causal_conv_ragged

#: tokens a block of the ragged form's first level
BLOCK = 16


def conv_step(x, conv_w, carry_pool, rows, keep):
    """One token a row: ``x`` [R, C], ``conv_w`` [K, C], ``carry_pool`` [M,
    K-1, C], ``rows`` [R], ``keep`` [R] (False: the row starts from zeros)
    → (out [R, C] float32, new carry_pool)."""
    K = conv_w.shape[0]
    xf = x.astype(jnp.float32)
    w = conv_w.astype(jnp.float32)
    carry = jnp.where(keep[:, None, None], carry_pool[rows], 0
                      ).astype(jnp.float32)                  # [R, K-1, C]
    out = w[K - 1][None] * xf + jnp.einsum("kc,rkc->rc", w[:K - 1], carry)
    new = jnp.concatenate([carry[:, 1:], xf[:, None]], axis=1)
    return out, carry_pool.at[rows].set(new.astype(carry_pool.dtype))


def _terms(x, delta, Bm, A):
    """``a`` [T, N, C] the decays, ``b`` [T, N, C] the inputs (float32)."""
    a = jnp.exp(delta[:, None, :] * A[None])
    b = (delta * x)[:, None, :] * Bm[:, :, None]
    return a, b


def _fold_starts(a, b, start_state, first_idx):
    """A sequence's first token of the batch takes the state it starts from
    into its input and a decay of 0: ``b' = a S_0 + b``, ``a' = 0``.
    ``start_state`` [S, N, C], ``first_idx`` [S] (>= T: no token)."""
    T = a.shape[0]
    at = jnp.clip(first_idx, 0, T - 1)
    b = b.at[first_idx].add(a[at] * start_state, mode="drop")
    a = a.at[first_idx].set(0.0, mode="drop")
    return a, b


def scan_flat(a, b, block: int = BLOCK):
    """``S_t = a_t S_{t-1} + b_t`` from ``S_{-1} = 0`` over the leading
    axis, every ``S_t`` returned: [T, N, C] float32."""
    T = a.shape[0]
    pad = -T % block
    if pad:
        a = jnp.pad(a, ((0, pad), (0, 0), (0, 0)))
        b = jnp.pad(b, ((0, pad), (0, 0), (0, 0)))
    nb = a.shape[0] // block
    a = a.reshape((nb, block) + a.shape[1:])
    b = b.reshape((nb, block) + b.shape[1:])
    # the blocks side by side, token after token, each from zero
    h, p = b[:, 0], a[:, 0]
    hs, ps = [h], [p]
    for i in range(1, block):
        h = a[:, i] * h + b[:, i]
        p = p * a[:, i]
        hs.append(h)
        ps.append(p)
    # the state each block starts from: an associative scan of its ends
    def combine(left, right):
        return left[0] * right[0], right[0] * left[1] + right[1]

    _, ends = jax.lax.associative_scan(combine, (p, h), axis=0)
    starts = jnp.concatenate([jnp.zeros_like(ends[:1]), ends[:-1]], axis=0)
    full = jnp.stack([hi + pi * starts for hi, pi in zip(hs, ps)], axis=1)
    return full.reshape((nb * block,) + full.shape[2:])[:T]


def _readout(S, x, Cm, D):
    return jnp.sum(S * Cm[:, :, None], axis=1) + D[None] * x


def ssm_mix(u, conv_w, conv_b, proj: Callable, A, D, pool, rows, *, kind,
            mode: str, batch, valid):
    """Everything of a selective-scan mixer between its input projection and
    its output gate.  ``u`` [T, C] before the convolution; ``conv_w`` [K,
    C], ``conv_b`` [C]; ``proj(x [T, C] float32) → (delta [T, C], B [T, N],
    C [T, N])`` float32; ``A`` [N, C] (negative), ``D`` [C]; ``pool`` =
    (state_pool [M, N, C], carry_pool [M, K-1, C]); ``rows`` [S].  ``mode``:
    ``"decode"`` (row-major one-token rows), ``"ragged"`` or ``"oracle"``.
    → (y [T, C] float32, pool)."""
    state_pool, carry_pool = pool
    T = u.shape[0]
    S = rows.shape[0]
    q_len, ctx_len = batch["q_len"], batch["ctx_len"]
    fresh = ctx_len == q_len
    A, D = A.astype(jnp.float32), D.astype(jnp.float32)
    R = min(S, T)
    with jax.named_scope("attention/ssm_conv"):
        if mode == "decode":
            # a fresh or padded row starts from zeros whatever its slot holds
            keep = (q_len[:R] > 0) & ~fresh[:R]
            x, carry_pool = conv_step(u[:R], conv_w, carry_pool, rows[:R],
                                      keep)
            if T > R:
                x = jnp.pad(x, ((0, T - R), (0, 0)))
        else:
            x, carry_pool = causal_conv_ragged(
                u, conv_w, carry_pool, rows,
                seq_of_token=batch["seq_of_token"],
                q_offset=batch["q_offset"], q_len=q_len, fresh=fresh)
        x = jax.nn.silu(x + conv_b.astype(jnp.float32)[None])
    with jax.named_scope("attention/ssm_proj"):
        delta, Bm, Cm = proj(x)
    with jax.named_scope("attention/ssm_scan"):
        if mode == "decode":
            S0 = jnp.where(keep[:, None, None], state_pool[rows[:R]], 0.0)
            a, b = _terms(x[:R], delta[:R], Bm[:R], A)
            S1 = a * S0 + b
            y = _readout(S1, x[:R], Cm[:R], D)
            state_pool = state_pool.at[rows[:R]].set(S1)
            if T > R:
                y = jnp.pad(y, ((0, T - R), (0, 0)))
        elif mode == "ragged":
            a, b = _terms(x, delta, Bm, A)
            S0 = jnp.where(fresh[:, None, None], 0.0, state_pool[rows])
            first = jnp.where(q_len > 0, batch["q_offset"], T)
            full = scan_flat(*_fold_starts(a, b, S0, first))
            y = _readout(full, x, Cm, D)
            last = jnp.clip(batch["q_offset"] + q_len - 1, 0, T - 1)
            trash = state_pool.shape[0] - 1
            state_pool = state_pool.at[
                jnp.where(q_len > 0, rows, trash)].set(full[last])
        else:
            trash = state_pool.shape[0] - 1
            row_of = jnp.where(valid, rows[batch["seq_of_token"]], trash)
            pos = batch["pos_of_token"]

            def token(t, carry):
                out, sp = carry
                St = jnp.where(pos[t] == 0, 0.0, sp[row_of[t]])
                St = jnp.exp(delta[t][None] * A) * St \
                    + (delta[t] * x[t])[None] * Bm[t][:, None]
                yt = jnp.sum(St * Cm[t][:, None], axis=0) + D * x[t]
                return out.at[t].set(yt), sp.at[row_of[t]].set(St)

            y, state_pool = jax.lax.fori_loop(
                0, T, token, (jnp.zeros((T, x.shape[1]), jnp.float32),
                              state_pool))
    return y, (state_pool, carry_pool)
