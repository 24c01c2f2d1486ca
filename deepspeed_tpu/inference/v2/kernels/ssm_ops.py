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

Three forms of the same recurrence (:func:`ssm_mix` dispatches), under
their own name scopes:

``decode``   one token a sequence: TWO Pallas kernels a layer, because the
             layer's projections lie between them.  The convolution is
             ``gdn_ops.causal_conv_step`` with the bias handed in;
             :func:`ssm_decode` is a grid step a sequence, its ``[N, C]``
             state read once at its pool row, updated in VMEM and written
             back IN PLACE (``input_output_aliases``): elementwise along
             the lanes, one reduction over the ``N`` sublanes, nothing
             through the MXU, nothing of ``[rows, N, C]`` in HBM.
``ragged``   a ragged batch of chunks (SplitFuse), ``jax.numpy``: the flat
             batch is ONE
             linear recurrence ``S_t = a_t S_{t-1} + b_t`` once a sequence's
             first token takes its slot's state into ``b`` and a decay of 0
             (:func:`_fold_starts`), so no chunk boundary is left; it is
             scanned in blocks of :data:`BLOCK` tokens — the blocks side by
             side token after token, then the blocks' ends by an
             associative scan, then every token's state from its block's
             start.  Products of decays only, never a quotient: a decay
             that underflows is a state forgotten, not an overflow.
``oracle``   token by token over the flat batch, each through its
             sequence's slot, ``jax.numpy``: the numerics oracle
             (``attn_impl="gather"``).
"""
from __future__ import annotations

import functools
import time
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ....telemetry.trace import get_tracer
from .gdn_ops import causal_conv_ragged, causal_conv_step
from .ragged_ops import _interpret

#: tokens a block of the ragged form's first level
BLOCK = 16
#: VMEM the decode kernel's state and decay blocks may take (the state in
#: and out and ``A``, each double-buffered: six blocks of ``[N, block]``)
_STATE_VMEM = 8 << 20
#: lanes of a state block the decode kernel updates at a time: every value
#: of the update's chain for ``[16, 512]`` float32 stays in the registers
_LANES = 512


def _channel_block(N: int, C: int) -> int:
    """Channels a grid step of :func:`ssm_decode` takes: all of them where
    the six ``[N, C]`` float32 blocks fit :data:`_STATE_VMEM` (``[16,
    5120]``: 1.9 MB), else the widest whole lane tiles that divide ``C``
    and fit."""
    fits = _STATE_VMEM // (6 * 4 * N)
    if C <= fits:
        return C
    for cb in range(fits - fits % 128, 0, -128):
        if C % cb == 0:
            return cb
    return C


def _ssm_decode_kernel(rows_ref, keep_ref, x_ref, dt_ref, b_ref, c_ref,
                       a_ref, d_ref, s_ref, y_ref, s_out_ref, *, rb: int):
    """One grid step = ``cb`` channels of one sequence.  ``x`` / ``dt`` /
    ``y`` blocks are ``[rb, cb]`` (``rb`` consecutive rows share one),
    ``b`` / ``c`` ``[1, N, 1]`` (a column: broadcast along the lanes), ``a``
    ``[N, cb]`` and ``d`` ``[1, cb]`` (the same blocks every row), ``s``
    ``[1, N, cb]`` at the sequence's pool row."""
    del rows_ref
    r = pl.program_id(1)
    i = r % rb
    kept = keep_ref[r] != 0
    Bc, Cc = b_ref[0], c_ref[0]                                 # [N, 1]
    cb = s_ref.shape[-1]
    for c0 in range(0, cb, _LANES):
        at = pl.ds(c0, min(_LANES, cb - c0))
        x, dt = x_ref[pl.ds(i, 1), at], dt_ref[pl.ds(i, 1), at]  # [1, w]
        # a fresh or padded row starts from zeros whatever the slot holds
        # (its last owner may have left anything there, NaN included)
        S = jnp.where(kept, s_ref[0, :, at], 0.0)               # [N, w]
        S = jnp.exp(dt * a_ref[:, at]) * S + (dt * x) * Bc
        s_out_ref[0, :, at] = S
        y_ref[pl.ds(i, 1), at] = jnp.sum(S * Cc, axis=0, keepdims=True) \
            + d_ref[:, at] * x


def ssm_decode(x, delta, Bm, Cm, A, D, state_pool, rows, keep):
    """One token a sequence row: ``x`` / ``delta`` [R, C], ``Bm`` / ``Cm``
    [R, N], ``A`` [N, C], ``D`` [C] float32, ``state_pool`` [M, N, C]
    float32, ``rows`` [R] pool rows, ``keep`` [R] (False: the row starts
    from zeros whatever the slot holds) → (y [R, C] float32, state_pool
    updated in place: each row's ``[N, C]`` read once and written once).
    Several rows may name the trash row: one after the other, and never
    read."""
    R, C = x.shape
    N = A.shape[0]
    assert state_pool.shape[1:] == (N, C), \
        f"state pool {state_pool.shape} does not hold [{N}, {C}]"
    cb = _channel_block(N, C)
    # rows of x, delta and y move a whole float32 tile at a time; the rows
    # are the INNER grid axis, so a block is fetched once for its rb rows
    rb = min(R, 8)
    row_block = pl.BlockSpec((rb, cb), lambda j, r, rows, keep: (r // rb, j))
    column = pl.BlockSpec((1, N, 1), lambda j, r, rows, keep: (r, 0, 0))
    state_block = pl.BlockSpec((1, N, cb),
                               lambda j, r, rows, keep: (rows[r], 0, j))
    return pl.pallas_call(
        functools.partial(_ssm_decode_kernel, rb=rb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(C // cb, R),
            in_specs=[row_block, row_block, column, column,
                      pl.BlockSpec((N, cb), lambda j, r, rows, keep: (0, j)),
                      pl.BlockSpec((1, cb), lambda j, r, rows, keep: (0, j)),
                      state_block],
            out_specs=[row_block, state_block]),
        # the pool is pinned to HBM as gdn_ops.causal_conv_step pins its
        # carry pool: XLA stages through VMEM whatever of it fits
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.float32),
                   pltpu.HBM(state_pool.shape, state_pool.dtype)],
        # operands count the scalar prefetches: the pool is operand 8
        input_output_aliases={8: 1},
        interpret=_interpret(),
        # not "…_decode_paged…", not "gdn_decode…": the benchmark reads
        # those kernels by name
        name="ssm_decode",
    )(rows.astype(jnp.int32), keep.astype(jnp.int32), x, delta,
      Bm[:, :, None], Cm[:, :, None], A, D[None], state_pool)


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
    R = min(S, T)                # decode: one token a row, row-major
    tail = lambda v: jnp.pad(v, ((0, T - R), (0, 0))) if T > R else v  # noqa: E731
    # trace time only: what a run says about the forms it compiled (the
    # recurrence's and the convolution's are chosen together)
    impl = "kernel" if mode == "decode" else "xla"
    get_tracer().record(
        "attn/ssm_layout", time.perf_counter(), 0.0,
        rows=R if mode == "decode" else T, channels=kind.channels,
        state_dim=kind.state_dim, conv_kernel=kind.conv_kernel, form=mode,
        impl=impl, conv_impl=impl,
        channel_block=_channel_block(kind.state_dim, kind.channels)
        if mode == "decode" else 0,
        state_dtype=jnp.dtype(state_pool.dtype).name)
    with jax.named_scope("attention/ssm_conv"):
        if mode == "decode":
            # a fresh or padded row starts from zeros whatever its slot
            # holds, in the convolution and in the recurrence
            keep = (q_len[:R] > 0) & ~fresh[:R]
            x, carry_pool = causal_conv_step(u[:R], conv_w, carry_pool,
                                             rows[:R], keep, conv_b)
            x = tail(x)
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
            y, state_pool = ssm_decode(x[:R], delta[:R], Bm[:R], Cm[:R], A,
                                       D, state_pool, rows[:R], keep)
            y = tail(y)
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
