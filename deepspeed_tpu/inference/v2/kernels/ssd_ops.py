"""The Mamba-2 recurrence (state-space dual, SSD) over per-sequence state, on
the paged serving path's flat token axis.  Nothing here is a model's: the
widths come with the state kind (``H`` heads of ``hd`` values, ``N`` state
values, ``G`` groups, a convolution of ``K`` taps).

A sequence owns, in each SSD layer, one SLOT of the state pool
(``ragged/state_pool.py``): a matrix a head, ``[H, hd, N]`` float32 values,
and the causal convolution's last ``K - 1`` inputs over ``x | B | C``.
``rows`` below are ABSOLUTE pool rows, one per sequence row of the batch
(``model_runner._LayerState`` makes them); the pool's last row is the trash
slot of padded rows.  A slot is never cleared: a sequence whose first token
is at position 0 starts from zeros on the device whatever the slot holds.

HOW A STATE IS STORED is the state kind's (``models/serving.SSDState.
arrays``) and read here off the pool's shape: ``[H / P, N, P * hd]``, the
state values along the sublanes and ``P`` heads side by side along the
lanes (two heads of 64: one whole tile).  A head's input ``x`` and output
``y`` are then rows of the update — ``hd`` lanes of ``[T, H hd]`` as they
lie — and the group's ``B`` / ``C`` columns, every operation elementwise
along the lanes or a reduction over the sublanes; no form ever transposes a
state (:func:`_rows_of`).

Per head ``h`` of group ``g``, with ``x | B | C = SiLU(conv(u) + bias)`` and
``delta = softplus(dt + dt_bias)`` (``dt`` comes out of the layer's input
projection: NOTHING lies between the convolution and the recurrence):

``S_t[h] = exp(-delta_t[h] A[h]) S_{t-1}[h] + delta_t[h] x_t[h] (x) B_t[g]``,
``y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]``,

and the output passes a gated RMSNorm over each group's values (gate
first).  Three forms of the same recurrence (:func:`ssd_mix` dispatches),
under their own name scopes:

``decode``   one token a sequence: the convolution is ``gdn_ops.
             causal_conv_step`` with the bias handed in; :func:`ssd_decode`
             is a grid step a (sequence, group's heads), the heads' ``[N, P
             hd]`` tiles read once at the sequence's pool row, updated in
             VMEM and written back IN PLACE (``input_output_aliases``):
             nothing through the MXU, nothing of ``[rows, H, hd, N]`` in
             HBM.
``ragged``   a ragged batch of chunks (SplitFuse): the CHUNKED form,
             ``kind.chunk`` tokens at a time, ``jax.numpy`` under its own
             name scope — inside a chunk a masked ``[chunk, chunk]``
             product a head (scores ``C B^T`` a group, decays ``exp(cs_i -
             cs_j)``: differences of a running sum of logarithms, never a
             quotient), between chunks ONE state: a ``while`` over the
             batch's real chunks, each reading its sequence's state from the
             slot (zeros at position 0) and leaving the state after its
             last token there.  Nothing of ``[T, H, hd, N]`` (every token's
             state, what ``ssm_ops.scan_flat`` makes) exists.  A chunk is
             any length and starts anywhere in the batch.
``oracle``   token by token over the flat batch, each through its
             sequence's slot, ``jax.numpy``: the numerics oracle
             (``attn_impl="gather"``).
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas import tpu as pltpu

from ....telemetry.trace import get_tracer
from .gdn_ops import causal_conv_ragged, causal_conv_step
from .ragged_ops import _interpret

_HI = jax.lax.Precision.HIGHEST
#: VMEM the decode kernel's state blocks may take (in and out, each
#: double-buffered: four blocks of ``[hb, N, P * hd]`` float32)
_STATE_VMEM = 8 << 20
#: sublanes ``B`` and ``C`` are tiled to so that the kernel transposes whole
#: (8, 128) tiles into columns
_TILE = 8


def _head_block(rows_per_group: int, N: int, width: int) -> int:
    """Stored rows (``P`` heads each) a grid step of :func:`ssd_decode`
    takes: a group's (they share ``B`` / ``C``), or the largest divisor of
    them whose four ``[hb, N, width]`` float32 blocks fit
    :data:`_STATE_VMEM`."""
    fits = max(_STATE_VMEM // (4 * 4 * N * width), 1)
    return max(hb for hb in range(1, rows_per_group + 1)
               if rows_per_group % hb == 0 and hb <= fits)


def _ssd_decode_kernel(rows_ref, a_ref, u_ref, bc_ref, s_ref, y_ref,
                       s_out_ref, *, hb: int):
    """One grid step = ``hb`` stored rows (``hb * P`` heads of ONE group) of
    one sequence.  ``a`` / ``u`` / ``y`` blocks are ``[1, 1, hb, P * hd]``
    (a head's decay and ``delta x`` over its own lanes), ``bc`` ``[1, 1, 2 *
    _TILE, N]`` (the group's ``B`` in the first tile's rows, ``C`` in the
    second's), ``s`` ``[1, hb, N, P * hd]`` at the sequence's pool row."""
    del rows_ref
    bcT = bc_ref[0, 0].T                             # [N, 2 * _TILE]
    Bc, Cc = bcT[:, 0:1], bcT[:, _TILE:_TILE + 1]    # [N, 1]
    for h in range(hb):
        a = a_ref[0, 0, h:h + 1, :]                  # [1, P * hd]
        # a decay of exactly 0: the row starts from zeros (a reused slot's
        # last owner may have left anything there, NaN included)
        S = jnp.where(a > 0.0, s_ref[0, h].astype(jnp.float32) * a,
                      0.0)                           # [N, P * hd]
        S = S + Bc * u_ref[0, 0, h:h + 1, :]
        s_out_ref[0, h] = S.astype(s_out_ref.dtype)
        y_ref[0, 0, h:h + 1, :] = jnp.sum(S * Cc, axis=0, keepdims=True)


def ssd_decode(x, delta, decay, Bm, Cm, state_pool, rows, *, interpret=None):
    """One token a sequence row: ``x`` [R, H, hd], ``delta`` / ``decay`` [R,
    H] (``decay`` 0: the row starts from zeros whatever the slot holds),
    ``Bm`` / ``Cm`` [R, G, N], all float32; ``state_pool`` [M, H / P, N, P *
    hd] float32 (``P`` heads a stored row: the module docstring), ``rows``
    [R] pool rows → (``S C`` [R, H, hd] float32 — without the ``D x`` skip
    — and state_pool updated in place: each row's state read once and
    written once).  Several rows may name the trash row: one after the
    other, and never read."""
    R, H, hd = x.shape
    G, N = Bm.shape[1:]
    Hr, width = state_pool.shape[1], state_pool.shape[-1]
    P = width // hd
    assert state_pool.shape[1:] == (H // P, N, P * hd), \
        f"state pool {state_pool.shape} does not store [{H}, {hd}, {N}]"
    rpg = Hr // G                            # stored rows of a group
    assert rpg * G == Hr, f"{P} heads a row straddle the {G} groups"
    hb = _head_block(rpg, N, width)
    per = rpg // hb                          # grid steps a group
    lanes = lambda v: jnp.broadcast_to(      # noqa: E731
        v[:, :, None], (R, H, hd)).reshape(R, G * per, hb, width)
    tiled = lambda m: jnp.broadcast_to(      # noqa: E731
        m[:, :, None, :], (R, G, _TILE, N))
    row_block = pl.BlockSpec((1, 1, hb, width),
                             lambda r, j, rows: (r, j, 0, 0))
    state_block = pl.BlockSpec((1, hb, N, width),
                               lambda r, j, rows: (rows[r], j, 0, 0))
    y, pool = pl.pallas_call(
        functools.partial(_ssd_decode_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R, G * per),
            in_specs=[row_block, row_block,
                      pl.BlockSpec((1, 1, 2 * _TILE, N),
                                   lambda r, j, rows: (r, j // per, 0, 0)),
                      state_block],
            out_specs=[row_block, state_block]),
        # the pool is pinned to HBM as gdn_ops.causal_conv_step pins its
        # carry pool: XLA stages through VMEM whatever of it fits
        out_shape=[jax.ShapeDtypeStruct((R, G * per, hb, width),
                                        jnp.float32),
                   pltpu.HBM(state_pool.shape, state_pool.dtype)],
        # operands count the scalar prefetch: the pool is operand 4
        input_output_aliases={4: 1},
        interpret=_interpret() if interpret is None else interpret,
        # the benchmark reads this kernel by name
        name="ssd_decode",
    )(rows.astype(jnp.int32), lanes(decay),
      (delta[:, :, None] * x).reshape(R, G * per, hb, width),
      jnp.concatenate([tiled(Bm), tiled(Cm)], axis=2), state_pool)
    return y.reshape(R, H, hd), pool


def _rows_of(x, delta, la, Bm, Cm, width: int):
    """The recurrence's inputs in the STORED layout (no state is ever
    transposed: with ``P`` heads side by side a head's ``x`` is ``hd`` lanes
    of its row as it lies).  ``x`` [..., H, hd], ``delta`` / ``la`` [...,
    H], ``Bm`` / ``Cm`` [..., G, N] → ``u = delta x`` and ``la`` [..., Hr,
    W] (``la`` a head's over its own lanes), ``B`` / ``C`` [..., Hr, N] (a
    row's group's)."""
    H, hd = x.shape[-2:]
    Hr = H * hd // width
    lead = x.shape[:-2]
    u = (delta[..., None] * x).reshape(lead + (Hr, width))
    la = jnp.broadcast_to(la[..., None], lead + (H, hd)).reshape(
        lead + (Hr, width))
    rep = Hr // Bm.shape[-2]
    return u, la, jnp.repeat(Bm, rep, axis=-2), jnp.repeat(Cm, rep, axis=-2)


def ssd_recurrent(x, delta, la, Bm, Cm, state_pool, rows, *, seq_of_token,
                  pos_of_token, valid):
    """Token by token over the flat batch (the oracle).  ``x`` [T, H, hd],
    ``delta`` / ``la`` (the decay's logarithm) [T, H], ``Bm`` / ``Cm`` [T,
    G, N] → (``S C`` [T, H, hd], pool)."""
    T, H, hd = x.shape
    Hr, _, width = state_pool.shape[1:]
    trash = state_pool.shape[0] - 1
    row_of = jnp.where(valid, rows[seq_of_token], trash)
    u, la, Br, Cr = _rows_of(x, delta, la, Bm, Cm, width)

    def token(t, carry):
        out, pool = carry
        S = jnp.where(pos_of_token[t] == 0, 0.0, pool[row_of[t]])
        S = jnp.exp(la[t])[:, None, :] * S \
            + Br[t][:, :, None] * u[t][:, None, :]
        y = jnp.sum(S * Cr[t][:, :, None], axis=1)
        return out.at[t].set(y), pool.at[row_of[t]].set(S.astype(pool.dtype))

    y, pool = jax.lax.fori_loop(
        0, T, token, (jnp.zeros((T, Hr, width), jnp.float32), state_pool))
    return y.reshape(T, H, hd), pool


def _chunk_update(S0, x, delta, la, Bm, Cm):
    """One chunk of one sequence in the state-space-dual form.  ``S0`` [Hr,
    N, W] the state before it, as stored; ``x`` [L, H, hd], ``delta`` /
    ``la`` [L, H], ``Bm`` / ``Cm`` [L, G, N]; a token past the chunk's end
    comes with ``delta`` 0 and ``la`` 0 (it neither decays nor adds) →
    (state after the chunk, ``S C`` [L, H, hd])."""
    L, H, hd = x.shape
    G = Bm.shape[1]
    rep = H // G
    width = S0.shape[-1]
    dot = functools.partial(jnp.einsum, precision=_HI)
    cs = jnp.cumsum(la, axis=0)                                  # [L, H]
    u, csr, Br, Cr = _rows_of(x, delta, cs, Bm, Cm, width)
    # inside: y_i += sum_{j <= i} exp(cs_i - cs_j) (C_i . B_j) delta_j x_j
    scores = dot("ign,jgn->gij", Cm, Bm)                         # [G, L, L]
    causal = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.exp(jnp.where(causal[:, :, None],
                              cs[:, None, :] - cs[None, :, :], -jnp.inf))
    mask = decay.reshape(L, L, G, rep) * scores.transpose(1, 2, 0)[..., None]
    y = dot("ijh,jhd->ihd", mask.reshape(L, L, H), u.reshape(L, H, hd))
    # from the state the chunk starts with, and the state it leaves: what
    # every token adds, decayed to the chunk's end — in the stored layout
    y = y + (jnp.exp(csr) * dot("irn,rnw->irw", Cr, S0)).reshape(L, H, hd)
    tail = jnp.exp(csr[-1][None] - csr)                          # [L, Hr, W]
    S = jnp.exp(csr[-1])[:, None, :] * S0 + dot("jrn,jrw->rnw", Br, tail * u)
    return S, y


def ssd_chunk_prefill(x, delta, la, Bm, Cm, state_pool, rows, *, cu_q_lens,
                      q_len, fresh, chunk: int):
    """The ragged batch's chunks, in order.  Shapes as :func:`ssd_recurrent`;
    ``cu_q_lens`` [S+1], ``q_len`` [S], ``fresh`` [S].  Tokens of no
    sequence (the batch's padding) get zeros."""
    T, H, hd = x.shape
    per_seq = -(-q_len // chunk)                                 # [S]
    ends = jnp.cumsum(per_seq)
    pad = lambda v: jnp.pad(v, ((0, chunk),) + ((0, 0),) * (v.ndim - 1))  # noqa: E731
    xp, dp, lp, bp, cp = (pad(v) for v in (x, delta, la, Bm, Cm))
    lane = jnp.arange(chunk)

    def one(c, carry):
        out, pool = carry
        s = jnp.searchsorted(ends, c, side="right").astype(jnp.int32)
        n = c - (ends[s] - per_seq[s])           # chunk n of sequence s
        start = cu_q_lens[s] + n * chunk
        live = lane < jnp.minimum(chunk, q_len[s] - n * chunk)   # [chunk]

        def cut(v):
            v = jax.lax.dynamic_slice_in_dim(v, start, chunk, axis=0)
            return jnp.where(live.reshape((chunk,) + (1,) * (v.ndim - 1)),
                             v, 0)

        S0 = jnp.where(fresh[s] & (n == 0), 0.0, pool[rows[s]])
        S, y = _chunk_update(S0, cut(xp), cut(dp), cut(lp), cut(bp), cut(cp))
        old = jax.lax.dynamic_slice_in_dim(out, start, chunk, axis=0)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(live[:, None, None], y, old), start, axis=0)
        # the pool keeps the layout it is stored in: left to itself the
        # compiler lays the carried pool out state-values-minor for the
        # chunk's products and copies the WHOLE pool (2.5 GiB at 128 slots
        # x 5 layers) into that layout at the step's entry and back at its
        # end; pinned, only the chunk's own 4 MiB are ever laid out anew
        return out, with_layout_constraint(
            pool.at[rows[s]].set(S.astype(pool.dtype)), as_stored)

    as_stored = Layout(major_to_minor=tuple(range(state_pool.ndim)))
    out, pool = jax.lax.fori_loop(
        0, ends[-1], one,
        (jnp.zeros((T + chunk, H, hd), jnp.float32), state_pool))
    return out[:T], pool


def gated_group_norm(y, z, weight, groups: int, eps: float):
    """``RMSNorm`` over each of ``groups`` equal parts of ``y SiLU(z)``
    (the gate first, then the norm), times ``weight``: [T, C] float32."""
    T, C = y.shape
    v = (y * jax.nn.silu(z.astype(jnp.float32))).reshape(T, groups,
                                                         C // groups)
    v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1, keepdims=True)
                          + eps)
    return v.reshape(T, C) * weight.astype(jnp.float32)[None]


def ssd_mix(xBC, dt, z, conv_w, conv_b, dt_bias, A, D, norm_w, eps, pool,
            rows, *, kind, mode: str, batch, valid):
    """Everything of a Mamba-2 mixer between its input projection and its
    output projection.  ``xBC`` [T, H hd + 2 G N] before the convolution,
    ``dt`` [T, H] and ``z`` [T, H hd] as the projection gives them;
    ``conv_w`` [K, C], ``conv_b`` [C]; ``dt_bias`` / ``A`` (positive: a
    decay is ``exp(-delta A)``) / ``D`` [H]; ``norm_w`` [H hd] and ``eps``
    of the gated norm; ``pool`` = (state_pool, carry_pool); ``rows`` [S].
    ``mode``: ``"decode"`` (row-major one-token rows), ``"ragged"`` or
    ``"oracle"``.  → (y [T, H hd] float32, pool)."""
    state_pool, carry_pool = pool
    T = xBC.shape[0]
    S = rows.shape[0]
    H, hd, N, G = kind.heads, kind.head_dim, kind.state_dim, kind.groups
    q_len, ctx_len = batch["q_len"], batch["ctx_len"]
    fresh = ctx_len == q_len
    R = min(S, T)                # decode: one token a row, row-major
    # trace time only: what a run says about the forms it compiled (the
    # recurrence's and the convolution's are chosen together)
    impl = "kernel" if mode == "decode" else "xla"
    get_tracer().record(
        "attn/ssd_layout", time.perf_counter(), 0.0,
        rows=R if mode == "decode" else T, heads=H, head_dim=hd,
        state_dim=N, groups=G, chunk=kind.chunk, lane_heads=kind.lane_heads,
        form=mode, impl=impl, conv_impl=impl,
        state_dtype=jnp.dtype(state_pool.dtype).name)
    conv_b = conv_b.astype(jnp.float32)
    with jax.named_scope("attention/ssd_conv"):
        if mode == "decode":
            # a fresh or padded row starts from zeros whatever its slot
            # holds, in the convolution and in the recurrence
            keep = (q_len[:R] > 0) & ~fresh[:R]
            v, carry_pool = causal_conv_step(xBC[:R], conv_w, carry_pool,
                                             rows[:R], keep, conv_b)
            if T > R:
                v = jnp.pad(v, ((0, T - R), (0, 0)))
        else:
            v, carry_pool = causal_conv_ragged(
                xBC, conv_w, carry_pool, rows,
                seq_of_token=batch["seq_of_token"],
                q_offset=batch["q_offset"], q_len=q_len, fresh=fresh)
            v = jax.nn.silu(v + conv_b[None])
    with jax.named_scope("attention/ssd_scan"):
        x = v[:, :H * hd].reshape(T, H, hd)
        Bm = v[:, H * hd:H * hd + G * N].reshape(T, G, N)
        Cm = v[:, H * hd + G * N:].reshape(T, G, N)
        delta = jax.nn.softplus(dt.astype(jnp.float32)
                                + dt_bias.astype(jnp.float32)[None])
        la = -delta * A.astype(jnp.float32)[None]    # a decay's logarithm
        if mode == "decode":
            # the kernel reads a decay of exactly 0 as "no state"
            decay = jnp.where(keep[:, None], jnp.exp(la[:R]), 0.0)
            y, state_pool = ssd_decode(x[:R], delta[:R], decay, Bm[:R],
                                       Cm[:R], state_pool, rows[:R])
            if T > R:
                y = jnp.pad(y, ((0, T - R), (0, 0), (0, 0)))
        elif mode == "ragged":
            y, state_pool = ssd_chunk_prefill(
                x, delta, la, Bm, Cm, state_pool, rows,
                cu_q_lens=batch["cu_q_lens"], q_len=q_len, fresh=fresh,
                chunk=kind.chunk)
        else:
            y, state_pool = ssd_recurrent(
                x, delta, la, Bm, Cm, state_pool, rows,
                seq_of_token=batch["seq_of_token"],
                pos_of_token=batch["pos_of_token"], valid=valid)
        y = y + D.astype(jnp.float32)[None, :, None] * x
    with jax.named_scope("attention/ssd_norm"):
        y = gated_group_norm(y.reshape(T, H * hd), z, norm_w, G, eps)
    return y, (state_pool, carry_pool)
