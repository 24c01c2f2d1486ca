"""Gated DeltaNet over per-sequence recurrent state (the linear attention
layers of Qwen3-Next and of Olmo-Hybrid), on the paged serving path's flat
token axis.  Nothing here is a model's: head counts and widths come with the
arrays (``H`` value heads, keys ``dk`` wide, values ``dv`` wide).

A sequence owns, in each state layer, one SLOT of the state pool
(``ragged/state_pool.py``): the delta-rule state ``S [H, dk, dv]`` float32
and the causal convolution's last ``K - 1`` inputs ``[K - 1, C]``.  The pool
is ``[state_layers * slots + 1, ...]``; its last row is the trash slot that
padded rows read and write.  ``rows`` below are ABSOLUTE pool rows, one per
sequence row of the batch (``model_runner._LayerState`` makes them).

HOW A STATE IS STORED is the state kind's (``models/serving.
GatedDeltaState.arrays``) and read here off the pool's shape: ``[H / P, dk,
P * dv]``, ``P`` heads side by side along the lanes.  ``P`` is 1 where
``dv`` is whole lane tiles (128: the plain ``[H, dk, dv]``); a ``dv`` of 192
would be padded to 256 lanes in HBM and in VMEM, a third more bytes in every
call, so two heads share a row of 384 = three whole tiles (:func:`pack_state`).
Every operation of the update is elementwise along the lanes or a reduction
over the sublanes, so the kernel needs no unpacking: it builds each packed
row's ``k`` and ``q`` columns with one lane select a head.

Per head, with ``a_t = exp(g_t)``:
``S_t = a_t S_{t-1} + k_t (x) b_t (v_t - (a_t S_{t-1})^T k_t)``,
``o_t = S_t^T q_t``.

Three forms of the same recurrence (:func:`gdn_mix` dispatches):

``gdn_decode``         one token a sequence: ONE Pallas kernel a layer, the
                       state read once and written once IN PLACE
                       (``input_output_aliases``), a grid step a (sequence,
                       block of heads: :func:`_head_block`).  The update is
                       ~110 vector operations a head on a ``[128, 128]``
                       tile; nothing goes through the MXU (every (sequence,
                       head) has its own state, so a matmul would have one
                       row).
``gdn_chunk_prefill``  a ragged batch of chunks (SplitFuse): the chunked
                       (WY) form, 64 tokens at a time, ``jax.numpy`` under
                       its own name scope: a ``while`` over the batch's real
                       chunks, each reading its sequence's state from the
                       slot (zeros at position 0) and leaving the state after
                       its last token there.  A chunk is any length; decode
                       rows riding in the batch are chunks of one token.
``gdn_recurrent``      token by token over the flat batch: the numerics
                       oracle (``attn_impl="gather"``).

The convolution with its carry has two forms, chosen with the recurrence's:
a decode batch takes :func:`causal_conv_step`, ONE Pallas kernel a layer
beside ``gdn_decode`` — a grid step a sequence, its ``[K - 1, C]`` carry (48-
69 KB) read once at its pool row and written back shifted by one input, in
place, the SiLU folded in.  The pool is pinned to HBM there: it is small
enough (53-71 MB) that XLA would otherwise stage ALL of it through VMEM
around every call.  Ragged batches and the oracle keep
:func:`causal_conv_ragged` (``jax.numpy``: gathers by token and a row
scatter), which is also the decode form's numerics reference.  The selective
scan's convolution (``ssm_ops``) is the same two forms with a ``bias``
handed to the kernel.
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


def unpack_state(stored, dv: int):
    """A slot's stored state ``[H / P, dk, P * dv]`` → ``[H, dk, dv]`` (the
    plain layout, ``P`` 1, as it is)."""
    G, dk, width = stored.shape
    P = width // dv
    if P == 1:
        return stored
    return stored.reshape(G, dk, P, dv).transpose(0, 2, 1, 3).reshape(
        G * P, dk, dv)


def pack_state(S, width: int):
    """``[H, dk, dv]`` → as a pool ``width`` lanes wide stores it."""
    H, dk, dv = S.shape
    P = width // dv
    if P == 1:
        return S
    return S.reshape(H // P, P, dk, dv).transpose(0, 2, 1, 3).reshape(
        H // P, dk, width)


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


def _gdn_conv_step_kernel(rows_ref, keep_ref, x_ref, w_ref, *refs, K: int,
                          rb: int):
    """One grid step = one sequence.  ``x`` / ``o`` blocks are ``[rb, C]``
    (``rb`` consecutive rows share one: fetched, and written back, once
    for all of them), the taps ``[K, C]`` (the same block every step), the
    carry ``[1, K - 1, C]`` at the sequence's pool row; ``xf`` is the ``x``
    block in float32.  ``refs`` opens with the bias ``[1, C]`` where the
    convolution has one."""
    del rows_ref
    *b_ref, c_ref, o_ref, c_out_ref, xf_ref = refs
    r = pl.program_id(0)
    i = r % rb

    @pl.when(i == 0)
    def _():
        xf_ref[...] = x_ref[...].astype(jnp.float32)

    x = xf_ref[pl.ds(i, 1), :]                                  # [1, C]
    # a fresh or padded row starts from zeros whatever the slot holds
    old = jnp.where(keep_ref[r] != 0, c_ref[0].astype(jnp.float32),
                    0.0)                                        # [K-1, C]
    out = w_ref[K - 1:K, :] * x
    for d in range(1, K):            # causal_conv_ragged's order of summation
        out = out + w_ref[K - 1 - d:K - d, :] * old[K - 1 - d:K - d]
    for ref in b_ref:
        out = out + ref[...]
    o_ref[pl.ds(i, 1), :] = out * jax.nn.sigmoid(out)
    c_out_ref[0] = jnp.concatenate([old[1:], x], axis=0).astype(
        c_out_ref.dtype)


def causal_conv_step(x, conv_w, carry_pool, rows, keep, bias=None, *,
                     interpret=None):
    """The decode form of :func:`causal_conv_ragged` with the SiLU folded in:
    one new input a sequence row.  ``x`` [R, C], ``conv_w`` [K, C],
    ``carry_pool`` [N, K-1, C], ``rows`` [R] pool rows, ``keep`` [R] (False:
    the carry is zeros whatever the slot holds — a sequence's first token, a
    padded row), ``bias`` [C] or None (added before the SiLU: the selective
    scan's convolution has one, DeltaNet's has none) → (``silu(conv +
    bias)`` [R, C] float32, carry_pool updated in place: each row's ``[K-1,
    C]`` read once and written once, shifted by one input).  Several rows
    may name the trash row: one after the other, and never read."""
    R, C = x.shape
    K = conv_w.shape[0]
    assert carry_pool.shape[1:] == (K - 1, C), \
        f"carry pool {carry_pool.shape} does not hold [{K - 1}, {C}]"
    # rows of x and of the output move a whole tile of the widest packing
    # at a time (16 bf16 rows), not a [1, C] sliver a sequence
    rb = min(R, 16)
    row_block = pl.BlockSpec((rb, C), lambda r, rows, keep: (r // rb, 0))
    carry_block = pl.BlockSpec((1, K - 1, C),
                               lambda r, rows, keep: (rows[r], 0, 0))
    whole = lambda n: pl.BlockSpec((n, C), lambda r, rows, keep: (0, 0))  # noqa: E731
    biases = [] if bias is None else [bias.astype(jnp.float32)[None]]
    return pl.pallas_call(
        functools.partial(_gdn_conv_step_kernel, K=K, rb=rb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(R,),
            in_specs=[row_block, whole(K)] + [whole(1) for _ in biases]
            + [carry_block],
            out_specs=[row_block, carry_block],
            scratch_shapes=[pltpu.VMEM((rb, C), jnp.float32)]),
        # the pool (output and, through the alias, operand) is pinned to
        # HBM: left to itself XLA's memory-space assignment copies all of a
        # pool that fits (53-71 MB) into VMEM before the call and back out
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.float32),
                   pltpu.HBM(carry_pool.shape, carry_pool.dtype)],
        # operands count the scalar prefetches: the pool is the last one
        input_output_aliases={4 + len(biases): 1},
        interpret=_interpret() if interpret is None else interpret,
        # not "gdn_decode…": the benchmark reads that kernel by name
        name="gdn_conv_step",
    )(rows.astype(jnp.int32), keep.astype(jnp.int32), x,
      conv_w.astype(jnp.float32), *biases, carry_pool)


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
    T, dv, width = q.shape[0], v.shape[-1], state_pool.shape[-1]
    trash = state_pool.shape[0] - 1
    row_of = jnp.where(valid, rows[seq_of_token], trash)

    def token(t, carry):
        out, pool = carry
        S = jnp.where(pos_of_token[t] == 0, 0.0,
                      unpack_state(pool[row_of[t]], dv))
        S, o = _token_update(S, q[t], k[t], v[t], g[t], beta[t])
        return out.at[t].set(o), pool.at[row_of[t]].set(pack_state(S, width))

    return jax.lax.fori_loop(
        0, T, token, (jnp.zeros(v.shape, jnp.float32), state_pool))


# --------------------------------------------------------------------- #
# The chunked (WY) form over a ragged batch
# --------------------------------------------------------------------- #
_SUB = 16       # rows of a diagonal block solved one after the other


def _solve_by_substitution(A, rhs):
    """``(I + A)^-1 rhs`` for ``A`` [H, C, C] strictly lower, ``rhs`` [H, C,
    W], by forward substitution: the diagonal blocks of ``_SUB`` rows are
    inverted row by row, all of them at once (``_SUB`` steps), then the block
    rows are solved one after the other (``C / _SUB`` steps of matmuls).
    Backward stable whatever ``A`` holds — where ``beta`` runs to 2 and the
    keys of a chunk are alike, ``A``'s entries pass 1 and its powers, which
    the squarings add and cancel, grow by orders of magnitude before they
    vanish (PR 34 read 0.06–0.15 off the reference after 1,387 tokens in
    half of the seeds; with ``beta`` <= 1 the squarings are exact to 1e-6)."""
    dot = functools.partial(jnp.einsum, precision=_HI,
                            preferred_element_type=jnp.float32)
    H, C, W = rhs.shape
    B = math.gcd(C, _SUB)
    nb = C // B
    blocks = A.reshape(H, nb, B, nb, B)
    diag = jnp.stack([blocks[:, b, :, b, :] for b in range(nb)], axis=1)
    inv = jnp.broadcast_to(jnp.eye(B, dtype=jnp.float32), diag.shape)
    for r in range(1, B):        # row r of every block's inverse
        row = inv[:, :, r, :] - dot("hbj,hbjw->hbw", diag[:, :, r, :], inv)
        inv = inv.at[:, :, r, :].set(row)
    rhs = rhs.reshape(H, nb, B, W)
    out = []
    for b in range(nb):
        y = rhs[:, b]
        for c in range(b):
            y = y - dot("hij,hjw->hiw", blocks[:, b, :, c, :], out[c])
        out.append(dot("hij,hjw->hiw", inv[:, b], y))
    return jnp.stack(out, axis=1).reshape(H, C, W)


def _chunk_update(S0, q, k, v, g, beta, substitution: bool = False):
    """One chunk of one sequence, every head.  ``S0`` [H, dk, dv]; ``q``/
    ``k`` [C, H, dk], ``v`` [C, H, dv], ``g``/``beta`` [C, H]; positions past
    the chunk's length come zeroed (``g`` 0 too).  → (S', o [C, H, dv]).

    With ``G_i = sum_{j<=i} g_j``, ``A = strictly_lower(beta_i k_i.k_j
    exp(G_i - G_j))`` and ``T = (I + A)^-1``: ``u = T (beta v)``, ``w = T
    (beta exp(G) k)``, ``v' = u - w S0``, ``o = exp(G) q S0 +
    lower(q.k exp(G_i - G_j)) v'``, ``S' = exp(G_C) S0 + sum_i exp(G_C -
    G_i) k_i (x) v'_i``.  ``A`` is nilpotent, so ``T = prod_n (I + (-A)^(2^n))``:
    six squarings instead of a row-by-row substitution — for ``beta`` in
    (0, 1).  ``substitution``: :func:`_solve_by_substitution` instead, for a
    state kind whose ``beta`` runs past 1."""
    C = q.shape[0]
    dot = functools.partial(jnp.einsum, precision=_HI,
                            preferred_element_type=jnp.float32)
    G = jnp.cumsum(g, axis=0)                                    # [C, H]
    decay = jnp.exp(jnp.minimum(G[:, None, :] - G[None, :, :], 0.0))
    i, j = jnp.arange(C)[:, None, None], jnp.arange(C)[None, :, None]
    kb = k * beta[:, :, None]
    A = jnp.where(i > j, dot("ihd,jhd->ijh", kb, k) * decay, 0.0)
    if substitution:
        dv = v.shape[-1]
        solved = jnp.moveaxis(_solve_by_substitution(
            jnp.moveaxis(A, -1, 0), jnp.moveaxis(jnp.concatenate(
                [v * beta[:, :, None], kb * jnp.exp(G)[:, :, None]],
                axis=-1), 1, 0)), 0, 1)                          # [C, H, dv+dk]
        u, w = solved[..., :dv], solved[..., dv:]
    else:
        neg = jnp.moveaxis(-A, -1, 0)                            # [H, C, C]
        eye = jnp.eye(C, dtype=jnp.float32)[None]
        Tm, power = eye + neg, neg
        for _ in range(max(math.ceil(math.log2(C)) - 1, 0)):
            power = dot("hij,hjk->hik", power, power)
            Tm = dot("hij,hjk->hik", Tm, eye + power)
        u = dot("hij,jhd->ihd", Tm, v * beta[:, :, None])        # [C, H, dv]
        w = dot("hij,jhd->ihd", Tm, kb * jnp.exp(G)[:, :, None])  # [C, H, dk]
    v_new = u - dot("chk,hkd->chd", w, S0)
    qk = jnp.where(i >= j, dot("ihd,jhd->ijh", q, k) * decay, 0.0)
    o = dot("chk,hkd->chd", q * jnp.exp(G)[:, :, None], S0) \
        + dot("ijh,jhd->ihd", qk, v_new)
    tail = jnp.exp(G[-1][None, :] - G)                           # [C, H]
    S = S0 * jnp.exp(G[-1])[:, None, None] \
        + dot("chk,chd->hkd", k * tail[:, :, None], v_new)
    return S, o


def gdn_chunk_prefill(q, k, v, g, beta, state_pool, rows, *, cu_q_lens,
                      q_len, fresh, chunk: int = CHUNK,
                      substitution: bool = False):
    """The ragged batch's chunks, in order.  Shapes as :func:`gdn_recurrent`;
    ``cu_q_lens`` [S+1], ``q_len`` [S], ``fresh`` [S].  Tokens of no
    sequence (the batch's padding) get zeros."""
    T, dv, width = q.shape[0], v.shape[-1], state_pool.shape[-1]
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

        S0 = jnp.where(fresh[s] & (n == 0), 0.0,
                       unpack_state(pool[rows[s]], dv))
        S, o = _chunk_update(S0, cut(qp), cut(kp), cut(vp), cut(gp), cut(bp),
                             substitution)
        old = jax.lax.dynamic_slice_in_dim(out, start, chunk, axis=0)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(live[:, None, None], o, old), start, axis=0)
        return out, pool.at[rows[s]].set(pack_state(S, width))

    out, pool = jax.lax.fori_loop(
        0, ends[-1], one,
        (jnp.zeros((T + chunk,) + v.shape[1:], jnp.float32), state_pool))
    return out[:T], pool


# --------------------------------------------------------------------- #
# One token a sequence: the Pallas kernel
# --------------------------------------------------------------------- #
def _head_block(groups: int, heads_per_step: int) -> int:
    """Packed head rows a grid step takes, of ``groups`` (= ``H / P``).  The
    block's second-minor extent in the ``q``/``k``/``v`` operands must be a
    multiple of 8 or the whole axis (Mosaic's block rule), so: the whole axis
    where it is no more than ``heads_per_step``, else the largest multiple of
    8 up to ``heads_per_step`` that divides it (32 heads: 16, two steps a
    sequence), else the whole axis (30 heads have no such divisor: one step
    a sequence)."""
    if groups <= heads_per_step:
        return groups
    for hb in range(heads_per_step - heads_per_step % 8, 0, -8):
        if groups % hb == 0:
            return hb
    return groups


def _gdn_decode_kernel(rows_ref, q_ref, k_ref, v_ref, a_ref, b_ref, s_ref,
                       o_ref, s_out_ref, *, hb: int, P: int):
    """One grid step = ``hb`` packed rows (``hb * P`` heads) of one sequence.
    ``q``/``k`` blocks are ``[1, hb * P, dk]``; ``v``/``a``/``b``/``o``
    ``[1, hb, P * dv]`` (``a``/``b`` broadcast along a head's lanes); ``s``
    ``[1, hb, dk, P * dv]``: row ``h`` holds heads ``P h .. P h + P - 1``
    side by side."""
    del rows_ref
    kT = k_ref[0].T                                  # [dk, hb * P]
    qT = q_ref[0].T
    if P > 1:
        dv = v_ref.shape[-1] // P
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, P * dv), 1)

    def column(xT, h):
        """Row ``h``'s heads' columns, each over its own head's lanes."""
        if P == 1:
            return xT[:, h:h + 1]                    # [dk, 1]
        col = xT[:, P * h + P - 1:P * h + P]
        for p in range(P - 2, -1, -1):
            col = jnp.where(lane < (p + 1) * dv,
                            xT[:, P * h + p:P * h + p + 1], col)
        return col                                   # [dk, P * dv]

    for h in range(hb):
        a = a_ref[0, h:h + 1, :]                     # [1, P * dv]
        # a decay of exactly 0: the row starts from zeros (a reused slot's
        # last owner may have left anything there, NaN included)
        S = jnp.where(a > 0.0, s_ref[0, h] * a, 0.0)  # [dk, P * dv]
        kc = column(kT, h)
        delta = (v_ref[0, h:h + 1, :]
                 - jnp.sum(S * kc, axis=0, keepdims=True)) \
            * b_ref[0, h:h + 1, :]                   # [1, P * dv]
        S = S + kc * delta
        s_out_ref[0, h] = S
        o_ref[0, h:h + 1, :] = jnp.sum(S * column(qT, h), axis=0,
                                       keepdims=True)


def gdn_decode(q, k, v, alpha, beta, state_pool, rows, *,
               heads_per_step: int = 16, interpret=None):
    """``q``/``k`` [R, H, dk], ``v`` [R, H, dv], ``alpha`` (= ``exp(g)``; 0:
    start from zeros) and ``beta`` [R, H] float32, ``state_pool`` [N, H / P,
    dk, P * dv] (``P`` heads a row: the module docstring), ``rows`` [R] →
    (o [R, H, dv] float32, state_pool updated in place).  Several rows may
    name the trash row: one after the other, and never read."""
    R, H, dk = q.shape
    dv = v.shape[-1]
    groups, width = state_pool.shape[1], state_pool.shape[-1]
    P = width // dv
    assert state_pool.shape[1:] == (H // P, dk, P * dv), \
        f"state pool {state_pool.shape} does not store [{H}, {dk}, {dv}]"
    hb = _head_block(groups, heads_per_step)
    lanes = lambda x: jnp.broadcast_to(  # noqa: E731
        x.astype(jnp.float32)[:, :, None], (R, H, dv))
    if P > 1:                # P heads side by side, as the pool has them
        packed = lambda x: x.reshape(R, groups, width)  # noqa: E731
    else:
        packed = lambda x: x  # noqa: E731
    row_block = lambda n, d: pl.BlockSpec(  # noqa: E731
        (1, n, d), lambda r, j, rows: (r, j, 0))
    state_block = pl.BlockSpec((1, hb, dk, width),
                               lambda r, j, rows: (rows[r], j, 0, 0))
    o, pool = pl.pallas_call(
        functools.partial(_gdn_decode_kernel, hb=hb, P=P),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R, groups // hb),
            in_specs=[row_block(hb * P, dk), row_block(hb * P, dk),
                      row_block(hb, width), row_block(hb, width),
                      row_block(hb, width), state_block],
            out_specs=[row_block(hb, width), state_block]),
        out_shape=[jax.ShapeDtypeStruct((R, groups, width), jnp.float32),
                   jax.ShapeDtypeStruct(state_pool.shape, state_pool.dtype)],
        # operands count the scalar prefetch: the pool is operand 6
        input_output_aliases={6: 1},
        interpret=_interpret() if interpret is None else interpret,
        name="gdn_decode",
    )(rows.astype(jnp.int32), q.astype(jnp.float32), k.astype(jnp.float32),
      packed(v.astype(jnp.float32)), packed(lanes(alpha)),
      packed(lanes(beta)), state_pool)
    return (o.reshape(R, H, dv) if P > 1 else o), pool


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
        if mode == "decode":
            R = min(S, T)            # one token a row, row-major
            # a fresh or padded row starts from zeros whatever its slot
            # holds, in the convolution and in the recurrence
            keep = (q_len[:R] > 0) & ~fresh[:R]
            x, carry_pool = causal_conv_step(mixed[:R], conv_w, carry_pool,
                                             rows[:R], keep)
            if T > R:
                x = jnp.pad(x, ((0, T - R), (0, 0)))
        else:
            x, carry_pool = causal_conv_ragged(
                mixed, conv_w, carry_pool, rows,
                seq_of_token=batch["seq_of_token"],
                q_offset=batch["q_offset"], q_len=q_len, fresh=fresh)
            x = jax.nn.silu(x)
    with jax.named_scope("attention/gdn_core"):
        Kd = Hk * dk
        q = l2norm(x[:, :Kd].reshape(T, Hk, dk)) / math.sqrt(dk)
        k = l2norm(x[:, Kd:2 * Kd].reshape(T, Hk, dk))
        q = jnp.repeat(q, Hv // Hk, axis=1)
        k = jnp.repeat(k, Hv // Hk, axis=1)
        v = x[:, 2 * Kd:].reshape(T, Hv, dv)
        g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
        # trace time only: what a run says about the form it compiled (the
        # recurrence's and the convolution's are chosen together)
        impl = "kernel" if mode == "decode" else "xla"
        get_tracer().record(
            "attn/gdn_layout", time.perf_counter(), 0.0,
            rows=R if mode == "decode" else T, heads=Hv, chunk=CHUNK,
            key_dim=dk, value_dim=dv, state_layout=kind.state_layout,
            state_dtype=jnp.dtype(state_pool.dtype).name, form=mode,
            impl=impl, conv_impl=impl)
        if mode == "decode":
            # the kernel reads a decay of exactly 0 as "no state"
            alpha = jnp.where(keep[:, None], jnp.exp(g[:R]), 0.0)
            live = (q_len[:R] > 0)[:, None]
            o, state_pool = gdn_decode(
                q[:R], k[:R], v[:R], alpha, jnp.where(live, beta[:R], 0.0),
                state_pool, rows[:R])
            if T > R:
                o = jnp.pad(o, ((0, T - R), (0, 0), (0, 0)))
        elif mode == "ragged":
            o, state_pool = gdn_chunk_prefill(
                q, k, v, g, beta, state_pool, rows,
                cu_q_lens=batch["cu_q_lens"], q_len=q_len, fresh=fresh,
                substitution=kind.beta_max > 1.0)
        else:
            o, state_pool = gdn_recurrent(
                q, k, v, g, beta, state_pool, rows,
                seq_of_token=batch["seq_of_token"],
                pos_of_token=batch["pos_of_token"], valid=valid)
    return o, (state_pool, carry_pool)
