"""Plain reference of the Phi-4-mini-flash-reasoning decoder (``model_type:
phi4flash``; the SambaY decoder-hybrid-decoder of Ren et al., arXiv
2507.06607, with differential attention): the forward pass in
straightforward ``jax.numpy`` and float32 — no kernels, no cache, no
batching, no scan over layers, the selective scan token by token, dense
causal and banded masks, one sequence at a time.  It shares no code with
``deepspeed_tpu``.

Follows microsoft/Phi-4-mini-flash-reasoning ``config.json``.  With ``L =
num_hidden_layers`` and ``M = L // 2`` the mixer of layer ``l`` is read off
``l`` (ISSUE 55; the configuration file lists under ``assumed`` what the
config does not settle):

  block      pre-norm: ``h = LN1(x)``, ``x += mixer(h)``, ``x += mlp(LN2(x))``
             with LayerNorm (weight and bias, eps ``layer_norm_eps``);
             ``mlp(u) = (silu(g) * y) W_2``, ``[g | y] = u W_1``, no bias; a
             final LayerNorm; logits ``= x E^T`` (tied, no bias).  No
             positional encoding of any kind.
  scan       ``l`` even, ``l <= M``.  ``[u | z] = h W_in``; ``c = silu(conv4(u)
             + b)``, causal depthwise, zeros before the sequence; ``[r | B |
             C] = c W_x``; ``delta = softplus(r W_dt + b_dt)``; ``A =
             -exp(A_log)`` ``[Ci, N]``; ``S_t = exp(delta_t A) S_{t-1} +
             (delta_t c_t) (x) B_t``; ``y_t = S_t C_t + D c_t``; ``out = (y *
             silu(z)) W_out``.  Layer ``M`` hands ``m = y`` (before the ``z``
             gate) to the memory units.
  attention  ``l`` odd, ``l < M``: window ``sliding_window`` (a token attends
             itself and the ``W - 1`` before it); ``l = M + 1``: full causal.
             ``[q | k | v] = h W_qkv + b``; ``q`` as ``H`` heads of ``hd``,
             ``k``, ``v`` as ``KV``; ``q1_i = q[2i]``, ``q2_i = q[2i+1]``,
             ``k1_j = k[2j]``, ``k2_j = k[2j+1]``, ``V_j = [v[2j] | v[2j+1]]``,
             ``j = i // (H / KV)``; ``A1_i = softmax(q1_i k1_j^T / sqrt(hd))
             V_j``, ``A2_i`` from ``q2``, ``k2``; ``lambda = exp(lq1 . lk1) -
             exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6
             exp(-0.3 l)``; ``o_i = (1 - lambda_init) rmsnorm(A1_i - lambda
             A2_i) * w``; ``out = concat(o) W_o + b_o``.
  memory     ``l`` even, ``l > M + 1``: ``out = (m * silu(h W_g)) W_o``.
  cross      ``l`` odd, ``l > M + 1``: ``q = h W_q + b`` only; the
             differential form against layer ``M + 1``'s ``k``, ``v`` (full
             causal), with its own lambda vectors, sub-norm and ``W_o``.

Departures, each on purpose:
  * every weight is cast to float32 where it is used, the head is computed
    over blocks of the vocabulary (each block's logits go to the host as
    they are made: ``logits`` returns numpy arrays) and attention over blocks
    of query rows, so that the model fits beside the system under test;
  * the loops over tokens (the scan) and query blocks are ``jax.lax.scan`` /
    ``fori_loop``: unrolled, a layer's program grows with the sequence;
  * ``mutation`` breaks one piece of the mathematics on purpose.  It is for
    the tests and the controls that show the comparison notices each piece.

Matrix multiplications run under ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

#: what ``mutation`` may be (None = the model as read above)
MUTATIONS = ("no_lambda", "window_plus_one", "window_minus_one",
             "m_after_gate", "m_other_layer", "no_d_skip", "no_conv_carry",
             "cross_own_window", "no_subln")

Q_BLOCK = 512          # query rows per attention block
V_BLOCK = 16384        # vocabulary rows per head block


def f32(x):
    return jnp.asarray(x, jnp.float32)


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * f32(w) + f32(b)


def selective_scan(h, w: Dict, mutation=None):
    """``h`` [S, D] → (out [S, D], y [S, Ci] before the gate, gated [S,
    Ci])."""
    S = h.shape[0]
    uz = h @ f32(w["w_in"])
    Ci = uz.shape[1] // 2
    u, z = uz[:, :Ci], uz[:, Ci:]
    conv = f32(w["conv"])                                   # [K, Ci]
    K = conv.shape[0]
    if mutation == "no_conv_carry":
        c = conv[K - 1][None, :] * u
    else:
        # out_t = sum_j conv[j] * u_{t-(K-1)+j}, zeros before the sequence
        padded = jnp.concatenate([jnp.zeros((K - 1, Ci), jnp.float32), u])
        c = sum(conv[j][None, :] * padded[j:j + S] for j in range(K))
    c = jax.nn.silu(c + f32(w["conv_b"]))
    rbc = c @ f32(w["w_x"])
    N = f32(w["A_log"]).shape[1]
    R = rbc.shape[1] - 2 * N
    delta = jax.nn.softplus(rbc[:, :R] @ f32(w["w_dt"]) + f32(w["b_dt"]))
    Bm, Cm = rbc[:, R:R + N], rbc[:, R + N:]
    A = -jnp.exp(f32(w["A_log"]))                           # [Ci, N]

    def token(state, x):
        d_t, c_t, b_t, c_out = x
        state = jnp.exp(d_t[:, None] * A) * state \
            + (d_t * c_t)[:, None] * b_t[None, :]
        return state, state @ c_out

    _, y = jax.lax.scan(token, jnp.zeros((Ci, N), jnp.float32),
                        (delta, c, Bm, Cm))
    if mutation != "no_d_skip":
        y = y + f32(w["D"]) * c
    gated = y * jax.nn.silu(z)
    return gated @ f32(w["w_out"]), y, gated


def differential_attention(h, w: Dict, c: Dict, l: int, window, kv=None,
                           mutation=None):
    """``h`` [S, D] → (out [S, D], (k, v) this layer made or was given).
    ``window`` None: full causal.  ``kv`` given: a query-only layer."""
    S = h.shape[0]
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // H
    qkv = h @ f32(w["w_qkv"]) + f32(w["b_qkv"])
    q = qkv[:, :H * hd].reshape(S, H, hd)
    if kv is None:
        k = qkv[:, H * hd:(H + KV) * hd].reshape(S, KV, hd)
        v = qkv[:, (H + KV) * hd:].reshape(S, KV, hd)
    else:
        k, v = kv
    q1, q2 = q[:, 0::2], q[:, 1::2]                         # [S, H/2, hd]
    k1, k2 = k[:, 0::2], k[:, 1::2]                         # [S, KV/2, hd]
    vv = jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1)  # [S, KV/2, 2hd]
    G = H // KV
    k1, k2, vv = (jnp.repeat(a, G, axis=1) for a in (k1, k2, vv))
    pos = jnp.arange(S)
    n_blocks = -(-S // Q_BLOCK)
    pad = ((0, n_blocks * Q_BLOCK - S), (0, 0), (0, 0))
    q1, q2 = jnp.pad(q1, pad), jnp.pad(q2, pad)

    def attend(qb, kk, rows):
        s = jnp.einsum("qhd,khd->hqk", qb, kk) / math.sqrt(hd)
        ok = pos[None, :] <= rows[:, None]
        if window is not None:
            ok = ok & (rows[:, None] - pos[None, :] < window)
        s = jnp.where(ok[None], s, -1e30)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), vv)

    def block(i, out):
        a1, a2 = out
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        cut = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, i * Q_BLOCK, Q_BLOCK, axis=0)
        put = lambda a, o: jax.lax.dynamic_update_slice_in_dim(  # noqa: E731
            a, o, i * Q_BLOCK, 0)
        return put(a1, attend(cut(q1), k1, rows)), \
            put(a2, attend(cut(q2), k2, rows))

    zeros = jnp.zeros((n_blocks * Q_BLOCK, H // 2, 2 * hd), jnp.float32)
    a1, a2 = jax.lax.fori_loop(0, n_blocks, block, (zeros, zeros))
    a1, a2 = a1[:S], a2[:S]
    lam = f32(w["lam"])                                     # [4, hd]
    init = 0.8 - 0.6 * jnp.exp(-0.3 * l)
    full = jnp.exp(jnp.sum(lam[0] * lam[1])) \
        - jnp.exp(jnp.sum(lam[2] * lam[3])) + init
    o = a1 if mutation == "no_lambda" else a1 - full * a2
    if mutation != "no_subln":
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                              + float(c["layer_norm_eps"])) * f32(w["subln"])
    o = (1.0 - init) * o
    return o.reshape(S, -1) @ f32(w["w_o"]) + f32(w["b_o"]), (k, v)


def role_of(l: int, c: Dict, mutation=None) -> str:
    """What layer ``l`` is, and whether it hands something on."""
    M = c["num_hidden_layers"] // 2
    if l % 2 == 0 and l <= M:
        hands = l == (M - 2 if mutation == "m_other_layer" else M)
        return "scan_hands" if hands else "scan"
    if l % 2 == 0:
        return "memory"
    return "window" if l < M else "full" if l == M + 1 else "cross"


def layer(x, w: Dict, c: Dict, role: str, l, memory, mutation=None):
    """One layer of kind ``role`` (:func:`role_of`), ``l`` its index (a
    number: only ``lambda_init`` reads it); ``memory`` = (m, kv) as the
    layers before left it → (x, memory)."""
    eps = float(c["layer_norm_eps"])
    W = c["sliding_window"]
    if mutation == "window_plus_one":
        W += 1
    elif mutation == "window_minus_one":
        W -= 1
    m, kv = memory
    with jax.default_matmul_precision("highest"):
        h = layer_norm(x, w["ln1_w"], w["ln1_b"], eps)
        if role in ("scan", "scan_hands"):
            out, y, gated = selective_scan(h, w, mutation)
            if role == "scan_hands":
                m = gated if mutation == "m_after_gate" else y
        elif role == "memory":
            out = (m * jax.nn.silu(h @ f32(w["w_g"]))) @ f32(w["w_o"])
        elif role in ("window", "full"):
            out, made = differential_attention(
                h, w, c, l, W if role == "window" else None,
                mutation=mutation)
            if role == "full":
                kv = made
        else:
            out, _ = differential_attention(
                h, w, c, l, W if mutation == "cross_own_window" else None,
                kv=kv, mutation=mutation)
        x = x + out
        gy = layer_norm(x, w["ln2_w"], w["ln2_b"], eps) @ f32(w["w1"])
        F = gy.shape[1] // 2
        return x + (jax.nn.silu(gy[:, :F]) * gy[:, F:]) @ f32(w["w2"]), \
            (m, kv)


def head_block(x_last, norm_w, norm_b, rows, eps):
    """The final norm and the logits of one block of the vocabulary
    (``rows`` [V_BLOCK, D] of the tied embedding)."""
    with jax.default_matmul_precision("highest"):
        return layer_norm(x_last, norm_w, norm_b, eps) @ f32(rows).T


class Reference:
    """Drives the layer function over a model whose weights arrive one layer
    at a time.  ``config`` holds the published ``config.json`` keys."""

    def __init__(self, config: Dict, mutation: Optional[str] = None):
        assert mutation is None or mutation in MUTATIONS, mutation
        self.config = config
        self.mutation = mutation
        self._layer = jax.jit(
            lambda x, w, role, l, memory: layer(x, w, config, role, l,
                                                memory, mutation),
            static_argnums=2)
        self._head = jax.jit(lambda x, nw, nb, rows: head_block(
            x, nw, nb, rows, float(config["layer_norm_eps"])))

    def logits(self, token_rows: List, weights: Dict,
               positions: List[List[int]]) -> List:
        """Each row of ``token_rows`` (a 1-D int array) through the model;
        for row r the logits [len(positions[r]), V] at its ``positions[r]``.

        ``weights``: ``embedding`` [V, D], ``norm_w`` / ``norm_b`` [D] and
        ``layers``, a list of zero-argument callables each returning one
        layer's weights: ``ln1_w``, ``ln1_b``, ``ln2_w``, ``ln2_b`` [D],
        ``w1`` [D, 2F], ``w2`` [F, D]; a scan layer ``w_in`` [D, 2Ci],
        ``conv`` [K, Ci], ``conv_b`` [Ci], ``w_x`` [Ci, R + 2N], ``w_dt``
        [R, Ci], ``b_dt`` [Ci], ``A_log`` [Ci, N], ``D`` [Ci], ``w_out``
        [Ci, D]; an attention layer ``w_qkv`` [D, (H + 2KV) hd] (a cross
        layer [D, H hd]), ``b_qkv``, ``lam`` [4, hd] (lq1, lk1, lq2, lk2),
        ``subln`` [2hd], ``w_o`` [H hd, D], ``b_o`` [D]; a memory unit
        ``w_g`` [D, Ci], ``w_o`` [Ci, D].  Any dtype: every use is in
        float32."""
        Ci = None
        xs = [f32(jnp.take(weights["embedding"], row, axis=0))
              for row in token_rows]
        memories = [(None, None)] * len(xs)
        for l, make in enumerate(weights["layers"]):
            w = make()
            if Ci is None:
                Ci = w["w_in"].shape[1] // 2
                memories = [(jnp.zeros((x.shape[0], Ci), jnp.float32), None)
                            for x in xs]
            for r, x in enumerate(xs):
                xs[r], memories[r] = self._layer(
                    x, w, role_of(l, self.config, self.mutation), float(l),
                    memories[r])
            del w
        # the logits leave the device a vocabulary block at a time: 2,560
        # positions of 200,064 float32 logits are 2 GB a row
        embedding = weights["embedding"]
        out = []
        for x, pos in zip(xs, positions):
            last = jnp.take(x, jnp.asarray(pos, jnp.int32), axis=0)
            out.append(np.concatenate([np.asarray(self._head(
                last, weights["norm_w"], weights["norm_b"],
                embedding[lo:lo + V_BLOCK]))
                for lo in range(0, embedding.shape[0], V_BLOCK)], axis=-1))
        return out
