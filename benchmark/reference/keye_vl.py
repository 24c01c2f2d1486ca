"""Plain reference of the Keye-VL-2.0 language model (``model_type:
KeyeVL2``): the forward pass in straightforward ``jax.numpy`` and float32 —
no kernels, no cache, no batching, no scan over layers, one sequence at a
time.  It shares no code with ``deepspeed_tpu``.

Follows Kwai-Keye/Keye-VL-2.0-30B-A3B ``config.json`` (the catalog row of
``/opt/skills/guides/model-configs``): 32 query / 4 K/V heads of 128 with
per-head RMS norms, M-RoPE over sections of 16 / 24 / 24 rotary pairs, and
``sa_config``'s indexer (16 heads of 64, one key a token, ``topk`` 2,048);
128 softmax-routed experts of 768, top 8 renormalised, no shared expert.

The layer (``p_t`` the token's three positions, ``(t, t, t)`` for text)::

    h = rms(x)
    q = rope(rms_head(h W_q))   k = rope(rms_head(h W_k))   v = h W_v
    qI = rope(h W_qI) [16, 64]  kI = rope(h W_kI) [64]      w = h W_wI [16]
    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])            s <= t
    S_t = the topk positions s <= t of largest I[t, s]
    o[t] = softmax over S_t of q[t] . k[s] / sqrt(128), times v[s]
    x = x + o W_o;  h = rms(x);  x = x + experts(h)

What the config does not fix, and what is assumed here (the configuration
file lists the same points under ``assumed``, each with its alternative):
  1. per-head RMS norms on q and k (weight as it is), before the rotary;
  2. the indexer reads the layer's normed input ``h``; rotary on ``qI`` and
     ``kI`` over their 32 pairs by the TEMPORAL stream; no norm on ``kI``;
  3. DSA's positive constants on ``I`` change no set and are left out;
  4. ties in ``I`` go to the LOWER position: the set is the first ``topk``
     of a STABLE descending sort of the causal scores;
  5. ``q_chunk_size`` / ``kv_chunk_size`` are tiles of the published code,
     not blocks of selection; rotary pairs in the half-split layout.

Departures, each on purpose:
  * the vision tower is not built: ids are text, the three streams equal
    (``pos3`` takes unequal ones, for the tests of the section arithmetic);
  * THE CHIP'S SHARE: the configuration's ``num_experts`` experts are the
    ones held here, ``ep_size`` chips share a layer and this is chip
    ``ep_rank``.  The router scores all ``num_experts * ep_size`` experts,
    takes the top 8 of all, renormalises over them, and the experts held
    here add their part; what the absent ones would add is left out;
  * every weight is cast to float32 where it is used; attention runs over
    blocks of query rows and the head over blocks of the vocabulary, so
    that the model fits beside the system under test;
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

#: what ``mutation`` may be (None = the model as published)
MUTATIONS = ("recent_topk", "dense", "future_in_chunk", "no_relu", "no_w",
             "permute_sections", "no_renorm", "no_qk_norm",
             "ties_to_higher")

Q_BLOCK = 256          # query rows per attention block
V_BLOCK = 16384        # vocabulary columns per head block
CHUNK = 16             # "future_in_chunk": the chunk a query's set may see


def f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * f32(w)


def angles(pos, dim, theta):
    inv = 1.0 / (float(theta) ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                                  / dim))
    return pos.astype(jnp.float32)[:, None] * inv[None, :]      # [S, dim/2]


def rotate(x, ang):
    """Half-split rotary of ``x [S, H, d]`` by ``ang [S, d/2]``."""
    half = x.shape[-1] // 2
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mrope_angles(pos3, c: Dict, mutation=None):
    """``pos3 [3, S]`` → ``[S, head_dim / 2]``: pair ``i`` turns by the
    stream of its section."""
    section = list(c["mrope_section"])
    streams = [0, 1, 2]
    if mutation == "permute_sections":
        streams = [1, 2, 0]
    hd = c["head_dim"]
    cols, lo = [], 0
    for n, stream in zip(section, streams):
        cols.append(angles(pos3[stream], hd, c["rope_theta"])[:, lo:lo + n])
        lo += n
    return jnp.concatenate(cols, axis=-1)


def chosen_rows(rows, qi, wi, ki, topk, mutation=None):
    """The sets of the queries at positions ``rows [Q]`` (``qi [Q, Hi, di]``,
    ``wi [Q, Hi]``) over the ``S`` keys ``ki [S, di]`` → bool ``[Q, S]``."""
    col = jnp.arange(ki.shape[0])
    causal = col[None, :] <= rows[:, None]                      # [Q, S]
    dots = jnp.einsum("qjd,sd->qjs", qi, ki)
    if mutation != "no_relu":
        dots = jnp.maximum(dots, 0.0)
    score = jnp.sum(wi[:, :, None] * dots, axis=1)              # I [Q, S]
    seen = causal
    if mutation == "future_in_chunk":
        seen = col[None, :] < ((rows // CHUNK + 1) * CHUNK)[:, None]
    if mutation == "dense":
        return causal
    if mutation == "recent_topk":
        return causal & (col[None, :] > rows[:, None] - topk)
    key = jnp.where(seen, -score, jnp.inf)
    if mutation == "ties_to_higher":
        key = key[:, ::-1]
    order = jnp.argsort(key, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)              # a column's place
    if mutation == "ties_to_higher":
        rank = rank[:, ::-1]
    return seen & (rank < topk)


def indexer(h, w: Dict, c: Dict, pos3, mutation=None):
    """``h`` [S, D] → (qI [S, Hi, di], kI [S, di], w [S, Hi])."""
    S = h.shape[0]
    sa = c["sa_config"]
    Hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    ang_i = angles(pos3[0], di, c["rope_theta"])
    qi = rotate((h @ f32(w["w_qi"])).reshape(S, Hi, di), ang_i)
    ki = rotate((h @ f32(w["w_ki"])).reshape(S, 1, di), ang_i)[:, 0]
    wi = h @ f32(w["w_wi"])                                     # [S, Hi]
    if mutation == "no_w":
        wi = jnp.ones_like(wi)
    return qi, ki, wi


def index_sets(x, w: Dict, c: Dict, pos3, rows):
    """The sets of the queries at ``rows`` in this layer, from the layer's
    input ``x``: bool ``[len(rows), S]``."""
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, w["in_norm"], float(c["rms_norm_eps"]))
        qi, ki, wi = indexer(h, w, c, pos3)
        return chosen_rows(rows, qi[rows], wi[rows], ki,
                           c["sa_config"]["topk"])


def sparse_attention(h, w: Dict, c: Dict, pos3, mutation=None):
    """``h`` [S, D] → [S, D]: the indexer's scores as a ``[S, S]`` matrix in
    blocks of query rows, the set by a stable descending sort, dense causal
    softmax under the set's mask."""
    S = h.shape[0]
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    topk = c["sa_config"]["topk"]
    eps = float(c["rms_norm_eps"])
    q = (h @ f32(w["w_q"])).reshape(S, H, hd)
    k = (h @ f32(w["w_k"])).reshape(S, KV, hd)
    v = (h @ f32(w["w_v"])).reshape(S, KV, hd)
    if mutation != "no_qk_norm":
        q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
    ang = mrope_angles(pos3, c, mutation)
    q, k = rotate(q, ang), rotate(k, ang)
    qi, ki, wi = indexer(h, w, c, pos3, mutation)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    n_blocks = -(-S // Q_BLOCK)
    pad = n_blocks * Q_BLOCK - S
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    qi = jnp.pad(qi, ((0, pad), (0, 0), (0, 0)))
    wi = jnp.pad(wi, ((0, pad), (0, 0)))

    def block(i, out):
        take = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, i * Q_BLOCK, Q_BLOCK, axis=0)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        chosen = chosen_rows(rows, take(qi), take(wi), ki, topk, mutation)
        s = jnp.einsum("qhd,khd->hqk", take(q), k) / math.sqrt(hd)
        s = jnp.where(chosen[None], s, -1e30)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
        return jax.lax.dynamic_update_slice_in_dim(out, o, i * Q_BLOCK, 0)

    o = jax.lax.fori_loop(0, n_blocks, block, jnp.zeros_like(q))[:S]
    return o.reshape(S, H * hd) @ f32(w["w_o"])


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ f32(w_gate)) * (x @ f32(w_up))) @ f32(w_down)


def route(h, w: Dict, c: Dict, mutation=None):
    """Softmax over ALL experts in float32, the top ``num_experts_per_tok``,
    renormalised → dense weights [S, E_all] (zero off the top)."""
    p = jax.nn.softmax(h @ f32(w["router"]), axis=-1)
    top, idx = jax.lax.top_k(p, c["num_experts_per_tok"])
    if c.get("norm_topk_prob", True) and mutation != "no_renorm":
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, idx].set(top)


def expert_layer(h, w: Dict, c: Dict, mutation=None):
    """The routed experts held here, one after the other; no shared one."""
    dense = route(h, w, c, mutation)
    held = w["e_gate"].shape[0]
    offset = int(c.get("ep_rank", 0)) * held

    def one_expert(e, out):
        y = swiglu(h, w["e_gate"][e], w["e_up"][e], w["e_down"][e])
        weight = jax.lax.dynamic_index_in_dim(dense, offset + e, axis=1)
        return out + weight * y

    return jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(h))


def layer(x, w: Dict, c: Dict, pos3, mutation=None):
    eps = float(c["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, w["in_norm"], eps)
        x = x + sparse_attention(h, w, c, pos3, mutation)
        h = rms_norm(x, w["post_norm"], eps)
        return x + expert_layer(h, w, c, mutation)


def head(x_last, norm_scale, w_head, eps):
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x_last, norm_scale, eps)
        V = w_head.shape[1]
        return jnp.concatenate(
            [x @ f32(w_head[:, lo:lo + V_BLOCK])
             for lo in range(0, V, V_BLOCK)], axis=-1)


class Reference:
    """Drives the layer function over a model whose weights arrive one layer
    at a time.  ``config`` holds the published ``config.json`` keys
    (``sa_config`` and ``mrope_section`` among them) and the share's own
    (``ep_size``, ``ep_rank``)."""

    def __init__(self, config: Dict, mutation: Optional[str] = None):
        assert mutation is None or mutation in MUTATIONS, mutation
        self.config = config
        self._layer = jax.jit(
            lambda x, w, pos3: layer(x, w, config, pos3, mutation))
        self._head = jax.jit(lambda x, s, w: head(
            x, s, w, float(config["rms_norm_eps"])))
        self._sets = jax.jit(
            lambda x, w, pos3, rows: index_sets(x, w, config, pos3, rows))

    def logits(self, token_rows: List, weights: Dict,
               positions: List[List[int]], pos3: Optional[List] = None,
               sets_at: Optional[List[int]] = None) -> List:
        """Each row of ``token_rows`` (a 1-D int array) through the model;
        for row r the logits [len(positions[r]), V] at its ``positions[r]``.
        ``pos3``: per row the M-RoPE positions ``[3, S]`` (None: text).

        ``weights``: ``embedding`` [V, D], ``norm`` [D], ``head`` [D, V] and
        ``layers``, a list of zero-argument callables each returning one
        layer's weights: ``in_norm`` / ``post_norm`` [D], ``w_q`` [D, H*hd],
        ``w_k`` / ``w_v`` [D, KV*hd], ``q_norm`` / ``k_norm`` [hd], ``w_o``
        [H*hd, D], ``w_qi`` [D, Hi*di], ``w_ki`` [D, di], ``w_wi`` [D, Hi],
        ``router`` [D, E_all], ``e_gate`` / ``e_up`` [E_held, D, F],
        ``e_down`` [E_held, F, D].  Any dtype: every use is in float32.

        ``sets_at``: positions of row 0; the return is then (logits, sets)
        with ``sets`` bool ``[layers, len(sets_at), S]``: the sets of those
        queries in every layer."""
        xs = [f32(jnp.take(weights["embedding"], row, axis=0))
              for row in token_rows]
        if pos3 is None:
            pos3 = [np.broadcast_to(np.arange(len(row)), (3, len(row)))
                    for row in token_rows]
        pos3 = [jnp.asarray(p, jnp.int32) for p in pos3]
        sets = []
        for make in weights["layers"]:
            w = make()
            if sets_at is not None:
                sets.append(self._sets(xs[0], w, pos3[0],
                                       jnp.asarray(sets_at, jnp.int32)))
            for r, x in enumerate(xs):
                xs[r] = self._layer(x, w, pos3[r])
            del w
        out = [self._head(jnp.take(x, jnp.asarray(pos, jnp.int32), axis=0),
                          weights["norm"], weights["head"])
               for x, pos in zip(xs, positions)]
        return out if sets_at is None else (out, jnp.stack(sets))
