"""Plain reference of the Qwen3-Next decoder (``model_type: qwen3_next``):
the published forward pass in straightforward ``jax.numpy`` and float32 — no
kernels, no cache, no batching, no scan over layers, the Gated DeltaNet
recurrence token by token, dense causal attention, the experts one after the
other, one sequence at a time.  It shares no code with ``deepspeed_tpu``.

Follows Qwen/Qwen3-Next-80B-A3B-Instruct ``config.json`` and
``modeling_qwen3_next.py`` (``torch_recurrent_gated_delta_rule``,
``Qwen3NextGatedDeltaNet``, ``Qwen3NextAttention``,
``Qwen3NextSparseMoeBlock``).  Layer ``l`` is full attention iff
``(l + 1) % full_attention_interval == 0``; every norm but the gated one is
``x / rms(x) * (1 + w)``.

What the config does not fix, and what is assumed here (the configuration
file lists the same points under ``assumed``):
  1. the fused projections' columns: ``w_qkvz`` is ``[q | k | v | z]`` with
     each part head-major (HF's checkpoint interleaves them per key head;
     a permutation of columns of a seeded matrix), ``w_ba`` is ``[b | a]``,
     ``w_q`` of the attention layer is per head ``[q | gate]`` as HF has it;
  2. value head ``h`` reads key head ``h // (value heads / key heads)``
     (``repeat_interleave``);
  3. the recurrent state is float32;
  4. rotary pairs are in the half-split ("rotate_half") layout over the
     first ``partial_rotary_factor * head_dim`` values of a head.

Departures, each on purpose:
  * the multi-token-prediction module is not held: it follows the last layer
    on the last stage of the deployment;
  * THE CHIP'S SHARE: the configuration's ``num_experts`` experts are the
    ones held here, ``ep_size`` chips share a layer and this is chip
    ``ep_rank``.  The router scores all ``num_experts * ep_size`` experts,
    takes the top ``num_experts_per_tok`` of all, renormalises over them,
    and the experts held here add their part; what the absent ones would
    add is left out (guide section 4).  ``ep_size`` 1 is the uncut layer;
  * every weight is cast to float32 where it is used, the head is computed
    over blocks of the vocabulary and attention over blocks of query rows,
    so that the model fits beside the system under test;
  * the loops over tokens (the recurrence), experts and query blocks are
    ``jax.lax.scan`` / ``fori_loop``: unrolled, a layer's program grows with
    the sequence and the TPU's compiler takes minutes per length (PR 28);
  * ``mutation`` breaks one piece of the mathematics on purpose.  It is for
    the tests that show the comparison notices each piece.

Matrix multiplications run under ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

#: what ``mutation`` may be (None = the model as published)
MUTATIONS = ("no_decay", "no_beta", "no_conv", "no_z_gate", "no_attn_gate",
             "full_rotary", "plain_norm_weight", "no_shared_gate",
             "no_renorm", "no_qk_norm")

Q_BLOCK = 512          # query rows per attention block
V_BLOCK = 16384        # vocabulary columns per head block


def f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps, mutation=None):
    """``x / rms(x) * (1 + w)``."""
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps)
    return y * (f32(w) if mutation == "plain_norm_weight" else 1.0 + f32(w))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


def gated_delta_net(h, w: Dict, c: Dict, mutation=None):
    """``h`` [S, D] → [S, D]: projections, the causal depthwise convolution
    and SiLU, the gated delta rule one token after the other, the gated
    norm, the output projection."""
    S = h.shape[0]
    Hk, Hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    K = c["linear_conv_kernel_dim"]
    Kd, Vd = Hk * dk, Hv * dv
    qkvz = h @ f32(w["w_qkvz"])
    ba = h @ f32(w["w_ba"])
    mixed, z = qkvz[:, :2 * Kd + Vd], qkvz[:, 2 * Kd + Vd:]
    b, a = ba[:, :Hv], ba[:, Hv:]
    if mutation != "no_conv":
        # out_t = sum_j conv[j] * x_{t-(K-1)+j}, zeros before the sequence
        padded = jnp.concatenate(
            [jnp.zeros((K - 1, mixed.shape[1]), jnp.float32), mixed])
        conv = f32(w["conv"])                               # [K, C]
        mixed = sum(conv[j][None, :] * padded[j:j + S] for j in range(K))
    mixed = jax.nn.silu(mixed)
    q = mixed[:, :Kd].reshape(S, Hk, dk)
    k = mixed[:, Kd:2 * Kd].reshape(S, Hk, dk)
    v = mixed[:, 2 * Kd:].reshape(S, Hv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(f32(w["A_log"])) * jax.nn.softplus(a + f32(w["dt_bias"]))
    if mutation == "no_decay":
        g = jnp.zeros_like(g)
    if mutation == "no_beta":
        beta = jnp.ones_like(beta)
    q = jnp.repeat(l2norm(q) / math.sqrt(dk), Hv // Hk, axis=1)
    k = jnp.repeat(l2norm(k), Hv // Hk, axis=1)

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x                 # [Hv, dk] .. [Hv]
        state = state * jnp.exp(g_t)[:, None, None]
        kv_mem = jnp.sum(state * k_t[:, :, None], axis=1)       # [Hv, dv]
        delta = (v_t - kv_mem) * b_t[:, None]
        state = state + k_t[:, :, None] * delta[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    _, o = jax.lax.scan(token, jnp.zeros((Hv, dk, dv), jnp.float32),
                        (q, k, v, g, beta))                    # [S, Hv, dv]
    # the gated norm: weight as it is (not 1 + w), over one head's values
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + float(c["rms_norm_eps"])) * f32(w["gnorm"])
    if mutation != "no_z_gate":
        o = o * jax.nn.silu(z.reshape(S, Hv, dv))
    return o.reshape(S, Vd) @ f32(w["w_o"])


def rope(x, pos, c: Dict, mutation=None):
    """Half-split rotary over the first ``partial_rotary_factor`` of the
    head's values; ``x`` [S, H, hd]."""
    hd = x.shape[-1]
    rd = hd if mutation == "full_rotary" \
        else int(hd * c["partial_rotary_factor"])
    inv = 1.0 / (float(c["rope_theta"])
                 ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]      # [S, rd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :rd // 2], x[..., rd // 2:rd], x[..., rd:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def gated_attention(h, w: Dict, c: Dict, mutation=None):
    """``h`` [S, D] → [S, D]: dense causal softmax attention with per-head
    q/k norms, partial rotary and a sigmoid output gate; query rows in
    blocks."""
    S = h.shape[0]
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    eps = float(c["rms_norm_eps"])
    qg = (h @ f32(w["w_q"])).reshape(S, H, 2, hd)
    q, gate = qg[:, :, 0, :], qg[:, :, 1, :]
    k = (h @ f32(w["w_k"])).reshape(S, KV, hd)
    v = (h @ f32(w["w_v"])).reshape(S, KV, hd)
    if mutation != "no_qk_norm":
        q = rms_norm(q, w["q_norm"], eps, mutation)
        k = rms_norm(k, w["k_norm"], eps, mutation)
    pos = jnp.arange(S)
    q, k = rope(q, pos, c, mutation), rope(k, pos, c, mutation)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    n_blocks = -(-S // Q_BLOCK)
    q = jnp.pad(q, ((0, n_blocks * Q_BLOCK - S), (0, 0), (0, 0)))

    def block(i, out):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=0)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(hd)
        s = jnp.where(pos[None, None, :] <= rows[None, :, None], s, -1e30)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
        return jax.lax.dynamic_update_slice_in_dim(out, o, i * Q_BLOCK, 0)

    o = jax.lax.fori_loop(0, n_blocks, block, jnp.zeros_like(q))[:S]
    if mutation != "no_attn_gate":
        o = o * jax.nn.sigmoid(gate)
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


def expert_layer(h, w: Dict, c: Dict, mutation=None, shared: bool = True):
    """The routed experts held here, one after the other, plus the shared
    expert behind its sigmoid gate."""
    dense = route(h, w, c, mutation)
    held = w["e_gate"].shape[0]
    offset = int(c.get("ep_rank", 0)) * held

    def one_expert(e, out):
        y = swiglu(h, w["e_gate"][e], w["e_up"][e], w["e_down"][e])
        weight = jax.lax.dynamic_index_in_dim(dense, offset + e, axis=1)
        return out + weight * y

    out = jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(h))
    if not shared:
        return out
    y = swiglu(h, w["s_gate"], w["s_up"], w["s_down"])
    if mutation != "no_shared_gate":
        y = y * jax.nn.sigmoid(h @ f32(w["s_gatew"]))[:, None]
    return out + y


def layer(x, w: Dict, c: Dict, mutation=None):
    eps = float(c["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, w["in_norm"], eps, mutation)
        mixer = gated_delta_net if "w_qkvz" in w else gated_attention
        x = x + mixer(h, w, c, mutation)
        h = rms_norm(x, w["post_norm"], eps, mutation)
        return x + expert_layer(h, w, c, mutation)


def head(x_last, norm_scale, w_head, eps, mutation=None):
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x_last, norm_scale, eps, mutation)
        V = w_head.shape[1]
        return jnp.concatenate(
            [x @ f32(w_head[:, lo:lo + V_BLOCK])
             for lo in range(0, V, V_BLOCK)], axis=-1)


class Reference:
    """Drives the layer function over a model whose weights arrive one layer
    at a time.  ``config`` holds the published ``config.json`` keys and the
    share's own (``ep_size``, ``ep_rank``)."""

    def __init__(self, config: Dict, mutation: Optional[str] = None):
        assert mutation is None or mutation in MUTATIONS, mutation
        self.config = config
        self._layer = jax.jit(lambda x, w: layer(x, w, config, mutation))
        self._head = jax.jit(lambda x, s, w: head(
            x, s, w, float(config["rms_norm_eps"]), mutation))

    def logits(self, token_rows: List, weights: Dict,
               positions: List[List[int]]) -> List:
        """Each row of ``token_rows`` (a 1-D int array) through the model;
        for row r the logits [len(positions[r]), V] at its ``positions[r]``.

        ``weights``: ``embedding`` [V, D], ``norm`` [D], ``head`` [D, V] and
        ``layers``, a list of zero-argument callables each returning one
        layer's weights: ``in_norm`` / ``post_norm`` [D]; a Gated DeltaNet
        layer ``w_qkvz`` [D, 2*Hk*dk + 2*Hv*dv], ``w_ba`` [D, 2*Hv],
        ``conv`` [K, 2*Hk*dk + Hv*dv], ``A_log`` / ``dt_bias`` [Hv],
        ``gnorm`` [dv], ``w_o`` [Hv*dv, D]; an attention layer ``w_q``
        [D, H*2*hd], ``w_k`` / ``w_v`` [D, KV*hd], ``q_norm`` / ``k_norm``
        [hd], ``w_o`` [H*hd, D]; and the experts ``router`` [D, E_all],
        ``e_gate`` / ``e_up`` [E_held, D, F], ``e_down`` [E_held, F, D],
        ``s_gate`` / ``s_up`` [D, Fs], ``s_down`` [Fs, D], ``s_gatew`` [D].
        Any dtype: every use is in float32."""
        xs = [f32(jnp.take(weights["embedding"], row, axis=0))
              for row in token_rows]
        for make in weights["layers"]:
            w = make()
            for r, x in enumerate(xs):
                xs[r] = self._layer(x, w)
            del w
        return [self._head(jnp.take(x, jnp.asarray(pos, jnp.int32), axis=0),
                           weights["norm"], weights["head"])
                for x, pos in zip(xs, positions)]
