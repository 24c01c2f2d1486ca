"""Plain reference of the Olmo-Hybrid decoder (``model_type: olmo_hybrid``):
the forward pass in straightforward ``jax.numpy`` and float32 — no kernels,
no cache, no batching, no scan over layers, the Gated DeltaNet recurrence
token by token, dense causal attention over every head, one sequence at a
time.  It shares no code with ``deepspeed_tpu``.

Follows allenai/Olmo-Hybrid-7B ``config.json``.  Layer ``l`` is what
``layer_types[l]`` says (``full_attention`` at ``(l + 1) % 4 == 0``), and
here a layer's kind is read off its weights.  The equations (ISSUE 34; the
configuration file lists under ``assumed`` every line the config does not
settle, with the alternative):

  block      ``h = x + norm(mixer(x))``, ``y = h + norm(mlp(h))``: the norms
             AFTER the mixer and the MLP, on the branch (OLMo-2 / OLMo-3);
             no pre-norm; one final norm before the head; ``norm(x) = x /
             rms(x) * w``; ``mlp(h) = W_down (silu(W_gate h) * (W_up h))``.
  attention  ``q = norm(W_q x)``, ``k = norm(W_k x)`` over the WHOLE
             projection (3840 values, not a head), ``v = W_v x``; 30 heads
             of 128, causal softmax at ``1 / sqrt(128)``; ``W_o``.  No
             rotary embedding: ``rope_parameters.rope_theta`` is null (a
             number there turns the half-split rotary on, over the whole
             head).
  linear     Gated DeltaNet with FLA's reading of the ``linear_*`` keys:
             ``q~, k~, v~ = silu(conv4(W_q x)), silu(conv4(W_k x)),
             silu(conv4(W_v x))`` (causal depthwise, zeros before the
             sequence), ``q = l2norm(q~) / sqrt(96)``, ``k = l2norm(k~)``,
             ``beta = 2 sigmoid(W_b x)`` (the 2 is
             ``linear_allow_neg_eigval``), ``g = -exp(A_log) softplus(W_a x
             + dt_bias)``; per head ``S_t = e^{g_t} S_{t-1} + k_t (x)
             beta_t (v_t - (e^{g_t} S_{t-1})^T k_t)``, ``o_t = S_t^T q_t``,
             ``S`` ``[96, 192]`` float32; ``out = W_o (norm_192(o) *
             silu(W_g x))``.  Key heads = value heads: no head repeat.

What is assumed about the arrangement of seeded matrices (a permutation of
columns, not mathematics): ``w_qkvg`` is ``[q | k | v | g]``, each part
head-major; ``w_ba`` is ``[b | a]``; ``conv`` is ``[K, q | k | v]`` with
``out_t = sum_j conv[j] x_{t-(K-1)+j}``.

Departures, each on purpose:
  * every weight is cast to float32 where it is used, the head is computed
    over blocks of the vocabulary and attention over blocks of query rows, so
    that the model fits beside the system under test;
  * the loops over tokens (the recurrence) and query blocks are
    ``jax.lax.scan`` / ``fori_loop``: unrolled, a layer's program grows with
    the sequence and the TPU's compiler takes minutes per length (PR 28);
  * ``mutation`` breaks one piece of the mathematics on purpose.  It is for
    the tests and the controls that show the comparison notices each piece.

Matrix multiplications run under ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

#: what ``mutation`` may be (None = the model as read above)
MUTATIONS = ("beta_not_doubled", "pre_norm", "qk_norm_per_head", "no_conv",
             "no_gate", "rotary", "no_decay", "no_qk_norm")
#: theta of the ``rotary`` mutation: the OLMo family's
ROTARY_THETA = 500000.0

Q_BLOCK = 512          # query rows per attention block
V_BLOCK = 16384        # vocabulary columns per head block


def f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    """``x / rms(x) * w``."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * f32(w)


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


def gated_delta_net(h, w: Dict, c: Dict, mutation=None):
    """``h`` [S, D] → [S, D]: projections, the causal depthwise convolutions
    and SiLU, the gated delta rule one token after the other, the gated
    norm, the output projection."""
    S = h.shape[0]
    H = c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    K = c["linear_conv_kernel_dim"]
    Kd, Vd = H * dk, H * dv
    qkvg = h @ f32(w["w_qkvg"])
    ba = h @ f32(w["w_ba"])
    mixed, gate = qkvg[:, :2 * Kd + Vd], qkvg[:, 2 * Kd + Vd:]
    b, a = ba[:, :H], ba[:, H:]
    if mutation != "no_conv":
        # out_t = sum_j conv[j] * x_{t-(K-1)+j}, zeros before the sequence
        padded = jnp.concatenate(
            [jnp.zeros((K - 1, mixed.shape[1]), jnp.float32), mixed])
        conv = f32(w["conv"])                               # [K, C]
        mixed = sum(conv[j][None, :] * padded[j:j + S] for j in range(K))
    mixed = jax.nn.silu(mixed)
    q = l2norm(mixed[:, :Kd].reshape(S, H, dk)) / math.sqrt(dk)
    k = l2norm(mixed[:, Kd:2 * Kd].reshape(S, H, dk))
    v = mixed[:, 2 * Kd:].reshape(S, H, dv)
    beta = jax.nn.sigmoid(b)
    if c.get("linear_allow_neg_eigval") and mutation != "beta_not_doubled":
        beta = 2.0 * beta
    g = -jnp.exp(f32(w["A_log"])) * jax.nn.softplus(a + f32(w["dt_bias"]))
    if mutation == "no_decay":
        g = jnp.zeros_like(g)

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x                 # [H, dk] .. [H]
        state = state * jnp.exp(g_t)[:, None, None]
        kv_mem = jnp.sum(state * k_t[:, :, None], axis=1)       # [H, dv]
        delta = (v_t - kv_mem) * b_t[:, None]
        state = state + k_t[:, :, None] * delta[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    _, o = jax.lax.scan(token, jnp.zeros((H, dk, dv), jnp.float32),
                        (q, k, v, g, beta))                    # [S, H, dv]
    # the gated norm: over one head's values
    o = rms_norm(o, w["gnorm"], float(c["rms_norm_eps"]))
    if mutation != "no_gate":
        o = o * jax.nn.silu(gate.reshape(S, H, dv))
    return o.reshape(S, Vd) @ f32(w["w_o"])


def rope(x, pos, theta):
    """Half-split rotary over the whole head; ``x`` [S, H, hd]."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]      # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def full_attention(h, w: Dict, c: Dict, mutation=None):
    """``h`` [S, D] → [S, D]: dense causal softmax attention over every
    head, QK-norm over the whole projection; query rows in blocks."""
    S = h.shape[0]
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // H
    eps = float(c["rms_norm_eps"])
    q, k = h @ f32(w["w_q"]), h @ f32(w["w_k"])
    v = (h @ f32(w["w_v"])).reshape(S, KV, hd)
    if mutation == "qk_norm_per_head":
        q = rms_norm(q.reshape(S, H, hd), f32(w["q_norm"]).reshape(H, hd),
                     eps)
        k = rms_norm(k.reshape(S, KV, hd), f32(w["k_norm"]).reshape(KV, hd),
                     eps)
    elif mutation != "no_qk_norm":
        q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
    q, k = q.reshape(S, H, hd), k.reshape(S, KV, hd)
    pos = jnp.arange(S)
    theta = (c.get("rope_parameters") or {}).get("rope_theta")
    if mutation == "rotary":
        theta = ROTARY_THETA
    if theta is not None:
        q, k = rope(q, pos, float(theta)), rope(k, pos, float(theta))
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
    return o.reshape(S, H * hd) @ f32(w["w_o"])


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ f32(w_gate)) * (x @ f32(w_up))) @ f32(w_down)


def layer(x, w: Dict, c: Dict, mutation=None):
    eps = float(c["rms_norm_eps"])
    mixer = gated_delta_net if "w_qkvg" in w else full_attention
    with jax.default_matmul_precision("highest"):
        if mutation == "pre_norm":      # the Llama block, same weights
            x = x + mixer(rms_norm(x, w["mixer_norm"], eps), w, c)
            return x + swiglu(rms_norm(x, w["mlp_norm"], eps),
                              w["w_gate"], w["w_up"], w["w_down"])
        x = x + rms_norm(mixer(x, w, c, mutation), w["mixer_norm"], eps)
        return x + rms_norm(swiglu(x, w["w_gate"], w["w_up"], w["w_down"]),
                            w["mlp_norm"], eps)


def head(x_last, norm_scale, w_head, eps):
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x_last, norm_scale, eps)
        V = w_head.shape[1]
        return jnp.concatenate(
            [x @ f32(w_head[:, lo:lo + V_BLOCK])
             for lo in range(0, V, V_BLOCK)], axis=-1)


class Reference:
    """Drives the layer function over a model whose weights arrive one layer
    at a time.  ``config`` holds the published ``config.json`` keys."""

    def __init__(self, config: Dict, mutation: Optional[str] = None):
        assert mutation is None or mutation in MUTATIONS, mutation
        self.config = config
        self._layer = jax.jit(lambda x, w: layer(x, w, config, mutation))
        self._head = jax.jit(lambda x, s, w: head(
            x, s, w, float(config["rms_norm_eps"])))

    def logits(self, token_rows: List, weights: Dict,
               positions: List[List[int]]) -> List:
        """Each row of ``token_rows`` (a 1-D int array) through the model;
        for row r the logits [len(positions[r]), V] at its ``positions[r]``.

        ``weights``: ``embedding`` [V, D], ``norm`` [D], ``head`` [D, V] and
        ``layers``, a list of zero-argument callables each returning one
        layer's weights: ``mixer_norm`` / ``mlp_norm`` [D] (the norms after
        the mixer and after the MLP), ``w_gate`` / ``w_up`` [D, F],
        ``w_down`` [F, D]; a Gated DeltaNet layer ``w_qkvg`` [D, 2*H*dk +
        2*H*dv], ``w_ba`` [D, 2*H], ``conv`` [K, 2*H*dk + H*dv], ``A_log`` /
        ``dt_bias`` [H], ``gnorm`` [dv], ``w_o`` [H*dv, D]; an attention
        layer ``w_q`` [D, H*hd], ``w_k`` / ``w_v`` [D, KV*hd], ``q_norm``
        [H*hd], ``k_norm`` [KV*hd], ``w_o`` [H*hd, D].  Any dtype: every use
        is in float32."""
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
