"""Plain reference of the Nemotron-H decoder with latent experts
(``model_type: nemotron_h``; NVIDIA-Nemotron-3-Super-120B-A12B): the
published forward pass in straightforward ``jax.numpy`` and float32 — no
kernels, no cache, no batching, no scan over layers, the Mamba-2 recurrence
token by token, dense causal attention, the experts one after the other, one
sequence at a time.  It shares no code with ``deepspeed_tpu``.

Follows nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 ``config.json`` and
the family's ``modeling_nemotron_h.py`` (``NemotronHMamba2Mixer.
torch_forward``, ``MambaRMSNormGated``, ``NemotronHAttention``,
``NemotronHMOE`` / ``NemotronHTopkRouter``).  Layer ``l`` is what character
``l`` of ``hybrid_override_pattern`` says; every layer is pre-norm, ``h <- h
+ F(RMSNorm(h))``, eps ``layer_norm_epsilon``, weights as they are (not ``1
+ w``), no bias but the convolution's:

``M``  Mamba-2 mixer (``mamba_num_heads`` H of ``mamba_head_dim`` P,
       ``n_groups`` G, ``ssm_state_size`` N, ``conv_kernel`` K): ``[z | xBC
       | dt] = u W_in`` (widths HP | HP + 2GN | H); ``xBC <- SiLU(
       causal_conv_K(xBC) + b_conv)`` split ``x [H, P] | B [G, N] | C [G,
       N]``; ``delta_h = softplus(dt_h + dt_bias_h)``, ``a_h = exp(-delta_h
       exp(A_log_h))``; with ``g = h // (H / G)``: ``S_h <- a_h S_h +
       delta_h x_h (x) B_g`` (``S_h`` [P, N] float32), ``y_h = S_h C_g +
       D_h x_h``; ``y <- RMSNorm over each of the G groups of HP / G values
       of (y * SiLU(z)) * w_norm`` (the gate first, then the norm); out ``=
       y W_out``.
``*``  attention: grouped queries, causal, scale ``head_dim^-1/2``, NO
       positional term (the family's modelling code applies no rotary
       embedding; ``rope_theta`` and ``partial_rotary_factor`` are carried
       by the config and unused; the Mamba layers carry order).
``E``  latent experts: ``s = sigmoid(u W_r)`` in float32 over all experts;
       the picks are the top ``num_experts_per_tok`` of ``s + b``
       (``e_score_correction_bias``; ``n_group`` 1: no group limit); ``g =
       routed_scaling_factor * s_pick / sum s_pick`` (``norm_topk_prob``);
       ``l = u W_down`` (``hidden_size`` -> ``moe_latent_size``); ``E_e(l)
       = relu(l W1_e)^2 W2_e`` (no gate: ``mlp_hidden_act`` ``relu2``);
       routed ``= (sum_picks g_e E_e(l)) W_up``; shared ``= relu(u Ws1)^2
       Ws2`` on the full width; out = routed + shared.
Embedding, final RMSNorm, untied head.

What the config does not fix, and what is assumed here (the configuration
file lists the same points under ``assumed``):
  1. ``W_in``'s columns are ``[z | x | B | C | dt]`` and the convolution
     runs over ``x | B | C`` in that order, each part head- (group-) major;
  2. head ``h`` reads group ``h // (H / G)``;
  3. the recurrent state is float32;
  4. no positional term in the attention layers (above).

Departures, each on purpose:
  * the multi-token-prediction module (``num_nextn_predict_layers``,
    ``mtp_hybrid_override_pattern``) is not held: it follows the last layer
    on the last stage of the deployment;
  * THE CHIP'S SHARE: the configuration's ``n_routed_experts`` experts are
    the ones held here, ``ep_size`` chips share a layer and this is chip
    ``ep_rank``.  The router scores all ``n_routed_experts * ep_size``
    experts, takes the top ``num_experts_per_tok`` of all, renormalises over
    them, and the experts held here add their part IN THE LATENT, which is
    up-projected; what the absent ones would add is left out (guide section
    4).  ``ep_size`` 1 is the uncut layer; the up-projection is linear, so
    the shares' parts plus the shared expert once are the uncut layer;
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
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

#: what ``mutation`` may be (None = the model as published)
MUTATIONS = ("no_D", "no_dt_bias", "no_conv_bias", "no_conv", "no_decay",
             "no_z_gate", "whole_norm", "norm_before_gate", "no_router_bias",
             "no_scaling", "no_renorm", "no_shared", "tied_latent_down",
             "tied_latent_up", "relu")

Q_BLOCK = 512          # query rows per attention block
V_BLOCK = 16384        # vocabulary columns per head block


def f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * f32(w)


def mamba2(h, w: Dict, c: Dict, mutation=None):
    """``h`` [S, D] → [S, D]: the input projection, the causal depthwise
    convolution with its bias and SiLU, the recurrence one token after the
    other, the gated group norm, the output projection."""
    S = h.shape[0]
    H, P = c["mamba_num_heads"], c["mamba_head_dim"]
    G, N, K = c["n_groups"], c["ssm_state_size"], c["conv_kernel"]
    Ci = H * P
    zxd = h @ f32(w["w_in"])
    z, xBC, dt = zxd[:, :Ci], zxd[:, Ci:Ci + Ci + 2 * G * N], zxd[:, -H:]
    if mutation != "no_conv":
        # out_t = sum_j conv[j] * x_{t-(K-1)+j}, zeros before the sequence
        padded = jnp.concatenate(
            [jnp.zeros((K - 1, xBC.shape[1]), jnp.float32), xBC])
        conv = f32(w["conv"])                               # [K, C]
        xBC = sum(conv[j][None, :] * padded[j:j + S] for j in range(K))
    if mutation != "no_conv_bias":
        xBC = xBC + f32(w["conv_b"])[None]
    xBC = jax.nn.silu(xBC)
    x = xBC[:, :Ci].reshape(S, H, P)
    B = jnp.repeat(xBC[:, Ci:Ci + G * N].reshape(S, G, N), H // G, axis=1)
    C = jnp.repeat(xBC[:, Ci + G * N:].reshape(S, G, N), H // G, axis=1)
    if mutation != "no_dt_bias":
        dt = dt + f32(w["dt_bias"])[None]
    delta = jax.nn.softplus(dt)                             # [S, H]
    a = jnp.exp(-delta * jnp.exp(f32(w["A_log"]))[None])
    if mutation == "no_decay":
        a = jnp.ones_like(a)

    def token(state, inp):
        x_t, B_t, C_t, d_t, a_t = inp           # [H, P] [H, N] [H, N] [H] [H]
        state = a_t[:, None, None] * state \
            + (d_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return state, jnp.sum(state * C_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                        (x, B, C, delta, a))                # [S, H, P]
    if mutation != "no_D":
        y = y + f32(w["D"])[None, :, None] * x
    y = y.reshape(S, Ci)
    eps = float(c["layer_norm_epsilon"])
    groups = 1 if mutation == "whole_norm" else G
    gate = jax.nn.silu(z) if mutation != "no_z_gate" else 1.0

    def group_norm(v):
        v = v.reshape(S, groups, Ci // groups)
        return (v * jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1,
                                           keepdims=True) + eps)
                ).reshape(S, Ci) * f32(w["gnorm"])[None]

    y = group_norm(y) * gate if mutation == "norm_before_gate" \
        else group_norm(y * gate)
    return y @ f32(w["w_out"])


def attention(h, w: Dict, c: Dict, mutation=None):
    """``h`` [S, D] → [S, D]: dense causal softmax attention over grouped
    queries, no positional term; query rows in blocks."""
    S = h.shape[0]
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    q = (h @ f32(w["w_q"])).reshape(S, H, hd)
    k = jnp.repeat((h @ f32(w["w_k"])).reshape(S, KV, hd), H // KV, axis=1)
    v = jnp.repeat((h @ f32(w["w_v"])).reshape(S, KV, hd), H // KV, axis=1)
    pos = jnp.arange(S)
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


def unit(x, w_1, w_2, mutation=None):
    """The ungated unit: ``relu(x W_1)^2 W_2``."""
    u = jax.nn.relu(x @ f32(w_1))
    return (u if mutation == "relu" else jnp.square(u)) @ f32(w_2)


def scores(h, w: Dict):
    return jax.nn.sigmoid(h @ f32(w["router"]))


def route(h, w: Dict, c: Dict, mutation=None):
    """Sigmoid scores over ALL experts in float32, the top
    ``num_experts_per_tok`` of ``s + b``, the weights ``s`` at those
    renormalised and scaled → dense weights [S, E_all] (zero off the
    top)."""
    s = scores(h, w)
    biased = s if mutation == "no_router_bias" else s + f32(w["router_b"])
    _, idx = jax.lax.top_k(biased, c["num_experts_per_tok"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    if c.get("norm_topk_prob", True) and mutation != "no_renorm":
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    if mutation != "no_scaling":
        top = top * float(c["routed_scaling_factor"])
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(top)


def expert_layer(h, w: Dict, c: Dict, mutation=None, shared: bool = True):
    """The routed experts held here, one after the other in the latent,
    their sum up-projected, plus (``shared``) the shared expert."""
    dense = route(h, w, c, mutation)
    held = w["e_up"].shape[0]
    offset = int(c.get("ep_rank", 0)) * held
    w_down, w_up = f32(w["l_down"]), f32(w["l_up"])
    if mutation == "tied_latent_down":
        w_down = w_up.T
    if mutation == "tied_latent_up":
        w_up = f32(w["l_down"]).T
    latent = h @ w_down

    def one_expert(e, out):
        pick = lambda x: jax.lax.dynamic_index_in_dim(    # noqa: E731
            x, e, keepdims=False)
        y = unit(latent, pick(w["e_up"]), pick(w["e_down"]), mutation)
        weight = jax.lax.dynamic_index_in_dim(dense, offset + e, axis=1)
        return out + weight * y

    out = jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(latent)) \
        @ w_up
    if shared and mutation != "no_shared":
        out = out + unit(h, w["s_up"], w["s_down"], mutation)
    return out


def layer(x, w: Dict, c: Dict, mutation=None):
    """One layer, of the kind its weights are: a mixer OR the experts."""
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, w["norm"], float(c["layer_norm_epsilon"]))
        if "w_in" in w:
            return x + mamba2(h, w, c, mutation)
        if "w_q" in w:
            return x + attention(h, w, c, mutation)
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
    at a time.  ``config`` holds the published ``config.json`` keys and the
    share's own (``ep_size``, ``ep_rank``)."""

    def __init__(self, config: Dict, mutation: Optional[str] = None):
        assert mutation is None or mutation in MUTATIONS, mutation
        self.config = config
        eps = float(config["layer_norm_epsilon"])
        self._layer = jax.jit(lambda x, w: layer(x, w, config, mutation))
        self._head = jax.jit(lambda x, s, w: head(x, s, w, eps))

        def router_scores(x, w):
            with jax.default_matmul_precision("highest"):
                return scores(rms_norm(x, w["norm"], eps), w)

        self._scores = jax.jit(router_scores)

    def logits(self, token_rows: List, weights: Dict,
               positions: List[List[int]]) -> List:
        """Each row of ``token_rows`` (a 1-D int array) through the model;
        for row r the logits [len(positions[r]), V] at its ``positions[r]``.

        ``weights``: ``embedding`` [V, D], ``norm`` [D], ``head`` [D, V] and
        ``layers``, a list of zero-argument callables each returning one
        layer's weights, ``norm`` [D] and: a Mamba-2 layer ``w_in`` [D, 2HP
        + 2GN + H], ``conv`` [K, HP + 2GN], ``conv_b`` [HP + 2GN],
        ``A_log`` / ``dt_bias`` / ``D`` [H], ``gnorm`` [HP], ``w_out`` [HP,
        D]; an attention layer ``w_q`` [D, H*hd], ``w_k`` / ``w_v`` [D,
        KV*hd], ``w_o`` [H*hd, D]; an expert layer ``router`` [D, E_all],
        ``router_b`` [E_all], ``l_down`` [D, R], ``l_up`` [R, D], ``e_up``
        [E_held, R, F], ``e_down`` [E_held, F, R], ``s_up`` [D, Fs],
        ``s_down`` [Fs, D].  Any dtype: every use is in float32."""
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

    def balanced_router_biases(self, row, weights: Dict,
                               balance: Callable) -> List:
        """For whoever MAKES seeded weights, not part of the comparison: one
        row of tokens through the model, and in front of every expert layer
        ``balance(scores [S, E_all], bias [E_all]) -> bias`` is asked for the
        ``router_b`` that layer then runs with (so the next layer is
        balanced on what this one passes on).  Returns the biases, one per
        expert layer."""
        x = f32(jnp.take(weights["embedding"], row, axis=0))
        biases = []
        for make in weights["layers"]:
            w = make()
            if "router" in w:
                w = dict(w, router_b=balance(self._scores(x, w),
                                             w["router_b"]))
                biases.append(w["router_b"])
            x = self._layer(x, w)
            del w
        return biases
