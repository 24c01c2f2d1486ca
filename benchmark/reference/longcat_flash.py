"""Plain reference of the LongCat-Flash decoder (``meituan-longcat/
LongCat-Flash-Chat``): the forward pass in straightforward ``jax.numpy`` and
float32 — no kernels, no cache, no batching, no scan over layers, the
EXPANDED attention with a dense causal softmax, the held experts one after
the other, the identity experts as ``g·h``, one sequence at a time.  It
shares no code with ``deepspeed_tpu``.

Follows the ``config.json`` keys (``mla_scale_q_lora``, ``mla_scale_kv_lora``,
``zero_expert_num`` / ``zero_expert_type: identity``, ``moe_topk``,
``routed_scaling_factor``) and the shortcut-connected layer of the LongCat-
Flash technical report.  One layer, ``x`` the residual::

    for i in (0, 1):
        x = x + MLA_i(rms(x; g_in_i))
        h = rms(x; g_post_i)
        if i == 0:  m = MoE(h)          # the shortcut leaves here ...
        x = x + FFN_i(h)
    x = x + m                           # ... and rejoins here

What the config does not fix, and what is assumed here (the configuration
file lists the same points under ``assumed``):
  1. ``s_q = sqrt(hidden / q_lora_rank)`` multiplies the query's latent AFTER
     its norm, ``s_kv = sqrt(hidden / kv_lora_rank)`` the compressed K/V
     latent after its norm (not the rotary key);
  2. rotary pairs are in the half-split ("rotate_half") layout, one rotary
     key for all heads, plain RoPE (no scaling key);
  3. embedding and head are untied;
  4. the router's bias ``b`` enters the selection only; the weights are the
     softmax scores themselves, times ``routed_scaling_factor``, NOT
     renormalised (the config has no ``norm_topk_prob``);
  5. the identity experts are the router's LAST ``zero_expert_num`` outputs.

Departures, each on purpose:
  * THE CHIP'S SHARE: the configuration's ``n_routed_experts`` real experts
    are the ones held here, ``ep_size`` chips share a layer and this is chip
    ``ep_rank``.  The router scores all ``n_routed_experts * ep_size`` real
    experts and every identity one and takes the top ``moe_topk`` of all;
    the experts held here add their part, the identity picks add ``g·h``
    (they have no weights and live on no chip: every chip computes them for
    its tokens), and what the absent experts would add is left out (guide
    section 4).  ``ep_size`` 1 is the uncut layer;
  * a layer's weights arrive in three pieces (block 0, the experts, block 1),
    each made when it is used and dropped after, every matrix is cast to
    float32 where it is used, attention is computed over blocks of query
    rows and the head over blocks of the vocabulary, so that the model fits
    beside the system under test at published widths (blocking changes no
    arithmetic);
  * the loops over the held experts and the query blocks are
    ``jax.lax.fori_loop``s that do what the Python loops did, in the same
    order (unrolled, the TPU's compiler takes minutes per length, PR 28);
  * ``mutation`` breaks one piece of the mathematics on purpose.  It is for
    the tests that show the comparison notices each piece.

Matrix multiplications run under ``jax.default_matmul_precision("highest")``:
on a TPU a float32 matmul is otherwise computed in bfloat16 passes.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

#: what ``mutation`` may be (None = the model as published)
MUTATIONS = ("identity_dropped", "moe_from_second_block", "moe_rejoins_early",
             "no_q_lora_scale", "no_kv_lora_scale", "renormalised",
             "no_selection_bias", "bias_in_weights", "no_scaling")

Q_BLOCK = 512          # query rows per attention block
V_BLOCK = 16384        # vocabulary columns per head block


def f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * f32(scale)


def rope(x, theta: float):
    """x [S, ..., rd] at positions 0..S-1, half-split rotation."""
    seq, rd = x.shape[0], x.shape[-1]
    freqs = jnp.asarray([theta ** (-2.0 * i / rd) for i in range(rd // 2)],
                        jnp.float32)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs[None, :]
    shape = (seq,) + (1,) * (x.ndim - 2) + (rd // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :rd // 2], x[..., rd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---- attention (MLA, expanded form) -----------------------------------------
def attention(h, w: Dict, c: Dict, mutation=None):
    seq, D = h.shape
    H = c["num_attention_heads"]
    dn, rd, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    R, Rq = c["kv_lora_rank"], c["q_lora_rank"]
    eps = float(c["rms_norm_eps"])
    theta = float(c["rope_theta"])
    s_q = math.sqrt(D / Rq) if c.get("mla_scale_q_lora") \
        and mutation != "no_q_lora_scale" else 1.0
    s_kv = math.sqrt(D / R) if c.get("mla_scale_kv_lora") \
        and mutation != "no_kv_lora_scale" else 1.0

    c_q = s_q * rms_norm(h @ f32(w["w_dq"]), w["q_norm"], eps)
    q = (c_q @ f32(w["w_uq"])).reshape(seq, H, dn + rd)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], theta)
    ckv = h @ f32(w["w_dkv"])
    c_kv = s_kv * rms_norm(ckv[:, :R], w["kv_norm"], eps)
    k_rope = rope(ckv[:, R:], theta)                    # shared by all heads
    kv = (c_kv @ f32(w["w_ukv"])).reshape(seq, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]

    scale = (dn + rd) ** -0.5
    # blocks of query rows, one after the other; the rows that pad the last
    # block are dropped
    blk = min(Q_BLOCK, seq)
    n_blocks = -(-seq // blk)
    pad = ((0, n_blocks * blk - seq), (0, 0), (0, 0))
    q_nope, q_rope = jnp.pad(q_nope, pad), jnp.pad(q_rope, pad)

    def block(i, out):
        lo = i * blk
        qn = jax.lax.dynamic_slice_in_dim(q_nope, lo, blk)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, lo, blk)
        scores = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
                  + jnp.einsum("qhd,kd->hqk", qr, k_rope)) * scale
        causal = jnp.arange(seq)[None, :] <= (lo + jnp.arange(blk))[:, None]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        rows = jnp.einsum("hqk,khd->qhd", probs, v).reshape(blk, H * dv)
        return jax.lax.dynamic_update_slice_in_dim(out, rows, lo, axis=0)

    out = jax.lax.fori_loop(0, n_blocks, block,
                            jnp.zeros((n_blocks * blk, H * dv), jnp.float32))
    return out[:seq] @ f32(w["w_o"])


# ---- MLPs -------------------------------------------------------------------
def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ f32(w_gate)) * (x @ f32(w_up))) @ f32(w_down)


def share_of(c: Dict):
    """(real experts the router scores, first one held here, held here)."""
    held = c["n_routed_experts"]
    return held * int(c.get("ep_size", 1)), held * int(c.get("ep_rank", 0)), \
        held


def route(h, w: Dict, c: Dict, mutation=None):
    """(ids [S, k] over ALL the router's outputs, weights [S, k], scores
    [S, real + zero])."""
    k = c["moe_topk"]
    s = jax.nn.softmax(h @ f32(w["router"]), axis=-1)
    b = f32(w["router_bias"])
    _, idx = jax.lax.top_k(s if mutation == "no_selection_bias" else s + b, k)
    g = jnp.take_along_axis(s + b if mutation == "bias_in_weights" else s,
                            idx, axis=-1)
    if mutation == "renormalised":
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    if mutation != "no_scaling":
        g = g * float(c["routed_scaling_factor"])
    return idx, g, s


def expert_layer(h, w: Dict, c: Dict, mutation=None):
    """→ (the held experts' part, the identity experts' part), both [S, D].
    No capacity, no dropped pair: every held expert sees every token, and a
    token's weight for an expert it did not pick is 0; a pick of an expert
    held on another chip adds nothing here."""
    idx, g, _ = route(h, w, c, mutation)
    real, offset, held = share_of(c)

    def one_expert(e, out):
        weight = jnp.sum(jnp.where(idx == offset + e, g, 0.0), axis=-1)
        pick = lambda x: jax.lax.dynamic_index_in_dim(    # noqa: E731
            x, e, keepdims=False)
        return out + weight[:, None] * swiglu(
            h, pick(w["e_gate"]), pick(w["e_up"]), pick(w["e_down"]))

    routed = jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(h))
    g_identity = jnp.sum(jnp.where(idx >= real, g, 0.0), axis=-1)
    identity = jnp.zeros_like(h) if mutation == "identity_dropped" \
        else g_identity[:, None] * h
    return routed, identity


def attend(x, w: Dict, c: Dict, mutation=None):
    """A block's first half: → (x after the attention, the normed input of
    what follows it)."""
    with jax.default_matmul_precision("highest"):
        eps = float(c["rms_norm_eps"])
        x = x + attention(rms_norm(x, w["in_norm"], eps), w, c, mutation)
        return x, rms_norm(x, w["post_norm"], eps)


def experts(h, w: Dict, c: Dict, mutation=None):
    with jax.default_matmul_precision("highest"):
        routed, identity = expert_layer(h, w, c, mutation)
        return routed + identity


def ffn(x, h, w: Dict):
    with jax.default_matmul_precision("highest"):
        return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"])


def router_scores(x, w_block: Dict, w_moe: Dict, c: Dict):
    """The softmax scores [S, real + zero] that a layer's router gives the
    tokens whose residual ENTERS the layer as ``x``.  Not part of the forward
    pass: for whoever makes the weights and wants a selection bias balanced
    on them (``Reference.balanced_router_biases``)."""
    _, h = attend(x, w_block, c)
    with jax.default_matmul_precision("highest"):
        return jax.nn.softmax(h @ f32(w_moe["router"]), axis=-1)


def head(x_last, norm_scale, w_head, eps):
    """The final norm and the untied head; the vocabulary in blocks."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x_last, norm_scale, eps)
        V = w_head.shape[1]
        return jnp.concatenate(
            [x @ f32(w_head[:, lo:lo + V_BLOCK])
             for lo in range(0, V, V_BLOCK)], axis=-1)


class Reference:
    """Drives the layer over a model whose weights arrive a piece at a time.
    ``config`` holds the published ``config.json`` keys and the share's own
    (``ep_size``, ``ep_rank``)."""

    def __init__(self, config: Dict, mutation: Optional[str] = None):
        assert mutation is None or mutation in MUTATIONS, mutation
        self.config, self.mutation = config, mutation
        self._attend = jax.jit(lambda x, w: attend(x, w, config, mutation))
        self._experts = jax.jit(lambda h, w: experts(h, w, config, mutation))
        self._ffn = jax.jit(ffn)
        self._head = jax.jit(lambda x, s, w: head(
            x, s, w, float(config["rms_norm_eps"])))
        self._scores = jax.jit(
            lambda x, wb, wm: router_scores(x, wb, wm, config))

    def _layer(self, xs: List, layer: Dict, bias=None) -> List:
        """One double layer over every row of ``xs``.  ``bias``: the router
        bias to run with instead of the layer's own."""
        branch = [None] * len(xs)
        early = self.mutation == "moe_rejoins_early"
        for i, make in enumerate(layer["blocks"]):
            w = make()
            hs = []
            for r, x in enumerate(xs):
                xs[r], h = self._attend(x, w)
                hs.append(h)
            if i == (1 if self.mutation == "moe_from_second_block" else 0):
                wm = layer["moe"]()
                if bias is not None:
                    wm = dict(wm, router_bias=bias)
                branch = [self._experts(h, wm) for h in hs]
                del wm
            for r, h in enumerate(hs):
                xs[r] = self._ffn(xs[r], h, w)
                if early and branch[r] is not None:
                    xs[r], branch[r] = xs[r] + branch[r], None
            del w, hs
        return [x if m is None else x + m for x, m in zip(xs, branch)]

    def logits(self, token_rows: List, weights: Dict,
               positions: List[List[int]]) -> List:
        """Each row of ``token_rows`` (a 1-D int array) through the model;
        for row r the logits [len(positions[r]), V] at its ``positions[r]``.

        ``weights``: ``embedding`` [V, D], ``norm`` [D], ``head`` [D, V] and
        ``layers``, a list with one entry a double layer: ``blocks``, two
        zero-argument callables each returning a block's weights
        (``in_norm`` [D], ``w_dq`` [D, q_rank], ``q_norm`` [q_rank], ``w_uq``
        [q_rank, H*(dn+rd)], ``w_dkv`` [D, R+rd], ``kv_norm`` [R], ``w_ukv``
        [R, H*(dn+dv)], ``w_o`` [H*dv, D], ``post_norm`` [D], ``w_gate`` /
        ``w_up`` [D, F], ``w_down`` [F, D]), and ``moe``, one returning
        ``router`` [D, real + zero], ``router_bias`` [real + zero],
        ``e_gate`` / ``e_up`` [E_held, D, Fe], ``e_down`` [E_held, Fe, D].
        Any dtype: every use is in float32."""
        xs = [f32(jnp.take(weights["embedding"], row, axis=0))
              for row in token_rows]
        for layer in weights["layers"]:
            xs = self._layer(xs, layer)
        return [self._head(jnp.take(x, jnp.asarray(pos, jnp.int32), axis=0),
                           weights["norm"], weights["head"])
                for x, pos in zip(xs, positions)]

    def balanced_router_biases(self, row, weights: Dict,
                               balance: Callable) -> List:
        """For whoever MAKES seeded weights, not part of the comparison: one
        row of tokens through the model, and in front of every expert branch
        ``balance(scores [S, real + zero], bias) -> bias`` is asked for the
        ``router_bias`` that layer then runs with (so the next layer is
        balanced on what this one passes on).  Returns the biases, one per
        layer."""
        xs = [f32(jnp.take(weights["embedding"], row, axis=0))]
        biases = []
        for layer in weights["layers"]:
            wm = layer["moe"]()
            bias = balance(self._scores(xs[0], layer["blocks"][0](), wm),
                           wm["router_bias"])
            del wm
            biases.append(bias)
            xs = self._layer(xs, layer, bias=bias)
        return biases
