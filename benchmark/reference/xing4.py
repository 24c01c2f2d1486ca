"""Plain reference of the Xing4.0 decoder (``model_type: xing4_0``): the
published forward pass in straightforward ``jax.numpy`` and float32 — no
kernels, no cache, no batching, no scan over layers, the EXPANDED attention,
the experts one after the other, one sequence at a time.  It shares no code with
``deepspeed_tpu``.

Follows XingChen-AGI/Xing4.0-29B-A4B ``config.json`` and the papers its keys
point to: DeepSeek-V3's multi-head latent attention and ``noaux_tc`` sigmoid
routing with a shared expert, YaRN rotary scaling, and manifold-constrained
hyper-connections (Hyper-Connections, arXiv:2409.19606; mHC,
arXiv:2512.24880).

What the config does not fix, and what is assumed here (the configuration
file lists the same five points under ``assumed``):
  1. the embedding is copied into all ``hc_mult`` residual streams;
  2. the streams are summed before the final norm;
  3. rotary pairs are in the half-split ("rotate_half") layout;
  4. the Sinkhorn rounds divide by ``sum + hc_eps``, rows first then
     columns, ``hc_sinkhorn_iters`` rounds;
  5. the flat norm over the ``hc_mult x hidden`` values takes
     ``rms_norm_eps`` and has no gain.

Departures, each on purpose:
  * the multi-token-prediction module (``num_nextn_predict_layers``) is not
    held: it follows the last layer on the last stage of the deployment;
  * attention is computed over blocks of query rows and the head over blocks
    of the vocabulary, and every weight is cast to float32 where it is used,
    so that the model fits beside the system under test at published widths
    (the values are the same: float32 of the weights as given);
  * the loops over the experts, the query blocks and the Sinkhorn rounds
    are ``jax.lax.fori_loop``s that do what the Python loops did, in the
    same order: unrolled, a layer's program grew with the sequence (64
    experts x 3 float32 matmuls + a block per 512 rows), took the TPU's
    compiler 100-250 s per sequence length and pushed the other cells'
    programs out of the compile cache;
  * ``mutation`` breaks one piece of the mathematics on purpose.  It is for
    the tests that show the comparison notices each piece, and for nothing
    else.

Matrix multiplications run under ``jax.default_matmul_precision("highest")``:
on a TPU a float32 matmul is otherwise computed in bfloat16 passes.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

#: what ``mutation`` may be (None = the model as published)
MUTATIONS = ("no_selection_bias", "bias_in_weights", "no_renorm",
             "no_scaling", "no_shared", "identity_h_res", "no_column_step",
             "no_mscale", "no_yarn_blend")

Q_BLOCK = 512          # query rows per attention block
V_BLOCK = 16384        # vocabulary columns per head block


def f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps)
    return y if scale is None else y * f32(scale)


# ---- rotary embedding (YaRN) -----------------------------------------------
def yarn_frequencies(c: Dict, mutation=None):
    rd = c["qk_rope_head_dim"]
    rs = c["rope_scaling"]
    theta = float(c["rope_theta"])
    original = [theta ** (-2.0 * i / rd) for i in range(rd // 2)]
    factor = float(rs["factor"])
    if mutation == "no_yarn_blend":         # plain interpolation everywhere
        return jnp.asarray([f / factor for f in original], jnp.float32)
    n_orig = rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return rd * math.log(n_orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), rd - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(original):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return jnp.asarray(out, jnp.float32)


def yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope(x, freqs, multiplier: float):
    """x [S, ..., rd] at positions 0..S-1, half-split rotation."""
    seq, rd = x.shape[0], x.shape[-1]
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs[None, :]
    shape = (seq,) + (1,) * (x.ndim - 2) + (rd // 2,)
    cos = (jnp.cos(ang) * multiplier).reshape(shape)
    sin = (jnp.sin(ang) * multiplier).reshape(shape)
    x1, x2 = x[..., :rd // 2], x[..., rd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---- attention (MLA, expanded form) -----------------------------------------
def attention(h, w: Dict, c: Dict, mutation=None):
    seq = h.shape[0]
    H = c["num_attention_heads"]
    dn, rd, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    R = c["kv_lora_rank"]
    eps = float(c["rms_norm_eps"])
    rs = c["rope_scaling"]
    freqs = yarn_frequencies(c, mutation)
    mult = yarn_mscale(rs["factor"], rs["mscale"]) \
        / yarn_mscale(rs["factor"], rs["mscale_all_dim"])

    c_q = rms_norm(h @ f32(w["w_dq"]), w["q_norm"], eps)
    q = (c_q @ f32(w["w_uq"])).reshape(seq, H, dn + rd)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], freqs, mult)
    ckv = h @ f32(w["w_dkv"])
    c_kv = rms_norm(ckv[:, :R], w["kv_norm"], eps)
    k_rope = rope(ckv[:, R:], freqs, mult)              # shared by all heads
    kv = (c_kv @ f32(w["w_ukv"])).reshape(seq, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]

    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    scale = (dn + rd) ** -0.5 * (1.0 if mutation == "no_mscale" else m * m)
    # blocks of query rows, one after the other (a ``fori_loop`` for the same
    # reason as the experts'); the rows that pad the last block are dropped
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


def route(h, w: Dict, c: Dict, mutation=None):
    """(expert ids [S, k], weights [S, k], scores [S, E])."""
    k = c["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ f32(w["router"]))
    b = f32(w["router_bias"])
    pick = s if mutation == "no_selection_bias" else s + b
    _, idx = jax.lax.top_k(pick, k)
    g = jnp.take_along_axis(s + b if mutation == "bias_in_weights" else s,
                            idx, axis=-1)
    if c["norm_topk_prob"] and mutation != "no_renorm":
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    if mutation != "no_scaling":
        g = g * float(c["routed_scaling_factor"])
    return idx, g, s


def router_scores(X, w: Dict, c: Dict):
    """The sigmoid scores [S, E] that an expert layer's router gives the
    tokens whose streams ENTER the layer as ``X``: the attention sublayer,
    then the mix and the norm in front of the experts.  Not part of the
    forward pass: for whoever makes the weights and wants a selection bias
    balanced on them (``Reference.balanced_router_biases``)."""
    with jax.default_matmul_precision("highest"):
        X = hyper_sublayer(X, w["hc_attn"], w["attn_norm"],
                           lambda h: attention(h, w, c), c)
        h_pre, _, _ = hyper_maps(X, w["hc_mlp"], c)
        h = rms_norm(jnp.einsum("sj,sjd->sd", h_pre, X), w["mlp_norm"],
                     float(c["rms_norm_eps"]))
        return jax.nn.sigmoid(h @ f32(w["router"]))


def expert_layer(h, w: Dict, c: Dict, mutation=None):
    """No capacity, no dropped pair: every expert sees every token, and a
    token's weight for an expert it did not pick is 0.  The loop over the
    experts is a ``fori_loop``, one expert after the other as a Python loop
    would go: unrolled, the 64 experts' float32 matmuls made a program that
    took the TPU's compiler 100-250 s a sequence length and filled the
    compile cache (PERF.md section 6, PR 28)."""
    idx, g, _ = route(h, w, c, mutation)

    def one_expert(e, out):
        weight = jnp.sum(jnp.where(idx == e, g, 0.0), axis=-1)        # [S]
        pick = lambda x: jax.lax.dynamic_index_in_dim(    # noqa: E731
            x, e, keepdims=False)
        return out + weight[:, None] * swiglu(
            h, pick(w["e_gate"]), pick(w["e_up"]), pick(w["e_down"]))

    out = jax.lax.fori_loop(0, c["n_routed_experts"], one_expert,
                            jnp.zeros_like(h))
    if mutation != "no_shared":
        out = out + swiglu(h, w["s_gate"], w["s_up"], w["s_down"])
    return out


# ---- hyper-connections -------------------------------------------------------
def hyper_maps(X, p: Dict, c: Dict, mutation=None):
    """X [S, n, D] → H_pre [S, n], H_post [S, n], H_res [S, n, n]."""
    seq, n, D = X.shape
    x = rms_norm(X.reshape(seq, n * D), None, float(c["rms_norm_eps"]))
    maps = x @ f32(p["phi"])
    a_pre, a_post, a_res = (f32(p["alpha"])[i] for i in range(3))
    h_pre = jax.nn.sigmoid(a_pre * maps[:, :n] + f32(p["b_pre"]))
    h_post = 2.0 * jax.nn.sigmoid(a_post * maps[:, n:2 * n] + f32(p["b_post"]))
    r = a_res * maps[:, 2 * n:].reshape(seq, n, n) + f32(p["b_res"])
    m = jnp.exp(jnp.clip(r, c["mhc_h_res_clamp_min"],
                         c["mhc_h_res_clamp_max"]))
    eps = float(c["hc_eps"])
    def sinkhorn_round(_, m):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)      # each row
        if mutation != "no_column_step":
            m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)  # each column
        return m

    m = jax.lax.fori_loop(0, c["hc_sinkhorn_iters"], sinkhorn_round, m)
    if mutation == "identity_h_res":
        m = jnp.broadcast_to(jnp.eye(n, dtype=jnp.float32), m.shape)
    return h_pre, h_post, m


def hyper_sublayer(X, p: Dict, norm_scale, fn: Callable, c: Dict,
                   mutation=None):
    h_pre, h_post, h_res = hyper_maps(X, p, c, mutation)
    u = jnp.einsum("sj,sjd->sd", h_pre, X)
    z = fn(rms_norm(u, norm_scale, float(c["rms_norm_eps"])))
    return jnp.einsum("sij,sjd->sid", h_res, X) + h_post[:, :, None] \
        * z[:, None, :]


def layer(X, w: Dict, c: Dict, mutation=None):
    with jax.default_matmul_precision("highest"):
        X = hyper_sublayer(X, w["hc_attn"], w["attn_norm"],
                           lambda h: attention(h, w, c, mutation), c,
                           mutation)
        if "router" in w:
            mlp = lambda h: expert_layer(h, w, c, mutation)    # noqa: E731
        else:
            mlp = lambda h: swiglu(h, w["w_gate"], w["w_up"],  # noqa: E731
                                   w["w_down"])
        return hyper_sublayer(X, w["hc_mlp"], w["mlp_norm"], mlp, c,
                              mutation)


def head(X_last, norm_scale, w_head, eps):
    """The streams summed, the final norm, the untied head; the vocabulary
    in blocks."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(jnp.sum(X_last, axis=1), norm_scale, eps)
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
        self._layer = jax.jit(lambda X, w: layer(X, w, config, mutation))
        self._head = jax.jit(lambda X, s, w: head(
            X, s, w, float(config["rms_norm_eps"])))
        self._scores = jax.jit(lambda X, w: router_scores(X, w, config))

    def _embed(self, row, weights: Dict):
        x = f32(jnp.take(weights["embedding"], row, axis=0))
        n = self.config["hc_mult"]
        return jnp.broadcast_to(x[:, None, :], (x.shape[0], n, x.shape[1]))

    def logits(self, token_rows: List, weights: Dict,
               positions: List[List[int]]) -> List:
        """Each row of ``token_rows`` (a 1-D int array) through the model;
        for row r the logits [len(positions[r]), V] at its ``positions[r]``.

        ``weights``: ``embedding`` [V, D], ``norm`` [D], ``head`` [D, V] and
        ``layers``, a list of zero-argument callables each returning one
        layer's weights (made once per layer, used for every row, dropped):
        ``hc_attn`` / ``hc_mlp`` (``phi`` [n*D, 2n+n*n], ``alpha`` [3],
        ``b_pre`` [n], ``b_post`` [n], ``b_res`` [n, n]), ``attn_norm`` [D],
        ``w_dq`` [D, q_rank], ``q_norm``, ``w_uq`` [q_rank, H*(dn+rd)],
        ``w_dkv`` [D, R+rd], ``kv_norm`` [R], ``w_ukv`` [R, H*(dn+dv)],
        ``w_o`` [H*dv, D], ``mlp_norm`` [D], and either ``w_gate``/``w_up``
        [D, F] + ``w_down`` [F, D] (dense) or ``router`` [D, E],
        ``router_bias`` [E], ``e_gate``/``e_up`` [E, D, Fe], ``e_down``
        [E, Fe, D], ``s_gate``/``s_up`` [D, Fs], ``s_down`` [Fs, D].
        Any dtype: every use is in float32."""
        xs = [self._embed(row, weights) for row in token_rows]
        for make in weights["layers"]:
            w = make()
            for r, X in enumerate(xs):
                xs[r] = self._layer(X, w)
            del w
        return [self._head(jnp.take(X, jnp.asarray(pos, jnp.int32), axis=0),
                           weights["norm"], weights["head"])
                for X, pos in zip(xs, positions)]

    def balanced_router_biases(self, row, weights: Dict,
                               balance: Callable) -> List:
        """For whoever MAKES seeded weights, not part of the comparison: one
        row of tokens through the model, and in front of every expert layer
        ``balance(scores [S, E], bias [E]) -> bias [E]`` is asked for the
        ``router_bias`` that layer then runs with (so the next layer is
        balanced on what this one passes on).  Returns the biases, one per
        expert layer."""
        X = self._embed(row, weights)
        biases = []
        for make in weights["layers"]:
            w = make()
            if "router" in w:
                w = dict(w, router_bias=balance(self._scores(X, w),
                                                w["router_bias"]))
                biases.append(w["router_bias"])
            X = self._layer(X, w)
            del w
        return biases
