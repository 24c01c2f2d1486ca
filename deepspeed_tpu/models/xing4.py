"""Xing4.0 family (``model_type: xing4_0``): multi-head latent attention
(MLA, DeepSeek-V3 style), sigmoid-routed experts beside a shared expert after
a few leading dense layers, and manifold-constrained hyper-connections
(``hc_mult`` residual streams mixed by a Sinkhorn-normalised map) in place
of the plain residual add.

This module holds the configuration (built from the published
``config.json`` keys), the seeded parameter tree, and the per-token layer
mathematics on the flat token axis ``[T, ...]``.  :func:`serving_family`
composes them into what the paged serving path asks of a model
(``models/serving.py``): a latent row, two layer stacks, the pair counts.
This family is served only (``loss_fn`` raises): latent attention's training
form (the expanded k/v through flash attention at q/k 192, v 128), with a
loss, partition specs and the step-balanced selection bias, is
``models/joyai_flash.py``, which shares :func:`mla_query`,
:func:`mla_latent` and :func:`mla_up_weights` with this file; what a
training path here still lacks is the hyper-connected residual's backward
at training shapes.

Layers are NOT all alike, so the parameters are two stacks, each scanned on
its own: ``dense_layers`` (the ``first_k_dense_replace`` leading ones) and
``moe_layers``.  The residual carry is ``[T, hc_mult, D]`` in float32.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .serving import ExpertPairs, LatentRow, LayerStack, ServingFamily
from .transformer import rms_norm

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Xing4Config:
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216        # dense-layer MLP width
    moe_intermediate_size: int = 1024    # one expert's width
    num_layers: int = 40
    first_k_dense: int = 2
    num_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    norm_topk_prob: bool = True
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    max_seq_len: int = 262144
    tie_embeddings: bool = False

    @property
    def num_dense_layers(self) -> int:
        return min(self.first_k_dense, self.num_layers)

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.num_dense_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Values cached per token per layer: ``c_kv`` and the shared
        ``k_rope``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """The cached row as stored: ``latent_dim`` padded with zeros to
        whole 128-lane tiles (the chip pads a 576-wide minor dimension to
        640 anyway; explicit zeros keep every in-kernel contraction
        aligned)."""
        return -(-self.latent_dim // 128) * 128

    @property
    def softmax_scale(self) -> float:
        m = 1.0
        if self.rope_factor > 1.0:
            m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0
        return self.qk_head_dim ** -0.5 * m * m

    @staticmethod
    def from_hf(hf: Dict, **overrides) -> "Xing4Config":
        """From the published ``config.json`` keys."""
        if hf.get("scoring_func", "sigmoid") != "sigmoid" \
                or hf.get("topk_method", "noaux_tc") != "noaux_tc" \
                or hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
            raise NotImplementedError(
                "xing4: only sigmoid / noaux_tc routing without group limits")
        rs = hf.get("rope_scaling") or {}
        if rs and rs.get("type", rs.get("rope_type")) != "yarn":
            raise NotImplementedError("xing4: rope_scaling must be yarn")
        kw = dict(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            first_k_dense=hf["first_k_dense_replace"],
            num_heads=hf["num_attention_heads"],
            q_lora_rank=hf["q_lora_rank"], kv_lora_rank=hf["kv_lora_rank"],
            qk_nope_head_dim=hf["qk_nope_head_dim"],
            qk_rope_head_dim=hf["qk_rope_head_dim"],
            v_head_dim=hf["v_head_dim"],
            n_routed_experts=hf["n_routed_experts"],
            n_shared_experts=hf["n_shared_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            routed_scaling_factor=float(hf["routed_scaling_factor"]),
            norm_topk_prob=bool(hf["norm_topk_prob"]),
            hc_mult=hf["hc_mult"], hc_sinkhorn_iters=hf["hc_sinkhorn_iters"],
            hc_eps=float(hf["hc_eps"]),
            hc_clamp=(float(hf["mhc_h_res_clamp_min"]),
                      float(hf["mhc_h_res_clamp_max"])),
            norm_eps=float(hf["rms_norm_eps"]),
            rope_theta=float(hf["rope_theta"]),
            rope_factor=float(rs.get("factor", 1.0)),
            rope_original_max=int(rs.get(
                "original_max_position_embeddings",
                hf["max_position_embeddings"])),
            rope_beta_fast=float(rs.get("beta_fast", 32)),
            rope_beta_slow=float(rs.get("beta_slow", 1)),
            rope_mscale=float(rs.get("mscale", 1)),
            rope_mscale_all_dim=float(rs.get("mscale_all_dim", 0)),
            max_seq_len=hf["max_position_embeddings"],
            tie_embeddings=bool(hf["tie_word_embeddings"]))
        kw.update(overrides)
        return Xing4Config(**kw)

    @staticmethod
    def tiny(**kw) -> "Xing4Config":
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    moe_intermediate_size=32, num_layers=3, first_k_dense=1,
                    num_heads=4, q_lora_rank=48, kv_lora_rank=32,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                    n_routed_experts=8, num_experts_per_tok=2, hc_mult=4,
                    rope_original_max=32, rope_factor=8.0, max_seq_len=256)
        base.update(kw)
        return Xing4Config(**base)


# --------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------- #
def _hc_init(key, L: int, cfg: Xing4Config) -> Dict:
    """One sublayer's hyper-connection parameters, stacked over ``L``
    layers.  Kept in float32 by the serving engine (keys ``hc_*``).  The
    scalars and biases are drawn large enough that every map matters: an
    identity ``H_res`` or a constant ``H_pre`` would hide a wrong mix."""
    n, D = cfg.hc_mult, cfg.hidden_size
    k = jax.random.split(key, 6)
    f32 = jnp.float32
    return {
        "phi": jax.random.normal(k[0], (L, n * D, 2 * n + n * n), f32)
        / math.sqrt(n * D),
        # [alpha_pre, alpha_post, alpha_res]
        "alpha": 1.0 + 0.5 * jax.random.uniform(k[1], (L, 3), f32),
        "b_pre": 0.5 * jax.random.normal(k[2], (L, n), f32),
        "b_post": 0.5 * jax.random.normal(k[3], (L, n), f32),
        "b_res": jax.random.normal(k[4], (L, n, n), f32)
        + 2.0 * jnp.eye(n, dtype=f32),
    }


def _stack_init(cfg: Xing4Config, key, L: int, moe: bool, dtype) -> Dict:
    D, H = cfg.hidden_size, cfg.num_heads
    ks = iter(jax.random.split(key, 16))

    def dense(shape, fan_in):
        return (jax.random.normal(next(ks), shape) / math.sqrt(fan_in)
                ).astype(dtype)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    p = {
        "hc_attn": _hc_init(next(ks), L, cfg),
        "hc_mlp": _hc_init(next(ks), L, cfg),
        "attn_norm": {"scale": ones(L, D)},
        "q_a_proj": {"kernel": dense((L, D, cfg.q_lora_rank), D)},
        "q_a_norm": {"scale": ones(L, cfg.q_lora_rank)},
        "q_b_proj": {"kernel": dense((L, cfg.q_lora_rank,
                                      H * cfg.qk_head_dim), cfg.q_lora_rank)},
        "kv_a_proj": {"kernel": dense((L, D, cfg.latent_dim), D)},
        "kv_a_norm": {"scale": ones(L, cfg.kv_lora_rank)},
        # [c_kv] -> per head [k_nope ; v]
        "kv_b_proj": {"kernel": dense(
            (L, cfg.kv_lora_rank,
             H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), cfg.kv_lora_rank)},
        "o_proj": {"kernel": dense((L, H * cfg.v_head_dim, D),
                                   H * cfg.v_head_dim)},
        "mlp_norm": {"scale": ones(L, D)},
    }
    if not moe:
        F = cfg.intermediate_size
        p["gate_proj"] = {"kernel": dense((L, D, F), D)}
        p["up_proj"] = {"kernel": dense((L, D, F), D)}
        p["down_proj"] = {"kernel": dense((L, F, D), F)}
        return p
    E, F = cfg.n_routed_experts, cfg.moe_intermediate_size
    Fs = F * cfg.n_shared_experts
    p["router"] = {
        "kernel": (jax.random.normal(next(ks), (L, D, E)) / math.sqrt(D)
                   ).astype(jnp.float32),
        # e_score_correction_bias: enters the selection, not the weights.
        # Drawn at the scale of the sigmoid scores' own spread, so that it
        # does change which experts are picked (the tests' mutation cases
        # rest on that).  Such a draw also SKEWS the loads (the fullest
        # expert takes 8% of the pairs, and a third of the experts see no
        # token in a 64-wide decode step); a trained bias exists to even
        # them.  Whoever measures speed with seeded weights replaces it
        # with one balanced on data, as the benchmark does
        # (benchmark/lib/xing4_system.balanced_router; PERF.md section 6,
        # PR 28).
        "bias": 0.3 * jax.random.normal(next(ks), (L, E), jnp.float32)}
    p["experts"] = {
        "gate": dense((L, E, D, F), D), "up": dense((L, E, D, F), D),
        "down": dense((L, E, F, D), F)}
    p["shared"] = {
        "gate": dense((L, D, Fs), D), "up": dense((L, D, Fs), D),
        "down": dense((L, Fs, D), Fs)}
    return p


def init_params(cfg: Xing4Config, key: jax.Array, dtype=jnp.float32) -> Dict:
    k = jax.random.split(key, 4)
    D, V = cfg.hidden_size, cfg.vocab_size
    params = {
        "embed": {"embedding": (jax.random.normal(k[0], (V, D)) * 0.02
                                ).astype(dtype)},
        "norm_f": {"scale": jnp.ones((D,), dtype)},
    }
    if cfg.num_dense_layers:
        params["dense_layers"] = _stack_init(cfg, k[1], cfg.num_dense_layers,
                                             False, dtype)
    if cfg.num_moe_layers:
        params["moe_layers"] = _stack_init(cfg, k[2], cfg.num_moe_layers,
                                           True, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": (jax.random.normal(k[3], (D, V))
                                        / math.sqrt(D)).astype(dtype)}
    return params


class Xing4LM:
    """Model object the serving engine takes (``config`` +
    ``init_params``).  Loading a checkpoint's tensors is out of scope; the
    family is served only (see the module docstring)."""

    def __init__(self, cfg: Xing4Config):
        self.config = cfg

    @classmethod
    def from_hf_config(cls, hf: Dict, **overrides) -> "Xing4LM":
        return cls(Xing4Config.from_hf(hf, **overrides))

    def init_params(self, key: jax.Array, dtype=jnp.float32):
        return init_params(self.config, key, dtype)

    def loss_fn(self, params, batch, rng):
        raise NotImplementedError(
            "xing4: this family is served through inference/v2 only; latent "
            "attention with sigmoid noaux_tc experts TRAINS in "
            "models/joyai_flash.py (plain residual), the hyper-connected "
            "residual has no training path (ROADMAP R3)")

    def serving_family(self) -> ServingFamily:
        return serving_family(self.config)

    def num_params(self, params=None) -> int:
        if params is None:
            params = jax.eval_shape(lambda k: self.init_params(k),
                                    jax.random.PRNGKey(0))
        n = 0
        for leaf in jax.tree.leaves(params):
            n += math.prod(leaf.shape)
        return int(n)


# --------------------------------------------------------------------- #
# Rotary embedding (YaRN)
# --------------------------------------------------------------------- #
def yarn_inv_freq(cfg: Xing4Config) -> jnp.ndarray:
    """[rd/2] rotary frequencies: YaRN's blend of the original ones (fast
    dimensions, below ``beta_fast`` rotations' correction dimension) and
    the ones interpolated by ``factor`` (slow dimensions), linear over the
    ramp between the two betas' correction dimensions."""
    import numpy as np

    rd = cfg.qk_rope_head_dim
    extra = 1.0 / (cfg.rope_theta ** (np.arange(0, rd, 2, dtype=np.float64)
                                      / rd))
    if cfg.rope_factor <= 1.0:
        return jnp.asarray(extra, jnp.float32)
    inter = extra / cfg.rope_factor

    def correction_dim(rotations):
        return rd * math.log(cfg.rope_original_max / (rotations * 2 * math.pi)
                             ) / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), rd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rd // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return jnp.asarray(inter * ramp + extra * (1.0 - ramp), jnp.float32)


def rope_at(pos, cfg: Xing4Config):
    """cos/sin [T, rd/2] at absolute positions.  The cos/sin multiplier is
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``, 1 for the
    published values (both 1)."""
    freqs = pos.astype(jnp.float32)[:, None] * yarn_inv_freq(cfg)[None, :]

    def mscale(s):
        return 0.1 * s * math.log(cfg.rope_factor) + 1.0 \
            if cfg.rope_factor > 1.0 else 1.0

    m = mscale(cfg.rope_mscale) / mscale(cfg.rope_mscale_all_dim)
    return jnp.cos(freqs) * m, jnp.sin(freqs) * m


def apply_rope(x, cos, sin):
    """Half-split (rotate-half) pairs: ``x`` [T, ..., rd], tables [T, rd/2],
    computed in float32."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[-1],)
    c, s = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


# --------------------------------------------------------------------- #
# Hyper-connections
# --------------------------------------------------------------------- #
def hc_maps(X, hp: Dict, cfg: Xing4Config):
    """``X`` [T, n, D] float32 → (H_pre [T, n], H_post [T, n], H_res [T, n,
    n]), all float32: the flat RMS norm (no gain), the map ``phi``, and the
    Sinkhorn normalisation of ``H_res``."""
    T, n, D = X.shape
    x = X.reshape(T, n * D)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + cfg.norm_eps)
    maps = jnp.dot(x, hp["phi"].astype(jnp.float32), precision=_HI)
    a = hp["alpha"].astype(jnp.float32)
    h_pre = jax.nn.sigmoid(a[0] * maps[:, :n] + hp["b_pre"])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * maps[:, n:2 * n] + hp["b_post"])
    r = a[2] * maps[:, 2 * n:].reshape(T, n, n) + hp["b_res"]
    m = jnp.exp(jnp.clip(r, cfg.hc_clamp[0], cfg.hc_clamp[1]))
    for _ in range(cfg.hc_sinkhorn_iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + cfg.hc_eps)   # rows
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + cfg.hc_eps)   # columns
    return h_pre, h_post, m


def hc_sublayer(X, hp: Dict, norm_scale, fn, cfg: Xing4Config, dtype):
    """One hyper-connected sublayer: ``X' = H_res·X + H_post ⊗ F(norm(Σ
    H_pre·X))``.  ``fn`` maps the normed ``[T, D]`` input (in the compute
    ``dtype``) to ``[T, D]`` and may return auxiliary outputs as a second
    value.

    ``X`` is carried in float32, and the mixes are ``hc_mult`` multiply-
    adds a value written as such (as a matmul they would go through the MXU
    with ``H`` rounded to bfloat16).  A plain residual stream only ever has
    sublayer outputs ADDED to it; here the whole carry is multiplied by
    ``H_res`` in every sublayer, so what is rounded compounds.  Neither
    costs device time that a profile shows (``hc/*`` is 1.6% of a decode
    step); on the chip neither moved the agreement with the reference
    either, whose floor is set by routing flips (PERF.md section 6, PR 28)."""
    n = cfg.hc_mult
    with jax.named_scope("hc/maps"):
        h_pre, h_post, h_res = hc_maps(X, hp, cfg)
    with jax.named_scope("hc/mix"):
        streams = [X[:, j, :] for j in range(n)]
        u = sum(h_pre[:, j, None] * streams[j] for j in range(n))
        h = rms_norm(u, norm_scale.astype(jnp.float32), cfg.norm_eps
                     ).astype(dtype)
    z, aux = fn(h)
    with jax.named_scope("hc/mix"):
        z = z.astype(jnp.float32)
        out = jnp.stack(
            [sum(h_res[:, i, j, None] * streams[j] for j in range(n))
             + h_post[:, i, None] * z for i in range(n)], axis=1)
    return out, aux


# --------------------------------------------------------------------- #
# MLA projections
# --------------------------------------------------------------------- #
def mla_query(h, lp: Dict, cos, sin, cfg, rope=apply_rope):
    """Normed input [T, D] → (q_nope [T, H, dn], q_rope [T, H, rd]), RoPE
    applied (``rope``: this family's half-split pairs, or another family's:
    ``models/joyai_flash.py`` rotates interleaved pairs).  ``cfg`` is any
    configuration with the MLA widths."""
    T, H = h.shape[0], cfg.num_heads
    c_q = rms_norm(h @ lp["q_a_proj"]["kernel"], lp["q_a_norm"]["scale"],
                   cfg.norm_eps)
    q = (c_q @ lp["q_b_proj"]["kernel"]).reshape(T, H, cfg.qk_head_dim)
    q_nope, q_rope = q[..., :cfg.qk_nope_head_dim], \
        q[..., cfg.qk_nope_head_dim:]
    return q_nope, rope(q_rope, cos, sin)


def mla_latent(h, lp: Dict, cos, sin, cfg, rope=apply_rope):
    """Normed input [T, D] → the cached row [T, latent_row]: ``c_kv`` after
    its norm, ``k_rope`` after RoPE, zero padding."""
    T = h.shape[0]
    ckv = h @ lp["kv_a_proj"]["kernel"]
    c_kv = rms_norm(ckv[:, :cfg.kv_lora_rank], lp["kv_a_norm"]["scale"],
                    cfg.norm_eps)
    k_rope = rope(ckv[:, cfg.kv_lora_rank:], cos, sin)
    pad = jnp.zeros((T, cfg.latent_row - cfg.latent_dim), c_kv.dtype)
    return jnp.concatenate([c_kv, k_rope, pad], axis=-1)


def mla_up_weights(lp: Dict, cfg):
    """(W_UK [R, H, dn], W_UV [R, H, dv]) views of ``kv_b_proj``."""
    w = lp["kv_b_proj"]["kernel"].reshape(
        cfg.kv_lora_rank, cfg.num_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def mla_absorb_query(q_nope, q_rope, lp: Dict, cfg: Xing4Config):
    """The absorbed query [T, H, latent_row]: ``q_nope·W_UKᵀ`` against
    ``c_kv``, ``q_rope`` against ``k_rope``, zeros against the padding."""
    w_uk, _ = mla_up_weights(lp, cfg)
    q_lat = jnp.einsum("thn,rhn->thr", q_nope, w_uk,
                       preferred_element_type=jnp.float32).astype(q_nope.dtype)
    pad = jnp.zeros(q_lat.shape[:2] + (cfg.latent_row - cfg.latent_dim,),
                    q_lat.dtype)
    return jnp.concatenate([q_lat, q_rope, pad], axis=-1)


def mla_output(o_lat, lp: Dict, cfg: Xing4Config):
    """Attention output in latent space [T, H, R] → [T, D] through ``W_UV``
    and ``W_O``."""
    _, w_uv = mla_up_weights(lp, cfg)
    o = jnp.einsum("thr,rhv->thv", o_lat, w_uv,
                   preferred_element_type=jnp.float32).astype(o_lat.dtype)
    return o.reshape(o.shape[0], -1) @ lp["o_proj"]["kernel"]


# --------------------------------------------------------------------- #
# MLPs
# --------------------------------------------------------------------- #
def dense_mlp(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


# --------------------------------------------------------------------- #
# Paged serving (models/serving.py says what each piece is handed)
# --------------------------------------------------------------------- #
def serving_family(cfg: Xing4Config) -> ServingFamily:
    """Latent (MLA) rows, attended in the absorbed form for prefill chunks
    and decode alike.  Two stacks, because the layers are not all alike: the
    leading dense ones, then the expert ones.  The carry is ``[T, hc_mult,
    D]`` float32; a step also returns the pairs per expert ``[E]``."""
    from ..moe.dropless import sigmoid_moe_block

    def embed(params, ids, pos, valid):
        valid = valid()
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"]["embedding"], ids, axis=0
                         ).astype(jnp.float32)
            # the embedding is copied into every residual stream; the carry
            # is float32 (hc_sublayer)
            x = jnp.broadcast_to(x[:, None, :],
                                 (x.shape[0], cfg.hc_mult, x.shape[-1]))
        cos, sin = rope_at(pos, cfg)
        return x, (cos, sin, valid, params["embed"]["embedding"].dtype)

    def body(moe: bool, experts=None):
        def layer(xs, lp, l_idx, cache, ctx):
            cos, sin, valid, dtype = ctx

            def attention(h):
                with jax.named_scope("attention/mla_q"):
                    q_nope, q_rope = mla_query(h, lp, cos, sin, cfg)
                    q_abs = mla_absorb_query(q_nope, q_rope, lp, cfg)
                with jax.named_scope("attention/mla_kv"):
                    cache.append(mla_latent(h, lp, cos, sin, cfg))
                with jax.named_scope("attention/mla_core"):
                    o_lat = cache.attend(
                        q_abs, scale=cfg.softmax_scale).astype(dtype)
                    return mla_output(o_lat, lp, cfg), None

            xs, _ = hc_sublayer(xs, lp["hc_attn"], lp["attn_norm"]["scale"],
                                attention, cfg, dtype)

            def mlp(h):
                if not moe:
                    with jax.named_scope("mlp"):
                        return dense_mlp(
                            h, lp["gate_proj"]["kernel"],
                            lp["up_proj"]["kernel"],
                            lp["down_proj"]["kernel"]), None
                # the experts' stack rides the closure, not the scan: see
                # moe/dropless.dropless_experts
                return sigmoid_moe_block(
                    h, lp, k=cfg.num_experts_per_tok,
                    scaling=cfg.routed_scaling_factor,
                    renormalise=cfg.norm_topk_prob, valid=valid,
                    experts=experts, layer=l_idx - cfg.num_dense_layers)

            return hc_sublayer(xs, lp["hc_mlp"], lp["mlp_norm"]["scale"],
                               mlp, cfg, dtype)

        return layer

    def stacks(params):
        Ld = cfg.num_dense_layers
        if Ld:
            yield LayerStack(params["dense_layers"], range(Ld), body(False),
                             scope="layers")
        if cfg.num_moe_layers:
            moe = params["moe_layers"]
            yield LayerStack(
                {k: v for k, v in moe.items() if k != "experts"},
                range(Ld, cfg.num_layers), body(True, moe["experts"]),
                scope="layers")

    def head(params, x, pick):
        dtype = params["embed"]["embedding"].dtype
        with jax.named_scope("final_norm"):
            # the streams are summed before the final norm
            x = rms_norm(jnp.sum(x, axis=1),
                         params["norm_f"]["scale"].astype(jnp.float32),
                         cfg.norm_eps).astype(dtype)
        with jax.named_scope("lm_head"):
            last = pick(x)
            if cfg.tie_embeddings:
                return last @ params["embed"]["embedding"].T
            return last @ params["lm_head"]["kernel"]

    return ServingFamily(
        num_layers=cfg.num_layers, num_heads=cfg.num_heads,
        row=LatentRow(width=cfg.latent_row, dim=cfg.latent_dim,
                      rank=cfg.kv_lora_rank),
        embed=embed, stacks=stacks, head=head,
        counts=ExpertPairs(cfg.n_routed_experts,
                           cfg.num_moe_layers * cfg.num_experts_per_tok))
