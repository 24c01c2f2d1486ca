"""Qwen3-Next family (``model_type: qwen3_next``): three Gated DeltaNet
(linear attention) layers to one gated softmax-attention layer, every layer
followed by softmax-routed experts beside a sigmoid-gated shared expert.

This module holds the configuration (built from the published
``config.json`` keys), the seeded parameter tree, and the per-token layer
mathematics on the flat token axis ``[T, ...]``.  :func:`serving_family`
composes them into what the paged serving path asks of a model
(``models/serving.py``): K/V rows for the attention layers, a recurrent
state (:class:`~.serving.GatedDeltaState`) for the DeltaNet ones, the pair
counts; the training path is open (``loss_fn`` raises).

Layers are of two kinds in a fixed period (``full_attention_interval``), so
the parameters are stacked BY PERIOD and the runner scans alike bodies: one
scanned step is ``interval - 1`` DeltaNet layers and one attention layer,
each with its expert layer.  The experts' weights are one stack over all
layers, handed to the grouped matmul whole with a layer index
(``moe/dropless.dropless_experts``).

A CHIP'S SHARE of an expert-parallel deployment is stated by keys of its own
and never by a width: ``num_experts`` of the config are the experts HELD,
``ep_size`` chips share a layer (the router scores ``num_experts * ep_size``
experts) and this is chip ``ep_rank``.  A sliced vocabulary is a smaller
``vocab_size``.  The multi-token-prediction module is not held.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp

from .serving import (ExpertPairs, GatedDeltaState, KVRow, LayerStack,
                      ServingFamily)
from .transformer import apply_rope_flat, rms_norm, rope_at


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    full_attention_interval: int = 4
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512              # what the router scores
    experts_held: int = 512             # of them, held here ...
    expert_offset: int = 0              # ... from this one on
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    norm_eps: float = 1e-6
    max_seq_len: int = 262144
    tie_embeddings: bool = False

    @property
    def num_periods(self) -> int:
        return self.num_layers // self.full_attention_interval

    @property
    def gdn_per_period(self) -> int:
        return self.full_attention_interval - 1

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def state(self) -> GatedDeltaState:
        return GatedDeltaState(
            num_layers=self.num_periods * self.gdn_per_period,
            num_heads=self.linear_num_value_heads,
            num_key_heads=self.linear_num_key_heads,
            key_dim=self.linear_key_head_dim,
            value_dim=self.linear_value_head_dim,
            conv_kernel=self.linear_conv_kernel_dim)

    @staticmethod
    def from_hf(hf: Dict, **overrides) -> "Qwen3NextConfig":
        """From the published ``config.json`` keys, plus the share's own
        (``ep_size``, ``ep_rank``; absent: the whole layer is held)."""
        if hf.get("decoder_sparse_step", 1) != 1 or hf.get("mlp_only_layers"):
            raise NotImplementedError(
                "qwen3_next: every layer must be an expert layer "
                "(decoder_sparse_step 1, no mlp_only_layers)")
        if hf.get("rope_scaling"):
            raise NotImplementedError("qwen3_next: rope_scaling must be null")
        interval = hf["full_attention_interval"]
        if hf["num_hidden_layers"] % interval:
            raise NotImplementedError(
                f"qwen3_next: {hf['num_hidden_layers']} layers are not whole "
                f"periods of {interval}")
        ep_size, ep_rank = int(hf.get("ep_size", 1)), int(hf.get("ep_rank", 0))
        kw = dict(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            num_layers=hf["num_hidden_layers"],
            full_attention_interval=interval,
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
            partial_rotary_factor=float(hf["partial_rotary_factor"]),
            rope_theta=float(hf["rope_theta"]),
            linear_num_key_heads=hf["linear_num_key_heads"],
            linear_num_value_heads=hf["linear_num_value_heads"],
            linear_key_head_dim=hf["linear_key_head_dim"],
            linear_value_head_dim=hf["linear_value_head_dim"],
            linear_conv_kernel_dim=hf["linear_conv_kernel_dim"],
            num_experts=hf["num_experts"] * ep_size,
            experts_held=hf["num_experts"],
            expert_offset=hf["num_experts"] * ep_rank,
            num_experts_per_tok=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            shared_expert_intermediate_size=hf[
                "shared_expert_intermediate_size"],
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            norm_eps=float(hf["rms_norm_eps"]),
            max_seq_len=hf["max_position_embeddings"],
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)))
        kw.update(overrides)
        return Qwen3NextConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "Qwen3NextConfig":
        base = dict(vocab_size=256, hidden_size=64, num_layers=4,
                    num_heads=4, num_kv_heads=2, head_dim=32,
                    linear_num_key_heads=2, linear_num_value_heads=4,
                    linear_key_head_dim=16, linear_value_head_dim=16,
                    num_experts=8, experts_held=8, num_experts_per_tok=2,
                    moe_intermediate_size=32,
                    shared_expert_intermediate_size=32, max_seq_len=256)
        base.update(kw)
        return Qwen3NextConfig(**base)


# --------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------- #
def init_params(cfg: Qwen3NextConfig, key: jax.Array, dtype=jnp.float32
                ) -> Dict:
    """Seeded.  The zero-centred norm weights, ``A_log``, ``dt_bias``, the
    convolution and the gates are drawn large enough that leaving any one of
    them out moves the logits (the tests' mutation cases rest on that)."""
    D, V = cfg.hidden_size, cfg.vocab_size
    P, G, I = cfg.num_periods, cfg.gdn_per_period, cfg.full_attention_interval
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    Kd, Vd, K = cfg.key_dim, cfg.value_dim, cfg.linear_conv_kernel_dim
    E, F = cfg.experts_held, cfg.moe_intermediate_size
    Fs = cfg.shared_expert_intermediate_size
    ks = iter(jax.random.split(key, 40))

    def dense(shape, fan_in):
        return (jax.random.normal(next(ks), shape) / math.sqrt(fan_in)
                ).astype(dtype)

    def centred(*shape):                # a (1 + w) norm's w
        return (0.3 * jax.random.normal(next(ks), shape)).astype(dtype)

    gdn = {
        "in_norm": {"scale": centred(P, G, D)},
        "qkvz": {"kernel": dense((P, G, D, 2 * Kd + 2 * Vd), D)},
        "ba": {"kernel": dense((P, G, D, 2 * Hv), D)},
        "conv": {"kernel": (jax.random.normal(next(ks), (P, G, K, 2 * Kd + Vd))
                            / math.sqrt(K)).astype(dtype)},
        # decays exp(-exp(A_log) softplus(a + dt_bias)) of 0.5-0.99 a token
        "A_log": jnp.log(jax.random.uniform(
            next(ks), (P, G, Hv), jnp.float32, 0.05, 1.0)).astype(dtype),
        "dt_bias": jax.random.uniform(next(ks), (P, G, Hv), jnp.float32,
                                      -1.0, 1.0).astype(dtype),
        "gnorm": {"scale": (1.0 + centred(P, G, dv).astype(jnp.float32)
                            ).astype(dtype)},
        "o_proj": {"kernel": dense((P, G, Vd, D), Vd)},
    }
    attn = {
        "in_norm": {"scale": centred(P, D)},
        "q_proj": {"kernel": dense((P, D, H * 2 * hd), D)},
        "k_proj": {"kernel": dense((P, D, KV * hd), D)},
        "v_proj": {"kernel": dense((P, D, KV * hd), D)},
        "q_norm": {"scale": centred(P, hd)},
        "k_norm": {"scale": centred(P, hd)},
        "o_proj": {"kernel": dense((P, H * hd, D), H * hd)},
    }
    moe = {
        "post_norm": {"scale": centred(P, I, D)},
        "router": {"kernel": (jax.random.normal(next(ks),
                                                (P, I, D, cfg.num_experts))
                              / math.sqrt(D)).astype(jnp.float32)},
        "shared": {"gate": dense((P, I, D, Fs), D),
                   "up": dense((P, I, D, Fs), D),
                   "down": dense((P, I, Fs, D), Fs)},
        "shared_gate": {"kernel": dense((P, I, D, 1), D / 4.0)},
    }
    L = cfg.num_layers
    params = {
        "embed": {"embedding": (jax.random.normal(next(ks), (V, D)) * 0.02
                                ).astype(dtype)},
        "periods": {"gdn": gdn, "attn": attn, "moe": moe},
        "experts": {"gate": dense((L, E, D, F), D),
                    "up": dense((L, E, D, F), D),
                    "down": dense((L, E, F, D), F)},
        "norm_f": {"scale": centred(D)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": dense((D, V), D)}
    return params


class Qwen3NextLM:
    """Model object the serving engine takes (``config`` +
    ``init_params``).  Loading a checkpoint's tensors is out of scope; the
    training path is open."""

    def __init__(self, cfg: Qwen3NextConfig):
        self.config = cfg

    @classmethod
    def from_hf_config(cls, hf: Dict, **overrides) -> "Qwen3NextLM":
        return cls(Qwen3NextConfig.from_hf(hf, **overrides))

    def init_params(self, key: jax.Array, dtype=jnp.float32):
        return init_params(self.config, key, dtype)

    def loss_fn(self, params, batch, rng):
        raise NotImplementedError(
            "qwen3_next: the training path is open (ROADMAP R5: the backward "
            "of the chunked delta rule); this family is served through "
            "inference/v2 only")

    def serving_family(self) -> ServingFamily:
        return serving_family(self.config)

    def num_params(self, params=None) -> int:
        if params is None:
            params = jax.eval_shape(lambda k: self.init_params(k),
                                    jax.random.PRNGKey(0))
        return int(sum(math.prod(leaf.shape)
                       for leaf in jax.tree.leaves(params)))


# --------------------------------------------------------------------- #
# Layer mathematics
# --------------------------------------------------------------------- #
def norm1p(x, w, eps):
    """``x / rms(x) * (1 + w)`` in float32, back in ``x``'s dtype."""
    return rms_norm(x.astype(jnp.float32), 1.0 + w.astype(jnp.float32),
                    eps).astype(x.dtype)


def gdn_inputs(h, lp: Dict, cfg: Qwen3NextConfig):
    """Normed input [T, D] → (mixed [T, 2Kd+Vd] before the convolution, z
    [T, Hv, dv], g [T, Hv], beta [T, Hv]; the last two float32)."""
    T = h.shape[0]
    Hv = cfg.linear_num_value_heads
    qkvz = h @ lp["qkvz"]["kernel"]
    ba = (h @ lp["ba"]["kernel"]).astype(jnp.float32)
    cut = 2 * cfg.key_dim + cfg.value_dim
    z = qkvz[:, cut:].reshape(T, Hv, cfg.linear_value_head_dim)
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[:, Hv:] + lp["dt_bias"].astype(jnp.float32))
    return qkvz[:, :cut], z, g, beta


def gdn_output(o, z, lp: Dict, cfg: Qwen3NextConfig, dtype):
    """The gated norm (its weight as it is, one head's values) and the output
    projection: ``o`` [T, Hv, dv] float32 → [T, D]."""
    o = rms_norm(o, lp["gnorm"]["scale"].astype(jnp.float32), cfg.norm_eps)
    o = (o * jax.nn.silu(z.astype(jnp.float32))).astype(dtype)
    return o.reshape(o.shape[0], -1) @ lp["o_proj"]["kernel"]


def attention_inputs(h, lp: Dict, cos, sin, cfg: Qwen3NextConfig):
    """Normed input [T, D] → (q [T, H, hd], gate [T, H*hd], k, v [T, KV,
    hd]): per-head ``(1 + w)`` norms on q and k, rotary on the first
    ``rotary_dim`` values."""
    T = h.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    qg = (h @ lp["q_proj"]["kernel"]).reshape(T, H, 2, hd)
    q, gate = qg[:, :, 0, :], qg[:, :, 1, :].reshape(T, H * hd)
    k = (h @ lp["k_proj"]["kernel"]).reshape(T, KV, hd)
    v = (h @ lp["v_proj"]["kernel"]).reshape(T, KV, hd)
    q = norm1p(q, lp["q_norm"]["scale"], cfg.norm_eps)
    k = norm1p(k, lp["k_norm"]["scale"], cfg.norm_eps)
    q = apply_rope_flat(q, cos, sin, rotary_dim=cfg.rotary_dim)
    k = apply_rope_flat(k, cos, sin, rotary_dim=cfg.rotary_dim)
    return q, gate, k, v


# --------------------------------------------------------------------- #
# Paged serving (models/serving.py says what each piece is handed)
# --------------------------------------------------------------------- #
def serving_family(cfg: Qwen3NextConfig) -> ServingFamily:
    """K/V rows of ``num_kv_heads`` x ``head_dim`` in one page layer a
    period; a Gated DeltaNet state in the period's other layers.  One stack
    of periods; a step also returns the pairs per expert held ``[E]`` and,
    of a share, the pairs held elsewhere as one more entry."""
    from ..moe.dropless import softmax_moe_block

    G, I = cfg.gdn_per_period, cfg.full_attention_interval
    share = cfg.experts_held != cfg.num_experts
    pick = lambda tree, j: jax.tree.map(lambda a: a[j], tree)  # noqa: E731

    def embed(params, ids, pos, valid):
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"]["embedding"], ids, axis=0)
        cos, sin = rope_at(pos, cfg.rotary_dim, cfg.rope_theta)
        return x, (cos, sin, valid())

    def body(experts):
        def period(x, lp, p_idx, cache, ctx, state):
            cos, sin, valid = ctx
            dtype = x.dtype
            counts = 0

            def expert_layer(x, j):
                ml = pick(lp["moe"], j)
                h = norm1p(x, ml["post_norm"]["scale"], cfg.norm_eps)
                y, pairs = softmax_moe_block(
                    h, ml, k=cfg.num_experts_per_tok,
                    renormalise=cfg.norm_topk_prob,
                    offset=cfg.expert_offset if share else None,
                    valid=valid, experts=experts, layer=I * p_idx + j)
                return x + y, pairs

            for j in range(G):
                gl = pick(lp["gdn"], j)
                h = norm1p(x, gl["in_norm"]["scale"], cfg.norm_eps)
                with jax.named_scope("attention/gdn_proj"):
                    mixed, z, g, beta = gdn_inputs(h, gl, cfg)
                # the convolution with its carry and the delta rule through
                # every sequence's slot (attention/gdn_conv, gdn_core)
                o = state(G * p_idx + j, mixed, g, beta,
                          gl["conv"]["kernel"])
                with jax.named_scope("attention/gdn_out"):
                    x = x + gdn_output(o, z, gl, cfg, dtype)
                x, pairs = expert_layer(x, j)
                counts = counts + pairs

            al = lp["attn"]
            h = norm1p(x, al["in_norm"]["scale"], cfg.norm_eps)
            with jax.named_scope("attention/qkv"):
                q, gate, k, v = attention_inputs(h, al, cos, sin, cfg)
            with jax.named_scope("attention/core"):
                o = cache(q, k, v, scale=cfg.head_dim ** -0.5).astype(dtype)
            with jax.named_scope("attention/gate"):
                o = o.reshape(o.shape[0], -1) * jax.nn.sigmoid(
                    gate.astype(jnp.float32)).astype(dtype)
            with jax.named_scope("attention/out"):
                x = x + o @ al["o_proj"]["kernel"]
            x, pairs = expert_layer(x, G)
            return x, counts + pairs

        return period

    def stacks(params):
        yield LayerStack(params["periods"], range(cfg.num_periods),
                         body(params["experts"]), scope="layers")

    def head(params, x, pick_rows):
        with jax.named_scope("final_norm"):
            x = norm1p(x, params["norm_f"]["scale"], cfg.norm_eps)
        with jax.named_scope("lm_head"):
            last = pick_rows(x)
            if cfg.tie_embeddings:
                return last @ params["embed"]["embedding"].T
            return last @ params["lm_head"]["kernel"]

    return ServingFamily(
        num_layers=cfg.num_layers, num_heads=cfg.num_heads,
        row=KVRow(cfg.num_kv_heads, cfg.head_dim),
        embed=embed, stacks=stacks, head=head,
        counts=ExpertPairs(cfg.experts_held,
                           cfg.num_layers * cfg.num_experts_per_tok,
                           elsewhere=share),
        state=cfg.state, page_layer_count=cfg.num_periods)
