"""Olmo-Hybrid family (``model_type: olmo_hybrid``): a DENSE decoder whose
layers are Gated DeltaNet (linear attention) mixers with, at a fixed period,
a full softmax-attention layer; every layer followed by a SwiGLU MLP.

What tells it from the other family with DeltaNet layers
(``models/qwen3_next.py``):

* the OLMo block: no pre-norm; the norms come AFTER the mixer and after the
  MLP, on the branch — ``h = x + norm(mixer(x))``, ``y = h + norm(mlp(h))``;
* full attention is plain multi-head (as many K/V heads as query heads, no
  output gate), QK-norm over the WHOLE projection (``[H * hd]``, not a
  head), and NO rotary embedding where ``rope_theta`` is None (position
  comes from the convolutions and decays of the linear layers; a number
  turns the repo's rotary on);
* the delta rule's ``beta`` runs to 2 (``linear_allow_neg_eigval``: ``I -
  beta k k^T`` may have eigenvalue -1) — the factor is applied HERE
  (:func:`gdn_inputs`); ``kernels/gdn_ops`` takes ``beta`` as it comes;
* ``q``, ``k`` and ``v`` each have their own short convolution: three
  depthwise convolutions are one over ``[q | k | v]``, which is what
  ``gdn_ops.causal_conv_ragged`` takes; key heads = value heads;
* a plain norm weight (``x / rms(x) * w``), dense MLP, no experts.

This module holds the configuration (from the published ``config.json``
keys), the seeded parameter tree, and the per-token layer mathematics on the
flat token axis ``[T, ...]``.  :func:`serving_family` composes them into what
the paged serving path asks of a model (``models/serving.py``): K/V rows for
the attention layers — stored in a head count that tiles a page
(``KVRow.tiled``) — a recurrent state (:class:`~.serving.GatedDeltaState`)
for the DeltaNet ones; the training path is open (``loss_fn`` raises).
Layers are scanned BY PERIOD, as Qwen3-Next's, so the runner scans alike
bodies: one scanned step is ``period - 1`` DeltaNet layers and one attention
layer, each with its MLP.  The parameters are one stack over periods FOR
EACH POSITION in the period (``init_params``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from .serving import GatedDeltaState, KVRow, LayerStack, ServingFamily
from .transformer import apply_rope_flat, rms_norm, rope_at

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_layers: int = 32
    period: int = 4                     # the last layer of a period is FULL
    num_heads: int = 30
    num_kv_heads: int = 30
    rope_theta: Optional[float] = None  # None: no rotary embedding
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    norm_eps: float = 1e-6
    max_seq_len: int = 65536
    tie_embeddings: bool = False

    @property
    def head_dim(self) -> int:          # the family's convention: no key
        return self.hidden_size // self.num_heads

    @property
    def num_periods(self) -> int:
        return self.num_layers // self.period

    @property
    def gdn_per_period(self) -> int:
        return self.period - 1

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
            conv_kernel=self.linear_conv_kernel_dim,
            beta_max=2.0 if self.linear_allow_neg_eigval else 1.0)

    @staticmethod
    def from_hf(hf: Dict, **overrides) -> "OlmoHybridConfig":
        """From the published ``config.json`` keys.  ``layer_types`` is read
        as given (its first ``num_hidden_layers`` entries, so that a depth
        cut keeps the published list) and must be periodic: ``p - 1`` linear
        layers, then a full one."""
        depth = hf["num_hidden_layers"]
        kinds = list(hf["layer_types"])[:depth]
        if len(kinds) != depth or FULL not in kinds:
            raise NotImplementedError(
                f"olmo_hybrid: layer_types names {len(kinds)} of {depth} "
                f"layers, or no full_attention layer")
        period = kinds.index(FULL) + 1
        want = ([LINEAR] * (period - 1) + [FULL]) * (depth // period)
        if depth % period or kinds != want:
            raise NotImplementedError(
                f"olmo_hybrid: layer_types is not whole periods of "
                f"{period - 1} linear_attention + 1 full_attention layers")
        if hf.get("attention_bias", False):
            raise NotImplementedError("olmo_hybrid: attention_bias")
        if hf["hidden_size"] % hf["num_attention_heads"]:
            raise NotImplementedError(
                "olmo_hybrid: hidden_size is not num_attention_heads heads")
        if hf["linear_num_key_heads"] != hf["linear_num_value_heads"]:
            raise NotImplementedError(
                "olmo_hybrid: key heads != value heads in the linear layers")
        theta = (hf.get("rope_parameters") or {}).get("rope_theta")
        kw = dict(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"], num_layers=depth,
            period=period, num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            rope_theta=None if theta is None else float(theta),
            linear_num_key_heads=hf["linear_num_key_heads"],
            linear_num_value_heads=hf["linear_num_value_heads"],
            linear_key_head_dim=hf["linear_key_head_dim"],
            linear_value_head_dim=hf["linear_value_head_dim"],
            linear_conv_kernel_dim=hf["linear_conv_kernel_dim"],
            linear_allow_neg_eigval=bool(hf.get("linear_allow_neg_eigval",
                                                False)),
            norm_eps=float(hf["rms_norm_eps"]),
            max_seq_len=hf["max_position_embeddings"],
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)))
        kw.update(overrides)
        return OlmoHybridConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "OlmoHybridConfig":
        """Toy widths that KEEP the awkward ratios: 6 heads (no multiple of
        8), keys 24 and values 48 wide (1 : 2, neither a lane tile)."""
        base = dict(vocab_size=256, hidden_size=96, intermediate_size=160,
                    num_layers=8, num_heads=6, num_kv_heads=6,
                    linear_num_key_heads=6, linear_num_value_heads=6,
                    linear_key_head_dim=24, linear_value_head_dim=48,
                    max_seq_len=256)
        base.update(kw)
        return OlmoHybridConfig(**base)


# --------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------- #
def init_params(cfg: OlmoHybridConfig, key: jax.Array, dtype=jnp.float32
                ) -> Dict:
    """Seeded.  The norm weights, ``A_log``, ``dt_bias``, the convolution and
    the gates are drawn large enough that leaving any one of them out moves
    the logits (the tests' mutation cases rest on that).  Norm weights,
    ``A_log`` and ``dt_bias`` are STORED in ``dtype`` like every leaf (the
    serving engine casts the tree) and used in float32."""
    D, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    P, G, I = cfg.num_periods, cfg.gdn_per_period, cfg.period
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    Kd, Vd, K = cfg.key_dim, cfg.value_dim, cfg.linear_conv_kernel_dim
    ks = iter(jax.random.split(key, 80))

    def dense(shape, fan_in):
        return (jax.random.normal(next(ks), shape) / math.sqrt(fan_in)
                ).astype(dtype)

    def weight(*shape):                 # a plain norm weight around 1
        return (1.0 + 0.3 * jax.random.normal(next(ks), shape)).astype(dtype)

    def gdn_layer():
        return {
            # [q | k | v | g]: the three convolved projections, then the gate
            "qkvg": {"kernel": dense((P, D, 2 * Kd + 2 * Vd), D)},
            "ba": {"kernel": dense((P, D, 2 * Hv), D)},
            "conv": {"kernel": (jax.random.normal(next(ks),
                                                  (P, K, 2 * Kd + Vd))
                                / math.sqrt(K)).astype(dtype)},
            # decays exp(-exp(A_log) softplus(a + dt_bias)) of 0.5-0.99 a
            # token
            "A_log": jnp.log(jax.random.uniform(
                next(ks), (P, Hv), jnp.float32, 0.05, 1.0)).astype(dtype),
            "dt_bias": jax.random.uniform(next(ks), (P, Hv), jnp.float32,
                                          -1.0, 1.0).astype(dtype),
            "gnorm": {"scale": weight(P, dv)},
            "o_proj": {"kernel": dense((P, Vd, D), Vd)},
            "post_norm": {"scale": weight(P, D)},
        }

    def mlp_layer():
        return {"gate": {"kernel": dense((P, D, F), D)},
                "up": {"kernel": dense((P, D, F), D)},
                "down": {"kernel": dense((P, F, D), F)},
                "post_norm": {"scale": weight(P, D)}}

    attn = {
        "q_proj": {"kernel": dense((P, D, H * hd), D)},
        "k_proj": {"kernel": dense((P, D, KV * hd), D)},
        "v_proj": {"kernel": dense((P, D, KV * hd), D)},
        "q_norm": {"scale": weight(P, H * hd)},
        "k_norm": {"scale": weight(P, KV * hd)},
        "o_proj": {"kernel": dense((P, H * hd, D), H * hd)},
        "post_norm": {"scale": weight(P, D)},
    }
    # ONE stack over periods for each position in the period (a tuple of G
    # linear layers, of I MLPs), not a [P, G, ...] stack: the scan over
    # periods then slices a LAYER's weights straight out of its stack, as a
    # scan over alike layers does.  A layer picked out of a period's slice
    # is a copy of the period's weights first and of the layer's again
    # (PR 34 read it on the chip: 45 ms a step against 21)
    gdn = tuple(gdn_layer() for _ in range(G))
    mlp = tuple(mlp_layer() for _ in range(I))
    params = {
        # unit variance: the post-norm branches add unit-rms vectors, and an
        # embedding of 0.02 would be lost under the first of them
        "embed": {"embedding": jax.random.normal(next(ks), (V, D)
                                                 ).astype(dtype)},
        "periods": {"gdn": gdn, "attn": attn, "mlp": mlp},
        "norm_f": {"scale": weight(D)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": dense((D, V), D)}
    return params


class OlmoHybridLM:
    """Model object the serving engine takes (``config`` +
    ``init_params``).  Loading a checkpoint's tensors is out of scope; the
    training path is open."""

    def __init__(self, cfg: OlmoHybridConfig):
        self.config = cfg

    @classmethod
    def from_hf_config(cls, hf: Dict, **overrides) -> "OlmoHybridLM":
        return cls(OlmoHybridConfig.from_hf(hf, **overrides))

    def init_params(self, key: jax.Array, dtype=jnp.float32):
        return init_params(self.config, key, dtype)

    def loss_fn(self, params, batch, rng):
        raise NotImplementedError(
            "olmo_hybrid: the training path is open (ROADMAP R5: the backward "
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
def norm(x, w, eps):
    """``x / rms(x) * w`` in float32, back in ``x``'s dtype."""
    return rms_norm(x.astype(jnp.float32), w.astype(jnp.float32),
                    eps).astype(x.dtype)


def gdn_inputs(x, lp: Dict, cfg: OlmoHybridConfig):
    """The layer's input [T, D] (no pre-norm) → (mixed [T, 2Kd+Vd] before
    the convolutions, gate [T, Hv, dv], g [T, Hv], beta [T, Hv]; the last
    two float32).  ``beta`` is in (0, 2) with ``linear_allow_neg_eigval``."""
    T = x.shape[0]
    Hv = cfg.linear_num_value_heads
    qkvg = x @ lp["qkvg"]["kernel"]
    ba = (x @ lp["ba"]["kernel"]).astype(jnp.float32)
    cut = 2 * cfg.key_dim + cfg.value_dim
    gate = qkvg[:, cut:].reshape(T, Hv, cfg.linear_value_head_dim)
    beta = jax.nn.sigmoid(ba[:, :Hv])
    if cfg.linear_allow_neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[:, Hv:] + lp["dt_bias"].astype(jnp.float32))
    return qkvg[:, :cut], gate, g, beta


def gdn_output(o, gate, lp: Dict, cfg: OlmoHybridConfig, dtype):
    """The gated norm (one head's values) and the output projection: ``o``
    [T, Hv, dv] float32 → [T, D]."""
    o = rms_norm(o, lp["gnorm"]["scale"].astype(jnp.float32), cfg.norm_eps)
    o = (o * jax.nn.silu(gate.astype(jnp.float32))).astype(dtype)
    return o.reshape(o.shape[0], -1) @ lp["o_proj"]["kernel"]


def attention_inputs(x, lp: Dict, rope, cfg: OlmoHybridConfig):
    """The layer's input [T, D] → (q [T, H, hd], k, v [T, KV, hd]): QK-norm
    over the whole projection, rotary only where the config has a theta."""
    T = x.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = norm(x @ lp["q_proj"]["kernel"], lp["q_norm"]["scale"], cfg.norm_eps)
    k = norm(x @ lp["k_proj"]["kernel"], lp["k_norm"]["scale"], cfg.norm_eps)
    q, k = q.reshape(T, H, hd), k.reshape(T, KV, hd)
    v = (x @ lp["v_proj"]["kernel"]).reshape(T, KV, hd)
    if rope is not None:
        q, k = apply_rope_flat(q, *rope), apply_rope_flat(k, *rope)
    return q, k, v


def mlp(h, lp: Dict):
    return (jax.nn.silu(h @ lp["gate"]["kernel"]) * (h @ lp["up"]["kernel"])
            ) @ lp["down"]["kernel"]


# --------------------------------------------------------------------- #
# Paged serving (models/serving.py says what each piece is handed)
# --------------------------------------------------------------------- #
def serving_family(cfg: OlmoHybridConfig) -> ServingFamily:
    """K/V rows of ``num_kv_heads`` x ``head_dim`` in one page layer a
    period, stored in a head count that tiles a page; a Gated DeltaNet state
    in the period's other layers.  One stack of periods."""
    G = cfg.gdn_per_period

    def embed(params, ids, pos, valid):
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"]["embedding"], ids, axis=0)
        rope = None if cfg.rope_theta is None \
            else rope_at(pos, cfg.head_dim, cfg.rope_theta)
        return x, rope

    def period(x, lp, p_idx, cache, rope, state):
        dtype = x.dtype

        def mlp_layer(x, j):
            ml = lp["mlp"][j]
            with jax.named_scope("mlp"):
                return x + norm(mlp(x, ml), ml["post_norm"]["scale"],
                                cfg.norm_eps)

        for j in range(G):
            gl = lp["gdn"][j]
            with jax.named_scope("attention/gdn_proj"):
                mixed, gate, g, beta = gdn_inputs(x, gl, cfg)
            # the convolution with its carry and the delta rule through
            # every sequence's slot (attention/gdn_conv, gdn_core)
            o = state(G * p_idx + j, mixed, g, beta, gl["conv"]["kernel"])
            with jax.named_scope("attention/gdn_out"):
                x = x + norm(gdn_output(o, gate, gl, cfg, dtype),
                             gl["post_norm"]["scale"], cfg.norm_eps)
            x = mlp_layer(x, j)

        al = lp["attn"]
        with jax.named_scope("attention/qkv"):
            q, k, v = attention_inputs(x, al, rope, cfg)
        with jax.named_scope("attention/core"):
            o = cache(q, k, v, scale=cfg.head_dim ** -0.5).astype(dtype)
        with jax.named_scope("attention/out"):
            x = x + norm(o.reshape(o.shape[0], -1) @ al["o_proj"]["kernel"],
                         al["post_norm"]["scale"], cfg.norm_eps)
        return mlp_layer(x, G)

    def stacks(params):
        yield LayerStack(params["periods"], range(cfg.num_periods), period,
                         scope="layers")

    def head(params, x, pick_rows):
        with jax.named_scope("final_norm"):
            x = norm(x, params["norm_f"]["scale"], cfg.norm_eps)
        with jax.named_scope("lm_head"):
            last = pick_rows(x)
            if cfg.tie_embeddings:
                return last @ params["embed"]["embedding"].T
            return last @ params["lm_head"]["kernel"]

    return ServingFamily(
        num_layers=cfg.num_layers, num_heads=cfg.num_heads,
        row=KVRow.tiled(cfg.num_kv_heads, cfg.head_dim),
        embed=embed, stacks=stacks, head=head,
        state=cfg.state, page_layer_count=cfg.num_periods)
