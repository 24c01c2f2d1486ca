"""LongCat-Flash family (``meituan-longcat/LongCat-Flash-Chat``): a
shortcut-connected DOUBLE layer — two multi-head latent attention (MLA)
blocks and two dense SwiGLU FFNs a layer, with one expert branch that leaves
the layer after its first attention block and rejoins at its end — and a
softmax router over real experts and ZERO-COMPUTATION identity experts alike.

One layer (``x`` the residual, ``rms`` with ``rms_norm_eps``)::

    for i in (0, 1):
        x = x + MLA_i(rms(x; g_in_i))               # page layer 2l + i
        h = rms(x; g_post_i)
        if i == 0:  m = MoE(h)                      # the shortcut leaves ...
        x = x + FFN_i(h)
    x = x + m                                       # ... and rejoins

``MLA``: the query's latent and the cached latent are both scaled after
their norms (``mla_scale_q_lora``: ``sqrt(hidden / q_lora_rank)``,
``mla_scale_kv_lora``: ``sqrt(hidden / kv_lora_rank)``); one rotary key for
all heads, plain RoPE.  ``MoE(h) = Σ_k g_k · E_id_k(h)`` with ``s =
softmax(h W_r)`` over ``n_routed_experts + zero_expert_num`` outputs in
float32, ``id = top_k(s + b)``, ``g = routed_scaling_factor · s[id]`` NOT
renormalised, ``E_id`` a SwiGLU for a real expert and the identity for
``id >= n_routed_experts``.  No shared expert; embedding and head untied.

This module holds the configuration (built from the published
``config.json`` keys), the seeded parameter tree, and the per-token layer
mathematics on the flat token axis ``[T, ...]``.  :func:`serving_family`
composes them into what the paged serving path asks of a model
(``models/serving.py``): a latent row, TWO page layers a layer (the body
takes them from one handle, ``cache.at``), the pair counts with the identity
pairs apart; the family is served only (``loss_fn`` raises and says what a
training path would still lack; latent attention itself trains in
``models/joyai_flash.py``).

A CHIP'S SHARE of an expert-parallel deployment is stated by keys of its own
and never by a width: ``n_routed_experts`` of the config file are the real
experts HELD, ``ep_size`` chips share a layer (the router scores
``n_routed_experts * ep_size`` real experts and every identity one) and this
is chip ``ep_rank``.  The identity experts have no weights to divide: every
chip computes them for the tokens whose residual it holds.  A sliced
vocabulary is a smaller ``vocab_size``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp

from .serving import ExpertPairs, LatentRow, LayerStack, ServingFamily
from .transformer import rms_norm, rope_at
from .xing4 import apply_rope, dense_mlp, mla_absorb_query, mla_output


@dataclasses.dataclass(frozen=True)
class LongCatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288            # each of a layer's two dense FFNs
    expert_ffn_hidden_size: int = 2048      # one expert's width
    num_layers: int = 28                    # double layers
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512             # real experts the router scores
    experts_held: int = 512                 # of them, held here ...
    expert_offset: int = 0                  # ... from this one on
    zero_expert_num: int = 256              # identity experts, behind them
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    norm_eps: float = 1e-5
    rope_theta: float = 1e7
    max_seq_len: int = 131072

    @property
    def router_outputs(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Values cached per token per page layer: ``c_kv`` and the shared
        ``k_rope``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """The cached row as stored: whole 128-lane tiles."""
        return -(-self.latent_dim // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @property
    def q_scale(self) -> float:
        return math.sqrt(self.hidden_size / self.q_lora_rank) \
            if self.mla_scale_q_lora else 1.0

    @property
    def kv_scale(self) -> float:
        return math.sqrt(self.hidden_size / self.kv_lora_rank) \
            if self.mla_scale_kv_lora else 1.0

    @staticmethod
    def from_hf(hf: Dict, **overrides) -> "LongCatFlashConfig":
        """From the published ``config.json`` keys, plus the share's own
        (``ep_size``, ``ep_rank``; absent: the whole layer is held)."""
        if hf.get("zero_expert_type", "identity") != "identity":
            raise NotImplementedError(
                "longcat_flash: zero experts must be of type identity")
        if hf.get("attention_method", "MLA") != "MLA":
            raise NotImplementedError("longcat_flash: attention must be MLA")
        if hf.get("rope_scaling"):
            raise NotImplementedError(
                "longcat_flash: rope_scaling must be absent")
        ep_size, ep_rank = int(hf.get("ep_size", 1)), int(hf.get("ep_rank", 0))
        kw = dict(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            ffn_hidden_size=hf["ffn_hidden_size"],
            expert_ffn_hidden_size=hf["expert_ffn_hidden_size"],
            num_layers=hf["num_layers"],
            num_heads=hf["num_attention_heads"],
            q_lora_rank=hf["q_lora_rank"], kv_lora_rank=hf["kv_lora_rank"],
            qk_nope_head_dim=hf["qk_nope_head_dim"],
            qk_rope_head_dim=hf["qk_rope_head_dim"],
            v_head_dim=hf["v_head_dim"],
            mla_scale_q_lora=bool(hf.get("mla_scale_q_lora", False)),
            mla_scale_kv_lora=bool(hf.get("mla_scale_kv_lora", False)),
            n_routed_experts=hf["n_routed_experts"] * ep_size,
            experts_held=hf["n_routed_experts"],
            expert_offset=hf["n_routed_experts"] * ep_rank,
            zero_expert_num=hf.get("zero_expert_num", 0),
            moe_topk=hf["moe_topk"],
            routed_scaling_factor=float(hf["routed_scaling_factor"]),
            norm_eps=float(hf["rms_norm_eps"]),
            rope_theta=float(hf["rope_theta"]),
            max_seq_len=hf["max_position_embeddings"])
        kw.update(overrides)
        return LongCatFlashConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "LongCatFlashConfig":
        base = dict(vocab_size=256, hidden_size=64, ffn_hidden_size=128,
                    expert_ffn_hidden_size=32, num_layers=2, num_heads=4,
                    q_lora_rank=16, kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
                    experts_held=8, zero_expert_num=4, moe_topk=3,
                    max_seq_len=256)
        base.update(kw)
        return LongCatFlashConfig(**base)


# --------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------- #
def init_params(cfg: LongCatFlashConfig, key: jax.Array, dtype=jnp.float32
                ) -> Dict:
    """Seeded.  ``layers``: what a layer's two blocks hold, a stack ``[L,
    ...]`` EACH (``blocks[0]``, ``blocks[1]``: stacked ``[L, 2, ...]`` the
    scan's slice of a layer is ``[2, D, F]``, and XLA copies a 300 MB slab
    to hand one matrix of it to a matmul, 9 ms of a decode step on the
    chip, PERF.md section 6, PR 41), and its one router ``[L, ...]``
    (float32, kernel and selection bias).  ``experts``: the held experts of
    all layers as one stack ``[L, E, ...]``, handed to the grouped matmul
    whole with a layer index (``moe/dropless.dropless_experts``).  The norm
    weights are drawn around 1 and the bias at the scale of the softmax
    scores' own spread, so that leaving either out moves the logits (the
    tests' mutation cases rest on that); ``q_b_proj`` and ``kv_b_proj`` at
    the fan-in their scaled latents stand for (``up_fan_in``)."""
    D, V, L, H = cfg.hidden_size, cfg.vocab_size, cfg.num_layers, cfg.num_heads
    F, E, Fe = cfg.ffn_hidden_size, cfg.experts_held, cfg.expert_ffn_hidden_size
    ks = iter(jax.random.split(key, 40))

    def dense(shape, fan_in):
        return (jax.random.normal(next(ks), shape) / math.sqrt(fan_in)
                ).astype(dtype)

    def up_fan_in(rank, scale):
        # the up-projection of a latent that is SCALED after its norm is
        # drawn as if its input had the hidden width (the scales exist to
        # align the low-rank paths' variance with a full-rank one's): q, k
        # and v come out at unit variance.  Drawn at 1/sqrt(rank) the
        # scores' spread would be sqrt(12) x 2 wider, a softmax that is an
        # argmax, and no precision holds the logits (read on the chip,
        # PERF.md section 6, PR 41)
        return rank * scale * scale

    def gain(*shape):
        return (1.0 + 0.2 * jax.random.normal(next(ks), shape)).astype(dtype)

    def block():
        return {
            "in_norm": {"scale": gain(L, D)},
            "q_a_proj": {"kernel": dense((L, D, cfg.q_lora_rank), D)},
            "q_a_norm": {"scale": gain(L, cfg.q_lora_rank)},
            "q_b_proj": {"kernel": dense(
                (L, cfg.q_lora_rank, H * cfg.qk_head_dim),
                up_fan_in(cfg.q_lora_rank, cfg.q_scale))},
            "kv_a_proj": {"kernel": dense((L, D, cfg.latent_dim), D)},
            "kv_a_norm": {"scale": gain(L, cfg.kv_lora_rank)},
            # [c_kv] -> per head [k_nope ; v]
            "kv_b_proj": {"kernel": dense(
                (L, cfg.kv_lora_rank,
                 H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                up_fan_in(cfg.kv_lora_rank, cfg.kv_scale))},
            "o_proj": {"kernel": dense((L, H * cfg.v_head_dim, D),
                                       H * cfg.v_head_dim)},
            "post_norm": {"scale": gain(L, D)},
            "gate_proj": {"kernel": dense((L, D, F), D)},
            "up_proj": {"kernel": dense((L, D, F), D)},
            "down_proj": {"kernel": dense((L, F, D), F)},
        }

    R = cfg.router_outputs
    router = {
        "kernel": (jax.random.normal(next(ks), (L, D, R)) / math.sqrt(D)
                   ).astype(jnp.float32),
        # enters the selection, not the weights; a softmax score is ~1/R
        "bias": (0.5 / R) * jax.random.normal(next(ks), (L, R), jnp.float32)}
    return {
        "embed": {"embedding": (jax.random.normal(next(ks), (V, D)) * 0.02
                                ).astype(dtype)},
        "layers": {"blocks": [block(), block()], "router": router},
        "experts": {"gate": dense((L, E, D, Fe), D),
                    "up": dense((L, E, D, Fe), D),
                    "down": dense((L, E, Fe, D), Fe)},
        "norm_f": {"scale": gain(D)},
        "lm_head": {"kernel": dense((D, V), D)},
    }


class LongCatFlashLM:
    """Model object the serving engine takes (``config`` +
    ``init_params``).  Loading a checkpoint's tensors is out of scope; the
    family is served only."""

    def __init__(self, cfg: LongCatFlashConfig):
        self.config = cfg

    @classmethod
    def from_hf_config(cls, hf: Dict, **overrides) -> "LongCatFlashLM":
        return cls(LongCatFlashConfig.from_hf(hf, **overrides))

    def init_params(self, key: jax.Array, dtype=jnp.float32):
        return init_params(self.config, key, dtype)

    def loss_fn(self, params, batch, rng):
        raise NotImplementedError(
            "longcat_flash: this family is served through inference/v2 "
            "only; latent attention's training form (expanded k/v through "
            "flash attention, forward and backward) is "
            "models/joyai_flash.py's, what a training path here still "
            "lacks is the two-attention layer's block and the identity "
            "experts' share of a dropless backward (ROADMAP R3)")

    def serving_family(self) -> ServingFamily:
        return serving_family(self.config)

    def num_params(self, params=None) -> int:
        if params is None:
            params = jax.eval_shape(lambda k: self.init_params(k),
                                    jax.random.PRNGKey(0))
        return int(sum(math.prod(leaf.shape)
                       for leaf in jax.tree.leaves(params)))


# --------------------------------------------------------------------- #
# MLA projections (the absorbed query and the output are Xing4's)
# --------------------------------------------------------------------- #
def mla_query(h, bp: Dict, cos, sin, cfg: LongCatFlashConfig):
    """Normed input [T, D] → (q_nope [T, H, dn], q_rope [T, H, rd]), RoPE
    applied; the query's latent is scaled after its norm."""
    T, H = h.shape[0], cfg.num_heads
    c_q = rms_norm(h @ bp["q_a_proj"]["kernel"],
                   bp["q_a_norm"]["scale"].astype(jnp.float32) * cfg.q_scale,
                   cfg.norm_eps).astype(h.dtype)
    q = (c_q @ bp["q_b_proj"]["kernel"]).reshape(T, H, cfg.qk_head_dim)
    q_nope, q_rope = q[..., :cfg.qk_nope_head_dim], \
        q[..., cfg.qk_nope_head_dim:]
    return q_nope, apply_rope(q_rope, cos, sin)


def mla_latent(h, bp: Dict, cos, sin, cfg: LongCatFlashConfig):
    """Normed input [T, D] → the cached row [T, latent_row]: ``c_kv`` after
    its norm AND its scale (the cache holds the scaled latent: one rounding,
    and ``kv_b_proj`` stays the published matrix), ``k_rope`` after RoPE,
    zero padding."""
    T = h.shape[0]
    ckv = h @ bp["kv_a_proj"]["kernel"]
    c_kv = rms_norm(ckv[:, :cfg.kv_lora_rank],
                    bp["kv_a_norm"]["scale"].astype(jnp.float32)
                    * cfg.kv_scale, cfg.norm_eps).astype(h.dtype)
    k_rope = apply_rope(ckv[:, cfg.kv_lora_rank:], cos, sin)
    pad = jnp.zeros((T, cfg.latent_row - cfg.latent_dim), c_kv.dtype)
    return jnp.concatenate([c_kv, k_rope, pad], axis=-1)


# --------------------------------------------------------------------- #
# Paged serving (models/serving.py says what each piece is handed)
# --------------------------------------------------------------------- #
def serving_family(cfg: LongCatFlashConfig) -> ServingFamily:
    """Latent (MLA) rows in TWO page layers a layer (block ``i`` of layer
    ``l`` owns page layer ``2l + i``), attended in the absorbed form for
    prefill chunks and decode alike.  One stack of double layers; a step
    also returns the pairs per expert held ``[E]``, of a share the pairs
    held elsewhere, and last the identity pairs."""
    from ..moe.dropless import zero_expert_moe_block

    share = cfg.experts_held != cfg.n_routed_experts
    identity_from = cfg.n_routed_experts if cfg.zero_expert_num else None

    def embed(params, ids, pos, valid):
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"]["embedding"], ids, axis=0)
        cos, sin = rope_at(pos, cfg.qk_rope_head_dim, cfg.rope_theta)
        return x, (cos, sin, valid())

    def attention(h, bp, cos, sin, cache):
        dtype = h.dtype
        with jax.named_scope("attention/mla_q"):
            q_nope, q_rope = mla_query(h, bp, cos, sin, cfg)
            q_abs = mla_absorb_query(q_nope, q_rope, bp, cfg)
        with jax.named_scope("attention/mla_kv"):
            cache.append(mla_latent(h, bp, cos, sin, cfg))
        with jax.named_scope("attention/mla_core"):
            o_lat = cache.attend(q_abs, scale=cfg.softmax_scale).astype(dtype)
            return mla_output(o_lat, bp, cfg)

    def body(experts):
        def layer(x, lp, l_idx, cache, ctx):
            cos, sin, valid = ctx
            for i in (0, 1):
                bp = lp["blocks"][i]
                h = rms_norm(x, bp["in_norm"]["scale"], cfg.norm_eps)
                x = x + attention(h, bp, cos, sin, cache.at(2 * l_idx + i))
                h = rms_norm(x, bp["post_norm"]["scale"], cfg.norm_eps)
                if i == 0:
                    # the shortcut: the expert branch leaves here, from the
                    # FIRST block's hidden state (the experts' stack rides
                    # the closure, not the scan: moe/dropless)
                    routed, pairs = zero_expert_moe_block(
                        h, lp, k=cfg.moe_topk,
                        scaling=cfg.routed_scaling_factor,
                        identity_from=identity_from,
                        offset=cfg.expert_offset if share else None,
                        valid=valid, experts=experts, layer=l_idx)
                with jax.named_scope("mlp"):
                    x = x + dense_mlp(h, bp["gate_proj"]["kernel"],
                                      bp["up_proj"]["kernel"],
                                      bp["down_proj"]["kernel"])
            with jax.named_scope("moe/combine"):
                return x + routed, pairs             # ... and rejoins here

        return layer

    def stacks(params):
        yield LayerStack(params["layers"], range(cfg.num_layers),
                         body(params["experts"]), scope="layers")

    def head(params, x, pick_rows):
        with jax.named_scope("final_norm"):
            x = rms_norm(x, params["norm_f"]["scale"], cfg.norm_eps)
        with jax.named_scope("lm_head"):
            return pick_rows(x) @ params["lm_head"]["kernel"]

    return ServingFamily(
        num_layers=cfg.num_layers, num_heads=cfg.num_heads,
        row=LatentRow(width=cfg.latent_row, dim=cfg.latent_dim,
                      rank=cfg.kv_lora_rank),
        embed=embed, stacks=stacks, head=head,
        counts=ExpertPairs(cfg.experts_held, cfg.num_layers * cfg.moe_topk,
                           elsewhere=share, identity=identity_from is not None),
        page_layer_count=2 * cfg.num_layers)
