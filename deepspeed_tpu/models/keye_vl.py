"""Keye-VL-2.0 language model (``model_type: KeyeVL2``): every layer is
grouped-query attention that reads only the ``topk`` cached tokens a learned
INDEXER picks for each query (DeepSeek-Sparse-Attention's indexer on a GQA
cache), followed by softmax-routed experts with no shared expert; positions
are M-RoPE's three streams.

This module holds the configuration (built from the published ``config.json``
keys and its ``sa_config``), the seeded parameter tree, the per-token layer
mathematics on the flat token axis ``[T, ...]``, and :func:`forward`, the
plain whole-sequence forward on positions ``[3, T]``.  :func:`serving_family`
composes the same pieces into what the paged serving path asks of a model
(``models/serving.py``): a K/V row that carries an index key, the pair
counts; the training path is open (``loss_fn`` raises).

The layer (``x`` the residual, ``p_t`` the token's three positions)::

    h = rms(x)
    q = rope(rms_head(h W_q))   k = rope(rms_head(h W_k))   v = h W_v
    qI = rope(h W_qI) [Hi, di]  kI = rope(h W_kI) [di]      w = h W_wI [Hi]
    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])     cached s <= t, float32
    S_t = the topk positions of largest I[t, s] (ties: the lower position)
    o = softmax attention of q over the rows of S_t;  x = x + o W_o
    h = rms(x);  p = softmax(h W_r);  top-k, renormalised;  x = x + experts

``rope`` is M-RoPE: rotary pair ``i`` of a head (``head_dim / 2`` pairs,
half-split) turns by stream 0 (temporal) for ``i < mrope_section[0]``, by
stream 1 (height) for the next ``mrope_section[1]``, by stream 2 (width) for
the rest; the indexer's ``di / 2`` pairs turn by the temporal stream.  The
serving family builds the three streams from the engine's ``pos`` (equal:
text).  The vision tower is NOT built, and with it requests that bring rows
of embeddings and unequal streams (ROADMAP Queue 2).

A CHIP'S SHARE of an expert-parallel deployment is stated by keys of its own
and never by a width: ``num_experts`` of the config are the experts HELD,
``ep_size`` chips share a layer (the router scores ``num_experts * ep_size``)
and this is chip ``ep_rank``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .serving import ExpertPairs, IndexKey, KVRow, LayerStack, ServingFamily
from .transformer import apply_rope_flat, rms_norm


@dataclasses.dataclass(frozen=True)
class KeyeVLConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    mrope_section: Tuple[int, int, int] = (16, 24, 24)
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    topk: int = 2048
    num_experts: int = 128              # what the router scores
    experts_held: int = 128             # of them, held here ...
    expert_offset: int = 0              # ... from this one on
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    norm_eps: float = 1e-6
    max_seq_len: int = 262144
    tie_embeddings: bool = False

    def __post_init__(self):
        if sum(self.mrope_section) != self.head_dim // 2:
            raise ValueError(
                f"mrope_section {self.mrope_section} must cover the "
                f"{self.head_dim // 2} rotary pairs of a head")

    @property
    def index(self) -> IndexKey:
        return IndexKey(dim=self.indexer_head_dim,
                        heads=self.indexer_num_heads, topk=self.topk)

    @staticmethod
    def from_hf(hf: Dict, **overrides) -> "KeyeVLConfig":
        """From the published ``config.json`` keys (``sa_config`` and
        ``rope_scaling.mrope_section`` nested as published), plus the
        share's own (``ep_size``, ``ep_rank``; absent: the whole layer)."""
        if hf.get("decoder_sparse_step", 1) != 1 or hf.get("mlp_only_layers"):
            raise NotImplementedError(
                "keye_vl: every layer must be an expert layer "
                "(decoder_sparse_step 1, no mlp_only_layers)")
        sa = hf["sa_config"]
        if sa.get("indexer_num_kv_heads", 1) != 1:
            raise NotImplementedError(
                "keye_vl: one index key a token (indexer_num_kv_heads 1)")
        section = hf.get("mrope_section") or \
            (hf.get("rope_scaling") or {}).get("mrope_section")
        ep_size, ep_rank = int(hf.get("ep_size", 1)), int(hf.get("ep_rank", 0))
        kw = dict(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
            rope_theta=float(hf["rope_theta"]),
            mrope_section=tuple(int(n) for n in section),
            indexer_num_heads=sa["indexer_num_heads"],
            indexer_head_dim=sa["indexer_head_dim"], topk=sa["topk"],
            num_experts=hf["num_experts"] * ep_size,
            experts_held=hf["num_experts"],
            expert_offset=hf["num_experts"] * ep_rank,
            num_experts_per_tok=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            norm_eps=float(hf["rms_norm_eps"]),
            max_seq_len=hf["max_position_embeddings"],
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)))
        kw.update(overrides)
        return KeyeVLConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "KeyeVLConfig":
        base = dict(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, num_kv_heads=2, head_dim=32,
                    mrope_section=(4, 6, 6), indexer_num_heads=4,
                    indexer_head_dim=16, topk=16, num_experts=8,
                    experts_held=8, num_experts_per_tok=2,
                    moe_intermediate_size=32, max_seq_len=256)
        base.update(kw)
        return KeyeVLConfig(**base)


# --------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------- #
def init_params(cfg: KeyeVLConfig, key: jax.Array, dtype=jnp.float32) -> Dict:
    """Seeded.  The indexer's projections are drawn so that ``I`` spreads
    over the cached tokens (a set that differs from the most recent ``topk``
    and from query to query), the norm gains away from 1 and the head
    weights ``w`` of both signs, so that leaving any piece out moves the
    logits (the tests' mutation cases rest on that)."""
    D, V, L = cfg.hidden_size, cfg.vocab_size, cfg.num_layers
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Hi, di = cfg.indexer_num_heads, cfg.indexer_head_dim
    E, F = cfg.experts_held, cfg.moe_intermediate_size
    ks = iter(jax.random.split(key, 24))

    def dense(shape, fan_in):
        return (jax.random.normal(next(ks), shape) / math.sqrt(fan_in)
                ).astype(dtype)

    def gain(*shape):
        return (1.0 + 0.2 * jax.random.normal(next(ks), shape)).astype(dtype)

    layers = {
        "in_norm": {"scale": gain(L, D)},
        "q_proj": {"kernel": dense((L, D, H * hd), D)},
        "k_proj": {"kernel": dense((L, D, KV * hd), D)},
        "v_proj": {"kernel": dense((L, D, KV * hd), D)},
        "q_norm": {"scale": gain(L, hd)},
        "k_norm": {"scale": gain(L, hd)},
        "o_proj": {"kernel": dense((L, H * hd, D), H * hd)},
        "index_q": {"kernel": dense((L, D, Hi * di), D)},
        "index_k": {"kernel": dense((L, D, di), D)},
        "index_w": {"kernel": dense((L, D, Hi), D)},
        "post_norm": {"scale": gain(L, D)},
        "router": {"kernel": (jax.random.normal(next(ks),
                                                (L, D, cfg.num_experts))
                              / math.sqrt(D)).astype(jnp.float32)},
    }
    params = {
        "embed": {"embedding": (jax.random.normal(next(ks), (V, D)) * 0.02
                                ).astype(dtype)},
        "layers": layers,
        "experts": {"gate": dense((L, E, D, F), D),
                    "up": dense((L, E, D, F), D),
                    "down": dense((L, E, F, D), F)},
        "norm_f": {"scale": gain(D)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": dense((D, V), D)}
    return params


class KeyeVLLM:
    """Model object the serving engine takes (``config`` + ``init_params``).
    Loading a checkpoint's tensors is out of scope; the training path is
    open."""

    def __init__(self, cfg: KeyeVLConfig):
        self.config = cfg

    @classmethod
    def from_hf_config(cls, hf: Dict, **overrides) -> "KeyeVLLM":
        return cls(KeyeVLConfig.from_hf(hf, **overrides))

    def init_params(self, key: jax.Array, dtype=jnp.float32):
        return init_params(self.config, key, dtype)

    def loss_fn(self, params, batch, rng):
        raise NotImplementedError(
            "keye_vl: the training path is open (ROADMAP Queue 2: the "
            "indexer's alignment loss is in no key of the config); this "
            "family is served through inference/v2 only")

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
def _norm(x, w, eps):
    return rms_norm(x.astype(jnp.float32), w.astype(jnp.float32),
                    eps).astype(x.dtype)


def mrope_tables(pos3, cfg: KeyeVLConfig):
    """Positions ``[3, T]`` → (cos, sin) ``[T, head_dim / 2]`` of a head's
    rotary pairs, each pair turned by its section's stream, and the
    indexer's ``[T, di / 2]`` by the temporal stream."""
    def angles(pos, dim):
        inv = 1.0 / (cfg.rope_theta ** (
            jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
        return pos.astype(jnp.float32)[..., None] * inv
    pairs = cfg.head_dim // 2
    stream = np.repeat(np.arange(3), cfg.mrope_section)         # [hd/2]
    of_stream = jnp.asarray(stream[None, :] == np.arange(3)[:, None],
                            jnp.float32)                        # [3, hd/2]
    per_stream = angles(pos3, cfg.head_dim)                     # [3, T, hd/2]
    assert per_stream.shape[-1] == pairs
    head = jnp.sum(per_stream * of_stream[:, None, :], axis=0)
    index = angles(pos3[0], cfg.indexer_head_dim)
    return (jnp.cos(head), jnp.sin(head)), (jnp.cos(index), jnp.sin(index))


def attention_inputs(h, lp: Dict, rope, cfg: KeyeVLConfig):
    """Normed input [T, D] → (q [T, H, hd], k, v [T, KV, hd])."""
    T = h.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cos, sin = rope
    q = (h @ lp["q_proj"]["kernel"]).reshape(T, H, hd)
    k = (h @ lp["k_proj"]["kernel"]).reshape(T, KV, hd)
    v = (h @ lp["v_proj"]["kernel"]).reshape(T, KV, hd)
    q = apply_rope_flat(_norm(q, lp["q_norm"]["scale"], cfg.norm_eps),
                        cos, sin)
    k = apply_rope_flat(_norm(k, lp["k_norm"]["scale"], cfg.norm_eps),
                        cos, sin)
    return q, k, v


def indexer_inputs(h, lp: Dict, rope, cfg: KeyeVLConfig):
    """Normed input [T, D] → (qI [T, Hi, di], kI [T, di], w [T, Hi])."""
    T = h.shape[0]
    Hi, di = cfg.indexer_num_heads, cfg.indexer_head_dim
    cos, sin = rope
    qi = apply_rope_flat((h @ lp["index_q"]["kernel"]).reshape(T, Hi, di),
                         cos, sin)
    ki = apply_rope_flat((h @ lp["index_k"]["kernel"]).reshape(T, 1, di),
                         cos, sin)[:, 0]
    return qi, ki, h @ lp["index_w"]["kernel"]


def expert_layer(h, lp: Dict, cfg: KeyeVLConfig, *, experts, layer=None,
                 valid=None):
    """Softmax router over ALL the layer's experts, the top ``k``
    renormalised, the experts held (all, or a chip's share) → ([T, D],
    pairs)."""
    from ..moe.dropless import dropless_experts, softmax_topk_route

    share = cfg.experts_held != cfg.num_experts
    with jax.named_scope("moe/route"):
        idx, weights = softmax_topk_route(h, lp["router"],
                                          cfg.num_experts_per_tok,
                                          cfg.norm_topk_prob)
    return dropless_experts(h, idx, weights, experts, valid=valid,
                            layer=layer,
                            offset=cfg.expert_offset if share else None)


def forward(params, ids, pos3, cfg: KeyeVLConfig, sets_at=None):
    """The whole-sequence forward of ONE sequence, no cache: ``ids [T]``,
    positions ``pos3 [3, T]`` (unequal streams allowed) → logits ``[T, V]``.
    Attention is dense over the causal context under the set's mask.
    ``sets_at`` (positions): also the sets of those queries in every layer,
    bool ``[L, len(sets_at), T]``, in the arithmetic of ``params``' dtype."""
    T = ids.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    x = jnp.take(params["embed"]["embedding"], ids, axis=0)
    rope, rope_i = mrope_tables(pos3, cfg)
    causal = jnp.tril(jnp.ones((T, T), jnp.bool_))
    sets = []
    for l in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        h = _norm(x, lp["in_norm"]["scale"], cfg.norm_eps)
        q, k, v = attention_inputs(h, lp, rope, cfg)
        qi, ki, w = indexer_inputs(h, lp, rope_i, cfg)
        I = jnp.einsum("tjd,sd->tjs", qi.astype(jnp.float32),
                       ki.astype(jnp.float32))
        I = jnp.sum(w.astype(jnp.float32)[..., None] * jax.nn.relu(I), axis=1)
        I = jnp.where(causal, jnp.where(I == 0, 0.0, I), -jnp.inf)
        _, best = jax.lax.top_k(I, min(cfg.topk, T))
        chosen = jnp.zeros((T, T), jnp.bool_).at[
            jnp.arange(T)[:, None], best].set(True) & causal
        if sets_at is not None:
            sets.append(chosen[jnp.asarray(sets_at)])
        qg = q.astype(jnp.float32).reshape(T, KV, H // KV, hd)
        s = jnp.einsum("tngd,snd->tngs", qg, k.astype(jnp.float32)) \
            * hd ** -0.5
        p = jax.nn.softmax(jnp.where(chosen[:, None, None, :], s, -1e30), -1)
        o = jnp.einsum("tngs,snd->tngd", p, v.astype(jnp.float32))
        x = x + o.reshape(T, H * hd).astype(x.dtype) @ lp["o_proj"]["kernel"]
        h = _norm(x, lp["post_norm"]["scale"], cfg.norm_eps)
        y, _ = expert_layer(h, lp, cfg, experts=params["experts"], layer=l)
        x = x + y
    x = _norm(x, params["norm_f"]["scale"], cfg.norm_eps)
    logits = x @ (params["embed"]["embedding"].T if cfg.tie_embeddings
                  else params["lm_head"]["kernel"])
    return logits if sets_at is None else (logits, jnp.stack(sets))


# --------------------------------------------------------------------- #
# Paged serving (models/serving.py says what each piece is handed)
# --------------------------------------------------------------------- #
def serving_family(cfg: KeyeVLConfig) -> ServingFamily:
    """K/V rows of ``num_kv_heads`` x ``head_dim`` with an index key of
    ``indexer_head_dim`` beside each, one page layer a layer.  One stack of
    layers; a step also returns the pairs per expert held ``[E]`` and, of a
    share, the pairs held elsewhere as one more entry."""
    share = cfg.experts_held != cfg.num_experts

    def embed(params, ids, pos, valid):
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"]["embedding"], ids, axis=0)
        # text: the three streams are the engine's one position
        rope, rope_i = mrope_tables(jnp.broadcast_to(pos, (3,) + pos.shape),
                                    cfg)
        return x, (rope, rope_i, valid())

    def body(experts):
        def layer(x, lp, l_idx, cache, ctx):
            rope, rope_i, valid = ctx
            dtype = x.dtype
            h = _norm(x, lp["in_norm"]["scale"], cfg.norm_eps)
            with jax.named_scope("attention/qkv"):
                q, k, v = attention_inputs(h, lp, rope, cfg)
            with jax.named_scope("attention/index_qk"):
                qi, ki, w = indexer_inputs(h, lp, rope_i, cfg)
            with jax.named_scope("attention/append"):
                cache.append(k, v, ki)
            with jax.named_scope("attention/core"):
                # inside: attention/index_score, index_select, sparse_read,
                # sparse_core (kernels/sparse_ops)
                o = cache.attend(q, qi, w, scale=cfg.head_dim ** -0.5
                                 ).astype(dtype)
            with jax.named_scope("attention/out"):
                x = x + o.reshape(o.shape[0], -1) @ lp["o_proj"]["kernel"]
            h = _norm(x, lp["post_norm"]["scale"], cfg.norm_eps)
            y, pairs = expert_layer(h, lp, cfg, experts=experts, layer=l_idx,
                                    valid=valid)
            with jax.named_scope("moe/combine"):
                return x + y, pairs

        return layer

    def stacks(params):
        yield LayerStack(params["layers"], range(cfg.num_layers),
                         body(params["experts"]), scope="layers")

    def head(params, x, pick_rows):
        with jax.named_scope("final_norm"):
            x = _norm(x, params["norm_f"]["scale"], cfg.norm_eps)
        with jax.named_scope("lm_head"):
            last = pick_rows(x)
            if cfg.tie_embeddings:
                return last @ params["embed"]["embedding"].T
            return last @ params["lm_head"]["kernel"]

    return ServingFamily(
        num_layers=cfg.num_layers, num_heads=cfg.num_heads,
        row=KVRow(cfg.num_kv_heads, cfg.head_dim, index=cfg.index),
        embed=embed, stacks=stacks, head=head,
        counts=ExpertPairs(cfg.experts_held,
                           cfg.num_layers * cfg.num_experts_per_tok,
                           elsewhere=share))
