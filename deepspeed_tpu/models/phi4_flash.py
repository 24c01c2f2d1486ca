"""Phi-4-mini-flash-reasoning family (``model_type: phi4flash``): the SambaY
decoder-hybrid-decoder (Ren et al., arXiv 2507.06607) with differential
attention.  Pre-norm LayerNorm (weight and bias) blocks, a SwiGLU MLP after
every mixer, NO positional encoding of any kind, tied embedding.  With ``L``
layers and ``M = L // 2``, the mixer of layer ``l`` (0-based) is

* ``l`` even, ``l <= M``: a **Mamba-1 selective scan** (``kernels/ssm_ops``);
  layer ``M`` also hands its scan output ``m`` (before the ``z`` gate) to the
  gated memory units;
* ``l`` odd, ``l < M``: **differential attention over a window** of
  ``sliding_window`` tokens — a ring of rows in the sequence's slot
  (``serving.WindowRing``), no page layer;
* ``l = M + 1``: differential attention, **full causal**: the model's ONE
  page layer;
* ``l`` even, ``l > M + 1``: a **gated memory unit**, ``(m * SiLU(h W_g))
  W_o``, ``m`` layer ``M``'s output of the same token;
* ``l`` odd, ``l > M + 1``: **cross attention** — a query only, against the
  rows layer ``M + 1`` cached (its own lambda vectors, sub-norm and output
  projection); it appends nothing.

Differential attention: ``q`` as ``H`` heads of ``hd``, ``k``, ``v`` as
``KV``; ``q1_i = q[2i]``, ``q2_i = q[2i+1]``, ``k1_j = k[2j]``, ``k2_j =
k[2j+1]``, ``V_j = [v[2j] | v[2j+1]]``, ``j = i // (H / KV)``; ``A1_i =
softmax(q1_i k1_j^T / sqrt(hd)) V_j``, ``A2_i`` likewise from ``q2``,
``k2``; ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
``lambda_init = 0.8 - 0.6 exp(-0.3 l)``; ``o_i = (1 - lambda_init)
RMSNorm(A1_i - lambda A2_i)``.  A cached token is therefore ``KV / 2`` K
rows ``[k1 | k2]`` and as many V rows ``[v1 | v2]`` of ``2 hd``
(:func:`serving_family`: ``KVRow.packed(KV / 2, 2 hd)``), and the two score
sets are the cache's differential read (``attend_pair``).

This module holds the configuration (from the published ``config.json``
keys), the seeded parameter tree and the per-token layer mathematics on the
flat token axis; the training path is open (``loss_fn`` raises).  The
forward runs every token through every layer: the prefill that stops at
layer ``M + 1`` for all but a prompt's last token is left open (ROADMAP R2).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp

from .serving import (KVRow, LayerStack, SelectiveScanState, ServingFamily,
                      WindowRing)
from .transformer import rms_norm


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_layers: int = 32
    num_heads: int = 40
    num_kv_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    norm_eps: float = 1e-5
    max_seq_len: int = 262144
    # what the catalog's config lacks and the family's modelling code fixes
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    #: rows of a page of a window layer's ring (divides ``sliding_window``)
    ring_page: int = 64

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return -(-self.hidden_size // 16)

    @property
    def mid(self) -> int:               # the layer that hands ``m`` over
        return self.num_layers // 2

    @property
    def self_pairs(self) -> int:        # (scan, window attention) pairs
        return self.mid // 2

    @property
    def cross_pairs(self) -> int:       # (memory unit, cross attention)
        return (self.num_layers - self.mid - 2) // 2

    @property
    def pairs(self) -> int:             # K/V pairs of a cached token
        return self.num_kv_heads // 2

    def lambda_init(self, layer):
        return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))

    @staticmethod
    def from_hf(hf: Dict, **overrides) -> "Phi4FlashConfig":
        if hf.get("mb_per_layer", 2) != 2:
            raise NotImplementedError(
                "phi4flash: mb_per_layer != 2 (a scan layer every other "
                "layer is the one layout this family is written for)")
        depth = hf["num_hidden_layers"]
        if depth % 4 or depth < 8:
            raise NotImplementedError(
                f"phi4flash: num_hidden_layers {depth} is not whole (scan, "
                f"attention) pairs on both sides of the hand-over")
        if hf["num_key_value_heads"] % 2 or \
                hf["num_attention_heads"] % hf["num_key_value_heads"]:
            raise NotImplementedError(
                "phi4flash: differential attention pairs the K/V heads")
        if hf.get("mlp_bias", False) or hf.get("lm_head_bias", False) \
                or not hf.get("tie_word_embeddings", True):
            raise NotImplementedError(
                "phi4flash: mlp_bias, lm_head_bias or an untied head")
        kw = dict(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"], num_layers=depth,
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            sliding_window=hf["sliding_window"],
            mb_per_layer=hf.get("mb_per_layer", 2),
            norm_eps=float(hf["layer_norm_eps"]),
            max_seq_len=hf["max_position_embeddings"])
        kw.update(overrides)
        return Phi4FlashConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "Phi4FlashConfig":
        """8 layers = [scan, window, scan, window, scan (hands ``m``), full,
        memory unit, cross]: every kind and both hand-overs."""
        base = dict(vocab_size=97, hidden_size=64, intermediate_size=96,
                    num_layers=8, num_heads=8, num_kv_heads=4,
                    sliding_window=8, ring_page=4, max_seq_len=256)
        base.update(kw)
        return Phi4FlashConfig(**base)


# --------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------- #
def init_params(cfg: Phi4FlashConfig, key: jax.Array, dtype=jnp.float32
                ) -> Dict:
    """Seeded.  ``A_log`` and ``dt_bias`` are drawn so that a token's decay
    ``exp(-delta exp(A_log))`` lies around 0.5-0.99, and ``D``, the
    convolution, its bias, the norms and the lambda vectors far enough from
    0 (or 1) that leaving any one out moves the logits (the tests' mutation
    cases rest on that).  Every leaf is STORED in ``dtype`` (the serving
    engine casts the tree) and the small ones are used in float32."""
    D, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Ci, N, K, R = cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dt_rank
    ks = iter(jax.random.split(key, 200))

    def dense(shape, fan_in, gain=1.0):
        return (gain * jax.random.normal(next(ks), shape)
                / math.sqrt(fan_in)).astype(dtype)

    def around(center, spread, *shape):
        return (center + spread * jax.random.normal(next(ks), shape)
                ).astype(dtype)

    def ln(n):
        return {"scale": around(1.0, 0.3, n, D), "bias": around(0, 0.1, n, D)}

    def mlp(n):
        return {"ln": ln(n), "w1": {"kernel": dense((n, D, 2 * F), D)},
                "w2": {"kernel": dense((n, F, D), F)}}

    def mamba(n):
        return {
            "ln": ln(n),
            "in_proj": {"kernel": dense((n, D, 2 * Ci), D)},
            "conv": {"kernel": around(0, 1 / math.sqrt(K), n, K, Ci),
                     "bias": around(0, 0.3, n, Ci)},
            "x_proj": {"kernel": dense((n, Ci, R + 2 * N), Ci, 2.0)},
            "dt_proj": {"kernel": dense((n, R, Ci), R, 0.5),
                        "bias": jax.random.uniform(
                            next(ks), (n, Ci), jnp.float32, -2.0, 0.0
                        ).astype(dtype)},
            # stored channels-minor, as the state is
            "A_log": jnp.log(jax.random.uniform(
                next(ks), (n, N, Ci), jnp.float32, 0.05, 1.0)).astype(dtype),
            "D": around(1.0, 0.3, n, Ci),
            "out_proj": {"kernel": dense((n, Ci, D), Ci)},
        }

    def attn(n, with_kv=True):
        width = H * hd + (2 * KV * hd if with_kv else 0)
        return {
            "ln": ln(n),
            "wqkv": {"kernel": dense((n, D, width), D),
                     "bias": around(0, 0.1, n, width)},
            # four vectors of hd a layer: lq1, lk1, lq2, lk2 (N(0, 0.1) in
            # the family's code; here wide enough that lambda differs from
            # lambda_init by a tenth or more)
            "lam": around(0, 0.3, n, 4, hd),
            "subln": around(1.0, 0.3, n, 2 * hd),
            "wo": {"kernel": dense((n, H * hd, D), H * hd),
                   "bias": around(0, 0.1, n, D)},
        }

    def gmu(n):
        return {"ln": ln(n), "in_proj": {"kernel": dense((n, D, Ci), D)},
                "out_proj": {"kernel": dense((n, Ci, D), Ci)}}

    def pairs(n, first, second):
        return {"first": first, "second": second, "mlp": (mlp(n), mlp(n))}

    P, X = cfg.self_pairs, cfg.cross_pairs
    return {
        "embed": {"embedding": jax.random.normal(next(ks), (V, D)
                                                 ).astype(dtype)},
        "self": pairs(P, mamba(P), attn(P)),
        "mid": pairs(1, mamba(1), attn(1)),
        "cross": pairs(X, gmu(X), attn(X, with_kv=False)),
        "norm_f": {"scale": around(1.0, 0.3, D), "bias": around(0, 0.1, D)},
    }


class Phi4FlashLM:
    """Model object the serving engine takes (``config`` +
    ``init_params``).  Loading a checkpoint's tensors is out of scope; the
    training path is open."""

    def __init__(self, cfg: Phi4FlashConfig):
        self.config = cfg

    @classmethod
    def from_hf_config(cls, hf: Dict, **overrides) -> "Phi4FlashLM":
        return cls(Phi4FlashConfig.from_hf(hf, **overrides))

    def init_params(self, key: jax.Array, dtype=jnp.float32):
        return init_params(self.config, key, dtype)

    def loss_fn(self, params, batch, rng):
        raise NotImplementedError(
            "phi4flash: the training path is open (the backward of the "
            "selective scan and of the windowed differential read); this "
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
def layer_norm(x, p: Dict, eps: float):
    """LayerNorm with weight and bias, in float32, back in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


def mlp(x, lp: Dict, cfg: Phi4FlashConfig):
    """``x + (SiLU(g) * y) W_2`` with ``[g | y] = LN(x) W_1``."""
    gy = layer_norm(x, lp["ln"], cfg.norm_eps) @ lp["w1"]["kernel"]
    F = cfg.intermediate_size
    return x + (jax.nn.silu(gy[:, :F]) * gy[:, F:]) @ lp["w2"]["kernel"]


def scan_projections(lp: Dict, cfg: Phi4FlashConfig, dtype):
    """``proj`` of ``ssm_ops.ssm_mix``: the convolved input → (delta, B,
    C), float32."""
    R, N = cfg.dt_rank, cfg.d_state

    def proj(x):
        rbc = (x.astype(dtype) @ lp["x_proj"]["kernel"]).astype(jnp.float32)
        delta = jax.nn.softplus(
            (rbc[:, :R].astype(dtype) @ lp["dt_proj"]["kernel"]
             ).astype(jnp.float32)
            + lp["dt_proj"]["bias"].astype(jnp.float32))
        return delta, rbc[:, R:R + N], rbc[:, R + N:]

    return proj


def mamba(x, lp: Dict, state_layer, state, cfg: Phi4FlashConfig):
    """→ (the mixer's output [T, D], the scan's output ``y`` [T, Ci] before
    the ``z`` gate)."""
    dtype = x.dtype
    with jax.named_scope("attention/ssm_in"):
        uz = layer_norm(x, lp["ln"], cfg.norm_eps) @ lp["in_proj"]["kernel"]
        u, z = uz[:, :cfg.d_inner], uz[:, cfg.d_inner:]
    y = state(state_layer, u, lp["conv"]["kernel"], lp["conv"]["bias"],
              scan_projections(lp, cfg, dtype),
              -jnp.exp(lp["A_log"].astype(jnp.float32)), lp["D"])
    with jax.named_scope("attention/ssm_out"):
        out = (y * jax.nn.silu(z.astype(jnp.float32))).astype(dtype) \
            @ lp["out_proj"]["kernel"]
    return out, y.astype(dtype)


def attention_inputs(x, lp: Dict, cfg: Phi4FlashConfig, with_kv=True):
    """The layer's input [T, D] → (q1, q2 [T, H/2, hd], K rows [T, KV/2,
    2hd] = ``[k1 | k2]``, V rows ``[v1 | v2]``); a query-only layer gets
    ``None`` rows."""
    T = x.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    qkv = layer_norm(x, lp["ln"], cfg.norm_eps) @ lp["wqkv"]["kernel"] \
        + lp["wqkv"]["bias"]
    q = qkv[:, :H * hd].reshape(T, H // 2, 2, hd)
    if not with_kv:
        return q[:, :, 0], q[:, :, 1], None, None
    k = qkv[:, H * hd:(H + KV) * hd].reshape(T, KV // 2, 2 * hd)
    v = qkv[:, (H + KV) * hd:].reshape(T, KV // 2, 2 * hd)
    return q[:, :, 0], q[:, :, 1], k, v


def differential_output(a1, a2, lp: Dict, layer, cfg: Phi4FlashConfig, dtype):
    """``(1 - lambda_init) RMSNorm(A1 - lambda A2)`` a head, then ``W_o``."""
    lam = lp["lam"].astype(jnp.float32)
    init = cfg.lambda_init(layer)
    lam_full = jnp.exp(jnp.sum(lam[0] * lam[1])) \
        - jnp.exp(jnp.sum(lam[2] * lam[3])) + init
    o = a1.astype(jnp.float32) - lam_full * a2.astype(jnp.float32)
    o = (1.0 - init) * rms_norm(o, lp["subln"].astype(jnp.float32),
                                cfg.norm_eps)
    return o.astype(dtype).reshape(o.shape[0], -1) @ lp["wo"]["kernel"] \
        + lp["wo"]["bias"]


def memory_unit(x, m, lp: Dict, cfg: Phi4FlashConfig):
    g = layer_norm(x, lp["ln"], cfg.norm_eps) @ lp["in_proj"]["kernel"]
    return (m * jax.nn.silu(g)) @ lp["out_proj"]["kernel"]


# --------------------------------------------------------------------- #
# Paged serving (models/serving.py says what each piece is handed)
# --------------------------------------------------------------------- #
def serving_family(cfg: Phi4FlashConfig) -> ServingFamily:
    """ONE page layer (layer ``M + 1``) of ``KV / 2`` row pairs ``2 hd``
    wide, read by itself and every cross-attention layer — a token STORED in
    its own bytes (``KVRow.packed``: the published 10 pairs of 128 tile no
    sublane tile, so 5 lie along the lanes of a row, 2 K rows and 2 V rows
    of 640 = 5,120 B in bf16, in the page layer and in every ring; a pair
    count that tiles is stored as it is); a selective-scan
    state in ``M / 2 + 1`` layers; a ring of ``sliding_window`` rows in
    ``M / 2`` window layers.  Three stacks: (scan, window attention) pairs,
    the hand-over pair, (memory unit, cross attention) pairs.  The residual
    carry is the pair ``(x, m)``: ``m`` is zeros until layer ``M`` makes
    it."""
    M, P = cfg.mid, cfg.self_pairs
    attn = dict(scale=cfg.head_dim ** -0.5)
    pairs = dict(pairs=cfg.pairs)

    def embed(params, ids, pos, valid):
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"]["embedding"], ids, axis=0)
        return (x, jnp.zeros((x.shape[0], cfg.d_inner), x.dtype)), None

    def mlp_layer(x, lp):
        with jax.named_scope("mlp"):
            return mlp(x, lp, cfg)

    def self_pair(carry, lp, p_idx, cache, ctx, state):
        x, m = carry
        out, _ = mamba(x, lp["first"], p_idx, state, cfg)
        x = mlp_layer(x + out, lp["mlp"][0])
        al, layer = lp["second"], 2 * p_idx + 1
        with jax.named_scope("attention/window"):
            q1, q2, k, v = attention_inputs(x, al, cfg)
            a1, a2 = cache.window(p_idx).pair(q1, q2, k, v, **pairs, **attn)
            x = x + differential_output(a1, a2, al, layer, cfg, x.dtype)
        return mlp_layer(x, lp["mlp"][1]), m

    def mid_pair(carry, lp, _, cache, ctx, state):
        x, _m = carry
        out, m = mamba(x, lp["first"], P, state, cfg)
        x = mlp_layer(x + out, lp["mlp"][0])
        al = lp["second"]
        with jax.named_scope("attention/shared"):
            q1, q2, k, v = attention_inputs(x, al, cfg)
            page = cache.at(0)
            page.append(k, v)
            a1, a2 = page.attend_pair(q1, q2, **pairs, **attn)
            x = x + differential_output(a1, a2, al, M + 1, cfg, x.dtype)
        return mlp_layer(x, lp["mlp"][1]), m

    def cross_pair(carry, lp, c_idx, cache, ctx, state):
        x, m = carry
        with jax.named_scope("attention/gmu"):
            x = x + memory_unit(x, m, lp["first"], cfg)
        x = mlp_layer(x, lp["mlp"][0])
        al, layer = lp["second"], M + 3 + 2 * c_idx
        with jax.named_scope("attention/shared"):
            q1, q2, _, _ = attention_inputs(x, al, cfg, with_kv=False)
            a1, a2 = cache.at(0).attend_pair(q1, q2, **pairs, **attn)
            x = x + differential_output(a1, a2, al, layer, cfg, x.dtype)
        return mlp_layer(x, lp["mlp"][1]), m

    def stacks(params):
        yield LayerStack(params["self"], range(P), self_pair, scope="layers")
        yield LayerStack(params["mid"], range(1), mid_pair, scope="layers")
        yield LayerStack(params["cross"], range(cfg.cross_pairs), cross_pair,
                         scope="layers")

    def head(params, carry, pick_rows):
        x, _ = carry
        with jax.named_scope("final_norm"):
            x = layer_norm(x, params["norm_f"], cfg.norm_eps)
        with jax.named_scope("lm_head"):
            return pick_rows(x) @ params["embed"]["embedding"].T

    return ServingFamily(
        num_layers=cfg.num_layers, num_heads=cfg.num_heads,
        row=KVRow.packed(cfg.pairs, 2 * cfg.head_dim),
        embed=embed, stacks=stacks, head=head,
        state=SelectiveScanState(num_layers=P + 1, channels=cfg.d_inner,
                                 state_dim=cfg.d_state,
                                 conv_kernel=cfg.d_conv),
        window=WindowRing(num_layers=P, window=cfg.sliding_window,
                          page=cfg.ring_page),
        page_layer_count=1, page_readers=(1 + cfg.cross_pairs,))
