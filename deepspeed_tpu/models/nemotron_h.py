"""Nemotron-H family with latent experts (``model_type: nemotron_h``, the
Nemotron 3 checkpoints): every layer is a mixer OR a feed-forward part
alone, ``h <- h + F(RMSNorm(h))``, in an order the published
``hybrid_override_pattern`` spells out layer by layer — ``M`` a Mamba-2
(state-space dual) mixer, ``*`` grouped-query softmax attention WITHOUT a
positional term, ``E`` sigmoid-routed experts that live in a latent narrower
than the residual, beside a shared expert on the full width; the units are
ungated, ``relu(x W_1)^2 W_2``.

This module holds the configuration (built from the published
``config.json`` keys), the seeded parameter tree, and the per-token layer
mathematics on the flat token axis ``[T, ...]``.  :func:`serving_family`
composes them into what the paged serving path asks of a model
(``models/serving.py``): K/V rows for the attention layers, a recurrent
state (:class:`~.serving.SSDState`) for the Mamba-2 ones, the pair counts;
the training path is open (``loss_fn`` raises).

The pattern is NOT periodic (``MEMEMEM*EMEMEMEM*E...``: 7 to 10 layers
between two attention layers), so the layers are cut into UNITS — ``ME`` (a
mixer and the experts behind it), or a single ``M``, ``*`` or ``E`` — and
every run of like units is one scanned stack (:func:`cut_pattern`); the
parameters are stored by stack, so no scan slices a weight.  The experts'
weights are one stack over all expert layers, handed to the grouped matmul
whole with a layer index (``moe/dropless.dropless_experts``).

A CHIP'S SHARE of an expert-parallel deployment is stated by keys of its own
and never by a width: ``n_routed_experts`` of the config are the experts
HELD, ``ep_size`` chips share a layer (the router scores ``n_routed_experts
* ep_size`` experts) and this is chip ``ep_rank``.  A sliced vocabulary is a
smaller ``vocab_size``.  The multi-token-prediction module is not held.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from .serving import (ExpertPairs, KVRow, LayerStack, SSDState,
                      ServingFamily)
from .transformer import rms_norm


def cut_pattern(pattern: str) -> List[Tuple[str, int]]:
    """The pattern string as runs of like units, in order: ``[(unit,
    count)]`` with ``unit`` one of ``"ME"``, ``"M"``, ``"*"``, ``"E"``
    (``MEMEMEM*EME`` -> ME x 3, M, *, E, ME)."""
    units, i = [], 0
    while i < len(pattern):
        unit = "ME" if pattern[i:i + 2] == "ME" else pattern[i]
        units.append(unit)
        i += len(unit)
    runs: List[Tuple[str, int]] = []
    for unit in units:
        if runs and runs[-1][0] == unit:
            runs[-1] = (unit, runs[-1][1] + 1)
        else:
            runs.append((unit, 1))
    return runs


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    pattern: str = "MEMEMEM*EME"
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    num_experts: int = 512              # what the router scores
    experts_held: int = 512             # of them, held here ...
    expert_offset: int = 0              # ... from this one on
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    norm_eps: float = 1e-5
    max_seq_len: int = 262144

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def state(self) -> SSDState:
        return SSDState(
            num_layers=self.count("M"), heads=self.mamba_num_heads,
            head_dim=self.mamba_head_dim, state_dim=self.ssm_state_size,
            groups=self.n_groups, conv_kernel=self.conv_kernel,
            chunk=self.chunk_size)

    @staticmethod
    def from_hf(hf: Dict, **overrides) -> "NemotronHConfig":
        """From the published ``config.json`` keys, plus the share's own
        (``ep_size``, ``ep_rank``; absent: the whole layer is held)."""
        pattern = hf["hybrid_override_pattern"]
        if len(pattern) != hf["num_hidden_layers"] or set(pattern) - set("ME*"):
            raise NotImplementedError(
                f"nemotron_h: a pattern of {hf['num_hidden_layers']} layers "
                f"each M, E or * (a dense MLP layer, '-', is not served): "
                f"{pattern!r}")
        if hf.get("num_nextn_predict_layers", 0):
            raise NotImplementedError(
                "nemotron_h: the multi-token-prediction module is not held "
                "(num_nextn_predict_layers must be 0): a module TRAINS as a "
                "second loss in models/joyai_flash.py; serving one as a "
                "self-drafter is not written (ROADMAP R8)")
        if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1 \
                or hf.get("n_shared_experts", 1) != 1:
            raise NotImplementedError(
                "nemotron_h: one router group (n_group 1, topk_group 1) and "
                "one shared expert")
        if hf.get("mlp_hidden_act", "relu2") != "relu2" \
                or hf.get("mamba_hidden_act", "silu") != "silu":
            raise NotImplementedError(
                "nemotron_h: mlp_hidden_act relu2 and mamba_hidden_act silu")
        if any(hf.get(k, False) for k in ("use_bias", "mamba_proj_bias",
                                          "mlp_bias", "attention_bias")) \
                or not hf.get("use_conv_bias", True) \
                or hf.get("tie_word_embeddings", False):
            raise NotImplementedError(
                "nemotron_h: no bias but the convolution's, an untied head")
        ep_size, ep_rank = int(hf.get("ep_size", 1)), int(hf.get("ep_rank", 0))
        kw = dict(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            pattern=pattern, num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
            mamba_num_heads=hf["mamba_num_heads"],
            mamba_head_dim=hf["mamba_head_dim"], n_groups=hf["n_groups"],
            ssm_state_size=hf["ssm_state_size"],
            conv_kernel=hf["conv_kernel"], chunk_size=hf["chunk_size"],
            num_experts=hf["n_routed_experts"] * ep_size,
            experts_held=hf["n_routed_experts"],
            expert_offset=hf["n_routed_experts"] * ep_rank,
            num_experts_per_tok=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            moe_latent_size=hf["moe_latent_size"],
            moe_shared_expert_intermediate_size=hf[
                "moe_shared_expert_intermediate_size"],
            routed_scaling_factor=float(hf["routed_scaling_factor"]),
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            norm_eps=float(hf["layer_norm_epsilon"]),
            max_seq_len=hf["max_position_embeddings"])
        kw.update(overrides)
        return NemotronHConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "NemotronHConfig":
        """Toy widths that keep every ratio: heads a multiple of the groups
        (4 a group, stored 4 along the lanes), experts a multiple of 4, a
        latent narrower than the residual, an expert width that is no power
        of two."""
        base = dict(vocab_size=256, hidden_size=64, pattern="MEMEMEM*EME",
                    num_heads=4, num_kv_heads=2, head_dim=16,
                    mamba_num_heads=8, mamba_head_dim=32, n_groups=2,
                    ssm_state_size=16, chunk_size=8, num_experts=16,
                    experts_held=16, num_experts_per_tok=3,
                    moe_intermediate_size=24, moe_latent_size=32,
                    moe_shared_expert_intermediate_size=48, max_seq_len=256)
        base.update(kw)
        return NemotronHConfig(**base)


# --------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------- #
def init_params(cfg: NemotronHConfig, key: jax.Array, dtype=jnp.float32
                ) -> Dict:
    """Seeded.  ``A_log`` and ``dt_bias`` are drawn so that a token's decay
    ``exp(-delta exp(A_log))`` lies around 0.5-0.99, and ``D``, the
    convolution, its bias, the norm weights and the router's bias far enough
    from 0 (or 1) that leaving any one out moves the logits (the tests'
    mutation cases rest on that).  ``stacks`` holds one tree a run of like
    units (:func:`cut_pattern`), each leaf stacked over the run."""
    D, V = cfg.hidden_size, cfg.vocab_size
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Hm, Ci, K = cfg.mamba_num_heads, cfg.d_inner, cfg.conv_kernel
    Cc = cfg.state.conv_channels
    E, F, R = cfg.experts_held, cfg.moe_intermediate_size, cfg.moe_latent_size
    Fs = cfg.moe_shared_expert_intermediate_size
    ks = iter(jax.random.split(key, 400))

    def dense(shape, fan_in):
        return (jax.random.normal(next(ks), shape) / math.sqrt(fan_in)
                ).astype(dtype)

    def around(center, spread, *shape):
        return (center + spread * jax.random.normal(next(ks), shape)
                ).astype(dtype)

    def uniform(lo, hi, *shape):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)

    def mamba(n):
        return {
            "norm": {"scale": around(1.0, 0.3, n, D)},
            # columns [z | x | B | C | dt]
            "in_proj": {"kernel": dense((n, D, Ci + Cc + Hm), D)},
            "conv": {"kernel": around(0, 1 / math.sqrt(K), n, K, Cc),
                     "bias": around(0, 0.3, n, Cc)},
            "A_log": jnp.log(uniform(0.05, 1.0, n, Hm)).astype(dtype),
            "dt_bias": uniform(-1.0, 1.0, n, Hm).astype(dtype),
            "D": around(1.0, 0.3, n, Hm),
            "gnorm": {"scale": around(1.0, 0.3, n, Ci)},
            "out_proj": {"kernel": dense((n, Ci, D), Ci)},
        }

    def attn(n):
        return {
            "norm": {"scale": around(1.0, 0.3, n, D)},
            "q_proj": {"kernel": dense((n, D, H * hd), D)},
            "k_proj": {"kernel": dense((n, D, KV * hd), D)},
            "v_proj": {"kernel": dense((n, D, KV * hd), D)},
            "o_proj": {"kernel": dense((n, H * hd, D), H * hd)},
        }

    def moe(n):
        return {
            "norm": {"scale": around(1.0, 0.3, n, D)},
            "router": {"kernel": (jax.random.normal(
                next(ks), (n, D, cfg.num_experts)) / math.sqrt(D)
            ).astype(jnp.float32),
                "bias": 0.1 * jax.random.normal(
                    next(ks), (n, cfg.num_experts), jnp.float32)},
            "latent_down": {"kernel": dense((n, D, R), D)},
            "latent_up": {"kernel": dense((n, R, D), R)},
            "shared": {"up": dense((n, D, Fs), D),
                       "down": dense((n, Fs, D), Fs)},
        }

    make = {"M": mamba, "*": attn, "E": moe}
    nE = cfg.count("E")
    return {
        "embed": {"embedding": jax.random.normal(next(ks), (V, D)
                                                 ).astype(dtype)},
        "stacks": [{kind: make[kind](n) for kind in unit}
                   for unit, n in cut_pattern(cfg.pattern)],
        # squared ReLU doubles a unit's spread: drawn for outputs of O(1)
        "experts": {"up": dense((nE, E, R, F), R),
                    "down": dense((nE, E, F, R), 3 * F)},
        "norm_f": {"scale": around(1.0, 0.3, D)},
        "lm_head": {"kernel": dense((D, V), D)},
    }


class NemotronHLM:
    """Model object the serving engine takes (``config`` +
    ``init_params``).  Loading a checkpoint's tensors is out of scope; the
    training path is open."""

    def __init__(self, cfg: NemotronHConfig):
        self.config = cfg

    @classmethod
    def from_hf_config(cls, hf: Dict, **overrides) -> "NemotronHLM":
        return cls(NemotronHConfig.from_hf(hf, **overrides))

    def init_params(self, key: jax.Array, dtype=jnp.float32):
        return init_params(self.config, key, dtype)

    def loss_fn(self, params, batch, rng):
        raise NotImplementedError(
            "nemotron_h: the training path is open (the backward of the "
            "chunked state-space-dual form and of the latent experts); this "
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
def norm(x, p: Dict, eps: float):
    """``x / rms(x) * w`` in float32, back in ``x``'s dtype."""
    return rms_norm(x.astype(jnp.float32), p["scale"].astype(jnp.float32),
                    eps).astype(x.dtype)


def mamba(x, lp: Dict, state_layer, state, cfg: NemotronHConfig):
    """``[z | xBC | dt] = u W_in``; the convolution, the recurrence and the
    gated group norm through the sequence's slot; ``W_out``."""
    Ci, Hm = cfg.d_inner, cfg.mamba_num_heads
    with jax.named_scope("attention/ssd_in"):
        zxd = norm(x, lp["norm"], cfg.norm_eps) @ lp["in_proj"]["kernel"]
        z, xBC, dt = zxd[:, :Ci], zxd[:, Ci:-Hm], zxd[:, -Hm:]
    y = state(state_layer, xBC, dt, z, lp["conv"]["kernel"],
              lp["conv"]["bias"], lp["dt_bias"],
              jnp.exp(lp["A_log"].astype(jnp.float32)), lp["D"],
              lp["gnorm"]["scale"], cfg.norm_eps)
    with jax.named_scope("attention/ssd_out"):
        return x + y.astype(x.dtype) @ lp["out_proj"]["kernel"]


def attention(x, lp: Dict, cache, cfg: NemotronHConfig):
    """Grouped-query softmax attention, no positional term."""
    T = x.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    h = norm(x, lp["norm"], cfg.norm_eps)
    with jax.named_scope("attention/qkv"):
        q = (h @ lp["q_proj"]["kernel"]).reshape(T, H, hd)
        k = (h @ lp["k_proj"]["kernel"]).reshape(T, KV, hd)
        v = (h @ lp["v_proj"]["kernel"]).reshape(T, KV, hd)
    with jax.named_scope("attention/core"):
        o = cache(q, k, v, scale=hd ** -0.5).astype(x.dtype)
    with jax.named_scope("attention/out"):
        return x + o.reshape(T, H * hd) @ lp["o_proj"]["kernel"]


# --------------------------------------------------------------------- #
# Paged serving (models/serving.py says what each piece is handed)
# --------------------------------------------------------------------- #
def serving_family(cfg: NemotronHConfig) -> ServingFamily:
    """K/V rows of ``num_kv_heads`` x ``head_dim`` in one page layer an
    attention layer; a state-space-dual state in every Mamba-2 layer.  One
    stack a run of like units, ``LayerStack.layers`` the run's place among
    the units; a step also returns the pairs per expert held ``[E]`` and,
    of a share, the pairs held elsewhere as one more entry."""
    from ..moe.dropless import latent_moe_block

    share = cfg.experts_held != cfg.num_experts

    def embed(params, ids, pos, valid):
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"]["embedding"], ids, axis=0)
        return x, valid()

    def body(unit: str, first_unit: int, base: Dict[str, int], experts):
        """The body of a run of ``unit`` that starts at unit ``first_unit``
        with ``base[kind]`` layers of each kind before it."""
        def run(x, lp, u_idx, cache, valid, state):
            at = {kind: base[kind] + u_idx - first_unit for kind in unit}
            pairs = None
            for kind in unit:
                if kind == "M":
                    x = mamba(x, lp["M"], at["M"], state, cfg)
                elif kind == "*":
                    x = attention(x, lp["*"], cache.at(at["*"]), cfg)
                else:
                    ml = lp["E"]
                    y, pairs = latent_moe_block(
                        norm(x, ml["norm"], cfg.norm_eps), ml,
                        k=cfg.num_experts_per_tok,
                        scaling=cfg.routed_scaling_factor,
                        renormalise=cfg.norm_topk_prob,
                        offset=cfg.expert_offset if share else None,
                        valid=valid, experts=experts, layer=at["E"])
                    x = x + y
            return x, pairs
        return run

    def stacks(params):
        base = {"M": 0, "*": 0, "E": 0}
        first = 0
        for (unit, n), tree in zip(cut_pattern(cfg.pattern),
                                   params["stacks"]):
            yield LayerStack(tree, range(first, first + n),
                             body(unit, first, dict(base), params["experts"]),
                             scope="layers")
            first += n
            for kind in unit:
                base[kind] += n

    def head(params, x, pick_rows):
        with jax.named_scope("final_norm"):
            x = norm(x, params["norm_f"], cfg.norm_eps)
        with jax.named_scope("lm_head"):
            return pick_rows(x) @ params["lm_head"]["kernel"]

    nE = cfg.count("E")
    return ServingFamily(
        num_layers=cfg.num_layers, num_heads=cfg.num_heads,
        row=KVRow(cfg.num_kv_heads, cfg.head_dim),
        embed=embed, stacks=stacks, head=head,
        counts=ExpertPairs(cfg.experts_held, nE * cfg.num_experts_per_tok,
                           elsewhere=share),
        state=cfg.state, page_layer_count=cfg.count("*"))
