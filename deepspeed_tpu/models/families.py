"""Per-architecture HF model families beyond the Llama recipe.

Reference analogues: ``module_inject/containers/`` (gpt2/opt/bloom/falcon
per-arch policies) and ``inference/v2/model_implementations/`` (falcon, phi,
qwen, opt per-arch model classes).  Round 1 ran these families on the Llama
compute path with a warning; this module implements the EXACT architectures —
LayerNorm with bias, learned/ALiBi positions, fused-QKV layouts, parallel
attention blocks, partial rotary — verified by logit-parity tests against HF
transformers (tests/unit/test_hf_parity.py).

One generalized transformer (:class:`UniversalCausalLM`) is driven by
:class:`ArchConfig` knobs rather than one class per architecture — on TPU the
differences are pure math selection, and a single stacked-layer scan keeps
XLA compilation shared across families.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .serving import KVRow, LayerStack, ServingFamily
from .transformer import _proj, apply_rope_flat, rms_norm, rope_at


@dataclasses.dataclass
class ArchConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: int = 12
    max_seq_len: int = 1024
    #: "rope" | "learned" | "alibi"
    pos: str = "learned"
    pos_offset: int = 0             # OPT stores positions at index pos+2
    rope_theta: float = 10000.0
    rope_pct: float = 1.0           # phi: rotary on a fraction of head_dim
    #: "neox" (half-split halves, llama/falcon/phi) | "gptj" (interleaved
    #: pairs, rotate_every_two)
    rope_style: str = "neox"
    #: "layernorm" | "rmsnorm"
    norm: str = "layernorm"
    norm_eps: float = 1e-5
    #: "gelu" | "relu" | "silu_glu"
    mlp: str = "gelu"
    gelu_exact: bool = False        # falcon uses erf-gelu; gpt2/bloom/phi tanh
    parallel_attn: bool = False     # falcon/phi: attn + mlp from the same input
    #: falcon-style ALiBi: bias added before 1/sqrt(hd) scaling, slope*pos in
    #: bf16 (bloom adds the unscaled f32 bias after scaling)
    alibi_scaled: bool = False
    dual_ln: bool = False           # falcon new-arch: separate ln_attn/ln_mlp
    qkv_bias: bool = True
    out_bias: bool = True           # o_proj bias
    mlp_bias: Optional[bool] = None  # fc biases (None → follow out_bias)
    embed_layernorm: bool = False   # bloom
    tie_embeddings: bool = True
    lm_head_bias: bool = False      # gptj/phi carry an lm-head bias

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def rotary_dim(self) -> int:
        rd = int(self.head_dim * self.rope_pct)
        return rd - rd % 2


# --------------------------------------------------------------------- #
# Math blocks
# --------------------------------------------------------------------- #
def layer_norm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale + bias).astype(x.dtype)


def _norm(x, p, cfg: ArchConfig):
    if cfg.norm == "rmsnorm":
        return rms_norm(x, p["scale"], cfg.norm_eps)
    return layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)


def alibi_slopes(num_heads: int) -> np.ndarray:
    """Standard ALiBi slopes (bloom/modeling_bloom.py build_alibi_tensor)."""
    closest = 2 ** math.floor(math.log2(num_heads))
    base = 2.0 ** (-(2.0 ** -(math.log2(closest) - 3)))
    slopes = [base ** (i + 1) for i in range(closest)]
    if closest != num_heads:
        extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * closest) - 3)))
        extra = [extra_base ** (2 * i + 1)
                 for i in range(num_heads - closest)]
        slopes += extra
    return np.asarray(slopes, np.float32)


def _rope_partial(x, cos, sin, rotary_dim, style="neox"):
    """Rope on the first ``rotary_dim`` features of each head.

    "neox": rotate split halves (llama/falcon/phi).  "gptj": rotate
    interleaved even/odd pairs (rotate_every_two)."""
    rot, passthrough = x[..., :rotary_dim], x[..., rotary_dim:]
    c = cos[None, :, None, :].astype(x.dtype)
    s = sin[None, :, None, :].astype(x.dtype)
    if style == "gptj":
        x1, x2 = rot[..., 0::2], rot[..., 1::2]
        r1 = x1 * c - x2 * s
        r2 = x1 * s + x2 * c
        rot = jnp.stack([r1, r2], axis=-1).reshape(rot.shape)
    else:
        x1, x2 = jnp.split(rot, 2, axis=-1)
        rot = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([rot, passthrough], axis=-1) \
        if rotary_dim < x.shape[-1] else rot


def _attention(q, k, v, cfg: ArchConfig, alibi: Optional[jnp.ndarray]):
    B, S, H, hd = q.shape
    if alibi is None and S >= 128 and jax.default_backend() == "tpu":
        # non-alibi families ride the Pallas flash kernel; the O(S²) f32
        # score materialization below is the CPU/short-seq fallback only
        from ..ops.transformer.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=True)
    KV = k.shape[2]
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    if alibi is not None:
        # ALiBi (bloom build_alibi_tensor): slope_h * k_pos — equivalent to
        # slope*(k_pos - q_pos) under softmax's per-row shift invariance.
        if cfg.alibi_scaled:
            # falcon variant (modeling_falcon.py:397-398): the bias is added
            # BEFORE the 1/sqrt(hd) scaling and slope*pos is computed in bf16
            bias = (alibi.astype(jnp.bfloat16)[None, :, None, None] *
                    jnp.arange(S, dtype=jnp.bfloat16)[None, None, None, :]
                    ).astype(jnp.float32) / math.sqrt(hd)
        else:
            bias = alibi[None, :, None, None] * \
                jnp.arange(S, dtype=jnp.float32)[None, None, None, :]
        scores = scores + bias
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


# --------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------- #
def universal_forward(params: Dict, tokens: jnp.ndarray,
                      cfg: ArchConfig) -> jnp.ndarray:
    B, S = tokens.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0)
    if cfg.pos == "learned":
        pos = jnp.arange(S) + cfg.pos_offset
        x = x + jnp.take(params["pos_embed"]["embedding"], pos, axis=0)
    if cfg.embed_layernorm:
        x = _norm(x, params["embed_ln"], cfg)

    cos = sin = None
    if cfg.pos == "rope":
        rd = cfg.rotary_dim
        inv = 1.0 / (cfg.rope_theta **
                     (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
        freqs = jnp.outer(jnp.arange(S, dtype=jnp.float32), inv)
        cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    alibi = jnp.asarray(alibi_slopes(H)) if cfg.pos == "alibi" else None

    def layer(x, lp):
        h_attn_in = _norm(x, lp["ln1"], cfg)
        q = _proj(h_attn_in, lp["q_proj"]).reshape(B, S, H, hd)
        k = _proj(h_attn_in, lp["k_proj"]).reshape(B, S, KV, hd)
        v = _proj(h_attn_in, lp["v_proj"]).reshape(B, S, KV, hd)
        if cfg.pos == "rope":
            q = _rope_partial(q, cos, sin, cfg.rotary_dim, cfg.rope_style)
            k = _rope_partial(k, cos, sin, cfg.rotary_dim, cfg.rope_style)
        o = _attention(q, k, v, cfg, alibi).reshape(B, S, H * hd)
        attn_out = _proj(o, lp["o_proj"])

        if cfg.parallel_attn:
            h_mlp_in = _norm(x, lp["ln2"], cfg) if cfg.dual_ln else h_attn_in
        else:
            x = x + attn_out
            h_mlp_in = _norm(x, lp["ln2"], cfg)

        if cfg.mlp == "silu_glu":
            gate = jax.nn.silu(_proj(h_mlp_in, lp["gate_proj"]))
            up = _proj(h_mlp_in, lp["up_proj"])
            mlp_out = _proj(gate * up, lp["down_proj"])
        else:
            if cfg.mlp == "gelu":
                act = lambda x: jax.nn.gelu(x, approximate=not cfg.gelu_exact)
            else:
                act = jax.nn.relu
            mlp_out = _proj(act(_proj(h_mlp_in, lp["fc1"])), lp["fc2"])

        if cfg.parallel_attn:
            x = x + attn_out + mlp_out
        else:
            x = x + mlp_out
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _norm(x, params["norm_f"], cfg)
    if cfg.tie_embeddings:
        return x @ params["embed"]["embedding"].T
    logits = x @ params["lm_head"]["kernel"]
    if "bias" in params["lm_head"]:                 # phi has an lm-head bias
        logits = logits + params["lm_head"]["bias"]
    return logits


def init_universal_params(cfg: ArchConfig, key: jax.Array,
                          dtype=jnp.float32) -> Dict:
    """Random init matching the per-arch converters' parameter layout."""
    L, D, F = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = iter(jax.random.split(key, 16))

    def dense(shape, fan_in, bias_dim=None):
        p = {"kernel": (jax.random.normal(next(ks), shape) /
                        math.sqrt(fan_in)).astype(dtype)}
        if bias_dim is not None:
            p["bias"] = jnp.zeros(bias_dim, dtype)
        return p

    def ln():
        p = {"scale": jnp.ones((L, D), dtype)}
        if cfg.norm == "layernorm":
            p["bias"] = jnp.zeros((L, D), dtype)
        return p

    qb = (L, H * hd) if cfg.qkv_bias else None
    kvb = (L, KV * hd) if cfg.qkv_bias else None
    ob = (L, D) if cfg.out_bias else None
    layers = {
        "ln1": ln(),
        "q_proj": dense((L, D, H * hd), D, qb),
        "k_proj": dense((L, D, KV * hd), D, kvb),
        "v_proj": dense((L, D, KV * hd), D, kvb),
        "o_proj": dense((L, H * hd, D), H * hd, ob),
    }
    if not (cfg.parallel_attn and not cfg.dual_ln):
        layers["ln2"] = ln()
    mlp_bias = cfg.out_bias if cfg.mlp_bias is None else cfg.mlp_bias
    if cfg.mlp == "silu_glu":
        layers["gate_proj"] = dense((L, D, F), D)
        layers["up_proj"] = dense((L, D, F), D)
        layers["down_proj"] = dense((L, F, D), F)
    else:
        layers["fc1"] = dense((L, D, F), D, (L, F) if mlp_bias else None)
        layers["fc2"] = dense((L, F, D), F, (L, D) if mlp_bias else None)

    params = {
        "embed": {"embedding": (jax.random.normal(next(ks),
                                                  (cfg.vocab_size, D)) * 0.02
                                ).astype(dtype)},
        "layers": layers,
        "norm_f": {"scale": jnp.ones((D,), dtype)},
    }
    if cfg.norm == "layernorm":
        params["norm_f"]["bias"] = jnp.zeros((D,), dtype)
    if cfg.pos == "learned":
        params["pos_embed"] = {"embedding": (jax.random.normal(
            next(ks), (cfg.max_seq_len + cfg.pos_offset, D)) * 0.02
        ).astype(dtype)}
    if cfg.embed_layernorm:
        params["embed_ln"] = {"scale": jnp.ones((D,), dtype),
                              "bias": jnp.zeros((D,), dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = dense((D, cfg.vocab_size), D,
                                  (cfg.vocab_size,) if cfg.lm_head_bias
                                  else None)
    return params


# --------------------------------------------------------------------- #
# Paged serving (models/serving.py says what each piece is handed)
# --------------------------------------------------------------------- #
def serving_family(cfg: ArchConfig) -> ServingFamily:
    """gpt2/gptj/opt/bloom/falcon/phi on the flat token axis (reference:
    inference/v2/model_implementations/{falcon,phi,opt}/), with the knobs of
    :func:`universal_forward`: learned positions (+opt's offset), ALiBi
    inside the attention (bloom + falcon-scaled), partial/interleaved rotary,
    parallel-attn, dual-LN, LayerNorm-with-bias, gelu/relu/glu MLPs."""
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    attn = dict(scale=1.0 / math.sqrt(hd),
                alibi=alibi_slopes(H) if cfg.pos == "alibi" else None,
                alibi_scaled=cfg.alibi_scaled)

    def embed(params, ids, pos, valid):
        dtype = params["layers"]["q_proj"]["kernel"].dtype
        x = jnp.take(params["embed"]["embedding"], ids, axis=0).astype(dtype)
        if cfg.pos == "learned":
            x = x + jnp.take(params["pos_embed"]["embedding"],
                             pos + cfg.pos_offset, axis=0).astype(dtype)
        if cfg.embed_layernorm:
            x = _norm(x, params["embed_ln"], cfg)
        if cfg.pos == "rope":
            return x, rope_at(pos, cfg.rotary_dim, cfg.rope_theta)
        return x, None

    def layer(x, lp, l_idx, cache, ctx):
        T, dtype = x.shape[0], x.dtype
        h_attn_in = _norm(x, lp["ln1"], cfg)
        q = _proj(h_attn_in, lp["q_proj"]).reshape(T, H, hd)
        k = _proj(h_attn_in, lp["k_proj"]).reshape(T, KV, hd)
        v = _proj(h_attn_in, lp["v_proj"]).reshape(T, KV, hd)
        if cfg.pos == "rope":
            cos, sin = ctx
            q = apply_rope_flat(q, cos, sin, cfg.rotary_dim, cfg.rope_style)
            k = apply_rope_flat(k, cos, sin, cfg.rotary_dim, cfg.rope_style)
        o_flat = cache(q, k, v, **attn).reshape(T, H * hd).astype(dtype)
        attn_out = _proj(o_flat, lp["o_proj"])

        if cfg.parallel_attn:
            h_mlp_in = _norm(x, lp["ln2"], cfg) if cfg.dual_ln else h_attn_in
        else:
            x = x + attn_out
            h_mlp_in = _norm(x, lp["ln2"], cfg)

        if cfg.mlp == "silu_glu":
            gate = jax.nn.silu(_proj(h_mlp_in, lp["gate_proj"]))
            up = _proj(h_mlp_in, lp["up_proj"])
            mlp_out = _proj(gate * up, lp["down_proj"])
        else:
            act = (lambda y: jax.nn.gelu(y, approximate=not cfg.gelu_exact)) \
                if cfg.mlp == "gelu" else jax.nn.relu
            mlp_out = _proj(act(_proj(h_mlp_in, lp["fc1"])), lp["fc2"])

        return x + attn_out + mlp_out if cfg.parallel_attn else x + mlp_out

    def stacks(params):
        yield LayerStack(params["layers"], range(cfg.num_layers), layer)

    def head(params, x, pick):
        last = pick(_norm(x, params["norm_f"], cfg))
        if cfg.tie_embeddings:
            return last @ params["embed"]["embedding"].T
        return _proj(last, params["lm_head"])       # phi: an lm-head bias

    return ServingFamily(num_layers=cfg.num_layers, num_heads=H,
                         row=KVRow(KV, hd), embed=embed, stacks=stacks,
                         head=head)



class UniversalCausalLM:
    """Per-arch compat model with the same engine interface as CausalLM."""

    def __init__(self, cfg: ArchConfig):
        self.config = cfg
        self.partition_specs = None   # replicated; TP comes from AutoTP specs

    def init_params(self, key: jax.Array, dtype=jnp.float32):
        return init_universal_params(self.config, key, dtype)

    def __call__(self, params, tokens):
        return universal_forward(params, tokens, self.config)

    def serving_family(self) -> ServingFamily:
        return serving_family(self.config)

    def loss_fn(self, params, batch, rng=None):
        tokens = batch["input_ids"] if isinstance(batch, dict) else batch
        logits = universal_forward(params, tokens, self.config)
        labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)), constant_values=-100)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        valid = labels >= 0
        safe = jnp.where(valid, labels, 0)
        tl = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        return -jnp.sum(tl * valid) / jnp.maximum(jnp.sum(valid), 1)

    def num_params(self, params) -> int:
        return int(sum(np.prod(l.shape) for l in jax.tree.leaves(params)))
