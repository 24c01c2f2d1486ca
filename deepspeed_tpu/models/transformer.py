"""TPU-native causal-LM transformer — the framework's flagship model family.

Reference analogue: the model containers DeepSpeed injects/serves
(``module_inject/containers/llama.py``, ``inference/v2/model_implementations/
llama_v2``) — but built as a first-class JAX model rather than a wrapper over
HF torch modules.

Design points (TPU-first):
  * stacked layer parameters + ``lax.scan`` over layers → O(1) compile time,
    XLA-friendly static control flow;
  * bf16 compute / fp32 master handled by the engine; this module computes in
    the dtype of the incoming params;
  * Megatron-style tensor-parallel sharding expressed as PartitionSpecs
    (``partition_specs``): qkv/gate/up kernels column-sharded over "tensor",
    o/down row-sharded; embeddings sharded over the hidden dim;
  * activation sharding constraints at layer boundaries: [batch→data axes,
    seq→"seq", hidden→None] so XLA lays out collectives over the right axes;
  * GQA (num_kv_heads ≤ num_heads), RoPE, RMSNorm, SwiGLU — the Llama recipe;
  * optional ``jax.checkpoint`` (remat) per layer for activation checkpointing.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..runtime.topology import (DATA, DATA_OUTER, EXPERT, SEQ, TENSOR,
                                shard_kernel)
from .serving import KVRow, LayerStack, ServingFamily

#: mesh axes the batch dimension of activations is laid out over
_BATCH_AXES = (DATA_OUTER, DATA, EXPERT)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 512
    intermediate_size: int = 1408
    num_layers: int = 4
    num_heads: int = 8
    num_kv_heads: int = 8
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    #: bias on q/k/v projections (qwen2-family); o_proj stays bias-free
    attn_bias: bool = False
    remat: bool = False
    #: what a checkpointed layer keeps for its backward pass.  "auto": the
    #: named outputs of its kernels and matmuls, as many as the device's
    #: memory holds beside the engine's state and the step's transients
    #: (``_remat_layout``; with no memory report, or outside an engine's
    #: step, none: full recompute).  A
    #: jax.checkpoint_policies name overrides it: "nothing_saveable" = full
    #: recompute (min memory); "dots_with_no_batch_dims_saveable" keeps
    #: matmul outputs whatever they take
    remat_policy: str = "auto"
    use_flash: bool = True          # pallas flash attention on TPU
    attn_impl: str = "auto"         # auto | flash | xla | ring | ulysses
    #: flash kernel tile sizes (clamped to the sequence): the best pair of
    #: {128, 256, 512} x {256, 512, 1024} on the v5e at [4 | 2, 32, 2048,
    #: 128] bf16, forward, dq and dkv summed: 6.78 ms against 10.94 at 256
    #: x 512 (tools/flash_split.py; PERF.md section 6, PR 54).  It compiles
    #: for float32 and for 64- and 256-wide heads too, which 1024 x 1024
    #: (6.56 ms) does not
    flash_block_q: int = 512
    flash_block_k: int = 1024
    #: fold rms_norm into the consuming projections' Pallas kernels
    #: (``kernels/fused_collective_matmul.rmsnorm_matmul`` — the norm's
    #: variance/rsqrt recomputed per output tile, normalized activations
    #: never round-trip HBM).  "auto" = TPU only, so the CPU sim keeps the
    #: unfused jaxpr; "on"/"off" force it.  Bitwise vs the unfused
    #: composition under jit, test-asserted through the interpreter seam.
    fused_rmsnorm: str = "auto"   # auto | on | off
    # MoE (Mixtral-family): >1 experts replaces the dense MLP with a
    # top-k routed expert MLP on every layer.
    num_experts: int = 1
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    moe_aux_loss_coef: float = 0.01
    moe_dispatch: str = "sparse"    # sparse (scatter, linear in tokens) | dense (oracle)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @staticmethod
    def tiny(**kw):
        return TransformerConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                                 num_layers=2, num_heads=4, num_kv_heads=2,
                                 max_seq_len=128, **kw)

    @staticmethod
    def tiny_moe(**kw):
        return TransformerConfig(vocab_size=256, hidden_size=64,
                                 intermediate_size=128, num_layers=2,
                                 num_heads=4, num_kv_heads=2, max_seq_len=128,
                                 num_experts=4, moe_top_k=2, **kw)


# --------------------------------------------------------------------- #
# Parameter init + sharding specs
# --------------------------------------------------------------------- #
def init_params(cfg: TransformerConfig, key: jax.Array, dtype=jnp.float32) -> Dict:
    """Stacked-layer parameter pytree. Layer arrays have leading dim L."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    D, F, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def norm_init(*shape):
        return jnp.ones(shape, dtype)

    def dense_init(k, shape, fan_in):
        return (jax.random.normal(k, shape) / math.sqrt(fan_in)).astype(dtype)

    ks = jax.random.split(k_layers, 8)
    layers = {
        "attn_norm": {"scale": norm_init(L, D)},
        "q_proj": {"kernel": dense_init(ks[0], (L, D, H * hd), D)},
        "k_proj": {"kernel": dense_init(ks[1], (L, D, KV * hd), D)},
        "v_proj": {"kernel": dense_init(ks[2], (L, D, KV * hd), D)},
        "o_proj": {"kernel": dense_init(ks[3], (L, H * hd, D), H * hd)},
        "mlp_norm": {"scale": norm_init(L, D)},
    }
    if cfg.attn_bias:
        layers["q_proj"]["bias"] = jnp.zeros((L, H * hd), dtype)
        layers["k_proj"]["bias"] = jnp.zeros((L, KV * hd), dtype)
        layers["v_proj"]["bias"] = jnp.zeros((L, KV * hd), dtype)
    if cfg.num_experts > 1:
        E = cfg.num_experts
        layers["router"] = {"kernel": dense_init(ks[7], (L, D, E), D).astype(jnp.float32)}
        layers["gate_proj"] = {"kernel": dense_init(ks[4], (L, E, D, F), D)}
        layers["up_proj"] = {"kernel": dense_init(ks[5], (L, E, D, F), D)}
        layers["down_proj"] = {"kernel": dense_init(ks[6], (L, E, F, D), F)}
    else:
        layers["gate_proj"] = {"kernel": dense_init(ks[4], (L, D, F), D)}
        layers["up_proj"] = {"kernel": dense_init(ks[5], (L, D, F), D)}
        layers["down_proj"] = {"kernel": dense_init(ks[6], (L, F, D), F)}
    params = {
        "embed": {"embedding": (jax.random.normal(k_embed, (cfg.vocab_size, D)) * 0.02).astype(dtype)},
        "layers": layers,
        "norm_f": {"scale": norm_init(D)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": dense_init(k_head, (D, cfg.vocab_size), D)}
    return params


def partition_specs(cfg: TransformerConfig) -> Dict:
    """Megatron-style TP specs (reference: module_inject/auto_tp.py row/col split).

    Column-parallel (output dim over "tensor"): q/k/v, gate, up.
    Row-parallel (input dim over "tensor"): o, down.  Embedding + lm_head
    sharded over the vocab/hidden as appropriate.
    """
    layer_specs = {
        "attn_norm": {"scale": P(None, None)},
        "q_proj": {"kernel": P(None, None, TENSOR)},
        "k_proj": {"kernel": P(None, None, TENSOR)},
        "v_proj": {"kernel": P(None, None, TENSOR)},
        "o_proj": {"kernel": P(None, TENSOR, None)},
        "mlp_norm": {"scale": P(None, None)},
    }
    if cfg.attn_bias:
        # column-parallel biases shard with the projection's output dim
        layer_specs["q_proj"]["bias"] = P(None, TENSOR)
        layer_specs["k_proj"]["bias"] = P(None, TENSOR)
        layer_specs["v_proj"]["bias"] = P(None, TENSOR)
    if cfg.num_experts > 1:
        # experts sharded over the "expert" mesh axis, TP within each expert
        layer_specs["router"] = {"kernel": P(None, None, None)}
        layer_specs["gate_proj"] = {"kernel": P(None, EXPERT, None, TENSOR)}
        layer_specs["up_proj"] = {"kernel": P(None, EXPERT, None, TENSOR)}
        layer_specs["down_proj"] = {"kernel": P(None, EXPERT, TENSOR, None)}
    else:
        layer_specs["gate_proj"] = {"kernel": P(None, None, TENSOR)}
        layer_specs["up_proj"] = {"kernel": P(None, None, TENSOR)}
        layer_specs["down_proj"] = {"kernel": P(None, TENSOR, None)}
    specs = {
        "embed": {"embedding": P(TENSOR, None)},
        "layers": layer_specs,
        "norm_f": {"scale": P(None)},
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = {"kernel": P(None, TENSOR)}
    return specs


# --------------------------------------------------------------------- #
# Building blocks
# --------------------------------------------------------------------- #
def rms_norm(x, scale, eps):
    # the fused path (kernels/fused_collective_matmul.rmsnorm_matmul)
    # folds exactly this composition into the consuming projection's
    # kernel — any change here must land there too (parity test-asserted)
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale


def _proj(x, p):
    y = x @ p["kernel"]
    return y + p["bias"] if "bias" in p else y


def _fused_rmsnorm_active(cfg: "TransformerConfig") -> bool:
    """"on"/"off" force; "auto" enables on TPU Pallas only — the CPU sim's
    jaxpr (and therefore every tier-1 numeric) is unchanged by default."""
    mode = getattr(cfg, "fused_rmsnorm", "auto")
    if mode in ("on", True):
        return True
    if mode in ("off", False):
        return False
    from ..kernels.fused_collective_matmul import supports_fused_rmsnorm

    return supports_fused_rmsnorm()


def rope_tables(seq_len: int, head_dim: int, theta: float, offset=0):
    pos = jnp.arange(seq_len, dtype=jnp.float32) + offset
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    freqs = jnp.outer(pos, inv)                      # [S, hd/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x, cos, sin):
    """x: [B, S, H, hd]; rotate pairs (even, odd stacked halves)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos = cos[None, :, None, :].astype(x.dtype)
    sin = sin[None, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def rope_at(pos, rotary_dim, theta):
    """cos/sin tables gathered at arbitrary positions [T] → [T, rd/2] (the
    flat serving token axis)."""
    inv = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                           / rotary_dim))
    freqs = pos.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope_flat(x, cos, sin, rotary_dim=None, style="neox"):
    """x [T, H, hd] with per-token tables [T, rd/2]; partial rotary (phi)
    and interleaved-pair style (gptj) supported, mirroring
    families._rope_partial for the flat serving token axis."""
    hd = x.shape[-1]
    rd = hd if rotary_dim is None else rotary_dim
    rot, passthrough = x[..., :rd], x[..., rd:]
    c = cos[:, None, :].astype(x.dtype)
    s = sin[:, None, :].astype(x.dtype)
    if style == "gptj":
        x1, x2 = rot[..., 0::2], rot[..., 1::2]
        rot = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c],
                        axis=-1).reshape(rot.shape)
    else:
        x1, x2 = jnp.split(rot, 2, axis=-1)
        rot = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([rot, passthrough], axis=-1) if rd < hd else rot


def _xla_attention(q, k, v, causal=True, seq_offset=0):
    """Plain XLA attention [B,S,H,hd] — fallback + CPU-sim path."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    if causal:
        q_pos = jnp.arange(S)[:, None] + seq_offset
        k_pos = jnp.arange(k.shape[1])[None, :]
        mask = q_pos >= k_pos
        scores = jnp.where(mask[None, None], scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _attn_impl(cfg: TransformerConfig, seq_len: int) -> str:
    """``cfg.attn_impl`` with "auto" resolved: flash on a TPU from 128
    tokens up, XLA math elsewhere."""
    impl = cfg.attn_impl
    if impl == "auto":
        from ..accelerator import get_accelerator

        impl = "flash" if (cfg.use_flash and get_accelerator().supports_pallas()
                           and seq_len >= 128) else "xla"
    return impl


def attention(q, k, v, cfg: TransformerConfig, causal=True):
    """Dispatch to the Pallas flash kernel on TPU, XLA math elsewhere."""
    if _attn_impl(cfg, q.shape[1]) == "flash":
        from ..ops.transformer.flash_attention import flash_attention

        # the kernel clamps blocks to the (128-aligned) sequence itself —
        # pre-clamping here would feed it non-lane-aligned tiles.  Each
        # device runs it on its own batch rows and (tensor-parallel) heads.
        heads = P(_BATCH_AXES, None, TENSOR, None)
        return shard_kernel(
            partial(flash_attention, causal=causal,
                    block_q=cfg.flash_block_q, block_k=cfg.flash_block_k),
            in_specs=(heads, heads, heads), out_specs=heads)(q, k, v)
    return _xla_attention(q, k, v, causal=causal)


def _norm_matmul(x, scale, w, eps):
    """``rms_norm(x) @ w`` through the fused kernel, on each device's batch
    rows and its column block of ``w`` (q/k/v/gate/up are column-parallel
    over "tensor", see :func:`partition_specs`)."""
    from ..kernels.fused_collective_matmul import rmsnorm_matmul

    return shard_kernel(
        lambda x, s, w: rmsnorm_matmul(x, s, w, eps),
        in_specs=(P(_BATCH_AXES, None, None), P(None), P(None, TENSOR)),
        out_specs=P(_BATCH_AXES, None, TENSOR))(x, scale, w)


# --------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------- #
def _activation_spec():
    return P(_BATCH_AXES, SEQ, None)


def _remat_layout(cfg: TransformerConfig, batch: int, seq_len: int,
                  itemsize: int):
    """What ``checkpointing.layer_policy`` chooses from, for ONE device, from
    the trace's shapes: the layer's named values (bytes held a layer, seconds
    its backward spends making them again: a kernel's or a matmul's FLOPs at
    the MXU's peak, a gathered weight's bytes at the links' rate) and the
    bytes the step needs beside them — the head's float32 ``[rows, vocab]``
    logits, log-softmax and cotangent, or one layer's backward pass (every
    named activation made again, and a cotangent for each), whichever is
    more: the two never live together.  Rows are a device's share over the
    batch and sequence axes; a tensor axis, which would divide the widths,
    is left out (it saves less than it could there)."""
    from ..moe.sharded_moe import ROW_NAMES, STACK_NAMES, own_pair_rows
    from ..ops.transformer.flash_attention import LSE_NAME, OUT_NAME
    from ..profiling.roofline import device_spec
    from ..runtime import topology as _topo
    from ..runtime.activation_checkpointing.checkpointing import Saveable

    topo = _topo._TOPOLOGY
    shards = devices = 1
    if topo is not None:
        devices = topo.mesh.size
        over_batch = math.prod(topo.dims[a] for a in _BATCH_AXES)
        shards = (over_batch if batch % over_batch == 0 else 1) * (
            topo.dims[SEQ] if seq_len % topo.dims[SEQ] == 0 else 1)
    spec = device_spec(None if topo is None else topo.mesh.devices.flat[0])
    rows = batch * seq_len // shards
    D, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    q_w, kv_w = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim

    def matmul(name, width, contract=D, rows=rows):
        return Saveable((name,), rows * width * itemsize,
                        2.0 * rows * contract * width / spec.peak_flops)

    tensors, pair_rows = [], 0
    if cfg.num_experts == 1:
        tensors += [matmul("gate_proj", F), matmul("up_proj", F)]
    elif cfg.moe_dispatch == "sparse":
        # a shard's grouped matmuls name the rows they make; the padded
        # einsums (one device, other axes) name nothing
        pair_rows = own_pair_rows(batch * seq_len, cfg.num_experts,
                                  cfg.moe_top_k, cfg.moe_capacity_factor)
        if pair_rows:
            gate, up, down = ROW_NAMES
            tensors += [matmul(gate, F, rows=pair_rows),
                        matmul(up, F, rows=pair_rows),
                        matmul(down, D, F, rows=pair_rows)]
    if _attn_impl(cfg, seq_len) == "flash":
        # causal: half of the two [S, S, hd] products a head
        tensors.append(Saveable(
            (OUT_NAME, LSE_NAME), rows * q_w * itemsize + rows * cfg.num_heads * 4,
            2.0 * rows * seq_len * q_w / spec.peak_flops))
    tensors += [matmul("q_proj", q_w), matmul("k_proj", kv_w),
                matmul("v_proj", kv_w), matmul("attn_residual", D, q_w)]
    head = 3 * rows * V * 4
    layer_backward = 2 * sum(t.bytes for t in tensors)
    if cfg.num_experts > 1 and not pair_rows:   # the einsums' gate and up
        layer_backward += 4 * rows * cfg.moe_top_k * F * itemsize
    if devices > 1:
        # a sharded (ZeRO-3) layer is gathered whole; a weight's gradient is
        # whole until it is scattered, one weight at a time (the compiler's
        # account, PERF.md section 6, PR 63)
        stack = cfg.num_experts * D * F * itemsize
        layer_backward += itemsize * (
            D * (q_w + 2 * kv_w) + q_w * D) + 3 * stack + max(
            itemsize * D * q_w, stack)
        if pair_rows:
            # the gather lands at the region's boundary (moe/sharded_moe.py):
            # a stack kept whole costs the other devices' shares over the
            # links to make again, and kept, the backward's gathered copy of
            # it above is the one it slices out of the layers' stacks
            freed = max(0, min(layer_backward - head, 3 * stack)) // 3
            tensors += [Saveable((n,), stack, stack * (devices - 1) / devices
                                 / spec.ici_bandwidth, freed)
                        for n in STACK_NAMES]
    return tensors, max(head, layer_backward)


def _constrain(x, spec):
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x  # outside jit/mesh context


def forward(params: Dict, tokens: jax.Array, cfg: TransformerConfig,
            dropout_rng: Optional[jax.Array] = None,
            return_aux_loss: bool = False) -> jax.Array:
    """tokens [B, S] int32 → logits [B, S, V] (+ MoE aux loss if requested)."""
    dtype = params["layers"]["q_proj"]["kernel"].dtype
    # jax.named_scope annotations flow into jaxpr name stacks (and xprof op
    # names), feeding the profiler's per-module cost tree
    # (profiling/module_tree.py) — zero runtime cost.
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"]["embedding"], tokens, axis=0)
        x = _constrain(x, _activation_spec())
    S = tokens.shape[1]
    cos, sin = rope_tables(S, cfg.head_dim, cfg.rope_theta)

    def mlp_block(h, lp, fused_scale=None):
        if cfg.num_experts > 1:
            # Mixtral-style routed expert MLP (see moe/).  Default dispatch
            # is the sparse scatter/gather path (linear in routing-chunk
            # tokens); "dense" keeps the GShard [S,E,C] einsum as the oracle.
            from ..moe.sharded_moe import moe_mlp_block

            B_, S_, D_ = h.shape
            out, l_aux = moe_mlp_block(
                lp, h.reshape(-1, D_), k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                dispatch_impl=cfg.moe_dispatch)
            return out.reshape(B_, S_, D_), l_aux
        if fused_scale is not None:
            # fused path: h is the UN-normalized residual; the norm is
            # folded into the gate/up projection kernels (down has no
            # norm in front and stays a plain matmul)
            gate = _norm_matmul(h, fused_scale, lp["gate_proj"]["kernel"],
                                cfg.norm_eps)
            up = _norm_matmul(h, fused_scale, lp["up_proj"]["kernel"],
                              cfg.norm_eps)
        else:
            gate = h @ lp["gate_proj"]["kernel"]
            up = h @ lp["up_proj"]["kernel"]
        gate = jax.nn.silu(checkpoint_name(gate, "gate_proj"))
        up = checkpoint_name(up, "up_proj")
        return (gate * up) @ lp["down_proj"]["kernel"], jnp.zeros((), jnp.float32)

    def proj(h, p, B, n_heads, name):
        y = h @ p["kernel"]
        if "bias" in p:
            y = y + p["bias"]
        return checkpoint_name(y.reshape(B, S, n_heads, cfg.head_dim), name)

    fused_norm = _fused_rmsnorm_active(cfg)

    def norm_proj(x, norm_scale, p, B, n_heads, name):
        """rms_norm folded into the projection kernel (the fused path's
        per-tile recompute of the norm is free VPU work; the normalized
        activations never hit HBM)."""
        y = _norm_matmul(x, norm_scale, p["kernel"], cfg.norm_eps)
        if "bias" in p:
            y = y + p["bias"]
        return checkpoint_name(y.reshape(B, S, n_heads, cfg.head_dim), name)

    # Under ``jax.checkpoint`` the policy selects by these names what a
    # layer keeps for its backward pass (``_remat_layout``): the
    # projections before RoPE and the GQA repeat, the flash kernel's own
    # (ops/transformer/flash_attention.py), the residual stream, and on the
    # experts' grouped path the matmuls' rows and the stacks ZeRO-3 gathered
    # (moe/sharded_moe.py).  Anywhere else a name is the identity.
    def layer(carry, lp):
        x, aux = carry
        B = x.shape[0]
        with jax.named_scope("attention"):
            if fused_norm:
                ns = lp["attn_norm"]["scale"]
                q = norm_proj(x, ns, lp["q_proj"], B, cfg.num_heads, "q_proj")
                k = norm_proj(x, ns, lp["k_proj"], B, cfg.num_kv_heads,
                              "k_proj")
                v = norm_proj(x, ns, lp["v_proj"], B, cfg.num_kv_heads,
                              "v_proj")
            else:
                h = rms_norm(x, lp["attn_norm"]["scale"], cfg.norm_eps)
                q = proj(h, lp["q_proj"], B, cfg.num_heads, "q_proj")
                k = proj(h, lp["k_proj"], B, cfg.num_kv_heads, "k_proj")
                v = proj(h, lp["v_proj"], B, cfg.num_kv_heads, "v_proj")
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
            o = attention(q, k, v, cfg, causal=True)
            x = x + (o.reshape(B, S, -1) @ lp["o_proj"]["kernel"])
        # Named + mesh-sharded residual stream: the activation-checkpointing
        # config's save/offload policies select these by name (runtime/
        # activation_checkpointing/checkpointing.py RESIDUAL_NAMES), and the
        # sharding constraint means a saved residual is PARTITIONED over the
        # data/seq axes — the reference's partition_activations.
        x = checkpoint_name(_constrain(x, _activation_spec()), "attn_residual")
        with jax.named_scope("mlp"):
            if fused_norm and cfg.num_experts == 1:
                # norm folded into the gate/up kernels; MoE keeps the
                # unfused norm (the router needs h itself)
                mlp_out, l_aux = mlp_block(
                    x, lp, fused_scale=lp["mlp_norm"]["scale"])
            else:
                h = rms_norm(x, lp["mlp_norm"]["scale"], cfg.norm_eps)
                mlp_out, l_aux = mlp_block(h, lp)
            x = x + mlp_out
        x = checkpoint_name(_constrain(x, _activation_spec()), "mlp_residual")
        return (x, aux + l_aux), None

    layer_fn = layer
    if cfg.remat:
        from ..runtime.activation_checkpointing import checkpointing as ac

        policy = ac.resolve_policy(
            cfg.remat_policy,
            lambda: _remat_layout(cfg, *tokens.shape,
                                  jnp.dtype(dtype).itemsize),
            layers=cfg.num_layers)
        layer_fn = jax.checkpoint(layer, policy=policy)

    with jax.named_scope("layers"):
        (x, aux_loss), _ = jax.lax.scan(layer_fn,
                                        (x, jnp.zeros((), jnp.float32)),
                                        params["layers"])
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["norm_f"]["scale"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            logits = x @ params["embed"]["embedding"].T
        else:
            logits = x @ params["lm_head"]["kernel"]
    if return_aux_loss:
        return logits, aux_loss
    return logits


def lm_loss(params: Dict, batch: Any, cfg: TransformerConfig,
            rng: Optional[jax.Array] = None) -> jax.Array:
    """Causal LM loss: predict batch['input_ids'] shifted by one.

    Accepts {'input_ids': [B,S]} (+ optional 'labels' [B,S] with -100 ignore).
    """
    tokens = batch["input_ids"] if isinstance(batch, dict) else batch
    labels = batch.get("labels") if isinstance(batch, dict) else None
    logits, aux_loss = forward(params, tokens, cfg, return_aux_loss=True)
    if labels is None:
        labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)), constant_values=-100)
    with jax.named_scope("loss"):
        logits = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        valid = labels >= 0
        safe_labels = jnp.where(valid, labels, 0)
        token_logp = jnp.take_along_axis(
            logp, safe_labels[..., None], axis=-1)[..., 0]
        loss = -jnp.sum(token_logp * valid) / jnp.maximum(jnp.sum(valid), 1)
    if cfg.num_experts > 1:
        loss = loss + cfg.moe_aux_loss_coef * aux_loss / cfg.num_layers
    return loss


# --------------------------------------------------------------------- #
# Paged serving (models/serving.py says what each piece is handed)
# --------------------------------------------------------------------- #
def serving_family(cfg: TransformerConfig) -> ServingFamily:
    """The llama recipe (Mistral, Mixtral) on the flat token axis:
    rmsnorm → qkv → RoPE → cache → o proj → SwiGLU or the routed experts."""
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    scale = 1.0 / math.sqrt(hd)

    def embed(params, ids, pos, valid):
        dtype = params["layers"]["q_proj"]["kernel"].dtype
        x = jnp.take(params["embed"]["embedding"], ids,
                     axis=0).astype(dtype)                          # [T, D]
        cos, sin = rope_at(pos, hd, cfg.rope_theta)
        return x, (cos, sin, valid())

    def layer(x, lp, l_idx, cache, ctx):
        cos, sin, valid = ctx
        T, dtype = x.shape[0], x.dtype
        h = rms_norm(x, lp["attn_norm"]["scale"], cfg.norm_eps)
        q = _proj(h, lp["q_proj"]).reshape(T, H, hd)
        k = _proj(h, lp["k_proj"]).reshape(T, KV, hd)
        v = _proj(h, lp["v_proj"]).reshape(T, KV, hd)
        q = apply_rope_flat(q, cos, sin)
        k = apply_rope_flat(k, cos, sin)
        o_flat = cache(q, k, v, scale=scale).reshape(T, H * hd).astype(dtype)
        x = x + o_flat @ lp["o_proj"]["kernel"]
        h = rms_norm(x, lp["mlp_norm"]["scale"], cfg.norm_eps)
        if cfg.num_experts > 1:
            # MoE serving (moe_gather/moe_scatter analogue): sparse-slot
            # dispatch over flat ragged tokens; the batch's padding is
            # excluded from expert capacity.
            from ..moe.sharded_moe import moe_mlp_block

            mlp_out, _ = moe_mlp_block(
                lp, h, k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                dispatch_impl="sparse", valid=valid)
            return x + mlp_out
        gate = jax.nn.silu(h @ lp["gate_proj"]["kernel"])
        up = h @ lp["up_proj"]["kernel"]
        return x + (gate * up) @ lp["down_proj"]["kernel"]

    def stacks(params):
        yield LayerStack(params["layers"], range(cfg.num_layers), layer)

    def head(params, x, pick):
        last = pick(rms_norm(x, params["norm_f"]["scale"], cfg.norm_eps))
        if cfg.tie_embeddings:
            return last @ params["embed"]["embedding"].T
        return last @ params["lm_head"]["kernel"]

    return ServingFamily(num_layers=cfg.num_layers, num_heads=H,
                         row=KVRow(KV, hd), embed=embed, stacks=stacks,
                         head=head)



class CausalLM:
    """Model object consumable by ``deepspeed_tpu.initialize``.

    Exposes ``loss_fn(params, batch, rng)``, ``partition_specs`` (read by the
    engine's ZeRO plan as TP base specs), and ``init_params``.
    """

    def __init__(self, cfg: TransformerConfig):
        self.config = cfg
        self.partition_specs = partition_specs(cfg)

    def init_params(self, key: jax.Array, dtype=jnp.float32):
        return init_params(self.config, key, dtype)

    def loss_fn(self, params, batch, rng):
        return lm_loss(params, batch, self.config, rng)

    def __call__(self, params, tokens):
        return forward(params, tokens, self.config)

    def serving_family(self) -> ServingFamily:
        return serving_family(self.config)

    def num_params(self, params=None) -> int:
        if params is None:
            params = jax.eval_shape(lambda k: self.init_params(k), jax.random.PRNGKey(0))
        import numpy as np

        return int(sum(np.prod(l.shape) for l in jax.tree.leaves(params)))

    def flops_per_token(self) -> float:
        """~6N flops/token for training (fwd+bwd), N = non-embedding params."""
        cfg = self.config
        D, F, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
        per_layer = 2 * D * (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim \
            + 2 * cfg.num_heads * cfg.head_dim * D + 3 * 2 * D * F
        return 3 * (L * per_layer + 2 * D * cfg.vocab_size)
