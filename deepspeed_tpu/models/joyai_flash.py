"""JoyAI-LLM-Flash (``model_type: joyai_llm_flash``, 48B-A2.7B) on the
TRAINING path: multi-head latent attention in its expanded form, 8-of-256
sigmoid-routed experts beside a shared one whose selection bias the step
itself balances (``noaux_tc``), and one multi-token-prediction module with a
loss of its own.

What is here: the configuration (from the published ``config.json`` keys),
the seeded parameter tree, ``partition_specs``, the dense forward on ``[B,
S]`` tokens, ``loss_fn`` and the state the optimizer does NOT train — the
selection bias and the step's counters — with the rule that gives the next
state from what the forward counted.  ``deepspeed_tpu.initialize(model=
JoyAIFlashLM(cfg))`` carries that state in the compiled step (``runtime/
engine.py``: ``init_model_state`` / ``next_model_state``).

Shared with the served families, not copied: ``mla_query`` / ``mla_latent``
(``models/xing4.py``; ``kv_b_proj``, whose two views ``mla_up_weights`` hands
the absorbed form, is applied whole here), ``sigmoid_topk_route`` and
``dropless_experts`` with ``offset`` (``moe/dropless.py``), the Mixtral
block's ``_routing_groups`` (``moe/sharded_moe.py``), ``attention``,
``rms_norm`` and ``rope_at`` (``models/transformer.py``).

Two keys of the published config do not mean what they say elsewhere:
``head_dim: 64`` is the ROTARY width (``qk_rope_head_dim``), not a head's —
a head is 192 wide for q/k (128 without position + 64 rotary) and 128 for v;
``num_key_value_heads: 32`` means nothing under MLA, where every head reads
the one latent.  ``rope_scaling`` is null, so there is no YaRN factor and no
``mscale``: the softmax scale is ``1 / sqrt(192)``.

Per token, pre-norm: ``x += Attn(RMSNorm(x))``, ``x += FFN(RMSNorm(x))``.
Layer 0 (``first_k_dense_replace``) has a dense SwiGLU, the others experts.
The multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437 section
2.2): ``h'_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]`` with ``h_i``
the main model's output after its final norm, one full expert layer of its
own, a final norm of its own, the SHARED embedding and head; it predicts
``t_{i+2}``.  ``loss = CE(main, t_{i+1}) + mtp_loss_weight · CE(mtp,
t_{i+2})``, each a mean over the positions that have a target.  The module
runs over all ``S`` positions: the token after the last one is id 0 (as
Megatron's rolled ids), that position is in neither mean, and its pairs are
routed and counted like any other's.

A chip's share (``experts_held < n_routed_experts``): the router scores all
its outputs, the chip computes experts ``expert_offset`` to ``expert_offset
+ experts_held`` and leaves out what the others would add; the loads that
balance the bias are over ALL the router's outputs.

Serving this family is not written: ``serving_family()`` raises by name.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..moe.dropless import (PAIR_ROW_NAMES, dropless_experts,
                            sigmoid_topk_route, whole_tiles)
from ..moe.sharded_moe import _routing_groups
from ..runtime import topology as _topo
from ..runtime.topology import TENSOR
from .transformer import (_BATCH_AXES, _activation_spec, _attn_impl,
                          _constrain, attention, rms_norm, rope_at)
from .xing4 import mla_latent, mla_query

#: ``checkpoint_name`` tags of one layer's values (``_remat_layout``)
Q_NAME, KV_NAME = "mla_q", "mla_kv"
SHARED_NAMES = ("shared_gate", "shared_up")


@dataclasses.dataclass(frozen=True)
class JoyAIFlashConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168        # the leading dense layers' SwiGLU
    moe_intermediate_size: int = 768     # one expert's width
    num_layers: int = 40                 # of the main model, dense + expert
    first_k_dense: int = 1
    num_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256          # the router's outputs
    #: experts whose weights live here (None: all), from ``expert_offset`` on
    experts_held: Optional[int] = None
    expert_offset: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    num_mtp_layers: int = 1              # num_nextn_predict_layers
    #: not in the config: DeepSeek-V3's values (section 4.2)
    mtp_loss_weight: float = 0.3
    bias_update_rate: float = 0.001
    norm_eps: float = 1e-6
    rope_theta: float = 32e6
    max_seq_len: int = 131072
    tie_embeddings: bool = False
    remat: bool = False
    remat_policy: str = "auto"           # see TransformerConfig.remat_policy
    use_flash: bool = True
    attn_impl: str = "auto"              # auto | flash | xla
    flash_block_q: int = 512
    flash_block_k: int = 1024

    @property
    def num_dense_layers(self) -> int:
        return min(self.first_k_dense, self.num_layers)

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.num_dense_layers

    @property
    def num_expert_layers(self) -> int:
        """Layers with a router: the main model's, then the MTP module's."""
        return self.num_moe_layers + self.num_mtp_layers

    @property
    def held(self) -> int:
        return self.n_routed_experts if self.experts_held is None \
            else self.experts_held

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    # what ``models/xing4.py``'s MLA functions read: the latent row is not
    # padded here (nothing caches it)
    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    latent_row = latent_dim

    @staticmethod
    def from_hf(hf: Dict, **overrides) -> "JoyAIFlashConfig":
        """From the published ``config.json`` keys."""
        if hf.get("scoring_func") != "sigmoid" \
                or hf.get("topk_method") != "noaux_tc" \
                or hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
            raise NotImplementedError(
                "joyai_flash: only sigmoid / noaux_tc routing without group "
                "limits")
        if hf.get("rope_scaling"):
            raise NotImplementedError("joyai_flash: rope_scaling is null in "
                                      "the published config")
        if not hf.get("rope_interleave", True):
            raise NotImplementedError("joyai_flash: rope_interleave is true "
                                      "in the published config")
        if hf.get("num_nextn_predict_layers", 1) > 1:
            raise NotImplementedError("joyai_flash: one MTP module")
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
            num_mtp_layers=int(hf.get("num_nextn_predict_layers", 0)),
            norm_eps=float(hf["rms_norm_eps"]),
            rope_theta=float(hf["rope_theta"]),
            max_seq_len=hf["max_position_embeddings"],
            tie_embeddings=bool(hf["tie_word_embeddings"]))
        kw.update(overrides)
        return JoyAIFlashConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "JoyAIFlashConfig":
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    moe_intermediate_size=32, num_layers=3, first_k_dense=1,
                    num_heads=4, q_lora_rank=48, kv_lora_rank=32,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                    n_routed_experts=16, num_experts_per_tok=4,
                    max_seq_len=256)
        base.update(kw)
        return JoyAIFlashConfig(**base)


# --------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------- #
def _stack_init(cfg: JoyAIFlashConfig, key, L: int, moe: bool, dtype) -> Dict:
    D, H = cfg.hidden_size, cfg.num_heads
    ks = iter(jax.random.split(key, 16))

    def dense(shape, fan_in):
        return (jax.random.normal(next(ks), shape) / math.sqrt(fan_in)
                ).astype(dtype)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    p = {
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
    E, F = cfg.held, cfg.moe_intermediate_size
    Fs = F * cfg.n_shared_experts
    # the selection bias is NOT here: the optimizer does not train it
    # (``init_model_state``)
    p["router"] = {"kernel": (
        jax.random.normal(next(ks), (L, D, cfg.n_routed_experts))
        / math.sqrt(D)).astype(jnp.float32)}
    p["experts"] = {
        "gate": dense((L, E, D, F), D), "up": dense((L, E, D, F), D),
        "down": dense((L, E, F, D), F)}
    p["shared"] = {
        "gate": dense((L, D, Fs), D), "up": dense((L, D, Fs), D),
        "down": dense((L, Fs, D), Fs)}
    return p


def init_params(cfg: JoyAIFlashConfig, key: jax.Array,
                dtype=jnp.float32) -> Dict:
    k = jax.random.split(key, 6)
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
    if cfg.num_mtp_layers:
        params["mtp"] = {
            "enorm": {"scale": jnp.ones((D,), dtype)},
            "hnorm": {"scale": jnp.ones((D,), dtype)},
            # rows [embedding's half ; hidden state's half]
            "eh_proj": {"kernel": (jax.random.normal(k[4], (2 * D, D))
                                   / math.sqrt(2 * D)).astype(dtype)},
            "layers": _stack_init(cfg, k[5], cfg.num_mtp_layers, True, dtype),
            "norm": {"scale": jnp.ones((D,), dtype)},
        }
    return params


def partition_specs(cfg: JoyAIFlashConfig) -> Dict:
    """Tensor-parallel base specs: the per-head projections by columns
    (``q_b``, ``kv_b``) and ``o`` by rows, the dense and the shared SwiGLU
    Megatron-style, embedding by rows and head by columns.  The latent
    down-projections, the router and the experts held are whole on every
    device (a chip's experts are its share already; the grouped matmul runs
    on a device's own rows)."""
    def stack(moe: bool) -> Dict:
        s = {
            "attn_norm": {"scale": P(None, None)},
            "q_a_proj": {"kernel": P(None, None, None)},
            "q_a_norm": {"scale": P(None, None)},
            "q_b_proj": {"kernel": P(None, None, TENSOR)},
            "kv_a_proj": {"kernel": P(None, None, None)},
            "kv_a_norm": {"scale": P(None, None)},
            "kv_b_proj": {"kernel": P(None, None, TENSOR)},
            "o_proj": {"kernel": P(None, TENSOR, None)},
            "mlp_norm": {"scale": P(None, None)},
        }
        if not moe:
            s["gate_proj"] = {"kernel": P(None, None, TENSOR)}
            s["up_proj"] = {"kernel": P(None, None, TENSOR)}
            s["down_proj"] = {"kernel": P(None, TENSOR, None)}
            return s
        s["router"] = {"kernel": P(None, None, None)}
        s["experts"] = {n: P(None, None, None, None)
                        for n in ("gate", "up", "down")}
        s["shared"] = {"gate": P(None, None, TENSOR),
                       "up": P(None, None, TENSOR),
                       "down": P(None, TENSOR, None)}
        return s

    specs = {"embed": {"embedding": P(TENSOR, None)},
             "norm_f": {"scale": P(None)}}
    if cfg.num_dense_layers:
        specs["dense_layers"] = stack(False)
    if cfg.num_moe_layers:
        specs["moe_layers"] = stack(True)
    if not cfg.tie_embeddings:
        specs["lm_head"] = {"kernel": P(None, TENSOR)}
    if cfg.num_mtp_layers:
        specs["mtp"] = {"enorm": {"scale": P(None)},
                        "hnorm": {"scale": P(None)},
                        "eh_proj": {"kernel": P(None, None)},
                        "layers": stack(True), "norm": {"scale": P(None)}}
    return specs


# --------------------------------------------------------------------- #
# State the optimizer does not train
# --------------------------------------------------------------------- #
def init_model_state(cfg: JoyAIFlashConfig) -> Dict:
    """One row an expert layer (the main model's, then the MTP module's):
    the selection bias, zero at the start, and what the steps counted since:
    the pairs per router output, the pairs this chip's grouped matmuls
    computed per expert held, and the last step's two loss terms."""
    Le, E = cfg.num_expert_layers, cfg.n_routed_experts
    return {
        "router_bias": jnp.zeros((Le, E), jnp.float32),
        "pairs_routed": jnp.zeros((Le, E), jnp.int32),
        "pairs_computed": jnp.zeros((Le, cfg.held), jnp.int32),
        "steps": jnp.zeros((), jnp.int32),
        "main_loss": jnp.zeros((), jnp.float32),
        "mtp_loss": jnp.zeros((), jnp.float32),
    }


def next_model_state(cfg: JoyAIFlashConfig, state: Dict, counted: Dict
                     ) -> Dict:
    """``noaux_tc``: after each step ``b_e += rate · sign(mean load −
    load_e)`` per expert layer, the loads being the step's (token, choice)
    pairs per router output over the WHOLE batch (the engine hands in what
    every micro-batch counted, added up; on a data-parallel mesh the forward
    summed them over the data axes).  The bias takes no gradient, no weight
    decay and no clipping: it is not among the optimizer's leaves."""
    with jax.named_scope("router_bias"):
        loads = counted["pairs_routed"].astype(jnp.float32)
        mean = jnp.mean(loads, axis=-1, keepdims=True)
        n = jnp.maximum(counted["micro_batches"], 1).astype(jnp.float32)
        return {
            "router_bias": state["router_bias"]
            + cfg.bias_update_rate * jnp.sign(mean - loads),
            "pairs_routed": state["pairs_routed"] + counted["pairs_routed"],
            "pairs_computed": state["pairs_computed"]
            + counted["pairs_computed"],
            "steps": state["steps"] + 1,
            "main_loss": counted["main_loss"] / n,
            "mtp_loss": counted["mtp_loss"] / n,
        }


def model_state_report(cfg: JoyAIFlashConfig, state: Dict) -> Dict[str, float]:
    """Host-side, from a fetched state: what the tracer's ``train/
    model_state`` record says (read once, after the window or at
    ``engine.close()``)."""
    import numpy as np

    routed = np.asarray(state["pairs_routed"], np.float64)
    computed = np.asarray(state["pairs_computed"], np.float64)
    total = routed.sum()
    return {
        "steps": int(state["steps"]),
        "moe_pairs_held_share": float(computed.sum() / total) if total
        else 0.0,
        "moe_load_max_share": float(
            (computed.max(axis=-1) / np.maximum(computed.sum(axis=-1), 1)
             ).max()),
        "router_bias_abs_max": float(np.abs(
            np.asarray(state["router_bias"])).max()),
        "main_loss": float(state["main_loss"]),
        "mtp_loss": float(state["mtp_loss"]),
    }


# --------------------------------------------------------------------- #
# Building blocks
# --------------------------------------------------------------------- #
def apply_rope_interleaved(x, cos, sin):
    """``rope_interleave``: the pairs are neighbours, ``(x[2i], x[2i+1])``
    turns by ``pos · freq_i`` and stays where it was.  ``x`` [T, ..., rd],
    tables [T, rd/2], float32 inside.  (HF's modelling code de-interleaves
    first and rotates halves: the same rotation with the outputs permuted
    alike in q and k, so every q·k is the same.)"""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[-1],)
    c, s = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = (x[..., i::2].astype(jnp.float32) for i in (0, 1))
    return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def moe_block(h, lp: Dict, bias, cfg: JoyAIFlashConfig):
    """``h`` [T, D] (normed) → (``Σ w_e · SwiGLU_e(h)`` over the experts
    held + the shared expert [T, D], pairs per router output [E_all] int32
    over the whole batch, pairs computed per expert held [held] int32)."""
    offset = None if cfg.held == cfg.n_routed_experts else cfg.expert_offset

    def block(h, router, experts, shared, bias):
        with jax.named_scope("moe/route"):
            idx, weights = sigmoid_topk_route(
                h, {"kernel": router, "bias": bias}, cfg.num_experts_per_tok,
                cfg.routed_scaling_factor, cfg.norm_topk_prob)
            routed_to = jnp.zeros((cfg.n_routed_experts,), jnp.int32
                                  ).at[idx.reshape(-1)].add(1)
        out, pairs = dropless_experts(h, idx, weights, experts, offset=offset,
                                      trained=True)
        with jax.named_scope("moe/shared"):
            gate = checkpoint_name(h @ shared["gate"], SHARED_NAMES[0])
            up = checkpoint_name(h @ shared["up"], SHARED_NAMES[1])
            out = out + (jax.nn.silu(gate) * up) @ shared["down"]
        return out, routed_to, pairs[:cfg.held]

    # each data shard routes its own tokens in a region manual over the
    # whole mesh (a grouped-matmul kernel cannot be partitioned by GSPMD:
    # the Mixtral block's decision, with no capacity to divide) and the
    # loads are summed over the batch axes; None = one program, whose counts
    # are of the whole batch by construction
    region = _routing_groups(h.shape[0], 0)
    args = (h, lp["router"]["kernel"], lp["experts"], lp["shared"], bias)
    if region is None:
        return block(*args)
    mesh, axes = region

    def local(*args):
        out, routed_to, pairs = block(*args)
        return out, jax.lax.psum(routed_to, axes), jax.lax.psum(pairs, axes)

    whole = jax.tree.map(lambda x: P(), args[1:])
    return _topo.compat_shard_map(
        local, mesh, (P(axes, None),) + whole, (P(axes, None), P(), P()))(
        *args)


def _remat_layout(cfg: JoyAIFlashConfig, batch: int, seq_len: int,
                  itemsize: int):
    """What ``checkpointing.layer_policy`` chooses from, for one device: an
    expert layer's named values — the queries after ``q_b``, the EXPANDED
    k/v after ``kv_b``, the flash kernel's output and row statistics, the
    residual after attention, the shared expert's gate and up, the rows of
    the three grouped matmuls over the SORTED (token, choice) pairs — each
    with its bytes and the seconds its FLOPs take at the MXU's peak, and
    the bytes the step needs beside them: one head's float32 logits, log-
    softmax and cotangent (each head and its loss is a checkpoint of its
    own), or one layer's backward pass (every named value again with a
    cotangent, and the pairs' gathered rows, the experts' outputs sorted
    and unsorted and their cotangents), whichever is more.  The leading
    dense layer shares the names it has; its wider gate and up are always
    made again (one layer of ``num_layers + 1``)."""
    from ..ops.transformer.flash_attention import LSE_NAME, OUT_NAME
    from ..profiling.roofline import device_spec
    from ..runtime.activation_checkpointing.checkpointing import Saveable

    topo = _topo._TOPOLOGY
    shards = 1
    if topo is not None:
        over_batch = math.prod(topo.dims[a] for a in _BATCH_AXES)
        shards = over_batch if batch % over_batch == 0 else 1
    spec = device_spec(None if topo is None else topo.mesh.devices.flat[0])
    rows = batch * seq_len // shards
    D, H, V = cfg.hidden_size, cfg.num_heads, cfg.vocab_size
    F = cfg.moe_intermediate_size
    q_w = H * cfg.qk_head_dim
    kv_w = H * (cfg.qk_nope_head_dim + cfg.v_head_dim)
    o_w = H * cfg.v_head_dim

    def matmul(name, width, contract, rows=rows, flop_rows=None):
        return Saveable(
            (name,), rows * width * itemsize,
            2.0 * (rows if flop_rows is None else flop_rows) * contract
            * width / spec.peak_flops)

    pairs = rows * cfg.num_experts_per_tok
    pair_rows = whole_tiles(pairs, cfg.held)
    here = pairs * cfg.held // cfg.n_routed_experts     # expected, balanced
    gate, up, down = PAIR_ROW_NAMES
    tensors = [
        matmul(Q_NAME, q_w, cfg.q_lora_rank),
        matmul(KV_NAME, kv_w, cfg.kv_lora_rank),
        matmul("attn_residual", D, o_w),
        matmul(SHARED_NAMES[0], F * cfg.n_shared_experts, D),
        matmul(SHARED_NAMES[1], F * cfg.n_shared_experts, D),
        matmul(gate, F, D, rows=pair_rows, flop_rows=here),
        matmul(up, F, D, rows=pair_rows, flop_rows=here),
        matmul(down, D, F, rows=pair_rows, flop_rows=here),
    ]
    if _attn_impl(cfg, seq_len) == "flash":
        # causal: half of q·kᵀ (192 wide) and of p·v (128 wide) a head
        tensors.append(Saveable(
            (OUT_NAME, LSE_NAME), rows * o_w * itemsize + rows * H * 4,
            rows * seq_len * (q_w + o_w) / spec.peak_flops))
    head = 3 * rows * V * 4
    layer_backward = 2 * sum(t.bytes for t in tensors) \
        + 2 * 3 * pair_rows * D * itemsize
    return tensors, max(head, layer_backward)


# --------------------------------------------------------------------- #
# Forward and loss
# --------------------------------------------------------------------- #
def _layer_fn(cfg: JoyAIFlashConfig, moe: bool, cos, sin):
    """One decoder layer on ``x`` [B, S, D]: ``(x, (lp, bias row or None))
    → (x, (pairs routed [E_all], pairs computed [held]))``."""
    H, dn, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim

    def layer(x, xs):
        lp, bias = xs
        B, S, D = x.shape
        with jax.named_scope("attention"):
            h = rms_norm(x, lp["attn_norm"]["scale"], cfg.norm_eps
                         ).reshape(B * S, D)
            with jax.named_scope("mla_q"):
                q_nope, q_rope = mla_query(h, lp, cos, sin, cfg,
                                           rope=apply_rope_interleaved)
                q = checkpoint_name(
                    jnp.concatenate([q_nope, q_rope], axis=-1), Q_NAME)
            with jax.named_scope("mla_kv"):
                latent = mla_latent(h, lp, cos, sin, cfg,
                                    rope=apply_rope_interleaved)
                c_kv = latent[:, :cfg.kv_lora_rank]
                k_rope = latent[:, cfg.kv_lora_rank:cfg.latent_dim]
                # the expanded form: every head's k_nope and v from the
                # latent (``mla_up_weights`` is this matrix's two views)
                kv = checkpoint_name(c_kv @ lp["kv_b_proj"]["kernel"],
                                     KV_NAME).reshape(B * S, H, dn + dv)
                k = jnp.concatenate(
                    [kv[..., :dn], jnp.broadcast_to(
                        k_rope[:, None, :],
                        (B * S, H, cfg.qk_rope_head_dim))], axis=-1)
                v = kv[..., dn:]
            with jax.named_scope("mla_core"):
                # q/k 192 wide, v 128: the kernel's own scale is 1/sqrt(192)
                o = attention(q.reshape(B, S, H, -1), k.reshape(B, S, H, -1),
                              v.reshape(B, S, H, dv), cfg, causal=True)
            x = x + o.reshape(B, S, H * dv) @ lp["o_proj"]["kernel"]
        x = checkpoint_name(_constrain(x, _activation_spec()),
                            "attn_residual")
        h = rms_norm(x, lp["mlp_norm"]["scale"], cfg.norm_eps)
        if not moe:
            with jax.named_scope("mlp"):
                gate = jax.nn.silu(h @ lp["gate_proj"]["kernel"])
                out = (gate * (h @ lp["up_proj"]["kernel"])
                       ) @ lp["down_proj"]["kernel"]
            counted = None
        else:
            out, routed_to, pairs = moe_block(h.reshape(B * S, D), lp, bias,
                                              cfg)
            out, counted = out.reshape(B, S, D), (routed_to, pairs)
        x = checkpoint_name(_constrain(x + out, _activation_spec()),
                            "mlp_residual")
        return x, counted

    return layer


def _head_loss(cfg: JoyAIFlashConfig, params: Dict, x, labels):
    """The shared head and a cross entropy: ``x`` [B, S, D] (normed) →
    (mean over the positions whose label is >= 0, logits or None).  A
    checkpoint of its own under ``cfg.remat``: the backward makes the
    float32 logits again and the two heads' never live together."""
    def fn(x, w, labels):
        with jax.named_scope("lm_head"):
            logits = x @ w
        with jax.named_scope("loss"):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            valid = labels >= 0
            picked = jnp.take_along_axis(
                logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
            return -jnp.sum(picked * valid) / jnp.maximum(jnp.sum(valid), 1)

    w = params["embed"]["embedding"].T if cfg.tie_embeddings \
        else params["lm_head"]["kernel"]
    if cfg.remat:
        fn = jax.checkpoint(fn)
    return fn(x, w, labels)


def forward(params: Dict, tokens: jax.Array, cfg: JoyAIFlashConfig,
            router_bias=None) -> Tuple[jax.Array, Optional[jax.Array], Dict]:
    """tokens [B, S] int32 → (the main model's output after its final norm
    [B, S, D], the MTP module's after its own (None without one), counted:
    ``pairs_routed`` [expert layers, E_all] and ``pairs_computed`` [expert
    layers, held]).  ``router_bias`` [expert layers, E_all] (None: zeros)."""
    from ..runtime.activation_checkpointing import checkpointing as ac

    B, S = tokens.shape
    dtype = params["norm_f"]["scale"].dtype
    if router_bias is None:
        router_bias = jnp.zeros((cfg.num_expert_layers, cfg.n_routed_experts),
                                jnp.float32)
    embedding = params["embed"]["embedding"]
    with jax.named_scope("embed"):
        x = _constrain(jnp.take(embedding, tokens, axis=0),
                       _activation_spec())
    # no scaling: the plain tables at the rotary width, one row a token
    cos, sin = rope_at(jnp.tile(jnp.arange(S), B), cfg.qk_rope_head_dim,
                       cfg.rope_theta)

    policy = None
    if cfg.remat:
        policy = ac.resolve_policy(
            cfg.remat_policy,
            lambda: _remat_layout(cfg, B, S, jnp.dtype(dtype).itemsize),
            layers=cfg.num_layers + cfg.num_mtp_layers)

    def run(x, stack, bias, moe):
        fn = _layer_fn(cfg, moe, cos, sin)
        if policy is not None:
            fn = jax.checkpoint(fn, policy=policy)
        return jax.lax.scan(fn, x, (stack, bias))

    Lm = cfg.num_moe_layers
    routed, computed = [], []
    with jax.named_scope("layers"):
        if cfg.num_dense_layers:
            x, _ = run(x, params["dense_layers"], None, False)
        if Lm:
            x, (r, c) = run(x, params["moe_layers"], router_bias[:Lm], True)
            routed.append(r)
            computed.append(c)
    with jax.named_scope("final_norm"):
        h_main = rms_norm(x, params["norm_f"]["scale"], cfg.norm_eps)
    h_mtp = None
    if cfg.num_mtp_layers:
        mp = params["mtp"]
        with jax.named_scope("mtp"):
            # position i holds t_{i+1}; past the end: id 0 (in no loss)
            nxt = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
            with jax.named_scope("embed"):
                e = jnp.take(embedding, nxt, axis=0)
            with jax.named_scope("eh_proj"):
                both = jnp.concatenate(
                    [rms_norm(e, mp["enorm"]["scale"], cfg.norm_eps),
                     rms_norm(h_main, mp["hnorm"]["scale"], cfg.norm_eps)],
                    axis=-1)
                y = _constrain(both @ mp["eh_proj"]["kernel"],
                               _activation_spec())
            y, (r, c) = run(y, mp["layers"], router_bias[Lm:], True)
            routed.append(r)
            computed.append(c)
            with jax.named_scope("final_norm"):
                h_mtp = rms_norm(y, mp["norm"]["scale"], cfg.norm_eps)
    counted = {"pairs_routed": jnp.concatenate(routed, axis=0),
               "pairs_computed": jnp.concatenate(computed, axis=0)}
    return h_main, h_mtp, counted


def lm_loss(params: Dict, batch: Any, cfg: JoyAIFlashConfig,
            model_state: Optional[Dict] = None):
    """``(loss, counted)``: ``CE(main, t_{i+1}) + mtp_loss_weight · CE(mtp,
    t_{i+2})``, each a mean over the positions that have a target, and what
    the forward counted (``next_model_state`` takes it)."""
    tokens = batch["input_ids"] if isinstance(batch, dict) else batch
    bias = None if model_state is None else model_state["router_bias"]
    h_main, h_mtp, counted = forward(params, tokens, cfg, router_bias=bias)
    pad = lambda t, n: jnp.pad(t[:, n:], ((0, 0), (0, n)),   # noqa: E731
                               constant_values=-100)
    main = _head_loss(cfg, params, h_main, pad(tokens, 1))
    mtp = jnp.zeros((), jnp.float32)
    if h_mtp is not None:
        with jax.named_scope("mtp"):
            mtp = _head_loss(cfg, params, h_mtp, pad(tokens, 2))
    counted = dict(counted, main_loss=main, mtp_loss=mtp,
                   micro_batches=jnp.ones((), jnp.int32))
    return main + cfg.mtp_loss_weight * mtp, counted


def logits(params: Dict, tokens, cfg: JoyAIFlashConfig, router_bias=None):
    """Both heads' logits [B, S, V] (the MTP's None without a module): what
    the tests and the benchmark hold against the reference."""
    h_main, h_mtp, _ = forward(params, tokens, cfg, router_bias)
    w = params["embed"]["embedding"].T if cfg.tie_embeddings \
        else params["lm_head"]["kernel"]
    return h_main @ w, None if h_mtp is None else h_mtp @ w


class JoyAIFlashLM:
    """Model object ``deepspeed_tpu.initialize`` takes: ``loss_fn``,
    ``partition_specs``, ``init_params`` like ``CausalLM``, and the state
    the optimizer does not train (``init_model_state`` / ``next_model_state``
    / ``model_state_report``: the engine carries it, see ``runtime/
    engine.py``).  Loading a checkpoint's tensors is out of scope."""

    def __init__(self, cfg: JoyAIFlashConfig):
        self.config = cfg
        self.partition_specs = partition_specs(cfg)

    @classmethod
    def from_hf_config(cls, hf: Dict, **overrides) -> "JoyAIFlashLM":
        return cls(JoyAIFlashConfig.from_hf(hf, **overrides))

    def init_params(self, key: jax.Array, dtype=jnp.float32):
        return init_params(self.config, key, dtype)

    def init_model_state(self):
        return init_model_state(self.config)

    def next_model_state(self, state, counted):
        return next_model_state(self.config, state, counted)

    def model_state_report(self, state):
        return model_state_report(self.config, state)

    def loss_fn(self, params, batch, rng, model_state=None):
        return lm_loss(params, batch, self.config, model_state)

    def __call__(self, params, tokens, router_bias=None):
        return logits(params, tokens, self.config, router_bias)

    def serving_family(self):
        raise NotImplementedError(
            "joyai_flash: this family is trained only; serving it (latent "
            "pages as models/xing4.py has them, the MTP module as a "
            "self-drafter) is not written")

    def num_params(self, params=None) -> int:
        if params is None:
            params = jax.eval_shape(lambda k: self.init_params(k),
                                    jax.random.PRNGKey(0))
        return int(sum(math.prod(leaf.shape)
                       for leaf in jax.tree.leaves(params)))
