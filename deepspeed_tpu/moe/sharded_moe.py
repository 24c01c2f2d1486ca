"""Mixture-of-Experts layer: gating, dispatch, stacked expert FFN, combine.

Reference analogue: ``deepspeed/moe/sharded_moe.py`` — top1/top2/topk gating
(:183,:290,:374), ``MOELayer`` einsum dispatch → all-to-all → experts →
all-to-all → combine (:533,:586), capacity/drop logic, load-balance aux loss.

Gating produces either dense one-hot dispatch/combine tensors [S, E, C] (the
GShard einsums, kept as the numerics oracle) or flat slot ids (scatter-add /
gather, linear in tokens); shapes are static (capacity padding), so
everything is jit-compatible.  :func:`moe_mlp_block` routes the WHOLE batch
as one group on every mesh — one capacity, slots filled in token order, the
balance term over all tokens — and what it lowers to depends on the mesh it
observes at trace time:

  * no mesh, one device, or inside a region that is manual already (the
    explicit-comm step, manual over the batch axes): plain local code on the
    tokens the call sees, no collective of its own;
  * a mesh whose devices all lie on the batch axes ``data_outer``/``data``
    (G shards; the ZeRO cells), sparse dispatch: one ``shard_map`` over the
    whole mesh.  Each shard routes its own tokens into their GLOBAL slots
    (an all-gather of G×E counts gives the offsets; capacity, drops and the
    balance term are the whole batch's), then applies the experts to ITS
    OWN kept pairs where they are: sorted by expert, through a grouped
    matmul over the real rows (``dropless.grouped_matmul``), summed back per
    token with the gate's weights.  Only the router's counts cross chips
    under ``moe/*``.  The expert weights enter replicated, so under ZeRO-3
    GSPMD all-gathers each bf16 weight at the region's boundary and
    reduce-scatters its gradient on the way back, like every other
    parameter — and since they are whole on every chip there, which chip
    computes a kept pair is free to choose.  (Until PR 31 a pair was
    computed in its global SLOT: the shards' [E, C, D] dispatch buffers
    were reduce-scattered so that each kept C/G slots of every expert, and
    the outputs all-gathered back — two collectives of the buffer a pass,
    and matmuls over ``E·C/G`` capacity-padded rows a chip, twice its own
    pairs at capacity factor 2.  Capacity per SHARD, the reference's
    ``TopKGate`` semantics, drops pairs the global capacity keeps: a
    sequence's tokens lean to the same few experts, so a shard's fullest
    expert runs over 2 × its mean long before the batch's does.  PERF.md
    section 6, PR 26 and PR 31.)
  * any other axis of more than one device (``expert``: expert-parallel
    weights, :func:`moe_partition_specs`; ``tensor``, ``seq``, ``pipe``), a
    token count or capacity that G does not divide, or the dense [S, E, C]
    dispatch (the oracle): the same function as one program under GSPMD,
    on the capacity-padded [E, C, D] buffer like the one-device program.  The partitioner then chooses the exchange
    itself — on a data-only mesh it was an all-reduce of the [E, C, F]
    expert activations, not the reference's all-to-all (:96 ``_AllToAll``);
    an all-to-all over ``expert`` is a different mechanism that no cell
    runs yet and is not attempted here, and neither is a region manual over
    ``data`` alone beside a tensor-parallel axis.
"""
from __future__ import annotations

import math
import time
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..runtime import topology as _topo
from ..runtime.topology import DATA, DATA_OUTER, EXPERT, get_topology
from ..telemetry import get_tracer
from .dropless import grouped_matmul, whole_tiles


class GateOutput(NamedTuple):
    l_aux: jnp.ndarray          # load-balance loss
    combine: jnp.ndarray        # [S, E, C] float combine weights
    dispatch: jnp.ndarray       # [S, E, C] bool dispatch mask
    exp_counts: jnp.ndarray     # [E] tokens routed per expert (pre-drop)


#: floor of every gate's per-expert capacity
MIN_CAPACITY = 4


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float,
              min_capacity: int) -> int:
    cap = math.ceil(num_tokens / num_experts * capacity_factor)
    return max(cap, min_capacity)


def _group_count(group_axes) -> int:
    """Shards of the batch under ``group_axes`` (static; 1 outside)."""
    return jax.lax.psum(1, group_axes) if group_axes else 1


def _group_sum(x, group_axes):
    return jax.lax.psum(x, group_axes) if group_axes else x


def _one_hot(idx, n):
    return jax.nn.one_hot(idx, n, dtype=jnp.float32)


def _mask_padded_experts(logits: jnp.ndarray,
                         num_experts_logical: Optional[int]) -> Tuple[jnp.ndarray, int]:
    """Routing over a padded expert stack (elastic resharding onto an
    ``ep_size`` that does not divide the expert count pads the stack to the
    next multiple — see :func:`pad_experts_for_ep`): padding columns get
    ``-inf`` logits, so softmax/argmax/top-k are bit-identical to the
    unpadded layer (``exp(-inf) == 0`` leaves every denominator unchanged).
    Returns (masked logits, logical expert count) — capacity and the
    load-balance loss must use the LOGICAL count, or padding would shrink
    per-expert capacity and change routing decisions."""
    E = logits.shape[1]
    if num_experts_logical is None or num_experts_logical >= E:
        return logits, E
    mask = jnp.where(jnp.arange(E) < num_experts_logical, 0.0, -jnp.inf)
    return logits + mask[None, :], int(num_experts_logical)


def _slot_positions(mask, counts, group_axes):
    """One choice's one-hot [S, E] → each token's position among its
    expert's slots [S, E] and the experts' loads over the whole batch [E].
    Tokens fill slots in order, after the ``counts`` slots earlier choices
    took.  Inside a region manual over ``group_axes`` the call sees one
    shard of the batch: the shards' tokens follow each other in mesh order,
    so positions, capacity and drops are those of one global routing."""
    load = jnp.sum(mask, axis=0)
    pos = jnp.cumsum(mask, axis=0) - mask + counts[None, :]
    if group_axes:
        loads = jax.lax.all_gather(load, group_axes)              # [G, E]
        before = jnp.arange(loads.shape[0]) < jax.lax.axis_index(group_axes)
        pos = pos + jnp.sum(loads * before[:, None], axis=0)[None, :]
        load = jnp.sum(loads, axis=0)
    return pos, load


def _topk_balance_loss(gates, ce_total, n_log: int, group_axes):
    """Switch balance term of a top-k gate from the whole batch's loads."""
    me = jnp.mean(gates, axis=0)
    if group_axes:
        me = jax.lax.pmean(me, group_axes)
    ce = ce_total / jnp.maximum(jnp.sum(ce_total), 1.0)
    return jnp.sum(me * ce) * n_log


def top1gating(logits: jnp.ndarray, capacity_factor: float = 1.0,
               min_capacity: int = MIN_CAPACITY,
               noisy_gate_policy: Optional[str] = None,
               rng: Optional[jax.Array] = None, drop_tokens: bool = True,
               used_capacity: Any = None,
               num_experts_logical: Optional[int] = None) -> GateOutput:
    """Switch-style top-1 gating (reference: sharded_moe.py:183)."""
    S, E = logits.shape
    logits, n_log = _mask_padded_experts(logits, num_experts_logical)
    C = _capacity(S, n_log, capacity_factor, min_capacity)
    gates = jax.nn.softmax(logits, axis=1)

    select_logits = logits
    if noisy_gate_policy == "RSample" and rng is not None:
        select_logits = logits + jax.random.gumbel(rng, logits.shape)
    idx = jnp.argmax(select_logits, axis=1)                       # [S]
    mask = _one_hot(idx, E)                                       # [S, E]

    # Load-balance loss (Switch):  E * Σ_e mean_tokens(mask_e) * mean(gates_e)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask, axis=0)
    l_aux = jnp.sum(me * ce) * n_log

    pos = jnp.cumsum(mask, axis=0) - mask                         # position in expert
    if drop_tokens:
        mask = mask * (pos < C)
    pos_in_expert = jnp.sum(pos * mask, axis=1).astype(jnp.int32)  # [S]
    gate_val = jnp.sum(gates * mask, axis=1)                      # [S]

    dispatch = (mask[:, :, None] *
                _one_hot(pos_in_expert, C)[:, None, :])           # [S, E, C]
    combine = dispatch * gate_val[:, None, None]
    return GateOutput(l_aux, combine, dispatch.astype(bool),
                      jnp.sum(_one_hot(idx, E), axis=0).astype(jnp.int32))


def topkgating(logits: jnp.ndarray, k: int = 2, capacity_factor: float = 1.0,
               min_capacity: int = MIN_CAPACITY, drop_tokens: bool = True,
               rng: Optional[jax.Array] = None,
               normalize_weights: bool = True,
               num_experts_logical: Optional[int] = None,
               group_axes: Tuple[str, ...] = ()) -> GateOutput:
    """Top-k gating (reference: sharded_moe.py:374; k=2 ≡ top2gating :290).

    ``group_axes``: the manual mesh axes over which the batch is split, when
    the call sees one shard of it (:func:`_slot_positions`); S then counts
    the shard's tokens, C and every output the whole batch's."""
    S, E = logits.shape
    logits, n_log = _mask_padded_experts(logits, num_experts_logical)
    C = _capacity(S * _group_count(group_axes) * k, n_log, capacity_factor,
                  min_capacity)
    gates = jax.nn.softmax(logits, axis=1)

    topk_val, topk_idx = jax.lax.top_k(gates, k)                  # [S, k]
    if normalize_weights:
        topk_val = topk_val / jnp.sum(topk_val, axis=1, keepdims=True)

    # masks per choice, cumulative positions account for earlier choices
    combine = jnp.zeros((S, E, C), jnp.float32)
    dispatch = jnp.zeros((S, E, C), jnp.bool_)
    counts = jnp.zeros((E,), jnp.float32)                          # running per-expert fill
    ce_total = jnp.zeros((E,), jnp.float32)
    for choice in range(k):
        idx = topk_idx[:, choice]
        mask = _one_hot(idx, E)                                   # [S, E]
        pos, load = _slot_positions(mask, counts, group_axes)
        ce_total = ce_total + load
        if drop_tokens:
            mask = mask * (pos < C)
        counts = counts + _group_sum(jnp.sum(mask, axis=0), group_axes)
        pos_in_expert = jnp.sum(pos * mask, axis=1).astype(jnp.int32)
        d = mask[:, :, None] * _one_hot(pos_in_expert, C)[:, None, :]
        dispatch = jnp.logical_or(dispatch, d.astype(bool))
        combine = combine + d * topk_val[:, choice][:, None, None]

    l_aux = _topk_balance_loss(gates, ce_total, n_log, group_axes)
    return GateOutput(l_aux, combine, dispatch, ce_total.astype(jnp.int32))


def top2gating(logits, capacity_factor: float = 1.0,
               min_capacity: int = MIN_CAPACITY, **kw) -> GateOutput:
    return topkgating(logits, k=2, capacity_factor=capacity_factor,
                      min_capacity=min_capacity, **kw)


# --------------------------------------------------------------------- #
# Sparse (scatter/gather) dispatch — the scalable path
# --------------------------------------------------------------------- #
class SparseGateOutput(NamedTuple):
    """Routing as flat slot ids instead of dense [S,E,C] one-hots.

    ``slot[s, choice]`` = expert*C + position-in-expert, or E*C (a trash row)
    when the token was dropped; ``gate_val`` carries the combine weight
    (zeroed for drops).  Dispatch becomes an O(S·D) scatter-add and combine
    an O(S·D) gather — vs the dense einsum's O(S·E·C·D) ≈ O(S²·k·D), which
    is quadratic in routing-chunk tokens (reference sharded_moe.py:533's
    einsum dispatch has the same blowup; its sort-based top-k path :374 is
    the analogue of this).
    """
    l_aux: jnp.ndarray
    slot: jnp.ndarray           # [S, k] int32
    gate_val: jnp.ndarray       # [S, k] f32
    exp_counts: jnp.ndarray     # [E]
    capacity: int


def top1gating_sparse(logits: jnp.ndarray, capacity_factor: float = 1.0,
                      min_capacity: int = MIN_CAPACITY,
                      noisy_gate_policy: Optional[str] = None,
                      rng: Optional[jax.Array] = None,
                      drop_tokens: bool = True,
                      num_experts_logical: Optional[int] = None) -> SparseGateOutput:
    """Sparse-form top-1 gating; routing decisions identical to top1gating."""
    S, E = logits.shape
    logits, n_log = _mask_padded_experts(logits, num_experts_logical)
    C = _capacity(S, n_log, capacity_factor, min_capacity)
    gates = jax.nn.softmax(logits, axis=1)

    select_logits = logits
    if noisy_gate_policy == "RSample" and rng is not None:
        select_logits = logits + jax.random.gumbel(rng, logits.shape)
    idx = jnp.argmax(select_logits, axis=1)
    mask = _one_hot(idx, E)

    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask, axis=0)
    l_aux = jnp.sum(me * ce) * n_log

    pos = jnp.cumsum(mask, axis=0) - mask
    if drop_tokens:
        mask = mask * (pos < C)
    kept = jnp.sum(mask, axis=1) > 0
    pos_in_expert = jnp.sum(pos * mask, axis=1).astype(jnp.int32)
    gate_val = jnp.sum(gates * mask, axis=1)
    # Beyond-capacity tokens always go to the trash row: the dense path's
    # one_hot(pos>=C, C) row is all-zeros, i.e. a silent zero-contribution —
    # a raw idx*C+pos slot would land in the NEXT expert's rows.
    kept = jnp.logical_and(kept, pos_in_expert < C)
    slot = jnp.where(kept, idx.astype(jnp.int32) * C + pos_in_expert, E * C)
    counts = jnp.sum(_one_hot(idx, E), axis=0).astype(jnp.int32)
    return SparseGateOutput(l_aux, slot[:, None], gate_val[:, None], counts, C)


def topkgating_sparse(logits: jnp.ndarray, k: int = 2,
                      capacity_factor: float = 1.0,
                      min_capacity: int = MIN_CAPACITY,
                      drop_tokens: bool = True,
                      rng: Optional[jax.Array] = None,
                      normalize_weights: bool = True,
                      valid: Optional[jnp.ndarray] = None,
                      num_experts_logical: Optional[int] = None,
                      group_axes: Tuple[str, ...] = ()) -> SparseGateOutput:
    """Sparse-form top-k gating; routing decisions identical to topkgating.

    ``valid`` [S] bool: tokens marked invalid (ragged-batch padding) are
    routed to the trash slot and consume no expert capacity.
    ``group_axes``: as in :func:`topkgating`.
    """
    S, E = logits.shape
    logits, n_log = _mask_padded_experts(logits, num_experts_logical)
    C = _capacity(S * _group_count(group_axes) * k, n_log, capacity_factor,
                  min_capacity)
    gates = jax.nn.softmax(logits, axis=1)

    topk_val, topk_idx = jax.lax.top_k(gates, k)
    if normalize_weights:
        topk_val = topk_val / jnp.sum(topk_val, axis=1, keepdims=True)

    slots, vals = [], []
    counts = jnp.zeros((E,), jnp.float32)
    ce_total = jnp.zeros((E,), jnp.float32)
    for choice in range(k):
        idx = topk_idx[:, choice]
        mask = _one_hot(idx, E)
        if valid is not None:
            mask = mask * valid.astype(jnp.float32)[:, None]
        pos, load = _slot_positions(mask, counts, group_axes)
        ce_total = ce_total + load
        if drop_tokens:
            mask = mask * (pos < C)
        counts = counts + _group_sum(jnp.sum(mask, axis=0), group_axes)
        kept = jnp.sum(mask, axis=1) > 0
        pos_in_expert = jnp.sum(pos * mask, axis=1).astype(jnp.int32)
        # beyond-capacity → trash row (dense one_hot(pos>=C) is all-zeros)
        kept = jnp.logical_and(kept, pos_in_expert < C)
        slots.append(jnp.where(kept, idx.astype(jnp.int32) * C + pos_in_expert,
                               E * C))
        vals.append(jnp.where(kept, topk_val[:, choice], 0.0))

    l_aux = _topk_balance_loss(gates, ce_total, n_log, group_axes)
    return SparseGateOutput(l_aux, jnp.stack(slots, axis=1),
                            jnp.stack(vals, axis=1),
                            ce_total.astype(jnp.int32), C)


def dispatch_sparse(slot: jnp.ndarray, tokens: jnp.ndarray, num_experts: int,
                    capacity: int, dtype) -> jnp.ndarray:
    """[S,k] slots × [S,D] tokens → [E,C,D] via scatter-add (O(S·k·D))."""
    S, D = tokens.shape
    EC = num_experts * capacity
    flat = jnp.zeros((EC + 1, D), dtype)          # +1 trash row for drops
    t = tokens.astype(dtype)
    for choice in range(slot.shape[1]):
        flat = flat.at[slot[:, choice]].add(t)
    return flat[:EC].reshape(num_experts, capacity, D)


def _pin_replicated(x: jnp.ndarray) -> jnp.ndarray:
    """Constrain ``x`` fully replicated on the global mesh (no-op on a
    trivial mesh or inside a manual shard_map region).

    Guards the sparse combine's gather against a GSPMD miscompile: with
    ``expert_out`` sharded on the expert axis and slots/tokens carrying a
    batch sharding, GSPMD partitions ``jnp.take`` into per-shard gathers
    and sums the partial contributions over EVERY replica group — including
    the pure data-replica groups — so the combined output comes back
    multiplied by the data-axis size (observed exactly 4x on an 8-device
    data4×expert2 mesh; same bug class PR 8 fixed in ``paged_kv_append``'s
    row-scatter).  Replicating the gather operand first makes the gather
    local and keeps the cross-expert exchange as one explicit all-gather.
    """
    topo = _topo._TOPOLOGY
    if topo is None or topo.mesh.size <= 1:
        return x
    _, manual = _topo.shard_map_context(topo)
    if manual:
        # inside a partial-manual region constraint specs may not name
        # manual axes; the manual body already owns its collectives
        return x
    from jax.sharding import NamedSharding

    return jax.lax.with_sharding_constraint(
        x, NamedSharding(topo.mesh, P()))


def combine_sparse(slot: jnp.ndarray, gate_val: jnp.ndarray,
                   expert_out: jnp.ndarray, dtype) -> jnp.ndarray:
    """[S,k] slots + weights × [E,C,D] expert outputs → [S,D] via gather."""
    E, C, D = expert_out.shape
    flat = jnp.concatenate(
        [expert_out.reshape(E * C, D),
         jnp.zeros((1, D), expert_out.dtype)], axis=0)
    flat = _pin_replicated(flat)
    out = None
    for choice in range(slot.shape[1]):
        contrib = gate_val[:, choice, None].astype(dtype) * \
            jnp.take(flat, slot[:, choice], axis=0).astype(dtype)
        out = contrib if out is None else out + contrib
    return out


# --------------------------------------------------------------------- #
# Expert FFN + MOELayer
# --------------------------------------------------------------------- #
def init_moe_params(key, hidden: int, ffn: int, num_experts: int,
                    dtype=jnp.float32) -> Dict:
    """Gate + stacked expert FFN params (reference Experts: moe/experts.py:13)."""
    k1, k2, k3 = jax.random.split(key, 3)
    scale1 = 1.0 / math.sqrt(hidden)
    scale2 = 1.0 / math.sqrt(ffn)
    return {
        "gate": {"kernel": (jax.random.normal(k1, (hidden, num_experts)) * scale1
                            ).astype(jnp.float32)},  # gate stays fp32 (reference keeps it)
        "experts": {
            "w1": (jax.random.normal(k2, (num_experts, hidden, ffn)) * scale1).astype(dtype),
            "b1": jnp.zeros((num_experts, ffn), dtype),
            "w2": (jax.random.normal(k3, (num_experts, ffn, hidden)) * scale2).astype(dtype),
            "b2": jnp.zeros((num_experts, hidden), dtype),
        },
    }


def moe_partition_specs() -> Dict:
    """Expert weights sharded over the "expert" mesh axis; gate replicated."""
    return {
        "gate": {"kernel": P(None, None)},
        "experts": {
            "w1": P(EXPERT, None, None),
            "b1": P(EXPERT, None),
            "w2": P(EXPERT, None, None),
            "b2": P(EXPERT, None),
        },
    }


def dispatch_to_experts(dispatch: jnp.ndarray, tokens: jnp.ndarray,
                        dtype) -> jnp.ndarray:
    """[S,E,C] mask × [S,D] tokens → [E,C,D] expert inputs (the GShard
    dispatch einsum; shared by moe_layer and the MoE transformer block)."""
    return jnp.einsum("sec,sd->ecd", dispatch.astype(dtype), tokens.astype(dtype))


def combine_from_experts(combine: jnp.ndarray, expert_out: jnp.ndarray,
                         dtype) -> jnp.ndarray:
    """[S,E,C] weights × [E,C,D] expert outputs → [S,D]."""
    return jnp.einsum("sec,ecd->sd", combine.astype(dtype), expert_out)


def _routing_groups(num_tokens: int, capacity: int):
    """``(mesh, batch axes)`` when each data shard computes its own pairs
    in a region of its own: the mesh's batch axes (what
    ``models/transformer.py`` lays every activation's batch dimension over)
    hold all of its G > 1
    devices — no expert, tensor, sequence or pipeline parallelism, whose
    exchanges are other mechanisms that no cell runs — no axis is manual
    already, and G divides the token count and the capacity.  The region is
    then manual over the whole mesh (one manual over some axes only aborted
    jax 0.9's CPU compiler on a bf16 collective, PR 26).  ``None`` = one
    program under GSPMD."""
    topo = _topo._TOPOLOGY
    if topo is None or topo.mesh.size <= 1:
        return None
    mesh, manual = _topo.shard_map_context(topo)
    axes = tuple(a for a in (DATA_OUTER, DATA) if topo.dims[a] > 1)
    groups = math.prod(topo.dims[a] for a in axes)
    if manual or groups != mesh.size \
            or num_tokens % groups or capacity % groups:
        return None
    return mesh, axes


def moe_mlp_block(lp: Dict, tokens: jnp.ndarray, k: int = 2,
                  capacity_factor: float = 2.0, dispatch_impl: str = "sparse",
                  rng: Optional[jax.Array] = None,
                  valid: Optional[jnp.ndarray] = None,
                  num_experts_logical: Optional[int] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mixtral-style routed SwiGLU expert MLP over flat tokens [T, D].

    ``lp`` carries router [D,E] (f32) + stacked expert weights
    gate_proj/up_proj [E,D,F], down_proj [E,F,D].  Shared by the training
    transformer (models/transformer.py) and the ragged serving runner, so
    train and serve route identically.  Router always runs in f32 (the
    reference keeps the gate fp32; under bf16 compute we re-cast to preserve
    routing decisions).

    The routing is one function of the whole batch on every mesh: capacity
    ``ceil(T × k / E × capacity_factor)``, slots filled in token order.
    Where the tokens are sharded over the batch axes (module docstring,
    :func:`_routing_groups`) each shard computes its own kept pairs
    (:func:`_experts_on_own_pairs`).  Every traced call leaves one
    ``moe/layout`` record on the tracer saying which program it became:
    ``compute`` ``grouped`` with the ``rows_per_group`` a shard's grouped
    matmul is handed, or ``padded`` with the ``E·C`` rows of the slot
    buffer; ``padded_rows_per_group`` is ``E·C/G`` either way.
    """
    assert dispatch_impl in ("sparse", "dense"), dispatch_impl
    sparse = dispatch_impl == "sparse"
    assert sparse or valid is None, \
        "ragged validity masks need dispatch_impl='sparse'"
    dtype = lp["gate_proj"]["kernel"].dtype
    T, E = tokens.shape[0], lp["router"]["kernel"].shape[1]
    capacity = _capacity(T * k, min(num_experts_logical or E, E),
                         capacity_factor, MIN_CAPACITY)
    # the dense [S, E, C] one-hots (the oracle) cannot feed a grouped matmul
    grouped = _routing_groups(T, capacity) if sparse else None
    mesh, group_axes = grouped or (None, ())
    groups = math.prod(mesh.shape[a] for a in group_axes)
    # a shard's (token, choice) pairs, to a whole row tile
    rows = whole_tiles(T // groups * k, E)
    # trace time only: what a run says about the program it compiled
    get_tracer().record(
        "moe/layout", time.perf_counter(), 0.0, groups=groups,
        tokens_per_group=T // groups, capacity=capacity, experts=E,
        local=grouped is not None,
        compute="grouped" if grouped else "padded",
        rows_per_group=rows if grouped else E * capacity,
        padded_rows_per_group=E * capacity // groups)

    def routed(tokens, valid, rng, router, w_gate, w_up, w_down):
        # one name scope per phase: device time (and every collective) shows
        # up under moe/route, moe/dispatch, moe/experts, moe/combine
        with jax.named_scope("moe/route"):
            logits_r = tokens.astype(jnp.float32) @ router.astype(jnp.float32)
            gating = dict(k=k, capacity_factor=capacity_factor, rng=rng,
                          num_experts_logical=num_experts_logical,
                          group_axes=group_axes)
            if sparse:
                gate_out = topkgating_sparse(logits_r, valid=valid, **gating)
            else:
                gate_out = topkgating(logits_r, **gating)
            assert capacity == (gate_out.capacity if sparse
                                else gate_out.dispatch.shape[2])
        if group_axes:
            return _experts_on_own_pairs(
                gate_out, tokens, rows, w_gate, w_up, w_down), gate_out.l_aux
        with jax.named_scope("moe/dispatch"):
            if sparse:
                dispatched = dispatch_sparse(gate_out.slot, tokens, E,
                                             capacity, dtype)
            else:
                dispatched = dispatch_to_experts(gate_out.dispatch, tokens,
                                                 dtype)
        with jax.named_scope("moe/experts"):
            act = jax.nn.silu(jnp.einsum("ecd,edf->ecf", dispatched, w_gate))
            up = jnp.einsum("ecd,edf->ecf", dispatched, w_up)
            eo = jnp.einsum("ecf,efd->ecd", act * up, w_down)
        with jax.named_scope("moe/combine"):
            if sparse:
                out = combine_sparse(gate_out.slot, gate_out.gate_val, eo,
                                     dtype)
            else:
                out = combine_from_experts(gate_out.combine, eo, dtype)
        return out, gate_out.l_aux

    if grouped is not None:
        # the weights come in whole: ZeRO-3's gather lands at this boundary,
        # outside moe/*
        shard, whole = P(group_axes), P()
        routed = _topo.compat_shard_map(
            routed, mesh,
            in_specs=(shard, None if valid is None else shard,
                      None if rng is None else whole,
                      whole, whole, whole, whole),
            out_specs=(shard, whole))
    return routed(tokens, valid, rng, lp["router"]["kernel"],
                  lp["gate_proj"]["kernel"], lp["up_proj"]["kernel"],
                  lp["down_proj"]["kernel"])


#: ``checkpoint_name`` tags in :func:`_experts_on_own_pairs`: the rows out of
#: the three grouped matmuls, and the three expert stacks as a shard holds
#: them WHOLE — under ZeRO-3 what the gather at the region's boundary made.
#: A checkpointed layer that saves them (``models/transformer.py``
#: ``_remat_layout``) runs neither the matmuls nor the gathers twice.
ROW_NAMES = ("expert_gate_rows", "expert_up_rows", "expert_down_rows")
STACK_NAMES = ("expert_gate_whole", "expert_up_whole", "expert_down_whole")


def _experts_on_own_pairs(gate_out: SparseGateOutput, tokens, rows: int,
                          w_gate, w_up, w_down):
    """One shard's kept (token, choice) pairs through their experts, sorted
    by expert, with a grouped matmul over ``rows`` rows (the pairs, to a
    whole row tile); nothing leaves the shard.  A pair the gate dropped or
    masked sorts behind the last expert and is in no group: its rows are
    not computed, and hold whatever memory held."""
    name = jax.ad_checkpoint.checkpoint_name
    w_gate, w_up, w_down = map(name, (w_gate, w_up, w_down), STACK_NAMES)
    dtype = w_gate.dtype
    (S, k), E, D = gate_out.slot.shape, w_gate.shape[0], tokens.shape[1]
    with jax.named_scope("moe/dispatch"):
        # a trash slot E*C gives expert E
        expert = (gate_out.slot // gate_out.capacity).reshape(S * k)
        order = jnp.argsort(expert, stable=True)
        sizes = jnp.zeros((E,), jnp.int32).at[expert].add(1, mode="drop")
        token_of = jnp.pad(order // k, (0, rows - S * k))
        live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
        # select, on the way in as on the way out: transposed, this one
        # keeps what the rows' gradient holds in no group's rows out of the
        # tokens' gradient
        x = jnp.where(live, jnp.take(tokens.astype(dtype), token_of, axis=0),
                      0)
    with jax.named_scope("moe/experts"):
        act = jax.nn.silu(name(grouped_matmul(x, w_gate, sizes),
                               ROW_NAMES[0]))
        up = name(grouped_matmul(x, w_up, sizes), ROW_NAMES[1])
        y = name(grouped_matmul(act * up, w_down, sizes), ROW_NAMES[2])
    with jax.named_scope("moe/combine"):
        y = jnp.where(live, y, 0)
        y = jnp.take(y, jnp.argsort(order), axis=0).reshape(S, k, D)
        return jnp.sum(gate_out.gate_val[:, :, None].astype(dtype) * y,
                       axis=1)


def own_pair_rows(num_tokens: int, num_experts: int, k: int,
                  capacity_factor: float) -> int:
    """Rows of a shard's grouped matmuls where :func:`moe_mlp_block` (sparse
    dispatch) takes :func:`_experts_on_own_pairs` at these sizes on the mesh
    of this trace; 0 where it does not."""
    grouped = _routing_groups(num_tokens, _capacity(
        num_tokens * k, num_experts, capacity_factor, MIN_CAPACITY))
    return whole_tiles(num_tokens // grouped[0].size * k,
                       num_experts) if grouped else 0


def moe_layer(params: Dict, x: jnp.ndarray, k: int = 1,
              capacity_factor: float = 1.0, eval_capacity_factor: float = 1.0,
              min_capacity: int = MIN_CAPACITY, drop_tokens: bool = True,
              noisy_gate_policy: Optional[str] = None,
              rng: Optional[jax.Array] = None, training: bool = True,
              activation=jax.nn.gelu,
              dispatch_impl: str = "sparse",
              num_experts_logical: Optional[int] = None) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Apply the MoE layer to x [..., D] → (out [..., D], l_aux, exp_counts).

    Reference: MOELayer.forward (sharded_moe.py:586): einsum dispatch →
    all-to-all → expert FFN → all-to-all → einsum combine.

    ``dispatch_impl``: "sparse" (default) routes via flat-slot scatter/gather
    — linear in tokens, required for 32k+ routing chunks; "dense" is the
    GShard [S,E,C] einsum kept as the numerics oracle.
    """
    assert dispatch_impl in ("sparse", "dense"), dispatch_impl
    orig_shape = x.shape
    D = orig_shape[-1]
    tokens = x.reshape(-1, D)
    S = tokens.shape[0]
    logits = tokens.astype(jnp.float32) @ params["gate"]["kernel"]
    cf = capacity_factor if training else eval_capacity_factor

    w = params["experts"]
    dtype = w["w1"].dtype

    def expert_ffn(dispatched):
        h = activation(jnp.einsum("ecd,edf->ecf", dispatched, w["w1"]) +
                       w["b1"][:, None, :])
        return jnp.einsum("ecf,efd->ecd", h, w["w2"]) + w["b2"][:, None, :]

    if dispatch_impl == "sparse":
        if k == 1:
            gate = top1gating_sparse(logits, cf, min_capacity,
                                     noisy_gate_policy, rng, drop_tokens,
                                     num_experts_logical=num_experts_logical)
        else:
            gate = topkgating_sparse(logits, k, cf, min_capacity, drop_tokens,
                                     rng,
                                     num_experts_logical=num_experts_logical)
        E = logits.shape[1]
        dispatched = dispatch_sparse(gate.slot, tokens, E, gate.capacity, dtype)
        expert_out = expert_ffn(dispatched)
        out = combine_sparse(gate.slot, gate.gate_val, expert_out, dtype)
    else:
        if k == 1:
            gate = top1gating(logits, cf, min_capacity, noisy_gate_policy, rng,
                              drop_tokens,
                              num_experts_logical=num_experts_logical)
        else:
            gate = topkgating(logits, k, cf, min_capacity, drop_tokens, rng,
                              num_experts_logical=num_experts_logical)
        dispatched = dispatch_to_experts(gate.dispatch, tokens, dtype)  # [E, C, D]
        expert_out = expert_ffn(dispatched)
        out = combine_from_experts(gate.combine, expert_out, dtype)
    return out.reshape(orig_shape), gate.l_aux, gate.exp_counts


# --------------------------------------------------------------------- #
# Expert resharding (elastic mesh-shape change, universal checkpoints)
# --------------------------------------------------------------------- #
def expert_shard_ranges(num_experts: int, ep_size: int) -> list:
    """Contiguous logical-expert ranges ``[(start, stop), ...]`` per
    expert-parallel rank, balanced for uneven remainders (sizes differ by
    at most one; the first ``num_experts % ep_size`` ranks carry the extra
    expert).  This is the IDEAL balanced split — what a reader that can
    address arbitrary rows should fetch per rank."""
    E, ep = int(num_experts), max(int(ep_size), 1)
    base, rem = divmod(E, ep)
    out, start = [], 0
    for r in range(ep):
        n = base + (1 if r < rem else 0)
        out.append((start, start + n))
        start += n
    return out


def placed_expert_ranges(num_experts: int, ep_size: int) -> list:
    """The LOGICAL expert rows each rank actually holds after
    :func:`pad_experts_for_ep` + even NamedSharding chunking of the padded
    stack: rank ``r`` owns padded rows ``[r*chunk, (r+1)*chunk)`` clipped
    to the logical count (trailing ranks may hold only padding → empty
    range).  Divisible counts make this identical to
    :func:`expert_shard_ranges`."""
    E, ep = int(num_experts), max(int(ep_size), 1)
    chunk = padded_expert_count(E, ep) // ep
    return [(min(r * chunk, E), min((r + 1) * chunk, E)) for r in range(ep)]


def padded_expert_count(num_experts: int, ep_size: int) -> int:
    """Smallest multiple of ``ep_size`` holding ``num_experts`` — the
    stacked-expert leading dim after :func:`pad_experts_for_ep` (jax
    NamedSharding requires even divisibility, the GSPMD pad trick)."""
    ep = max(int(ep_size), 1)
    return -(-int(num_experts) // ep) * ep


def _pad_axis(arr: jnp.ndarray, axis: int, target: int) -> jnp.ndarray:
    pad = target - arr.shape[axis]
    if pad <= 0:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, pad)
    return jnp.pad(arr, widths)


def pad_experts_for_ep(params: Dict, ep_size: int) -> Tuple[Dict, int]:
    """Pad a stacked-expert param tree so the expert axis divides
    ``ep_size`` — expert stacks get zero experts appended (axis 0) and the
    gate/router kernel gets matching zero columns (axis 1).

    Returns ``(padded params, num_experts_logical)``.  Callers MUST pass
    the logical count to the gating functions (``num_experts_logical=``):
    padded experts route ``-inf`` logits, so outputs are bit-identical to
    the unpadded layer while the weights shard evenly.  Supports both
    param families: ``gate``+``experts`` (:func:`moe_layer`) and
    ``router``+``*_proj`` (:func:`moe_mlp_block`).
    """
    gate_key = "gate" if "gate" in params else "router"
    if gate_key not in params:
        raise ValueError("not a MoE param tree: no 'gate' or 'router' entry")
    E = int(params[gate_key]["kernel"].shape[1])
    E_pad = padded_expert_count(E, ep_size)
    if E_pad == E:
        return params, E
    out = dict(params)
    out[gate_key] = {"kernel": _pad_axis(params[gate_key]["kernel"], 1, E_pad)}
    if "experts" in params:
        out["experts"] = {k: _pad_axis(v, 0, E_pad)
                          for k, v in params["experts"].items()}
    for k in ("gate_proj", "up_proj", "down_proj"):
        if k in params:
            out[k] = {"kernel": _pad_axis(params[k]["kernel"], 0, E_pad)}
    return out, E


def reshard_expert_params(params: Dict, topology=None) -> Tuple[Dict, Dict]:
    """Lay a stacked-expert MoE param tree out for the CURRENT mesh's
    expert axis — the MoE leg of a mesh-shape change (chips lost, ep_size
    re-planned, train→serve).

    When the logical expert count divides the new ``ep_size`` this is a
    plain re-placement onto ``moe_partition_specs``; when it does not
    (e.g. 6 experts onto ep=4 after losing a host), the stack is padded to
    the next multiple (:func:`pad_experts_for_ep`) and sharded evenly.
    Returns ``(params, info)`` where ``info["num_experts_logical"]`` must
    be forwarded to the gating call whenever ``info["padded"]`` is true.
    """
    topo = topology or get_topology()
    ep = int(topo.dims[EXPERT])
    gate_key = "gate" if "gate" in params else "router"
    E = int(params[gate_key]["kernel"].shape[1])
    params, E_logical = pad_experts_for_ep(params, ep)
    info = {"num_experts_logical": E_logical,
            "num_experts_padded": int(params[gate_key]["kernel"].shape[1]),
            "ep_size": ep, "padded": E_logical !=
            int(params[gate_key]["kernel"].shape[1]),
            # the rows each rank ACTUALLY holds (even chunks of the padded
            # stack, clipped to logical experts) — not the ideal balanced
            # split, which padding cannot realize
            "shard_ranges": placed_expert_ranges(E, ep)}
    specs = moe_partition_specs()
    placed = {}
    for key, sub in params.items():
        spec_sub = specs.get(key) if key in ("gate", "experts") else None
        placed[key] = {}
        for name, arr in sub.items():
            if key == "experts" or key.endswith("_proj"):
                spec = P(EXPERT, *([None] * (arr.ndim - 1)))
            elif spec_sub is not None and name in spec_sub:
                spec = spec_sub[name]
            else:
                spec = P(*([None] * arr.ndim))
            placed[key][name] = jax.device_put(
                arr, jax.sharding.NamedSharding(topo.mesh, spec))
    return placed, info
