"""Jaxpr detectors: each encodes a miscompile / NaN-poisoning bug class
this repo has already paid for at runtime (the motivating PR is named on
every pass).  All passes walk the full nested jaxpr via
``jaxpr_walk.iter_eqns`` and attach ``file:line`` provenance from eqn
source info, so the ``# dstpu-check: disable=<pass>`` pragma on the traced
source line can allowlist a deliberate exception.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from ..utils.jaxpr_utils import _is_leaf_eqn, _sub_jaxprs
from .core import (ERROR, WARN, Finding, GraphPass, PassContext,
                   register_pass, relpath)
from .jaxpr_walk import (COLLECTIVE_PRIMS, LAYOUT_PRIMS, WIRE_LAYOUT_PRIMS,
                         as_jaxpr, chase, describe_eqn, eqn_site, iter_eqns,
                         value_graph)

_REPLICATED = "rep"
_SHARDED = "shard"

#: primitives GSPMD may rewrite into per-replica-group operations when the
#: operand is sharded (the PR-8/9 miscompile class)
_GROUP_REWRITE_PRIMS = ("gather", "dynamic_slice", "dynamic_update_slice")

#: value-preserving ops sharding knowledge propagates through (compute ops
#: let GSPMD re-decide placement — knowledge stops there, conservatively)
_SHARDING_PROP = frozenset({
    "reshape", "transpose", "convert_element_type", "squeeze",
    "expand_dims", "copy", "broadcast_in_dim",
})

_COMPARISONS = frozenset({"eq", "ne", "lt", "le", "gt", "ge", "is_finite"})
_BOOL_COMBINE = frozenset({"and", "or", "not", "xor"})

#: mask producer chains run through these before the multiply
_MASK_CHAIN = frozenset({
    "broadcast_in_dim", "reshape", "convert_element_type", "transpose",
    "expand_dims", "squeeze", "copy",
})

_COLLECTIVE_PRIMS = COLLECTIVE_PRIMS
_WIRE_LAYOUT = WIRE_LAYOUT_PRIMS


def _classify_sharding(s) -> Optional[str]:
    """Sharding object → replicated / sharded / unknown(None)."""
    if s is None:
        return None
    try:
        if bool(getattr(s, "is_fully_replicated")):
            return _REPLICATED
    except Exception:  # noqa: BLE001 — e.g. UnspecifiedValue
        return None
    mesh = getattr(s, "mesh", None)
    if mesh is not None and getattr(mesh, "size", 0) <= 1:
        return _REPLICATED
    return _SHARDED


def _is_literal(v) -> bool:
    return hasattr(v, "val") and not hasattr(v, "count")


def _shared_graph(closed, ctx: PassContext):
    """The run-level value graph when :func:`~.core.run_graph_passes`
    built one for this exact program, else a fresh build (direct pass
    invocation, e.g. ``assert_fused_pack``)."""
    cached = ctx.extra.get("value_graph")
    if cached is not None and cached[0] is closed:
        return cached[1]
    return value_graph(closed)


@register_pass
class ReplicaGroupGatherPass(GraphPass):
    """gather/dynamic-slice/scatter over a *sharded* operand outside a
    manual ``shard_map`` region.

    Bug class: GSPMD partitions the op per shard and psums the partial
    results over EVERY replica group — including pure data-replica groups —
    so the result comes back multiplied by the replica-group count.
    Observed twice: PR 8 ``paged_kv_append`` row-scatter cached K/V exactly
    4x on a dp4×tp2 mesh; PR 9 ``combine_sparse``'s ``jnp.take`` scaled MoE
    output by the data-axis size.  Fix idiom: pin the operand replicated
    (``with_sharding_constraint``, see ``moe/sharded_moe._pin_replicated``
    and ``paged_kv_append(replicate=)``) or move the op inside a manual
    ``shard_map`` region where GSPMD cannot rewrite it.

    Sharding knowledge comes from ``sharding_constraint`` eqns in the
    trace, pjit in_shardings, and ``ctx.arg_shardings``; it propagates
    through layout ops only (after real compute GSPMD re-decides placement,
    so the pass stays silent — no false positives on unknown shardings).
    """

    name = "replica-group-gather"
    severity = ERROR
    bug_class = ("GSPMD per-replica-group rewrite of gather/scatter over a "
                 "sharded operand (PR 8 paged_kv_append, PR 9 "
                 "combine_sparse)")

    def run(self, closed, ctx: PassContext) -> List[Finding]:
        findings: List[Finding] = []
        top = as_jaxpr(closed)
        seeds: Dict[object, str] = {}
        if ctx.arg_shardings:
            for v, s in zip(top.invars, ctx.arg_shardings):
                st = _classify_sharding(s)
                if st is not None:
                    seeds[v] = st
        self._walk(top, seeds, False, ctx, findings)
        return findings

    # ---- dataflow over one jaxpr level ---------------------------------
    def _walk(self, jx, seeds: Dict[object, str], in_shard_map: bool,
              ctx: PassContext, findings: List[Finding]) -> None:
        state: Dict[object, str] = dict(seeds)

        def get(v) -> Optional[str]:
            if _is_literal(v):
                return _REPLICATED
            return state.get(v)

        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name == "sharding_constraint":
                st = _classify_sharding(eqn.params.get("sharding"))
                if st is not None:
                    for ov in eqn.outvars:
                        state[ov] = st
                continue
            if (name in _GROUP_REWRITE_PRIMS or name.startswith("scatter")) \
                    and not in_shard_map and eqn.invars:
                if get(eqn.invars[0]) == _SHARDED:
                    f, ln = eqn_site(eqn)
                    findings.append(self.finding(
                        f"{name} over a sharded operand outside a manual "
                        f"shard_map region — GSPMD may rewrite this into "
                        f"per-replica-group ops and sum the groups' partial "
                        f"results (PR-8/9 miscompile class); pin the operand "
                        f"replicated with with_sharding_constraint or move "
                        f"it inside shard_map",
                        file=relpath(f), line=ln, eqn=describe_eqn(eqn),
                        ctx=ctx))
            if name in _SHARDING_PROP and eqn.invars:
                st = get(eqn.invars[0])
                if st is not None:
                    for ov in eqn.outvars:
                        state[ov] = st
            elif name == "concatenate":
                sts = {get(v) for v in eqn.invars}
                if len(sts) == 1 and None not in sts:
                    for ov in eqn.outvars:
                        state[ov] = sts.pop()
            # ---- recursion ---------------------------------------------
            if _is_leaf_eqn(eqn):
                continue
            inner_sm = in_shard_map or name == "shard_map"
            if name == "jit":      # the jit-call primitive (was "pjit")
                cj = eqn.params.get("jaxpr")
                inner = getattr(cj, "jaxpr", cj)
                if inner is not None and hasattr(inner, "invars"):
                    sub_seeds: Dict[object, str] = {}
                    in_sh = eqn.params.get("in_shardings") or ()
                    for i, iv in enumerate(inner.invars):
                        st = get(eqn.invars[i]) if i < len(eqn.invars) \
                            else None
                        if st is None and i < len(in_sh):
                            st = _classify_sharding(in_sh[i])
                        if st is not None:
                            sub_seeds[iv] = st
                    self._walk(inner, sub_seeds, inner_sm, ctx, findings)
                    continue
            for sub in _sub_jaxprs(eqn):
                # scan/while/cond/custom_* bodies: no positional seed
                # mapping attempted — unknown-in, conservative
                self._walk(sub, {}, inner_sm, ctx, findings)


@register_pass
class MaskedNaNPass(GraphPass):
    """Multiply-by-mask over memory that can hold garbage/NaN.

    Bug class: ``mask * v`` where ``mask`` is a (broadcast of a)
    comparison and ``v`` was gathered/sliced from a buffer whose unused
    slots are uninitialized — ``0 × NaN = NaN``, so one poisoned padding
    slot NaNs the whole row.  Fixed three times in this repo (PR 6
    ``decode_attend_dense``, PR 8 ``_attend_gather``, PR 10's ragged
    verify kernel): the correct idiom is select-BEFORE-multiply
    (``jnp.where(mask, v, 0)``), which this pass recognizes as clean
    (the chase stops at ``select_n``).
    """

    name = "masked-nan-propagation"
    severity = ERROR
    bug_class = ("0×NaN through mask-multiply of gathered padding slots "
                 "(fixed in _attend_gather, decode_attend_dense, and the "
                 "PR-10 ragged kernel)")

    def run(self, closed, ctx: PassContext) -> List[Finding]:
        graph = _shared_graph(closed, ctx)
        findings: List[Finding] = []
        for info in iter_eqns(closed):
            eqn = info.eqn
            if eqn.primitive.name != "mul" or len(eqn.invars) != 2:
                continue
            a, b = eqn.invars
            for mask_v, val_v in ((a, b), (b, a)):
                if not self._mask_like(mask_v, graph):
                    continue
                origin = self._garbage_origin(val_v, graph)
                if origin is None:
                    continue
                f, ln = eqn_site(eqn)
                findings.append(self.finding(
                    f"mask-multiply over values read by "
                    f"{origin.primitive.name} — padding/unused slots can "
                    f"hold garbage and 0×NaN=NaN poisons the row; "
                    f"select-before-multiply instead "
                    f"(jnp.where(mask, v, 0))",
                    file=relpath(f), line=ln, eqn=describe_eqn(eqn),
                    ctx=ctx))
                break
        return findings

    def _mask_like(self, v, graph) -> bool:
        if getattr(getattr(v, "aval", None), "dtype", None) == bool:
            return True
        origin, _ = chase(v, graph, _MASK_CHAIN)
        if origin is None:
            return False
        name = origin.primitive.name
        return name in _COMPARISONS or name in _BOOL_COMBINE

    def _garbage_origin(self, v, graph):
        """The gather/dynamic_slice this value was read by, or None when a
        select_n (the fixed idiom) or any compute sits in between.  The
        *read buffer* must be a program input (KV pages, expert stacks —
        memory whose unused slots nobody initialized); a gather over
        freshly-computed values (e.g. log-probs in the loss mask) is
        defined everywhere and stays clean."""
        origin, _ = chase(v, graph, LAYOUT_PRIMS)
        if origin is None or \
                origin.primitive.name not in ("gather", "dynamic_slice"):
            return None
        if not origin.invars:
            return None
        src, terminal = chase(origin.invars[0], graph, LAYOUT_PRIMS)
        if src is None and terminal is not None and \
                hasattr(terminal, "count"):
            return origin
        return None


@register_pass
class FusedWirePass(GraphPass):
    """Quantized-collective wire contract (generalizes PR 9's
    ``assert_fused_pack``): every int8-operand collective must consume the
    output of a Pallas quantize+pack kernel through layout-only ops —
    any arithmetic in between means the pack fell out of the kernel and a
    full-precision intermediate is materialized on the wire path (the
    legacy strided int4 nibble pack is the historical offender).  Also
    flags duplicate collectives over the same operand (warn): the same
    tensor exchanged twice is paid-for bandwidth."""

    name = "fused-wire-layout"
    severity = ERROR
    bug_class = ("unfused quantize→exchange wire (PR 9: legacy jnp int4 "
                 "pack between quantize and collective)")

    #: collectives checked under the fused-gemm expectation: the epilogue
    #: exchanges (reduce-scatter family + the quantized a2a wire).  The
    #: prologue's all_gather is exempt — its operand is the raw weight
    #: shard, a program input with no producer to fuse.
    GEMM_COLLECTIVES = ("reduce_scatter", "psum_scatter", "all_to_all")

    def run(self, closed, ctx: PassContext) -> List[Finding]:
        import jax.numpy as jnp

        graph = _shared_graph(closed, ctx)
        findings: List[Finding] = []
        seen: Dict[tuple, int] = {}
        # fused-gemm edge contract (PR 15, T3 arXiv:2401.16677): on
        # artifacts traced with ctx.extra["expect_fused_gemm"], EVERY
        # epilogue-family collective operand — any dtype, not just the
        # int8 wire — must chase through layout-only ops to the producing
        # pallas_call; the unfused matmul→psum_scatter composition is the
        # tested negative control (fixtures.py)
        expect_gemm = bool(ctx.extra.get("expect_fused_gemm"))
        gemm_prims = tuple(ctx.extra.get("fused_gemm_collectives",
                                         self.GEMM_COLLECTIVES))
        for info in iter_eqns(closed):
            eqn = info.eqn
            name = eqn.primitive.name
            if not any(name.startswith(p) for p in _COLLECTIVE_PRIMS):
                continue
            if expect_gemm and eqn.invars and \
                    any(name.startswith(p) for p in gemm_prims):
                findings.extend(self._check_gemm_edge(eqn, graph, ctx))
            if eqn.invars:
                key = (name, id(eqn.invars[0]))
                seen[key] = seen.get(key, 0) + 1
                if seen[key] == 2:
                    f, ln = eqn_site(eqn)
                    findings.append(self.finding(
                        f"duplicate {name} over the same operand — the "
                        f"same tensor is exchanged twice",
                        file=relpath(f), line=ln, eqn=describe_eqn(eqn),
                        ctx=ctx, severity=WARN))
            wire = next((v for v in eqn.invars
                         if getattr(getattr(v, "aval", None), "dtype", None)
                         == jnp.int8), None)
            if wire is None:
                continue
            findings.extend(self._check_wire(eqn, wire, graph, ctx))
        return findings

    def _check_gemm_edge(self, eqn, graph, ctx) -> List[Finding]:
        """Epilogue collective under the fused-gemm expectation: operand
        must be the producing Pallas kernel's output (through layout ops).
        A program-input operand stays clean — there was no producer to
        fuse (the degenerate leaf-seam edge)."""
        origin, terminal = chase(eqn.invars[0], graph, _WIRE_LAYOUT)
        if origin is not None and origin.primitive.name == "pallas_call":
            return []
        if origin is None:
            return []          # program input / literal — nothing unfused
        f, ln = eqn_site(origin)
        return [self.finding(
            f"fused-gemm edge: {eqn.primitive.name} operand produced by "
            f"{origin.primitive.name!r} instead of the fused matmul "
            f"pallas_call — the collective fell out of the producing "
            f"kernel (unfused matmul→collective composition); use "
            f"kernels/fused_collective_matmul.matmul_reduce_scatter",
            file=relpath(f), line=ln, eqn=describe_eqn(origin), ctx=ctx)]

    def _check_wire(self, eqn, v, graph, ctx) -> List[Finding]:
        origin, _hops = chase(v, graph, _WIRE_LAYOUT)
        if origin is not None and origin.primitive.name == "pallas_call":
            return []
        if origin is not None:
            f, ln = eqn_site(origin)
            return [self.finding(
                f"int8 wire operand of {eqn.primitive.name} produced "
                f"through non-layout op {origin.primitive.name!r} — pack "
                f"is not fused into the quant kernel",
                file=relpath(f), line=ln, eqn=describe_eqn(origin),
                ctx=ctx)]
        f, ln = eqn_site(eqn)
        return [self.finding(
            f"int8 wire operand of {eqn.primitive.name} does not "
            f"originate from a Pallas quant+pack kernel",
            file=relpath(f), line=ln, eqn=describe_eqn(eqn), ctx=ctx)]


@register_pass
class GatherBudgetPass(GraphPass):
    """``all-gather`` count vs the caller's budget (scan trip counts
    multiplied).  Bug class: the PR-4 weight-prefetch invariant — with
    ``GatherWindowCache`` active the per-micro-batch program must carry
    ZERO param all-gathers (they moved to the once-per-window gather fn);
    a regression here silently re-pays (gas-1) gathers per window.  Runs
    only when ``ctx.gather_budget`` is set."""

    name = "gather-budget"
    severity = ERROR
    bug_class = ("per-micro all_gather leak under GatherWindowCache "
                 "(PR 4 prefetch invariant)")

    def run(self, closed, ctx: PassContext) -> List[Finding]:
        if ctx.gather_budget is None:
            return []
        total = 0.0
        sites = []
        for info in iter_eqns(closed):
            if info.eqn.primitive.name.startswith("all_gather"):
                total += info.mult
                if len(sites) < 4:
                    f, ln = eqn_site(info.eqn)
                    sites.append(f"{relpath(f)}:{ln}")
        count = int(round(total))
        if count <= ctx.gather_budget:
            return []
        return [self.finding(
            f"{count} all-gather eqn(s) (scan-multiplied) exceed the "
            f"budget of {ctx.gather_budget} for this program — e.g. the "
            f"prefetched per-micro step must carry none (PR-4 "
            f"GatherWindowCache invariant); first sites: "
            f"{', '.join(sites)}",
            file=None, line=None, ctx=ctx)]
