"""Recursive jaxpr traversal shared by the graph passes.

Generalizes the walk ``utils/jaxpr_utils`` does for flop attribution:
every eqn is visited with its static execution multiplicity (scan trip
counts multiplied through nesting, while bodies count one trip — an
explicit undercount) and with a flag saying whether it sits inside a
``shard_map`` manual region (where per-device collectives/gathers are
hand-written and GSPMD cannot rewrite them — several passes exempt those).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, Optional, Tuple

from ..utils.jaxpr_utils import _is_leaf_eqn, _sub_jaxprs


def as_jaxpr(traced):
    """``jax.make_jaxpr`` result / ClosedJaxpr / raw jaxpr → raw jaxpr."""
    j = traced
    while hasattr(j, "jaxpr"):
        j = j.jaxpr
    if not hasattr(j, "eqns"):
        raise TypeError(f"not a jaxpr: {type(traced).__name__}")
    return j


@dataclasses.dataclass
class EqnInfo:
    eqn: object
    #: static execution count (scan trip counts multiplied through nesting)
    mult: float
    #: inside a shard_map body (manual region — GSPMD keeps its hands off)
    in_shard_map: bool
    #: nesting depth (0 = top level)
    depth: int


def iter_eqns(traced) -> Iterator[EqnInfo]:
    """Every eqn of ``traced`` and its sub-jaxprs (pjit/scan/cond/while/
    remat/custom_vjp/pallas bodies), scalar-combiner sub-jaxprs excluded —
    same conventions as the profiler's cost walk."""
    def walk(jx, mult: float, in_sm: bool, depth: int):
        for eqn in jx.eqns:
            yield EqnInfo(eqn, mult, in_sm, depth)
            if _is_leaf_eqn(eqn):
                continue
            inner_mult = mult
            if eqn.primitive.name == "scan":
                inner_mult *= float(eqn.params.get("length", 1))
            inner_sm = in_sm or eqn.primitive.name == "shard_map"
            for sub in _sub_jaxprs(eqn):
                yield from walk(sub, inner_mult, inner_sm, depth + 1)

    yield from walk(as_jaxpr(traced), 1.0, False, 0)


def eqn_site(eqn) -> Tuple[Optional[str], Optional[int]]:
    """Best-effort (file, line) of the user source that emitted ``eqn`` —
    the provenance findings carry and the pragma filter resolves."""
    si = getattr(eqn, "source_info", None)
    if si is None:
        return None, None
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(si)
        if frame is not None:
            line = getattr(frame, "start_line", None) or \
                getattr(frame, "line_num", None)
            return frame.file_name, int(line) if line else None
    except Exception:  # noqa: BLE001 — provenance is best-effort by design
        pass
    return None, None


def describe_eqn(eqn) -> str:
    """Short eqn description for finding text: primitive + operand avals."""
    def aval_str(v):
        aval = getattr(v, "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            return "?"
        return f"{getattr(aval, 'dtype', '?')}{list(aval.shape)}"

    ins = ",".join(aval_str(v) for v in eqn.invars[:3])
    more = ",…" if len(eqn.invars) > 3 else ""
    return f"{eqn.primitive.name}({ins}{more})"


#: container primitives whose eqn invars/outvars map POSITIONALLY onto the
#: sub-jaxpr's invars/outvars, so a producer chase can cross the boundary
#: (scan: consts+carry+xs in / carry+ys out — positional either side;
#: cond/while have multiple bodies or split signatures and are excluded)
_ALIASING_CONTAINERS = frozenset({
    "jit", "closed_call", "core_call", "remat", "remat2", "checkpoint",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "shard_map", "scan",
})


def value_graph(traced) -> Tuple[Dict, Dict, Dict]:
    """(producers, out_alias, in_alias) across every nesting level.

    ``producers``: var → producing eqn.  ``out_alias``: a container eqn's
    outvar → the sub-jaxpr outvar it forwards.  ``in_alias``: a sub-jaxpr
    invar → the outer eqn invar bound to it.  Together these let
    :func:`chase` follow a value through pjit/remat/custom_vjp/shard_map/
    scan boundaries instead of stopping at the call eqn.
    """
    producers: Dict[object, object] = {}
    out_alias: Dict[object, object] = {}
    in_alias: Dict[object, object] = {}

    def handle(jx):
        for eqn in jx.eqns:
            for v in eqn.outvars:
                producers[v] = eqn
            if _is_leaf_eqn(eqn):
                continue
            subs = list(_sub_jaxprs(eqn))
            if eqn.primitive.name in _ALIASING_CONTAINERS and len(subs) == 1:
                inner = subs[0]
                if len(inner.invars) == len(eqn.invars):
                    for iv, ov in zip(inner.invars, eqn.invars):
                        in_alias[iv] = ov
                if len(inner.outvars) == len(eqn.outvars):
                    for outer_ov, inner_ov in zip(eqn.outvars, inner.outvars):
                        out_alias[outer_ov] = inner_ov
            for sub in subs:
                handle(sub)

    handle(as_jaxpr(traced))
    return producers, out_alias, in_alias


def chase(var, graph, through: frozenset, max_hops: int = 64):
    """Follow ``var`` back through producer eqns whose primitive is in
    ``through`` (first operand only — layout chains are unary), crossing
    container boundaries via the :func:`value_graph` aliases.

    Returns (origin_eqn_or_None, terminal_var_or_None): the first producer
    OUTSIDE ``through``, or — when the chain ends without one —
    the terminal value itself: a jaxpr invar/constvar (``Var``: a buffer
    fed INTO the program) or a ``Literal`` (an initialized constant).
    Exactly one of the two is non-None, except on hop exhaustion."""
    producers, out_alias, in_alias = graph
    hops = 0
    while hops < max_hops:
        if not hasattr(var, "count"):      # Literal — no producer
            return None, var
        if var in out_alias:               # container result → inner value
            var = out_alias[var]
            hops += 1
            continue
        eqn = producers.get(var)
        if eqn is None:
            if var in in_alias:            # sub-jaxpr arg → outer value
                var = in_alias[var]
                hops += 1
                continue
            return None, var               # program input / constvar
        if eqn.primitive.name not in through:
            return eqn, None
        if not eqn.invars:
            return eqn, None
        var = eqn.invars[0]
        hops += 1
    return None, None


#: pure layout/dtype ops: value-preserving reshapes a producer chain may
#: run through without "computing" anything
LAYOUT_PRIMS = frozenset({
    "reshape", "transpose", "squeeze", "expand_dims", "broadcast_in_dim",
    "convert_element_type", "copy", "slice", "rev",
})

#: collective primitive name prefixes — the ONE definition shared by the
#: fused-wire pass, ``runtime/comm/fused_wire.wire_ops``, and
#: ``assert_quantized_wire`` (a primitive added to one consumer but not
#: another would make the CI gate and the in-test assertion disagree)
COLLECTIVE_PRIMS = ("all_to_all", "all_gather", "psum", "reduce_scatter")

#: the fused-wire contract: between a quantize kernel and its collective
#: nothing but these may sit (narrower than LAYOUT_PRIMS: no slice/rev —
#: the wire must consume the pack's bytes whole)
WIRE_LAYOUT_PRIMS = frozenset({
    "reshape", "transpose", "squeeze", "expand_dims", "broadcast_in_dim",
    "convert_element_type",
})
