"""Fused quantize→exchange→dequantize collectives (EQuARX, arXiv:2506.17615).

The PR-4 quantized wires computed group scales, quantized, and (for int4)
nibble-packed in separate passes *outside* the collective, so every exchange
paid extra HBM round-trips for the full-precision intermediate and XLA could
not fuse the pack with the transfer.  Here the whole pipeline is one region:

  * the collective's operand is produced DIRECTLY by a single Pallas
    scale+quantize+pack kernel (``ops/quantizer/quantizer.py``
    ``quant_pack_wire``) — between the quantize and the ``all_to_all``/
    ``all_gather`` there is nothing but a layout reshape, a property the
    tests assert by jaxpr inspection (:func:`wire_ops`);
  * the receive side unpacks + dequantizes + mean-reduces in one kernel
    (``unpack_dequant_mean``), never materializing the n full-precision
    peer copies.

All functions must run inside ``shard_map`` with ``axes`` bound (the
engine's explicit-comm step, ``runtime/comm_path.py``).  Values are
bit-identical to the unfused compositions under jit (same scale math, same
rounding; only the int4 wire byte layout differs — pack∘unpack is the
identity either way), which the parity tests assert on the 8-device CPU
sim mesh.  The Pallas kernels run in interpreter mode off-TPU (the same
seam the quantizer kernels always had).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...analysis.jaxpr_walk import (
    COLLECTIVE_PRIMS as _COLLECTIVE_PRIMS,
    WIRE_LAYOUT_PRIMS as _LAYOUT_PRIMS,
)
from ...ops.quantizer.quantizer import (
    quant_pack_wire,
    unpack_dequant_mean,
    unpack_dequant_wire,
)


def _group_count(axes) -> int:
    """Exchange group size inside shard_map (trace-time constant)."""
    return jax.lax.psum(1, axes)


def fused_quantized_reduce_scatter(tensor: jnp.ndarray, axes,
                                   bits: int = 4, group_size: int = 256,
                                   return_sent: bool = False):
    """qgZ stage 1, fused: quantize+pack my contribution in one kernel,
    ``all_to_all`` the wire bytes, dequantize+mean-reduce my partition in
    one kernel.  Returns this rank's mean-reduced partition (f32 flat).

    ``return_sent=True`` additionally returns the dequantized transmitted
    signal (trimmed to the input length) — the LoCo error-feedback seam:
    the residual is reconstructed from the SAME quant+pack output the
    exchange used, so no second quantization pass runs."""
    n = _group_count(axes)
    flat = tensor.reshape(-1).astype(jnp.float32)
    size = flat.shape[0]
    if n <= 1:
        return (flat, flat) if return_sent else flat
    pad = (-size) % (n * group_size)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    w, s = quant_pack_wire(flat, bits, group_size)     # [n*gpc, W], [n*gpc, 1]
    gpc = w.shape[0] // n                              # groups per chunk
    w_x = jax.lax.all_to_all(w.reshape(n, gpc, w.shape[1]), axes,
                             split_axis=0, concat_axis=0, tiled=True)
    s_x = jax.lax.all_to_all(s.reshape(n, gpc, 1), axes,
                             split_axis=0, concat_axis=0, tiled=True)
    mine = unpack_dequant_mean(w_x, s_x, bits, n)      # [per] = my partition
    if return_sent:
        return mine, unpack_dequant_wire(w, s, bits)[:size]
    return mine


def fused_quantized_all_gather(flat_shard: jnp.ndarray, axes,
                               bits: int = 8, group_size: int = 256,
                               out_dtype=jnp.bfloat16) -> jnp.ndarray:
    """qwZ, fused: one quantize+pack kernel on my flat shard, int8 wire
    ``all_gather``, one unpack+dequant kernel.  Returns the flat
    concatenation of every rank's shard (tail-group padding stripped)."""
    n = _group_count(axes)
    flat = flat_shard.reshape(-1)
    if n <= 1:
        return flat.astype(out_dtype)
    w, s = quant_pack_wire(flat, bits, group_size)
    w_all = jax.lax.all_gather(w, axes, axis=0, tiled=False)   # [n, g, W]
    s_all = jax.lax.all_gather(s, axes, axis=0, tiled=False)
    padded = w.shape[0] * group_size                   # per-rank padded length
    vals = unpack_dequant_wire(w_all.reshape(-1, w.shape[1]),
                               s_all.reshape(-1, 1), bits,
                               dtype=out_dtype).reshape(n, padded)
    return vals[:, :flat.shape[0]].reshape(-1)


def fused_quantized_allreduce(grad: jnp.ndarray, axes, bits: int = 8,
                              group_size: int = 256,
                              error: Optional[jnp.ndarray] = None,
                              server_error: Optional[jnp.ndarray] = None,
                              ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray],
                                         Optional[jnp.ndarray]]:
    """Fully quantized mean-allreduce, fused (qgZ analogue of
    ``comm_path.quantized_allreduce``): stage 1 quantized all-to-all +
    fused mean of my partition, stage 2 re-quantized allgather.  With LoCo
    both hops carry error feedback; the residual reconstruction
    (``unpack_dequant_wire`` of the local wire bytes) is independent of the
    exchange, so XLA is free to overlap it with the transfer."""
    n = _group_count(axes)
    if n <= 1:
        return grad, error, server_error
    flat = grad.reshape(-1).astype(jnp.float32)
    if error is not None:
        flat = flat + error.reshape(-1)
    size = flat.shape[0]
    pad = (-size) % (n * group_size)
    if pad:
        flat = jnp.pad(flat, (0, pad))

    # stage 1: one quant+pack kernel, wire all-to-all, fused dequant+mean
    w, s = quant_pack_wire(flat, bits, group_size)
    new_error = None
    if error is not None:
        sent = unpack_dequant_wire(w, s, bits)         # what hit the wire
        new_error = (flat - sent)[:size].reshape(grad.shape)
    gpc = w.shape[0] // n
    w_x = jax.lax.all_to_all(w.reshape(n, gpc, w.shape[1]), axes,
                             split_axis=0, concat_axis=0, tiled=True)
    s_x = jax.lax.all_to_all(s.reshape(n, gpc, 1), axes,
                             split_axis=0, concat_axis=0, tiled=True)
    mine = unpack_dequant_mean(w_x, s_x, bits, n)      # my reduced partition

    # stage 2: re-quantize the partition, wire allgather, fused dequant
    new_server_error = None
    if server_error is not None:
        mine = mine + server_error.reshape(-1)
    w2, s2 = quant_pack_wire(mine, bits, group_size)
    if server_error is not None:
        sent2 = unpack_dequant_wire(w2, s2, bits)
        new_server_error = (mine - sent2).reshape(server_error.shape)
    w2_all = jax.lax.all_gather(w2, axes, axis=0, tiled=False)  # [n, g2, W]
    s2_all = jax.lax.all_gather(s2, axes, axis=0, tiled=False)
    full = unpack_dequant_wire(w2_all.reshape(-1, w2.shape[1]),
                               s2_all.reshape(-1, 1), bits).reshape(-1)[:size]
    return (full.reshape(grad.shape).astype(grad.dtype), new_error,
            new_server_error)


# --------------------------------------------------------------------- #
# jaxpr inspection (the fusion property the tests assert)
# --------------------------------------------------------------------- #
# _COLLECTIVE_PRIMS/_LAYOUT_PRIMS are the shared analysis/jaxpr_walk.py
# definitions (imported above): the fused-wire pass, wire_ops, and
# assert_quantized_wire must agree on what counts as a collective / a
# layout-only hop


def _all_eqns(jaxpr):
    """Every eqn in a (closed) jaxpr, recursing into sub-jaxprs (pjit /
    shard_map / custom_jvp bodies)."""
    def as_jaxpr(v):
        if hasattr(v, "eqns"):                     # raw Jaxpr (shard_map)
            return v
        inner = getattr(v, "jaxpr", None)          # ClosedJaxpr (pjit/scan)
        return inner if inner is not None and hasattr(inner, "eqns") else None

    eqns = []
    stack = [jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            eqns.append(eqn)
            for v in eqn.params.values():
                for vv in (v if isinstance(v, (tuple, list)) else (v,)):
                    inner = as_jaxpr(vv)
                    if inner is not None:
                        stack.append(inner)
    return eqns


def wire_ops(traced) -> list:
    """(primitive name, operand dtypes, operand bytes) for every collective
    in a traced computation — the seam the fusion tests and the comm_sweep
    byte accounting both read.  ``traced`` is anything with a ``.jaxpr``
    (``jax.make_jaxpr(...)`` result) or a raw jaxpr."""
    out = []
    for eqn in _all_eqns(traced):
        name = eqn.primitive.name
        if any(name.startswith(p) for p in _COLLECTIVE_PRIMS):
            dtypes = tuple(str(v.aval.dtype) for v in eqn.invars
                           if hasattr(v.aval, "dtype"))
            nbytes = sum(int(v.aval.size) * v.aval.dtype.itemsize
                         for v in eqn.invars if hasattr(v.aval, "dtype"))
            out.append({"prim": name, "dtypes": dtypes, "bytes": nbytes})
    return out


def assert_fused_pack(traced) -> None:
    """Raise unless every int8 collective operand is produced by a Pallas
    quant+pack kernel through layout-only ops (reshape/transpose) — i.e.
    the exchange consumes the kernel's wire bytes directly, with no
    intermediate arithmetic (and hence no full-precision materialization)
    between quantize and exchange.  The legacy jnp-composed int4 wire fails
    this (its nibble pack is an ``or`` of shifted slices between the
    quantize and the collective), which the tests use as the negative
    control.

    The walk itself is the ``fused-wire-layout`` pass of the
    ``dstpu-check`` framework (``analysis/graph_passes.py``) — this
    assertion keeps its historical raise-on-first-violation contract (plus
    the wires-must-exist check, which the general pass deliberately lacks:
    a program with no quantized collectives is not a wire regression)."""
    from ...analysis.core import ERROR, PassContext
    from ...analysis.graph_passes import FusedWirePass

    if not any("int8" in o["dtypes"] for o in wire_ops(traced)):
        raise AssertionError("no int8-wire collectives found")
    findings = FusedWirePass().run(
        traced, PassContext(artifact="assert_fused_pack"))
    errors = [f for f in findings if f.severity == ERROR]
    if errors:
        raise AssertionError(errors[0].message)


def assert_quantized_wire(traced, expect_exchanges: int) -> None:
    """Raise unless every large collective operand in ``traced`` is int8
    wire bytes (scales ride as small f32 sidecars) — i.e. no full-precision
    tensor is materialized between the quantize kernel and the exchange.

    ``expect_exchanges``: number of collectives expected to carry int8
    payloads (a2a / allgather hops)."""
    ops = wire_ops(traced)
    int8_ops = [o for o in ops if "int8" in o["dtypes"]]
    if len(int8_ops) < expect_exchanges:
        raise AssertionError(
            f"expected >= {expect_exchanges} int8-wire collectives, found "
            f"{len(int8_ops)} in {ops}")
    for o in ops:
        if "int8" in o["dtypes"]:
            continue
        # non-wire collectives may only carry the small scale sidecars
        # (f32, one scalar per quantization group) — a full-precision
        # payload here means the fusion regressed
        wire_bytes = max((w["bytes"] for w in int8_ops), default=0)
        if o["bytes"] > wire_bytes:
            raise AssertionError(
                f"full-precision collective payload bigger than the wire: "
                f"{o} vs int8 wire {wire_bytes} bytes")
