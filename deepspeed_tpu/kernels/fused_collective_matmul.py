"""T3-style fused compute+collective matmul kernels (arXiv:2401.16677).

Scheduler-level overlap (PR 4 deferral/bucketing, PR 9 wire/algorithm
selection) can only hide a collective behind OTHER work; T3's observation
is that the producing kernel itself is the best hiding place — walk the
output tiles in shard-major order and exchange each shard's block as it
completes, so the MXU keeps streaming while earlier shards are already on
the wire.  EQuARX (arXiv:2506.17615) shows the same tile-granular schedule
composes with quantized wires, which is why the int8 edges below ride the
PR-9 fused-wire kernels (``ops/quantizer/quantizer.py quant_pack_wire`` /
``unpack_dequant_mean``) unchanged.

Three kernels, each with the collective fused onto an edge:

  * :func:`matmul_reduce_scatter` — reduce-scatter EPILOGUE.  The Pallas
    grid walks output tiles shard-major (grid dim 0 = destination shard),
    so on TPU each completed shard block can enter the exchange while the
    MXU continues on the next shard.  Replaces the trailing
    ``psum_scatter`` on ZeRO grad buckets and TP row-parallel projections.
  * :func:`all_gather_matmul` — all-gather PROLOGUE for ZeRO-3 / TP
    column-parallel weight shards: tile k-loops begin on the
    locally-resident shard while remote shards stream in (the int8 edge
    dequantizes each arriving shard inside the consuming kernel).
  * :func:`rmsnorm_matmul` — RMSNorm folded into the consuming
    projection's kernel (the norm is memory-bound; recomputing it per
    output tile is free and saves the normalized activations' HBM
    round-trip).

Seams (the same discipline as the PR-9 wire kernels): ``impl="pallas"``
runs the Pallas kernels — interpreter mode off-TPU — and ``impl="dense"``
is the XLA lowering built from the *identical* composition, so the CPU sim
can assert the contracts the silicon relies on:

  * fp edge: BITWISE equality with the unfused matmul→collective
    composition (:func:`matmul_reference` followed by the plain
    collective) under both seams;
  * int8 edge: bitwise equality with unfused-matmul→PR-9-fused-wire, and
    the PR-9 half-step error bound vs the fp oracle (|err| ≤ 0.5 · group
    scale per exchanged element).

What the CPU sim canNOT measure — the tile-granular exchange actually
overlapping MXU time — is the on-silicon item the ROADMAP carries as
STILL OWED; here the fused property is asserted structurally (the
collective's operand chases through layout-only ops to the producing
``pallas_call`` — the ``fused-wire-layout`` dstpu-check pass, extended for
gemm edges).
"""
from __future__ import annotations

from functools import partial as _partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..ops.quantizer.quantizer import (
    quant_pack_wire,
    unpack_dequant_mean,
    unpack_dequant_wire,
)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def resolve_impl(impl: str = "auto") -> str:
    """"pallas" on TPU, "dense" elsewhere (``"auto"``); explicit values
    pass through — tests pin ``"pallas"`` to exercise interpreter mode."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "dense"
    if impl not in ("pallas", "dense"):
        raise ValueError(f"impl must be auto|pallas|dense, got {impl!r}")
    return impl


def _largest_divisor(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is <= cap (tile sizes must divide the
    array — Pallas partial blocks would pad the shard-major walk)."""
    for d in range(min(n, cap), 0, -1):
        if n % d == 0:
            return d
    return 1


def matmul_reference(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """THE unfused matmul every parity contract in this module is defined
    against: f32 accumulation, output in the promoted input dtype.  The
    kernels' per-tile dots use the same primitive over the same contraction
    ordering, which is what makes the fp edges bitwise-comparable."""
    out_dtype = jnp.promote_types(x.dtype, w.dtype)
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(out_dtype)


# --------------------------------------------------------------------- #
# Shard-major tiled matmul (the epilogue's producing kernel)
# --------------------------------------------------------------------- #
def _matmul_kernel(x_ref, w_ref, o_ref):
    o_ref[:] = jnp.dot(x_ref[:], w_ref[:],
                       preferred_element_type=jnp.float32
                       ).astype(o_ref.dtype)


def shard_major_matmul(x: jnp.ndarray, w: jnp.ndarray, n_shards: int,
                       block_m: int = 256, block_n: int = 512
                       ) -> jnp.ndarray:
    """``x @ w`` as a Pallas kernel whose grid walks output tiles in
    SHARD-MAJOR order: grid dim 0 is the destination shard of the trailing
    reduce-scatter, so shard ``s``'s rows ``[s·M/n, (s+1)·M/n)`` complete
    before any tile of shard ``s+1`` starts — on TPU the epilogue exchange
    of shard ``s`` overlaps the MXU's work on shard ``s+1``.

    Full-K tiles (no k-loop): each output element is ONE dot over the same
    contraction ordering as :func:`matmul_reference`, keeping the fp edge
    bitwise.  ``M`` must divide by ``n_shards``.
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    if M % n_shards:
        raise ValueError(f"rows {M} not divisible by {n_shards} shards")
    rows = M // n_shards
    bm = _largest_divisor(rows, block_m)
    bn = _largest_divisor(N, block_n)
    out_dtype = jnp.promote_types(x.dtype, w.dtype)
    return pl.pallas_call(
        _matmul_kernel,
        grid=(n_shards, rows // bm, N // bn),
        in_specs=[pl.BlockSpec((bm, K), lambda s, i, j:
                               (s * (rows // bm) + i, 0)),
                  pl.BlockSpec((K, bn), lambda s, i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda s, i, j:
                               (s * (rows // bm) + i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=_interpret(),
    )(x, w)


# --------------------------------------------------------------------- #
# (a) reduce-scatter epilogue
# --------------------------------------------------------------------- #
def matmul_reduce_scatter(x: jnp.ndarray, w: jnp.ndarray, axes,
                          wire_bits: int = 0, group_size: int = 256,
                          impl: str = "auto",
                          n: Optional[int] = None) -> jnp.ndarray:
    """``mean-reduce-scatter(x @ w)`` over ``axes`` along rows, with the
    matmul walked shard-major so the exchange is an epilogue of the kernel
    (must run inside shard_map with ``axes`` manual).

    Returns each rank's ``[M/n, N]`` mean partition.  ``wire_bits`` 8/4
    exchanges the epilogue on the PR-9 fused quantized wire (one
    quant+pack kernel per rank's output, ``all_to_all`` of wire bytes,
    fused ``unpack_dequant_mean`` on the receive side); 0 is the
    full-precision ``psum_scatter`` edge — bitwise vs
    ``psum_scatter(matmul_reference(x, w))/n``.
    """
    impl = resolve_impl(impl)
    if n is None:
        n = jax.lax.psum(1, axes)
    M, N = x.shape[0], w.shape[1]
    if M % max(n, 1):
        raise ValueError(f"rows {M} not divisible by group size {n}")
    y = shard_major_matmul(x, w, max(n, 1)) if impl == "pallas" \
        else matmul_reference(x, w)
    if n <= 1:
        return y
    if wire_bits:
        flat = y.reshape(-1).astype(jnp.float32)       # layout-only hop
        chunk = flat.shape[0] // n                     # one shard's block
        if chunk % group_size:
            raise ValueError(
                f"per-shard block of {chunk} elements not divisible by "
                f"quantization group_size={group_size}; pick N so that "
                f"(M/n)·N aligns (production shapes are 128-multiples)")
        wv, s = quant_pack_wire(flat, wire_bits, group_size)
        gpc = wv.shape[0] // n
        w_x = jax.lax.all_to_all(wv.reshape(n, gpc, wv.shape[1]), axes,
                                 split_axis=0, concat_axis=0, tiled=True)
        s_x = jax.lax.all_to_all(s.reshape(n, gpc, 1), axes,
                                 split_axis=0, concat_axis=0, tiled=True)
        mine = unpack_dequant_mean(w_x, s_x, wire_bits, n)
        return mine.reshape(M // n, N).astype(y.dtype)
    part = jax.lax.psum_scatter(y, axes, scatter_dimension=0, tiled=True)
    return part / n


# --------------------------------------------------------------------- #
# (b) all-gather prologue
# --------------------------------------------------------------------- #
def _gathered_dequant_matmul(x, w_wire, s_wire, wire_bits, k_shard, N,
                             out_dtype):
    """One kernel: per arriving shard, unpack+dequantize its weight block
    and accumulate its k-slice dot — the int8 prologue's consuming kernel.
    The shard loop is static (``n`` known at trace time); on TPU each
    iteration's wire block is what just streamed in, so the local shard's
    k-block starts with zero wait.  Accumulation is per-shard partial sums
    (the int8 edge is bound-checked, not bitwise — only the fp edge must
    match the single-dot ordering).  ``out_dtype`` is the caller's
    promote(x, w_shard) so the pallas and dense seams agree for bf16
    weights."""
    n = w_wire.shape[0]
    M = x.shape[0]

    def kernel(x_ref, w_ref, s_ref, o_ref):
        acc = jnp.zeros((M, N), jnp.float32)
        for r in range(n):
            wr = w_ref[r]                                    # [g, W]
            vals = unpack_dequant_wire_values(wr, s_ref[r], wire_bits)
            w_r = vals.reshape(-1)[:k_shard * N].reshape(k_shard, N)
            xk = x_ref[:, r * k_shard:(r + 1) * k_shard]
            acc = acc + jnp.dot(xk.astype(jnp.float32), w_r,
                                preferred_element_type=jnp.float32)
        o_ref[:] = acc.astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=_interpret(),
    )(x, w_wire, s_wire)


def unpack_dequant_wire_values(w: jnp.ndarray, scales: jnp.ndarray,
                               bits: int) -> jnp.ndarray:
    """In-kernel unpack+dequant: the quantizer's ``_unpack_wire`` (plain
    jnp ops — usable inside another Pallas kernel body, unlike its
    ``pallas_call`` wrappers) plus the scale multiply, so the wire's
    half-split nibble layout stays single-sourced."""
    from ..ops.quantizer.quantizer import _unpack_wire

    return _unpack_wire(w, bits).astype(jnp.float32) * scales


def all_gather_matmul(x: jnp.ndarray, w_shard: jnp.ndarray, axes,
                      wire_bits: int = 0, group_size: int = 256,
                      impl: str = "auto",
                      n: Optional[int] = None) -> jnp.ndarray:
    """``x @ all_gather(w_shard)`` with the gather fused as the matmul's
    PROLOGUE (must run inside shard_map with ``axes`` manual).

    ``w_shard`` is this rank's ``[K/n, N]`` row block of the weight (the
    ZeRO-3 param shard / TP column-parallel k-slice).  fp edge: the
    gathered full weight feeds the shard-major Pallas matmul — bitwise vs
    ``matmul_reference(x, all_gather(w_shard))``.  int8 edge: the wire on
    the gather is the PR-9 quant+pack kernel's output and the consuming
    kernel dequantizes each shard block as it arrives, k-looping
    shard-by-shard (locally-resident shard first on TPU).
    """
    impl = resolve_impl(impl)
    if n is None:
        n = jax.lax.psum(1, axes)
    k_shard, N = w_shard.shape
    if n <= 1:
        return matmul_reference(x, w_shard) if impl == "dense" \
            else shard_major_matmul(x, w_shard, 1)
    if wire_bits:
        flat = w_shard.reshape(-1)
        wv, s = quant_pack_wire(flat, wire_bits, group_size)
        w_all = jax.lax.all_gather(wv, axes, axis=0, tiled=False)
        s_all = jax.lax.all_gather(s, axes, axis=0, tiled=False)
        if impl == "pallas":
            return _gathered_dequant_matmul(
                x, w_all, s_all, wire_bits, k_shard, N,
                jnp.promote_types(x.dtype, w_shard.dtype))
        padded = wv.shape[0] * group_size
        vals = unpack_dequant_wire(w_all.reshape(-1, wv.shape[1]),
                                   s_all.reshape(-1, 1), wire_bits)
        w_full = vals.reshape(n, padded)[:, :k_shard * N].reshape(-1, N)
        return matmul_reference(x, w_full.astype(w_shard.dtype))
    w_full = jax.lax.all_gather(w_shard, axes, axis=0, tiled=True)
    if impl == "pallas":
        return shard_major_matmul(x, w_full, 1)
    return matmul_reference(x, w_full)


# --------------------------------------------------------------------- #
# (c) fused RMSNorm + matmul epilogue
# --------------------------------------------------------------------- #
def rmsnorm_matmul_reference(x: jnp.ndarray, scale: jnp.ndarray,
                             w: jnp.ndarray, eps: float) -> jnp.ndarray:
    """The unfused composition (``models/transformer.py rms_norm`` followed
    by the projection matmul) the fused kernel is parity-checked against."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    h = (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale
    return matmul_reference(h, w)


def _rmsnorm_matmul_kernel(eps, x_ref, s_ref, w_ref, o_ref):
    x = x_ref[:]
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    h = (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * s_ref[:]
    o_ref[:] = jnp.dot(h, w_ref[:],
                       preferred_element_type=jnp.float32
                       ).astype(o_ref.dtype)


#: Mosaic's default scoped-VMEM limit on v4/v5e/v5p (v6e allows 32 MiB); the
#: kernel's tiles are sized to the smallest so one choice compiles everywhere
_VMEM_LIMIT_BYTES = 16 * 1024 * 1024


def rmsnorm_vmem_bytes(bm: int, bn: int, D: int, in_bytes: int,
                       out_bytes: int) -> int:
    """Upper bound on the kernel's VMEM need for one ``(bm, D) x (D, bn)``
    grid step: double-buffered x / w / out tiles plus the norm's
    intermediates (the f32 copy of the row tile and the normalized tile in
    the input dtype).  Checked against the chip's compiler at D = 4096 and
    8192 (tests/unit/test_chip_compile.py): every tiling this admits
    compiles; it over-counts by 1-4 MiB where Mosaic reuses a buffer."""
    tiles = 2 * (bm * D + D * bn) * in_bytes + 2 * bm * bn * out_bytes
    return tiles + bm * D * (4 + in_bytes)


def rmsnorm_blocks(M: int, D: int, F: int, in_dtype, out_dtype,
                   block_m: int = 256, block_n: int = 512
                   ) -> Optional[Tuple[int, int]]:
    """``(bm, bn)`` for the fused kernel, or ``None`` when no legal tiling
    fits VMEM — the caller then runs the unfused composition.

    Legal on the chip: ``bn`` a multiple of the 128-lane width (or all of
    ``F``), ``bm`` a multiple of the dtype's sublane packing (8 rows of
    f32, 16 of bf16; or all of ``M``), both dividing their dimension (a
    partial block would pad the walk).  The row tile is taken as large as
    it goes first: the weight is re-streamed once per row tile, so ``bm``
    sets the kernel's HBM traffic; ``bn`` only sets how often the norm is
    recomputed."""
    in_bytes = jnp.dtype(in_dtype).itemsize
    out_bytes = jnp.dtype(out_dtype).itemsize
    sublane = 32 // in_bytes
    rows = [d for d in range(min(M, block_m), 0, -1)
            if M % d == 0 and (d % sublane == 0 or d == M)]
    cols = [d for d in range(min(F, block_n), 0, -1)
            if F % d == 0 and (d % 128 == 0 or d == F)]
    for bm in rows:
        for bn in cols:
            if rmsnorm_vmem_bytes(bm, bn, D, in_bytes,
                                  out_bytes) <= _VMEM_LIMIT_BYTES:
                return bm, bn
    return None


@_partial(jax.custom_vjp, nondiff_argnums=(0, 4, 5))
def _rmsnorm_matmul_pallas(eps, x2, scale, w, bm, bn):
    """Fused kernel over ``x2 [M, D] @ w [D, F]`` in ``(bm, bn)`` tiles
    (from :func:`rmsnorm_blocks`) with a custom VJP: the forward is the
    Pallas kernel, the backward differentiates the reference composition
    (same math — the forward is bitwise against it, test-asserted — so the
    cotangents are the unfused path's).  Without this, ``jax.grad``
    through the ``pallas_call`` raises and the ``fused_rmsnorm="auto"``
    default would break TPU *training* (the same reason
    ``flash_attention`` carries a custom VJP)."""
    M, D = x2.shape
    F = w.shape[1]
    out_dtype = jnp.promote_types(x2.dtype, w.dtype)
    return pl.pallas_call(
        _partial(_rmsnorm_matmul_kernel, eps),
        grid=(M // bm, F // bn),
        in_specs=[pl.BlockSpec((bm, D), lambda i, j: (i, 0)),
                  pl.BlockSpec((1, D), lambda i, j: (0, 0)),
                  pl.BlockSpec((D, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, F), out_dtype),
        interpret=_interpret(),
        name="rmsnorm_matmul",
    )(x2, scale, w)


def _rmsnorm_matmul_fwd(eps, x2, scale, w, bm, bn):
    return _rmsnorm_matmul_pallas(eps, x2, scale, w, bm, bn), \
        (x2, scale, w)


def _rmsnorm_matmul_bwd(eps, _bm, _bn, res, g):
    x2, scale, w = res
    _, vjp = jax.vjp(
        lambda x, s, ww: rmsnorm_matmul_reference(x, s.reshape(-1), ww,
                                                  eps), x2, scale, w)
    dx, ds, dw = vjp(g)
    return dx, ds.reshape(scale.shape), dw


_rmsnorm_matmul_pallas.defvjp(_rmsnorm_matmul_fwd, _rmsnorm_matmul_bwd)


def rmsnorm_matmul(x: jnp.ndarray, scale: jnp.ndarray, w: jnp.ndarray,
                   eps: float, impl: str = "auto",
                   block_m: int = 256, block_n: int = 512) -> jnp.ndarray:
    """``rms_norm(x, scale, eps) @ w`` in one kernel: the norm's variance/
    rsqrt is recomputed per output row tile (VPU work over rows already in
    VMEM for the dot), so the normalized activations never round-trip HBM.

    ``x`` may carry leading batch dims; the last dim contracts with ``w``
    ``[D, F]``.  Per-tile math is the exact ``rms_norm`` composition, so
    the fused kernel is bitwise against
    :func:`rmsnorm_matmul_reference` — test-asserted.  Differentiable:
    the Pallas path carries a custom VJP whose backward is the reference
    composition's (training through the fused model works).

    ``block_m``/``block_n`` cap the tiles :func:`rmsnorm_blocks` picks from
    the shapes.  Where no tiling fits VMEM (very wide ``D``, rows that do
    not tile), ``"auto"`` runs the unfused composition and an explicit
    ``"pallas"`` raises — the kernel is never asked for what the chip's
    compiler would refuse.
    """
    explicit = impl != "auto"
    if resolve_impl(impl) == "dense":
        return rmsnorm_matmul_reference(x, scale, w, eps)
    lead = x.shape[:-1]
    D = x.shape[-1]
    x2 = x.reshape(-1, D)
    blocks = rmsnorm_blocks(x2.shape[0], D, w.shape[1], x2.dtype,
                            jnp.promote_types(x2.dtype, w.dtype),
                            block_m, block_n)
    if blocks is None:
        what = (f"rmsnorm_matmul: no tiling of [{x2.shape[0]}, {D}] x "
                f"[{D}, {w.shape[1]}] {x2.dtype} fits "
                f"{_VMEM_LIMIT_BYTES >> 20} MiB of VMEM")
        if explicit:
            raise ValueError(what)
        from ..utils.logging import warning_once

        warning_once(what + "; running the unfused composition")
        return rmsnorm_matmul_reference(x, scale, w, eps)
    out = _rmsnorm_matmul_pallas(float(eps), x2, scale.reshape(1, D), w,
                                 *blocks)
    return out.reshape(lead + (w.shape[1],))


def supports_fused_rmsnorm() -> bool:
    """Whether the fused RMSNorm+matmul path should be used by default —
    TPU only (the CPU sim keeps the unfused jaxpr so tier-1 numerics and
    compile behavior are unchanged; parity is asserted through the
    interpreter seam in the kernel tests)."""
    from ..accelerator import get_accelerator

    return bool(get_accelerator().supports_pallas())
