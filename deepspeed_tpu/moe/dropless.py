"""Dropless expert layer for serving: sigmoid scores with a selection bias
(``noaux_tc``), renormalised and scaled weights, a grouped matmul over the
(token, choice) pairs sorted by expert, and a shared expert added to every
token.

No capacity and no dropped pair: the pairs of one expert are a contiguous
group of rows, the grouped matmul walks each group in row tiles (a tile that
straddles two groups is visited once per group, masked), so the padding is
to a tile and never to a capacity.  At decode widths (a handful of tokens an
expert) the layer is a stream of every touched expert's weights.

On the TPU the grouped matmul is ``jax.experimental.pallas.ops.tpu.megablox``;
elsewhere ``jax.lax.ragged_dot`` (same semantics, XLA).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def sigmoid_topk_route(h, router: Dict, k: int, scaling: float,
                       renormalise: bool = True):
    """``h`` [T, D] → (expert ids [T, k] int32, weights [T, k] float32).

    ``s = sigmoid(h·W_r)`` in float32; the experts are the top ``k`` of
    ``s + b``; the weights are ``s`` at those experts WITHOUT ``b``,
    divided by their sum (+1e-20) and scaled."""
    logits = jnp.dot(h.astype(jnp.float32),
                     router["kernel"].astype(jnp.float32), precision=_HI)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + router["bias"].astype(jnp.float32), k)
    g = jnp.take_along_axis(s, idx, axis=-1)
    if renormalise:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), g * scaling


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _tile(dim: int, want: int) -> int:
    """Largest multiple of 128 that divides ``dim`` and is <= ``want``
    (``dim`` itself when it is smaller or has no such divisor)."""
    best = None
    for t in range(128, min(dim, want) + 1, 128):
        if dim % t == 0:
            best = t
    return best or dim


def grouped_matmul(x, w, group_sizes, impl: Optional[str] = None):
    """``x`` [M, K] rows sorted by group, ``w`` [G, K, N], ``group_sizes``
    [G] int32 summing to M → [M, N] in ``x``'s dtype, float32 accumulate.
    M is a multiple of the row tile (see :func:`dropless_experts`)."""
    if impl is None:
        impl = "megablox" if _on_tpu() else "ragged_dot"
    if impl == "ragged_dot":
        return jax.lax.ragged_dot(
            x, w, group_sizes, preferred_element_type=jnp.float32
        ).astype(x.dtype)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    M, K = x.shape
    N = w.shape[-1]
    tiling = (min(M, 128), _tile(K, 1792), _tile(N, 512))
    return gmm(x, w, group_sizes, preferred_element_type=x.dtype,
               tiling=tiling, interpret=not _on_tpu())


def dropless_experts(h, idx, weights, experts: Dict,
                     valid=None, impl: Optional[str] = None, layer=None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Routed experts' part of the layer: ``Σ_k g_k · E_idx_k(h)``.

    ``h`` [T, D]; ``idx``/``weights`` [T, k]; ``experts``: ``gate``/``up``
    [E, D, F], ``down`` [E, F, D] — or, with ``layer`` (a traced index),
    the whole stack ``[L, E, ...]``: the grouped matmul then takes the
    stack as ``L·E`` groups of which only this layer's are non-empty.  A
    layer's slice of the stack handed to a Mosaic call is first COPIED by
    XLA (a custom call cannot fuse the dynamic slice): three copies of 470
    MB a layer a step at Xing4.0-29B's widths, over half of a decode step
    (read on the chip, PR 28).  Returns ([T, D], pairs per expert [E] int32
    over the ``valid`` tokens).  Rows of invalid (padding) tokens are
    computed like any other and never read."""
    T, D = h.shape
    k = idx.shape[1]
    E = experts["gate"].shape[-3]
    M = T * k
    flat = idx.reshape(M)
    order = jnp.argsort(flat, stable=True)              # pairs by expert
    token_of = order // k
    sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1)
    x = jnp.take(h, token_of, axis=0)                   # [M, D]
    # rows to a whole tile: the extra rows are zeros in the last group
    tm = 128 if M >= 128 else -(-M // 16) * 16
    M_pad = -(-M // tm) * tm
    if M_pad != M:
        x = jnp.pad(x, ((0, M_pad - M), (0, 0)))
        sizes = sizes.at[E - 1].add(M_pad - M)
    if layer is not None:
        L = experts["gate"].shape[0]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((L * E,), jnp.int32), sizes, (layer * E,))
        experts = {n: w.reshape((L * E,) + w.shape[2:])
                   for n, w in experts.items()}
    with jax.named_scope("moe/experts"):
        g = grouped_matmul(x, experts["gate"], sizes, impl)
        u = grouped_matmul(x, experts["up"], sizes, impl)
        y = grouped_matmul((jax.nn.silu(g) * u).astype(h.dtype),
                           experts["down"], sizes, impl)[:M]
    with jax.named_scope("moe/combine"):
        inv = jnp.argsort(order)                        # back to pair order
        y = jnp.take(y, inv, axis=0).reshape(T, k, D)
        out = jnp.einsum("tk,tkd->td", weights,
                         y.astype(jnp.float32)).astype(h.dtype)
    counted = flat if valid is None else jnp.where(
        jnp.repeat(valid, k), flat, E)
    pairs = jnp.zeros((E + 1,), jnp.int32).at[counted].add(1)[:E]
    return out, pairs


def sigmoid_moe_block(h, lp: Dict, *, k: int, scaling: float,
                      renormalise: bool = True, valid=None,
                      impl: Optional[str] = None, experts=None, layer=None):
    """The whole expert layer of one decoder layer: routed experts plus the
    shared expert.  ``lp`` holds ``router`` (``kernel`` [D, E], ``bias``
    [E]), ``shared`` (``gate``/``up`` [D, Fs], ``down`` [Fs, D]) and
    ``experts`` — unless the whole stack of experts and this layer's index
    in it are given apart (``experts``, ``layer``; see
    :func:`dropless_experts`).  → ([T, D], pairs per expert [E])."""
    with jax.named_scope("moe/route"):
        idx, weights = sigmoid_topk_route(h, lp["router"], k, scaling,
                                          renormalise)
    routed, pairs = dropless_experts(
        h, idx, weights, lp["experts"] if experts is None else experts,
        valid=valid, impl=impl, layer=layer)
    with jax.named_scope("moe/shared"):
        sh = lp["shared"]
        shared = (jax.nn.silu(h @ sh["gate"]) * (h @ sh["up"])) @ sh["down"]
    with jax.named_scope("moe/combine"):
        return routed + shared, pairs
