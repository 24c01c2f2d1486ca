"""Dropless expert layer for serving: sigmoid scores with a selection bias
(``noaux_tc``) or softmax scores (with one too: :func:`softmax_bias_topk_
route`), renormalised weights or not, a grouped matmul over the (token,
choice) pairs sorted by expert, and a shared expert added to every token
(behind a sigmoid gate where the model has one).  An expert is a gated unit
at the residual's width (``gate``/``up``/``down``) or a plain one at
whatever width the layer hands in (``up``/``down``: Nemotron-H's squared-
ReLU experts in a latent, :func:`latent_moe_block`).  The layer can be told
that it holds only a chip's share of the experts (:func:`dropless_experts`,
``offset``) and that the router's last outputs are experts WITHOUT weights
(``identity_from``: zero-computation identity experts, whose pair adds
``g·h`` and costs no matmul row).

No capacity and no dropped pair: the pairs of one expert are a contiguous
group of rows, the grouped matmul walks each group in row tiles (a tile that
straddles two groups is visited once per group, masked), so the padding is
to a tile and never to a capacity.  At decode widths (a handful of tokens an
expert) the layer is a stream of every touched expert's weights.

:func:`grouped_matmul` is the repo's one grouped matmul and also trains: the
Mixtral block's data-sharded region (``moe/sharded_moe.py``) runs each
shard's kept pairs through it, forward and backward, with tiles chosen from
the shapes per program (:func:`_tilings`).  On the TPU it is
``jax.experimental.pallas.ops.tpu.megablox`` (``gmm``, and for the gradients
``gmm`` on the transposed weights and ``tgmm``); elsewhere
``jax.lax.ragged_dot`` (same semantics, XLA).
"""
from __future__ import annotations

import functools
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..telemetry import get_tracer

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


def softmax_topk_route(h, router: Dict, k: int, renormalise: bool = True):
    """``h`` [T, D] → (expert ids [T, k] int32, weights [T, k] float32):
    ``p = softmax(h·W_r)`` over ALL the router's experts in float32, the top
    ``k``, and with ``renormalise`` ``p_top / sum(p_top)``."""
    logits = jnp.dot(h.astype(jnp.float32),
                     router["kernel"].astype(jnp.float32), precision=_HI)
    g, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    if renormalise:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), g


def softmax_bias_topk_route(h, router: Dict, k: int, scaling: float):
    """``h`` [T, D] → (ids [T, k] int32, weights [T, k] float32) over ALL the
    router's outputs, experts without weights among them: ``s = softmax(h·
    W_r)`` in float32, the top ``k`` of ``s + b``, the weights ``s`` at those
    WITHOUT ``b``, scaled and NOT renormalised (a token's weights sum to
    what its picks' scores sum to)."""
    logits = jnp.dot(h.astype(jnp.float32),
                     router["kernel"].astype(jnp.float32), precision=_HI)
    s = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(s + router["bias"].astype(jnp.float32), k)
    return idx.astype(jnp.int32), jnp.take_along_axis(s, idx, axis=-1) \
        * scaling


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


#: the row tile stops growing here: a tile that straddles two groups is
#: visited once per group, and a taller tile leaves no room in VMEM for a
#: whole contraction (:func:`_tilings`); swept on the chip, PERF.md PR 31
MAX_ROW_TILE = 256


def row_tile(M: int, groups: int) -> int:
    """Row tile of a grouped matmul over ``M`` rows in ``groups`` groups; the
    caller pads its rows to a multiple of it.  128 rows (all of them, to a
    multiple of 16, where there are fewer) as long as a mean group is
    smaller than two tiles — decode and prefill widths, where a tile is
    mostly one expert's padding anyway; :data:`MAX_ROW_TILE` where it is
    larger — training widths, where a 128-row tile re-reads a group's
    weights once per 128 rows."""
    if M < 128:
        return -(-M // 16) * 16
    tile = 128
    while tile * 2 <= min(M // groups, MAX_ROW_TILE):
        tile *= 2
    return tile


def whole_tiles(M: int, groups: int) -> int:
    """``M`` rows up to a whole number of row tiles."""
    tile = row_tile(M, groups)
    return -(-M // tile) * tile


def _tilings(M: int, K: int, N: int, groups: int):
    """Tiles of the three programs of ``[M, K] x [groups, K, N]``: forward
    ``(rows, K, N)``, the rows' gradient ``(rows, N, K)`` (it contracts N),
    the weights' gradient ``(rows, K, N)``.  At a 128-row tile — every
    shape the serving path compiles — the forward's are PR 28's."""
    tm = row_tile(M, groups)

    def rows_by(contracted: int, out: int):
        if tm <= 128:
            return tm, _tile(contracted, 1792), _tile(out, 512)
        if contracted <= 4096:
            # the whole contraction in one block: the next row tile of the
            # same group finds its [K, 512] of weights still in VMEM (a
            # block whose index did not change is not copied again), so the
            # weights are read once and not once per row tile
            return tm, contracted, _tile(out, 512)
        return tm, _tile(contracted, 1024), _tile(out, 2048)

    return rows_by(K, N), rows_by(N, K), (tm, _tile(K, 1024), _tile(N, 1024))


@functools.cache
def _megablox_kernels():
    """megablox's ``gmm`` and ``tgmm``, with the group metadata they derive
    from the sizes under a ``jit`` of its own.  Each kernel builds that
    metadata from some sixty ``jax.numpy`` calls, traced from Python anew
    in every kernel of every program: 2.9 s for the twelve calls of the
    Mixtral step on the chip's host, at every start, compile cache or not
    (PERF.md PR 31).  Under its own ``jit`` it is traced once per (rows,
    tile) and the other kernels find that trace."""
    import importlib

    backend = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    backend.make_group_metadata = jax.jit(
        backend.make_group_metadata,
        static_argnames=("m", "tm", "num_nonzero_groups",
                         "visit_empty_groups"))
    return backend.gmm, backend.tgmm


def _megablox_forward(x, w, group_sizes):
    gmm, _ = _megablox_kernels()
    forward, _, _ = _tilings(*x.shape, w.shape[-1], w.shape[0])
    return gmm(x, w, group_sizes, preferred_element_type=x.dtype,
               tiling=forward, interpret=not _on_tpu())


_megablox = jax.custom_vjp(_megablox_forward)


def _megablox_fwd(x, w, group_sizes):
    return _megablox_forward(x, w, group_sizes), (x, w, group_sizes)


def _megablox_bwd(saved, dy):
    """megablox's own VJP (``ops.gmm``) with a tiling per program."""
    gmm, tgmm = _megablox_kernels()
    x, w, group_sizes = saved
    _, rows, weights = _tilings(*x.shape, w.shape[-1], w.shape[0])
    dy = dy.astype(x.dtype)
    dx = gmm(dy, w, group_sizes, preferred_element_type=x.dtype, tiling=rows,
             transpose_rhs=True, interpret=not _on_tpu())
    dw = tgmm(x.swapaxes(0, 1), dy, group_sizes,
              preferred_element_type=w.dtype, tiling=weights,
              interpret=not _on_tpu())
    return dx, dw, None


_megablox.defvjp(_megablox_fwd, _megablox_bwd)


def grouped_matmul(x, w, group_sizes, impl: Optional[str] = None):
    """``x`` [M, K] rows sorted by group, ``w`` [G, K, N], ``group_sizes``
    [G] int32 → [M, N] in ``x``'s dtype, float32 accumulate.  M is a whole
    number of row tiles (:func:`whole_tiles`).  The sizes may sum to less than M: the rows
    behind the last group are in no group, a tile of them alone is skipped,
    and what the result — and, differentiated, ``x``'s gradient — holds
    there is whatever memory held (megablox) or zeros (``ragged_dot``): the
    caller selects them out.  Differentiable in ``x`` and ``w``."""
    if impl is None:
        impl = "megablox" if _on_tpu() else "ragged_dot"
    if impl == "ragged_dot":
        return jax.lax.ragged_dot(
            x, w, group_sizes, preferred_element_type=jnp.float32
        ).astype(x.dtype)
    return _megablox(x, w, group_sizes)


#: ``checkpoint_name`` tags of the three grouped matmuls' rows over the
#: SORTED pairs (gate, up — or the plain form's one up — and down): what a
#: checkpointed training layer may keep (``models/joyai_flash.py::
#: _remat_layout``); anywhere else a name is the identity
PAIR_ROW_NAMES = ("pair_gate_rows", "pair_up_rows", "pair_down_rows")


@jax.custom_vjp
def _rows_in_groups(x, live):
    """``x`` as it is; differentiated, the rows that are in no group
    (``live`` false) take a ZERO cotangent.  The grouped matmul leaves those
    rows of its input's gradient unwritten (:func:`grouped_matmul`), and the
    gather's transpose would add that memory into the tokens' gradient.  A
    ``where`` on the way in would say the same at the cost of one more pass
    over the rows in the forward."""
    return x


_rows_in_groups.defvjp(
    lambda x, live: (x, live),
    lambda live, dx: (jnp.where(live[:, None], dx, 0).astype(dx.dtype),
                      None))


def dropless_experts(h, idx, weights, experts: Dict,
                     valid=None, impl: Optional[str] = None, layer=None,
                     offset: Optional[int] = None,
                     identity_from: Optional[int] = None,
                     act=jax.nn.silu, trained: bool = False
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Routed experts' part of the layer: ``Σ_k g_k · E_idx_k(h)``.

    ``h`` [T, D]; ``idx``/``weights`` [T, k]; ``experts`` says the expert's
    FORM by the matrices it holds: ``gate``/``up`` [E, D, F] and ``down``
    [E, F, D] is the gated unit ``(act(h W_gate) * h W_up) W_down``;
    ``up``/``down`` alone the plain one ``act(h W_up) W_down`` (``act`` a
    function: SiLU, or a squared ReLU).  ``E``, ``D`` and ``F`` are read
    off ``up``: ``D`` is whatever width the layer hands in (the residual's,
    or a latent's) and ``F`` need not be a power of two (2,688 = 21 x 128
    runs in tiles of 384 and 896: :func:`_tile`).  With ``layer`` (a traced
    index) ``experts`` is
    the whole stack ``[L, E, ...]``: the grouped matmul then takes the
    stack as ``L·E`` groups of which only this layer's are non-empty.  A
    layer's slice of the stack handed to a Mosaic call is first COPIED by
    XLA (a custom call cannot fuse the dynamic slice): three copies of 470
    MB a layer a step at Xing4.0-29B's widths, over half of a decode step
    (read on the chip, PR 28).  Returns ([T, D], pairs per expert [E] int32
    over the ``valid`` tokens).  Rows of invalid (padding) tokens are
    computed like any other and never read.

    A CHIP'S SHARE (``offset`` given): ``experts`` are experts ``offset`` to
    ``offset + E`` of a layer whose router scores more (``idx`` are ids of
    the whole layer).  The pairs of experts held elsewhere are sorted behind
    the last group, where the grouped matmul computes nothing, add nothing
    to the result, and are counted in one more entry at the end of the
    pairs: ``[E + 1]``.  They are not dropped: the chip that holds their
    expert computes them (expert parallelism without its exchange).

    EXPERTS WITHOUT WEIGHTS (``identity_from`` given): ids from
    ``identity_from`` on (of the whole layer, past every real expert) are
    identity experts, ``E_id(h) = h``.  Such a pair is neither held here nor
    elsewhere: it adds ``g·h`` where the token's residual lives (here, for
    every token: ``moe/identity``), is sorted behind the last group like a
    pair held elsewhere (no matmul row), and is counted in an entry of its
    own, the last of the pairs.

    A caller that DIFFERENTIATES the layer says so (``trained``): the rows of
    the three grouped matmuls are then named for its checkpoint policy
    (:data:`PAIR_ROW_NAMES`) and the rows in no group hand the tokens a zero
    cotangent (:func:`_rows_in_groups`).  A serving caller traces exactly
    what it always did."""
    T, D = h.shape
    k = idx.shape[1]
    E = experts["up"].shape[-3]
    M = T * k
    flat = idx.reshape(M)
    identity = None if identity_from is None else flat >= identity_from
    if offset is not None:
        flat = flat - offset
        flat = jnp.where((flat >= 0) & (flat < E), flat, E)
    # buckets behind the E groups: held elsewhere, then identity
    n = E + int(offset is not None) + int(identity is not None)
    if identity is not None:
        flat = jnp.where(identity, n - 1, flat)
    order = jnp.argsort(flat, stable=True)              # pairs by expert
    token_of = order // k
    sizes = jnp.zeros((n,), jnp.int32).at[flat].add(1)
    if n > E:
        sizes = sizes[:E]
    x = jnp.take(h, token_of, axis=0)                   # [M, D]
    if trained and n > E:
        x = _rows_in_groups(x, jnp.take(flat, order) < E)
    # rows to a whole tile: the extra rows are zeros in the last group (where
    # pairs lie behind it, held elsewhere or identity: in no group, as they)
    M_pad = whole_tiles(M, E)
    if M_pad != M:
        x = jnp.pad(x, ((0, M_pad - M), (0, 0)))
        if n == E:
            sizes = sizes.at[E - 1].add(M_pad - M)
    if layer is not None:
        L = experts["up"].shape[0]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((L * E,), jnp.int32), sizes, (layer * E,))
        experts = {n: w.reshape((L * E,) + w.shape[2:])
                   for n, w in experts.items()}
    with jax.named_scope("moe/experts"):
        name = checkpoint_name if trained else (lambda x, _: x)
        gate_rows, up_rows, down_rows = PAIR_ROW_NAMES
        if "gate" in experts:
            g = name(grouped_matmul(x, experts["gate"], sizes, impl),
                     gate_rows)
            u = name(grouped_matmul(x, experts["up"], sizes, impl), up_rows)
            u = act(g) * u
        else:
            u = act(name(grouped_matmul(x, experts["up"], sizes, impl),
                         up_rows))
        y = name(grouped_matmul(u.astype(h.dtype), experts["down"], sizes,
                                impl), down_rows)[:M]
    with jax.named_scope("moe/combine"):
        inv = jnp.argsort(order)                        # back to pair order
        y = jnp.take(y, inv, axis=0).reshape(T, k, D)
        if n > E:
            # a row in no group holds whatever memory held: select, then
            # weigh
            y = jnp.where((flat < E).reshape(T, k, 1), y, 0)
        out = jnp.einsum("tk,tkd->td", weights, y.astype(jnp.float32))
        if identity is None:
            out = out.astype(h.dtype)
    if identity is not None:
        with jax.named_scope("moe/identity"):
            # Σ g over a token's identity picks, times its own input; added
            # in float32 to the experts' sum before the one rounding
            g_id = jnp.sum(jnp.where(identity.reshape(T, k), weights, 0.0),
                           axis=-1, keepdims=True)
            out = (out + g_id * h.astype(jnp.float32)).astype(h.dtype)
    counted = flat if valid is None else jnp.where(
        jnp.repeat(valid, k), flat, n)
    pairs = jnp.zeros((n + 1,), jnp.int32).at[counted].add(1)[:n]
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


def softmax_moe_block(h, lp: Dict, *, k: int, renormalise: bool = True,
                      offset: Optional[int] = None, valid=None,
                      impl: Optional[str] = None, experts=None, layer=None):
    """The expert layer of a Qwen3-Next decoder layer: softmax-routed experts
    (all of them, or with ``offset`` a chip's share: see
    :func:`dropless_experts`) plus the shared expert behind its sigmoid
    gate.  ``lp``: ``router`` (``kernel`` [D, E_all]), ``shared`` (``gate``/
    ``up`` [D, Fs], ``down`` [Fs, D]), ``shared_gate`` (``kernel`` [D, 1]),
    ``experts`` unless the stack and ``layer`` are given apart.  → ([T, D],
    pairs per expert held [E] or, of a share, [E + 1])."""
    with jax.named_scope("moe/route"):
        idx, weights = softmax_topk_route(h, lp["router"], k, renormalise)
    routed, pairs = dropless_experts(
        h, idx, weights, lp["experts"] if experts is None else experts,
        valid=valid, impl=impl, layer=layer, offset=offset)
    with jax.named_scope("moe/shared"):
        sh = lp["shared"]
        shared = (jax.nn.silu(h @ sh["gate"]) * (h @ sh["up"])) @ sh["down"]
        gate = jax.nn.sigmoid((h @ lp["shared_gate"]["kernel"]
                               ).astype(jnp.float32))
        shared = (gate * shared.astype(jnp.float32)).astype(h.dtype)
    with jax.named_scope("moe/combine"):
        return routed + shared, pairs


def zero_expert_moe_block(h, lp: Dict, *, k: int, scaling: float,
                          identity_from: Optional[int],
                          offset: Optional[int] = None, valid=None,
                          impl: Optional[str] = None, experts=None,
                          layer=None):
    """The expert layer of a LongCat-Flash decoder layer: a softmax router
    with a selection bias over real experts and identity experts alike
    (:func:`softmax_bias_topk_route`; ids from ``identity_from`` on are the
    identity ones, None: there are none), the real experts all held or with
    ``offset`` a chip's share (:func:`dropless_experts`), no shared expert.
    ``lp``: ``router`` (``kernel`` [D, E_all + Z], ``bias`` [E_all + Z]),
    ``experts`` unless the stack and ``layer`` are given apart.  → ([T, D],
    pairs: the experts held [E], of a share then those held elsewhere, then
    the identity ones).  Every traced call leaves one ring-only
    ``moe/serve_layout`` record."""
    if experts is None:
        experts = lp["experts"]
    held = experts["gate"].shape[-3]
    # trace time only: what a run says about the share it compiled
    get_tracer().record(
        "moe/serve_layout", time.perf_counter(), 0.0,
        router_outputs=lp["router"]["kernel"].shape[-1], held=held,
        offset=offset, identity_from=identity_from, k=k,
        rows=whole_tiles(h.shape[0] * k, held))
    with jax.named_scope("moe/route"):
        idx, weights = softmax_bias_topk_route(h, lp["router"], k, scaling)
    return dropless_experts(h, idx, weights, experts, valid=valid, impl=impl,
                            layer=layer, offset=offset,
                            identity_from=identity_from)


def squared_relu(x):
    return jnp.square(jax.nn.relu(x))


def latent_moe_block(h, lp: Dict, *, k: int, scaling: float,
                     renormalise: bool = True, offset: Optional[int] = None,
                     valid=None, impl: Optional[str] = None, experts=None,
                     layer=None, act=squared_relu):
    """The expert layer of a Nemotron-H decoder layer: sigmoid scores with a
    selection bias over ALL the router's experts (:func:`sigmoid_topk_route`,
    on the layer's input at the residual's width), routed experts that live
    in a LATENT — ``l = h W_down`` (``moe/latent_down``), ungated experts
    ``act(l W1_e) W2_e`` at the latent's width (:func:`dropless_experts`'s
    plain form), their weighted sum up-projected (``moe/latent_up``) — and a
    shared expert of the same plain form on the full width (``moe/shared``).
    With ``offset`` the experts are a chip's share: the sum over the experts
    HELD is formed in the latent and up-projected, and the up-projection is
    linear, so the shares' parts plus the shared expert once are the uncut
    layer.  ``lp``: ``router`` (``kernel`` [D, E_all], ``bias`` [E_all]),
    ``latent_down`` [D, R], ``latent_up`` [R, D], ``shared`` (``up`` [D,
    Fs], ``down`` [Fs, D]), ``experts`` (``up`` [E, R, F], ``down`` [E, F,
    R]) unless the stack and ``layer`` are given apart.  → ([T, D], pairs per
    expert held [E] or, of a share, [E + 1])."""
    with jax.named_scope("moe/route"):
        idx, weights = sigmoid_topk_route(h, lp["router"], k, scaling,
                                          renormalise)
    with jax.named_scope("moe/latent_down"):
        latent = h @ lp["latent_down"]["kernel"]
    routed, pairs = dropless_experts(
        latent, idx, weights, lp["experts"] if experts is None else experts,
        valid=valid, impl=impl, layer=layer, offset=offset, act=act)
    with jax.named_scope("moe/latent_up"):
        routed = routed @ lp["latent_up"]["kernel"]
    with jax.named_scope("moe/shared"):
        sh = lp["shared"]
        shared = act(h @ sh["up"]) @ sh["down"]
    with jax.named_scope("moe/combine"):
        return routed + shared, pairs
