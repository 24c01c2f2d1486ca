"""Pallas TPU flash attention (fwd + bwd), the framework's hot attention op.

Reference analogues: the CUDA inference/training attention kernels
(``csrc/transformer/inference/csrc/softmax.cu``, evoformer/cutlass attention
``csrc/deepspeed4science/evoformer_attn``, FastGen ``blocked_flash``).  This is
the TPU equivalent: blocked online-softmax attention tiled for the MXU, with a
recompute-based backward (dq and dkv kernels), exposed through
``jax.custom_vjp`` so it drops into any autodiff'd model.

Layout: inputs [B, S, H, hd] (GQA allowed: KV heads = H // group).  The kernel
operates per (batch, head, q-block) with kv-blocks as the innermost grid dim,
accumulating in VMEM scratch (f32).  Causal masking skips fully-masked blocks.
``v`` may have a head width of its own (latent attention trains at q/k 192,
v 128): the output, ``do``, ``dv`` and the forward's accumulator take ``v``'s
width, ``dq`` and ``dk`` the queries'; nothing is padded.

Precision: every dot takes its operands in the dtype the inputs arrive in
and accumulates in float32.  The scores, the probabilities, ``ds`` and the
row statistics (``m``, ``l``, ``lse``, ``delta``) are float32 whatever the
inputs are; ``p`` and ``ds`` are rounded to the operand dtype only where they
enter a dot as its left operand.  On the TPU a float32 dot at default
precision is one bf16 pass of the MXU too, so there the dtype moves neither
the result nor the time (PERF.md section 6, PR 54); in interpret mode and on
the CPU bf16 inputs now compute what the chip does.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
_NEG_INF = -1e30
#: Row-stats arrays (lse, delta) carry a trailing lane dim so their block
#: shape satisfies Mosaic's (sublane, lane) tiling rule — a rank-3 [B, H, S]
#: block of (1, 1, block_q) fails lowering on real TPUs.  8 lanes (== the
#: array dim, which Mosaic accepts) keeps the residual 16x smaller than the
#: canonical 128-lane layout.
_STATS_LANES = 8


def _interpret() -> bool:
    """Pallas TPU kernels run in interpreter mode on non-TPU backends
    (CPU-simulated meshes in tests)."""
    return jax.default_backend() != "tpu"


def _cdiv(a, b):
    return (a + b - 1) // b


def _dot_tn(a, b):
    """``a.T @ b`` with float32 accumulation: [K, M] x [K, N] -> [M, N].
    Mosaic lowers the contraction over dimension 0 and ``jnp.dot(a.T, b)``
    alike (equal times on the v5e: ``tools/flash_split.py``, ``tree+T``)."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _causal_kv_index(causal: bool, block_q: int, block_k: int):
    """KV index map with the causal DMA skip: fully-masked kv blocks clamp
    to the last needed one, so Pallas skips the copy (unchanged block
    between consecutive grid steps).  Grid order (b, h, iq, ik)."""
    if not causal:
        return lambda b, h, i, j: (b, h, j, 0)

    def index(b, h, i, j):
        needed_last = ((i + 1) * block_q - 1) // block_k
        return (b, h, jnp.minimum(j, needed_last), 0)

    return index


def _causal_q_index(causal: bool, block_q: int, block_k: int):
    """Q-side index map for the dkv grid (b, h, ik, iq): below-diagonal q
    blocks clamp UP to the first needed one (same DMA-skip trick)."""
    if not causal:
        return lambda b, h, j, i: (b, h, i, 0)

    def index(b, h, j, i):
        first_needed = (j * block_k) // block_q
        i_eff = jnp.maximum(i, first_needed)
        return (b, h, i_eff, 0)

    return index


# ===================================================================== #
# Forward kernel
# ===================================================================== #
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr, *,
                scale, causal, block_q, block_k, seq_len):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    q_first = iq * block_q
    k_first = ik * block_k
    # Causal: block fully above the diagonal contributes nothing.
    needed = jnp.logical_or(not causal, q_first + block_q - 1 >= k_first)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0]                                 # [BQ, hd]
        k = k_ref[0, 0]                                 # [BK, hd]
        v = v_ref[0, 0]                                 # [BK, vd]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

        q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_len
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[:, :1]                           # [BQ, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                 # rescale factor
        p = jnp.exp(s - m_new)                          # [BQ, BK]
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc[:] = acc[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(m_scr[:, :1] + jnp.log(l_safe),
                                         lse_ref.shape[2:])


def _fwd(q, k, v, scale, causal, block_q, block_k):
    B, H, S, hd = q.shape
    vd = v.shape[-1]
    nq, nk = _cdiv(S, block_q), _cdiv(S, block_k)
    Sq, Sk = nq * block_q, nk * block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, Sq - S), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, Sk - S), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, Sk - S), (0, 0)))

    # Causal DMA skip: compute for masked blocks
    # is pl.when-gated; the clamped index maps remove their DMA too.
    kv_index = _causal_kv_index(causal, block_q, block_k)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_len=S)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd), kv_index),
            pl.BlockSpec((1, 1, block_k, vd), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, vd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, _STATS_LANES),
                         lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, vd), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, _STATS_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, vd), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(qp, kp, vp)
    return out[:, :, :S], lse[:, :, :S, 0]


# ===================================================================== #
# Backward kernels
# ===================================================================== #
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale, causal, block_q, block_k, seq_len):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_first = iq * block_q
    k_first = ik * block_k
    needed = jnp.logical_or(not causal, q_first + block_q - 1 >= k_first)

    @pl.when(needed)
    def _compute():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        # dp before s: the same values, and on the v5e 10% off this kernel
        # with bf16 operands (PERF.md section 6, PR 54)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_len
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        ds = p * (dp - delta) * scale
        dq_acc[:] += jnp.dot(ds.astype(k.dtype), k,
                             preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _write():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale, causal, block_q, block_k, seq_len):
    ik, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_first = iq * block_q
    k_first = ik * block_k
    needed = jnp.logical_or(not causal, q_first + block_q - 1 >= k_first)

    @pl.when(needed)
    def _compute():
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_len
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv_acc[:] += _dot_tn(p.astype(do.dtype), do)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[:] += _dot_tn(ds.astype(q.dtype), q)

    @pl.when(iq == nq - 1)
    def _write():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, res, g):
    q, k, v, out, lse = res
    do = g
    B, H, S, hd = q.shape
    vd = v.shape[-1]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # [B,H,S]

    nq, nk = _cdiv(S, block_q), _cdiv(S, block_k)
    Sq, Sk = nq * block_q, nk * block_k
    pad_q = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, Sq - S), (0, 0)))
    pad_k = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, Sk - S), (0, 0)))
    qp, kp, vp, dop = pad_q(q), pad_k(k), pad_k(v), pad_q(do)
    pad_r = lambda x: jnp.broadcast_to(
        jnp.pad(x, ((0, 0), (0, 0), (0, Sq - S)))[..., None],
        (B, H, Sq, _STATS_LANES))
    lsep = pad_r(lse)
    deltap = pad_r(delta)

    def q_rows(width):
        return pl.BlockSpec((1, 1, block_q, width),
                            lambda b, h, i, j: (b, h, i, 0))

    def k_rows(width):
        return pl.BlockSpec((1, 1, block_k, width),
                            _causal_kv_index(causal, block_q, block_k))

    q_spec = q_rows(hd)
    r_spec = pl.BlockSpec((1, 1, block_q, _STATS_LANES),
                          lambda b, h, i, j: (b, h, i, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=S),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, k_rows(hd), k_rows(vd), q_rows(vd), r_spec, r_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(qp, kp, vp, dop, lsep, deltap)

    # dkv: kv-blocks outer, q-blocks inner; below-diagonal q blocks are the
    # masked ones here, so the q index map clamps UP to the first needed one
    def q_rows2(width):
        return pl.BlockSpec((1, 1, block_q, width),
                            _causal_q_index(causal, block_q, block_k))

    def k_rows2(width):
        return pl.BlockSpec((1, 1, block_k, width),
                            lambda b, h, j, i: (b, h, j, 0))

    r_spec2 = pl.BlockSpec((1, 1, block_q, _STATS_LANES),
                           _causal_q_index(causal, block_q, block_k))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=S),
        grid=(B, H, nk, nq),
        in_specs=[q_rows2(hd), k_rows2(hd), k_rows2(vd), q_rows2(vd),
                  r_spec2, r_spec2],
        out_specs=[k_rows2(hd), k_rows2(vd)],
        out_shape=[jax.ShapeDtypeStruct((B, H, Sk, hd), k.dtype),
                   jax.ShapeDtypeStruct((B, H, Sk, vd), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                        pltpu.VMEM((block_k, vd), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(qp, kp, vp, dop, lsep, deltap)
    return dq[:, :, :S], dk[:, :, :S], dv[:, :, :S]


# ===================================================================== #
# Public API
# ===================================================================== #
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_bhsd(q, k, v, scale, causal, block_q, block_k):
    out, _ = _fwd(q, k, v, scale, causal, block_q, block_k)
    return out


#: ``checkpoint_name`` tags of the forward kernel's two outputs.  A
#: ``jax.checkpoint`` policy that saves both leaves its backward pass no use
#: for the forward ``pallas_call``; outside a checkpoint a name is the identity
OUT_NAME, LSE_NAME = "flash_out", "flash_lse"


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k):
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k)
    out = checkpoint_name(out, OUT_NAME)
    lse = checkpoint_name(lse, LSE_NAME)
    return out, (q, k, v, out, lse)


_flash_bhsd.defvjp(_flash_fwd_rule, _bwd)


def flash_attention(q, k, v, causal: bool = True, scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K):
    """Flash attention over [B, S, H, hd] inputs (GQA: kv may have fewer heads).

    ``v`` is [B, S, KV, vd]; ``vd`` need not be ``hd``.  Returns [B, S, H, vd].
    Mosaic handles minor dims that are no multiple of 128 lanes (64, 192):
    each width is kept as it is.  The default scale is ``1 / sqrt(hd)``, of
    the q/k width.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    if KV != H:
        assert H % KV == 0, "query heads must be a multiple of kv heads"
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    # Clamp block sizes to the sequence, rounded UP to a lane-aligned
    # multiple of 128 (padding handles S not divisible by the block); a
    # non-128-multiple minor dim fails Mosaic lowering on real TPUs.
    align = lambda x: ((x + 127) // 128) * 128
    bq = min(block_q, align(max(128, S)))
    bk = min(block_k, align(max(128, S)))
    # [B,S,H,hd] -> [B,H,S,hd]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash_bhsd(qt, kt, vt, scale, causal, bq, bk)
    return out.transpose(0, 2, 1, 3)
