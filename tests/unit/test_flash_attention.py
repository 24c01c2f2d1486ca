"""Flash attention kernel vs XLA reference (reference pattern:
tests/unit/ops/transformer/inference kernel-vs-torch tests).

On the CPU backend Pallas runs in interpret-compatible lowering via
pltpu — these tests exercise the kernel on the 8-dev CPU sim where supported,
else skip (real check happens on TPU via bench/driver).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import _xla_attention


def _pallas_supported():
    try:
        from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

        q = jnp.zeros((1, 128, 1, 64))
        flash_attention(q, q, q)
        return True
    except Exception:
        return False


pytestmark = [
    pytest.mark.kernels,
    pytest.mark.skipif(not _pallas_supported(),
                       reason="pallas not supported on this backend"),
]


F32, BF16 = jnp.float32, jnp.bfloat16


def _inputs(seed, q_shape, kv_shape, dtype):
    """Normal draws rounded to ``dtype``, and the same values as float32:
    the reference attends the numbers the kernel was handed."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    got = [jax.random.normal(key, shape, F32).astype(dtype)
           for key, shape in zip(keys, (q_shape, kv_shape, kv_shape))]
    return got, [x.astype(F32) for x in got]


def _assert_close(got, want, dtype, f32_tol):
    """float32 inputs: the tolerances these tests have always had (float32
    dots, as before).  bf16 inputs: bf16 keeps 8 bits of mantissa, so one
    rounding moves a value by at most 2**-9 of itself.  The kernel rounds a
    term at most four times on its way to an output element (``do`` arrives
    as bf16; ``p``, then ``ds``, enter their dots as bf16; the result is
    stored as bf16) and every term of a sum is at most the sum's largest
    size, so: each element within 4 * 2**-9 = 2**-7 of the LARGEST
    reference element, and the whole tensor within 2**-7 in relative L2
    (measured 2**-8.6: the roundings do not line up).  A wrong mask, scale
    or transpose is off by O(1)."""
    got, want = np.asarray(got, np.float32), np.asarray(want)
    if dtype == F32:
        np.testing.assert_allclose(got, want, atol=f32_tol, rtol=f32_tol)
        return
    assert got.dtype == np.float32 and np.isfinite(got).all()
    assert np.abs(got - want).max() <= 2.0 ** -7 * np.abs(want).max()
    assert np.linalg.norm(got - want) <= 2.0 ** -7 * np.linalg.norm(want)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype, S", [
    (F32, 128), (F32, 256), (F32, 384),
    (BF16, 128), (BF16, 200), (BF16, 512)])     # 200: a padded last block
def test_forward_matches_xla(causal, dtype, S):
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    B, H, hd = 2, 4, 64
    (q, k, v), f32 = _inputs(0, (B, S, H, hd), (B, S, H, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    assert out.dtype == dtype
    _assert_close(out, _xla_attention(*f32, causal=causal), dtype, 2e-3)


@pytest.mark.parametrize("dtype", [F32, BF16])
def test_gqa_forward(dtype):
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    B, S, H, KV, hd = 1, 256, 8, 2, 64
    (q, k, v), f32 = _inputs(1, (B, S, H, hd), (B, S, KV, hd), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    _assert_close(out, _xla_attention(*f32, causal=True), dtype, 2e-3)


@pytest.mark.parametrize("dtype, S, KV", [
    (F32, 256, 2), (BF16, 256, 2), (BF16, 200, 2), (BF16, 256, 1)])
def test_backward_matches_xla(dtype, S, KV):
    """dq, dk, dv.  The bf16 cases' reference is float32 XLA attention of
    the same bf16-rounded inputs, differentiated in float32."""
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    B, H, hd = 1, 2, 64
    qkv, f32 = _inputs(2, (B, S, H, hd), (B, S, KV, hd), dtype)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=128,
                                       block_k=128).astype(F32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(*qkv)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(*f32)
    for a, b, x in zip(g1, g2, qkv):
        assert a.dtype == dtype and a.shape == x.shape
        _assert_close(a, b, dtype, 5e-3)


@pytest.mark.parametrize("dtype", [F32, BF16])
@pytest.mark.parametrize("hd, vd", [(192, 128), (96, 64), (64, 64),
                                    (128, 128)])
def test_v_width_of_its_own(hd, vd, dtype):
    """Latent attention trains at q/k width 192 and v width 128: forward,
    dq, dk and dv against XLA math, the equal-width cases beside them.  The
    output and dv are ``vd`` wide, dq and dk ``hd``; the scale is the q/k
    width's."""
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    B, S, H = 1, 200, 2                 # 200: a padded last block
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    shapes = ((B, S, H, hd), (B, S, H, hd), (B, S, H, vd))
    qkv = [jax.random.normal(k, s, F32).astype(dtype)
           for k, s in zip(keys, shapes)]
    f32 = [x.astype(F32) for x in qkv]

    out = flash_attention(*qkv, causal=True, block_q=128, block_k=128)
    assert out.shape == (B, S, H, vd) and out.dtype == dtype
    _assert_close(out, _xla_attention(*f32, causal=True), dtype, 2e-3)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=128,
                                       block_k=128).astype(F32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(*qkv)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(*f32)
    for a, b, x in zip(g1, g2, qkv):
        assert a.dtype == dtype and a.shape == x.shape
        _assert_close(a, b, dtype, 5e-3)


def _kernel_dots(fn, *args):
    """``{kernel name: [(lhs dtype, rhs dtype, result dtype), ...]}`` of
    every ``dot_general`` inside the Pallas kernels ``fn`` traces to."""
    from jax.extend import core as jex

    def subjaxprs(params):
        for value in params.values():
            for item in value if isinstance(value, (list, tuple)) else [value]:
                if isinstance(item, jex.ClosedJaxpr):
                    yield item.jaxpr
                elif isinstance(item, jex.Jaxpr):
                    yield item

    found = {}

    def walk(jaxpr, kernel):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                assert kernel is not None
                found[kernel].append(tuple(
                    v.aval.dtype for v in (*eqn.invars, *eqn.outvars)))
            inside = kernel
            if eqn.primitive.name == "pallas_call":
                inside = eqn.params["name"]
                found.setdefault(inside, [])
            for sub in subjaxprs(eqn.params):
                walk(sub, inside)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, None)
    return found


@pytest.mark.parametrize("dtype", [BF16, F32])
def test_dots_take_the_inputs_dtype_and_accumulate_in_float32(dtype):
    """bf16 inputs: no ``dot_general`` of the three kernels has a float32
    operand (the MXU takes them in one pass) and every one accumulates in
    float32.  float32 inputs: float32 dots, as before the operands were
    left as they lie.  2 + 3 + 4 products: s, p.v / s, dp, ds.k / s, pT.do,
    dp, dsT.q."""
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    (q, k, v), _ = _inputs(3, (1, 256, 2, 64), (1, 256, 2, 64), dtype)

    def loss(q, k, v):
        return flash_attention(q, k, v, block_q=128,
                               block_k=128).astype(F32).sum()

    dots = _kernel_dots(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert {name: len(d) for name, d in dots.items()} == {
        "flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
    for name, kernel_dots in dots.items():
        for lhs, rhs, out in kernel_dots:
            assert (lhs, rhs, out) == (dtype, dtype, F32), (name, lhs, rhs)
