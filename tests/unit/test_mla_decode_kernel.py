"""The latent (MLA) decode kernel against its dense form, interpret mode.

``mla_paged_decode`` hands a sequence's first chunk across grid steps (the
SMEM ``carry`` of ``_mla_decode_kernel``): the previous sequence's walk
starts it behind its own last compute.  What that must not change: contexts
of one token, on and either side of a chunk boundary, of several chunks;
``kv_lens == 0`` rows first, last and BETWEEN live rows (they start nothing,
are handed nothing, return zeros, and the row behind them fetches for
itself); any ``pages_per_chunk``; rows of a buffer that were never fetched,
or were fetched behind the context's end, holding NaN.  The K/V kernel's
cases (``test_serving_decode.py::TestDecodeKernelParity``) are the model.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.kernels import mla_ops

pytestmark = pytest.mark.serving

# Both cells' latent row: 512 c_kv + 64 k_rope lanes in 640, 64-token pages
W, R, PS = 640, 512, 64
SCALE = 192 ** -0.5
# float32 pool: nothing is rounded, summation order only.  bf16 pool: the
# probabilities and both outputs are rounded to bf16 (one ulp, 2^-8,
# between them), as TestDecodeKernelParity.BF16_TOL
TOL = {jnp.float32: dict(rtol=3e-5, atol=3e-5),
       jnp.bfloat16: dict(rtol=2.0 ** -7, atol=6e-3)}
DTYPES = [pytest.param(jnp.float32, id="f32"),
          pytest.param(jnp.bfloat16, id="bf16")]
HEADS = [pytest.param(32, id="32-heads"), pytest.param(64, id="64-heads")]


def _case(seed, ctx, H, dtype, poison=True):
    """One query token a sequence over a pool whose pages are dealt out in
    a random order; ``poison``: every page no walk may read holds NaN."""
    rng = np.random.default_rng(seed)
    S, NB = len(ctx), max(2, -(-max(ctx) // PS) + 1)
    npages = S * NB + 1
    q = jnp.asarray(rng.normal(size=(S, H, W)), dtype)
    pages = rng.normal(size=(npages, PS, W)).astype(np.float32)
    pt = rng.permutation(npages - 1).astype(np.int32).reshape(S, NB)
    if poison:
        for s, c in enumerate(ctx):
            pages[pt[s, -(-c // PS):]] = np.nan
        pages[-1] = np.nan
    return q, jnp.asarray(pages, dtype), jnp.asarray(ctx, jnp.int32), \
        jnp.asarray(pt)


def _assert_matches_dense(q, pages, kvl, pt, **kernel_kw):
    out = mla_ops.mla_paged_decode(q, pages, kvl, pt, rank=R, scale=SCALE,
                                   interpret=True, **kernel_kw)
    assert out.shape == (q.shape[0], q.shape[1], R) and out.dtype == q.dtype
    ref = mla_ops.mla_attend_dense(q[:, None], pages, pt,
                                   jnp.minimum(kvl, 1), kvl, rank=R,
                                   scale=SCALE)[:, 0].astype(q.dtype)
    out, ref = (np.asarray(o.astype(jnp.float32)) for o in (out, ref))
    assert np.all(np.isfinite(out))
    np.testing.assert_allclose(out, ref, **TOL[q.dtype.type])
    for s, c in enumerate(np.asarray(kvl)):
        if c == 0:
            np.testing.assert_array_equal(out[s], 0.0)
    return out


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("H", HEADS)
@pytest.mark.parametrize("ctx", [
    pytest.param([1, 130, 1], id="one-token"),
    pytest.param([512, 511, 513], id="chunk-boundary"),
    pytest.param([1100, 64, 1537], id="several-chunks"),
    pytest.param([0, 513, 0, 65, 0, 0, 512, 0], id="empty-rows-around"),
    pytest.param([640, 64, 600], id="nan-never-fetched"),
])
def test_hand_over_matches_dense(ctx, H, dtype):
    """Every sequence but the first and those behind an empty row walks
    from a chunk the grid step before it started."""
    _assert_matches_dense(*_case(41, ctx, H, dtype))


@pytest.mark.parametrize("H", HEADS)
@pytest.mark.parametrize("ppc", [1, 2, 8])
def test_pages_per_chunk_invariance(ppc, H):
    """1, 2 and 8 pages a chunk (1, 5 or 10 chunks a walk, a hand-over
    into either buffer) walk the same contexts to the same answer."""
    _assert_matches_dense(*_case(42, [577, 0, 256, 129], H, jnp.bfloat16),
                          pages_per_chunk=ppc)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("H", HEADS)
def test_partial_last_page_holds_nan(H, dtype):
    """A context ending INSIDE a page: the page is fetched whole, the rows
    behind the context's end hold NaN and must not reach the output through
    a 0-probability product."""
    ctx = [100, 577]
    q, pages, kvl, pt = _case(43, ctx, H, dtype, poison=False)
    for s, c in enumerate(ctx):
        pages = pages.at[int(pt[s, c // PS]), c % PS:].set(jnp.nan)
    _assert_matches_dense(q, pages, kvl, pt)


def test_a_handed_over_chunk_is_the_next_sequences_own():
    """Two batches that differ only in the SECOND sequence's pages give
    the same first row and different second rows: the chunk started behind
    sequence 0's last compute is read from sequence 1's page table."""
    q, pages, kvl, pt = _case(44, [200, 300], 32, jnp.float32, poison=False)
    other = pages.at[pt[1]].multiply(-1.0)
    a, b = (_assert_matches_dense(q, p, kvl, pt, pages_per_chunk=2)
            for p in (pages, other))
    np.testing.assert_array_equal(a[0], b[0])
    assert np.abs(a[1] - b[1]).max() > 0.1
