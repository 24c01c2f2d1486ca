"""FPDT backward memory proof (reference:
sequence/fpdt_layer.py:510 — offloaded KV must stay off-device through the
BACKWARD pass too)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import _xla_attention
from deepspeed_tpu.sequence.fpdt_layer import chunked_attention

pytestmark = pytest.mark.slow


def _grad_temp_bytes(fn, *args):
    g = jax.jit(jax.grad(lambda *a: fn(*a).sum()))
    mem = g.lower(*args).compile().memory_analysis()
    return int(getattr(mem, "temp_size_in_bytes", 0))


class TestFPDTBackwardMemory:
    def test_remat_keeps_backward_peak_low(self):
        """Without per-step remat, autodiff residuals re-materialize the
        whole KV history during backward (measured ~10x); the default
        remat=True must keep peak temp far below both the dense path and
        the non-remat chunked path."""
        B, S, H, hd, c = 1, 4096, 4, 64, 256
        q = jnp.zeros((B, S, H, hd), jnp.float32)

        full = _grad_temp_bytes(
            lambda q, k, v: _xla_attention(q, k, v, causal=True), q, q, q)
        rematted = _grad_temp_bytes(
            lambda q, k, v: chunked_attention(q, k, v, c, causal=True,
                                              remat=True), q, q, q)
        no_remat = _grad_temp_bytes(
            lambda q, k, v: chunked_attention(q, k, v, c, causal=True,
                                              remat=False), q, q, q)
        assert rematted < full / 4, (rematted, full)
        assert rematted < no_remat / 4, (rematted, no_remat)

    @pytest.mark.parametrize("remat", [True, False])
    def test_backward_numerics_match_dense(self, remat):
        rng = np.random.default_rng(0)
        B, S, H, hd, c = 2, 256, 2, 32, 64
        q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)

        def loss_dense(q, k, v):
            return jnp.sum(_xla_attention(q, k, v, causal=True) ** 2)

        def loss_chunk(q, k, v):
            return jnp.sum(chunked_attention(q, k, v, c, causal=True,
                                             remat=remat) ** 2)

        g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        g_c = jax.grad(loss_chunk, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_d, g_c):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)

    def test_offload_flag_backward_works(self):
        """offload=True (host parking where supported; no-op on CPU) must
        keep the gradient path intact."""
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(1, 128, 2, 16)), jnp.float32)
        g = jax.grad(lambda q: jnp.sum(
            chunked_attention(q, q, q, 32, causal=True, offload=True)))(q)
        assert np.isfinite(np.asarray(g)).all()
