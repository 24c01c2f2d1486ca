"""Pallas block-sparse attention kernel vs the masked-dense oracle
(reference: deepspeed/ops/sparse_attention Triton block-sparse kernels)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.sparse_attention.block_sparse_kernel import (
    block_sparse_attention,
    build_fetch_table,
)
from deepspeed_tpu.ops.sparse_attention.sparse_self_attention import (
    SparseSelfAttention,
)
from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
    BigBirdSparsityConfig,
    FixedSparsityConfig,
)

pytestmark = pytest.mark.kernels


def _qkv(B=2, H=2, S=128, hd=32, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(B, H, S, hd)), jnp.float32)
    return mk(), mk(), mk()


class TestBlockSparseKernel:
    @pytest.mark.parametrize("cfg_cls,kw", [
        (FixedSparsityConfig, dict(num_local_blocks=2, num_global_blocks=1,
                                   attention="unidirectional")),
        (BigBirdSparsityConfig, dict(num_random_blocks=1,
                                     num_sliding_window_blocks=2,
                                     num_global_blocks=1,
                                     attention="bidirectional")),
    ])
    def test_matches_masked_dense(self, cfg_cls, kw):
        q, k, v = _qkv()
        attn = SparseSelfAttention(cfg_cls(num_heads=2, block=16, **kw))
        ref = attn(q, k, v)
        out = attn(q, k, v, use_kernel=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_fetch_table_reuses_last_active_block(self):
        layout = np.array([[[1, 0, 0, 1],
                            [0, 1, 1, 0]]])
        table = build_fetch_table(layout)
        # masked steps re-fetch the last active block (no new DMA)
        np.testing.assert_array_equal(table[0, 0], [0, 0, 0, 3])
        np.testing.assert_array_equal(table[0, 1], [1, 1, 2, 2])

    def test_rows_with_no_active_block_emit_zeros(self):
        q, k, v = _qkv(B=1, H=1, S=32, hd=32)
        layout = np.zeros((1, 2, 2), np.int64)
        layout[0, 0, 0] = 1                  # second q block fully masked
        out = block_sparse_attention(q, k, v, layout, 16)
        assert np.all(np.asarray(out[0, 0, 16:]) == 0.0)
        assert np.any(np.asarray(out[0, 0, :16]) != 0.0)


class TestBlockSparseBackward:
    """Reference ops/sparse_attention/matmul.py fwd+bwd:
    training goes THROUGH the sparse kernels — grad parity vs the
    masked-dense oracle on every layout family, and the backward is the
    Pallas dq/dkv pair (not autodiff through dense attention)."""

    @pytest.mark.parametrize("cfg_cls,kw", [
        (FixedSparsityConfig, dict(num_local_blocks=2, num_global_blocks=1,
                                   attention="unidirectional")),
        (BigBirdSparsityConfig, dict(num_random_blocks=1,
                                     num_sliding_window_blocks=2,
                                     num_global_blocks=1)),
    ])
    def test_grad_parity_vs_masked_dense(self, cfg_cls, kw):
        from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
            BSLongformerSparsityConfig, VariableSparsityConfig)

        q, k, v = _qkv(S=96, hd=32)
        cfg = cfg_cls(num_heads=2, block=16, **kw)
        attn = SparseSelfAttention(cfg)
        layout = np.asarray(cfg.make_layout(96))

        def loss_kernel(q, k, v):
            return jnp.sum(block_sparse_attention(q, k, v, layout, 16) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(attn(q, k, v) ** 2)

        gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gk, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)

    def test_longformer_and_variable_grads(self):
        from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
            BSLongformerSparsityConfig, VariableSparsityConfig)

        q, k, v = _qkv(S=96, hd=32)
        for cfg in (BSLongformerSparsityConfig(
                        num_heads=2, block=16,
                        num_sliding_window_blocks=2, global_block_indices=[0]),
                    VariableSparsityConfig(
                        num_heads=2, block=16, num_random_blocks=0,
                        local_window_blocks=[2], global_block_indices=[0])):
            attn = SparseSelfAttention(cfg)
            layout = np.asarray(cfg.make_layout(96))
            gk = jax.grad(lambda q, k, v: jnp.sum(
                block_sparse_attention(q, k, v, layout, 16) ** 2),
                argnums=(0, 1, 2))(q, k, v)
            gd = jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v) ** 2),
                          argnums=(0, 1, 2))(q, k, v)
            for a, b in zip(gk, gd):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-3, atol=2e-3)

    def test_backward_is_sparse_kernels_not_dense_autodiff(self):
        """The grad program must contain the THREE pallas calls (fwd from
        the vjp rule + dq + dkv) and no dense [S,S] softmax batch-matmul
        chain from autodiff."""
        q, k, v = _qkv(S=64, hd=32)
        cfg = FixedSparsityConfig(num_heads=2, block=16, num_local_blocks=2,
                                  num_global_blocks=1)
        layout = np.asarray(cfg.make_layout(64))

        def loss(q, k, v):
            return jnp.sum(block_sparse_attention(q, k, v, layout, 16) ** 2)

        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

        def count_prim(jxp, name):
            n = 0
            for eqn in jxp.eqns:
                if eqn.primitive.name == name:
                    n += 1
                for val in eqn.params.values():
                    inner = val
                    while hasattr(inner, "jaxpr"):
                        inner = inner.jaxpr
                    if hasattr(inner, "eqns"):
                        n += count_prim(inner, name)
            return n

        assert count_prim(jaxpr.jaxpr, "pallas_call") == 3
