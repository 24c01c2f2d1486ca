"""FPDT chunked attention + Domino overlap tests (reference:
sequence/fpdt tests in tests/unit/sequence_parallelism, domino tests)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.transformer import _xla_attention
from deepspeed_tpu.runtime.topology import TENSOR, TopologyConfig, initialize_mesh

pytestmark = pytest.mark.core


class TestChunkedAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        from deepspeed_tpu.sequence.fpdt_layer import chunked_attention

        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        B, S, H, hd = 2, 128, 4, 16
        q = jax.random.normal(ks[0], (B, S, H, hd))
        k = jax.random.normal(ks[1], (B, S, H, hd))
        v = jax.random.normal(ks[2], (B, S, H, hd))
        out = chunked_attention(q, k, v, chunk_size=32, causal=causal)
        ref = _xla_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.slow

    def test_gqa_and_grads(self):
        from deepspeed_tpu.sequence.fpdt_layer import chunked_attention

        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 64, 4, 8))
        k = jax.random.normal(ks[1], (1, 64, 2, 8))
        v = jax.random.normal(ks[2], (1, 64, 2, 8))
        g = jax.grad(lambda q: jnp.sum(
            chunked_attention(q, k, v, chunk_size=16) ** 2))(q)
        gr = jax.grad(lambda q: jnp.sum(_xla_attention(q, k, v) ** 2))(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-4)

    def test_chunked_mlp_and_loss(self):
        from deepspeed_tpu.sequence.fpdt_layer import chunked_lm_loss, chunked_mlp

        x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 8))
        w = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
        out = chunked_mlp(lambda h: h @ w, x, chunk_size=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                                   atol=1e-5, rtol=1e-5)

        head = jax.random.normal(jax.random.PRNGKey(2), (8, 32))
        labels = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0, 32)
        loss_c = chunked_lm_loss(x, labels, head, chunk_size=16)
        logits = (x @ head).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, -1)
        ref = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
        np.testing.assert_allclose(float(loss_c), float(ref), rtol=1e-5)


class TestDomino:
    def test_matches_plain_layer_tp2(self):
        from deepspeed_tpu.models.transformer import (
            TransformerConfig,
            forward,
            init_params,
        )
        from deepspeed_tpu.runtime.domino.transformer import DominoTransformer

        topo = initialize_mesh(TopologyConfig(tensor=2), force=True)
        cfg = TransformerConfig.tiny(use_flash=False)
        params = init_params(cfg, jax.random.PRNGKey(0))
        x_tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, 256, size=(4, 32)), jnp.int32)
        ref_logits = forward(params, x_tokens, cfg)

        # domino path: embed → domino stack → norm/head, TP over "tensor"
        embed = jnp.take(params["embed"]["embedding"], x_tokens, axis=0)
        domino = DominoTransformer(cfg, micro_splits=2)

        col = lambda spec: spec  # layer specs already encode TP dims
        from deepspeed_tpu.models.transformer import partition_specs

        lp_specs = partition_specs(cfg)["layers"]

        def pipeify(s):
            return P(*([None] + list(s)[1:]))  # keep TP axes, stacked dim whole

        def body(layers, x):
            return domino(layers, x)

        out = jax.shard_map(
            body, mesh=topo.mesh,
            in_specs=(lp_specs, P(None, None, None)),
            out_specs=P(None, None, None), check_vma=False,
        )(params["layers"], embed)
        from deepspeed_tpu.models.transformer import rms_norm

        h = rms_norm(out, params["norm_f"]["scale"], cfg.norm_eps)
        logits = h @ params["lm_head"]["kernel"]
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                                   atol=2e-4, rtol=2e-3)


    def test_micro_batches_are_independent(self):
        """The property Domino contributes — and the one the overlap needs:
        μ-batch 1's outputs must not depend on μ-batch 0's inputs (and vice
        versa), so the TP psum of one half is schedulable against the other
        half's GEMMs.  Checked as a zero cross-half jacobian-vector product.
        (The overlap itself needs XLA:TPU's latency-hiding scheduler on a
        real tp>1 mesh — see domino/transformer.py docstring.)"""
        from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                      init_params)
        from deepspeed_tpu.runtime.domino.transformer import (
            DominoTransformerLayer)

        topo = initialize_mesh(TopologyConfig(tensor=2), force=True)
        cfg = TransformerConfig.tiny(use_flash=False)
        params = init_params(cfg, jax.random.PRNGKey(0))
        lp = jax.tree.map(lambda a: a[0], params["layers"])
        from deepspeed_tpu.models.transformer import partition_specs

        lp_specs = jax.tree.map(lambda s: P(*list(s)[1:]),
                                partition_specs(cfg)["layers"],
                                is_leaf=lambda x: isinstance(x, P))
        layer = DominoTransformerLayer(cfg, micro_splits=2)
        x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 32, 64)),
                        jnp.float32)

        def f(x):
            return jax.shard_map(
                lambda lp, x: layer(lp, x), mesh=topo.mesh,
                in_specs=(lp_specs, P()), out_specs=P(),
                check_vma=False)(lp, x)

        # tangent confined to μ-batch 0 (rows 0:2) must not leak into
        # μ-batch 1's output rows (2:4)
        tangent = jnp.zeros_like(x).at[:2].set(1.0)
        _, jvp_out = jax.jvp(f, (x,), (tangent,))
        leak = float(jnp.abs(jvp_out[2:]).max())
        assert leak == 0.0, f"cross-μ-batch dependence: |J01| = {leak}"
        assert float(jnp.abs(jvp_out[:2]).max()) > 0.0

    def test_overlap_evidence_reports(self):
        """overlap_evidence runs and reports the async-pair counts for the
        attached backend (zero on CPU — the artifact hook for real meshes)."""
        from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                      init_params)
        from deepspeed_tpu.runtime.domino.transformer import overlap_evidence

        initialize_mesh(TopologyConfig(tensor=2), force=True)
        cfg = TransformerConfig.tiny(use_flash=False)
        params = init_params(cfg, jax.random.PRNGKey(0))
        lp = jax.tree.map(lambda a: a[0], params["layers"])
        from deepspeed_tpu.models.transformer import partition_specs

        lp_specs = jax.tree.map(lambda s: P(*list(s)[1:]),
                                partition_specs(cfg)["layers"],
                                is_leaf=lambda x: isinstance(x, P))
        x = jnp.ones((4, 32, 64), jnp.float32)
        ev = overlap_evidence(cfg, lp, x, lp_specs=lp_specs)
        assert set(ev) == {"all_reduce_start", "all_reduce_done", "hlo"}
        assert "all-reduce" in ev["hlo"]
