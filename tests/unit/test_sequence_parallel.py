"""Ulysses + ring attention tests (reference: tests/unit/sequence_parallelism/test_ulysses.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.models.transformer import _xla_attention
from deepspeed_tpu.runtime.topology import SEQ, TopologyConfig, initialize_mesh
from deepspeed_tpu.sequence import (
    DistributedAttention,
    UlyssesAttention,
    ring_attention,
    vocab_sequence_parallel_cross_entropy,
)

pytestmark = pytest.mark.core


def qkv(B=2, S=64, H=4, hd=16, kv=None, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    kvh = kv or H
    return (jax.random.normal(ks[0], (B, S, H, hd), jnp.float32),
            jax.random.normal(ks[1], (B, S, kvh, hd), jnp.float32),
            jax.random.normal(ks[2], (B, S, kvh, hd), jnp.float32))


def place_seq_sharded(topo, *arrays):
    sh = NamedSharding(topo.mesh, P(None, SEQ, None, None))
    return tuple(jax.device_put(a, sh) for a in arrays)


class TestUlysses:
    @pytest.mark.parametrize("sp", [2, 4, 8])
    @pytest.mark.slow
    def test_matches_single_device(self, sp):
        topo = initialize_mesh(TopologyConfig(seq=sp), force=True)
        q, k, v = qkv(H=8)
        ref = _xla_attention(q, k, v, causal=True)
        attn = DistributedAttention(lambda q, k, v: _xla_attention(q, k, v, causal=True))
        out = attn(*place_seq_sharded(topo, q, k, v))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_sp1_passthrough(self):
        initialize_mesh(TopologyConfig(), force=True)
        q, k, v = qkv()
        attn = UlyssesAttention()
        out = attn(q, k, v, causal=True)
        ref = _xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_uneven_heads_raise(self):
        initialize_mesh(TopologyConfig(seq=4), force=True)
        q, k, v = qkv(H=6)
        attn = DistributedAttention(lambda q, k, v: _xla_attention(q, k, v))
        with pytest.raises(ValueError, match="divisible"):
            attn(q, k, v)

    @pytest.mark.slow

    def test_gradients_flow(self):
        topo = initialize_mesh(TopologyConfig(seq=2), force=True)
        q, k, v = qkv(H=4)
        attn = DistributedAttention(lambda q, k, v: _xla_attention(q, k, v, causal=True))

        def loss(q, k, v):
            return jnp.sum(attn(q, k, v) ** 2)

        def ref_loss(q, k, v):
            return jnp.sum(_xla_attention(q, k, v, causal=True) ** 2)

        g = jax.grad(loss)(q, k, v)
        gr = jax.grad(ref_loss)(q, k, v)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-4, rtol=1e-4)


class TestRingAttention:
    @pytest.mark.parametrize("sp", [2, 4])
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.slow
    def test_matches_single_device(self, sp, causal):
        topo = initialize_mesh(TopologyConfig(seq=sp), force=True)
        q, k, v = qkv(S=64)
        ref = _xla_attention(q, k, v, causal=causal)
        out = ring_attention(*place_seq_sharded(topo, q, k, v), causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    @pytest.mark.slow

    def test_gqa(self):
        topo = initialize_mesh(TopologyConfig(seq=2), force=True)
        q, k, v = qkv(H=8, kv=2)
        ref = _xla_attention(q, k, v, causal=True)
        out = ring_attention(*place_seq_sharded(topo, q, k, v), causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    @pytest.mark.slow

    def test_gradients_flow(self):
        topo = initialize_mesh(TopologyConfig(seq=2), force=True)
        q, k, v = qkv(S=32)

        def loss(q, k, v):
            return jnp.sum(ring_attention(q, k, v, causal=True) ** 2)

        def ref_loss(q, k, v):
            return jnp.sum(_xla_attention(q, k, v, causal=True) ** 2)

        g = jax.grad(loss)(q, k, v)
        gr = jax.grad(ref_loss)(q, k, v)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-4, rtol=1e-4)


class TestSPCrossEntropy:
    def test_matches_plain(self):
        topo = initialize_mesh(TopologyConfig(seq=4), force=True)
        key = jax.random.PRNGKey(0)
        logits = jax.random.normal(key, (2, 32, 64))
        labels = jax.random.randint(key, (2, 32), 0, 64)
        labels = labels.at[:, -4:].set(-100)

        # plain reference
        logp = jax.nn.log_softmax(logits, axis=-1)
        valid = labels != -100
        tok = jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None], -1)[..., 0]
        ref = -jnp.sum(tok * valid) / jnp.sum(valid)

        out = jax.shard_map(
            lambda lg, lb: vocab_sequence_parallel_cross_entropy(lg, lb)[None],
            mesh=topo.mesh,
            in_specs=(P(None, SEQ, None), P(None, SEQ)),
            out_specs=P(SEQ),
            check_vma=False,
        )(logits, labels)
        np.testing.assert_allclose(np.asarray(out), np.full(4, float(ref)), rtol=1e-5)
