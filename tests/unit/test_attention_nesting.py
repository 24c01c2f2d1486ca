"""Ulysses / ring attention composition with manual shard_map regions.

The SP layers are PARTIAL-manual over the seq axis only (layer.py), so they
must work three ways:
  1. eager top-level call (user code outside jit),
  2. nested inside a manual-over-data region (the explicit-comm train step),
  3. inside a region already manual over seq (the pipeline tick loop) —
     where they must skip their own shard_map and let the enclosing region
     resolve the collectives (topology.shard_map_context detection).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.runtime.topology import (TopologyConfig, initialize_mesh,
                                            shard_map_context, get_topology)
from deepspeed_tpu.sequence.layer import UlyssesAttention
from deepspeed_tpu.sequence.ring_attention import ring_attention

pytestmark = pytest.mark.kernels


@pytest.fixture
def sp_mesh():
    return initialize_mesh(TopologyConfig(seq=2), force=True)


def _qkv():
    rngs = [np.random.default_rng(i) for i in range(3)]
    return tuple(jnp.asarray(r.normal(size=(4, 16, 4, 8)), jnp.float32)
                 for r in rngs)


class TestUlyssesNesting:
    def test_eager_toplevel(self, sp_mesh):
        q, k, v = _qkv()
        ua = UlyssesAttention()
        ref = ua.local_attn(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(ua(q, k, v, causal=True)),
                                   np.asarray(ref), rtol=2e-5, atol=2e-5)


    def test_nested_inside_manual_over_data(self, sp_mesh):
        q, k, v = _qkv()
        ua = UlyssesAttention()
        ref = ua.local_attn(q, k, v, causal=True)
        f = jax.jit(jax.shard_map(
            lambda a, b, c: ua(a, b, c, causal=True), mesh=sp_mesh.mesh,
            in_specs=(P("data"), P("data"), P("data")), out_specs=P("data"),
            axis_names={"data"}, check_vma=False))
        np.testing.assert_allclose(np.asarray(f(q, k, v)), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


    def test_inside_already_manual_seq_region(self, sp_mesh):
        """When seq is already manual the layer must call its body directly
        (a nested shard_map over a Manual axis is ill-formed)."""
        q, k, v = _qkv()
        ua = UlyssesAttention()
        ref = ua.local_attn(q, k, v, causal=True)
        spec = P("data", "seq")
        f = jax.jit(jax.shard_map(
            lambda a, b, c: ua(a, b, c, causal=True), mesh=sp_mesh.mesh,
            in_specs=(spec, spec, spec), out_specs=spec,
            axis_names={"data", "seq"}, check_vma=False))
        np.testing.assert_allclose(np.asarray(f(q, k, v)), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


    def test_context_detection(self, sp_mesh):
        """shard_map_context reports the already-manual axes from inside a
        manual region, and the concrete mesh at top level."""
        mesh_top, manual_top = shard_map_context(sp_mesh)
        assert manual_top == set() and mesh_top is sp_mesh.mesh

        seen = {}

        def body(x):
            _, already = shard_map_context(get_topology())
            seen["axes"] = already
            return x.sum()

        jax.jit(jax.shard_map(body, mesh=sp_mesh.mesh, in_specs=P("data"),
                              out_specs=P(), axis_names={"data"},
                              check_vma=False))(jnp.ones((8, 4)))
        assert seen["axes"] == {"data"}


class TestRingNesting:
    def test_eager_and_nested(self, sp_mesh):
        q, k, v = _qkv()
        ref = ring_attention(q, k, v, causal=True, sp_axis="tensor")  # sp=1
        np.testing.assert_allclose(
            np.asarray(ring_attention(q, k, v, causal=True)),
            np.asarray(ref), rtol=2e-4, atol=2e-4)
        f = jax.jit(jax.shard_map(
            lambda a, b, c: ring_attention(a, b, c, causal=True),
            mesh=sp_mesh.mesh,
            in_specs=(P("data"), P("data"), P("data")), out_specs=P("data"),
            axis_names={"data"}, check_vma=False))
        np.testing.assert_allclose(np.asarray(f(q, k, v)), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
