"""Size-targeted gradient bucketing: plan shape, fused-exchange exactness
(psum is elementwise — bucketing may never change a value), and the
explicit-path wiring through ``overlap.bucket_bytes``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.runtime.comm.coalesced_collectives import \
    bucketed_allreduce_coalesced
from deepspeed_tpu.runtime.overlap.bucketing import (bucket_stats,
                                                     leaf_bytes,
                                                     plan_buckets)
from deepspeed_tpu.runtime.topology import (DATA, TopologyConfig,
                                            compat_shard_map,
                                            initialize_mesh)

pytestmark = pytest.mark.overlap


class TestPlanBuckets:
    def _leaves(self, *sizes):
        return [jnp.zeros(s, jnp.float32) for s in sizes]

    def test_in_order_first_fit(self):
        # 4B floats: target 48B = 12 floats per bucket
        plans = plan_buckets(self._leaves(4, 4, 4, 4), bucket_bytes=48)
        assert [p.indices for p in plans] == [(0, 1, 2), (3,)]

    def test_big_leaf_gets_singleton_unfused(self):
        plans = plan_buckets(self._leaves(2, 100, 2, 2), bucket_bytes=48)
        big = next(p for p in plans if p.indices == (1,))
        assert not big.fused          # no concat copy for big tensors
        # the small leaves around it still coalesce
        assert any(len(p.indices) > 1 for p in plans)

    def test_every_leaf_exactly_once(self):
        sizes = [3, 500, 7, 1, 1, 1, 64, 2]
        plans = plan_buckets(self._leaves(*sizes), bucket_bytes=64)
        seen = sorted(i for p in plans for i in p.indices)
        assert seen == list(range(len(sizes)))

    def test_zero_target_means_per_leaf(self):
        plans = plan_buckets(self._leaves(2, 2, 2), bucket_bytes=0)
        assert all(len(p.indices) == 1 for p in plans)

    def test_stats(self):
        plans = plan_buckets(self._leaves(4, 4, 4, 4), bucket_bytes=48)
        stats = bucket_stats(plans)
        assert stats["bucket_count"] == 2
        assert stats["fused_leaves"] == 3
        assert stats["total_bytes"] == 4 * 4 * 4

    def test_leaf_bytes(self):
        assert leaf_bytes(jnp.zeros((3, 5), jnp.float32)) == 60


class TestBucketedExchangeExact:
    def test_bit_identical_to_per_leaf_psum(self, mesh8):
        """Fused flat-bucket psum vs per-leaf psum: identical bits."""
        rng = np.random.default_rng(0)
        shapes = [(8, 16, 3), (8, 7), (8, 129), (8, 2, 2), (8, 33)]
        leaves = [jnp.asarray(rng.normal(size=s), jnp.float32)
                  for s in shapes]

        def bucketed(*ls):
            outs, _stats = bucketed_allreduce_coalesced(
                list(ls), (DATA,), bucket_bytes=512)
            return tuple(outs)

        def per_leaf(*ls):
            n = jax.lax.psum(1, DATA)
            return tuple(jax.lax.psum(x, DATA) / n for x in ls)

        specs = tuple(P(DATA) for _ in leaves)
        out_b = jax.jit(compat_shard_map(bucketed, mesh8.mesh, specs, specs,
                                         manual_axes={DATA}))(*leaves)
        out_p = jax.jit(compat_shard_map(per_leaf, mesh8.mesh, specs, specs,
                                         manual_axes={DATA}))(*leaves)
        for b, p in zip(out_b, out_p):
            np.testing.assert_array_equal(np.asarray(b), np.asarray(p))

    def test_shapes_and_dtypes_preserved(self, mesh8):
        leaves = [jnp.ones((8, 5), jnp.float32), jnp.ones((8, 3, 2),
                                                          jnp.float32)]

        def fn(*ls):
            outs, stats = bucketed_allreduce_coalesced(
                list(ls), (DATA,), bucket_bytes=1 << 20)
            assert stats["bucket_count"] == 1   # everything coalesced
            return tuple(outs)

        specs = tuple(P(DATA) for _ in leaves)
        outs = jax.jit(compat_shard_map(fn, mesh8.mesh, specs, specs,
                                        manual_axes={DATA}))(*leaves)
        for o, l in zip(outs, leaves):
            assert o.shape == l.shape and o.dtype == l.dtype
            np.testing.assert_array_equal(np.asarray(o), np.asarray(l))


class TestExplicitPathBucketing:
    def _engine(self, bucket_bytes):
        topo = initialize_mesh(TopologyConfig(), force=True)
        cfg = TransformerConfig.tiny(use_flash=False)
        model = CausalLM(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config={"train_micro_batch_size_per_gpu": 2,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 2},
                    "bf16": {"enabled": True},
                    "overlap": {"enabled": True, "explicit_wire": True,
                                "bucket_bytes": bucket_bytes}},
            topology=topo)
        return eng

    def _batch(self):
        rng = np.random.default_rng(0)
        return {"input_ids": jnp.asarray(
            rng.integers(0, 64, size=(16, 32)), jnp.int32)}

    def test_bucketed_vs_per_leaf_bit_exact(self):
        batch = self._batch()
        e_bucket = self._engine(bucket_bytes=1 << 20)
        e_leaf = self._engine(bucket_bytes=0)
        lb = e_bucket.train_batch(batch)
        ll = e_leaf.train_batch(batch)
        assert float(lb) == float(ll)
        for a, b in zip(jax.tree.leaves(e_bucket.state.params),
                        jax.tree.leaves(e_leaf.state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the plan's stats reached the manager (→ overlap/bucket_count)
        stats = e_bucket.overlap.last_bucket_stats
        assert stats is not None and stats["bucket_count"] >= 1
        assert stats["fused_leaves"] > 1   # tiny model: leaves coalesce

    @pytest.mark.slow
    def test_fewer_collectives_in_stablehlo(self):
        # slow: two extra engine builds + full step traces; the bit-exact
        # test above already proves the bucketed wire is live
        """Bucketing must actually reduce collective launch count in the
        lowered program (the whole point)."""
        batch = self._batch()
        e_bucket = self._engine(bucket_bytes=1 << 20)
        e_leaf = self._engine(bucket_bytes=0)
        count = lambda eng: eng._build_train_batch_fn().lower(
            eng.state, batch).as_text().count("all_reduce")
        n_bucket, n_leaf = count(e_bucket), count(e_leaf)
        assert n_bucket < n_leaf, (n_bucket, n_leaf)
