"""SparseTensor + sparse allreduce tests (reference: sparse grad tests)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.runtime.sparse_tensor import SparseTensor, sparse_allreduce
from deepspeed_tpu.runtime.topology import DATA, TopologyConfig, initialize_mesh

pytestmark = pytest.mark.core


class TestSparseTensor:
    def test_roundtrip(self):
        dense = jnp.zeros((10, 4)).at[jnp.asarray([1, 7])].set(1.5)
        sp = SparseTensor.from_dense(dense, max_nnz=2)
        np.testing.assert_allclose(np.asarray(sp.to_dense()), np.asarray(dense))

    def test_topk_keeps_heaviest(self):
        dense = jnp.zeros((8, 2)).at[3].set(5.0).at[5].set(1.0).at[6].set(0.1)
        sp = SparseTensor.from_dense(dense, max_nnz=2)
        assert set(np.asarray(sp.indices).tolist()) == {3, 5}


    def test_sparse_allreduce_matches_dense(self):
        topo = initialize_mesh(TopologyConfig(), force=True)
        # rank r has nonzero row r
        grads = jnp.eye(8)[:, :, None] * jnp.arange(1.0, 9.0)[:, None, None]
        grads = grads.reshape(8, 8, 1)

        def body(g):
            g = g.reshape(8, 1)
            sp = SparseTensor.from_dense(g, max_nnz=1)
            return sparse_allreduce(sp, (DATA,))[None]

        out = jax.shard_map(body, mesh=topo.mesh, in_specs=P(DATA, None, None),
                            out_specs=P(DATA, None, None), check_vma=False)(grads)
        expect = np.asarray(jnp.mean(grads, axis=0))
        for r in range(8):
            np.testing.assert_allclose(np.asarray(out[r]), expect, rtol=1e-6)

    def test_truncation_count(self):
        from deepspeed_tpu.runtime.sparse_tensor import truncation_count

        dense = jnp.zeros((10, 2)).at[jnp.asarray([0, 3, 7])].set(1.0)
        assert int(truncation_count(dense, max_nnz=2)) == 1
        assert int(truncation_count(dense, max_nnz=4)) == 0
