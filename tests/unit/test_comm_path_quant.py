"""Direct coverage of comm_path's quantized collectives (previously only
exercised through whole-engine steps): round-trip error bounds and
shape/sharding invariants for the qwZ shard all-gather and the qgZ
two-stage quantized allreduce on the 8-device CPU mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.runtime.comm_path import (quantized_all_gather_shard,
                                             quantized_allreduce)
from deepspeed_tpu.runtime.topology import (DATA, compat_shard_map)

pytestmark = pytest.mark.overlap

N_DEV = 8


def _sharded(fn, mesh8, in_specs, out_specs):
    # partial-manual (DATA only) maps must run under jit on jax 0.9
    return jax.jit(compat_shard_map(fn, mesh8.mesh, in_specs, out_specs,
                                    manual_axes={DATA}))


class TestQuantizedAllGatherShard:
    @pytest.mark.parametrize(
        "bits,tol",
        [(8, 2e-2),
         pytest.param(4, 2e-1, marks=pytest.mark.slow)])
    def test_round_trip_error_bounds(self, mesh8, bits, tol):
        """Gathered full param must equal the exact concatenation within
        the wire's quantization error (relative to per-group dynamic
        range)."""
        rng = np.random.default_rng(0)
        full = jnp.asarray(rng.normal(size=(N_DEV * 64, 16)), jnp.float32)

        def gather(x):
            return quantized_all_gather_shard(x, (DATA,), dim=0, bits=bits,
                                              out_dtype=jnp.float32)

        out = _sharded(gather, mesh8, (P(DATA),), P())(full)
        assert out.shape == full.shape
        err = np.abs(np.asarray(out) - np.asarray(full))
        scale = np.abs(np.asarray(full)).max()
        assert err.max() <= tol * scale, (err.max(), scale)

    def test_output_replicated_over_data(self, mesh8):
        """The gather reconstructs the FULL tensor on every shard: every
        rank's copy must be identical (replication invariant behind the
        P() out_spec)."""
        rng = np.random.default_rng(1)
        full = jnp.asarray(rng.normal(size=(N_DEV * 8, 4)), jnp.float32)

        def gather_and_stack(x):
            out = quantized_all_gather_shard(x, (DATA,), dim=0, bits=8,
                                             out_dtype=jnp.float32)
            assert out.shape == (N_DEV * 8, 4)   # full shape per shard
            # restack every rank's copy so the host can compare them
            return jax.lax.all_gather(out, DATA, axis=0, tiled=False)

        out = _sharded(gather_and_stack, mesh8, (P(DATA),),
                       P(DATA))(full)
        # global layout [rank_viewing * N_DEV + rank_copied, ...]: rank 0's
        # view of every rank's reconstruction — all must match
        copies = np.asarray(out).reshape(N_DEV, N_DEV, N_DEV * 8, 4)
        for r in range(1, N_DEV):
            np.testing.assert_array_equal(copies[0][0], copies[0][r])

    def test_sharded_dim_one(self, mesh8):
        rng = np.random.default_rng(2)
        full = jnp.asarray(rng.normal(size=(4, N_DEV * 64)), jnp.float32)

        def gather(x):
            return quantized_all_gather_shard(x, (DATA,), dim=1, bits=8,
                                              out_dtype=jnp.float32)

        out = _sharded(gather, mesh8, (P(None, DATA),), P())(full)
        assert out.shape == full.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                                   atol=2e-2 * float(np.abs(full).max()))

    def test_bf16_out_dtype(self, mesh8):
        full = jnp.ones((N_DEV * 256, 2), jnp.float32)

        def gather(x):
            return quantized_all_gather_shard(x, (DATA,), dim=0, bits=8)

        out = _sharded(gather, mesh8, (P(DATA),), P())(full)
        assert out.dtype == jnp.bfloat16 and out.shape == full.shape


class TestQuantizedAllreduce:
    def _per_rank(self, shape=(N_DEV, 32, 8), seed=0):
        rng = np.random.default_rng(seed)
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    @pytest.mark.parametrize(
        "bits,tol",
        [(8, 5e-2),
         pytest.param(4, 4e-1, marks=pytest.mark.slow)])
    def test_error_bound_vs_exact_mean(self, mesh8, bits, tol):
        """qgZ two-stage quantized mean-allreduce vs the exact psum mean:
        bounded by the wire precision on BOTH hops."""
        stacked = self._per_rank()
        exact = np.asarray(stacked).mean(axis=0)

        def exchange(x):
            g = x[0]                       # this rank's contribution
            out, _, _ = quantized_allreduce(g, (DATA,), bits=bits)
            return out[None]

        out = _sharded(exchange, mesh8, (P(DATA),), P(DATA))(stacked)
        got = np.asarray(out[0])
        assert got.shape == exact.shape
        scale = np.abs(np.asarray(stacked)).max()
        assert np.abs(got - exact).max() <= tol * scale

    def test_all_ranks_agree(self, mesh8):
        """Stage-2 allgather makes the reduced value replicated: every
        rank's output row must be identical."""
        stacked = self._per_rank(seed=3)

        def exchange(x):
            out, _, _ = quantized_allreduce(x[0], (DATA,), bits=8)
            return out[None]

        out = _sharded(exchange, mesh8, (P(DATA),), P(DATA))(stacked)
        rows = np.asarray(out)
        for r in range(1, N_DEV):
            np.testing.assert_array_equal(rows[0], rows[r])

    @pytest.mark.slow
    def test_loco_error_feedback_round_trip(self, mesh8):
        """LoCo: residuals carry exactly what the wire dropped — adding
        them back to the transmitted signal recovers the corrected input
        (worker hop), and shapes/specs are stable across steps."""
        from deepspeed_tpu.runtime.comm_path import loco_partition_size

        stacked = self._per_rank(shape=(N_DEV, 16, 16), seed=4)
        numel = 16 * 16
        per = loco_partition_size(numel, N_DEV)

        def exchange(x, err, serr):
            out, new_e, new_se = quantized_allreduce(
                x[0], (DATA,), bits=4,
                error=err[0], server_error=serr[0])
            return out[None], new_e[None], new_se[None]

        err0 = jnp.zeros((N_DEV, 16, 16), jnp.float32)
        serr0 = jnp.zeros((N_DEV, per), jnp.float32)
        specs = (P(DATA), P(DATA), P(DATA))
        out, new_e, new_se = _sharded(exchange, mesh8, specs, specs)(
            stacked, err0, serr0)
        assert new_e.shape == err0.shape
        assert new_se.shape == serr0.shape
        # residuals are nonzero (the int4 wire is lossy) but bounded by it
        e = np.asarray(new_e)
        assert 0 < np.abs(e).max() < np.abs(np.asarray(stacked)).max()

    def test_single_rank_group_is_identity(self):
        """n=1 short-circuit: no wire, exact pass-through."""
        g = jnp.arange(12.0).reshape(3, 4)
        out, e, se = quantized_allreduce(g, (), bits=4)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(g))


@pytest.mark.comm
class TestFusedWireParity:
    """The EQuARX-style fused wire (one Pallas scale+quantize+pack kernel
    feeding the collective, fused unpack+dequant+mean on the receive side)
    must be BITWISE equal to the legacy jnp-composed wire under jit — the
    fusion moves HBM traffic, never values."""

    def _stacked(self, seed=0, shape=(N_DEV, 48, 8)):
        rng = np.random.default_rng(seed)
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    @pytest.mark.parametrize("bits", [8, 4])
    def test_fused_allreduce_bitwise_vs_unfused(self, mesh8, bits):
        stacked = self._stacked()

        def ex(fused):
            def body(x):
                out, _, _ = quantized_allreduce(x[0], (DATA,), bits=bits,
                                                fused=fused)
                return out[None]

            return np.asarray(jax.jit(_sharded(
                body, mesh8, (P(DATA),), P(DATA)))(stacked))

        np.testing.assert_array_equal(ex(True), ex(False))

    @pytest.mark.parametrize("bits", [8, 4])
    def test_fused_gather_bitwise_vs_unfused(self, mesh8, bits):
        rng = np.random.default_rng(1)
        full = jnp.asarray(rng.normal(size=(N_DEV * 64, 16)), jnp.float32)

        def ex(fused):
            def body(x):
                return quantized_all_gather_shard(
                    x, (DATA,), dim=0, bits=bits, out_dtype=jnp.float32,
                    fused=fused)

            return np.asarray(jax.jit(_sharded(
                body, mesh8, (P(DATA),), P()))(full))

        np.testing.assert_array_equal(ex(True), ex(False))

    def test_fused_loco_bitwise_vs_unfused(self, mesh8):
        """LoCo residuals must also match: the fused path reconstructs
        "what hit the wire" from the SAME quant+pack output the exchange
        used, the legacy path re-quantizes — same math, same values."""
        stacked = self._stacked(seed=2, shape=(N_DEV, 16, 16))
        err0 = jnp.zeros((N_DEV, 16, 16), jnp.float32)
        from deepspeed_tpu.runtime.comm_path import loco_partition_size

        per = loco_partition_size(16 * 16, N_DEV)
        serr0 = jnp.zeros((N_DEV, per), jnp.float32)
        specs = (P(DATA),) * 3

        def ex(fused):
            def body(x, e, se):
                out, ne, nse = quantized_allreduce(
                    x[0], (DATA,), bits=4, error=e[0], server_error=se[0],
                    fused=fused)
                return out[None], ne[None], nse[None]

            return jax.jit(_sharded(body, mesh8, specs, specs))(
                stacked, err0, serr0)

        a, b = ex(True), ex(False)
        for got, ref in zip(a, b):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_coalesced_loco_fused_parity_unaligned(self, mesh8):
        """The single-quantization fused LoCo path (return_sent seam) must
        match the legacy double-quantization composition bitwise — also on
        a length that does NOT divide the quantization group, where the
        two passes' padded shapes differ."""
        from deepspeed_tpu.runtime.comm.coalesced_collectives import \
            loco_quantized_reduce_scatter

        rng = np.random.default_rng(5)
        stacked = jnp.asarray(rng.normal(size=(N_DEV, 300)), jnp.float32)
        err = jnp.asarray(rng.normal(size=(N_DEV, 300)) * 0.01, jnp.float32)

        def run(fused):
            def body(x, e):
                r, ne = loco_quantized_reduce_scatter(
                    x[0], e[0], (DATA,), bits=4, fused=fused)
                return r[None], ne[None]

            return jax.jit(_sharded(body, mesh8, (P(DATA), P(DATA)),
                                    (P(DATA), P(DATA))))(stacked, err)

        for got, ref in zip(run(True), run(False)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    @pytest.mark.parametrize("bits", [8, 4])
    def test_coalesced_reduce_scatter_fused_parity(self, mesh8, bits):
        from deepspeed_tpu.runtime.comm.coalesced_collectives import \
            quantized_reduce_scatter

        stacked = self._stacked(seed=3)

        def ex(fused):
            def body(x):
                return quantized_reduce_scatter(x[0], (DATA,), bits=bits,
                                                fused=fused)[None]

            return np.asarray(jax.jit(_sharded(
                body, mesh8, (P(DATA),), P(DATA)))(stacked))

        np.testing.assert_array_equal(ex(True), ex(False))
