"""Pipeline generality: heterogeneous LayerSpec
stage lists under pp>1, SP×PP composition, and the remat memory profile
(reference: runtime/pipe/schedule.py:189 TrainSchedule, module.py:393)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.runtime.pipe import PipelinedCausalLM
from deepspeed_tpu.runtime.pipe.engine import (
    pipeline_lm_loss,
    pipeline_module_loss,
)
from deepspeed_tpu.runtime.pipe.module import LayerSpec, PipelineModule
from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

pytestmark = pytest.mark.slow


def _mlp_spec(din, dout, key_scale, act=True):
    def init_fn(key):
        return {"w": jax.random.normal(key, (din, dout)) * key_scale,
                "b": jnp.zeros((dout,))}

    def apply_fn(p, x, *, rng=None):
        y = x @ p["w"] + p["b"]
        return jax.nn.tanh(y) if act else y

    return LayerSpec(init_fn, apply_fn, name=f"mlp{din}x{dout}")


def _conv_like_spec(d, width):
    """A deliberately different layer type (elementwise mix) so the stage
    list is heterogeneous."""
    def init_fn(key):
        return {"scale": jax.random.normal(key, (width, d)) * 0.1}

    def apply_fn(p, x, *, rng=None):
        return x + jnp.tanh(x @ p["scale"].T @ p["scale"]) * 0.5

    return LayerSpec(init_fn, apply_fn, name="mix")


def _mse_loss(h, labels):
    return jnp.mean(jnp.square(h - labels))


def _hetero_module(topo, num_stages):
    d = 16
    specs = [
        _mlp_spec(8, d, 0.3),            # input projection
        _conv_like_spec(d, 4),           # different layer type
        _mlp_spec(d, d, 0.2),
        _conv_like_spec(d, 8),           # stage-2 material differs again
        _mlp_spec(d, 4, 0.3, act=False), # head — output shape must match
    ]
    # first layer maps 8->16; to keep the ppermute boundary uniform ALL
    # stages must emit [mb, 16]; keep the head inside loss instead
    head = specs.pop()
    mod = PipelineModule(specs, num_stages=num_stages, topology=topo,
                         loss_fn=None, partition_method="uniform")
    head_params = head.init_fn(jax.random.PRNGKey(99))

    def loss_fn(h, labels):
        y = h @ head_params["w"] + head_params["b"]
        return _mse_loss(y, labels)

    mod.loss_fn = loss_fn
    return mod


class TestHeterogeneousPipeline:
    def test_pp2_matches_pp1_loss(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
        labels = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)

        topo1 = initialize_mesh(TopologyConfig(), force=True)
        mod1 = _hetero_module(topo1, num_stages=1)
        params = mod1.init_params(jax.random.PRNGKey(0))
        loss1 = float(pipeline_module_loss(
            mod1, params, {"x": x, "labels": labels}, None, 2, topo1))

        topo2 = initialize_mesh(TopologyConfig(pipe=2), force=True)
        mod2 = _hetero_module(topo2, num_stages=2)
        loss2 = float(pipeline_module_loss(
            mod2, params, {"x": x, "labels": labels}, None, 2, topo2))
        np.testing.assert_allclose(loss1, loss2, rtol=1e-5)

    def test_trains_under_engine(self):
        topo = initialize_mesh(TopologyConfig(pipe=2), force=True)
        mod = _hetero_module(topo, num_stages=2)
        params = mod.init_params(jax.random.PRNGKey(0))
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=mod, model_parameters=params,
            config={"train_micro_batch_size_per_gpu": 2,
                    "gradient_accumulation_steps": 2,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
                    "zero_optimization": {"stage": 1},
                    "bf16": {"enabled": False}},
            topology=topo)
        rng = np.random.default_rng(0)
        n = eng.train_batch_size()
        batch = {"x": jnp.asarray(rng.normal(size=(n, 8)), jnp.float32),
                 "labels": jnp.asarray(rng.normal(size=(n, 4)), jnp.float32)}
        losses = [float(eng.train_batch(batch)) for _ in range(10)]
        assert losses[-1] < losses[0], losses


class TestSPxPP:
    def test_spxpp_matches_pp_only(self):
        """pp=2×sp=2 loss must match pp=2 (and plain) loss."""
        cfg = TransformerConfig(vocab_size=256, hidden_size=64,
                                intermediate_size=128, num_layers=2,
                                num_heads=4, num_kv_heads=4, max_seq_len=128,
                                use_flash=False)
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, 64, size=(8, 32)), jnp.int32)

        topo_pp = initialize_mesh(TopologyConfig(pipe=2), force=True)
        model = PipelinedCausalLM(cfg, topology=topo_pp)
        params = model.init_params(jax.random.PRNGKey(0))
        loss_pp = float(pipeline_lm_loss(params, {"input_ids": tokens}, cfg,
                                         topo_pp, None, 2))

        topo_sp = initialize_mesh(TopologyConfig(pipe=2, seq=2), force=True)
        loss_spp = float(pipeline_lm_loss(params, {"input_ids": tokens}, cfg,
                                          topo_sp, None, 2))
        np.testing.assert_allclose(loss_pp, loss_spp, rtol=2e-4, atol=2e-4)

    def test_spxpp_trains(self):
        cfg = TransformerConfig(vocab_size=256, hidden_size=64,
                                intermediate_size=128, num_layers=2,
                                num_heads=4, num_kv_heads=4, max_seq_len=128,
                                use_flash=False)
        topo = initialize_mesh(TopologyConfig(pipe=2, seq=2), force=True)
        model = PipelinedCausalLM(cfg, topology=topo)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=model.init_params(jax.random.PRNGKey(0)),
            config={"train_micro_batch_size_per_gpu": 2,
                    "gradient_accumulation_steps": 2,
                    "optimizer": {"type": "AdamW", "params": {"lr": 5e-3}},
                    "zero_optimization": {"stage": 1},
                    "bf16": {"enabled": True}},
            topology=topo)
        rng = np.random.default_rng(0)
        batch = {"input_ids": jnp.asarray(
            rng.integers(0, 64, size=(eng.train_batch_size(), 32)), jnp.int32)}
        losses = [float(eng.train_batch(batch)) for _ in range(6)]
        assert losses[-1] < losses[0], losses


class TestPipelineMemory:
    def test_remat_reduces_peak_memory(self):
        """remat=True (the 1F1B-memory analogue: activations recomputed in
        backward) must lower the compiled step's temp allocation vs
        full-activation GPipe."""
        def temp_bytes(remat):
            cfg = TransformerConfig(
                vocab_size=128, hidden_size=64, intermediate_size=128,
                num_layers=4, num_heads=4, num_kv_heads=4, max_seq_len=64,
                remat=remat, use_flash=False)
            topo = initialize_mesh(TopologyConfig(pipe=2), force=True)
            model = PipelinedCausalLM(cfg, topology=topo)
            params = model.init_params(jax.random.PRNGKey(0))
            tokens = jnp.zeros((16, 64), jnp.int32)

            def loss(p, t):
                return pipeline_lm_loss(p, {"input_ids": t}, cfg, topo, None, 4)

            compiled = jax.jit(jax.grad(loss)).lower(params, tokens).compile()
            mem = compiled.memory_analysis()
            return int(getattr(mem, "temp_size_in_bytes", 0))

        full = temp_bytes(remat=False)
        rematted = temp_bytes(remat=True)
        assert rematted < full, (rematted, full)
