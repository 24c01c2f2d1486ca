"""Inference v2 model-implementation + modular layer registries (reference:
inference/v2/model_implementations/, modules/module_registry.py) and hybrid
engine LoRA fuse."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.inference


class TestModuleRegistry:
    def test_builtin_modules_registered(self):
        from deepspeed_tpu.inference.v2.modules import list_modules

        assert "paged" in list_modules("attention")
        assert "gather" in list_modules("attention")
        assert "sparse" in list_modules("moe")
        assert "rmsnorm" in list_modules("norm")
        assert "layernorm" in list_modules("norm")
        assert "tied" in list_modules("unembed")

    def test_get_and_call(self):
        from deepspeed_tpu.inference.v2.modules import get_module

        norm = get_module("norm", "rmsnorm")
        x = jnp.ones((2, 4))
        out = norm(x, jnp.ones((4,)), 1e-5)
        np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-5)

    def test_unknown_raises_with_alternatives(self):
        from deepspeed_tpu.inference.v2.modules import get_module

        with pytest.raises(KeyError, match="paged"):
            get_module("attention", "nonexistent")
        with pytest.raises(ValueError, match="interface"):
            from deepspeed_tpu.inference.v2.modules import DSModuleRegistry

            DSModuleRegistry.register("bogus", "x", lambda: None)


class TestModelImplementations:
    def test_all_reference_archs_covered(self):
        from deepspeed_tpu.inference.v2.model_implementations import (
            get_implementation,
            list_implementations,
        )

        archs = list_implementations()
        for a in ("LlamaForCausalLM", "MistralForCausalLM", "MixtralForCausalLM",
                  "Qwen2ForCausalLM", "FalconForCausalLM", "OPTForCausalLM",
                  "PhiForCausalLM", "BloomForCausalLM", "GPT2LMHeadModel",
                  "GPTJForCausalLM"):
            assert a in archs
            impl = get_implementation(a)
            assert impl.family

    @pytest.mark.slow
    def test_build_and_convert_roundtrip(self):
        from transformers import LlamaConfig, LlamaForCausalLM
        import torch

        from deepspeed_tpu.inference.v2.model_implementations import (
            get_implementation,
        )

        cfg = LlamaConfig(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                          num_attention_heads=2, num_key_value_heads=2,
                          intermediate_size=64, tie_word_embeddings=False)
        torch.manual_seed(0)
        hf = LlamaForCausalLM(cfg)
        impl = get_implementation(cfg)
        assert impl.ragged_native
        model = impl.build(cfg)
        params = impl.convert(hf.state_dict(), model)
        logits = model(params, jnp.asarray([[1, 2, 3]], jnp.int32))
        assert logits.shape == (1, 3, 64)

    def test_factory_serves_universal_archs_ragged(self):
        """gpt2 & co now serve ragged through put/query/flush (the
        engine_factory rejection is gone)."""
        from transformers import GPT2Config

        from deepspeed_tpu.inference.v2.engine_factory import build_hf_engine
        from deepspeed_tpu.inference.v2.engine_v2 import (
            RaggedInferenceEngineConfig,
        )

        cfg = GPT2Config(vocab_size=64, n_embd=32, n_layer=1, n_head=2)
        eng = build_hf_engine(cfg, random_weights=True,
                              engine_config=RaggedInferenceEngineConfig(
                                  max_tokens=16, max_seqs=2, max_ctx=64,
                                  block_size=8, dtype=jnp.float32))
        logits = eng.put([0], [[1, 2, 3]])
        assert logits.shape[1] == 64
        eng.flush([0])


class TestHybridLoRA:
    def test_fuse_lora_matches_adapter_forward(self):
        from deepspeed_tpu.linear.optimized_linear import (
            LoRAConfig,
            OptimizedLinear,
        )
        from deepspeed_tpu.runtime.hybrid_engine import fuse_lora, unfuse_lora

        lin = OptimizedLinear(8, 8, lora_config=LoRAConfig(),
                              dtype=jnp.float32)
        params = lin.init_params(jax.random.PRNGKey(0))
        params["lora_B"] = jnp.asarray(
            np.random.default_rng(0).normal(size=params["lora_B"].shape),
            jnp.float32)
        x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 8)),
                        jnp.float32)
        ref = lin.apply(params, x)

        fused = fuse_lora({"proj": params}, lora_alpha=lin.lora.lora_alpha,
                          lora_r=lin.lora.lora_r)["proj"]
        # adapters stay structurally present (the module forward reads them)
        # but lora_B is zeroed so they contribute nothing
        assert np.all(np.asarray(fused["lora_B"]) == 0)
        # THROUGH the module: fused forward == adapter forward
        out = lin.apply(fused, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        # unfuse restores the live-adapter tree
        restored = unfuse_lora({"proj": params})
        assert np.any(np.asarray(restored["proj"]["lora_B"]) != 0)
