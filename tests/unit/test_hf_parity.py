"""Per-architecture logit parity vs HF transformers (CPU, tiny random
models).  Reference analogue: tests/unit/inference/test_inference.py's model
sweep + module_inject/containers per-arch mappings.

Each test builds a tiny randomly-initialized HF model, converts its
state_dict with the exact per-arch recipe, and compares full logits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

from deepspeed_tpu.models.hf import (
    arch_config_from_hf,
    config_from_hf,
    convert_arch_state_dict,
    convert_llama_state_dict,
    from_pretrained_config,
    policy_for,
)

pytestmark = pytest.mark.slow  # torch+jax double compile per arch

TOKENS = np.array([[3, 17, 41, 9, 25, 7, 19, 2]], np.int64)


def _parity(hf_model, hf_cfg, atol=2e-4):
    hf_model.eval()
    with torch.no_grad():
        ref = hf_model(torch.tensor(TOKENS)).logits.float().numpy()
    from deepspeed_tpu.models.hf import NATIVE_FAMILIES

    fam = policy_for(hf_cfg)
    model = from_pretrained_config(hf_cfg)
    if fam in NATIVE_FAMILIES:
        params = convert_llama_state_dict(hf_model.state_dict(), model.config)
    else:
        params = convert_arch_state_dict(hf_model.state_dict(), model.config, fam)
    got = np.asarray(model(params, jax.numpy.asarray(TOKENS, jax.numpy.int32)))
    np.testing.assert_allclose(got, ref, atol=atol, rtol=1e-3)


class TestUniversalFamilyEngine:
    def test_gpt2_style_model_trains(self):
        """Universal compat families plug into deepspeed_tpu.initialize."""
        import jax.numpy as jnp

        import deepspeed_tpu
        from deepspeed_tpu.models.families import ArchConfig, UniversalCausalLM
        from deepspeed_tpu.runtime.topology import (
            TopologyConfig,
            initialize_mesh,
        )

        topo = initialize_mesh(TopologyConfig(), force=True)
        model = UniversalCausalLM(ArchConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_layers=2, num_heads=2, num_kv_heads=2, max_seq_len=32))
        params = model.init_params(jax.random.PRNGKey(0))
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config={"train_micro_batch_size_per_gpu": 2,
                    "optimizer": {"type": "AdamW", "params": {"lr": 5e-3}},
                    "zero_optimization": {"stage": 1},
                    "bf16": {"enabled": True}},
            topology=topo)
        batch = {"input_ids": jax.numpy.asarray(
            np.random.default_rng(0).integers(0, 64, size=(16, 16)), jnp.int32)}
        losses = [float(eng.train_batch(batch)) for _ in range(8)]
        assert losses[-1] < losses[0]

    def test_universal_family_serves_ragged(self):
        """UniversalCausalLM models serve through the ragged engine (the
        round-2 guard is gone)."""
        from deepspeed_tpu.inference.v2.engine_v2 import (
            InferenceEngineV2,
            RaggedInferenceEngineConfig,
        )
        from deepspeed_tpu.models.families import ArchConfig, UniversalCausalLM

        model = UniversalCausalLM(ArchConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_layers=1, num_heads=2, num_kv_heads=2))
        eng = InferenceEngineV2(
            model, model.init_params(jax.random.PRNGKey(0)),
            RaggedInferenceEngineConfig(max_tokens=16, max_seqs=2, max_ctx=64,
                                        block_size=8, dtype=jnp.float32))
        logits = eng.put([0], [[1, 2, 3]])
        assert logits.shape[1] == 64
        eng.flush([0])


class TestArchParity:
    def test_gpt2(self):
        from transformers import GPT2Config, GPT2LMHeadModel

        cfg = GPT2Config(vocab_size=128, n_positions=64, n_embd=64,
                         n_layer=2, n_head=4)
        torch.manual_seed(0)
        _parity(GPT2LMHeadModel(cfg), cfg)

    def test_opt(self):
        from transformers import OPTConfig, OPTForCausalLM

        cfg = OPTConfig(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                        num_attention_heads=4, ffn_dim=128,
                        max_position_embeddings=64, do_layer_norm_before=True,
                        word_embed_proj_dim=64)
        torch.manual_seed(0)
        _parity(OPTForCausalLM(cfg), cfg)

    def test_bloom(self):
        from transformers import BloomConfig, BloomForCausalLM

        cfg = BloomConfig(vocab_size=128, hidden_size=64, n_layer=2, n_head=4)
        torch.manual_seed(0)
        _parity(BloomForCausalLM(cfg), cfg)

    def test_falcon_7b_style(self):
        from transformers import FalconConfig, FalconForCausalLM

        cfg = FalconConfig(vocab_size=128, hidden_size=64,
                           num_hidden_layers=2, num_attention_heads=4,
                           multi_query=True, parallel_attn=True,
                           new_decoder_architecture=False, bias=False,
                           alibi=False)
        torch.manual_seed(0)
        _parity(FalconForCausalLM(cfg), cfg)

    def test_falcon_new_arch(self):
        from transformers import FalconConfig, FalconForCausalLM

        cfg = FalconConfig(vocab_size=128, hidden_size=64,
                           num_hidden_layers=2, num_attention_heads=4,
                           new_decoder_architecture=True, num_kv_heads=2,
                           bias=False, alibi=False)
        torch.manual_seed(0)
        _parity(FalconForCausalLM(cfg), cfg)

    def test_falcon_rw_style(self):
        """falcon-rw: alibi=True + parallel_attn=False + multi_query=False
        (the ADVICE r2 medium finding — previously silently wrong logits)."""
        from transformers import FalconConfig, FalconForCausalLM

        cfg = FalconConfig(vocab_size=128, hidden_size=64,
                           num_hidden_layers=2, num_attention_heads=4,
                           multi_query=False, parallel_attn=False,
                           new_decoder_architecture=False, bias=True,
                           alibi=True)
        torch.manual_seed(0)
        _parity(FalconForCausalLM(cfg), cfg)

    def test_phi(self):
        from transformers import PhiConfig, PhiForCausalLM

        cfg = PhiConfig(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                        num_attention_heads=4, intermediate_size=128,
                        partial_rotary_factor=0.5, max_position_embeddings=64)
        torch.manual_seed(0)
        _parity(PhiForCausalLM(cfg), cfg)

    def test_qwen2(self):
        from transformers import Qwen2Config, Qwen2ForCausalLM

        cfg = Qwen2Config(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          intermediate_size=128, tie_word_embeddings=False)
        torch.manual_seed(0)
        _parity(Qwen2ForCausalLM(cfg), cfg)

    def test_gptj(self):
        from transformers import GPTJConfig, GPTJForCausalLM

        cfg = GPTJConfig(vocab_size=128, n_embd=64, n_layer=2, n_head=4,
                         n_inner=128, rotary_dim=8, n_positions=64)
        torch.manual_seed(0)
        _parity(GPTJForCausalLM(cfg), cfg)

    def test_llama(self):
        from transformers import LlamaConfig, LlamaForCausalLM

        cfg = LlamaConfig(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          intermediate_size=128, tie_word_embeddings=False)
        torch.manual_seed(0)
        _parity(LlamaForCausalLM(cfg), cfg)

    def test_mixtral_expert_import(self):
        from transformers import MixtralConfig, MixtralForCausalLM

        cfg = MixtralConfig(vocab_size=128, hidden_size=64,
                            num_hidden_layers=2, num_attention_heads=4,
                            num_key_value_heads=2, intermediate_size=128,
                            num_local_experts=4, num_experts_per_tok=2,
                            tie_word_embeddings=False)
        torch.manual_seed(0)
        hf_model = MixtralForCausalLM(cfg)
        hf_model.eval()
        with torch.no_grad():
            ref = hf_model(torch.tensor(TOKENS)).logits.float().numpy()
        # capacity high enough that no token drops → routing matches HF's
        # dropless top-k exactly
        model = from_pretrained_config(cfg, moe_capacity_factor=float(
            cfg.num_local_experts))
        params = convert_llama_state_dict(hf_model.state_dict(), model.config)
        got = np.asarray(model(params,
                               jax.numpy.asarray(TOKENS, jax.numpy.int32)))
        np.testing.assert_allclose(got, ref, atol=5e-4, rtol=1e-3)
