"""Quantizer kernels, ZeRO++ quantized collectives, sparse attention, HF policy.
(reference: tests/unit/ops/quantizer, runtime/zero/test_zeropp.py,
ops/sparse_attention, module_inject tests)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.runtime.topology import DATA, TopologyConfig, initialize_mesh

pytestmark = pytest.mark.kernels


class TestQuantizerKernels:
    @pytest.mark.parametrize("bits", [4, 8])
    def test_roundtrip_error(self, bits):
        from deepspeed_tpu.ops.quantizer.quantizer import Quantizer

        x = jax.random.normal(jax.random.PRNGKey(0), (1000,))
        q = Quantizer(q_bits=bits, group_size=128)
        qt, s = q.quantize(x)
        back = q.dequantize(qt, s, shape=x.shape)
        maxerr = float(jnp.max(jnp.abs(x - back)))
        bound = float(jnp.max(jnp.abs(x))) / (127 if bits == 8 else 7)
        assert maxerr <= bound * 1.01

    def test_int8_shapes(self):
        from deepspeed_tpu.ops.quantizer.quantizer import quantize_int8

        q, s = quantize_int8(jnp.ones((10, 50)), group_size=128)
        assert q.shape == (4, 128) and s.shape == (4, 1)
        assert q.dtype == jnp.int8

    def test_int4_packing(self):
        from deepspeed_tpu.ops.quantizer.quantizer import (
            dequantize_int4,
            quantize_int4,
        )

        x = jnp.asarray([1.0, -1.0, 0.5, -0.5] * 64)
        q, s = quantize_int4(x, group_size=256)
        assert q.shape == (1, 128)  # packed two per byte
        back = dequantize_int4(q, s, shape=x.shape)
        np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=0.15)


class TestQuantizedCollectives:
    def test_quantized_reduce_scatter_close_to_exact(self):
        topo = initialize_mesh(TopologyConfig(), force=True)
        from deepspeed_tpu.runtime.comm.coalesced_collectives import (
            quantized_reduce_scatter,
        )

        g = jax.random.normal(jax.random.PRNGKey(0), (8, 2048))

        def body(g):
            return quantized_reduce_scatter(g.reshape(-1), axes=(DATA,), bits=8,
                                            group_size=256)[None]

        out = jax.shard_map(body, mesh=topo.mesh, in_specs=P(DATA, None),
                            out_specs=P(DATA, None), check_vma=False)(g)
        exact = np.asarray(jnp.mean(g, axis=0)).reshape(8, 256)
        np.testing.assert_allclose(np.asarray(out), exact, atol=0.05)


    def test_quantized_allgather(self):
        topo = initialize_mesh(TopologyConfig(), force=True)
        from deepspeed_tpu.runtime.comm.coalesced_collectives import (
            quantized_all_gather_params,
        )

        shards = jax.random.normal(jax.random.PRNGKey(1), (8, 256))

        def body(s):
            return quantized_all_gather_params(s.reshape(-1), axes=(DATA,),
                                               bits=8, group_size=128)[None]

        out = jax.shard_map(body, mesh=topo.mesh, in_specs=P(DATA, None),
                            out_specs=P(DATA, None), check_vma=False)(shards)
        full = np.asarray(shards).reshape(-1)
        for r in range(8):
            np.testing.assert_allclose(np.asarray(out[r]), full, atol=0.05)


    def test_reduce_scatter_coalesced(self):
        topo = initialize_mesh(TopologyConfig(), force=True)
        from deepspeed_tpu.runtime.comm.coalesced_collectives import (
            reduce_scatter_coalesced,
        )

        t1 = jnp.ones((8, 16))
        t2 = jnp.full((8, 24), 2.0)

        def body(a, b):
            o1, o2 = reduce_scatter_coalesced([a.reshape(-1), b.reshape(-1)],
                                              axes=(DATA,))
            return o1[None], o2[None]

        o1, o2 = jax.shard_map(body, mesh=topo.mesh,
                               in_specs=(P(DATA, None), P(DATA, None)),
                               out_specs=(P(DATA, None), P(DATA, None)),
                               check_vma=False)(t1, t2)
        np.testing.assert_allclose(np.asarray(o1), 1.0)
        np.testing.assert_allclose(np.asarray(o2), 2.0)


class TestSparseAttention:
    def test_fixed_layout_properties(self):
        from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
            FixedSparsityConfig,
        )

        cfg = FixedSparsityConfig(num_heads=2, block=16, num_local_blocks=2,
                                  num_global_blocks=1)
        layout = cfg.make_layout(128)
        assert layout.shape == (2, 8, 8)
        assert layout[0, 0, 0] and layout[0, 1, 1]
        assert layout[0, :, 0].all()  # global column

    def test_longformer_window(self):
        from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
            BSLongformerSparsityConfig,
        )

        cfg = BSLongformerSparsityConfig(num_heads=1, block=16,
                                         num_sliding_window_blocks=3)
        layout = cfg.make_layout(160)
        n = 10
        for i in range(n):
            assert layout[0, i, i]          # diagonal always on
        # outside window + not global row/col → masked (row 0/col 0 are global)
        assert not layout[0, 3, 6] and not layout[0, 6, 3]
        assert layout[0, 5, 0] and layout[0, 0, 5]  # global block 0

    def test_bigbird_and_variable(self):
        from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
            BigBirdSparsityConfig,
            VariableSparsityConfig,
        )

        bb = BigBirdSparsityConfig(num_heads=1, block=16).make_layout(128)
        assert bb[0, :, 0].all()
        vr = VariableSparsityConfig(num_heads=1, block=16,
                                    local_window_blocks=[2, 4]).make_layout(128)
        assert vr[0, 0, 1]

    def test_sparse_attention_matches_dense_when_dense(self):
        from deepspeed_tpu.models.transformer import _xla_attention
        from deepspeed_tpu.ops.sparse_attention.sparse_self_attention import (
            SparseSelfAttention,
        )
        from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
            DenseSparsityConfig,
        )

        B, H, S, hd = 1, 2, 64, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, H, S, hd))
        k = jax.random.normal(ks[1], (B, H, S, hd))
        v = jax.random.normal(ks[2], (B, H, S, hd))
        attn = SparseSelfAttention(DenseSparsityConfig(num_heads=H, block=16))
        out = attn(q, k, v)
        ref = _xla_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), causal=False)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref.transpose(0, 2, 1, 3)),
                                   atol=2e-5, rtol=2e-5)

    def test_sparsity_actually_masks(self):
        from deepspeed_tpu.ops.sparse_attention.sparse_self_attention import (
            SparseSelfAttention,
        )
        from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
            FixedSparsityConfig,
        )

        attn = SparseSelfAttention(FixedSparsityConfig(
            num_heads=1, block=16, num_local_blocks=2, num_global_blocks=1))
        mask = attn.token_mask(64)
        # block (1,3): outside the local window {0,1} and col 3 is not a
        # global column (globals sit at window starts 0 and 2) → masked
        assert not bool(mask[0, 17, 56])
        assert bool(mask[0, 17, 1])   # local window
        assert bool(mask[0, 17, 33])  # global column of window 2


class TestHFPolicies:
    def test_llama_policy_mapping(self):
        from deepspeed_tpu.models.hf import config_from_hf

        class FakeCfg:
            architectures = ["LlamaForCausalLM"]
            vocab_size = 1000
            hidden_size = 64
            intermediate_size = 128
            num_hidden_layers = 2
            num_attention_heads = 4
            num_key_value_heads = 2
            max_position_embeddings = 256
            rope_theta = 10000.0
            rms_norm_eps = 1e-5
            tie_word_embeddings = False

        cfg = config_from_hf(FakeCfg())
        assert cfg.hidden_size == 64 and cfg.num_kv_heads == 2

    def test_weight_conversion_roundtrip(self):
        import torch

        from deepspeed_tpu.models.hf import convert_llama_state_dict
        from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

        cfg = TransformerConfig(vocab_size=64, hidden_size=16, intermediate_size=32,
                                num_layers=2, num_heads=4, num_kv_heads=2,
                                max_seq_len=32)
        D, F, H, KV, hd = 16, 32, 4, 2, 4
        sd = {"model.embed_tokens.weight": torch.randn(64, D),
              "model.norm.weight": torch.ones(D),
              "lm_head.weight": torch.randn(64, D)}
        for i in range(2):
            p = f"model.layers.{i}"
            sd[f"{p}.input_layernorm.weight"] = torch.ones(D)
            sd[f"{p}.post_attention_layernorm.weight"] = torch.ones(D)
            sd[f"{p}.self_attn.q_proj.weight"] = torch.randn(H * hd, D)
            sd[f"{p}.self_attn.k_proj.weight"] = torch.randn(KV * hd, D)
            sd[f"{p}.self_attn.v_proj.weight"] = torch.randn(KV * hd, D)
            sd[f"{p}.self_attn.o_proj.weight"] = torch.randn(D, H * hd)
            sd[f"{p}.mlp.gate_proj.weight"] = torch.randn(F, D)
            sd[f"{p}.mlp.up_proj.weight"] = torch.randn(F, D)
            sd[f"{p}.mlp.down_proj.weight"] = torch.randn(D, F)
        params = convert_llama_state_dict(sd, cfg)
        model = CausalLM(cfg)
        logits = model(params, jnp.zeros((1, 8), jnp.int32))
        assert logits.shape == (1, 8, 64)
        np.testing.assert_allclose(
            np.asarray(params["layers"]["q_proj"]["kernel"][0]),
            sd["model.layers.0.self_attn.q_proj.weight"].numpy().T, rtol=1e-6)

    def test_tp_model_init(self):
        from deepspeed_tpu.models.hf import tp_model_init
        from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

        initialize_mesh(TopologyConfig(), force=True)
        model = CausalLM(TransformerConfig.tiny(use_flash=False))
        params = model.init_params(jax.random.PRNGKey(0))
        model, placed = tp_model_init(model, params, tp_size=2)
        kernel = placed["layers"]["q_proj"]["kernel"]
        assert not kernel.sharding.is_fully_replicated
