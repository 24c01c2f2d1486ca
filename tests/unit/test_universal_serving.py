"""Ragged paged-KV serving parity for the universal (ArchConfig) families
(reference analogue:
tests/unit/inference/v2/model_implementations/ per-arch serving tests).

Each case serves split prompt chunks + decode steps through
InferenceEngineV2.put() and must reproduce the compat forward's logits for
the same tokens — covering learned positions (+OPT's offset), ALiBi (bloom
and falcon-scaled variants), parallel attention, dual-LN, partial and
interleaved rotary, LayerNorm-with-bias, and the lm-head bias.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (
    InferenceEngineV2,
    RaggedInferenceEngineConfig,
)
from deepspeed_tpu.models.families import ArchConfig, UniversalCausalLM

pytestmark = pytest.mark.inference

BASE = dict(vocab_size=97, hidden_size=32, intermediate_size=64,
            num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=128)

FAMILY_CASES = {
    "gpt2": dict(pos="learned", norm="layernorm", mlp="gelu",
                 qkv_bias=True, out_bias=True),
    "opt": dict(pos="learned", pos_offset=2, norm="layernorm", mlp="relu",
                qkv_bias=True, out_bias=True),
    "bloom": dict(pos="alibi", norm="layernorm", mlp="gelu",
                  embed_layernorm=True, qkv_bias=True, out_bias=True),
    "falcon7b": dict(pos="rope", norm="layernorm", mlp="gelu",
                     gelu_exact=True, parallel_attn=True, num_kv_heads=1,
                     qkv_bias=False, out_bias=False),
    "falcon_new": dict(pos="rope", norm="layernorm", mlp="gelu",
                       gelu_exact=True, parallel_attn=True, dual_ln=True,
                       num_kv_heads=2, qkv_bias=False, out_bias=False),
    "falcon_rw": dict(pos="alibi", alibi_scaled=True, norm="layernorm",
                      mlp="gelu", gelu_exact=True, parallel_attn=False,
                      qkv_bias=True, out_bias=True),
    "gptj": dict(pos="rope", rope_style="gptj", rope_pct=0.5,
                 norm="layernorm", mlp="gelu", parallel_attn=True,
                 qkv_bias=False, out_bias=False, mlp_bias=True,
                 tie_embeddings=False, lm_head_bias=True),
    "phi": dict(pos="rope", rope_pct=0.5, norm="layernorm", mlp="gelu",
                parallel_attn=True, qkv_bias=True, out_bias=True,
                tie_embeddings=False, lm_head_bias=True),
}


def _make(case):
    cfg = ArchConfig(**{**BASE, **case})
    model = UniversalCausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    if cfg.lm_head_bias:
        params["lm_head"]["bias"] = jnp.asarray(
            np.random.default_rng(1).normal(size=(cfg.vocab_size,)) * 0.1,
            jnp.float32)
    return model, params


@pytest.mark.parametrize("family", sorted(FAMILY_CASES))
@pytest.mark.parametrize("impl", ["paged", "gather"])
def test_ragged_matches_compat_forward(family, impl):
    model, params = _make(FAMILY_CASES[family])
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 96, size=13).tolist()

    eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
        max_tokens=8, max_seqs=2, max_ctx=64, block_size=8,
        dtype=jnp.float32, attn_impl=impl))
    # serve the prompt in splitfuse chunks of 8, then 2 decode steps
    logits = None
    for i in range(0, len(prompt), 8):
        logits = eng.put([0], [prompt[i:i + 8]])
    toks = list(prompt)
    for _ in range(2):
        nxt = int(jnp.argmax(logits[0]))
        toks.append(nxt)
        logits = eng.put([0], [[nxt]])
    eng.flush([0])

    full = model(params, jnp.asarray([toks], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[0]),
                               np.asarray(full[0, -1]), atol=2e-4, rtol=2e-4)


def test_two_universal_sequences_batched():
    """Mixed prefill+decode batch of two sequences through one forward."""
    model, params = _make(FAMILY_CASES["gpt2"])
    eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
        max_tokens=12, max_seqs=2, max_ctx=64, block_size=8,
        dtype=jnp.float32, attn_impl="paged"))
    p0 = [3, 5, 7, 11, 13]
    p1 = [17, 19, 23]
    logits = eng.put([0, 1], [p0, p1])
    eng.flush([0, 1])
    full0 = model(params, jnp.asarray([p0], jnp.int32))
    full1 = model(params, jnp.asarray([p1], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[0]),
                               np.asarray(full0[0, -1]), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(logits[1]),
                               np.asarray(full1[0, -1]), atol=2e-4, rtol=2e-4)
