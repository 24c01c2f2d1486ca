"""Minimal serving example: continuous batching through the FastGen-style
ragged engine — paged KV cache, SplitFuse scheduling, fused decode windows.

    JAX_PLATFORMS=cpu python examples/serve_continuous_batching.py

For a real checkpoint, build the engine via ``deepspeed_tpu.init_inference``
(HF-style) instead; this example uses a random tiny model so it runs
anywhere.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from deepspeed_tpu.inference.v2.engine_v2 import (
    ContinuousBatcher,
    InferenceEngineV2,
    RaggedInferenceEngineConfig,
)
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh


def main():
    initialize_mesh(TopologyConfig(), force=True)
    cfg = TransformerConfig(
        vocab_size=1000, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=256,
        use_flash=jax.default_backend() == "tpu")
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))

    engine = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
        max_tokens=64,          # SplitFuse token budget per forward
        max_seqs=8,             # live sequences per batch
        max_ctx=256,
        block_size=16,          # KV page size
        attn_impl="paged" if jax.default_backend() == "tpu" else "gather"))

    # --- one-shot batch API --------------------------------------------- #
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 1000, size=n).tolist() for n in (12, 5, 30)]
    outs = engine.generate(prompts, max_new_tokens=16)
    for i, o in enumerate(outs):
        print(f"request {i}: prompt {len(prompts[i])} tokens -> {o[:8]}...")

    # --- streaming/server-style API: requests arrive over time ---------- #
    batcher = ContinuousBatcher(engine, max_new_tokens=12)
    for uid in range(20):                       # 20 queued requests
        batcher.add_request(uid, rng.integers(1, 1000, size=8).tolist())
    steps = 0
    while batcher.pending:
        finished = batcher.step()               # one SplitFuse forward
        steps += 1
        for uid in finished:
            print(f"  step {steps}: request {uid} done "
                  f"({len(batcher.finished[uid])} tokens)")
    print(f"served 20 requests in {steps} engine steps "
          f"(KV blocks free again: {engine.state_manager.free_blocks})")


if __name__ == "__main__":
    main()
