"""Continuous-batching scheduler at the operating point:
64-sequence churn (admission, eviction, block recycling) and O(batch)
scheduling cost independent of queue depth.

Reference analogue: the MII scheduling layer over
deepspeed/inference/v2/engine_v2.py:158-242 budget primitives.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (
    ContinuousBatcher,
    InferenceEngineV2,
    RaggedInferenceEngineConfig,
    SchedulingResult,
)
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

pytestmark = pytest.mark.inference


@pytest.fixture(scope="module")
def tiny():
    initialize_mesh(TopologyConfig(), force=True)
    cfg = TransformerConfig.tiny(use_flash=False)
    model = CausalLM(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def _engine(model, params, **kw):
    defaults = dict(max_tokens=16, max_seqs=4, max_ctx=64, block_size=8,
                    dtype=jnp.float32, attn_impl="gather")
    defaults.update(kw)
    return InferenceEngineV2(model, params,
                             RaggedInferenceEngineConfig(**defaults))


class TestChurn:
    def test_64_stream_churn_with_tight_kv(self, tiny):
        """64 staggered requests through a cache that holds only ~4 live
        sequences: the batcher must admit in waves, evict at completion,
        recycle every block, and complete ALL streams."""
        model, params = tiny
        # 16 blocks x 8 = 128 slots; each request reserves
        # ceil((prompt + max_new)/8) blocks -> ~3-4 concurrent residents
        eng = _engine(model, params, num_blocks=16)
        b = ContinuousBatcher(eng, max_new_tokens=6)
        rng = np.random.default_rng(0)
        for u in range(64):
            b.add_request(u, rng.integers(1, 255, size=int(rng.integers(
                3, 20))).tolist())
        steps = 0
        while b.pending:
            b.step()
            steps += 1
            assert steps < 2000, "churn did not converge"
        assert len(b.finished) == 64
        assert all(len(v) == 6 for v in b.finished.values())
        # every block back in the pool; no tracked-sequence leak
        assert eng.state_manager.free_blocks == 16
        assert eng.state_manager.n_tracked_sequences == 0

    def test_matches_generate_output(self, tiny):
        """Batcher-driven serving produces the same greedy tokens as the
        one-shot generate loop (same engine semantics underneath)."""
        model, params = tiny
        prompts = [[3, 5, 7, 11, 13], [17, 19], [23, 29, 31]]
        eng1 = _engine(model, params)
        ref = eng1.generate(prompts, max_new_tokens=8)
        eng2 = _engine(model, params)
        b = ContinuousBatcher(eng2, max_new_tokens=8)
        for u, p in enumerate(prompts):
            b.add_request(u, p)
        out = b.run()
        assert [out[u] for u in range(3)] == ref

    def test_eos_and_rejection(self, tiny):
        model, params = tiny
        eng = _engine(model, params)
        b = ContinuousBatcher(eng, max_new_tokens=8, eos_token_id=1)
        b.add_request(0, [3, 5])
        b.add_request(1, list(range(1, 200)))     # > max_ctx: rejected
        b.add_request(2, [])                      # empty: finished at once
        out = b.run()
        assert out[1] == [] and out[2] == []
        assert 1 <= len(out[0]) <= 8
        assert eng.state_manager.free_blocks == eng.kv.config.num_blocks


class TestSchedulingCost:
    def test_next_batch_touch_count_independent_of_queue_depth(self, tiny):
        """Scheduling examines O(batch) uids regardless of how many requests
        are queued — the kill-the-rescan criterion, pinned structurally
        (touched-uid count), not by wall clock."""
        model, params = tiny
        touched = {}
        for depth in (100, 5000):
            eng = _engine(model, params, num_blocks=16)
            b = ContinuousBatcher(eng, max_new_tokens=4)
            for u in range(depth):
                b.add_request(u, [3, 5, 7])
            b.step()
            touched[depth] = b.touched
        assert touched[5000] == touched[100], touched
        assert touched[5000] <= 4 + 4      # max_seqs decodes + admissions

    def test_steady_state_touch_bound(self, tiny):
        """Mid-churn (mixed decodes + prefills + deep queue) the per-step
        touch count stays within the batch budget bound."""
        model, params = tiny
        eng = _engine(model, params, num_blocks=16)
        b = ContinuousBatcher(eng, max_new_tokens=4)
        for u in range(500):
            b.add_request(u, [3, 5, 7, 11, 13])
        cap = eng.config.max_seqs * 2 + 1
        for _ in range(25):
            if not b.pending:
                break
            b.step()
            assert b.touched <= cap, (b.touched, cap)


class TestEvictionEdgeCases:
    """Scheduler eviction paths that existed untested: flushing a uid whose
    async DecodeWindow has not been drained yet, and admission of a request
    whose whole-lifetime block reservation can never fit the pool."""

    def test_flush_of_uid_inside_undrained_window(self, tiny):
        """flush() while the uid's fused window is still in flight: the
        window must still drain cleanly, the blocks must be back in the
        pool immediately, the engine's device-resume state must be
        invalidated (a later window repacks instead of resuming the
        flushed stream), and the freed blocks must be re-admittable."""
        model, params = tiny
        eng = _engine(model, params, num_blocks=6)
        logits = eng.put([0], [[3, 5, 7, 11]])
        seed = int(jnp.argmax(logits[0]))
        window = eng.decode_batch_async([0], [seed], steps=4)
        eng.flush([0])                          # mid-flight eviction
        assert eng.state_manager.free_blocks == 6
        assert eng._decode_state is None        # resume state invalidated
        toks = window.tokens()                  # drains without error
        assert toks.shape == (4, 1)
        assert window.nonfinite is not None and not window.nonfinite.any()
        # freed blocks are re-admittable: a new request prefills + decodes
        logits = eng.put([1], [[2] * 14])
        seed = int(jnp.argmax(logits[0]))
        toks2 = eng.decode_batch([1], [seed], steps=4)
        assert toks2.shape == (4, 1)
        # the flushed uid's stale stream was NOT resumed into uid 1
        assert eng.decode_resume_hits == 0
        eng.flush([1])
        assert eng.state_manager.free_blocks == 6

    def test_whole_lifetime_reservation_exceeding_pool_rejects(self, tiny):
        """A request whose prompt+decode reservation exceeds the pool must
        be rejected at admission — NOT hold the queue head hostage while
        the allocator waits for blocks that can never exist."""
        model, params = tiny
        eng = _engine(model, params, num_blocks=4)   # 32-token pool
        assert eng.can_schedule([0], [40]) is not SchedulingResult.Success
        b = ContinuousBatcher(eng, max_new_tokens=16)
        b.add_request(0, [2] * 30)          # 30+16 = 46 tokens > pool
        b.add_request(1, [3, 5, 7])         # fits easily behind it
        done = b.run()
        assert b.rejected == [0]
        assert done[0] == []                # rejected, empty stream
        assert len(done[1]) == 16           # the head never wedged
        assert eng.state_manager.free_blocks == 4
