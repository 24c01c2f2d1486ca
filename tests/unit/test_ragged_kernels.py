"""Flat-token ragged paged-attention kernel vs the dense page-gather oracle
(reference test analogue: tests/unit/inference/v2/kernels/ragged_ops/).

Covers the round-4 kernel redesign: mixed prefill/decode batches, several
sequences inside one query block, GQA, multi-chunk context walks (double-
buffered DMA), ALiBi (bloom + falcon-scaled), interior zero-q-len rows,
layout-invariance across block_q/pages_per_chunk, the paged KV append, and
the VMEM budget clamp.  Runs in interpret mode off-TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.kernels.ragged_ops import (
    paged_kv_append,
    ragged_paged_attention,
)
from deepspeed_tpu.inference.v2.kernels.page_ops import _attend_gather

pytestmark = pytest.mark.kernels


def _case(rng, q_lens, ctx_lens, KV, G, hd, ps, NB):
    """Random flat-token batch in the page-pool layout."""
    S = len(q_lens)
    H = KV * G
    T = int(sum(q_lens))
    np_tot = S * NB + 1                      # + shared trash page
    q = jnp.asarray(rng.normal(size=(T, H, hd)), jnp.float32)
    pages = jnp.asarray(rng.normal(size=(np_tot, ps, 2 * KV, hd)), jnp.float32)
    pt = np.zeros((S, NB), np.int32)
    perm = rng.permutation(np_tot - 1)       # distinct pages, never trash
    for s in range(S):
        pt[s] = perm[s * NB:(s + 1) * NB]
    cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
    return (q, pages, jnp.asarray(ctx_lens, jnp.int32), jnp.asarray(pt),
            jnp.asarray(cu))


def _oracle(q, pages, pt, q_lens, ctx_lens, hd, alibi=None,
            alibi_scaled=False):
    """Flat [T, H, hd] reference output via the per-sequence gather oracle."""
    S = len(q_lens)
    mq = max(int(n) for n in q_lens) if q_lens else 1
    T, H, _ = q.shape
    q_seq = np.zeros((S, mq, H, hd), np.float32)
    c = 0
    for s, n in enumerate(q_lens):
        q_seq[s, :n] = np.asarray(q)[c:c + n]
        c += n
    o = _attend_gather(jnp.asarray(q_seq), pages, pt,
                       jnp.asarray(q_lens, jnp.int32),
                       jnp.asarray(ctx_lens, jnp.int32),
                       1.0 / np.sqrt(hd), alibi=alibi,
                       alibi_scaled=alibi_scaled)
    out = np.zeros((T, H, hd), np.float32)
    c = 0
    for s, n in enumerate(q_lens):
        out[c:c + n] = np.asarray(o)[s, :n]
        c += n
    return out


class TestRaggedPagedAttention:
    @pytest.mark.parametrize("gqa", [1, 2, 4])
    def test_matches_oracle_mixed_batch(self, gqa):
        """Prefill + decode + short-prefill in one batch; BQ covers all
        three sequences, so one grid step walks multiple sequences."""
        rng = np.random.default_rng(0)
        KV, hd, ps, NB = 2, 64, 16, 6
        q_lens, ctx_lens = [5, 1, 3], [5, 37, 90]
        q, pages, kvl, pt, cu = _case(rng, q_lens, ctx_lens, KV, gqa, hd, ps, NB)
        out = ragged_paged_attention(q, pages, kvl, pt, cu, num_kv_heads=KV,
                                     block_q=16, pages_per_chunk=2)
        ref = _oracle(q, pages, pt, q_lens, ctx_lens, hd)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)

    def test_multi_chunk_context_walk(self):
        """Context much longer than one DMA chunk (P*ps) exercises the
        double-buffered chunk loop."""
        rng = np.random.default_rng(1)
        KV, hd, ps, NB = 1, 32, 8, 16
        q_lens, ctx_lens = [1, 1], [97, 128]       # 13 and 16 chunks at P=1
        q, pages, kvl, pt, cu = _case(rng, q_lens, ctx_lens, KV, 2, hd, ps, NB)
        out = ragged_paged_attention(q, pages, kvl, pt, cu, num_kv_heads=KV,
                                     block_q=8, pages_per_chunk=1)
        ref = _oracle(q, pages, pt, q_lens, ctx_lens, hd)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)

    def test_causal_within_prefill(self):
        """A prefill row must not see keys beyond its own position: poison
        every context slot past position 0; row 0 is fixed, row 3 changes."""
        rng = np.random.default_rng(2)
        KV, hd, ps, NB = 2, 32, 4, 2
        q_lens, ctx_lens = [4], [4]
        q, pages, kvl, pt, cu = _case(rng, q_lens, ctx_lens, KV, 1, hd, ps, NB)
        kw = dict(num_kv_heads=KV, block_q=8, pages_per_chunk=1)
        out = ragged_paged_attention(q, pages, kvl, pt, cu, **kw)
        p0 = int(pt[0, 0])
        poisoned = pages.at[p0, 1:].set(99.0)      # rows 1.. of first page
        out2 = ragged_paged_attention(q, poisoned, kvl, pt, cu, **kw)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(out2[0]),
                                   atol=1e-5, rtol=1e-5)
        assert not np.allclose(np.asarray(out[3]), np.asarray(out2[3]))

    @pytest.mark.parametrize("scaled", [False, True])
    def test_alibi(self, scaled):
        """Bloom (unscaled f32) and falcon (bf16 pre-scale) ALiBi variants."""
        rng = np.random.default_rng(3)
        KV, G, hd, ps, NB = 2, 2, 32, 8, 4
        H = KV * G
        slopes = [2.0 ** (-(i + 1)) for i in range(H)]
        q_lens, ctx_lens = [3, 1], [3, 20]
        q, pages, kvl, pt, cu = _case(rng, q_lens, ctx_lens, KV, G, hd, ps, NB)
        out = ragged_paged_attention(q, pages, kvl, pt, cu, num_kv_heads=KV,
                                     alibi=slopes, alibi_scaled=scaled,
                                     block_q=8, pages_per_chunk=2)
        ref = _oracle(q, pages, pt, q_lens, ctx_lens, hd, alibi=slopes,
                      alibi_scaled=scaled)
        np.testing.assert_allclose(np.asarray(out), ref, atol=3e-3, rtol=3e-3)

    def test_interior_zero_qlen_row_is_skipped(self):
        """ADVICE r4: an empty row mid-batch must not hide later sequences.
        cu_q_lens = [0, 2, 2, 4] — row 1 contributes no queries; row 2's
        output must still match the oracle."""
        rng = np.random.default_rng(4)
        KV, hd, ps, NB = 2, 32, 8, 4
        q_lens_real = [2, 0, 2]
        ctx_lens = [2, 0, 17]
        q, pages, kvl, pt, cu = _case(rng, q_lens_real, ctx_lens, KV, 1, hd,
                                      ps, NB)
        out = ragged_paged_attention(q, pages, kvl, pt, cu, num_kv_heads=KV,
                                     block_q=8, pages_per_chunk=1)
        # oracle over the two real sequences only
        ref = _oracle(q, pages, pt[jnp.asarray([0, 2])], [2, 2], [2, 17], hd)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)

    def test_layout_invariance(self):
        """block_q / pages_per_chunk are tuning knobs, not semantics."""
        rng = np.random.default_rng(5)
        KV, hd, ps, NB = 2, 32, 8, 6
        q_lens, ctx_lens = [7, 1, 1, 2], [7, 30, 44, 11]
        q, pages, kvl, pt, cu = _case(rng, q_lens, ctx_lens, KV, 2, hd, ps, NB)
        outs = []
        for bq, p in [(8, 1), (16, 2), (128, 4)]:
            outs.append(np.asarray(ragged_paged_attention(
                q, pages, kvl, pt, cu, num_kv_heads=KV, block_q=bq,
                pages_per_chunk=p)))
        np.testing.assert_allclose(outs[0], outs[1], atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(outs[0], outs[2], atol=2e-5, rtol=2e-5)

    def test_vmem_budget_clamp(self):
        """An over-budget config must fail with the clear message, not an
        opaque Mosaic error (ADVICE r4)."""
        q = jnp.zeros((8, 8, 256), jnp.float32)
        pages = jnp.zeros((4, 512, 16, 256), jnp.float32)  # 8MB per page set
        kvl = jnp.ones(1, jnp.int32)
        pt = jnp.zeros((1, 2), jnp.int32)
        cu = jnp.asarray([0, 8], jnp.int32)
        with pytest.raises(ValueError, match="VMEM budget"):
            ragged_paged_attention(q, pages, kvl, pt, cu, num_kv_heads=8,
                                   block_q=8, pages_per_chunk=2)


class TestRaggedFuzz:
    @pytest.mark.parametrize("seed", [10, 11, 12, 13])
    def test_random_batches_match_oracle(self, seed):
        """Randomized mixed batches: prefill spans crossing block_q
        boundaries, T landing exactly on tile edges, fresh prefills
        (ctx == q_len), partial tail chunks — all must match the oracle."""
        rng = np.random.default_rng(seed)
        KV = int(rng.choice([1, 2]))
        G = int(rng.choice([1, 2, 4]))
        hd = int(rng.choice([32, 64]))
        ps = int(rng.choice([4, 8, 16]))
        S = int(rng.integers(1, 5))
        q_lens, ctx_lens = [], []
        for _ in range(S):
            q = int(rng.integers(1, 12))
            seen = int(rng.integers(0, 40))
            q_lens.append(q)
            ctx_lens.append(seen + q)
        NB = max(-(-max(ctx_lens) // ps), 1)
        q, pages, kvl, pt, cu = _case(rng, q_lens, ctx_lens, KV, G, hd, ps, NB)
        bq = int(rng.choice([8, 16]))
        p = int(rng.choice([1, 2, 4]))
        out = ragged_paged_attention(q, pages, kvl, pt, cu, num_kv_heads=KV,
                                     block_q=bq, pages_per_chunk=p)
        ref = _oracle(q, pages, pt, q_lens, ctx_lens, hd)
        np.testing.assert_allclose(
            np.asarray(out), ref, atol=3e-5, rtol=3e-5,
            err_msg=f"cfg KV={KV} G={G} hd={hd} ps={ps} q={q_lens} "
                    f"ctx={ctx_lens} bq={bq} P={p}")


class TestPagedKVAppend:
    def test_append_and_trash_isolation(self):
        KV, hd, ps, nb = 2, 16, 4, 3
        pages = jnp.zeros((nb + 1, ps, 2 * KV, hd))
        T = 5
        k = jnp.ones((T, KV, hd)) * jnp.arange(1, T + 1)[:, None, None]
        v = -k
        trash = nb
        page_of = jnp.asarray([0, 0, 2, trash, trash], jnp.int32)
        off_of = jnp.asarray([0, 1, 1, 0, 0], jnp.int32)
        out = paged_kv_append(pages, k, v, page_of, off_of)
        np.testing.assert_allclose(np.asarray(out[0, 0, :KV, 0]), 1.0)
        np.testing.assert_allclose(np.asarray(out[0, 1, :KV, 0]), 2.0)
        np.testing.assert_allclose(np.asarray(out[2, 1, :KV, 0]), 3.0)
        np.testing.assert_allclose(np.asarray(out[2, 1, KV:, 0]), -3.0)
        # untouched rows stay zero; padded writes landed in the trash page
        assert np.all(np.asarray(out[1]) == 0.0)
        assert np.all(np.asarray(out[0, 2:]) == 0.0)


class TestEngineAttnImpls:
    def test_paged_vs_gather_logits(self):
        """End-to-end serving: both attention impls produce the same logits."""
        from deepspeed_tpu.inference.v2.engine_v2 import (
            InferenceEngineV2,
            RaggedInferenceEngineConfig,
        )
        from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

        cfg = TransformerConfig.tiny(use_flash=False)
        model = CausalLM(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        prompts = [[3, 5, 7, 11, 13], [17, 19]]
        outs = {}
        for impl in ("paged", "gather"):
            eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
                max_tokens=16, max_seqs=4, max_ctx=64, block_size=8,
                dtype=jnp.float32, attn_impl=impl, block_q=16,
                pages_per_chunk=2))
            logits = eng.put([0, 1], prompts)
            outs[impl] = np.asarray(logits)
        np.testing.assert_allclose(outs["paged"], outs["gather"],
                                   atol=3e-4, rtol=3e-4)

    def test_block_q_logit_parity(self):
        """Different query tiles give identical logits (layout-invariant)."""
        from deepspeed_tpu.inference.v2.engine_v2 import (
            InferenceEngineV2,
            RaggedInferenceEngineConfig,
        )
        from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

        cfg = TransformerConfig.tiny(use_flash=False)
        model = CausalLM(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        prompts = [[3, 5, 7, 11, 13, 2, 4], [17, 19]]
        outs = {}
        for bq in (8, 16):
            eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
                max_tokens=16, max_seqs=4, max_ctx=64, block_size=8,
                dtype=jnp.float32, attn_impl="paged", block_q=bq,
                pages_per_chunk=2))
            outs[bq] = np.asarray(eng.put([0, 1], prompts))
        np.testing.assert_allclose(outs[8], outs[16], atol=2e-5, rtol=2e-5)
