"""Serving decode fast path: decode-specialized paged attention parity,
on-device sampling, device-resident continuous decode, and compile-cache
bucketing (PR 6; marker: serving).

The decode kernel (one query token per sequence, online softmax over the
page walk) is tolerance-asserted against the dense q_len=1 lowering and the
prefill-shaped gather oracle at MHA and GQA head layouts and at
block-boundary context lengths.  The engine layer is probed for retraces
(``trace_counts``) across a mixed prefill/decode schedule and for sampling
determinism under a fixed key.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.kernels.page_ops import _attend_gather
from deepspeed_tpu.inference.v2.kernels.ragged_ops import (
    _decode_head_load,
    _pairs_per_pass,
    decode_attend_dense,
    decode_attention,
    decode_paged_attention,
)
from deepspeed_tpu.inference.v2.model_runner import sample_tokens

pytestmark = pytest.mark.serving


def _decode_case(rng, ctx_lens, KV, G, hd, ps, NB):
    """One-query-token-per-sequence batch in the page-pool layout."""
    S = len(ctx_lens)
    H = KV * G
    npages = S * NB + 1                      # + never-referenced spare page
    q = jnp.asarray(rng.normal(size=(S, H, hd)), jnp.float32)
    pages = jnp.asarray(rng.normal(size=(npages, ps, 2 * KV, hd)),
                        jnp.float32)
    pt = np.zeros((S, NB), np.int32)
    perm = rng.permutation(npages - 1)
    for s in range(S):
        pt[s] = perm[s * NB:(s + 1) * NB]
    return q, pages, jnp.asarray(ctx_lens, jnp.int32), jnp.asarray(pt)


def _gather_oracle(q, pages, pt, ctx_lens, hd):
    """Decode reference via the prefill-shaped gather oracle (q_len = 1)."""
    S, H, _ = q.shape
    ones = jnp.ones(S, jnp.int32)
    o = _attend_gather(q[:, None], pages, pt, ones,
                       jnp.asarray(ctx_lens, jnp.int32), 1.0 / np.sqrt(hd))
    return np.asarray(o[:, 0])


class TestDecodeKernelParity:
    @pytest.mark.parametrize("gqa", [1, 4])      # 1 = MHA (KV == H)
    def test_paged_vs_gather_parity(self, gqa):
        """Decode kernel (interpret mode) and its dense lowering both match
        the gather oracle at MHA and GQA head layouts."""
        rng = np.random.default_rng(20)
        KV, hd, ps, NB = 2, 32, 8, 6
        ctx = [44, 17, 1, 30]
        q, pages, kvl, pt = _decode_case(rng, ctx, KV, gqa, hd, ps, NB)
        ref = _gather_oracle(q, pages, pt, ctx, hd)
        out_k = decode_paged_attention(q, pages, kvl, pt, num_kv_heads=KV,
                                       pages_per_chunk=2, interpret=True)
        out_d = decode_attend_dense(q, pages, kvl, pt, num_kv_heads=KV)
        np.testing.assert_allclose(np.asarray(out_k), ref,
                                   atol=3e-5, rtol=3e-5)
        np.testing.assert_allclose(np.asarray(out_d), ref,
                                   atol=3e-5, rtol=3e-5)

    @pytest.mark.parametrize("rem", [0, 1, -1])
    def test_block_boundary_contexts(self, rem):
        """ctx % page_size ∈ {0, 1, page_size-1}: the page walk's tail
        masking must be exact at every boundary alignment."""
        rng = np.random.default_rng(21)
        KV, G, hd, ps, NB = 2, 2, 32, 8, 5
        base = 3 * ps                              # 3 full pages
        ctx = [base + rem, ps + rem if ps + rem > 0 else ps, 2 * ps + rem]
        q, pages, kvl, pt = _decode_case(rng, ctx, KV, G, hd, ps, NB)
        ref = _gather_oracle(q, pages, pt, ctx, hd)
        out_k = decode_paged_attention(q, pages, kvl, pt, num_kv_heads=KV,
                                       pages_per_chunk=2, interpret=True)
        out_d = decode_attend_dense(q, pages, kvl, pt, num_kv_heads=KV)
        np.testing.assert_allclose(np.asarray(out_k), ref,
                                   atol=3e-5, rtol=3e-5)
        np.testing.assert_allclose(np.asarray(out_d), ref,
                                   atol=3e-5, rtol=3e-5)

    def test_padding_rows_yield_zeros(self):
        """kv_lens == 0 rows are bucket padding: all-zero output, and no
        NaN contamination from never-written pages."""
        rng = np.random.default_rng(22)
        KV, G, hd, ps, NB = 1, 2, 16, 4, 3
        ctx = [9, 0, 5]
        q, pages, kvl, pt = _decode_case(rng, ctx, KV, G, hd, ps, NB)
        pages = pages.at[int(pt[1, 0])].set(jnp.nan)   # pad row's first page
        for out in (
            decode_paged_attention(q, pages, kvl, pt, num_kv_heads=KV,
                                   pages_per_chunk=2, interpret=True),
            decode_attend_dense(q, pages, kvl, pt, num_kv_heads=KV),
        ):
            out = np.asarray(out)
            assert np.all(np.isfinite(out))
            np.testing.assert_allclose(out[1], 0.0)

    def test_pages_per_chunk_invariance(self):
        """pages_per_chunk is a DMA tuning knob, not semantics."""
        rng = np.random.default_rng(23)
        KV, G, hd, ps, NB = 2, 2, 32, 8, 6
        ctx = [41, 48, 7]
        q, pages, kvl, pt = _decode_case(rng, ctx, KV, G, hd, ps, NB)
        outs = [np.asarray(decode_paged_attention(
            q, pages, kvl, pt, num_kv_heads=KV, pages_per_chunk=p,
            interpret=True)) for p in (1, 4)]
        np.testing.assert_allclose(outs[0], outs[1], atol=2e-5, rtol=2e-5)

    def test_alibi_parity(self):
        """Per-head ALiBi bias rides the decode kernel's [G, chunk] tile."""
        rng = np.random.default_rng(24)
        KV, G, hd, ps, NB = 2, 2, 32, 8, 4
        H = KV * G
        slopes = [2.0 ** (-(i + 1)) for i in range(H)]
        ctx = [25, 8]
        q, pages, kvl, pt = _decode_case(rng, ctx, KV, G, hd, ps, NB)
        out_k = decode_paged_attention(q, pages, kvl, pt, num_kv_heads=KV,
                                       alibi=slopes, pages_per_chunk=2,
                                       interpret=True)
        out_d = decode_attend_dense(q, pages, kvl, pt, num_kv_heads=KV,
                                    alibi=slopes)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_d),
                                   atol=3e-5, rtol=3e-5)

    # ---- the cells' geometries: bf16 pools of 64-token pages --------------- #
    # Tolerance.  The kernel hands q, K, V and the probabilities to the MXU
    # in the pool's dtype and accumulates in float32; the dense lowering
    # computes everything in float32 from the same bf16 inputs.  q.K
    # products of bf16 values are exact in float32, so the scores differ
    # by summation order only; the probabilities are rounded to bf16
    # (relative 2^-9 each, on a weighted mean of |v| ~ 1 values: a few
    # 1e-3 absolute at most) and BOTH outputs are rounded to bf16 at the
    # end (2^-9 relative each: one bf16 ulp, 2^-8, between them).
    BF16_TOL = dict(rtol=2.0 ** -7, atol=6e-3)
    # Mistral's 32 / 8 heads of 128 (one lane tile a page) and Qwen3-Next's
    # 16 / 2 heads of 256 (PR 33: 4 combined rows a token, two lane tiles)
    CELL = dict(KV=8, G=4, hd=128, ps=64, NB=10)
    QWEN = dict(KV=2, G=8, hd=256, ps=64, NB=10)
    # PR 37, a pass of several head pairs (``_pairs_per_pass``): Olmo-
    # Hybrid's pool of 32 stored heads at one query row a head (4 pairs a
    # pass), the same pool serving the model's 30 heads (``heads``: the
    # queries get two zero heads and the output is cut back), and a group
    # of 2 on 16 kv heads (2 pairs a pass)
    OLMO = dict(KV=32, G=1, hd=128, ps=64, NB=10)
    OLMO30 = dict(OLMO, heads=30)
    GROUP2 = dict(KV=16, G=2, hd=128, ps=64, NB=10)
    GEOMETRIES = [pytest.param(CELL, id="mistral"),
                  pytest.param(QWEN, id="qwen3next"),
                  pytest.param(OLMO, id="olmo"),
                  pytest.param(OLMO30, id="olmo-30-in-32"),
                  pytest.param(GROUP2, id="group2")]

    @staticmethod
    def _heads(geom):
        """The MODEL's kv heads (the pool stores ``geom["KV"]``)."""
        return geom.get("heads", geom["KV"])

    def _cell_case(self, seed, ctx, poison=True, geom=None):
        rng = np.random.default_rng(seed)
        g = geom or self.CELL
        q, pages, kvl, pt = _decode_case(rng, ctx, g["KV"], g["G"], g["hd"],
                                         g["ps"], g["NB"])
        q = q[:, :self._heads(g) * g["G"]]
        q, pages = q.astype(jnp.bfloat16), pages.astype(jnp.bfloat16)
        if poison:        # every page the walk must not read: NaN
            for s, c in enumerate(ctx):
                for b in range(-(-c // g["ps"]), g["NB"]):
                    pages = pages.at[int(pt[s, b])].set(jnp.nan)
        return q, pages, kvl, pt

    def _assert_matches_dense(self, q, pages, kvl, pt, KV, **kernel_kw):
        """Kernel (interpret mode) against the dense float32 lowering;
        returns the kernel's output as float32."""
        dense_kw = {k: v for k, v in kernel_kw.items() if k == "alibi"}
        out, ref = (np.asarray(o.astype(jnp.float32)) for o in (
            decode_paged_attention(q, pages, kvl, pt, num_kv_heads=KV,
                                   interpret=True, **kernel_kw),
            decode_attend_dense(q, pages, kvl, pt, num_kv_heads=KV,
                                **dense_kw)))
        assert np.all(np.isfinite(out))
        np.testing.assert_allclose(out, ref, **self.BF16_TOL)
        return out

    @pytest.mark.parametrize("geom", GEOMETRIES)
    @pytest.mark.parametrize("ctx", [
        pytest.param([512, 511, 513], id="chunk-boundary"),
        pytest.param([1, 130, 1], id="one-token"),
        pytest.param([513, 0, 65, 0, 512], id="padding-rows-between"),
        pytest.param([640, 64, 600], id="nan-never-fetched"),
    ])
    def test_bf16_pool_at_cell_geometry(self, ctx, geom):
        """bf16 pool, the strided pair load (interpret mode) against the
        dense float32 lowering: contexts on and either side of a 512-token
        chunk boundary, one-token contexts, ``kv_lens == 0`` padding rows
        between live rows (the first-chunk hand-over must skip them), and
        NaN in every page past each context (never fetched, or fetched
        behind the context's end: masked AND zeroed)."""
        out = self._assert_matches_dense(
            *self._cell_case(30, ctx, geom=geom), self._heads(geom))
        for s, c in enumerate(ctx):
            if c == 0:
                np.testing.assert_array_equal(out[s], 0.0)

    @pytest.mark.parametrize("geom", GEOMETRIES)
    def test_bf16_pool_partial_last_page_holds_nan(self, geom):
        """A context ending INSIDE a page: the page is fetched, the rows
        behind the context's end hold NaN (bf16) and must not reach the
        output through a 0-probability product."""
        ctx = [100, 577]
        q, pages, kvl, pt = self._cell_case(31, ctx, poison=False, geom=geom)
        ps = geom["ps"]
        for s, c in enumerate(ctx):
            pid = int(pt[s, c // ps])
            pages = pages.at[pid, c % ps:].set(jnp.nan)
        self._assert_matches_dense(q, pages, kvl, pt, self._heads(geom))

    @pytest.mark.parametrize("geom,ppc", [
        pytest.param(CELL, 1, id="mistral-1"),
        pytest.param(CELL, 4, id="mistral-4"),
        pytest.param(CELL, 8, id="mistral-8"),
        pytest.param(QWEN, 1, id="qwen3next-1"),
        pytest.param(QWEN, 4, id="qwen3next-4"),
        pytest.param(QWEN, 8, id="qwen3next-8"),
        pytest.param(dict(QWEN, NB=17), 16, id="qwen3next-16"),
        pytest.param(OLMO30, 1, id="olmo-30-in-32-1"),
        pytest.param(OLMO, 2, id="olmo-2"),
        pytest.param(OLMO30, 8, id="olmo-30-in-32-8"),     # a 2 MiB chunk: 2
        pytest.param(GROUP2, 1, id="group2-1"),
        pytest.param(GROUP2, 8, id="group2-8"),
    ])
    def test_bf16_pool_pages_per_chunk_invariance(self, geom, ppc):
        """1, 4, 8 and 16 pages a chunk walk the same context to the same
        answer (against the dense lowering, so each case stands alone)."""
        self._assert_matches_dense(
            *self._cell_case(32, [577, 0, 256, 129], geom=geom),
            self._heads(geom), pages_per_chunk=ppc)

    @pytest.mark.parametrize("geom", [
        pytest.param(dict(KV=8, G=2, hd=256, ps=64, NB=10), id="KV8-hd256"),
        pytest.param(dict(KV=4, G=2, hd=128, ps=64, NB=10), id="KV4-hd128"),
    ])
    def test_bf16_pool_strided_load_at_other_pools(self, geom):
        """Pools the strided load takes since PR 33 and no cell runs: GQA-8
        with 256-wide heads (the parent promised ``strided`` there and the
        chip refused the kernel) and 8 combined rows a token."""
        assert _decode_head_load(jnp.bfloat16, geom["KV"], geom["hd"],
                                 geom["ps"]) == "strided"
        self._assert_matches_dense(
            *self._cell_case(37, [513, 0, 70, 600], geom=geom), geom["KV"])

    @pytest.mark.parametrize("KV,G,pairs", [
        (8, 4, 1), (2, 8, 1),              # Mistral, Qwen3-Next: PR 29's pass
        (32, 1, 4), (16, 2, 2), (4, 1, 2), (2, 1, 1), (24, 1, 4), (40, 1, 4),
    ])
    def test_pairs_per_pass_fills_the_sublane_tile(self, KV, G, pairs):
        """The largest divisor of ``KV / 2`` whose ``2·m·G`` query rows fit
        8 sublanes, read off the stored head count and the group alone."""
        assert _pairs_per_pass(KV, G) == pairs

    def test_bf16_pool_alibi_rides_the_pair_tile(self):
        """Per-head slopes in the two-heads-a-pass layout (MHA, so a pass
        holds two query rows of different heads)."""
        rng = np.random.default_rng(33)
        KV, hd, ps, NB = 8, 128, 64, 4
        slopes = [2.0 ** (-(i + 1) / 2) for i in range(KV)]
        q, pages, kvl, pt = _decode_case(rng, [130, 64], KV, 1, hd, ps, NB)
        self._assert_matches_dense(
            q.astype(jnp.bfloat16), pages.astype(jnp.bfloat16), kvl, pt, KV,
            alibi=slopes, pages_per_chunk=2)

    def test_decode_layout_record_names_the_load(self):
        """``attn/decode_layout`` (ring only, one per traced call): strided
        at the cells' geometries (one lane tile a page at 128-wide heads,
        two at Qwen3-Next's 256) and at a bf16 pool of 8 combined rows a
        token; general at the float32 toy shapes, at an odd number of kv
        heads, at narrow heads.  ``pairs_per_pass`` / ``passes_per_chunk``
        (PR 37) say which pass the program got: 1 / 4 at Mistral's pool,
        1 / 1 at Qwen3-Next's, 4 / 4 at Olmo-Hybrid's 30 heads stored in
        32, 1 wherever the load is general.  ``row_bytes`` / ``read_bytes``
        (PR 59): what a token takes in the pool and what the model reads
        of it — equal but for Olmo's two padded heads; ``lane_heads`` 1
        (heads along the lanes: ``test_kv_row_forms.py``)."""
        from deepspeed_tpu.telemetry import get_tracer

        def layout(q, pages, kvl, pt, KV):
            n = len(get_tracer().records())
            decode_paged_attention(q, pages, kvl, pt, num_kv_heads=KV,
                                   interpret=True)
            recs = [r.attrs for r in get_tracer().records()[n:]
                    if r.name == "attn/decode_layout"]
            assert len(recs) == 1
            return recs[0]

        q, pages, kvl, pt = self._cell_case(34, [70], poison=False)
        assert layout(q, pages, kvl, pt, 8) == dict(
            load="strided", P=8, dtype="bfloat16", kv_heads=8,
            stored_kv_heads=8, group=4, lane_tiles=1, pairs_per_pass=1,
            passes_per_chunk=4, lane_heads=1, row_bytes=4096, read_bytes=4096)
        q, pages, kvl, pt = self._cell_case(34, [70], poison=False,
                                            geom=self.QWEN)
        assert layout(q, pages, kvl, pt, 2) == dict(
            load="strided", P=8, dtype="bfloat16", kv_heads=2,
            stored_kv_heads=2, group=8, lane_tiles=2, pairs_per_pass=1,
            passes_per_chunk=1, lane_heads=1, row_bytes=2048, read_bytes=2048)
        q, pages, kvl, pt = self._cell_case(34, [70], poison=False,
                                            geom=self.OLMO30)
        assert layout(q, pages, kvl, pt, 30) == dict(
            load="strided", P=2, dtype="bfloat16", kv_heads=30,
            stored_kv_heads=32, group=1, lane_tiles=1, pairs_per_pass=4,
            passes_per_chunk=4, lane_heads=1, row_bytes=16384,
            read_bytes=15360)
        rng = np.random.default_rng(35)
        toy = _decode_case(rng, [9, 5], 1, 2, 16, 4, 3)
        assert layout(*toy, 1) == dict(
            load="general", P=3, dtype="float32", kv_heads=1,
            stored_kv_heads=1, group=2, lane_tiles=1, pairs_per_pass=1,
            passes_per_chunk=1, lane_heads=1, row_bytes=128, read_bytes=128)
        q4, p4, kvl4, pt4 = _decode_case(rng, [40], 4, 2, 128, 16, 4)
        rec = layout(q4.astype(jnp.bfloat16), p4.astype(jnp.bfloat16),
                     kvl4, pt4, 4)
        assert (rec["load"], rec["dtype"], rec["lane_tiles"]) == (
            "strided", "bfloat16", 1)
        q3, p3, kvl3, pt3 = _decode_case(rng, [40], 3, 2, 256, 16, 4)
        rec = layout(q3.astype(jnp.bfloat16), p3.astype(jnp.bfloat16),
                     kvl3, pt3, 3)
        assert (rec["load"], rec["lane_tiles"]) == ("general", 1)
        q5, p5, kvl5, pt5 = _decode_case(rng, [40], 8, 2, 64, 16, 4)
        assert layout(q5.astype(jnp.bfloat16), p5.astype(jnp.bfloat16),
                      kvl5, pt5, 8)["load"] == "general"

    @pytest.mark.parametrize("KV,G", [(6, 2), (1, 4)])
    def test_bf16_pool_general_load_parity(self, KV, G):
        """A bf16 pool the strided load cannot take (12 combined rows a
        token are padded to 16 in VMEM; with one kv head a word row is K
        beside V, not a pair of heads) runs the general load with bf16
        operands."""
        rng = np.random.default_rng(36)
        hd, ps, NB = 128, 16, 6
        ctx = [33, 0, 80]
        q, pages, kvl, pt = _decode_case(rng, ctx, KV, G, hd, ps, NB)
        self._assert_matches_dense(
            q.astype(jnp.bfloat16), pages.astype(jnp.bfloat16), kvl, pt, KV,
            pages_per_chunk=2)

    def test_dispatch_seam(self):
        """decode_attention(impl=...) forces either lowering explicitly."""
        rng = np.random.default_rng(25)
        q, pages, kvl, pt = _decode_case(rng, [12], 1, 2, 16, 4, 4)
        a = decode_attention(q, pages, kvl, pt, num_kv_heads=1, impl="dense")
        b = decode_attend_dense(q, pages, kvl, pt, num_kv_heads=1)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestOnDeviceSampling:
    def _logits(self):
        return jax.random.normal(jax.random.PRNGKey(7), (5, 64), jnp.float32)

    def test_greedy_is_argmax(self):
        logits = self._logits()
        toks = sample_tokens(logits, None, temperature=0.0)
        np.testing.assert_array_equal(np.asarray(toks),
                                      np.asarray(jnp.argmax(logits, -1)))

    def test_fixed_key_is_deterministic(self):
        logits = self._logits()
        key = jax.random.PRNGKey(42)
        a = sample_tokens(logits, key, temperature=0.8, top_k=8)
        b = sample_tokens(logits, key, temperature=0.8, top_k=8)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        c = sample_tokens(logits, jax.random.PRNGKey(43), temperature=0.8,
                          top_k=8)
        assert not np.array_equal(np.asarray(a), np.asarray(c))

    def test_top_k_restricts_support(self):
        logits = self._logits()
        k = 4
        top = np.asarray(jax.lax.top_k(logits, k)[1])
        for seed in range(8):
            toks = np.asarray(sample_tokens(
                logits, jax.random.PRNGKey(seed), temperature=1.5, top_k=k))
            for row, t in enumerate(toks):
                assert t in top[row], f"token {t} outside top-{k} of row {row}"

    def test_engine_decode_fixed_rng_deterministic(self, tiny_lm):
        """Two fresh engines, same params, same explicit window rng → the
        SAME sampled token stream (on-device sampling determinism)."""
        model, params = tiny_lm
        toks = []
        for _ in range(2):
            eng = _engine(model, params, attn_impl="gather")
            logits = eng.put([0], [[3, 5, 7, 11]])
            seed = int(jnp.argmax(logits[0]))
            out = eng.decode_batch([0], [seed], steps=6, temperature=0.9,
                                   top_k=4, rng=jax.random.PRNGKey(123))
            toks.append(np.asarray(out))
        np.testing.assert_array_equal(toks[0], toks[1])


@pytest.fixture(scope="module")
def tiny_lm():
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    cfg = TransformerConfig.tiny(use_flash=False)
    model = CausalLM(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def _engine(model, params, **kw):
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2,
        RaggedInferenceEngineConfig,
    )

    base = dict(max_tokens=16, max_seqs=4, max_ctx=64, block_size=8,
                dtype=jnp.float32, block_q=16, pages_per_chunk=2)
    base.update(kw)
    return InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
        **base))


class TestEngineDecodeParity:
    def test_paged_vs_gather_greedy_decode(self, tiny_lm):
        """End-to-end fused decode: both attention impls generate the same
        greedy token stream from the same prefill."""
        model, params = tiny_lm
        streams = {}
        for impl in ("paged", "gather"):
            eng = _engine(model, params, attn_impl=impl)
            logits = eng.put([0, 1], [[3, 5, 7, 11, 13], [17, 19]])
            seeds = [int(t) for t in np.asarray(jnp.argmax(logits, -1))]
            toks = eng.decode_batch([0, 1], seeds, steps=5)
            streams[impl] = np.asarray(toks)
        np.testing.assert_array_equal(streams["paged"], streams["gather"])

    def test_decode_window_chaining_matches_stepwise(self, tiny_lm):
        """Two chained fused windows (the second resuming from device-
        resident metadata) reproduce the stepwise put() token stream."""
        model, params = tiny_lm
        prompt = [3, 5, 7, 11]

        eng = _engine(model, params, attn_impl="gather")
        logits = eng.put([0], [prompt])
        tok = int(jnp.argmax(logits[0]))
        stepwise = []
        for _ in range(4):
            logits = eng.put([0], [[tok]])
            tok = int(jnp.argmax(logits[0]))
            stepwise.append(tok)

        # window sizes chosen so window 2 fits the block allocated by
        # window 1 (4 prompt + 2 + 2 ≤ block_size 8): resume requires an
        # unchanged block table
        eng2 = _engine(model, params, attn_impl="gather")
        logits = eng2.put([0], [prompt])
        seed = int(jnp.argmax(logits[0]))
        w1 = eng2.decode_batch([0], [seed], steps=2)
        w2 = eng2.decode_batch([0], [int(w1[-1, 0])], steps=2)
        assert eng2.decode_resume_hits == 1, \
            "second window must resume from device-resident metadata"
        fused = [int(t) for t in np.concatenate([w1[:, 0], w2[:, 0]])]
        assert fused == stepwise
        # a host put() invalidates the cached device metadata (the cache
        # changed shape under it): the next window must NOT resume
        eng2.put([1], [[2, 4]])                   # unrelated admission
        eng2.decode_batch([0], [int(w2[-1, 0])], steps=2)
        assert eng2.decode_resume_hits == 1

    def test_undrained_growth_chain_uses_device_seeds(self, tiny_lm):
        """Async chaining (dispatch window 2 BEFORE draining window 1)
        across a block-growth boundary cannot resume — and the caller's
        seeds are unknowable then, so the repack must read the true next
        tokens from the advanced device metadata, not pack the advisory
        seeds into the stream."""
        model, params = tiny_lm
        prompt = [3, 5, 7, 11]
        # oracle: the same two windows chained with drains in between
        # (window 2 grows a block: 4 prompt + 2 + 4 > block_size 8)
        eng = _engine(model, params, attn_impl="gather")
        logits = eng.put([0], [prompt])
        seed = int(jnp.argmax(logits[0]))
        w1 = eng.decode_batch([0], [seed], steps=2)
        w2 = eng.decode_batch([0], [int(w1[-1, 0])], steps=4)
        expect = [int(t) for t in np.concatenate([w1[:, 0], w2[:, 0]])]

        eng2 = _engine(model, params, attn_impl="gather")
        logits = eng2.put([0], [prompt])
        a1 = eng2.decode_batch_async([0], [seed], steps=2)
        # window 1 is NOT drained: pass a deliberately wrong advisory seed
        a2 = eng2.decode_batch_async([0], [0], steps=4)
        assert eng2.decode_resume_hits == 0
        got = [int(t) for t in np.concatenate(
            [a1.tokens()[:, 0], a2.tokens()[:, 0]])]
        assert got == expect

    def test_drained_seed_override_forces_repack(self, tiny_lm):
        """Once a window is drained its last tokens are host-known, so a
        caller-supplied seed that DIFFERS from the cached stream (stop-token
        rewrite, guided decoding) must be honored via a repack, not silently
        dropped by the resume path."""
        model, params = tiny_lm
        prompt = [3, 5, 7, 11]

        eng = _engine(model, params, attn_impl="gather")
        logits = eng.put([0], [prompt])
        seed = int(jnp.argmax(logits[0]))
        w1 = eng.decode_batch([0], [seed], steps=2)
        override = (int(w1[-1, 0]) + 1) % model.config.vocab_size
        w2 = eng.decode_batch([0], [override], steps=2)
        assert eng.decode_resume_hits == 0, \
            "a mismatching seed must not resume device-side"

        # oracle: the same override decoded stepwise from the same prefix
        eng2 = _engine(model, params, attn_impl="gather")
        eng2.put([0], [prompt])
        eng2.decode_batch([0], [seed], steps=2)
        tok, expect = override, []
        for _ in range(2):
            lg = eng2.put([0], [[tok]])
            tok = int(jnp.argmax(lg[0]))
            expect.append(tok)
        assert [int(t) for t in w2[:, 0]] == expect


class TestDecodeRoofline:
    def test_window_publishes_serving_gauges(self, tiny_lm, tmp_path):
        """A drained decode window under installed telemetry publishes the
        serving/* gauges and `dstpu-telemetry` renders the per-kernel
        decode HBM %-of-peak table (the roofline acceptance probe)."""
        from deepspeed_tpu.telemetry import Telemetry, set_telemetry
        from deepspeed_tpu.telemetry.summary import (
            format_summary,
            serving_summary,
        )

        model, params = tiny_lm
        tel = Telemetry(output_dir=str(tmp_path))
        set_telemetry(tel)
        try:
            eng = _engine(model, params, attn_impl="gather")
            logits = eng.put([0], [[3, 5, 7, 11]])
            w1 = eng.decode_batch([0], [int(jnp.argmax(logits[0]))], steps=4)
            # window 1 compiled the decode loop: its wall time is XLA
            # compile, so it must be flagged and kept OFF the gauges
            assert eng.last_decode_roofline["compile_polluted"]
            assert "serving/decode_tok_per_s" not in {
                m["name"] for m in tel.metrics.snapshot()}
            eng.decode_batch([0], [int(w1[-1, 0])], steps=4)
            rep = eng.last_decode_roofline
            assert rep is not None and rep["steps"] == 4
            assert not rep["compile_polluted"]
            assert set(rep["kernels"]) == {"decode_attention", "kv_append",
                                           "param_stream"}
            srv = serving_summary(tel.metrics.snapshot())
            assert srv["decode_tok_per_s"] > 0
            assert "decode_hbm_pct_peak" in srv
            assert set(srv["kernels"]) == set(rep["kernels"])
            rendered = format_summary({
                "run_dir": "x", "wall_s": 1.0, "counts": {},
                "sources": {"events": "in-memory", "trace": None},
                "step_breakdown": [], "comm": [], "overlap": {},
                "serving": srv, "profile": None, "xprof": {}, "memory": {},
                "incidents": {"event_counts": {}, "checkpoints": [],
                              "incidents": []},
                "events_total": 0})
            assert "serving (decode HBM roofline)" in rendered
            assert "decode_attention" in rendered and "%peak" in rendered
        finally:
            set_telemetry(None)


class TestCompileCacheBucketing:
    def test_bucket_for_rounding(self, tiny_lm):
        model, params = tiny_lm
        eng = _engine(model, params, max_tokens=64, max_seqs=8,
                      min_token_bucket=16)
        # put() buckets tokens only (seq padding is free for prefill)
        assert eng.bucket_for(5, 1) == (16, 8)
        assert eng.bucket_for(16, 2) == (16, 8)
        assert eng.bucket_for(17, 3) == (32, 8)
        assert eng.bucket_for(1000, 100) == (64, 8)   # clamped to budget
        # decode windows bucket the seq axis (flat tokens == seqs there)
        assert eng._seq_bucket(3) == 4
        assert eng._seq_bucket(100) == 8
        eng_off = _engine(model, params, max_tokens=64, max_seqs=8,
                          bucket_tokens=False)
        assert eng_off.bucket_for(5, 1) == (64, 8)
        assert eng_off._seq_bucket(3) == 8

    def test_mixed_schedule_one_compile_per_bucket(self, tiny_lm):
        """Acceptance probe: a mixed prefill/decode schedule with variable
        SplitFuse chunk sizes shows exactly ONE compile per (tokens, seqs)
        bucket and per decode-loop shape."""
        model, params = tiny_lm
        eng = _engine(model, params, max_tokens=32)
        logits = eng.put([0, 1], [[3, 5, 7, 11], [2, 4]])   # 6 tok → (16, 4)
        seeds = [int(t) for t in np.asarray(jnp.argmax(logits, -1))]
        toks = eng.decode_batch([0, 1], seeds, steps=2)
        toks = eng.decode_batch([0, 1], [int(t) for t in toks[-1]], steps=2)
        eng.put([0], [[9] * 5])                             # 5 tok → (16, 4)
        eng.put([0, 1], [[4] * 7, [4] * 7])                 # 14 tok → (16, 4)
        toks2 = eng.decode_batch([0, 1], [3, 4], steps=2)
        assert toks2 is not None
        assert eng.trace_counts[(16, 4)] == 1, \
            "SplitFuse chunk sizes within one bucket must not retrace"
        eng.put([0], [[6] * 20])                            # 20 tok → (32, 4)
        for key, count in eng.trace_counts.items():
            assert count == 1, f"bucket {key} retraced: {count} traces"
        assert (32, 4) in eng.trace_counts
        # decode windows of the same shape share ONE compiled loop
        decode_keys = [k for k in eng.trace_counts if k[0] == "decode"]
        assert len(decode_keys) == 1
