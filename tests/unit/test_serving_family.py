"""The seam between a model and the paged serving path (``models/serving.py``):
the engine serves whatever model object says what family it is, and asks the
family — not the config's class — for the row a cached token holds.

``RenamedLM`` is a fourth family written HERE: the llama layer under other
parameter names, with its own dense forward.  It imports nothing of
``inference/v2`` but the engine and its config; serving it edits nothing there.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (
    InferenceEngineV2,
    RaggedInferenceEngineConfig,
)
from deepspeed_tpu.models.families import ArchConfig, UniversalCausalLM
from deepspeed_tpu.models.nemotron_h import NemotronHConfig, NemotronHLM
from deepspeed_tpu.models.olmo_hybrid import OlmoHybridConfig, OlmoHybridLM
from deepspeed_tpu.models.phi4_flash import Phi4FlashConfig, Phi4FlashLM
from deepspeed_tpu.models.qwen3_next import Qwen3NextConfig, Qwen3NextLM
from deepspeed_tpu.models.serving import (IndexKey, KVRow, LayerStack,
                                          ServingFamily)
from deepspeed_tpu.models.transformer import (
    CausalLM,
    TransformerConfig,
    apply_rope_flat,
    rms_norm,
    rope_at,
)
from deepspeed_tpu.models.xing4 import Xing4Config, Xing4LM

pytestmark = pytest.mark.inference


# --------------------------------------------------------------------- #
# A family the serving path has never heard of
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class RenamedConfig:
    vocab: int = 89
    width: int = 32
    ffn: int = 48
    depth: int = 3
    heads: int = 4
    kv_heads: int = 2
    theta: float = 10000.0
    eps: float = 1e-5


class RenamedLM:
    def __init__(self, cfg: RenamedConfig):
        self.config = cfg

    def init_params(self, key, dtype=jnp.float32):
        c = self.config
        D, kv = c.width, c.kv_heads * (c.width // c.heads)
        keys = iter(jax.random.split(key, 9))

        def w(*shape):
            return (jax.random.normal(next(keys), shape)
                    / math.sqrt(shape[-2])).astype(dtype)

        blocks = {n: w(c.depth, *s) for n, s in dict(
            wq=(D, D), wk=(D, kv), wv=(D, kv), wo=(D, D), wg=(D, c.ffn),
            wu=(D, c.ffn), wd=(c.ffn, D)).items()}
        blocks["n1"] = blocks["n2"] = jnp.ones((c.depth, D), dtype)
        return {"wte": w(c.vocab, D), "blocks": blocks,
                "nf": jnp.ones((D,), dtype), "out": w(D, c.vocab)}

    def _qkv(self, x, bp, cos, sin):
        c, T = self.config, x.shape[0]
        h = rms_norm(x, bp["n1"], c.eps)
        q, k, v = ((h @ bp[w]).reshape(T, n, -1) for w, n in
                   (("wq", c.heads), ("wk", c.kv_heads), ("wv", c.kv_heads)))
        return apply_rope_flat(q, cos, sin), apply_rope_flat(k, cos, sin), v

    def _rest(self, x, o, bp):
        x = x + o.reshape(x.shape[0], -1) @ bp["wo"]
        h = rms_norm(x, bp["n2"], self.config.eps)
        return x + (jax.nn.silu(h @ bp["wg"]) * (h @ bp["wu"])) @ bp["wd"]

    def __call__(self, params, tokens):
        """The dense forward of ONE sequence [S] → logits [S, V]: plain
        causal attention, no cache."""
        c = self.config
        S, hd = tokens.shape[0], c.width // c.heads
        cos, sin = rope_at(jnp.arange(S), hd, c.theta)
        x = params["wte"][tokens]
        for i in range(c.depth):
            bp = jax.tree.map(lambda a: a[i], params["blocks"])
            q, k, v = self._qkv(x, bp, cos, sin)
            k = jnp.repeat(k, c.heads // c.kv_heads, axis=1)
            v = jnp.repeat(v, c.heads // c.kv_heads, axis=1)
            s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
            s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -1e30)
            o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
            x = self._rest(x, o, bp)
        return rms_norm(x, params["nf"], c.eps) @ params["out"]

    def serving_family(self) -> ServingFamily:
        c = self.config
        hd = c.width // c.heads

        def embed(params, ids, pos, valid):
            return params["wte"][ids], rope_at(pos, hd, c.theta)

        def layer(x, bp, l_idx, cache, ctx):
            q, k, v = self._qkv(x, bp, *ctx)
            o = cache(q, k, v, scale=1.0 / math.sqrt(hd)).astype(x.dtype)
            return self._rest(x, o, bp)

        def stacks(params):
            yield LayerStack(params["blocks"], range(c.depth), layer)

        def head(params, x, pick):
            return pick(rms_norm(x, params["nf"], c.eps)) @ params["out"]

        return ServingFamily(num_layers=c.depth, num_heads=c.heads,
                             row=KVRow(c.kv_heads, hd), embed=embed,
                             stacks=stacks, head=head)


def _engine(model, params, impl="paged", **kw):
    kw = {**dict(max_tokens=8, max_seqs=2, max_ctx=64, block_size=8,
                 dtype=jnp.float32, attn_impl=impl), **kw}
    return InferenceEngineV2(model, params, RaggedInferenceEngineConfig(**kw))


@pytest.mark.parametrize("impl", ["paged", "gather"])
def test_a_family_defined_in_the_test_is_served(impl):
    """Split prefill, a put() decode step and a fused decode window of a
    family ``inference/v2`` has no line about, against its dense forward."""
    model = RenamedLM(RenamedConfig())
    params = model.init_params(jax.random.PRNGKey(0))
    eng = _engine(model, params, impl)
    prompt = np.random.default_rng(0).integers(1, 88, size=13).tolist()
    for i in range(0, len(prompt), 8):
        logits = eng.put([0], [prompt[i:i + 8]])
    np.testing.assert_allclose(
        np.asarray(logits[0]),
        np.asarray(model(params, jnp.asarray(prompt))[-1]),
        atol=2e-4, rtol=2e-4)

    toks = prompt + [int(jnp.argmax(logits[0]))]
    logits = eng.put([0], [toks[-1:]])
    np.testing.assert_allclose(
        np.asarray(logits[0]),
        np.asarray(model(params, jnp.asarray(toks))[-1]),
        atol=2e-4, rtol=2e-4)

    # the fused window's greedy tokens are the dense forward's greedy chain
    seed = int(jnp.argmax(logits[0]))
    window = eng.decode_batch([0], [seed], 3)[:, 0].tolist()
    chain = toks + [seed]
    for tok in window:
        want = int(jnp.argmax(model(params, jnp.asarray(chain))[-1]))
        assert tok == want
        chain.append(tok)
    assert all(n == 1 for n in eng.trace_counts.values())


def test_a_model_without_a_family_is_refused_by_name():
    class NoFamilyLM:
        config = TransformerConfig.tiny()

    with pytest.raises(NotImplementedError,
                       match=r"ragged serving needs .*NoFamilyLM"):
        InferenceEngineV2(NoFamilyLM(), {})


# --------------------------------------------------------------------- #
# The pool is what the family's row says
# --------------------------------------------------------------------- #
_UNIVERSAL = dict(vocab_size=97, hidden_size=32, intermediate_size=64,
                  num_layers=2, num_heads=4, max_seq_len=128,
                  norm="layernorm", mlp="gelu")
#: name → (model, one token's shape in a page, the bf16 bytes attention reads
#: of it: K and V of every kv head, or the 40 real values of a 128-wide row)
CASES = {
    "llama": (lambda: CausalLM(TransformerConfig.tiny(use_flash=False)),
              (4, 16), 128),
    "mixtral": (lambda: CausalLM(TransformerConfig.tiny_moe(use_flash=False)),
                (4, 16), 128),
    "gpt2": (lambda: UniversalCausalLM(ArchConfig(
        **_UNIVERSAL, num_kv_heads=4, pos="learned")), (8, 8), 128),
    "falcon": (lambda: UniversalCausalLM(ArchConfig(
        **_UNIVERSAL, num_kv_heads=1, pos="rope", parallel_attn=True,
        qkv_bias=False, out_bias=False)), (2, 8), 32),
    "xing4": (lambda: Xing4LM(Xing4Config.tiny()), (128,), 80),
    # 6 heads of 16 STORED in 8 (a page's rows tile): attention reads the
    # model's 6; its 6 linear layers keep a state, 2 of 8 layers own pages
    "olmo_hybrid": (lambda: OlmoHybridLM(OlmoHybridConfig.tiny()),
                    (16, 16), 384),
    # 2 K/V heads of 16 in its ONE attention layer of 11; its 5 Mamba-2
    # layers keep a state, its 5 expert layers nothing
    "nemotron_h": (lambda: NemotronHLM(NemotronHConfig.tiny()), (4, 16), 128),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_pool_has_the_row_the_family_says(name):
    """The pool's shape and dtype, and the bytes per cached token that
    ``last_decode_roofline`` reports after one fused window, from the
    family's row alone."""
    make, token_shape, token_bytes = CASES[name]
    model = make()
    fam = model.serving_family()
    assert fam.row.token_shape == token_shape
    eng = _engine(model, model.init_params(jax.random.PRNGKey(0)),
                  dtype=jnp.bfloat16, num_blocks=6)
    assert eng.kv.pages.shape == (fam.page_layers * 6 + 1, 8) + token_shape
    assert eng.kv.pages.dtype == jnp.bfloat16
    assert eng.latent_kv == fam.row.latent == (name == "xing4")
    assert (eng.state_pool is not None) == (fam.state is not None) \
        == (name in ("olmo_hybrid", "nemotron_h"))

    logits = eng.put([0, 1], [[3, 5, 7], [11, 13, 17, 19, 23]])
    window = eng.decode_batch_async(
        [0, 1], [int(t) for t in jnp.argmax(logits, axis=-1)], 2)
    window.tokens()
    assert (window.moe_pairs is not None) == (fam.counts is not None)
    # 2 sequences x 2 steps append one row a layer each
    appended = eng.last_decode_roofline["kernels"]["kv_append"]["bytes"]
    assert appended / (2 * 2 * fam.page_layers) == token_bytes


# --------------------------------------------------------------------- #
# A family WITH recurrent state, written here
# --------------------------------------------------------------------- #
class HybridLM(RenamedLM):
    """``RenamedLM`` with a gated delta-rule layer in front of every
    attention layer: a period of two, the first keeping a per-sequence
    state (``GatedDeltaState``) instead of cached rows.  Its dense forward
    runs the recurrence token by token, no slot, no pool."""

    HK, HV, DK, DV, K = 1, 2, 8, 8, 3

    def init_params(self, key, dtype=jnp.float32):
        c = self.config
        params = super().init_params(key, dtype)
        C = 2 * self.HK * self.DK + self.HV * self.DV
        keys = iter(jax.random.split(jax.random.fold_in(key, 1), 6))
        n = lambda *s: jax.random.normal(next(keys), s).astype(dtype)  # noqa: E731
        params["delta"] = {
            "mix": n(c.depth, c.width, C) / math.sqrt(c.width),
            "gates": n(c.depth, c.width, 2 * self.HV) / math.sqrt(c.width),
            "conv": n(c.depth, self.K, C) / math.sqrt(self.K),
            "out": n(c.depth, self.HV * self.DV, c.width) / 4.0}
        return params

    def _delta_inputs(self, x, dp):
        h = rms_norm(x, jnp.ones((x.shape[-1],), x.dtype), self.config.eps)
        gates = (h @ dp["gates"]).astype(jnp.float32)
        return (h @ dp["mix"], -jax.nn.softplus(gates[:, :self.HV]),
                jax.nn.sigmoid(gates[:, self.HV:]))

    def __call__(self, params, tokens):
        c = self.config
        S, hd = tokens.shape[0], c.width // c.heads
        cos, sin = rope_at(jnp.arange(S), hd, c.theta)
        x = params["wte"][tokens]
        Kd = self.HK * self.DK
        for i in range(c.depth):
            dp = jax.tree.map(lambda a: a[i], params["delta"])
            mixed, g, beta = self._delta_inputs(x, dp)
            padded = jnp.concatenate(
                [jnp.zeros((self.K - 1, mixed.shape[1])), mixed])
            u = jax.nn.silu(sum(dp["conv"][j][None] * padded[j:j + S]
                                for j in range(self.K)))
            unit = lambda a: a / jnp.sqrt(  # noqa: E731
                jnp.sum(a * a, -1, keepdims=True) + 1e-6)
            q = unit(u[:, :Kd].reshape(S, self.HK, self.DK)) \
                / math.sqrt(self.DK)
            k = unit(u[:, Kd:2 * Kd].reshape(S, self.HK, self.DK))
            q, k = (jnp.repeat(a, self.HV // self.HK, axis=1) for a in (q, k))
            v = u[:, 2 * Kd:].reshape(S, self.HV, self.DV)
            state, outs = jnp.zeros((self.HV, self.DK, self.DV)), []
            for t in range(S):
                state = state * jnp.exp(g[t])[:, None, None]
                delta = (v[t] - jnp.einsum("hkv,hk->hv", state, k[t])) \
                    * beta[t][:, None]
                state = state + k[t][:, :, None] * delta[:, None, :]
                outs.append(jnp.einsum("hkv,hk->hv", state, q[t]))
            x = x + jnp.stack(outs).reshape(S, -1) @ dp["out"]
            bp = jax.tree.map(lambda a: a[i], params["blocks"])
            q, k, v = self._qkv(x, bp, cos, sin)
            k = jnp.repeat(k, c.heads // c.kv_heads, axis=1)
            v = jnp.repeat(v, c.heads // c.kv_heads, axis=1)
            s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
            s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -1e30)
            o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
            x = self._rest(x, o, bp)
        return rms_norm(x, params["nf"], c.eps) @ params["out"]

    def serving_family(self) -> ServingFamily:
        from deepspeed_tpu.models.serving import GatedDeltaState

        c = self.config
        hd = c.width // c.heads
        base = super().serving_family()

        def period(x, lp, p_idx, cache, ctx, state):
            bp, dp = lp
            mixed, g, beta = self._delta_inputs(x, dp)
            o = state(p_idx, mixed, g, beta, dp["conv"])
            x = x + o.reshape(x.shape[0], -1).astype(x.dtype) @ dp["out"]
            q, k, v = self._qkv(x, bp, *ctx)
            o = cache(q, k, v, scale=1.0 / math.sqrt(hd)).astype(x.dtype)
            return self._rest(x, o, bp)

        def stacks(params):
            yield LayerStack((params["blocks"], params["delta"]),
                             range(c.depth), period)

        return dataclasses.replace(
            base, num_layers=2 * c.depth, stacks=stacks,
            page_layer_count=c.depth,
            state=GatedDeltaState(num_layers=c.depth, num_heads=self.HV,
                                  num_key_heads=self.HK, key_dim=self.DK,
                                  value_dim=self.DV, conv_kernel=self.K))


@pytest.mark.parametrize("impl", ["paged", "gather"])
def test_a_family_with_recurrent_state_is_served(impl):
    """Two sequences through split prefill, put() steps and a fused window:
    each continues from its own slot of the state pool, which the engine
    sized from the family's descriptor alone; a flushed sequence's slot goes
    to the next one, which starts from zeros."""
    model = HybridLM(RenamedConfig(depth=2))
    params = model.init_params(jax.random.PRNGKey(0))
    eng = _engine(model, params, impl, max_tokens=16)
    assert eng.kv.pages.shape[0] == 2 * eng.kv.config.num_blocks + 1
    state, carry = eng.state_pool.arrays
    assert state.shape == (2 * 2 + 1, 2, 8, 8) and state.dtype == jnp.float32
    assert carry.shape == (2 * 2 + 1, 2, 32)
    rng = np.random.default_rng(0)
    a, b = (rng.integers(1, 88, size=n).tolist() for n in (13, 9))

    def same(got, seq):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(model(params, jnp.asarray(seq))[-1]),
            atol=3e-4, rtol=3e-4)

    eng.put([0], [a[:8]])
    logits = eng.put([0, 1], [a[8:], b])        # chunks of two sequences
    same(logits[0], a)
    same(logits[1], b)
    a, b = a + [int(jnp.argmax(logits[0]))], b + [int(jnp.argmax(logits[1]))]
    logits = eng.put([1, 0], [b[-1:], a[-1:]])
    same(logits[0], b)
    same(logits[1], a)
    seeds = [int(jnp.argmax(logits[1])), int(jnp.argmax(logits[0]))]
    window = eng.decode_batch([0, 1], seeds, 3)
    for col, chain in enumerate((a + seeds[:1], b + seeds[1:])):
        for tok in window[:, col].tolist():
            assert tok == int(jnp.argmax(model(params, jnp.asarray(chain))[-1]))
            chain.append(tok)
    slot = eng.state_manager.get_sequence(1).slot
    eng.flush([1])
    fresh = rng.integers(1, 88, size=11).tolist()
    same(eng.put([2], [fresh])[0], fresh)
    assert eng.state_manager.get_sequence(2).slot == slot


# --------------------------------------------------------------------- #
# A family with a SELECTIVE-SCAN state and WINDOW layers, written here
# --------------------------------------------------------------------- #
class WindowedLM(RenamedLM):
    """``RenamedLM`` without rotary (a ring of rows forgets their order)
    whose every period is a selective-scan mixer (``SelectiveScanState``),
    an attention layer over the last ``W`` tokens (``WindowRing``: rows in
    the sequence's slot, no page layer) and the full-attention block.  Its
    dense forward runs the scan token by token and a banded mask."""

    C, N, K, W = 16, 4, 3, 8

    def init_params(self, key, dtype=jnp.float32):
        c = self.config
        params = super().init_params(key, dtype)
        D, kv = c.width, c.kv_heads * (c.width // c.heads)
        keys = iter(jax.random.split(jax.random.fold_in(key, 2), 12))
        n = lambda *s: jax.random.normal(next(keys), s).astype(dtype)  # noqa: E731
        params["scan"] = {
            "inp": n(c.depth, D, self.C) / math.sqrt(D),
            "conv": n(c.depth, self.K, self.C) / math.sqrt(self.K),
            "bias": n(c.depth, self.C) / 3,
            "proj": n(c.depth, self.C, self.C + 2 * self.N) / 4,
            "A": -jax.random.uniform(next(keys), (c.depth, self.N, self.C),
                                     minval=0.05, maxval=1.0).astype(dtype),
            "skip": n(c.depth, self.C),
            "out": n(c.depth, self.C, D) / 4}
        params["win"] = {"wq": n(c.depth, D, D) / math.sqrt(D),
                         "wk": n(c.depth, D, kv) / math.sqrt(D),
                         "wv": n(c.depth, D, kv) / math.sqrt(D),
                         "wo": n(c.depth, D, D) / math.sqrt(D)}
        return params

    def _scan_proj(self, sp):
        def proj(x):
            r = x @ sp["proj"]
            return (jax.nn.softplus(r[:, :self.C]),
                    r[:, self.C:self.C + self.N], r[:, self.C + self.N:])
        return proj

    def _win_qkv(self, x, wp):
        c, T = self.config, x.shape[0]
        h = rms_norm(x, jnp.ones((c.width,), x.dtype), c.eps)
        return ((h @ wp[w]).reshape(T, n, -1) for w, n in
                (("wq", c.heads), ("wk", c.kv_heads), ("wv", c.kv_heads)))

    def __call__(self, params, tokens):
        c = self.config
        S, hd = tokens.shape[0], c.width // c.heads
        cos, sin = rope_at(jnp.zeros((S,), jnp.int32), hd, c.theta)
        x = params["wte"][tokens]
        causal = jnp.tril(jnp.ones((S, S), bool))
        band = causal & ~jnp.tril(jnp.ones((S, S), bool), -self.W)

        def attend(q, k, v, mask):
            k = jnp.repeat(k, c.heads // c.kv_heads, axis=1)
            v = jnp.repeat(v, c.heads // c.kv_heads, axis=1)
            s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
            s = jnp.where(mask[None], s, -1e30)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

        for i in range(c.depth):
            sp = jax.tree.map(lambda a: a[i], params["scan"])
            u = x @ sp["inp"]
            padded = jnp.concatenate([jnp.zeros((self.K - 1, self.C)), u])
            xs = jax.nn.silu(sum(sp["conv"][j][None] * padded[j:j + S]
                                 for j in range(self.K)) + sp["bias"])
            delta, B, Cm = self._scan_proj(sp)(xs)
            state, ys = jnp.zeros((self.N, self.C)), []
            for t in range(S):
                state = jnp.exp(delta[t][None] * sp["A"]) * state \
                    + (delta[t] * xs[t])[None] * B[t][:, None]
                ys.append(jnp.sum(state * Cm[t][:, None], axis=0)
                          + sp["skip"] * xs[t])
            x = x + jnp.stack(ys) @ sp["out"]
            wp = jax.tree.map(lambda a: a[i], params["win"])
            q, k, v = self._win_qkv(x, wp)
            x = x + attend(q, k, v, band).reshape(S, -1) @ wp["wo"]
            bp = jax.tree.map(lambda a: a[i], params["blocks"])
            q, k, v = self._qkv(x, bp, cos, sin)
            x = self._rest(x, attend(q, k, v, causal), bp)
        return rms_norm(x, params["nf"], c.eps) @ params["out"]

    def serving_family(self) -> ServingFamily:
        from deepspeed_tpu.models.serving import (SelectiveScanState,
                                                  WindowRing)

        c = self.config
        hd = c.width // c.heads
        base = super().serving_family()
        scale = 1.0 / math.sqrt(hd)

        def embed(params, ids, pos, valid):
            return params["wte"][ids], rope_at(jnp.zeros_like(pos), hd,
                                               c.theta)

        def period(x, lp, p_idx, cache, ctx, state):
            bp, sp, wp = lp
            y = state(p_idx, x @ sp["inp"], sp["conv"], sp["bias"],
                      self._scan_proj(sp), sp["A"], sp["skip"])
            x = x + y.astype(x.dtype) @ sp["out"]
            q, k, v = self._win_qkv(x, wp)
            o = cache.window(p_idx)(q, k, v, scale=scale)
            x = x + o.reshape(x.shape[0], -1) @ wp["wo"]
            q, k, v = self._qkv(x, bp, *ctx)
            o = cache(q, k, v, scale=scale).astype(x.dtype)
            return self._rest(x, o, bp)

        def stacks(params):
            yield LayerStack((params["blocks"], params["scan"],
                              params["win"]), range(c.depth), period)

        return dataclasses.replace(
            base, num_layers=3 * c.depth, embed=embed, stacks=stacks,
            page_layer_count=c.depth,
            state=SelectiveScanState(num_layers=c.depth, channels=self.C,
                                     state_dim=self.N, conv_kernel=self.K),
            window=WindowRing(num_layers=c.depth, window=self.W, page=4))


@pytest.mark.parametrize("impl", ["paged", "gather"])
def test_a_family_with_a_scan_state_and_window_layers_is_served(impl):
    """A second state kind and a window, both named by a family written
    HERE: chunks that cross the window and wrap the ring, put() steps, a
    fused window, a reused slot — against the dense forward.  The engine
    sized the state pool and the rings from the family's descriptors alone,
    and a long sequence holds the rows a short one does."""
    model = WindowedLM(RenamedConfig(depth=2))
    params = model.init_params(jax.random.PRNGKey(0))
    eng = _engine(model, params, impl, max_tokens=16)
    assert eng.kv.pages.shape[0] == 2 * eng.kv.config.num_blocks + 1
    state, carry, ring = eng.state_pool.arrays
    assert state.shape == (2 * 2 + 1, 4, 16) and state.dtype == jnp.float32
    assert carry.shape == (2 * 2 + 1, 2, 16)
    assert ring.shape == (2 * 2 + 1, 8, 2 * 2, 8)
    rng = np.random.default_rng(0)
    a, b = (rng.integers(1, 88, size=n).tolist() for n in (29, 11))

    def same(got, seq):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(model(params, jnp.asarray(seq))[-1]),
            atol=3e-4, rtol=3e-4)

    eng.put([0], [a[:9]])                       # past the window already
    eng.put([0], [a[9:18]])                     # a chunk over a ring wrap
    logits = eng.put([0, 1], [a[18:], b[:5]])   # two sequences' chunks
    same(logits[0], a)
    same(logits[1], b[:5])
    logits = eng.put([1, 0], [b[5:], [int(jnp.argmax(logits[0]))]])
    a = a + [int(jnp.argmax(model(params, jnp.asarray(a))[-1]))]
    same(logits[0], b)
    same(logits[1], a)
    seeds = [int(jnp.argmax(logits[1])), int(jnp.argmax(logits[0]))]
    window = eng.decode_batch([0, 1], seeds, 3)
    for col, chain in enumerate((a + seeds[:1], b + seeds[1:])):
        for tok in window[:, col].tolist():
            assert tok == int(jnp.argmax(model(params, jnp.asarray(chain))[-1]))
            chain.append(tok)
    slot = eng.state_manager.get_sequence(1).slot
    eng.flush([1])
    fresh = rng.integers(1, 88, size=5).tolist()    # shorter than the window
    same(eng.put([2], [fresh])[0], fresh)
    assert eng.state_manager.get_sequence(2).slot == slot
    assert eng.state_pool.arrays[2].shape == ring.shape


#: the families that hold recurrent state: what re-reads, parks or ships
#: cached tokens is refused for each BY NAME, the same way
STATEFUL = {
    "hybrid_in_this_file": lambda: HybridLM(RenamedConfig(depth=2)),
    "qwen3_next": lambda: Qwen3NextLM(Qwen3NextConfig.tiny()),
    "olmo_hybrid": lambda: OlmoHybridLM(OlmoHybridConfig.tiny()),
    "windowed_in_this_file": lambda: WindowedLM(RenamedConfig(depth=2)),
    "phi4_flash": lambda: Phi4FlashLM(Phi4FlashConfig.tiny()),
    "nemotron_h": lambda: NemotronHLM(NemotronHConfig.tiny()),
}


@pytest.mark.parametrize("name", sorted(STATEFUL))
def test_a_family_with_state_refuses_what_a_state_cannot_do(name):
    from deepspeed_tpu.inference.v2 import kv_ship
    from deepspeed_tpu.inference.v2.model_runner import build_verify_step

    model = STATEFUL[name]()
    params = model.init_params(jax.random.PRNGKey(0))
    for kw, what in ((dict(prefix_cache=True), "prefix_cache"),
                     (dict(host_tier_mb=1.0), "host_tier_mb")):
        with pytest.raises(NotImplementedError,
                           match=what + r".* recurrent state"):
            _engine(model, params, **kw)
    eng = _engine(model, params, max_tokens=16)
    eng.put([0], [[3, 5, 7, 11]])
    with pytest.raises(NotImplementedError, match="recurrent state"):
        kv_ship.export_kv(eng, 0, [3, 5, 7, 11])
    with pytest.raises(NotImplementedError, match="recurrent state"):
        build_verify_step(model.serving_family(), max_q=8, num_blocks=4,
                          max_seqs=2, max_blocks=4, jit=False)(
            eng.params, eng._cache(), jnp.zeros((64,), jnp.int32))
    fam = model.serving_family()
    # the layers that keep a state or a ring are among those without pages
    held = fam.state.num_layers + (fam.window.num_layers if fam.window else 0)
    assert fam.page_layers < fam.num_layers \
        and held <= fam.num_layers - fam.page_layers
    # (a layer that is the experts alone holds neither: Nemotron-H's five)
    alone = 5 if name == "nemotron_h" else 0
    assert fam.window is not None \
        or held == fam.num_layers - fam.page_layers - alone


class PairedLM(RenamedLM):
    """``RenamedLM`` served two blocks a scan step: ONE layer body owns TWO
    page layers (``page_layer_count`` > ``num_layers``) and takes both from
    the handle it is given, ``cache.at``.  The dense forward is the
    parent's."""

    def serving_family(self) -> ServingFamily:
        c = self.config
        hd = c.width // c.heads
        base = super().serving_family()

        def pair(x, lp, l_idx, cache, ctx):
            for i in (0, 1):
                bp = jax.tree.map(lambda a: a[i], lp)
                q, k, v = self._qkv(x, bp, *ctx)
                o = cache.at(2 * l_idx + i)(
                    q, k, v, scale=1.0 / math.sqrt(hd)).astype(x.dtype)
                x = self._rest(x, o, bp)
            return x

        def stacks(params):
            yield LayerStack(
                jax.tree.map(lambda a: a.reshape((c.depth // 2, 2)
                                                 + a.shape[1:]),
                             params["blocks"]), range(c.depth // 2), pair)

        return dataclasses.replace(base, num_layers=c.depth // 2,
                                   stacks=stacks, page_layer_count=c.depth)


@pytest.mark.parametrize("impl", ["paged", "gather"])
def test_a_body_that_owns_two_page_layers_is_served(impl):
    """Split prefill, put() steps, a fused window and a grafted prefix: the
    pool holds ``page_layers`` = 2 x ``num_layers`` layers of blocks, both
    appends of a step land in it, and what counts blocks (the graft's
    copy-on-write, a shipment) walks every page layer."""
    from deepspeed_tpu.inference.v2 import kv_ship

    model = PairedLM(RenamedConfig(depth=4))
    params = model.init_params(jax.random.PRNGKey(0))
    fam = model.serving_family()
    assert (fam.num_layers, fam.page_layers) == (2, 4)
    eng = _engine(model, params, impl, max_tokens=16, prefix_cache=True)
    assert eng.kv.pages.shape[0] == 4 * eng.kv.config.num_blocks + 1
    rng = np.random.default_rng(0)
    a, b = (rng.integers(1, 88, size=n).tolist() for n in (13, 9))

    def same(got, seq):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(model(params, jnp.asarray(seq))[-1]),
            atol=3e-4, rtol=3e-4)

    eng.put([0], [a[:8]])
    logits = eng.put([0, 1], [a[8:], b])        # chunks of two sequences
    same(logits[0], a)
    same(logits[1], b)
    seeds = [int(jnp.argmax(logits[0])), int(jnp.argmax(logits[1]))]
    window = eng.decode_batch([0, 1], seeds, 3)
    for col, chain in enumerate((a + seeds[:1], b + seeds[1:])):
        for tok in window[:, col].tolist():
            assert tok == int(jnp.argmax(model(params, jnp.asarray(chain))[-1]))
            chain.append(tok)
    # a shipment carries every page layer's rows
    ship = kv_ship.export_kv(eng, 0, a, n_tokens=12)
    assert ship.rows.shape[:2] == (4, 12) and ship.num_layers == 4
    # a re-asked prompt is grafted: one full page shared, the partial second
    # copied in all four page layers before it is appended to
    eng.commit_prefix(0, a, allow_partial=True)
    eng.flush([0])
    again = a + rng.integers(1, 88, size=5).tolist()
    assert eng.graft_prefix(2, again) == 13
    same(eng.put([2], [again[13:]])[0], again)


class IndexedLM(RenamedLM):
    """``RenamedLM`` with a learned sparse-attention indexer written HERE: a
    cached token also holds an index key (``KVRow.index``), a query scores
    every cached key of its sequence and attends to the ``TOPK`` best.  The
    body hands the indexer's queries and weights to the cache handle and
    never sees a page table; the dense forward below masks to the set."""

    TOPK, IH, ID = 6, 2, 8

    def init_params(self, key, dtype=jnp.float32):
        c = self.config
        params = super().init_params(key, dtype)
        ks = jax.random.split(jax.random.fold_in(key, 7), 3)
        shapes = dict(iq=(c.width, self.IH * self.ID), ik=(c.width, self.ID),
                      iw=(c.width, self.IH))
        for k, (name, shape) in zip(ks, shapes.items()):
            params["blocks"][name] = (jax.random.normal(
                k, (c.depth,) + shape) / math.sqrt(c.width)).astype(dtype)
        return params

    def _index(self, x, bp):
        h = rms_norm(x, bp["n1"], self.config.eps)
        return ((h @ bp["iq"]).reshape(-1, self.IH, self.ID), h @ bp["ik"],
                h @ bp["iw"])

    def __call__(self, params, tokens):
        c = self.config
        S, hd = tokens.shape[0], c.width // c.heads
        cos, sin = rope_at(jnp.arange(S), hd, c.theta)
        causal = jnp.tril(jnp.ones((S, S), bool))
        x = params["wte"][tokens]
        for i in range(c.depth):
            bp = jax.tree.map(lambda a: a[i], params["blocks"])
            q, k, v = self._qkv(x, bp, cos, sin)
            qi, ki, w = self._index(x, bp)
            score = jnp.sum(w[..., None] * jax.nn.relu(
                jnp.einsum("tjd,sd->tjs", qi, ki)), axis=1)
            _, best = jax.lax.top_k(jnp.where(causal, score, -jnp.inf),
                                    min(self.TOPK, S))
            chosen = jnp.zeros((S, S), bool).at[
                jnp.arange(S)[:, None], best].set(True) & causal
            k = jnp.repeat(k, c.heads // c.kv_heads, axis=1)
            v = jnp.repeat(v, c.heads // c.kv_heads, axis=1)
            s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
            s = jnp.where(chosen[None], s, -1e30)
            o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
            x = self._rest(x, o, bp)
        return rms_norm(x, params["nf"], c.eps) @ params["out"]

    def serving_family(self) -> ServingFamily:
        c = self.config
        hd = c.width // c.heads
        base = super().serving_family()

        def layer(x, bp, l_idx, cache, ctx):
            q, k, v = self._qkv(x, bp, *ctx)
            qi, ki, w = self._index(x, bp)
            cache.append(k, v, ki)
            o = cache.attend(q, qi, w, scale=1.0 / math.sqrt(hd))
            return self._rest(x, o.astype(x.dtype), bp)

        def stacks(params):
            yield LayerStack(params["blocks"], range(c.depth), layer)

        return dataclasses.replace(
            base, stacks=stacks,
            row=KVRow(c.kv_heads, hd,
                      index=IndexKey(self.ID, self.IH, self.TOPK)))


@pytest.mark.parametrize("impl", ["paged", "gather"])
def test_a_family_whose_row_carries_an_index_key_is_served(impl):
    """The pool is the pair (K/V pages, index-key pages) under one set of
    block ids; split prefill, a batch of chunks, a fused window and a
    grafted prefix (its partial page copied in BOTH arrays) give the dense
    forward's logits; what ships K/V rows without the keys is refused."""
    from deepspeed_tpu.inference.v2 import kv_ship

    model = IndexedLM(RenamedConfig(depth=2))
    params = model.init_params(jax.random.PRNGKey(0))
    eng = _engine(model, params, impl, max_tokens=16, prefix_cache=True)
    kv, ix = eng.kv.pages
    pages = 2 * eng.kv.config.num_blocks + 1
    assert kv.shape == (pages, 8, 4, 8) and ix.shape == (pages, 4, 16)
    assert eng.kv.mem_bytes() == (kv.size + ix.size) * 4
    rng = np.random.default_rng(0)
    a, b = (rng.integers(1, 88, size=n).tolist() for n in (19, 5))

    def same(got, seq):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(model(params, jnp.asarray(seq))[-1]),
            atol=3e-4, rtol=3e-4)

    eng.put([0], [a[:8]])
    logits = eng.put([0, 1], [a[8:], b])    # beyond TOPK, and within it
    same(logits[0], a)
    same(logits[1], b)
    seeds = [int(jnp.argmax(logits[0])), int(jnp.argmax(logits[1]))]
    window = eng.decode_batch([0, 1], seeds, 3)
    for col, chain in enumerate((a + seeds[:1], b + seeds[1:])):
        for tok in window[:, col].tolist():
            assert tok == int(jnp.argmax(model(params, jnp.asarray(chain))[-1]))
            chain.append(tok)
    with pytest.raises(NotImplementedError, match="index keys"):
        kv_ship.export_kv(eng, 0, a, n_tokens=12)
    eng.commit_prefix(0, a, allow_partial=True)
    eng.flush([0])
    again = a + rng.integers(1, 88, size=5).tolist()
    assert eng.graft_prefix(2, again) == 19
    same(eng.put([2], [again[19:]])[0], again)


@pytest.mark.parametrize("stored", ["tiled", "packed"])
def test_a_shipment_carries_the_models_heads_not_the_pools_form(stored):
    """A K/V-row family whose row kind stores a token another way than the
    model's heads lie — padded (``KVRow.tiled``: 3 heads in 4) or along the
    lanes (``KVRow.packed``: 3 heads in one K row and one V row): the
    canonical rows of a shipment hold the model's 3 + 3, one head a row, and
    an engine that stores them in any form continues from the import with
    the same logits."""
    from deepspeed_tpu.inference.v2 import kv_ship

    config = RenamedConfig(depth=2, width=48, heads=6, kv_heads=3)
    model = RenamedLM(config)
    params = model.init_params(jax.random.PRNGKey(0))
    base = model.serving_family()
    hd = config.width // config.heads

    def storing(row):
        class Stored(RenamedLM):
            def serving_family(self):
                return dataclasses.replace(base, row=row)
        return Stored(config)

    forms = {"tiled": (KVRow.tiled(3, hd), (8, hd)),
             "packed": (KVRow.packed(3, hd), (2, 3 * hd))}
    prompt = np.random.default_rng(0).integers(1, 88, size=11).tolist()
    row, token_shape = forms[stored]
    src = _engine(storing(row), params, "gather", max_tokens=16)
    assert src.kv.pages.shape[2:] == token_shape
    want = np.asarray(src.put([0], [prompt])[0])
    ship = kv_ship.export_kv(src, 0, prompt, n_tokens=10)
    assert ship.rows.shape == (2, 10, 6, hd) and ship.num_kv_heads == 3
    for target in (storing(forms["tiled"][0]), storing(forms["packed"][0]),
                   model):                  # padded, along the lanes, as it is
        dst = _engine(target, params, "gather", max_tokens=16)
        assert kv_ship.import_kv(dst, ship, 7)
        got = np.asarray(dst.put([7], [prompt[10:]])[0])
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
