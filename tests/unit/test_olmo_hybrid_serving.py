"""Olmo-Hybrid (a dense hybrid: Gated DeltaNet layers whose state widths tile
neither sublanes nor lanes, full attention on K/V heads stored in more heads
than the model has, the OLMo post-norm block, no rotary) through
``InferenceEngineV2``, against the benchmark's plain reference
(``benchmark/reference/olmo_hybrid.py``, the same file the benchmark imports;
it shares no code with ``deepspeed_tpu``)."""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.kernels import gdn_ops
from deepspeed_tpu.inference.v2.lifecycle import (LifecycleScheduler,
                                                  ServeRequest)
from deepspeed_tpu.models import olmo_hybrid as O
from deepspeed_tpu.models.serving import GatedDeltaState, KVRow

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load(os.path.join(REPO, "benchmark", "reference",
                               "olmo_hybrid.py"),
                  "benchmark_reference_olmo_hybrid")

#: published keys at a tiny size that KEEPS the awkward ratios: 6 heads (no
#: multiple of 8: stored in 8), keys 24 and values 48 wide, two periods of
#: 3 linear + 1 full layer
HF = dict(
    model_type="olmo_hybrid", vocab_size=256, hidden_size=96,
    intermediate_size=160, num_hidden_layers=8, num_attention_heads=6,
    num_key_value_heads=6, hidden_act="silu", max_position_embeddings=256,
    attention_bias=False, rms_norm_eps=1e-6, tie_word_embeddings=False,
    layer_types=(["linear_attention"] * 3 + ["full_attention"]) * 2,
    linear_num_key_heads=6, linear_num_value_heads=6, linear_key_head_dim=24,
    linear_value_head_dim=48, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None})
#: the same with values 64 wide: a head PAIR is a whole 128-lane tile, so the
#: state is stored in pairs (the layout the benchmark's widths get)
HF_PAIRS = dict(HF, linear_value_head_dim=64)
PROMPT = 75         # several 16-token chunks, no multiple of 16 or of 64
TOL = 5e-4          # float32 system against the float32 reference


def make(hf):
    m = O.OlmoHybridLM.from_hf_config(hf)
    return m, m.init_params(jax.random.PRNGKey(0), jnp.float32), hf


@pytest.fixture(scope="module")
def model():
    return make(HF)


@pytest.fixture(scope="module")
def model_pairs():
    return make(HF_PAIRS)


def ref_weights(params, period=4):
    gdn_names = {"w_qkvg": ("qkvg", "kernel"), "w_ba": ("ba", "kernel"),
                 "conv": ("conv", "kernel"), "gnorm": ("gnorm", "scale"),
                 "w_o": ("o_proj", "kernel"),
                 "mixer_norm": ("post_norm", "scale")}
    attn_names = {"w_q": ("q_proj", "kernel"), "w_k": ("k_proj", "kernel"),
                  "w_v": ("v_proj", "kernel"), "q_norm": ("q_norm", "scale"),
                  "k_norm": ("k_norm", "scale"), "w_o": ("o_proj", "kernel"),
                  "mixer_norm": ("post_norm", "scale")}
    per = params["periods"]
    layers = []
    for p in range(per["attn"]["post_norm"]["scale"].shape[0]):
        for j in range(period):
            if j < period - 1:
                g = per["gdn"][j]
                w = {k: g[a][b][p] for k, (a, b) in gdn_names.items()}
                w.update(A_log=g["A_log"][p], dt_bias=g["dt_bias"][p])
            else:
                w = {k: per["attn"][a][b][p]
                     for k, (a, b) in attn_names.items()}
            m = per["mlp"][j]
            w.update(w_gate=m["gate"]["kernel"][p],
                     w_up=m["up"]["kernel"][p],
                     w_down=m["down"]["kernel"][p],
                     mlp_norm=m["post_norm"]["scale"][p])
            layers.append(lambda w=w: w)
    return {"embedding": params["embed"]["embedding"],
            "norm": params["norm_f"]["scale"],
            "head": params["lm_head"]["kernel"], "layers": layers}


def engine_for(model, **kw):
    m, params, _ = model
    cfg = dict(max_tokens=16, max_seqs=4, max_ctx=128, block_size=8,
               dtype=jnp.float32)
    cfg.update(kw)
    return InferenceEngineV2(m, params, RaggedInferenceEngineConfig(**cfg))


def prompt_tokens(seed=0, n=PROMPT):
    return np.random.default_rng(seed).integers(1, 256, size=n).tolist()


def system_logits(engine, prompt, body, uid=1):
    """Chunked prefill of ``prompt[:body]``, then the rest fed singly
    through slot and pages: logits at positions body-1 .. len-1."""
    got = []
    for pos in range(0, body, 16):
        logits = engine.put([uid], [prompt[pos:min(pos + 16, body)]])
    got.append(np.asarray(logits[0]))
    for tok in prompt[body:]:
        got.append(np.asarray(engine.put([uid], [[tok]])[0]))
    return np.stack(got)


def rel_l2(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def reference_logits(model, prompt, positions, mutation=None):
    (out,) = reference.Reference(model[2], mutation).logits(
        [jnp.asarray(prompt, jnp.int32)], ref_weights(model[1]),
        positions=[positions])
    return np.asarray(out)


@pytest.fixture(scope="module")
def got(model):
    prompt = prompt_tokens()
    engine = engine_for(model)
    body = PROMPT - 4
    return prompt, body, system_logits(engine, prompt, body)


def test_the_family_says_what_it_stores(model, model_pairs):
    fam = model[0].serving_family()
    assert fam.row == KVRow(6, 16, stored_kv_heads=8)
    assert fam.row.token_shape == (16, 16) and fam.row.read_values == 192
    assert fam.page_layers == 2 and fam.state.num_layers == 6
    assert fam.state.state_layout == "plain"
    assert fam.state.arrays(jnp.float32)[0][0] == (6, 24, 48)
    pairs = model_pairs[0].serving_family().state
    assert pairs.state_layout == "pairs"
    assert pairs.arrays(jnp.float32)[0][0] == (3, 24, 128)
    assert pairs.slot_bytes(jnp.float32) == 6 * 4 * (6 * 24 * 64 + 3 * 672)
    # the published widths: a pair of 192-wide heads is three whole tiles
    full = GatedDeltaState(6, 30, 30, 96, 192, 4)
    assert full.arrays(jnp.bfloat16)[0][0] == (15, 96, 384)
    assert fam.state.beta_max == 2.0 and full.beta_max == 1.0
    assert KVRow.tiled(30, 128).stored == 32 and KVRow(8, 128).stored == 8
    assert [KVRow.tiled(n, 128).stored for n in (1, 2, 3, 6, 8, 12)] \
        == [1, 2, 4, 8, 8, 16]
    with pytest.raises(NotImplementedError, match="training path is open"):
        model[0].loss_fn(model[1], None, None)


def test_layer_types_are_read_as_given():
    cfg = O.OlmoHybridConfig.from_hf(HF)
    assert (cfg.period, cfg.num_periods, cfg.rope_theta) == (4, 2, None)
    assert O.OlmoHybridConfig.from_hf(
        dict(HF, rope_parameters={"rope_theta": 500000})).rope_theta == 5e5
    bad = list(HF["layer_types"])
    bad[1], bad[3] = bad[3], bad[1]
    with pytest.raises(NotImplementedError, match="whole periods"):
        O.OlmoHybridConfig.from_hf(dict(HF, layer_types=bad))
    with pytest.raises(NotImplementedError, match="whole periods"):
        O.OlmoHybridConfig.from_hf(dict(HF, num_hidden_layers=6))


@pytest.mark.parametrize("impl", ["paged", "gather"])
@pytest.mark.parametrize("which", ["model", "model_pairs"])
def test_prefill_then_decode_through_slot_and_pages(request, which, impl):
    model = request.getfixturevalue(which)
    prompt = prompt_tokens()
    engine = engine_for(model, attn_impl=impl)
    body = PROMPT - 4
    ref = reference_logits(model, prompt, list(range(body - 1, PROMPT)))
    got = system_logits(engine, prompt, body)
    assert rel_l2(got, ref) < TOL
    # fused windows, teacher-forced: the greedy token is the reference's
    more = prompt_tokens(1, 6)
    seq = prompt + more
    ref = reference_logits(model, seq, list(range(PROMPT - 1, len(seq))))
    first = int(np.argmax(got[-1]))
    assert first == int(np.argmax(ref[0]))
    for i, tok in enumerate(more):
        out = int(engine.decode_batch([1], [tok], 1)[0, 0])
        assert out == int(np.argmax(ref[1 + i]))


@pytest.mark.parametrize("mutation", ["beta_not_doubled", "pre_norm",
                                      "qk_norm_per_head", "no_conv",
                                      "no_gate", "rotary"])
def test_each_piece_of_the_mathematics_is_noticed(model, got, mutation):
    """Reading any one line of the equations another way moves the reference
    away from the system by far more than the tolerance: the comparison
    above holds each of them."""
    prompt, body, logits = got
    ref = reference_logits(model, prompt, list(range(body - 1, PROMPT)),
                           mutation)
    assert rel_l2(logits, ref) > 20 * TOL, mutation


def test_rotary_is_a_number_in_the_config(model):
    """``rope_theta`` a NUMBER turns the repo's rotary on: the reference's
    other reading (``rotary`` mutation, the OLMo family's 500,000) is then
    the one the system agrees with."""
    m = O.OlmoHybridLM.from_hf_config(
        dict(HF, rope_parameters={"rope_theta": reference.ROTARY_THETA}))
    engine = engine_for((m, model[1], None))
    prompt = prompt_tokens()
    got = system_logits(engine, prompt, PROMPT - 4)
    positions = list(range(PROMPT - 5, PROMPT))
    assert rel_l2(got, reference_logits(model, prompt, positions,
                                        "rotary")) < TOL
    assert rel_l2(got, reference_logits(model, prompt, positions)) > 20 * TOL


def test_stored_heads_give_the_logits_of_the_unpadded_row(model, monkeypatch):
    """6 heads stored in 8 against the row kind that stores 6, under the
    page-gather oracle: the same logits (the padded heads' rows are zeros
    and no query head reads them)."""
    prompt = prompt_tokens()
    padded = engine_for(model, attn_impl="gather")
    assert padded.kv.pages.shape[2:] == (16, 16)
    family = model[0].serving_family

    def unpadded():
        return dataclasses.replace(family(), row=KVRow(6, 16))

    monkeypatch.setattr(model[0], "serving_family", unpadded)
    plain = engine_for(model, attn_impl="gather")
    assert plain.kv.pages.shape[2:] == (12, 16)
    a = system_logits(padded, prompt, PROMPT - 4)
    b = system_logits(plain, prompt, PROMPT - 4)
    np.testing.assert_allclose(a, b, atol=1e-5)
    pages = np.asarray(padded.kv.pages)
    assert np.abs(pages[:, :, :6]).max() > 0
    assert np.abs(pages[:, :, 6:8]).max() == 0 \
        and np.abs(pages[:, :, 14:]).max() == 0


def test_a_mixed_batch_of_chunks_and_decode_rows(model_pairs):
    """SplitFuse: chunks of two sequences and a decode row in ONE flat
    batch; every sequence continues from its own slot."""
    model = model_pairs
    a, b, c = prompt_tokens(2, 40), prompt_tokens(3, 29), prompt_tokens(4, 21)
    engine = engine_for(model, max_tokens=32)
    engine.put([1], [a[:20]])
    engine.put([3], [c[:20]])
    out = np.asarray(engine.put([1, 2, 3], [a[20:31], b[:20], [c[20]]]))
    for row, (seq, n) in enumerate(((a, 31), (b, 20), (c, 21))):
        ref = reference_logits(model, seq[:n], [n - 1])
        assert rel_l2(out[row], ref[0]) < TOL, row
    out = np.asarray(engine.put([2, 1], [b[20:], a[31:]]))
    assert rel_l2(out[0], reference_logits(model, b, [len(b) - 1])[0]) < TOL
    assert rel_l2(out[1], reference_logits(model, a, [len(a) - 1])[0]) < TOL


def test_a_fused_window_of_several_sequences(model_pairs):
    model = model_pairs
    seqs = [prompt_tokens(5, 23), prompt_tokens(6, 37), prompt_tokens(7, 18)]
    engine = engine_for(model, max_tokens=128)
    logits = np.asarray(engine.put([1, 2, 3], [s[:-1] for s in seqs]))
    toks = engine.decode_batch([1, 2, 3], [s[-1] for s in seqs], 4)
    for col, seq in enumerate(seqs):
        full = list(seq)
        for step in range(4):
            ref = reference_logits(model, full, [len(full) - 1])[0]
            assert int(toks[step, col]) == int(np.argmax(ref)), (col, step)
            full.append(int(toks[step, col]))
    assert np.isfinite(logits).all()


def test_a_reused_slot_starts_from_zeros(model_pairs):
    """A flushed sequence's slot goes to the next one, which must not see
    the state left there: position 0 starts from zeros on the device."""
    model = model_pairs
    engine = engine_for(model, max_seqs=1, max_tokens=32)
    sm = engine.state_manager
    engine.put([1], [prompt_tokens(8, 30)])
    slot = sm.get_sequence(1).slot
    engine.flush([1])
    fresh = prompt_tokens(9, 27)
    out = np.asarray(engine.put([2], [fresh])[0])
    assert sm.get_sequence(2).slot == slot
    assert rel_l2(out, reference_logits(model, fresh, [26])[0]) < TOL
    # a first token fed as a decode row takes the same slot: zeros again
    engine.flush([2])
    toks = engine.decode_batch([3], [fresh[0]], 1)
    ref = reference_logits(model, fresh[:1], [0])[0]
    assert int(toks[0, 0]) == int(np.argmax(ref))


def test_the_scheduler_serves_preempts_and_resumes(model_pairs):
    """Through ``LifecycleScheduler``: more requests than slots wait for one,
    a preempted request gives its slot back and is prefilled again from
    zeros, and every answer is the reference's greedy continuation; the
    ring says which layouts the programs compiled."""
    from deepspeed_tpu.telemetry.trace import get_tracer

    model = model_pairs
    engine = engine_for(model, max_seqs=2, max_tokens=32, max_ctx=64)
    sched = LifecycleScheduler(engine, max_queue=8, window_steps=4,
                               kv_high_watermark=0.0)
    prompts = [prompt_tokens(20 + i, 18 + 3 * i) for i in range(4)]
    reqs = [ServeRequest(uid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    tracer = get_tracer()
    before = len(tracer.records())
    for r in reqs:
        assert sched.submit(r).admitted
    guard = 0
    while sched.pending and guard < 400:
        sched.step()
        guard += 1
    assert not sched.pending
    for r, p in zip(reqs, prompts):
        assert r.state.name == "FINISHED", (r.uid, r.state)
        full = list(p)
        for tok in r.produced:
            ref = reference_logits(model, full, [len(full) - 1])[0]
            assert int(tok) == int(np.argmax(ref)), r.uid
            full.append(int(tok))
    assert engine.state_manager.free_slots == 2
    assert sum(r.preempt_count for r in reqs) > 0
    records = tracer.records()[before:]
    layouts = {(rec.attrs["form"], rec.attrs["impl"], rec.attrs["conv_impl"],
                rec.attrs["state_layout"], rec.attrs["key_dim"],
                rec.attrs["value_dim"])
               for rec in records if rec.name == "attn/gdn_layout"}
    assert layouts == {("ragged", "xla", "xla", "pairs", 24, 64),
                       ("decode", "kernel", "kernel", "pairs", 24, 64)}
    accounts = [rec for rec in records
                if rec.name == "engine/window_account"]
    assert accounts and all(
        rec.attrs["state_slots"] >= 1
        and rec.attrs["state_bytes"] == engine.state_pool.mem_bytes()
        and 0.0 <= rec.attrs["state_pad_share"] < 1.0 for rec in accounts)


def test_the_engine_refuses_what_a_state_cannot_do(model):
    m, params, _ = model
    for kw in (dict(prefix_cache=True), dict(host_tier_mb=1.0)):
        with pytest.raises(NotImplementedError, match="recurrent state"):
            InferenceEngineV2(m, params, RaggedInferenceEngineConfig(
                max_tokens=16, max_seqs=2, max_ctx=64, block_size=8, **kw))


def _ragged_case(dv, alike, seed=0):
    """A ragged batch (a chunk of 150 tokens continuing a state, one of 64
    from position 0, a single token, an empty row), ``beta`` drawn in
    (1.5, 2); ``alike``: how much of every key is one direction a head (the
    keys of a trained or seeded model are ``silu`` outputs, mostly
    positive: alike)."""
    T, S, H, dk = 256, 4, 6, 24
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = gdn_ops.l2norm(jax.random.normal(ks[0], (T, H, dk))) / dk ** 0.5
    k = gdn_ops.l2norm(alike * jax.random.normal(ks[6], (1, H, dk))
                       + (1 - alike) * jax.random.normal(ks[1], (T, H, dk)))
    v = jax.random.normal(ks[2], (T, H, dv))
    g = -jax.random.uniform(ks[3], (T, H), minval=0.01, maxval=0.7)
    beta = jax.random.uniform(ks[4], (T, H), minval=1.5, maxval=2.0)
    kind = GatedDeltaState(1, H, H, dk, dv, 4, beta_max=2.0)
    shape = kind.arrays(jnp.float32)[0][0]
    q_len = jnp.asarray([150, 64, 1, 0], jnp.int32)
    cu = jnp.asarray([0, 150, 214, 215, 215], jnp.int32)
    fresh = jnp.asarray([False, True, False, True])
    pool = jax.random.normal(ks[5], (S + 2,) + shape)
    rows = jnp.asarray([3, 0, 1, S + 1], jnp.int32)
    seq_of = jnp.searchsorted(cu[1:], jnp.arange(T), side="right")
    seq_of = jnp.minimum(seq_of, S - 1).astype(jnp.int32)
    pos = jnp.arange(T) - cu[seq_of] + jnp.where(fresh, 0, 7)[seq_of]
    oracle = gdn_ops.gdn_recurrent(q, k, v, g, beta, pool, rows,
                                   seq_of_token=seq_of, pos_of_token=pos,
                                   valid=jnp.arange(T) < 215)
    chunked = lambda substitution: gdn_ops.gdn_chunk_prefill(  # noqa: E731
        q, k, v, g, beta, pool, rows, cu_q_lens=cu, q_len=q_len, fresh=fresh,
        substitution=substitution)
    return oracle, chunked, pool, S


@pytest.mark.parametrize("alike", [0.0, 0.8, 0.95])
@pytest.mark.parametrize("dv", [48, 64])
def test_the_chunked_form_agrees_with_the_oracle_at_beta_near_2(dv, alike):
    """``beta`` in (1.5, 2): ``I - beta k k^T`` has eigenvalues near -1 and
    ``A``'s entries pass 1.  By forward substitution (what a state kind with
    ``beta_max`` 2 gets) ``T = (I + A)^-1`` is the token-by-token oracle at
    the tolerance the form has, whether the keys are alike or not; states
    stored plain (48) and in pairs (64)."""
    (o2, p2), chunked, pool, S = _ragged_case(dv, alike)
    o1, p1 = chunked(True)
    np.testing.assert_allclose(o1[:215], o2[:215], atol=2e-5)
    np.testing.assert_allclose(p1[:S], p2[:S], atol=2e-5)
    np.testing.assert_array_equal(p1[2], pool[2])       # nobody's slot


def test_squarings_hold_for_unlike_keys_and_not_for_alike_ones():
    """Why the substitution exists: the squarings (``beta`` <= 1's form)
    agree with the oracle at ``beta`` near 2 while the keys are unlike, and
    are off by orders of magnitude once they are alike — the powers of ``A``
    they add and cancel grow before they vanish.  On the chip this read
    0.06-0.15 off the reference after a 1,387-token prompt in half of the
    seeds (PERF.md section 6, PR 34)."""
    (o2, _), chunked, _, _ = _ragged_case(64, 0.0)
    np.testing.assert_allclose(chunked(False)[0][:215], o2[:215], atol=2e-5)
    (o2, _), chunked, _, _ = _ragged_case(64, 0.95)
    off = float(jnp.abs(chunked(False)[0][:215] - o2[:215]).max())
    assert not off < 1e-2, off


@pytest.mark.parametrize("dv,heads_per_step", [(48, 16), (64, 16), (64, 2)])
def test_the_decode_kernel_takes_widths_that_do_not_tile(dv, heads_per_step):
    """One token of the recurrence at 6 heads of [24, dv], the state stored
    as the state kind says (plain at 48, head pairs at 64), ``beta`` to 2."""
    R, H, dk = 5, 6, 24
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    q = gdn_ops.l2norm(jax.random.normal(ks[0], (R, H, dk))) / dk ** 0.5
    k = gdn_ops.l2norm(jax.random.normal(ks[1], (R, H, dk)))
    v = jax.random.normal(ks[2], (R, H, dv))
    g = -jax.random.uniform(ks[3], (R, H), minval=0.01, maxval=0.7)
    beta = jax.random.uniform(ks[4], (R, H), minval=0.5, maxval=2.0)
    kind = GatedDeltaState(1, H, H, dk, dv, 4)
    width = kind.arrays(jnp.float32)[0][0][-1]
    plain = jax.random.normal(ks[5], (9, H, dk, dv))
    pool = jax.vmap(lambda S: gdn_ops.pack_state(S, width))(plain)
    assert pool.shape[1:] == kind.arrays(jnp.float32)[0][0]
    rows = jnp.asarray([4, 0, 7, 8, 8], jnp.int32)      # 8: the trash row
    alpha = jnp.exp(g).at[1].set(0.0)                   # row 1 is fresh
    o, new = gdn_ops.gdn_decode(q, k, v, alpha, beta, pool, rows,
                                heads_per_step=heads_per_step)
    new = jax.vmap(lambda S: gdn_ops.unpack_state(S, dv))(new)
    for r in range(3):
        S0 = jnp.zeros_like(plain[0]) if r == 1 else plain[rows[r]]
        S, want = gdn_ops._token_update(S0, q[r], k[r], v[r], g[r], beta[r])
        np.testing.assert_allclose(o[r], want, atol=1e-5)
        np.testing.assert_allclose(new[rows[r]], S, atol=1e-5)
    untouched = jnp.asarray([1, 2, 3, 5, 6])
    np.testing.assert_array_equal(new[untouched], plain[untouched])


def test_the_head_block_follows_the_head_count():
    assert gdn_ops._head_block(32, 16) == 16     # Qwen3-Next: two steps
    assert gdn_ops._head_block(32, 8) == 8 and gdn_ops._head_block(32, 32) == 32
    assert gdn_ops._head_block(15, 16) == 15     # 30 heads in pairs: whole
    assert gdn_ops._head_block(30, 16) == 30     # no multiple of 8 divides 30
    assert gdn_ops._head_block(48, 16) == 16 and gdn_ops._head_block(40, 16) == 8


@pytest.mark.parametrize("op", ["decode", "ragged"])
def test_the_page_kernels_attend_the_models_heads_on_a_wider_pool(op):
    """The Pallas kernels (interpret mode) with 6 query and K/V heads on a
    pool that stores a token in 8 against the same rows in a pool of 6: the
    outputs of the model's heads are equal, and there are no others."""
    from deepspeed_tpu.inference.v2.kernels import ragged_ops

    S, H, hd, ps, NB = 3, 6, 16, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    plain = jax.random.normal(ks[0], (S * NB + 1, ps, 2 * H, hd))
    zeros = jnp.zeros((S * NB + 1, ps, 2, hd))
    wide = jnp.concatenate([plain[:, :, :H], zeros, plain[:, :, H:], zeros],
                           axis=2)
    # the padded heads hold garbage a query head must never read
    wide = wide.at[:, :, H:H + 2].set(jnp.nan)
    table = jnp.arange(S * NB, dtype=jnp.int32).reshape(S, NB)
    if op == "decode":
        q = jax.random.normal(ks[1], (S, H, hd))
        lens = jnp.asarray([5, 29, 0], jnp.int32)
        run = lambda pool: ragged_ops.decode_paged_attention(  # noqa: E731
            q, pool, lens, table, num_kv_heads=H, interpret=True)
    else:
        q = jax.random.normal(ks[1], (24, H, hd))
        lens = jnp.asarray([9, 30, 4], jnp.int32)
        cu = jnp.asarray([0, 9, 20, 24], jnp.int32)
        run = lambda pool: ragged_ops.ragged_paged_attention(  # noqa: E731
            q, pool, lens, table, cu, num_kv_heads=H, block_q=8,
            interpret=True)
    got, want = run(wide), run(plain)
    assert got.shape == want.shape == q.shape
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert np.isfinite(np.asarray(got)).all()
