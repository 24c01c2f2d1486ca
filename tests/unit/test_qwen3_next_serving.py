"""Qwen3-Next (Gated DeltaNet layers with per-sequence recurrent state beside
the K/V pages, gated attention, softmax-routed experts as a chip's share)
through ``InferenceEngineV2``, against the benchmark's plain reference
(``benchmark/reference/qwen3_next.py``, the same file the benchmark imports;
it shares no code with ``deepspeed_tpu``)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (
    InferenceEngineV2, RaggedInferenceEngineConfig, SchedulingResult)
from deepspeed_tpu.inference.v2.kernels import gdn_ops
from deepspeed_tpu.inference.v2.lifecycle import (LifecycleScheduler,
                                                  ServeRequest)
from deepspeed_tpu.models import qwen3_next as Q
from deepspeed_tpu.moe import dropless

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load(os.path.join(REPO, "benchmark", "reference",
                               "qwen3_next.py"),
                  "benchmark_reference_qwen3_next")

#: published keys at a tiny size: two periods of 3 DeltaNet + 1 attention
#: layer; 16 experts of which this chip holds 4 (ep_size 4, the second share)
HF = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=8,
    full_attention_interval=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=32, partial_rotary_factor=0.25, rope_theta=10000000,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4, num_experts=4,
    num_experts_per_tok=3, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[], rms_norm_eps=1e-6,
    rope_scaling=None, max_position_embeddings=256,
    tie_word_embeddings=False, ep_size=4, ep_rank=1)
PROMPT = 75         # several 16-token chunks, no multiple of 16 or of 64
TOL = 5e-4          # float32 system against the float32 reference


@pytest.fixture(scope="module")
def model():
    m = Q.Qwen3NextLM.from_hf_config(HF)
    return m, m.init_params(jax.random.PRNGKey(0), jnp.float32)


def ref_weights(params, interval=4):
    gdn_names = {"in_norm": ("in_norm", "scale"), "w_qkvz": ("qkvz", "kernel"),
                 "w_ba": ("ba", "kernel"), "conv": ("conv", "kernel"),
                 "gnorm": ("gnorm", "scale"), "w_o": ("o_proj", "kernel")}
    attn_names = {"in_norm": ("in_norm", "scale"), "w_q": ("q_proj", "kernel"),
                  "w_k": ("k_proj", "kernel"), "w_v": ("v_proj", "kernel"),
                  "q_norm": ("q_norm", "scale"), "k_norm": ("k_norm", "scale"),
                  "w_o": ("o_proj", "kernel")}
    per, experts = params["periods"], params["experts"]
    layers = []
    for p in range(per["attn"]["in_norm"]["scale"].shape[0]):
        for j in range(interval):
            if j < interval - 1:
                g = per["gdn"]
                w = {k: g[a][b][p, j] for k, (a, b) in gdn_names.items()}
                w.update(A_log=g["A_log"][p, j], dt_bias=g["dt_bias"][p, j])
            else:
                w = {k: per["attn"][a][b][p]
                     for k, (a, b) in attn_names.items()}
            m = per["moe"]
            w.update(post_norm=m["post_norm"]["scale"][p, j],
                     router=m["router"]["kernel"][p, j],
                     s_gate=m["shared"]["gate"][p, j],
                     s_up=m["shared"]["up"][p, j],
                     s_down=m["shared"]["down"][p, j],
                     s_gatew=m["shared_gate"]["kernel"][p, j, :, 0])
            layer = p * interval + j
            w.update(e_gate=experts["gate"][layer], e_up=experts["up"][layer],
                     e_down=experts["down"][layer])
            layers.append(lambda w=w: w)
    return {"embedding": params["embed"]["embedding"],
            "norm": params["norm_f"]["scale"],
            "head": params["lm_head"]["kernel"], "layers": layers}


def engine_for(model, **kw):
    m, params = model
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
    (out,) = reference.Reference(HF, mutation).logits(
        [jnp.asarray(prompt, jnp.int32)], ref_weights(model[1]),
        positions=[positions])
    return np.asarray(out)


@pytest.fixture(scope="module")
def got(model):
    prompt = prompt_tokens()
    engine = engine_for(model)
    body = PROMPT - 4
    return prompt, body, system_logits(engine, prompt, body)


@pytest.mark.parametrize("impl", ["paged", "gather"])
def test_prefill_then_decode_through_slot_and_pages(model, impl):
    from deepspeed_tpu.telemetry.trace import get_tracer

    tracer = get_tracer()
    before = len(tracer.records())
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
    # which convolution each compiled program took: the decode form's kernel
    # in the windows, the ragged form everywhere else
    convs = {(rec.attrs["form"], rec.attrs["conv_impl"])
             for rec in tracer.records()[before:]
             if rec.name == "attn/gdn_layout"}
    assert convs == ({("ragged", "xla"), ("decode", "kernel")}
                     if impl == "paged" else {("oracle", "xla")})


@pytest.mark.parametrize("mutation", reference.MUTATIONS)
def test_each_piece_of_the_mathematics_is_noticed(model, got, mutation):
    """Leaving out any one piece moves the reference away from the system by
    far more than the tolerance: the comparison above holds each of them."""
    prompt, body, logits = got
    ref = reference_logits(model, prompt, list(range(body - 1, PROMPT)),
                           mutation)
    assert rel_l2(logits, ref) > 20 * TOL, mutation


def test_a_mixed_batch_of_chunks_and_decode_rows(model):
    """SplitFuse: chunks of two sequences and a decode row in ONE flat
    batch; every sequence continues from its own slot."""
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


def test_a_fused_window_of_several_sequences(model):
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


def test_a_reused_slot_starts_from_zeros(model):
    """A flushed sequence's slot goes to the next one, which must not see
    the state left there: position 0 starts from zeros on the device."""
    engine = engine_for(model, max_seqs=1, max_tokens=32)
    sm = engine.state_manager
    engine.put([1], [prompt_tokens(8, 30)])
    slot = sm.get_sequence(1).slot
    assert sm.free_slots == 0
    assert engine.can_schedule([2], [4]) \
        is SchedulingResult.EngineSequenceLimitExceeded
    engine.flush([1])
    assert sm.free_slots == 1
    fresh = prompt_tokens(9, 27)
    out = np.asarray(engine.put([2], [fresh])[0])
    assert sm.get_sequence(2).slot == slot
    assert rel_l2(out, reference_logits(model, fresh, [26])[0]) < TOL
    # the stale-state mutation: without the reset the slot's last owner
    # shows (what the comparison above would let through if it were loose)
    stale = engine_for(model, max_seqs=1, max_tokens=32)
    stale.put([1], [prompt_tokens(8, 30)])
    stale.flush([1])
    # a first token fed as a decode row takes the same slot: zeros again
    toks = stale.decode_batch([2], [fresh[0]], 1)
    ref = reference_logits(model, fresh[:1], [0])[0]
    assert int(toks[0, 0]) == int(np.argmax(ref))
    pool = [np.asarray(a) for a in stale.state_pool.arrays]
    assert np.abs(pool[0][:-1]).max() > 0       # something WAS left there


def test_stale_state_would_be_noticed(model, monkeypatch):
    """The mutation of the test above: a chunk that starts from whatever the
    slot holds, position 0 or not, is far outside the tolerance."""
    real = gdn_ops.gdn_chunk_prefill

    def never_fresh(*a, fresh, **kw):
        return real(*a, fresh=jnp.zeros_like(fresh), **kw)

    monkeypatch.setattr(gdn_ops, "gdn_chunk_prefill", never_fresh)
    engine = engine_for(model, max_seqs=1, max_tokens=32)
    engine.put([1], [prompt_tokens(8, 30)])
    engine.flush([1])
    fresh = prompt_tokens(9, 27)
    out = np.asarray(engine.put([2], [fresh])[0])
    assert rel_l2(out, reference_logits(model, fresh, [26])[0]) > 20 * TOL


def test_the_scheduler_serves_preempts_and_resumes(model):
    """Through ``LifecycleScheduler``: more requests than slots wait for one
    (``state_blocked``), a preempted request gives its slot back and is
    prefilled again from zeros, and every answer is the reference's greedy
    continuation."""
    from deepspeed_tpu.telemetry.trace import get_tracer

    engine = engine_for(model, max_seqs=2, max_tokens=32, max_ctx=64)
    # a watermark of 0: a head that finds no slot preempts a decoding
    # request whatever the pool's filling
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
    admits = [rec for rec in tracer.records()[before:]
              if rec.name == "serve/admit"]
    assert any(rec.attrs.get("state_blocked") == 1 for rec in admits)
    reserves = [rec for rec in tracer.records()[before:]
                if rec.name == "serve/reserve" and "slot" in rec.attrs]
    assert {rec.attrs["slot"] for rec in reserves} == {0, 1}
    layouts = {(rec.attrs["form"], rec.attrs["impl"], rec.attrs["conv_impl"])
               for rec in tracer.records()[before:]
               if rec.name == "attn/gdn_layout"}
    assert layouts == {("ragged", "xla", "xla"),
                       ("decode", "kernel", "kernel")}
    accounts = [rec for rec in tracer.records()[before:]
                if rec.name == "engine/window_account"]
    assert accounts and all(rec.attrs["moe_pairs_dropped"] == 0
                            and rec.attrs["moe_pairs_elsewhere"] > 0
                            and rec.attrs["state_slots"] >= 1
                            and rec.attrs["state_bytes"] > 0
                            for rec in accounts)


def _gdn_inputs(seed, T, H=4, dk=16, dv=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = gdn_ops.l2norm(jax.random.normal(ks[0], (T, H, dk))) / 4.0
    k = gdn_ops.l2norm(jax.random.normal(ks[1], (T, H, dk)))
    v = jax.random.normal(ks[2], (T, H, dv))
    g = -jax.random.uniform(ks[3], (T, H), minval=0.01, maxval=0.7)
    beta = jax.random.uniform(ks[4], (T, H), minval=0.1, maxval=0.95)
    return q, k, v, g, beta


def test_the_chunked_form_is_the_recurrence():
    """A ragged batch (a chunk of 150 tokens continuing a state, one of 64
    from position 0, a single token, an empty row) through the chunked form
    and token by token: same outputs, same states."""
    T, S, H, dk, dv = 256, 4, 4, 16, 16
    q, k, v, g, beta = _gdn_inputs(0, T)
    q_len = jnp.asarray([150, 64, 1, 0], jnp.int32)
    cu = jnp.asarray([0, 150, 214, 215, 215], jnp.int32)
    fresh = jnp.asarray([False, True, False, True])
    pool = jax.random.normal(jax.random.PRNGKey(9), (S + 2, H, dk, dv))
    rows = jnp.asarray([3, 0, 1, S + 1], jnp.int32)
    seq_of = jnp.searchsorted(cu[1:], jnp.arange(T), side="right")
    seq_of = jnp.minimum(seq_of, S - 1).astype(jnp.int32)
    pos = jnp.arange(T) - cu[seq_of] + jnp.where(fresh, 0, 7)[seq_of]
    valid = jnp.arange(T) < 215
    o1, p1 = gdn_ops.gdn_chunk_prefill(q, k, v, g, beta, pool, rows,
                                       cu_q_lens=cu, q_len=q_len, fresh=fresh)
    o2, p2 = gdn_ops.gdn_recurrent(q, k, v, g, beta, pool, rows,
                                   seq_of_token=seq_of, pos_of_token=pos,
                                   valid=valid)
    np.testing.assert_allclose(o1[:215], o2[:215], atol=2e-5)
    np.testing.assert_allclose(p1[:S], p2[:S], atol=2e-5)
    assert float(jnp.abs(o1[215:]).max()) == 0.0
    np.testing.assert_array_equal(p1[2], pool[2])       # nobody's slot


def test_the_decode_kernel_is_one_token_of_the_recurrence():
    R, H, dk, dv = 5, 4, 16, 16
    q, k, v, g, beta = _gdn_inputs(1, R)
    pool = jax.random.normal(jax.random.PRNGKey(3), (9, H, dk, dv))
    rows = jnp.asarray([4, 0, 7, 8, 8], jnp.int32)      # 8: the trash row
    alpha = jnp.exp(g).at[1].set(0.0)                   # row 1 is fresh
    o, new = gdn_ops.gdn_decode(q, k, v, alpha, beta, pool, rows,
                                heads_per_step=2)
    for r in range(3):
        S0 = jnp.zeros_like(pool[0]) if r == 1 else pool[rows[r]]
        S, want = gdn_ops._token_update(S0, q[r], k[r], v[r], g[r], beta[r])
        np.testing.assert_allclose(o[r], want, atol=1e-5)
        np.testing.assert_allclose(new[rows[r]], S, atol=1e-5)
    untouched = [1, 2, 3, 5, 6]
    np.testing.assert_array_equal(new[jnp.asarray(untouched)],
                                  pool[jnp.asarray(untouched)])


def test_the_convolution_carries_its_last_inputs():
    """A sequence's inputs split over batches at any point give the same
    outputs as in one piece; a fresh row ignores what its slot held."""
    T, C, K = 23, 12, 4
    x = jax.random.normal(jax.random.PRNGKey(0), (T, C))
    w = jax.random.normal(jax.random.PRNGKey(1), (K, C))
    padded = jnp.concatenate([jnp.zeros((K - 1, C)), x])
    want = sum(w[j][None] * padded[j:j + T] for j in range(K))
    pool = jax.random.normal(jax.random.PRNGKey(2), (3, K - 1, C))
    rows = jnp.asarray([1, 2], jnp.int32)               # row 1 unused: trash
    got, start = [], 0
    for n in (2, 1, 9, 11):
        out, pool = gdn_ops.causal_conv_ragged(
            x[start:start + n], w, pool, rows,
            seq_of_token=jnp.zeros((n,), jnp.int32),
            q_offset=jnp.asarray([0, n], jnp.int32),
            q_len=jnp.asarray([n, 0], jnp.int32),
            fresh=jnp.asarray([start == 0, True]))
        got.append(out)
        start += n
    np.testing.assert_allclose(jnp.concatenate(got), want, atol=1e-5)
    np.testing.assert_allclose(pool[1], x[-(K - 1):], atol=1e-6)


@pytest.mark.parametrize("batch", ["shuffled", "fresh", "padded"])
@pytest.mark.parametrize("C,K,dtype", [
    (8192, 4, jnp.bfloat16),        # Qwen3-Next's channels
    (11520, 4, jnp.bfloat16),       # Olmo-Hybrid's: 90 lane tiles
    (12, 4, jnp.float32),
    (384, 2, jnp.bfloat16)])
def test_the_decode_convolution_is_the_ragged_one(C, K, dtype, batch):
    """One token a row through ``causal_conv_step`` (the kernel, interpreted
    here) and through the ragged form: same outputs, same pool outside the
    trash row — rows in shuffled pool order; fresh rows over a slot full of
    NaN; padded rows that all name the trash row."""
    R, N = 6, 13
    ks = jax.random.split(jax.random.PRNGKey(C + K), 3)
    x = jax.random.normal(ks[0], (R, C)).astype(dtype)
    w = jax.random.normal(ks[1], (K, C)).astype(dtype)
    pool = jax.random.normal(ks[2], (N, K - 1, C)).astype(dtype)
    rows = jnp.asarray([7, 2, 11, 0, 5, 9], jnp.int32)
    q_len = jnp.ones((R,), jnp.int32)
    fresh = jnp.zeros((R,), bool)
    if batch == "fresh":
        fresh = fresh.at[jnp.asarray([1, 4])].set(True)
        pool = pool.at[rows[jnp.asarray([1, 4])]].set(jnp.nan)
    if batch == "padded":
        rows = rows.at[3:].set(N - 1)
        q_len = q_len.at[3:].set(0)
        fresh = fresh.at[3:].set(True)      # ctx_len == q_len == 0
    live = np.asarray(q_len) > 0
    want, want_pool = gdn_ops.causal_conv_ragged(
        x, w, pool, rows, seq_of_token=jnp.arange(R),
        q_offset=jnp.arange(R), q_len=q_len, fresh=fresh)
    got, got_pool = gdn_ops.causal_conv_step(x, w, pool, rows,
                                             (q_len > 0) & ~fresh)
    assert got.dtype == jnp.float32 and got_pool.dtype == pool.dtype
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(jax.nn.silu(want))[live],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(got_pool[:-1].astype(jnp.float32)),
        np.asarray(want_pool[:-1].astype(jnp.float32)))
    assert np.isfinite(np.asarray(got)).all()


def test_the_ragged_form_hands_its_carry_to_the_decode_form():
    """A sequence prefilled in chunks by the ragged form and then decoded
    five tokens, one a call, by the decode form: the one-piece convolution."""
    T, C, K, steps = 23, 12, 4, 5
    x = jax.random.normal(jax.random.PRNGKey(0), (T + steps, C))
    w = jax.random.normal(jax.random.PRNGKey(1), (K, C))
    padded = jnp.concatenate([jnp.zeros((K - 1, C)), x])
    want = jax.nn.silu(sum(w[j][None] * padded[j:j + T + steps]
                           for j in range(K)))
    pool = jax.random.normal(jax.random.PRNGKey(2), (3, K - 1, C))
    rows = jnp.asarray([1, 2], jnp.int32)               # row 1 unused: trash
    got, start = [], 0
    for n in (2, 1, 9, 11):
        out, pool = gdn_ops.causal_conv_ragged(
            x[start:start + n], w, pool, rows,
            seq_of_token=jnp.zeros((n,), jnp.int32),
            q_offset=jnp.asarray([0, n], jnp.int32),
            q_len=jnp.asarray([n, 0], jnp.int32),
            fresh=jnp.asarray([start == 0, True]))
        got.append(jax.nn.silu(out))
        start += n
    for t in range(T, T + steps):
        out, pool = gdn_ops.causal_conv_step(
            jnp.stack([x[t], x[0]]), w, pool, rows,
            jnp.asarray([True, False]))
        got.append(out[:1])
    np.testing.assert_allclose(jnp.concatenate(got), want, atol=1e-5)
    np.testing.assert_allclose(pool[1], x[-(K - 1):], atol=1e-6)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Route over all 16 experts, compute the pairs of the 4 held here: the
    four shares' routed parts plus the shared expert ONCE are the uncut
    layer, as the reference computes it with ep_size 1."""
    T, D, E, F, k = 24, 32, 16, 16, 3
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    h = jax.random.normal(ks[0], (T, D))
    router = {"kernel": jax.random.normal(ks[1], (D, E))}
    experts = {"gate": jax.random.normal(ks[2], (E, D, F)) / 6,
               "up": jax.random.normal(ks[3], (E, D, F)) / 6,
               "down": jax.random.normal(ks[4], (E, F, D)) / 4}
    shared = {"gate": jax.random.normal(ks[5], (D, F)) / 6,
              "up": jax.random.normal(ks[6], (D, F)) / 6,
              "down": jax.random.normal(ks[7], (F, D)) / 4}
    gate_w = jax.random.normal(jax.random.PRNGKey(5), (D, 1))
    idx, weights = dropless.softmax_topk_route(h, router, k)
    total = jnp.zeros((T, D))
    elsewhere = 0
    for rank in range(4):
        held = {n: w[rank * 4:(rank + 1) * 4] for n, w in experts.items()}
        part, pairs = dropless.dropless_experts(h, idx, weights, held,
                                                offset=rank * 4)
        assert pairs.shape == (5,)
        assert int(pairs.sum()) == T * k
        elsewhere += int(pairs[4])
        total = total + part
    assert elsewhere == 3 * T * k       # every pair is held by one share
    lp = {"router": router, "shared": shared,
          "shared_gate": {"kernel": gate_w}, "experts": experts}
    whole, pairs = dropless.softmax_moe_block(h, lp, k=k)
    assert pairs.shape == (E,) and int(pairs.sum()) == T * k
    shared_once = whole - dropless.dropless_experts(h, idx, weights,
                                                    experts)[0]
    c = dict(num_experts_per_tok=k, norm_topk_prob=True, ep_size=1, ep_rank=0)
    w = {"router": router["kernel"], "e_gate": experts["gate"],
         "e_up": experts["up"], "e_down": experts["down"],
         "s_gate": shared["gate"], "s_up": shared["up"],
         "s_down": shared["down"], "s_gatew": gate_w[:, 0]}
    with jax.default_matmul_precision("highest"):
        uncut = reference.expert_layer(h, w, c)
    np.testing.assert_allclose(total + shared_once, uncut, atol=2e-5)


WHAT = ("prefix_cache", "host_tier_mb", "verify_decode", "speculative",
        "kv_export", "kv_import")


@pytest.mark.parametrize("what", WHAT)
def test_what_recurrent_state_cannot_do_is_refused_by_name(model, what):
    from deepspeed_tpu.inference.v2 import kv_ship

    with pytest.raises(NotImplementedError) as err:
        if what == "prefix_cache":
            engine_for(model, prefix_cache=True)
        elif what == "host_tier_mb":
            engine_for(model, host_tier_mb=1.0)
        else:
            engine = engine_for(model)
            if what == "verify_decode":
                engine.verify_decode([1], [3], [[4, 5]])
            elif what == "speculative":
                LifecycleScheduler(engine, drafter=object())
            elif what == "kv_export":
                engine.put([1], [[3, 4, 5]])
                kv_ship.export_kv(engine, 1, [3, 4, 5])
            else:
                kv_ship.import_kv(engine, object(), 1)
    assert "recurrent state" in str(err.value)
    assert "ROADMAP R5" in str(err.value)


def test_published_config_builds_the_published_shapes():
    """The catalog's keys, the benchmark's share: no width is a key of the
    share, and the parameter count is the issue's arithmetic."""
    import json

    with open(os.path.join(REPO, "benchmark", "configs",
                           "qwen3-next-80b-a3b-depth8-ep4.json")) as f:
        hf = json.load(f)
    m = Q.Qwen3NextLM.from_hf_config(hf)
    c = m.config
    assert (c.num_experts, c.experts_held, c.expert_offset) == (512, 128, 0)
    assert (c.num_periods, c.gdn_per_period, c.rotary_dim) == (2, 3, 64)
    state = c.state
    assert state.num_layers == 6 and state.conv_channels == 8192
    assert state.slot_bytes(jnp.bfloat16) == 6 * 2146304
    assert abs(m.num_params() - 3.67e9) < 0.01e9
    fam = m.serving_family()
    assert fam.page_layers == 2 and fam.row.token_shape == (4, 256)
    assert fam.counts.size == 129 and fam.counts.per_token == 80
    with pytest.raises(NotImplementedError):
        m.loss_fn(None, None, None)
