"""Phi-4-mini-flash-reasoning (a selective-scan state, window layers that own
a bounded ring, ONE page layer that the cross-attention layers read, gated
memory units, differential attention) through ``InferenceEngineV2``, against
the benchmark's plain reference (``benchmark/reference/phi4_flash.py``, the
same file the benchmark imports; it shares no code with ``deepspeed_tpu``).

The size: hidden 64, 8 layers = [scan, window, scan, window, scan (hands
``m``), full, memory unit, cross], window 8, ring pages and blocks of 4,
vocabulary 97 — every kind of layer and both hand-overs."""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.kernels import ssm_ops, window_ops
from deepspeed_tpu.inference.v2.lifecycle import (LifecycleScheduler,
                                                  ServeRequest)
from deepspeed_tpu.models import phi4_flash as P
from deepspeed_tpu.models.serving import (KVRow, SelectiveScanState,
                                          WindowRing)

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load(os.path.join(REPO, "benchmark", "reference",
                               "phi4_flash.py"),
                  "benchmark_reference_phi4_flash")

HF = dict(model_type="phi4flash", vocab_size=97, hidden_size=64,
          intermediate_size=96, num_hidden_layers=8, num_attention_heads=8,
          num_key_value_heads=4, sliding_window=8, mb_per_layer=2,
          layer_norm_eps=1e-5, max_position_embeddings=256,
          tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False)
W = HF["sliding_window"]
PROMPT = 43         # several 16-token chunks: five windows, ten ring pages
TOL = 5e-4          # float32 system against the float32 reference


@pytest.fixture(scope="module")
def model():
    m = P.Phi4FlashLM.from_hf_config(HF, ring_page=4)
    return m, m.init_params(jax.random.PRNGKey(0), jnp.float32)


def ref_weights(params):
    """The program's tree as the reference takes it, a layer at a time."""
    def common(stack, i, j):
        mlp = stack["mlp"][j]
        mixer = stack["first" if j == 0 else "second"]
        return mixer, {
            "ln1_w": mixer["ln"]["scale"][i], "ln1_b": mixer["ln"]["bias"][i],
            "ln2_w": mlp["ln"]["scale"][i], "ln2_b": mlp["ln"]["bias"][i],
            "w1": mlp["w1"]["kernel"][i], "w2": mlp["w2"]["kernel"][i]}

    def scan(stack, i):
        p, w = common(stack, i, 0)
        w.update(w_in=p["in_proj"]["kernel"][i], conv=p["conv"]["kernel"][i],
                 conv_b=p["conv"]["bias"][i], w_x=p["x_proj"]["kernel"][i],
                 w_dt=p["dt_proj"]["kernel"][i], b_dt=p["dt_proj"]["bias"][i],
                 A_log=p["A_log"][i].T, D=p["D"][i],
                 w_out=p["out_proj"]["kernel"][i])
        return w

    def attn(stack, i):
        p, w = common(stack, i, 1)
        w.update(w_qkv=p["wqkv"]["kernel"][i], b_qkv=p["wqkv"]["bias"][i],
                 lam=p["lam"][i], subln=p["subln"][i],
                 w_o=p["wo"]["kernel"][i], b_o=p["wo"]["bias"][i])
        return w

    def memory(stack, i):
        p, w = common(stack, i, 0)
        w.update(w_g=p["in_proj"]["kernel"][i],
                 w_o=p["out_proj"]["kernel"][i])
        return w

    layers = []
    for name, first in (("self", scan), ("mid", scan), ("cross", memory)):
        stack = params[name]
        for i in range(stack["mlp"][0]["w2"]["kernel"].shape[0]):
            layers += [first(stack, i), attn(stack, i)]
    return {"embedding": params["embed"]["embedding"],
            "norm_w": params["norm_f"]["scale"],
            "norm_b": params["norm_f"]["bias"],
            "layers": [lambda w=w: w for w in layers]}


def engine_for(model, **kw):
    cfg = dict(max_tokens=16, max_seqs=4, max_ctx=128, block_size=4,
               dtype=jnp.float32)
    cfg.update(kw)
    return InferenceEngineV2(model[0], model[1],
                             RaggedInferenceEngineConfig(**cfg))


def prompt_tokens(seed=0, n=PROMPT):
    return np.random.default_rng(seed).integers(1, 97, size=n).tolist()


def system_logits(engine, prompt, body, uid=1):
    """Chunked prefill of ``prompt[:body]``, then the rest fed singly
    through slot, ring and pages: logits at positions body-1 .. len-1."""
    got = []
    for pos in range(0, body, 16):
        logits = engine.put([uid], [prompt[pos:min(pos + 16, body)]])
    got.append(np.asarray(logits[0]))
    for tok in prompt[body:]:
        got.append(np.asarray(engine.put([uid], [[tok]])[0]))
    return np.stack(got)


def rel_l2(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


_REFERENCES = {}
PADDED = 128        # one length: the reference compiles once a mutation


def reference_logits(model, prompt, positions, mutation=None):
    """The model is causal: tokens behind ``prompt`` move nothing at
    ``positions``, so every sequence is padded to one length."""
    if mutation not in _REFERENCES:
        _REFERENCES[mutation] = reference.Reference(HF, mutation)
    row = list(prompt) + [0] * (PADDED - len(prompt))
    (out,) = _REFERENCES[mutation].logits(
        [jnp.asarray(row, jnp.int32)], ref_weights(model[1]),
        positions=[positions])
    return np.asarray(out)


def greedy_is_the_references(model, chain, produced):
    """``produced`` continues ``chain`` greedily, teacher-forced."""
    full = list(chain) + [int(t) for t in produced]
    ref = reference_logits(model, full, list(range(len(chain) - 1,
                                                   len(full) - 1)))
    return [int(t) for t in produced] == np.argmax(ref, axis=1).tolist()


BODY = PROMPT - 10      # ten single tokens: past a window and a ring wrap


@pytest.fixture(scope="module")
def got(model):
    prompt = prompt_tokens()
    engine = engine_for(model)
    return prompt, engine, system_logits(engine, prompt, BODY)


def test_the_family_says_what_it_holds(model):
    fam = model[0].serving_family()
    assert fam.row == KVRow(2, 16) and fam.row.token_shape == (4, 16)
    assert fam.page_layers == 1 and fam.page_readers == (2,)
    assert fam.state == SelectiveScanState(3, 128, 16, 4)
    assert fam.state.arrays(jnp.bfloat16) == (
        ((16, 128), jnp.float32), ((3, 128), jnp.bfloat16))
    assert fam.window == WindowRing(2, 8, page=4)
    state, ring = fam.slot_kinds
    assert ring.arrays(jnp.bfloat16) == (((8, 4, 16), jnp.bfloat16),)
    # the published widths: 10 row pairs of 128, five along the lanes of a
    # row, a token in its own 5,120 B (bf16); nothing cut
    full = P.Phi4FlashLM.from_hf_config(dict(
        HF, vocab_size=200064, hidden_size=2560, intermediate_size=10240,
        num_hidden_layers=32, num_attention_heads=40, num_key_value_heads=20,
        sliding_window=512))
    fam = full.serving_family()
    assert fam.row == KVRow(10, 128, lane_heads=5) == KVRow.packed(10, 128)
    assert fam.row.token_shape == (4, 640)
    assert fam.slot_kinds[1].arrays(jnp.bfloat16) == (
        ((512, 4, 640), jnp.bfloat16),)
    assert (fam.state.num_layers, fam.window.num_layers, fam.page_layers,
            fam.page_readers) == (9, 8, 1, (8,))
    assert fam.state.slot_bytes(jnp.bfloat16) == 9 * (16 * 5120 * 4
                                                      + 3 * 5120 * 2)
    assert 3.8e9 < full.num_params() < 3.9e9
    with pytest.raises(NotImplementedError, match="training path is open"):
        model[0].loss_fn(model[1], None, None)
    with pytest.raises(NotImplementedError, match="whole .* pairs"):
        P.Phi4FlashConfig.from_hf(dict(HF, num_hidden_layers=6))


def test_a_pair_count_that_tiles_no_sublanes_lies_along_the_lanes():
    """Six row pairs (12 combined rows tile nothing) are stored three to a
    row, ``[4, 3 * 2 hd]``, in the page layer and in the rings, and the
    system is the reference's through chunks, the window, two ring wraps
    and a fused window: the engine, the append, the ragged kernel, the
    window's three forms and the decode lowering read the stored form off
    the pools."""
    hf = dict(HF, hidden_size=96, num_attention_heads=24,
              num_key_value_heads=12)
    m = P.Phi4FlashLM.from_hf_config(hf, ring_page=4)
    params = m.init_params(jax.random.PRNGKey(1), jnp.float32)
    fam = m.serving_family()
    assert fam.row == KVRow(6, 8, lane_heads=3)
    assert fam.row.token_shape == (4, 24)
    seq = prompt_tokens(seed=5) + prompt_tokens(6, 4)
    ref = np.asarray(reference.Reference(hf).logits(
        [jnp.asarray(seq + [0] * (PADDED - len(seq)), jnp.int32)],
        ref_weights(params), positions=[list(range(BODY - 1, len(seq)))])[0])
    for impl in ("paged", "gather"):
        engine = engine_for((m, params), attn_impl=impl)
        assert engine.kv.pages.shape[2:] == (4, 24)
        assert engine.state_pool.arrays[-1].shape[1:] == (W, 4, 24)
        logits = system_logits(engine, seq[:PROMPT], BODY)
        assert rel_l2(logits, ref[:PROMPT - BODY + 1]) < TOL, impl
        # fused one-step windows, teacher-forced: the reference's greedy
        for i, tok in enumerate(seq[PROMPT:]):
            out = int(engine.decode_batch([1], [tok], 1)[0, 0])
            assert out == int(np.argmax(ref[PROMPT - BODY + 1 + i])), impl


def test_chunked_prefill_then_single_tokens_across_window_and_wrap(model,
                                                                   got):
    prompt, _, logits = got
    ref = reference_logits(model, prompt, list(range(BODY - 1, PROMPT)))
    assert rel_l2(logits, ref) < TOL
    assert max(rel_l2(a, b) for a, b in zip(logits, ref)) < 4 * TOL


def test_the_oracle_path_and_fused_windows(model, got):
    """``attn_impl="gather"`` runs the token-by-token forms; the fused
    window's greedy tokens, teacher-forced, are the reference's."""
    prompt, engine, logits = got
    oracle = engine_for(model, attn_impl="gather")
    assert rel_l2(system_logits(oracle, prompt, BODY), logits) < TOL
    more = prompt_tokens(1, 6)
    seq = prompt + more
    ref = reference_logits(model, seq, list(range(PROMPT - 1, len(seq))))
    assert int(np.argmax(logits[-1])) == int(np.argmax(ref[0]))
    for i, tok in enumerate(more):
        out = int(engine.decode_batch([1], [tok], 1)[0, 0])
        assert out == int(np.argmax(ref[1 + i]))


@pytest.mark.parametrize("mutation", [
    "no_lambda", "window_plus_one", "window_minus_one", "m_after_gate",
    "m_other_layer", "no_d_skip", "no_conv_carry", "cross_own_window",
    "no_subln"])
def test_each_piece_of_the_mathematics_is_noticed(model, got, mutation):
    """Reading any one line of the equations another way moves the reference
    away from the system by far more than the tolerance: the lambda term
    left out, the window off by one either way, ``m`` taken after the ``z``
    gate or from another layer, the ``D`` skip left out, the convolution
    without its carried inputs, a cross layer with a window of its own."""
    prompt, _, logits = got
    ref = reference_logits(model, prompt, list(range(BODY - 1, PROMPT)),
                           mutation)
    assert rel_l2(logits, ref) > 20 * TOL, mutation


def test_a_cross_layer_that_appends_rows_is_noticed(model, monkeypatch):
    """The cross layers READ layer ``M + 1``'s page layer; one that appended
    there (here: ones) would overwrite the rows the later readers need."""
    family = model[0].serving_family

    def appending():
        fam = family()

        def stacks(params):
            first, mid, cross = fam.stacks(params)

            def body(carry, lp, idx, cache, ctx, state):
                rows = jnp.ones((carry[0].shape[0],) + (2, 16),
                                carry[0].dtype)
                cache.at(0).append(rows, rows)
                return cross.body(carry, lp, idx, cache, ctx, state)

            return first, mid, dataclasses.replace(cross, body=body)

        return dataclasses.replace(fam, stacks=stacks)

    monkeypatch.setattr(model[0], "serving_family", appending)
    prompt = prompt_tokens()
    logits = system_logits(engine_for(model), prompt, BODY)
    ref = reference_logits(model, prompt, list(range(BODY - 1, PROMPT)))
    assert rel_l2(logits, ref) > 20 * TOL


def test_a_mixed_batch_of_chunks_and_decode_rows(model):
    """SplitFuse: chunks of two sequences and a decode row in ONE flat
    batch while a third's state waits; every sequence continues from its
    own slot and ring."""
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
    # a fused window of the three, every column its own slot and ring
    seeds = [int(np.argmax(o)) for o in out] + [c[20]]
    chains = [b + seeds[:1], a + seeds[1:2], c[:21]]
    engine.flush([3])
    engine.put([3], [c[:20]])
    toks = engine.decode_batch([2, 1, 3], seeds, 3)
    for col, chain in enumerate(chains):
        assert greedy_is_the_references(model, chain, toks[:, col]), col


def test_a_reused_slot_and_ring_start_from_nothing(model):
    """A flushed sequence's slot goes to the next one, which must see
    neither the state nor the ring rows left there."""
    engine = engine_for(model, max_seqs=1, max_tokens=32)
    sm = engine.state_manager
    engine.put([1], [prompt_tokens(8, 30)])
    slot = sm.get_sequence(1).slot
    engine.flush([1])
    fresh = prompt_tokens(9, 5)         # shorter than the window
    out = np.asarray(engine.put([2], [fresh])[0])
    assert sm.get_sequence(2).slot == slot
    assert rel_l2(out, reference_logits(model, fresh, [4])[0]) < TOL
    # a first token fed as a decode row takes the same slot: nothing again
    engine.flush([2])
    toks = engine.decode_batch([3], [fresh[0]], 3)
    assert greedy_is_the_references(model, fresh[:1], toks[:, 0])


def test_the_scheduler_serves_preempts_and_resumes(model):
    """Through ``LifecycleScheduler``: more requests than slots, a preempted
    request gives slot and ring back and is prefilled again; every answer
    is the reference's greedy continuation; the window account says what
    the rings hold."""
    from deepspeed_tpu.telemetry.trace import get_tracer

    engine = engine_for(model, max_seqs=2, max_tokens=32, max_ctx=64)
    sched = LifecycleScheduler(engine, max_queue=8, window_steps=4,
                               kv_high_watermark=0.0)
    prompts = [prompt_tokens(20 + i, 18 + 3 * i) for i in range(4)]
    reqs = [ServeRequest(uid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    before = len(get_tracer().records())
    for r in reqs:
        assert sched.submit(r).admitted
    guard = 0
    while sched.pending and guard < 400:
        sched.step()
        guard += 1
    assert not sched.pending
    for r, p in zip(reqs, prompts):
        assert r.state.name == "FINISHED", (r.uid, r.state)
        assert greedy_is_the_references(model, p, r.produced), r.uid
    assert engine.state_manager.free_slots == 2
    assert sum(r.preempt_count for r in reqs) > 0
    accounts = [rec.attrs for rec in get_tracer().records()[before:]
                if rec.name == "engine/window_account"]
    assert accounts and all(
        a["window_rows_held"] == W and a["window_layers"] == 2
        and a["shared_read_layers"] == 2 and 0 < a["state_fill"] <= 1
        for a in accounts)


def test_a_long_sequence_holds_no_more_window_rows_than_a_short_one(model):
    """What a sequence owns in a window layer is its ring: ``W`` rows at 2 x
    the window and at 10 x; only the page layer grows, a block a ``block``
    tokens.  The pool is sized by slots, not by ``max_ctx``."""
    engine = engine_for(model, max_seqs=2, max_tokens=32)
    state, carry, ring = engine.state_pool.arrays
    assert ring.shape == (2 * 2 + 1, W, 4, 16)
    assert state.shape == (3 * 2 + 1, 16, 128) and carry.shape[1:] == (3, 128)
    assert engine_for(model, max_seqs=2, max_ctx=1024
                      ).state_pool.arrays[2].shape == ring.shape
    short, long = prompt_tokens(11, 2 * W), prompt_tokens(12, 10 * W)
    engine.put([1], [short])
    for pos in range(0, len(long), 32):
        out = engine.put([2], [long[pos:pos + 32]])
    sm = engine.state_manager
    assert len(sm.get_sequence(1).blocks) == 4 \
        and len(sm.get_sequence(2).blocks) == 20
    ref = reference_logits(model, long, [len(long) - 1])[0]
    assert rel_l2(np.asarray(out[0]), ref) < TOL
    # a row older than the window is not in the ring at all: the slot of
    # sequence 2 in window layer 0 holds its last W tokens' rows only
    held = np.asarray(engine.state_pool.arrays[2])
    assert held.shape[1] == W


def test_the_engine_refuses_what_a_state_cannot_do(model):
    for kw in (dict(prefix_cache=True), dict(host_tier_mb=1.0)):
        with pytest.raises(NotImplementedError,
                           match=r"recurrent state \(SelectiveScanState\)"):
            engine_for(model, **kw)


def _scan_case(seed=0):
    """A ragged batch (a chunk of 70 tokens continuing a state, one of 33
    from position 0, a single token, an empty row)."""
    T, S, C, N = 112, 4, 128, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    kind = SelectiveScanState(1, C, N, 4)
    q_len = jnp.asarray([70, 33, 1, 0], jnp.int32)
    cu = jnp.asarray([0, 70, 103, 104, 104], jnp.int32)
    fresh = jnp.asarray([False, True, False, True])
    ctx = q_len + jnp.where(fresh, 0, 7)
    pool = (jax.random.normal(ks[0], (S + 2, N, C)),
            jax.random.normal(ks[1], (S + 2, 3, C)))
    rows = jnp.asarray([3, 0, 1, S + 1], jnp.int32)
    seq_of = jnp.minimum(jnp.searchsorted(cu[1:], jnp.arange(T),
                                          side="right"), S - 1)
    batch = dict(q_len=q_len, ctx_len=ctx, q_offset=cu[:-1],
                 seq_of_token=seq_of.astype(jnp.int32),
                 pos_of_token=jnp.arange(T) - cu[seq_of]
                 + jnp.where(fresh, 0, 7)[seq_of])
    u = jax.random.normal(ks[2], (T, C))
    wx = jax.random.normal(ks[3], (C, 2 * N + C)) / C ** 0.5

    def proj(x):
        r = x @ wx
        return jax.nn.softplus(r[:, 2 * N:] - 1.0), r[:, :N], r[:, N:2 * N]

    A = -jax.random.uniform(ks[4], (N, C), minval=0.05, maxval=4.0)
    args = (u, jax.random.normal(ks[5], (4, C)) / 2,
            jax.random.normal(ks[6], (C,)) / 3, proj, A,
            jax.random.normal(ks[7], (C,)))
    run = lambda mode: ssm_ops.ssm_mix(  # noqa: E731
        *args, pool, rows, kind=kind, mode=mode, batch=batch,
        valid=jnp.arange(T) < 104)
    return run, S


#: a decode batch: (pool row, tokens behind it) a sequence row — ``None``
#: = a padded row (it names the trash row); ``T`` tokens in the flat batch
DECODE_BATCHES = {
    "a kept row": ([(3, 7)], 1),
    "a fresh row whose slot holds NaN": ([(1, 0), (3, 7)], 2),
    "padded rows that all name the trash row":
        ([(3, 7), None, None, None], 4),
    "fewer rows than tokens": ([(3, 7), (0, 2)], 8),
}


def _decode_case(C, seqs, T, seed=0):
    """One token a sequence through ``ssm_mix``: → (run(mode, pool, behind)
    → (y, pool), the pool with NaN in slot 1 and in the trash row, the
    tokens behind each sequence)."""
    N, K, S = 16, 4, len(seqs)
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    kind = SelectiveScanState(1, C, N, K)
    trash = 5
    pool = tuple(jax.random.normal(k, (trash + 1, n, C)).astype(dt)
                 .at[jnp.asarray([1, trash])].set(jnp.nan)
                 for k, n, dt in ((ks[0], N, jnp.float32),
                                  (ks[1], K - 1, jnp.float32)))
    live = jnp.asarray([s is not None for s in seqs])
    rows = jnp.asarray([trash if s is None else s[0] for s in seqs],
                       jnp.int32)
    q_len = live.astype(jnp.int32)
    offset = jnp.minimum(jnp.arange(S), T).astype(jnp.int32)
    seq_of = jnp.minimum(jnp.arange(T), S - 1).astype(jnp.int32)
    u = jax.random.normal(ks[2], (T, C))
    wx = jax.random.normal(ks[3], (C, 3 * N)) / C ** 0.5
    wdt = jax.random.normal(ks[3], (N, C)) / N ** 0.5

    def proj(x):         # delta through a low rank, as the model's
        r = x @ wx
        return (jax.nn.softplus(r[:, 2 * N:] @ wdt - 1.0), r[:, :N],
                r[:, N:2 * N])

    args = (jax.random.normal(ks[5], (K, C)) / 2,
            jax.random.normal(ks[6], (C,)) / 3, proj,
            -jax.random.uniform(ks[4], (N, C), minval=0.05, maxval=4.0),
            jax.random.normal(ks[7], (C,)))

    def run(mode, pool, behind, u=u):
        behind = jnp.asarray(behind, jnp.int32)
        batch = dict(q_len=q_len, ctx_len=q_len + behind, q_offset=offset,
                     seq_of_token=seq_of, pos_of_token=behind[seq_of])
        return ssm_ops.ssm_mix(u, *args, pool, rows, kind=kind, mode=mode,
                               batch=batch, valid=jnp.arange(T) < live.sum())

    return run, pool, [0 if s is None else s[1] for s in seqs]


@pytest.mark.parametrize("case", list(DECODE_BATCHES) + [
    "two consecutive steps through a donated pool"])
@pytest.mark.parametrize("C", [128, 5120])
def test_the_decode_kernels_agree_with_the_oracle_and_the_ragged_form(
        C, case):
    """``ssm_decode`` and the convolution step (Pallas, interpreted here)
    against the token-by-token form and the blocked scan on one-token
    chunks: outputs and BOTH pools to float32 round-off, at a tiny width
    and at the model's (a ``[16, 5120]`` state, four taps).  A fresh row
    starts from zeros though its slot holds NaN; padded rows only ever
    touch the trash row; a slot no row names is left as it was."""
    seqs, T = DECODE_BATCHES.get(case, DECODE_BATCHES[
        "a fresh row whose slot holds NaN"])
    run, pool, behind = _decode_case(C, seqs, T)
    steps = 1 + (case not in DECODE_BATCHES)
    step = jax.jit(run, static_argnums=0, donate_argnums=1)
    got = {}
    for mode in ("decode", "ragged", "oracle"):
        p, ys = jax.tree.map(jnp.copy, pool), []
        for i in range(steps):
            u = jax.random.normal(jax.random.PRNGKey(10 + i), (T, C))
            y, p = step(mode, p, [b + i for b in behind], u)
            ys.append(y)
        got[mode] = ys, p
    live = np.asarray([i for i, s in enumerate(seqs) if s is not None])
    named = np.asarray(sorted(seqs[i][0] for i in live))
    others = np.asarray([r for r in range(5) if r not in named])
    ys, (state, carry) = got["decode"]
    assert np.isfinite(np.asarray(state[named])).all()
    for mode in ("oracle", "ragged"):
        ys0, (state0, carry0) = got[mode]
        for y, y0 in zip(ys, ys0):
            np.testing.assert_allclose(y[live], y0[live], atol=2e-5,
                                       rtol=2e-5, err_msg=mode)
        np.testing.assert_allclose(state[named], state0[named], atol=2e-5,
                                   rtol=2e-5, err_msg=mode)
        np.testing.assert_allclose(carry[named], carry0[named], atol=1e-6,
                                   err_msg=mode)
    for new, old in zip((state, carry), pool):
        np.testing.assert_array_equal(new[others], old[others])


def test_the_decode_kernel_takes_its_channels_in_blocks(monkeypatch):
    """All of a row's channels a grid step where the blocks fit the VMEM
    they may take (the model's [16, 5120]: 1.9 MB), whole lane tiles that
    divide them where they do not — and the blocked walk is the same
    update."""
    assert ssm_ops._channel_block(16, 5120) == 5120
    assert ssm_ops._channel_block(16, 1 << 20) == 16384
    seqs, T = DECODE_BATCHES["fewer rows than tokens"]
    run, pool, behind = _decode_case(256, seqs, T)
    whole = run("decode", pool, behind)
    monkeypatch.setattr(ssm_ops, "_STATE_VMEM", 6 * 4 * 16 * 128)
    assert ssm_ops._channel_block(16, 256) == 128
    blocked = run("decode", pool, behind)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(blocked)):
        np.testing.assert_array_equal(np.asarray(a)[:5], np.asarray(b)[:5])


def test_every_traced_scan_says_which_form_it_compiled(model):
    """``attn/ssm_layout``, one ring record a traced scan layer: a decode
    window took both kernels, a prefill step the blocked scan, the oracle
    engine the token-by-token form."""
    from deepspeed_tpu.telemetry.trace import get_tracer

    def layouts(**kw):
        before = len(get_tracer().records())
        engine = engine_for(model, **kw)
        engine.put([1], [prompt_tokens(5, 9)])
        engine.decode_batch([1], [7], 2)
        return [rec.attrs for rec in get_tracer().records()[before:]
                if rec.name == "attn/ssm_layout"]

    recs = layouts()
    forms = {(a["form"], a["impl"], a["conv_impl"]) for a in recs}
    assert forms == {("ragged", "xla", "xla"), ("decode", "kernel", "kernel")}
    for a in recs:
        assert (a["channels"], a["state_dim"], a["conv_kernel"],
                a["state_dtype"]) == (128, 16, 4, "float32")
        decode = a["form"] == "decode"
        # a window's rows are its riders, a step's its token bucket
        assert a["rows"] == (1 if decode else 16)
        assert a["channel_block"] == (128 if decode else 0)
    # the three scan layers lie in two stacks: a program traces two bodies
    by_form = [sum(a["form"] == f for a in recs) for f in ("ragged", "decode")]
    assert by_form[0] % 2 == 0 and by_form[1] % 2 == 0 and min(by_form) >= 2
    assert {(a["form"], a["impl"], a["conv_impl"])
            for a in layouts(attn_impl="gather")} == {("oracle", "xla", "xla")}


def test_the_blocked_scan_agrees_with_the_token_by_token_oracle():
    run, S = _scan_case()
    (y, (state, carry)), (y0, (state0, carry0)) = run("ragged"), run("oracle")
    np.testing.assert_allclose(y[:104], y0[:104], atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(state[:S], state0[:S], atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(carry[:S], carry0[:S], atol=1e-6)
    # the slot no sequence of the batch owns is untouched
    assert (np.asarray(state[2]) == np.asarray(state0[2])).all()


def test_the_ragged_window_reads_no_row_older_than_the_window():
    """The ragged form against the token-by-token one on a ring that holds
    NaN wherever a row is out of every query's window: a chunk continuing a
    sequence, one from position 0 shorter than the window, a single
    token."""
    T, S, H, KV, hd, Wn = 48, 4, 4, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (T, H, hd))
    k = jax.random.normal(ks[1], (T, KV, hd))
    v = jax.random.normal(ks[2], (T, KV, hd))
    q_len = jnp.asarray([29, 5, 1, 0], jnp.int32)
    before = jnp.asarray([13, 0, 3, 0], jnp.int32)
    cu = jnp.asarray([0, 29, 34, 35, 35], jnp.int32)
    seq_of = jnp.minimum(jnp.searchsorted(cu[1:], jnp.arange(T),
                                          side="right"), S - 1)
    batch = dict(q_len=q_len, ctx_len=q_len + before, cu_q_lens=cu,
                 seq_of_token=seq_of.astype(jnp.int32),
                 pos_of_token=jnp.arange(T) - cu[seq_of] + before[seq_of])
    ring = jax.random.normal(ks[3], (S + 2, Wn, 2 * KV, hd))
    # sequence 2 has 3 tokens behind it: slots 3.. of its ring are nobody's
    ring = ring.at[1, 3:].set(jnp.nan)
    # sequence 1 starts at position 0: its whole ring is the last owner's
    ring = ring.at[0].set(jnp.nan)
    rows = jnp.asarray([3, 0, 1, S + 1], jnp.int32)
    run = lambda mode: window_ops.window_attention(  # noqa: E731
        q, k, v, ring, rows, mode=mode, batch=batch,
        valid=jnp.arange(T) < 35, num_kv_heads=KV, scale=0.25, page=4)
    (out, new), (out0, new0) = run("ragged"), run("oracle")
    assert np.isfinite(np.asarray(out[:35])).all()
    np.testing.assert_allclose(out[:35], out0[:35], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(new[3], new0[3], atol=1e-6)
    np.testing.assert_allclose(new[0, :5], new0[0, :5], atol=1e-6)
    np.testing.assert_allclose(new[1, :4], new0[1, :4], atol=1e-6)
