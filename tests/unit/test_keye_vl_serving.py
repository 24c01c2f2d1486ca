"""Keye-VL-2.0's language model (grouped-query attention that reads only the
``topk`` cached tokens a learned indexer picks, an index key beside every K/V
row, softmax-routed experts as a chip's share, M-RoPE) through
``InferenceEngineV2``, against the benchmark's plain reference
(``benchmark/reference/keye_vl.py``, the same file the benchmark imports; it
shares no code with ``deepspeed_tpu``).  Tiny widths, ``topk`` 16 over
contexts of 40-150."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.kernels import sparse_ops
from deepspeed_tpu.inference.v2.lifecycle import (LifecycleScheduler,
                                                  ServeRequest)
from deepspeed_tpu.models import keye_vl as K

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load(os.path.join(REPO, "benchmark", "reference", "keye_vl.py"),
                  "benchmark_reference_keye_vl")

TOPK = 16
#: published keys at a tiny size; 8 experts of which this chip holds 4
#: (ep_size 2, the second share)
HF = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    rope_theta=10000000,
    rope_scaling={"mrope_section": [4, 6, 6], "rope_type": "default"},
    mrope_section=[4, 6, 6],
    sa_config=dict(indexer_num_heads=4, indexer_head_dim=16,
                   indexer_num_kv_heads=1, topk=TOPK, q_chunk_size=512,
                   kv_chunk_size=512),
    num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32,
    norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
    rms_norm_eps=1e-6, max_position_embeddings=256,
    tie_word_embeddings=False, ep_size=2, ep_rank=1)
PROMPT = 75         # several 16-token chunks, no multiple of 16 or of 8
TOL = 5e-4          # float32 system against the float32 reference


@pytest.fixture(scope="module")
def model():
    m = K.KeyeVLLM.from_hf_config(HF)
    return m, m.init_params(jax.random.PRNGKey(0), jnp.float32)


_NAMES = dict(
    in_norm=("in_norm", "scale"), post_norm=("post_norm", "scale"),
    w_q=("q_proj", "kernel"), w_k=("k_proj", "kernel"),
    w_v=("v_proj", "kernel"), q_norm=("q_norm", "scale"),
    k_norm=("k_norm", "scale"), w_o=("o_proj", "kernel"),
    w_qi=("index_q", "kernel"), w_ki=("index_k", "kernel"),
    w_wi=("index_w", "kernel"), router=("router", "kernel"))


def ref_weights(params):
    stack, experts = params["layers"], params["experts"]
    layers = []
    for l in range(stack["in_norm"]["scale"].shape[0]):
        w = {k: stack[a][b][l] for k, (a, b) in _NAMES.items()}
        w.update(e_gate=experts["gate"][l], e_up=experts["up"][l],
                 e_down=experts["down"][l])
        layers.append(lambda w=w: w)
    return {"embedding": params["embed"]["embedding"],
            "norm": params["norm_f"]["scale"],
            "head": params["lm_head"]["kernel"], "layers": layers}


def engine_for(model, **kw):
    m, params = model
    cfg = dict(max_tokens=16, max_seqs=4, max_ctx=160, block_size=8,
               dtype=jnp.float32)
    cfg.update(kw)
    return InferenceEngineV2(m, params, RaggedInferenceEngineConfig(**cfg))


def prompt_tokens(seed=0, n=PROMPT):
    return np.random.default_rng(seed).integers(1, 256, size=n).tolist()


def system_logits(engine, prompt, body, uid=1, start=0):
    """Chunked prefill of ``prompt[start:body]``, then the rest fed singly:
    logits at positions body-1 .. len-1."""
    got = []
    for pos in range(start, body, 16):
        logits = engine.put([uid], [prompt[pos:min(pos + 16, body)]])
    got.append(np.asarray(logits[0]))
    for tok in prompt[body:]:
        got.append(np.asarray(engine.put([uid], [[tok]])[0]))
    return np.stack(got)


def rel_l2(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def reference_logits(model, prompt, positions, mutation=None, hf=HF,
                     pos3=None):
    (out,) = reference.Reference(hf, mutation).logits(
        [jnp.asarray(prompt, jnp.int32)], ref_weights(model[1]),
        positions=[positions], pos3=None if pos3 is None else [pos3])
    return np.asarray(out)


def greedy_is_the_references(model, prompt, produced):
    """Teacher-forced, one reference run: every produced token is the
    reference's greedy choice after ``prompt`` and the tokens before it."""
    full = list(prompt) + [int(t) for t in produced]
    n = len(prompt)
    ref = reference_logits(model, full[:-1], list(range(n - 1, len(full) - 1)))
    assert np.argmax(ref, axis=-1).tolist() == full[n:]


@pytest.fixture(scope="module")
def got(model):
    prompt = prompt_tokens()
    engine = engine_for(model)
    body = PROMPT - 4
    return prompt, body, system_logits(engine, prompt, body)


@pytest.mark.parametrize("impl", ["paged", "gather"])
def test_prefill_single_tokens_and_fused_windows(model, impl):
    """Chunks whose sets are real (contexts of 17-75 over ``topk`` 16),
    single tokens through the cache, one-step fused windows."""
    from deepspeed_tpu.telemetry.trace import get_tracer

    tracer = get_tracer()
    before = len(tracer.records())
    prompt = prompt_tokens()
    engine = engine_for(model, attn_impl=impl)
    body = PROMPT - 4
    ref = reference_logits(model, prompt, list(range(body - 1, PROMPT)))
    got = system_logits(engine, prompt, body)
    assert rel_l2(got, ref) < TOL
    more = prompt_tokens(1, 6)
    seq = prompt + more
    ref = reference_logits(model, seq, list(range(PROMPT - 1, len(seq))))
    assert int(np.argmax(got[-1])) == int(np.argmax(ref[0]))
    for i, tok in enumerate(more):
        out = int(engine.decode_batch([1], [tok], 1)[0, 0])
        assert out == int(np.argmax(ref[1 + i]))
    records = tracer.records()[before:]
    layouts = [r.attrs for r in records if r.name == "attn/sparse_layout"]
    if impl == "paged":
        assert layouts and all(
            a["topk"] == TOPK and a["index_heads"] == 4
            and a["index_dim"] == 16 and a["index_row_bytes"] == 64
            and a["kv_row_bytes"] == 2 * 2 * 32 * 4 and a["page_size"] == 8
            for a in layouts)
        assert {a["read"] for a in layouts} == {"xla_gather", "masked_walk"}
        # (off the chip the decode score is the XLA gather too)
        assert {a["score"] for a in layouts} == {"xla_gather"}
    accounts = [r.attrs for r in records if r.name == "engine/window_account"]
    assert accounts
    for i, a in enumerate(accounts):
        ctx = PROMPT + i + 1            # one row, one step a window
        assert a["sparse_tokens_scored"] == 2 * ctx
        assert a["sparse_tokens_selected"] == 2 * TOPK
        assert a["sparse_select_share"] == pytest.approx(TOPK / ctx)
        assert a["sparse_dense_queries"] == 0
        assert a["moe_pairs_dropped"] == 0 and a["moe_pairs_elsewhere"] > 0


@pytest.mark.parametrize("mutation", [
    "recent_topk", "dense", "future_in_chunk", "no_relu", "no_w",
    "no_renorm", "no_qk_norm", "ties_to_higher"])
def test_each_piece_of_the_mathematics_is_noticed(model, got, mutation):
    """The set replaced by the most recent ``topk``, by the whole context, by
    one that may hold later tokens of the query's chunk; the ReLU or the head
    weights dropped; the expert weights not renormalised: each moves the
    reference away from the system by far more than the tolerance."""
    prompt, body, logits = got
    ref = reference_logits(model, prompt, list(range(body - 1, PROMPT)),
                           mutation)
    assert rel_l2(logits, ref) > 20 * TOL, mutation


@pytest.fixture
def score(request, monkeypatch):
    """Which form of the decode score a test's programs are traced with:
    ``pallas_walk`` steers ``sparse_ops._walk_serves`` on (the kernel then
    runs in interpret mode here), in the test and not through an option."""
    if request.param == "pallas_walk":
        monkeypatch.setattr(sparse_ops, "_walk_serves", lambda ix, Hi: True)
    return request.param


both_scores = pytest.mark.parametrize(
    "score", ["xla_gather", "pallas_walk"], indirect=True)


@both_scores
def test_contexts_below_at_and_above_topk_in_one_batch(model, score):
    """SplitFuse: chunks of sequences whose contexts end below, at and above
    ``topk``, and a decode row, in ONE flat batch; then one fused window of
    all of them."""
    from deepspeed_tpu.telemetry.trace import get_tracer

    before = len(get_tracer().records())
    a, b, c, d = (prompt_tokens(2, 41), prompt_tokens(3, TOPK + 1),
                  prompt_tokens(4, 10), prompt_tokens(5, 60))
    engine = engine_for(model, max_tokens=64)
    engine.put([1], [a[:30]])
    engine.put([4], [d[:59]])
    out = np.asarray(engine.put(
        [1, 2, 3, 4], [a[30:40], b[:TOPK], c[:9], [d[59]]]))
    for row, (seq, n) in enumerate(((a, 40), (b, TOPK), (c, 9), (d, 60))):
        ref = reference_logits(model, seq[:n], [n - 1])
        assert rel_l2(out[row], ref[0]) < TOL, row
    toks = np.asarray(engine.decode_batch([1, 2, 3],
                                          [a[40], b[TOPK], c[9]], 3))
    for row, seq in enumerate((a, b, c)):
        greedy_is_the_references(model, seq, toks[:, row])
    decode = [r.attrs["score"] for r in get_tracer().records()[before:]
              if r.name == "attn/sparse_layout"
              and r.attrs["read"] == "xla_gather"]
    assert decode and set(decode) == {score}


def test_a_grafted_prefix_brings_its_index_keys(model):
    """A document longer than ``topk`` is committed to the trie, flushed and
    grafted into a new sequence: the tokens after it read the logits of a
    fresh prefill (the index keys live under the blocks' ids).  With the
    grafted blocks' index keys zeroed the same tokens do NOT."""
    doc, tail = prompt_tokens(6, 61), prompt_tokens(7, 9)
    turn = doc + tail
    ref = reference_logits(model, turn, list(range(len(doc) + 3, len(turn))))
    engine = engine_for(model, prefix_cache=True)
    system_logits(engine, doc, len(doc), uid=1)
    engine.commit_prefix(1, doc, allow_partial=True)
    engine.flush([1])
    grafted = engine.graft_prefix(2, turn)
    assert grafted >= len(doc) - 8 and grafted > 3 * TOPK
    got = system_logits(engine, turn, len(doc) + 4, uid=2, start=grafted)
    assert rel_l2(got, ref) < TOL
    fresh = system_logits(engine_for(model), turn, len(doc) + 4, uid=3)
    assert rel_l2(got, fresh) < 1e-5
    # mutation: the graft without its index keys
    engine.flush([2])
    grafted = engine.graft_prefix(4, turn)
    kv, ix = engine.kv.pages
    blocks = engine.state_manager.get_sequence(4).blocks[:grafted // 8]
    phys = jnp.asarray([b + l * engine._num_blocks for l in range(2)
                        for b in blocks])
    engine.kv.update((kv, ix.at[phys].set(0)))
    got = system_logits(engine, turn, len(doc) + 4, uid=4, start=grafted)
    assert rel_l2(got, ref) > 20 * TOL


def test_the_scheduler_preempts_and_resumes(model):
    """Through ``LifecycleScheduler`` on a pool too small for all: a
    preempted request's blocks are freed and prefilled again (K/V rows and
    index keys both), and every answer is the reference's greedy
    continuation."""
    engine = engine_for(model, max_seqs=2, max_tokens=32, max_ctx=96,
                        num_blocks=14)
    sched = LifecycleScheduler(engine, max_queue=8, window_steps=4,
                               kv_high_watermark=0.5)
    prompts = [prompt_tokens(20 + i, 30 + 7 * i) for i in range(3)]
    reqs = [ServeRequest(uid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    for r in reqs:
        assert sched.submit(r).admitted
    guard = 0
    while sched.pending and guard < 400:
        sched.step()
        guard += 1
    assert not sched.pending
    assert sum(r.preempt_count for r in reqs) > 0
    for r, p in zip(reqs, prompts):
        assert r.state.name == "FINISHED", (r.uid, r.state)
        greedy_is_the_references(model, p, r.produced)


@pytest.fixture(scope="module")
def small_engine(model):
    engine = engine_for(model)
    engine.put([1], [prompt_tokens(8, 12)])
    return engine


@pytest.mark.parametrize("what", ["host_tier", "kv_ship", "verify"])
def test_what_ships_rows_without_index_keys_is_refused_by_name(
        model, small_engine, what):
    if what == "host_tier":
        with pytest.raises(ValueError, match="index keys"):
            engine_for(model, host_tier_mb=1.0)
        return
    engine = small_engine
    if what == "kv_ship":
        from deepspeed_tpu.inference.v2.kv_ship import export_kv

        with pytest.raises(NotImplementedError, match="index keys"):
            export_kv(engine, 1, prompt_tokens(8, 12))
    else:
        with pytest.raises(NotImplementedError, match="index keys"):
            engine.verify_decode([1], [5], [[6, 7]])


# --------------------------------------------------------------------- #
# The selection alone
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("case", ["ties", "zeros", "short", "random"])
def test_the_set_is_exact_and_ties_go_to_the_lower_position(case):
    rng = np.random.default_rng(11)
    k, C = 16, 256
    scores = rng.normal(size=(5, C)).astype(np.float32)
    valid = np.ones((5, C), bool)
    if case == "ties":          # the k-th largest value is shared
        scores = rng.integers(0, 6, size=(5, C)).astype(np.float32)
    elif case == "zeros":       # w·relu(...) makes 0.0 and -0.0
        scores = np.where(rng.random((5, C)) < 0.97, 0.0, scores)
        scores[:, ::2] *= -1.0
        scores = np.maximum(scores, -0.0).astype(np.float32)
    elif case == "short":       # fewer valid than k: all of them
        valid[:, 9:] = False
    u = sparse_ops._ordered(jnp.asarray(scores), jnp.asarray(valid))
    chosen = np.asarray(sparse_ops._select(u, k))
    pos, count = sparse_ops._compact(jnp.asarray(chosen), k)
    pos, count = np.asarray(pos), np.asarray(count)
    for r in range(5):
        order = sorted(range(C), key=lambda s: (-float(scores[r, s]), s))
        want = sorted(s for s in order if valid[r, s])[:k] \
            if case == "short" else sorted(order[:k])
        assert sorted(np.flatnonzero(chosen[r]).tolist()) == want, (case, r)
        assert count[r] == len(want)
        assert pos[r, :count[r]].tolist() == want


def test_index_keys_are_appended_two_a_row():
    ix = jnp.zeros((3, 4, 8), jnp.float32)
    kv = jnp.zeros((3, 8, 2, 4), jnp.float32)
    ki = jnp.arange(5 * 4, dtype=jnp.float32).reshape(5, 4) + 1
    page = jnp.asarray([1, 1, 1, 2, 1])
    off = jnp.asarray([0, 3, 4, 7, 6])
    z = jnp.zeros((5, 1, 4))
    _, out = sparse_ops.indexed_append((kv, ix), z, z, ki, page, off)
    out = np.asarray(out)
    assert (out[1, 0, :4] == np.asarray(ki[0])).all()
    assert (out[1, 3, :4] == np.asarray(ki[1])).all()
    assert (out[1, 0, 4:] == np.asarray(ki[2])).all()
    assert (out[2, 3, 4:] == np.asarray(ki[3])).all()
    assert (out[1, 2, 4:] == np.asarray(ki[4])).all()
    assert np.count_nonzero(out) == 5 * 4


# --------------------------------------------------------------------- #
# The decode score as a page walk
# --------------------------------------------------------------------- #
#: pages of 8 (4 rows of two keys): 32 pages fill 128 rows, a chunk is 32
#: pages = 256 tokens; tables of 70 pages (two chunks and 6 pages)
WALK = dict(ps=8, NB=70, chunk=256)
WALK_CASES = {
    # contexts of one batch, a row each
    "a_padding_row_among_real_ones": [0, 300, 0, 41],
    "under_one_page": [5, 1, 7, 8],
    "exactly_a_chunk": [256, 512, 256, 3],
    "a_chunk_and_one_token": [257, 513, 255, 249],
    "the_longest_the_table_holds": [560, 559, 553, 2],
    "every_row_is_padding": [0, 0, 0, 0],
    "one_sequence": [400],
    "a_grafted_prefix_shared_by_two": [300, 420, 130, 77],
    "unvisited_pages_and_the_trash_page_hold_nan": [0, 300, 9, 256],
}


# (jitted once: the cases of one batch width share a trace of the kernel)
_walk_scores = jax.jit(sparse_ops.index_score_paged)
_gather_scores = jax.jit(sparse_ops._index_scores)


@pytest.mark.parametrize("case", list(WALK_CASES))
def test_the_page_walk_scores_as_the_gather(case):
    """``index_score_paged`` (interpret mode) against ``_index_scores``:
    page ids out of order, a table that is no multiple of the chunk; the
    scores equal to float32 rounding wherever a token is cached, zero from
    there on, and the ``_select`` sets equal."""
    ps, NB = WALK["ps"], WALK["NB"]
    ctx = np.asarray(WALK_CASES[case], np.int32)
    S, Hi, di, half = len(ctx), 4, 16, ps // 2
    rng = np.random.default_rng(31)
    pages = S * NB + 1                      # the last: the trash page
    table = rng.permutation(S * NB).reshape(S, NB).astype(np.int32)
    if case == "a_grafted_prefix_shared_by_two":
        table[1, :16] = table[0, :16]       # 128 tokens under the same ids
        table[3, :9] = table[0, :9]
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    ix = jax.random.normal(keys[0], (pages, half, 2 * di))
    qi = jax.random.normal(keys[1], (S, Hi, di))
    w = jax.random.normal(keys[2], (S, Hi))
    want = np.asarray(_gather_scores(
        qi, w, ix, jnp.asarray(table), jnp.max(jnp.asarray(ctx))))
    if case == "unvisited_pages_and_the_trash_page_hold_nan":
        used = np.zeros(pages, bool)
        for s, n in enumerate(ctx):
            used[table[s, :-(-int(n) // ps)]] = True
        ix = jnp.where(jnp.asarray(used)[:, None, None], ix, jnp.nan)
    got = np.asarray(_walk_scores(
        qi, w, ix, jnp.asarray(ctx), jnp.asarray(table)))
    C = got.shape[1]
    assert C % 128 == 0 and NB * ps <= C < NB * ps + WALK["chunk"]
    live = np.arange(C)[None, :] < ctx[:, None]
    assert (got[~live] == 0).all()
    np.testing.assert_allclose(got[live], want[:, :C][live], rtol=1e-6,
                               atol=1e-6)
    sets = [np.asarray(sparse_ops._select(sparse_ops._ordered(
        jnp.asarray(x), jnp.asarray(live)), TOPK)) for x in (got, want[:, :C])]
    assert (sets[0] == sets[1]).all()
    assert (sets[0].sum(-1) == np.minimum(ctx, TOPK)).all()


# --------------------------------------------------------------------- #
# The model's own forward: M-RoPE sections, the shares
# --------------------------------------------------------------------- #
def _unequal_streams(n, seed=12):
    """A text prefix, then a 'grid' whose height and width streams differ
    from the temporal one, then text again."""
    rng = np.random.default_rng(seed)
    t = np.arange(n)
    return np.stack([t, t + rng.integers(0, 9, n), t + rng.integers(0, 5, n)]
                    ).astype(np.int32)


@pytest.mark.parametrize("mutation", [None, "permute_sections"])
def test_unequal_mrope_streams(model, mutation):
    m, params = model
    ids = prompt_tokens(13, 50)
    pos3 = _unequal_streams(50)
    got = np.asarray(K.forward(params, jnp.asarray(ids, jnp.int32),
                               jnp.asarray(pos3), m.config))
    ref = reference_logits(model, ids, list(range(50)), mutation, pos3=pos3)
    if mutation is None:
        assert rel_l2(got, ref) < TOL
    else:
        assert rel_l2(got, ref) > 20 * TOL


def test_the_shares_add_up():
    """The routed parts of both shares of a layer equal the uncut layer's."""
    whole_hf = dict(HF, num_experts=8, ep_size=1, ep_rank=0)
    whole = K.KeyeVLLM.from_hf_config(whole_hf)
    params = whole.init_params(jax.random.PRNGKey(3), jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(4), (23, 64))
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    experts = jax.tree.map(lambda a: a[0], params["experts"])
    full, pairs = K.expert_layer(h, lp, whole.config, experts=experts)
    assert int(pairs.sum()) == 23 * 2
    parts, held = 0.0, 0
    for rank in range(2):
        share = K.KeyeVLLM.from_hf_config(dict(HF, ep_rank=rank)).config
        mine = jax.tree.map(lambda a: a[4 * rank:4 * rank + 4], experts)
        y, p = K.expert_layer(h, lp, share, experts=mine)
        parts = parts + y
        held += int(p[:4].sum())
        assert int(p.sum()) == 23 * 2
    assert held == 23 * 2
    np.testing.assert_allclose(np.asarray(parts), np.asarray(full),
                               rtol=1e-5, atol=1e-5)


@both_scores
def test_long_tables_take_every_width_and_several_passes(score):
    """The operations alone over tables longer than a scoring pass (200 pages
    of 8, three sequences whose contexts fall into the three widths of the
    prefill form): the ragged form and the decode form (its score by the XLA
    gather, and by the page walk: 7 chunks of 32 pages) against the oracle
    (the padded context, ``lax.top_k``)."""
    from deepspeed_tpu.models.serving import IndexKey

    H, KV, hd, Hi, di, ps, NB = 4, 2, 16, 2, 8, 8, 200
    index = IndexKey(dim=di, heads=Hi, topk=TOPK)
    ctx = np.asarray([300, 1100, 1590], np.int32)
    q_len = np.asarray([5, 7, 4], np.int32)
    rng = np.random.default_rng(21)
    pages = 3 * NB + 1
    table = rng.permutation(3 * NB).reshape(3, NB).astype(np.int32)
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    kv = jax.random.normal(keys[0], (pages, ps, 2 * KV, hd))
    ix = jax.random.normal(keys[1], (pages, ps // 2, 2 * di))
    T = int(q_len.sum())
    q = jax.random.normal(keys[2], (T, H, hd))
    qi = jax.random.normal(keys[3], (T, Hi, di))
    w = jax.random.normal(keys[4], (T, Hi))
    cu = np.concatenate([[0], np.cumsum(q_len)]).astype(np.int32)
    kw = dict(index=index, num_kv_heads=KV, scale=hd ** -0.5)
    got = np.asarray(sparse_ops.sparse_ragged_attention(
        (q, qi, w), (kv, ix), jnp.asarray(ctx), jnp.asarray(table),
        jnp.asarray(cu), block_q=16, pages_per_chunk=2, **kw))
    mq = int(q_len.max())
    rows = np.clip(cu[:-1, None] + np.arange(mq)[None, :], 0, T - 1)
    seq = lambda a: jnp.asarray(np.asarray(a)[rows])  # noqa: E731
    want = np.asarray(sparse_ops.sparse_attend_dense(
        (seq(q), seq(qi), seq(w)), (kv, ix), jnp.asarray(table),
        jnp.asarray(q_len), jnp.asarray(ctx), **kw))
    for s in range(3):
        np.testing.assert_allclose(got[cu[s]:cu[s + 1]],
                                   want[s, :q_len[s]], rtol=2e-4, atol=2e-5)
    # one query a sequence: each sequence's last
    last = cu[1:] - 1
    got = np.asarray(sparse_ops.sparse_decode_attention(
        (q[last], qi[last], w[last]), (kv, ix), jnp.asarray(ctx),
        jnp.asarray(table), pages_per_chunk=2, **kw))
    for s in range(3):
        np.testing.assert_allclose(got[s], want[s, q_len[s] - 1],
                                   rtol=2e-4, atol=2e-5)
