"""Xing4 (latent attention, sigmoid-routed experts beside a shared expert,
hyper-connection streams) through ``InferenceEngineV2``, against the
benchmark's plain reference (``benchmark/reference/xing4.py``, the same file
the benchmark imports; it shares no code with ``deepspeed_tpu``)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.kernels import mla_ops
from deepspeed_tpu.inference.v2.lifecycle import (LifecycleScheduler,
                                                  ServeRequest)
from deepspeed_tpu.models import xing4 as X
from deepspeed_tpu.moe import dropless

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load(os.path.join(REPO, "benchmark", "reference", "xing4.py"),
                  "benchmark_reference_xing4")

#: published keys at a tiny size: 1 dense + 2 expert layers, positions past
#: original_max_position_embeddings so that YaRN's blend matters
HF = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=3, first_k_dense_replace=1,
    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
    routed_scaling_factor=2, norm_topk_prob=True, scoring_func="sigmoid",
    topk_method="noaux_tc", n_group=1, topk_group=1, hc_mult=4,
    hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling={"type": "yarn", "factor": 8, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 16},
    max_position_embeddings=256, tie_word_embeddings=False)
PROMPT = 75
TOL = 2e-4          # float32 system against the float32 reference


@pytest.fixture(scope="module")
def model():
    m = X.Xing4LM.from_hf_config(HF)
    return m, m.init_params(jax.random.PRNGKey(0), jnp.float32)


def ref_weights(params):
    names = {
        "attn_norm": ("attn_norm", "scale"), "w_dq": ("q_a_proj", "kernel"),
        "q_norm": ("q_a_norm", "scale"), "w_uq": ("q_b_proj", "kernel"),
        "w_dkv": ("kv_a_proj", "kernel"), "kv_norm": ("kv_a_norm", "scale"),
        "w_ukv": ("kv_b_proj", "kernel"), "w_o": ("o_proj", "kernel"),
        "mlp_norm": ("mlp_norm", "scale"), "w_gate": ("gate_proj", "kernel"),
        "w_up": ("up_proj", "kernel"), "w_down": ("down_proj", "kernel"),
        "router": ("router", "kernel"), "router_bias": ("router", "bias"),
        "e_gate": ("experts", "gate"), "e_up": ("experts", "up"),
        "e_down": ("experts", "down"), "s_gate": ("shared", "gate"),
        "s_up": ("shared", "up"), "s_down": ("shared", "down")}
    layers = []
    for stack in ("dense_layers", "moe_layers"):
        tree = params[stack]
        for i in range(tree["attn_norm"]["scale"].shape[0]):
            w = {k: tree[a][b][i] for k, (a, b) in names.items()
                 if a in tree}
            for hc in ("hc_attn", "hc_mlp"):
                w[hc] = {k: v[i] for k, v in tree[hc].items()}
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


def system_logits(engine, prompt, body):
    """Chunked prefill of ``prompt[:body]``, then the rest fed singly
    through the latent cache: logits at positions body-1 .. len-1."""
    got = []
    for pos in range(0, body, 16):
        logits = engine.put([1], [prompt[pos:min(pos + 16, body)]])
    got.append(np.asarray(logits[0]))
    for tok in prompt[body:]:
        got.append(np.asarray(engine.put([1], [[tok]])[0]))
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
def test_prefill_then_decode_through_the_latent_cache(model, impl):
    prompt = prompt_tokens()
    engine = engine_for(model, attn_impl=impl)
    body = PROMPT - 4
    logits = system_logits(engine, prompt, body)
    ref = reference_logits(model, prompt, list(range(body - 1, PROMPT)))
    assert logits.shape == ref.shape
    assert max(rel_l2(g, r) for g, r in zip(logits, ref)) < TOL
    # the fused window's greedy tokens are the reference's, fed back
    toks = engine.decode_batch([1], [int(np.argmax(ref[-1]))], 3)[:, 0]
    full = prompt + [int(np.argmax(ref[-1]))]
    for tok in toks:
        nxt = reference_logits(model, full, [len(full) - 1])[0]
        assert int(tok) == int(np.argmax(nxt))
        full.append(int(tok))


@pytest.mark.parametrize("mutation", reference.MUTATIONS)
def test_each_piece_of_the_mathematics_is_noticed(model, got, mutation):
    """The comparison the true model passes fails when the reference drops
    the selection bias, puts it into the weights, skips the
    renormalisation, the factor 2, the shared expert, H_res, the Sinkhorn's
    column step, the mscale^2 of the softmax scale, or YaRN's blend."""
    prompt, body, logits = got
    positions = list(range(body - 1, PROMPT))
    true = reference_logits(model, prompt, positions)
    assert max(rel_l2(g, r) for g, r in zip(logits, true)) < TOL
    broken = reference_logits(model, prompt, positions, mutation)
    assert max(rel_l2(g, r) for g, r in zip(logits, broken)) > 20 * TOL


def test_absorbed_attention_is_the_expanded_attention(model):
    """One layer's attention: the absorbed form against latent pages (both
    Pallas kernels in interpret mode, and the dense form) against the
    reference's expanded form."""
    m, params = model
    cfg = m.config
    lp = jax.tree.map(lambda x: x[0], params["moe_layers"])
    rng = np.random.default_rng(3)
    T, ps = 21, 8
    h = jnp.asarray(rng.normal(size=(T, cfg.hidden_size)), jnp.float32)
    cos, sin = X.rope_at(jnp.arange(T), cfg)
    q_nope, q_rope = X.mla_query(h, lp, cos, sin, cfg)
    q_abs = X.mla_absorb_query(q_nope, q_rope, lp, cfg)
    rows = X.mla_latent(h, lp, cos, sin, cfg)
    NB = -(-T // ps)
    pages = jnp.zeros((NB + 1, ps, cfg.latent_row), jnp.float32)
    pages = mla_ops.latent_append(pages, rows, jnp.arange(T) // ps,
                                  jnp.arange(T) % ps)
    table = jnp.arange(NB, dtype=jnp.int32)[None, :]
    kw = dict(rank=cfg.kv_lora_rank, scale=cfg.softmax_scale)
    prefill = mla_ops.mla_ragged_prefill(
        q_abs, pages, jnp.asarray([T]), table, jnp.asarray([0, T]),
        block_q=8, pages_per_chunk=2, interpret=True, **kw)
    decode = mla_ops.mla_paged_decode(
        q_abs[-1:], pages, jnp.asarray([T]), table, pages_per_chunk=2,
        interpret=True, **kw)
    dense = mla_ops.mla_attend_dense(
        q_abs[None], pages, table, jnp.asarray([T]), jnp.asarray([T]), **kw)
    out = np.asarray(X.mla_output(prefill, lp, cfg))
    w = {"w_dq": lp["q_a_proj"]["kernel"], "q_norm": lp["q_a_norm"]["scale"],
         "w_uq": lp["q_b_proj"]["kernel"], "w_dkv": lp["kv_a_proj"]["kernel"],
         "kv_norm": lp["kv_a_norm"]["scale"],
         "w_ukv": lp["kv_b_proj"]["kernel"], "w_o": lp["o_proj"]["kernel"]}
    with jax.default_matmul_precision("highest"):
        expanded = np.asarray(reference.attention(h, w, HF))
    assert rel_l2(out, expanded) < 1e-5
    assert rel_l2(np.asarray(dense[0]), np.asarray(prefill)) < 1e-5
    assert rel_l2(np.asarray(decode[0]), np.asarray(prefill[-1])) < 1e-5


def test_a_grafted_turn_is_bit_equal_to_a_cold_one(model):
    """Two turns of one session, the second continuing the first: grafted
    from the trie (seven full pages shared, the partial eighth copied before
    it is appended to), it gives the logits and the tokens of the same turn
    on an engine that never saw the first."""
    first = prompt_tokens(1, 57)
    second = first + prompt_tokens(3, 7)

    def prefill(engine, uid, prompt, start, stop):
        for pos in range(start, stop, 16):
            logits = engine.put([uid], [prompt[pos:min(pos + 16, stop)]])
        return logits

    warm = engine_for(model, prefix_cache=True)
    logits = prefill(warm, 1, first, 0, 57)
    warm.decode_batch([1], [int(np.argmax(logits[0]))], 4)
    warm.commit_prefix(1, first, allow_partial=True)
    matched, blocks, partial = warm.prefix_cache.match(list(second))
    assert (matched, len(blocks), partial) == (57, 8, 1)
    before = np.asarray(warm.kv.pages[jnp.asarray(blocks)])
    warm.flush([1])
    grafted = warm.graft_prefix(2, second)
    assert grafted == 57
    private = warm.state_manager.get_sequence(2).blocks[-1]
    assert private != blocks[-1]                    # copied, not shared
    logits_w = warm.put([2], [second[grafted:]])
    toks_w = warm.decode_batch([2], [int(np.argmax(logits_w[0]))], 4)
    cold = engine_for(model)
    prefill(cold, 2, second, 0, 57)                 # the first turn's chunks
    logits_c = cold.put([2], [second[57:]])
    toks_c = cold.decode_batch([2], [int(np.argmax(logits_c[0]))], 4)
    assert np.array_equal(np.asarray(logits_w[0]), np.asarray(logits_c[0]))
    assert np.array_equal(toks_w, toks_c)
    # copy-on-write: the trie's pages are what they were
    assert np.array_equal(np.asarray(warm.kv.pages[jnp.asarray(blocks)]),
                          before)


def test_the_scheduler_serves_sessions_with_the_prefix_cache(model):
    from deepspeed_tpu.telemetry import get_tracer

    engine = engine_for(model, prefix_cache=True)
    sched = LifecycleScheduler(engine)
    doc = prompt_tokens(4, 50)
    hits = []
    for uid in (10, 11, 12):
        req = ServeRequest(uid=uid, prompt=doc + prompt_tokens(uid, 6),
                           max_new_tokens=9)
        sched.submit(req)
        sched.run_until_idle()
        assert len(req.produced) == 9
        hits.append(req.prefix_hit_tokens)
    assert hits[0] == 0 and hits[1] >= 48 and hits[2] >= 48
    records = get_tracer().records()
    admits = [r.attrs for r in records if r.name == "serve/admit"
              and r.attrs.get("admitted")]
    assert admits[-1]["prefix_tokens"] == hits[2]
    assert admits[-1]["prompt_tokens"] == 56
    account = [r.attrs for r in records if r.name == "engine/window_account"
               and "moe_pairs" in (r.attrs or {})][-1]
    assert account["moe_pairs_dropped"] == 0 and account["moe_pairs"] > 0
    assert 1 / 8 <= account["moe_load_max_share"] <= 1.0


def test_dropless_experts_against_a_loop_over_experts():
    rng = np.random.default_rng(0)
    T, D, F, E, k = 13, 16, 24, 8, 3
    h = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    lp = {"router": {"kernel": jnp.asarray(rng.normal(size=(D, E)),
                                           jnp.float32),
                     "bias": jnp.asarray(rng.normal(size=(E,)), jnp.float32)},
          "experts": {n: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
                      for n, s in (("gate", (E, D, F)), ("up", (E, D, F)),
                                   ("down", (E, F, D)))},
          "shared": {n: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
                     for n, s in (("gate", (D, F)), ("up", (D, F)),
                                  ("down", (F, D)))}}
    valid = jnp.arange(T) < 10
    out, pairs = dropless.sigmoid_moe_block(h, lp, k=k, scaling=2.0,
                                            valid=valid)
    c = dict(num_experts_per_tok=k, norm_topk_prob=True,
             routed_scaling_factor=2.0, n_routed_experts=E)
    w = {"router": lp["router"]["kernel"], "router_bias": lp["router"]["bias"],
         "e_gate": lp["experts"]["gate"], "e_up": lp["experts"]["up"],
         "e_down": lp["experts"]["down"], "s_gate": lp["shared"]["gate"],
         "s_up": lp["shared"]["up"], "s_down": lp["shared"]["down"]}
    with jax.default_matmul_precision("highest"):
        ref = reference.expert_layer(h, w, c)
        idx, _, _ = reference.route(h, w, c)
    assert rel_l2(np.asarray(out), np.asarray(ref)) < 1e-5
    assert int(pairs.sum()) == 10 * k           # no pair dropped, pads out
    assert np.array_equal(np.asarray(pairs),
                          np.bincount(np.asarray(idx[:10]).ravel(),
                                      minlength=E))


@pytest.mark.parametrize("what", ["host_tier_mb", "speculative", "kv_import",
                                  "verify_decode"])
def test_what_latent_pages_cannot_do_is_refused_by_name(model, what):
    m, params = model
    if what == "host_tier_mb":
        with pytest.raises(ValueError, match="host_tier_mb"):
            engine_for(model, host_tier_mb=1.0)
        return
    engine = engine_for(model)
    if what == "speculative":
        from deepspeed_tpu.inference.v2.speculative import SpeculativeConfig

        with pytest.raises(ValueError, match="speculative"):
            LifecycleScheduler(engine, speculative=SpeculativeConfig())
    elif what == "kv_import":
        from deepspeed_tpu.inference.v2 import kv_ship

        engine.put([1], [prompt_tokens(5, 9)])
        with pytest.raises(NotImplementedError, match="export_kv"):
            kv_ship.export_kv(engine, 1, prompt_tokens(5, 9))
    else:
        with pytest.raises(NotImplementedError, match="verify_decode"):
            engine.verify_decode([1], [3], [[4, 5]])


def test_published_config_builds_the_published_shapes():
    import json

    with open(os.path.join(REPO, "benchmark", "configs",
                           "xing4.0-29b-a4b-depth5.json")) as f:
        hf = json.load(f)
    m = X.Xing4LM.from_hf_config(hf)
    cfg = m.config
    assert (cfg.num_dense_layers, cfg.num_moe_layers) == (1, 4)
    assert (cfg.latent_dim, cfg.latent_row) == (576, 640)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 1.4159 ** 2,
                                              rel=1e-4)
    # 4.05B parameters (ISSUE 28's arithmetic), of which the hyper-connection
    # maps are 2 x 14336 x 24 a layer
    assert m.num_params() == pytest.approx(4.05e9, rel=0.01)
    inv = np.asarray(X.yarn_inv_freq(cfg))
    base = 1.0 / 10000 ** (np.arange(0, 64, 2) / 64)
    assert np.allclose(inv[:8], base[:8])               # fast: as they were
    assert np.allclose(inv[-8:], base[-8:] / 64)        # slow: interpolated
