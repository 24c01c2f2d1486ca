"""LongCat-Flash (a shortcut-connected double layer that owns two latent page
layers, a softmax router a third of whose outputs are identity experts)
through ``InferenceEngineV2``, against the benchmark's plain reference
(``benchmark/reference/longcat_flash.py``, the same file the benchmark
imports; it shares no code with ``deepspeed_tpu``)."""
import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.lifecycle import (LifecycleScheduler,
                                                  ServeRequest)
from deepspeed_tpu.models import longcat_flash as LC
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
                               "longcat_flash.py"),
                  "benchmark_reference_longcat_flash")

#: published keys at a tiny size: two double layers (four page layers), 8
#: real experts + 4 identity ones, the top 3 of the 12
HF = dict(
    vocab_size=256, hidden_size=64, ffn_hidden_size=128,
    expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
    q_lora_rank=16, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, mla_scale_q_lora=True,
    mla_scale_kv_lora=True, routed_scaling_factor=6, n_routed_experts=8,
    zero_expert_num=4, zero_expert_type="identity", moe_topk=3,
    attention_method="MLA", rms_norm_eps=1e-5, rope_theta=10000,
    max_position_embeddings=256)
#: the same model as chip 1 of 2 sees it: real experts 4-7 held
SHARE = dict(HF, n_routed_experts=4, ep_size=2, ep_rank=1)
PROMPT = 75
TOL = 2e-4          # float32 system against the float32 reference

_BLOCK = {
    "in_norm": ("in_norm", "scale"), "w_dq": ("q_a_proj", "kernel"),
    "q_norm": ("q_a_norm", "scale"), "w_uq": ("q_b_proj", "kernel"),
    "w_dkv": ("kv_a_proj", "kernel"), "kv_norm": ("kv_a_norm", "scale"),
    "w_ukv": ("kv_b_proj", "kernel"), "w_o": ("o_proj", "kernel"),
    "post_norm": ("post_norm", "scale"), "w_gate": ("gate_proj", "kernel"),
    "w_up": ("up_proj", "kernel"), "w_down": ("down_proj", "kernel")}


def build(hf):
    """The whole model's seeded parameters, cut to ``hf``'s share: a chip
    holds its experts of the SAME model."""
    whole = LC.LongCatFlashLM.from_hf_config(HF)
    params = whole.init_params(jax.random.PRNGKey(0), jnp.float32)
    m = LC.LongCatFlashLM.from_hf_config(hf)
    lo, n = m.config.expert_offset, m.config.experts_held
    params = dict(params, experts={k: v[:, lo:lo + n]
                                   for k, v in params["experts"].items()})
    return m, params


@pytest.fixture(scope="module", params=["whole", "share"])
def model(request):
    return build(HF if request.param == "whole" else SHARE) \
        + (HF if request.param == "whole" else SHARE,)


@pytest.fixture(scope="module")
def share():
    return build(SHARE) + (SHARE,)


def ref_weights(params):
    blocks, router = params["layers"]["blocks"], params["layers"]["router"]
    layers = []
    for l in range(router["kernel"].shape[0]):
        moe = {"router": router["kernel"][l], "router_bias": router["bias"][l],
               "e_gate": params["experts"]["gate"][l],
               "e_up": params["experts"]["up"][l],
               "e_down": params["experts"]["down"][l]}
        layers.append({
            "blocks": [lambda l=l, i=i: {k: blocks[i][a][b][l]
                                         for k, (a, b) in _BLOCK.items()}
                       for i in (0, 1)],
            "moe": lambda moe=moe: moe})
    return {"embedding": params["embed"]["embedding"],
            "norm": params["norm_f"]["scale"],
            "head": params["lm_head"]["kernel"], "layers": layers}


def engine_for(model, **kw):
    m, params = model[:2]
    cfg = dict(max_tokens=16, max_seqs=4, max_ctx=128, block_size=8,
               dtype=jnp.float32)
    cfg.update(kw)
    return InferenceEngineV2(m, params, RaggedInferenceEngineConfig(**cfg))


def prompt_tokens(seed=0, n=PROMPT):
    return np.random.default_rng(seed).integers(1, 256, size=n).tolist()


def system_logits(engine, prompt, body, uid=1):
    """Chunked prefill of ``prompt[:body]`` (chunks of 16: the last is
    partial and no multiple of a page), then the rest fed singly through
    both page layers of every layer: logits at positions body-1 .. len-1."""
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
def got(share):
    prompt = prompt_tokens()
    body = PROMPT - 4
    return prompt, body, system_logits(engine_for(share), prompt, body)


@pytest.mark.parametrize("impl", ["paged", "gather"])
def test_prefill_then_decode_through_both_page_layers(model, impl):
    prompt = prompt_tokens()
    engine = engine_for(model, attn_impl=impl)
    fam = engine.family
    assert fam.page_layers == 2 * fam.num_layers == 4
    assert engine.kv.pages.shape[0] == 4 * engine._num_blocks + 1
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


def test_two_sequences_prefilled_together_then_fed_singly(share):
    """Several sequences' chunks in one flat batch, then each one's next
    tokens in a batch of single tokens: every row reads its own pages in
    both page layers."""
    a, b = prompt_tokens(5, 11), prompt_tokens(6, 7)
    engine = engine_for(share)
    first = np.asarray(engine.put([1, 2], [a[:-2], b[:-2]]))
    rest = [np.asarray(engine.put([1, 2], [[a[i]], [b[i]]])) for i in (-2, -1)]
    for j, row in enumerate((a, b)):
        ref = reference_logits(share, row, list(range(len(row) - 3, len(row))))
        got_rows = [first[j]] + [r[j] for r in rest]
        assert max(rel_l2(g, r) for g, r in zip(got_rows, ref)) < TOL


@pytest.mark.parametrize("mutation", reference.MUTATIONS)
def test_each_piece_of_the_mathematics_is_noticed(share, got, mutation):
    """The comparison the true model passes fails when the reference drops
    the identity pairs, feeds the expert branch from the second block's
    hidden state or lets it rejoin after the first FFN, leaves out ``s_q``
    or ``s_kv``, renormalises the weights, drops the selection bias, puts
    it into the weights, or skips the factor 6."""
    prompt, body, logits = got
    positions = list(range(body - 1, PROMPT))
    true = reference_logits(share, prompt, positions)
    assert max(rel_l2(g, r) for g, r in zip(logits, true)) < TOL
    broken = reference_logits(share, prompt, positions, mutation)
    assert max(rel_l2(g, r) for g, r in zip(logits, broken)) > 20 * TOL


class _OtherBlocksPages:
    """A cache handle whose views append to their own page layer and attend
    the OTHER block's (``2l`` <-> ``2l + 1``)."""

    def __init__(self, cache):
        self._cache = cache

    def at(self, page_layer):
        own, other = self._cache.at(page_layer), self._cache.at(page_layer ^ 1)
        own.attend = other.attend
        return own

    @property
    def pages(self):
        return self._cache.pages


def test_swapped_page_layers_are_noticed(share, got):
    """The body gets BOTH its page layers from the runner's handle; a body
    whose blocks read each other's page layer fails the comparison the true
    one passes."""
    m, params, hf = share
    family = m.serving_family()

    def stacks(p):
        for stack in family.stacks(p):
            yield dataclasses.replace(
                stack, body=lambda x, lp, l, cache, ctx, body=stack.body:
                body(x, lp, l, _OtherBlocksPages(cache), ctx))

    class Swapped:
        config = m.config

        def serving_family(self):
            return dataclasses.replace(family, stacks=stacks)

    prompt, body, logits = got
    broken = system_logits(engine_for((Swapped(), params)), prompt, body)
    assert max(rel_l2(g, r) for g, r in zip(broken, logits)) > 20 * TOL


def _layer_inputs(T=13, valid_to=10, seed=0):
    m, params = build(HF)
    cfg = m.config
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(size=(T, cfg.hidden_size)), jnp.float32)
    router = jax.tree.map(lambda a: a[0], params["layers"]["router"])
    experts = jax.tree.map(lambda a: a[0], params["experts"])
    w = {"router": router["kernel"], "router_bias": router["bias"],
         "e_gate": experts["gate"], "e_up": experts["up"],
         "e_down": experts["down"]}
    return cfg, h, router, experts, w, jnp.arange(T) < valid_to


@pytest.mark.parametrize("identity", ["as_is", "as_elsewhere"])
def test_identity_pairs_are_computed_here_and_counted_apart(identity):
    """The uncut layer against a loop over the experts: an identity pair
    adds ``g·h`` and is counted in its own entry.  Told nothing of identity
    experts (``identity_from`` None), the share's arithmetic sorts them
    behind the last group as pairs another chip owes: the sum is wrong and
    ``elsewhere`` counts pairs that no chip will compute."""
    cfg, h, router, experts, w, valid = _layer_inputs()
    k, E = cfg.moe_topk, cfg.n_routed_experts
    idx, g = dropless.softmax_bias_topk_route(
        h, router, k, cfg.routed_scaling_factor)
    with jax.default_matmul_precision("highest"):
        ref = sum(reference.expert_layer(h, w, HF))
        ref_idx, ref_g, _ = reference.route(h, w, HF)
    assert np.array_equal(np.asarray(idx), np.asarray(ref_idx))
    assert rel_l2(np.asarray(g), np.asarray(ref_g)) < 1e-6
    live = np.asarray(ref_idx[:10]).ravel()
    n_identity = int((live >= E).sum())
    assert 0 < n_identity < live.size           # the draw has both kinds
    if identity == "as_is":
        out, pairs = dropless.dropless_experts(
            h, idx, g, experts, valid=valid, identity_from=E)
        assert rel_l2(np.asarray(out), np.asarray(ref)) < 1e-5
        assert pairs.shape == (E + 1,)
        assert np.array_equal(np.asarray(pairs[:E]),
                              np.bincount(live[live < E], minlength=E))
        assert int(pairs[E]) == n_identity
        assert int(pairs.sum()) == 10 * k       # no pair dropped, pads out
    else:
        out, pairs = dropless.dropless_experts(
            h, idx, g, experts, valid=valid, offset=0)
        assert rel_l2(np.asarray(out), np.asarray(ref)) > 1e-2
        assert int(pairs[E]) == n_identity      # "owed" by a chip that is not


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of all ``ep_size`` shares plus the identity part
    counted ONCE equal the uncut layer; every share counts the same identity
    pairs, and a real pair is held by exactly one."""
    cfg, h, router, experts, w, valid = _layer_inputs(T=21, valid_to=21)
    k, E, ep = cfg.moe_topk, cfg.n_routed_experts, 4
    held = E // ep
    with jax.default_matmul_precision("highest"):
        routed_ref, identity_ref = reference.expert_layer(h, w, HF)
        ref_idx, _, _ = reference.route(h, w, HF)
    total, pairs_held, identity_counts = 0.0, [], []
    lp = {"router": router}
    for rank in range(ep):
        mine = {n: a[rank * held:(rank + 1) * held]
                for n, a in experts.items()}
        out, pairs = dropless.zero_expert_moe_block(
            h, dict(lp, experts=mine), k=k,
            scaling=cfg.routed_scaling_factor, identity_from=E,
            offset=rank * held, valid=valid)
        assert pairs.shape == (held + 2,)       # held, elsewhere, identity
        assert int(pairs.sum()) == 21 * k
        # the reference's share is the program's share
        hf = dict(HF, n_routed_experts=held, ep_size=ep, ep_rank=rank)
        with jax.default_matmul_precision("highest"):
            routed_r, identity_r = reference.expert_layer(
                h, dict(w, **{"e_" + n: a for n, a in mine.items()}), hf)
        assert rel_l2(np.asarray(out), np.asarray(routed_r + identity_r)) \
            < 1e-5
        assert rel_l2(np.asarray(identity_r), np.asarray(identity_ref)) < 1e-6
        total = total + (out - identity_r)
        pairs_held.append(np.asarray(pairs[:held]))
        identity_counts.append(int(pairs[-1]))
    uncut = np.asarray(routed_ref + identity_ref)
    assert rel_l2(np.asarray(total + identity_ref), uncut) < 1e-5
    live = np.asarray(ref_idx).ravel()
    assert np.array_equal(np.concatenate(pairs_held),
                          np.bincount(live[live < E], minlength=E))
    assert identity_counts == [int((live >= E).sum())] * ep


def test_a_grafted_turn_is_bit_equal_to_a_cold_one(share):
    """Two turns of one session, the second continuing the first: grafted
    from the trie (seven full pages shared IN ALL FOUR PAGE LAYERS, the
    partial eighth copied in each before it is appended to), it gives the
    logits and the tokens of the same turn on an engine that never saw the
    first."""
    first = prompt_tokens(1, 57)
    second = first + prompt_tokens(3, 7)

    def prefill(engine, uid, prompt, start, stop):
        for pos in range(start, stop, 16):
            logits = engine.put([uid], [prompt[pos:min(pos + 16, stop)]])
        return logits

    warm = engine_for(share, prefix_cache=True)
    logits = prefill(warm, 1, first, 0, 57)
    warm.decode_batch([1], [int(np.argmax(logits[0]))], 4)
    warm.commit_prefix(1, first, allow_partial=True)
    matched, blocks, partial = warm.prefix_cache.match(list(second))
    assert (matched, len(blocks), partial) == (57, 8, 1)
    every_layer = jnp.asarray([b + layer * warm._num_blocks
                               for layer in range(4) for b in blocks])
    before = np.asarray(warm.kv.pages[every_layer])
    assert all(np.abs(before[i * 8:(i + 1) * 8]).sum() > 0 for i in range(4))
    warm.flush([1])
    grafted = warm.graft_prefix(2, second)
    assert grafted == 57
    logits_w = warm.put([2], [second[grafted:]])
    toks_w = warm.decode_batch([2], [int(np.argmax(logits_w[0]))], 4)
    cold = engine_for(share)
    prefill(cold, 2, second, 0, 57)                 # the first turn's chunks
    logits_c = cold.put([2], [second[57:]])
    toks_c = cold.decode_batch([2], [int(np.argmax(logits_c[0]))], 4)
    assert np.array_equal(np.asarray(logits_w[0]), np.asarray(logits_c[0]))
    assert np.array_equal(toks_w, toks_c)
    # copy-on-write: the trie's pages are what they were, in every page layer
    assert np.array_equal(np.asarray(warm.kv.pages[every_layer]), before)


def test_the_scheduler_serves_sessions_and_accounts_for_every_pair(share):
    from deepspeed_tpu.telemetry import get_tracer

    engine = engine_for(share, prefix_cache=True)
    sched = LifecycleScheduler(engine)
    doc = prompt_tokens(4, 50)
    seen = {id(r) for r in get_tracer().records()}      # other tests' windows
    hits = []
    for uid in (10, 11, 12):
        req = ServeRequest(uid=uid, prompt=doc + prompt_tokens(uid, 6),
                           max_new_tokens=9)
        sched.submit(req)
        sched.run_until_idle()
        assert len(req.produced) == 9
        hits.append(req.prefix_hit_tokens)
    assert hits[0] == 0 and hits[1] >= 48 and hits[2] >= 48
    records = [r for r in get_tracer().records() if id(r) not in seen]
    accounts = [r.attrs for r in records if r.name == "engine/window_account"]
    assert len(accounts) >= 3
    for account in accounts:            # every window: nothing lost
        routed = account["moe_pairs"] + account["moe_pairs_elsewhere"] \
            + account["moe_pairs_identity"]
        assert account["moe_pairs_dropped"] == 0
        assert account["moe_identity_pair_share"] == pytest.approx(
            account["moe_pairs_identity"] / routed)
    # over the windows together, pairs of all three kinds
    for kind in ("moe_pairs", "moe_pairs_elsewhere", "moe_pairs_identity"):
        assert sum(a[kind] for a in accounts) > 0, kind
    layout = [r.attrs for r in records if r.name == "moe/serve_layout"][-1]
    assert layout == dict(router_outputs=12, held=4, offset=4,
                          identity_from=8, k=3, rows=layout["rows"])


@pytest.mark.parametrize("what", ["host_tier_mb", "speculative",
                                  "verify_decode"])
def test_what_latent_pages_cannot_do_is_refused_by_name(share, what):
    if what == "host_tier_mb":
        with pytest.raises(ValueError, match="host_tier_mb"):
            engine_for(share, host_tier_mb=1.0)
        return
    engine = engine_for(share)
    if what == "speculative":
        from deepspeed_tpu.inference.v2.speculative import SpeculativeConfig

        with pytest.raises(ValueError, match="speculative"):
            LifecycleScheduler(engine, speculative=SpeculativeConfig())
    else:
        with pytest.raises(NotImplementedError, match="verify_decode"):
            engine.verify_decode([1], [3], [[4, 5]])


def test_published_config_builds_the_published_shapes():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "longcat-flash-chat-depth4-ep32.json")) as f:
        hf = json.load(f)
    m = LC.LongCatFlashLM.from_hf_config(hf)
    cfg = m.config
    assert (cfg.num_layers, cfg.experts_held, cfg.expert_offset) == (4, 16, 0)
    assert (cfg.n_routed_experts, cfg.router_outputs) == (512, 768)
    assert (cfg.latent_dim, cfg.latent_row) == (576, 640)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5)
    assert (cfg.q_scale, cfg.kv_scale) == (2.0, pytest.approx(12 ** 0.5))
    fam = m.serving_family()
    assert (fam.page_layers, fam.counts.size) == (8, 16 + 2)
    assert fam.counts.per_token == 4 * 12
    # 5.17B parameters (ISSUE 41's arithmetic): a layer outside its experts
    # 638.9M, 16 experts 604.0M, embedding + head 201.3M
    assert m.num_params() == pytest.approx(5.17e9, rel=0.005)
