"""Nemotron-H with latent experts (a Mamba-2 / SSD head state as the third
state kind, attention without a positional term, 3-of-16 squared-ReLU
experts in a latent narrower than the residual, a pattern that is not
periodic) through ``InferenceEngineV2``, against the benchmark's plain
reference (``benchmark/reference/nemotron_h.py``, the same file the
benchmark imports; it shares no code with ``deepspeed_tpu``).

The size keeps every ratio: hidden 64, the published period ``MEMEMEM*EME``
(5 M : 5 E : 1 *), 8 Mamba heads of 32 in 2 groups (4 a group, stored 4
along the lanes), state 16, chunks of 8, 16 experts (a multiple of 4) of 24
(no power of two) in a latent of 32, a shared expert of 48."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.kernels import ssd_ops
from deepspeed_tpu.models import nemotron_h as NH
from deepspeed_tpu.models.serving import ExpertPairs, KVRow, SSDState
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
                               "nemotron_h.py"),
                  "benchmark_reference_nemotron_h")

HF = dict(model_type="nemotron_h", vocab_size=256, hidden_size=64,
          num_hidden_layers=11, hybrid_override_pattern="MEMEMEM*EME",
          num_attention_heads=4, num_key_value_heads=2, head_dim=16,
          mamba_num_heads=8, mamba_head_dim=32, n_groups=2,
          ssm_state_size=16, conv_kernel=4, chunk_size=8,
          n_routed_experts=16, num_experts_per_tok=3,
          moe_intermediate_size=24, moe_latent_size=32,
          moe_shared_expert_intermediate_size=48, n_shared_experts=1,
          n_group=1, topk_group=1, routed_scaling_factor=5.0,
          norm_topk_prob=True, layer_norm_epsilon=1e-5,
          mlp_hidden_act="relu2", mamba_hidden_act="silu",
          use_conv_bias=True, use_bias=False, mamba_proj_bias=False,
          mlp_bias=False, attention_bias=False, tie_word_embeddings=False,
          max_position_embeddings=256, num_nextn_predict_layers=0)
PROMPT = 43         # three 16-token chunks of a prompt that is no multiple
TOL = 5e-4          # float32 system against the float32 reference
PADDED = 128        # one length: the reference compiles once a mutation


@pytest.fixture(scope="module")
def model():
    m = NH.NemotronHLM.from_hf_config(HF)
    return m, m.init_params(jax.random.PRNGKey(0), jnp.float32)


def ref_weights(params):
    """The program's tree as the reference takes it, a layer at a time, in
    the pattern's order."""
    pick = lambda tree, i: jax.tree.map(lambda x: x[i], tree)  # noqa: E731
    layers, e = [], 0
    for stack in params["stacks"]:
        for i in range(jax.tree.leaves(stack)[0].shape[0]):
            for kind in ("M", "*", "E"):
                if kind not in stack:
                    continue
                p = pick(stack[kind], i)
                w = {"norm": p["norm"]["scale"]}
                if kind == "M":
                    w.update(w_in=p["in_proj"]["kernel"],
                             conv=p["conv"]["kernel"],
                             conv_b=p["conv"]["bias"], A_log=p["A_log"],
                             dt_bias=p["dt_bias"], D=p["D"],
                             gnorm=p["gnorm"]["scale"],
                             w_out=p["out_proj"]["kernel"])
                elif kind == "*":
                    w.update(w_q=p["q_proj"]["kernel"],
                             w_k=p["k_proj"]["kernel"],
                             w_v=p["v_proj"]["kernel"],
                             w_o=p["o_proj"]["kernel"])
                else:
                    w.update(router=p["router"]["kernel"],
                             router_b=p["router"]["bias"],
                             l_down=p["latent_down"]["kernel"],
                             l_up=p["latent_up"]["kernel"],
                             s_up=p["shared"]["up"],
                             s_down=p["shared"]["down"],
                             e_up=params["experts"]["up"][e],
                             e_down=params["experts"]["down"][e])
                    e += 1
                layers.append(w)
    return {"embedding": params["embed"]["embedding"],
            "norm": params["norm_f"]["scale"],
            "head": params["lm_head"]["kernel"],
            "layers": [lambda w=w: w for w in layers]}


def engine_for(model, **kw):
    cfg = dict(max_tokens=16, max_seqs=4, max_ctx=128, block_size=4,
               dtype=jnp.float32)
    cfg.update(kw)
    return InferenceEngineV2(model[0], model[1],
                             RaggedInferenceEngineConfig(**cfg))


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


_REFERENCES = {}


def reference_logits(model, prompt, positions, mutation=None, hf=HF):
    """The model is causal: tokens behind ``prompt`` move nothing at
    ``positions``, so every sequence is padded to one length."""
    key = (mutation, hf["n_routed_experts"], hf.get("ep_rank", 0))
    if key not in _REFERENCES:
        _REFERENCES[key] = reference.Reference(hf, mutation)
    row = list(prompt) + [0] * (PADDED - len(prompt))
    (out,) = _REFERENCES[key].logits(
        [jnp.asarray(row, jnp.int32)], ref_weights(model[1]),
        positions=[positions])
    return np.asarray(out)


BODY = PROMPT - 6


@pytest.fixture(scope="module")
def got(model):
    prompt = prompt_tokens()
    engine = engine_for(model)
    return prompt, engine, system_logits(engine, prompt, BODY)


def test_the_family_says_what_it_holds(model):
    fam = model[0].serving_family()
    assert fam.row == KVRow(2, 16) and fam.page_layers == 1
    assert fam.state == SSDState(5, 8, 32, 16, 2, 4, chunk=8)
    assert fam.state.recurrence == "ssd" and fam.state.lane_heads == 4
    # [heads, head_dim, state_dim] values, stored four heads along the lanes
    assert fam.state.arrays(jnp.bfloat16) == (
        ((2, 16, 128), jnp.float32), ((3, 8 * 32 + 2 * 2 * 16), jnp.bfloat16))
    assert fam.counts == ExpertPairs(16, 5 * 3)
    assert NH.cut_pattern("MEMEMEM*EME") == [
        ("ME", 3), ("M", 1), ("*", 1), ("E", 1), ("ME", 1)]
    assert [(s.layers.start, s.layers.stop)
            for s in fam.stacks(model[1])] == [(0, 3), (3, 4), (4, 5),
                                               (5, 6), (6, 7)]
    with pytest.raises(NotImplementedError, match="training path is open"):
        model[0].loss_fn(model[1], None, None)
    with pytest.raises(NotImplementedError, match="multi-token-prediction"):
        NH.NemotronHConfig.from_hf(dict(HF, num_nextn_predict_layers=1))
    with pytest.raises(NotImplementedError, match="each M, E or"):
        NH.NemotronHConfig.from_hf(dict(HF, hybrid_override_pattern="M-M*EMEMEME"))


def test_the_published_widths_add_up():
    """The whole model at the published keys is the published 120B-A12B, the
    benchmark's cut 4.65B, and a sequence owns 21.3 MB of state."""
    pattern = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
               "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    hf = dict(HF, vocab_size=131072, hidden_size=4096, num_hidden_layers=88,
              hybrid_override_pattern=pattern, num_attention_heads=32,
              head_dim=128, mamba_num_heads=128, mamba_head_dim=64,
              n_groups=8, ssm_state_size=128, chunk_size=128,
              n_routed_experts=512, num_experts_per_tok=22,
              moe_intermediate_size=2688, moe_latent_size=1024,
              moe_shared_expert_intermediate_size=5376)
    whole = NH.NemotronHLM.from_hf_config(hf)
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) \
        == (40, 40, 8)
    assert [len(s) for s in pattern.split("*")] == [7, 8, 8, 10, 10, 10, 10,
                                                    8, 9]
    assert 120.6e9 < whole.num_params() < 120.75e9
    cut = NH.NemotronHLM.from_hf_config(dict(
        hf, num_hidden_layers=11, hybrid_override_pattern=pattern[:11],
        n_routed_experts=128, ep_size=4, ep_rank=0, vocab_size=32768))
    assert pattern[:11] == "MEMEMEM*EME"
    assert 4.64e9 < cut.num_params() < 4.66e9
    fam = cut.serving_family()
    assert fam.counts == ExpertPairs(128, 5 * 22, elsewhere=True)
    assert fam.state.lane_heads == 2
    assert fam.state.arrays(jnp.bfloat16) == (
        ((64, 128, 128), jnp.float32), ((3, 10240), jnp.bfloat16))
    assert fam.state.slot_bytes(jnp.bfloat16) == 5 * (4194304 + 3 * 10240 * 2)
    assert fam.page_layers == 1 and fam.row.read_values * 2 == 1024


def test_chunked_prefill_then_single_tokens(model, got):
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


def test_a_mixed_batch_and_a_reused_slot(model):
    """Two sequences' chunks in one flat batch (the second starts
    mid-batch, its chunk edge inside the batch's), then both fed singly
    together; after a flush a fresh sequence in the slot that came back
    starts from zeros."""
    engine = engine_for(model)
    a, b = prompt_tokens(2, 9), prompt_tokens(3, 14)
    first = np.asarray(engine.put([5, 6], [a[:7], b[:9]]))
    nxt = np.asarray(engine.put([5, 6], [[a[7]], [b[9]]]))
    ref_a = reference_logits(model, a, [6, 7])
    ref_b = reference_logits(model, b, [8, 9])
    assert rel_l2(first[0], ref_a[0]) < TOL and rel_l2(nxt[0], ref_a[1]) < TOL
    assert rel_l2(first[1], ref_b[0]) < TOL and rel_l2(nxt[1], ref_b[1]) < TOL
    slot = engine.state_manager.get_sequence(6).slot
    engine.flush([5, 6])
    c = prompt_tokens(4, 11)
    out = np.asarray(engine.put([7], [c]))[0]
    assert engine.state_manager.get_sequence(7).slot == slot
    assert rel_l2(out, reference_logits(model, c, [10])[0]) < TOL


@pytest.mark.parametrize("mutation", list(reference.MUTATIONS))
def test_each_piece_of_the_mathematics_is_noticed(model, got, mutation):
    """Reading any one line of the equations another way moves the reference
    away from the system by at least ten times the agreement."""
    prompt, _, logits = got
    ref = reference_logits(model, prompt, list(range(BODY - 1, PROMPT)))
    agreement = rel_l2(logits, ref)
    mutated = reference_logits(model, prompt, list(range(BODY - 1, PROMPT)),
                               mutation)
    assert rel_l2(logits, mutated) > max(10 * agreement, 0.01), mutation


# ---- the three SSD forms -------------------------------------------------
def _ssd_case(seed=0, S=3, slots=4, kind=None):
    """A flat batch of three sequences: one continuing with a chunk that
    crosses two chunk edges, one fresh that starts mid-batch, one of a
    single token; the pool holds seeded states (the fresh one's slot NaN)."""
    kind = kind or SSDState(1, 8, 32, 16, 2, 4, chunk=8)
    rng = np.random.default_rng(seed)
    q_len = np.array([19, 6, 1], np.int32)
    ctx_len = np.array([30, 6, 12], np.int32)
    T = 32
    cu = np.concatenate([[0], np.cumsum(q_len)]).astype(np.int32)
    seq_of = np.repeat(np.arange(S), q_len)
    seq_of = np.concatenate([seq_of, np.full(T - len(seq_of), S - 1)])
    valid = np.arange(T) < cu[-1]
    pos = np.concatenate([np.arange(c - q, c) for q, c in zip(q_len, ctx_len)])
    pos = np.concatenate([pos, np.zeros(T - len(pos), np.int64)])
    batch = dict(q_len=jnp.asarray(q_len), ctx_len=jnp.asarray(ctx_len),
                 cu_q_lens=jnp.asarray(cu), q_offset=jnp.asarray(cu[:-1]),
                 seq_of_token=jnp.asarray(seq_of, jnp.int32),
                 pos_of_token=jnp.asarray(pos, jnp.int32))
    H, hd, N, G = kind.heads, kind.head_dim, kind.state_dim, kind.groups
    C = kind.conv_channels
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    (sshape, _), (cshape, _) = kind.arrays(jnp.float32)
    state = f(slots + 1, *sshape).at[1].set(jnp.nan)
    carry = f(slots + 1, *cshape).at[1].set(jnp.nan)
    inputs = (f(T, C), f(T, H), f(T, H * hd), 0.5 * f(4, C), 0.3 * f(C),
              f(H), jnp.asarray(rng.uniform(0.05, 1.0, H), jnp.float32),
              1 + 0.3 * f(H), 1 + 0.3 * f(H * hd), 1e-5)
    rows = jnp.asarray([2, 1, 0], jnp.int32)
    return kind, inputs, (state, carry), rows, batch, jnp.asarray(valid)


def test_the_chunked_form_is_the_oracle_with_edges_anywhere():
    kind, inputs, pool, rows, batch, valid = _ssd_case()
    run = lambda mode: ssd_ops.ssd_mix(  # noqa: E731
        *inputs, pool, rows, kind=kind, mode=mode, batch=batch, valid=valid)
    y_o, (s_o, c_o) = run("oracle")
    y_r, (s_r, c_r) = run("ragged")
    live = np.asarray(valid)
    assert np.isfinite(np.asarray(y_r)[live]).all()
    np.testing.assert_allclose(np.asarray(y_r)[live], np.asarray(y_o)[live],
                               rtol=2e-4, atol=2e-4)
    for got_pool, want in ((s_r, s_o), (c_r, c_o)):
        np.testing.assert_allclose(np.asarray(got_pool)[:3],
                                   np.asarray(want)[:3], rtol=2e-4,
                                   atol=2e-4)


def test_the_decode_kernel_is_the_oracle_in_place():
    """One token a row (a continuing row, a fresh row whose slot holds NaN,
    a padded row on the trash slot): the interpret-mode kernel against the
    token-by-token form; untouched slots keep their bytes."""
    kind = SSDState(1, 8, 32, 16, 2, 4, chunk=8)
    _, inputs, pool, _, _, _ = _ssd_case(seed=1, kind=kind)
    per_token, constants = inputs[:3], inputs[3:]
    q_len = jnp.asarray([1, 1, 0, 1], jnp.int32)
    ctx_len = jnp.asarray([9, 1, 0, 4], jnp.int32)
    batch = dict(q_len=q_len, ctx_len=ctx_len,
                 cu_q_lens=jnp.asarray([0, 1, 2, 2, 3], jnp.int32),
                 q_offset=jnp.asarray([0, 1, 2, 2], jnp.int32),
                 seq_of_token=jnp.asarray([0, 1, 3, 3], jnp.int32),
                 pos_of_token=jnp.asarray([8, 0, 3, 0], jnp.int32))
    rows = jnp.asarray([2, 1, 4, 0], jnp.int32)       # 4: the trash slot
    # the kernel takes one token a ROW (row 2 is padding), the oracle flat
    # tokens: rows 0, 1, 3 are tokens 0, 1, 2
    inputs = tuple(v[:4] for v in per_token) + constants
    flat = tuple(jnp.concatenate([v[:2], v[3:4], v[3:4]])
                 for v in per_token) + constants
    valid = jnp.asarray([True, True, True, False])
    y_o, (s_o, c_o) = ssd_ops.ssd_mix(*flat, pool, rows, kind=kind,
                                      mode="oracle", batch=batch, valid=valid)
    y_d, (s_d, c_d) = ssd_ops.ssd_mix(*inputs, pool, rows, kind=kind,
                                      mode="decode", batch=batch, valid=None)
    for row, tok in ((0, 0), (1, 1), (3, 2)):
        np.testing.assert_allclose(np.asarray(y_d)[row], np.asarray(y_o)[tok],
                                   rtol=2e-4, atol=2e-4)
    for slot in (0, 1, 2):
        np.testing.assert_allclose(np.asarray(s_d)[slot],
                                   np.asarray(s_o)[slot], rtol=2e-4,
                                   atol=2e-4)
        np.testing.assert_allclose(np.asarray(c_d)[slot],
                                   np.asarray(c_o)[slot], rtol=2e-4,
                                   atol=2e-4)
    np.testing.assert_array_equal(np.asarray(s_d)[3], np.asarray(pool[0])[3])


# ---- the latent expert block ----------------------------------------------
def test_four_shares_and_the_shared_expert_once_are_the_uncut_layer(model):
    """Each share's part is formed in the latent over the experts it HOLDS
    and up-projected; the up-projection is linear, so the four parts plus
    the shared expert once are the reference's uncut layer."""
    params = model[1]
    stack = params["stacks"][0]
    lp = jax.tree.map(lambda x: x[1], stack["E"])
    experts = jax.tree.map(lambda x: x[1], params["experts"])
    h = jnp.asarray(np.random.default_rng(7).normal(size=(13, 64)),
                    jnp.float32)
    w = ref_weights(params)["layers"][3]()
    c = dict(HF)
    with jax.default_matmul_precision("highest"):
        uncut = reference.expert_layer(h, w, c)
        shared = reference.unit(h, w["s_up"], w["s_down"])
    kw = dict(k=3, scaling=5.0)
    whole, pairs = dropless.latent_moe_block(h, dict(lp, experts=experts),
                                             **kw)
    assert rel_l2(np.asarray(whole), np.asarray(uncut)) < 1e-5
    assert int(pairs.sum()) == 13 * 3
    parts, elsewhere = 0, 0
    for rank in range(4):
        held = jax.tree.map(lambda x: x[4 * rank:4 * rank + 4], experts)
        part, p = dropless.latent_moe_block(
            h, dict(lp, experts=held), offset=4 * rank, **kw)
        assert p.shape == (5,) and int(p.sum()) == 13 * 3
        elsewhere += int(p[-1])
        parts = parts + part - shared
        # the reference's share is the same part
        ref_part = reference.expert_layer(
            h, dict(w, e_up=w["e_up"][4 * rank:4 * rank + 4],
                    e_down=w["e_down"][4 * rank:4 * rank + 4]),
            dict(c, n_routed_experts=4, ep_size=4, ep_rank=rank))
        assert rel_l2(np.asarray(part), np.asarray(ref_part)) < 1e-5
    assert elsewhere == 3 * 13 * 3      # every pair held on one chip of four
    assert rel_l2(np.asarray(parts + shared), np.asarray(uncut)) < 1e-5


def test_a_share_is_served_as_the_references_share():
    """``ep_size`` 4, ``ep_rank`` 1: the engine and the reference both hold
    experts 4-7 of 16 and leave the others' parts out."""
    hf = dict(HF, n_routed_experts=4, ep_size=4, ep_rank=1)
    m = NH.NemotronHLM.from_hf_config(hf)
    assert m.config.expert_offset == 4 and m.config.num_experts == 16
    params = m.init_params(jax.random.PRNGKey(3), jnp.float32)
    assert m.serving_family().counts == ExpertPairs(4, 15, elsewhere=True)
    prompt = prompt_tokens(9, 21)
    engine = engine_for((m, params))
    logits = system_logits(engine, prompt, 16)
    ref = reference_logits((m, params), prompt, list(range(15, 21)), hf=hf)
    assert rel_l2(logits, ref) < TOL


def test_the_plain_expert_form_and_its_tiles():
    """``up``/``down`` without ``gate`` is ``act(x W1) W2``; 2,688 = 21 x
    128 runs in tiles of 384 and 896."""
    assert dropless._tile(2688, 512) == 384
    assert dropless._tile(2688, 1792) == 896
    (tm, tk, tn), _, _ = dropless._tilings(2816, 1024, 2688, 640)
    assert (tm, tk, tn) == (128, 1024, 384)
    (tm, tk, tn), _, _ = dropless._tilings(2816, 2688, 1024, 640)
    assert (tm, tk, tn) == (128, 896, 512)
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    h, up, down = f(5, 8), f(3, 8, 12), f(3, 12, 8)
    idx = jnp.asarray([[0, 2], [1, 0], [2, 1], [0, 1], [2, 0]], jnp.int32)
    g = jnp.abs(f(5, 2))
    out, pairs = dropless.dropless_experts(
        h, idx, g, {"up": up, "down": down}, act=dropless.squared_relu)
    want = sum(g[:, j:j + 1] * jnp.einsum(
        "tf,tfd->td", jnp.square(jax.nn.relu(jnp.einsum(
            "td,tdf->tf", h, up[idx[:, j]]))), down[idx[:, j]])
        for j in range(2))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4,
                               atol=1e-4)
    assert pairs.tolist() == [4, 3, 3]


# ---- the other callers of dropless.py keep the parent's programs -----------
def _block_digests():
    """name → digest of the jaxpr of each OTHER caller's expert block (the
    sigmoid block of Xing4, the softmax block of a share: Qwen3-Next and
    Keye, LongCat's zero-expert block, one layer's experts without a stack,
    the grouped matmul's forward and backward as Mixtral trains it), on the
    device branch (megablox)."""
    import hashlib
    import re

    bf16 = jnp.bfloat16

    def sds(shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype)

    T, D, F, E, L = 64, 256, 128, 8, 3
    experts = {"gate": sds((L, E, D, F)), "up": sds((L, E, D, F)),
               "down": sds((L, E, F, D))}
    shared = {"gate": sds((D, F)), "up": sds((D, F)), "down": sds((F, D))}

    def digest(fn, *args):
        text = " ".join(str(jax.make_jaxpr(fn)(*args)).split())
        # a kernel's source line rides in its call's parameters
        text = re.sub(r" at [^ ]*\.py:\d+", "", text)
        return f"{len(text)}:{hashlib.sha256(text.encode()).hexdigest()[:16]}"

    h, valid, layer = sds((T, D)), sds((T,), jnp.bool_), sds((), jnp.int32)
    f32 = jnp.float32
    return {
        "sigmoid": digest(
            lambda h, r, b, s, e, v, l: dropless.sigmoid_moe_block(
                h, {"router": {"kernel": r, "bias": b}, "shared": s}, k=2,
                scaling=2.5, valid=v, experts=e, layer=l),
            h, sds((D, E), f32), sds((E,), f32), shared, experts, valid,
            layer),
        "softmax_share": digest(
            lambda h, r, s, g, e, v, l: dropless.softmax_moe_block(
                h, {"router": {"kernel": r}, "shared": s,
                    "shared_gate": {"kernel": g}}, k=2, offset=8, valid=v,
                experts=e, layer=l),
            h, sds((D, 4 * E), f32), shared, sds((D, 1)), experts, valid,
            layer),
        "zero_expert": digest(
            lambda h, r, b, e, v, l: dropless.zero_expert_moe_block(
                h, {"router": {"kernel": r, "bias": b}}, k=3, scaling=6.0,
                identity_from=4 * E, offset=0, valid=v, experts=e, layer=l),
            h, sds((D, 4 * E + 4), f32), sds((4 * E + 4,), f32), experts,
            valid, layer),
        "one_layer": digest(
            lambda h, i, w, e: dropless.dropless_experts(h, i, w, e),
            h, sds((T, 2), jnp.int32), sds((T, 2), f32),
            {k: sds(v.shape[1:]) for k, v in experts.items()}),
        "train": digest(
            jax.grad(lambda x, w, s: dropless.grouped_matmul(x, w, s).astype(
                f32).sum(), argnums=(0, 1)),
            sds((256, D)), sds((E, D, F)), sds((E,), jnp.int32)),
    }


#: at the parent commit (6ae5adc), from ``_block_digests`` run there
PARENT_BLOCKS = {
    "sigmoid": "63109:0fd97f9d19369f4d",
    "softmax_share": "64067:1732cb386870bce3",
    "zero_expert": "65262:a296b399614da5e2",
    "one_layer": "59506:4074a2e7f7f938fa",
    "train": "51863:83cf9a1a6cbb3143",
}


def test_the_other_expert_blocks_keep_the_parents_programs(monkeypatch):
    """The expert form is read off the tree and the activation has a
    default, so no other caller passes anything new: their jaxprs are the
    parent's text for text (the whole programs of Mistral, Qwen3-Next and
    Olmo-Hybrid: ``test_kv_row_forms.py``)."""
    monkeypatch.setattr(dropless, "_on_tpu", lambda: True)
    assert _block_digests() == PARENT_BLOCKS
