"""JoyAI-LLM-Flash on the training path (latent attention expanded, 8-of-256
style sigmoid experts whose selection bias the step balances, the multi-
token-prediction loss) against the benchmark's plain reference
(``benchmark/reference/joyai_flash.py``, the same file the benchmark
imports; it shares no code with ``deepspeed_tpu``), and the state the engine
carries for a model that the optimizer does not train."""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import joyai_flash as J
from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

pytestmark = pytest.mark.moe

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")


@pytest.fixture(scope="module")
def bench():
    """``benchmark/lib/joyai_system`` (the program's tree as the
    reference's weights) and the reference, as the benchmark imports them."""
    sys.path[:0] = [BENCH]
    try:
        system = importlib.import_module("lib.joyai_system")
        reference = importlib.import_module("reference.joyai_flash")
    finally:
        sys.path.remove(BENCH)
    return system, reference


#: published keys at a tiny size: 1 dense + 2 expert layers + the MTP module
HF = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=3, first_k_dense_replace=1,
    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4,
    routed_scaling_factor=2.5, norm_topk_prob=True, scoring_func="sigmoid",
    topk_method="noaux_tc", n_group=1, topk_group=1, rms_norm_eps=1e-6,
    rope_theta=32000000, rope_scaling=None, rope_interleave=True,
    num_nextn_predict_layers=1, max_position_embeddings=256,
    tie_word_embeddings=False)
S, ROWS = 40, 2


def sizes_of(held=16, offset=0):
    return dict(HF, router_outputs=HF["n_routed_experts"],
                n_routed_experts=held, expert_offset=offset,
                mtp_loss_weight=0.3, bias_update_rate=0.001)


def model_of(held=None, offset=0, **kw):
    return J.JoyAIFlashLM.from_hf_config(HF, experts_held=held,
                                         expert_offset=offset, **kw)


def tokens_of(seed=0, rows=ROWS, seq=S):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, HF["vocab_size"], size=(rows, seq)), jnp.int32)


def a_bias(model, seed=5, scale=0.05):
    """A selection bias large enough to change which experts are picked."""
    cfg = model.config
    return scale * jax.random.normal(
        jax.random.PRNGKey(seed),
        (cfg.num_expert_layers, cfg.n_routed_experts), jnp.float32)


@pytest.fixture(scope="module")
def whole(bench):
    """All 16 experts held: model, float32 parameters, reference weights."""
    system, reference = bench
    model = model_of()
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    return model, params, system.reference_weights(params), \
        reference.Reference(sizes_of())


# --------------------------------------------------------------------- #
# (2) model against reference
# --------------------------------------------------------------------- #
def test_both_loss_terms_and_both_heads_logits(whole):
    model, params, weights, ref = whole
    tokens, bias = tokens_of(), a_bias(model)
    state = dict(model.init_model_state(), router_bias=bias)
    loss, counted = jax.jit(model.loss_fn)(params, {"input_ids": tokens},
                                           None, state)
    main_logits, mtp_logits = jax.jit(model)(params, tokens, bias)
    mains, mtps, loads = [], [], 0
    for r in range(ROWS):
        main, mtp, out = ref.loss_terms(weights, tokens[r], bias)
        mains.append(float(main))
        mtps.append(float(mtp))
        loads = loads + np.asarray(out["loads"])
        np.testing.assert_allclose(main_logits[r], out["main_logits"],
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(mtp_logits[r], out["mtp_logits"],
                                   atol=2e-4, rtol=2e-4)
    assert abs(float(counted["main_loss"]) - np.mean(mains)) < 1e-5
    assert abs(float(counted["mtp_loss"]) - np.mean(mtps)) < 1e-5
    assert abs(float(loss) - (np.mean(mains) + 0.3 * np.mean(mtps))) < 1e-5
    assert abs(float(loss) - float(ref.loss(weights, list(tokens), bias))) \
        < 1e-5
    np.testing.assert_array_equal(np.asarray(counted["pairs_routed"]), loads)
    # all experts held: every pair is computed here
    np.testing.assert_array_equal(np.asarray(counted["pairs_computed"]),
                                  loads)
    # the bias changed the picks (else the test shows nothing about it)
    _, plain = jax.jit(model.loss_fn)(params, {"input_ids": tokens}, None,
                                      model.init_model_state())
    assert (np.asarray(plain["pairs_routed"]) != loads).any()


def test_the_reference_in_blocks_is_the_reference(whole, bench):
    """What the chip's comparison runs to fit beside the engine's state —
    heads a group at a time, every layer a checkpoint — gives the numbers
    of the plain form, gradients too."""
    _, reference = bench
    model, params, weights, ref = whole
    blocks = reference.Reference(sizes_of(), head_groups=2, remat=True)
    tokens, bias = tokens_of(8)[0], a_bias(model)
    a, b = ref.forward(weights, tokens, bias), \
        blocks.forward(weights, tokens, bias)
    for key in a:
        np.testing.assert_allclose(a[key], b[key], atol=1e-5, rtol=1e-5)
    paths = [("layers", 1, "kv_b"), ("mtp", "eh_proj")]
    ga = ref.grads(weights, [tokens], bias, paths)
    gb = blocks.grads(weights, [tokens], bias, paths)
    for path in paths:
        np.testing.assert_allclose(ga[path], gb[path], atol=1e-6, rtol=1e-4)


def test_gradients_of_every_leaf(whole, bench):
    system, _ = bench
    model, params, weights, ref = whole
    tokens, bias = tokens_of(1), a_bias(model)
    state = dict(model.init_model_state(), router_bias=bias)
    grads = jax.jit(jax.grad(lambda p: model.loss_fn(
        p, {"input_ids": tokens}, None, state)[0]))(params)
    want = jax.jit(jax.grad(lambda w: ref.loss(w, list(tokens), bias)))(
        weights)
    got = system.reference_weights(grads)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves_with_path(want)
    # the program's stacks as the reference's per-layer leaves: all of them
    assert len(flat_got) == len(flat_want) >= len(jax.tree.leaves(params))
    for (path, g), (_, w) in zip(flat_got, flat_want):
        g, w = np.asarray(g), np.asarray(w)
        assert np.abs(w).max() > 0, path        # every leaf takes gradient
        assert np.linalg.norm(g - w) <= 2e-4 * np.linalg.norm(w), path


@pytest.mark.parametrize("mutation", [
    "scale_of_128", "no_routed_scaling", "bias_in_the_weights",
    "mtp_target_shifted"])
def test_a_wrong_model_is_told_from_the_reference(whole, mutation,
                                                  monkeypatch):
    """What the chip's comparison must catch, caught here at float32: each
    mutation of the PROGRAM moves a loss term or a head's logits far beyond
    the agreement the right program reaches (2e-4)."""
    model, params, weights, ref = whole
    tokens, bias = tokens_of(2), a_bias(model)
    if mutation == "scale_of_128":
        from deepspeed_tpu.models import transformer as T

        real = T._xla_attention
        # 1/sqrt(128-like) for 1/sqrt(192-like): the width without position
        monkeypatch.setattr(
            J, "attention", lambda q, k, v, cfg, causal: real(
                q * (cfg.qk_head_dim / cfg.qk_nope_head_dim) ** 0.5, k, v,
                causal=causal))
    elif mutation == "no_routed_scaling":
        model = model_of(routed_scaling_factor=1.0)
    elif mutation == "bias_in_the_weights":
        from deepspeed_tpu.moe import dropless

        def route(h, router, k, scaling, renormalise=True):
            s = jax.nn.sigmoid(h.astype(jnp.float32)
                               @ router["kernel"].astype(jnp.float32)) \
                + router["bias"]
            g, idx = jax.lax.top_k(s, k)
            g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
            return idx.astype(jnp.int32), g * scaling

        monkeypatch.setattr(J, "sigmoid_topk_route", route)
        assert dropless.sigmoid_topk_route is not route
    state = dict(model.init_model_state(), router_bias=bias)
    if mutation == "mtp_target_shifted":
        shifted = tokens.at[:, 2:].set(tokens[:, 1:-1])   # t+1 where t+2 is
        _, counted = model.loss_fn(params, {"input_ids": tokens}, None, state)
        main_logits, mtp_logits = model(params, tokens, bias)
        logp = jax.nn.log_softmax(mtp_logits[:, :-2], axis=-1)
        wrong = -jnp.mean(jnp.take_along_axis(
            logp, shifted[:, 2:, None], axis=-1))
        right = float(counted["mtp_loss"])
        assert abs(float(wrong) - right) > 0.01
        return
    main_logits, mtp_logits = model(params, tokens, bias)
    out = ref.forward(weights, tokens[0], bias)
    worst = max(
        float(np.linalg.norm(np.asarray(a[0]) - np.asarray(out[k]))
              / np.linalg.norm(np.asarray(out[k])))
        for a, k in ((main_logits, "main_logits"), (mtp_logits,
                                                    "mtp_logits")))
    assert worst > 0.02, (mutation, worst)


# --------------------------------------------------------------------- #
# (3) the shares add up to the uncut layer
# --------------------------------------------------------------------- #
def test_the_shares_add_up_to_the_uncut_layer(whole, bench):
    """Model-configs guide section 4: the routed parts the 4 shares of 4
    experts compute, plus the shared expert counted ONCE, are what the
    uncut reference gives for the whole expert layer; every share routes
    over all 16 outputs and counts the same loads."""
    system, reference = bench
    model, params, weights, ref = whole
    cfg = model.config
    lp = jax.tree.map(lambda x: x[1], params["moe_layers"])
    w = weights["layers"][2]
    bias = a_bias(model)[1]
    h = jax.random.normal(jax.random.PRNGKey(3), (48, cfg.hidden_size))
    want, loads, _ = ref.experts(h, w, bias)
    shared = ref.swiglu(h, w["shared"]["gate"], w["shared"]["up"],
                        w["shared"]["down"])
    total, computed = 0, 0
    for rank in range(4):
        share = model_of(held=4, offset=4 * rank).config
        part = dict(lp, experts={k: v[4 * rank:4 * rank + 4]
                                 for k, v in lp["experts"].items()})
        out, routed_to, pairs = J.moe_block(h, part, bias, share)
        np.testing.assert_array_equal(np.asarray(routed_to),
                                      np.asarray(loads))
        np.testing.assert_array_equal(
            np.asarray(pairs), np.asarray(loads)[4 * rank:4 * rank + 4])
        total = total + (out - shared)
        computed += int(pairs.sum())
        # the reference given the same share gives the same part
        ref_share = reference.Reference(sizes_of(4, 4 * rank))
        part_w = dict(w, experts={k: v[4 * rank:4 * rank + 4]
                                  for k, v in w["experts"].items()})
        np.testing.assert_allclose(out, ref_share.experts(h, part_w, bias)[0],
                                   atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(total + shared, want, atol=5e-5, rtol=2e-4)
    assert computed == 48 * cfg.num_experts_per_tok    # none dropped


def test_a_share_takes_gradient_only_through_the_pairs_it_holds(whole):
    """The rows of a share's grouped matmul that are in no group (pairs held
    elsewhere) hand the tokens a ZERO cotangent: the input's gradient is the
    dense oracle's over the experts held."""
    model, params, _, _ = whole
    cfg = model_of(held=4, offset=8).config
    lp = jax.tree.map(lambda x: x[0], params["moe_layers"])
    part = dict(lp, experts={k: v[8:12] for k, v in lp["experts"].items()})
    bias = a_bias(model)[0]
    h = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.hidden_size))

    def dense(h):
        idx, g = J.sigmoid_topk_route(
            h, {"kernel": part["router"]["kernel"], "bias": bias},
            cfg.num_experts_per_tok, cfg.routed_scaling_factor)
        out = 0
        for e in range(4):
            w = jnp.sum(jnp.where(idx == 8 + e, g, 0.0), axis=-1)
            x = part["experts"]
            out = out + w[:, None] * (
                (jax.nn.silu(h @ x["gate"][e]) * (h @ x["up"][e]))
                @ x["down"][e])
        sh = part["shared"]
        return jnp.sum((out + (jax.nn.silu(h @ sh["gate"]) * (h @ sh["up"]))
                        @ sh["down"]) ** 2)

    got = jax.grad(lambda h: jnp.sum(J.moe_block(h, part, bias, cfg)[0] ** 2)
                   )(h)
    np.testing.assert_allclose(got, jax.grad(dense)(h), atol=1e-4, rtol=1e-3)


# --------------------------------------------------------------------- #
# (5) MTP targets
# --------------------------------------------------------------------- #
def test_main_predicts_t1_and_mtp_t2_over_the_positions_with_a_target(whole):
    model, params, _, _ = whole
    tokens = tokens_of(6)
    state = model.init_model_state()
    _, counted = model.loss_fn(params, {"input_ids": tokens}, None, state)
    main_logits, mtp_logits = model(params, tokens)

    def ce(logits, targets):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return float(-jnp.mean(jnp.take_along_axis(
            logp, targets[..., None], axis=-1)))

    # S - 1 positions have a next token, S - 2 a token after that
    assert abs(ce(main_logits[:, :-1], tokens[:, 1:])
               - float(counted["main_loss"])) < 1e-5
    assert abs(ce(mtp_logits[:, :-2], tokens[:, 2:])
               - float(counted["mtp_loss"])) < 1e-5
    # the positions without a target are out of both means: another last
    # token (never an input of a counted position's target) moves the main
    # term only through position S - 1's own logits, which are not in it
    other = tokens.at[:, -1].set((tokens[:, -1] + 1) % HF["vocab_size"])
    _, moved = model.loss_fn(params, {"input_ids": other}, None, state)
    main_a = ce(main_logits[:, :-2], tokens[:, 1:-1])
    main_b = ce(model(params, other)[0][:, :-2], other[:, 1:-1])
    assert abs(main_a - main_b) < 1e-6
    assert float(moved["main_loss"]) != float(counted["main_loss"])


# --------------------------------------------------------------------- #
# (4) through initialize() / train_batch()
# --------------------------------------------------------------------- #
DS = {"train_micro_batch_size_per_gpu": 2,
      "optimizer": {"type": "AdamW",
                    "params": {"lr": 3e-4, "weight_decay": 0.1}},
      "gradient_clipping": 1.0, "bf16": {"enabled": True},
      "zero_optimization": {"stage": 0}, "steps_per_print": 1000}


def engine_of(devices=1, rows=12, seed=0, held=4, ds=None, **kw):
    model = model_of(held=held, offset=4 if held else 0, **kw)
    params = model.init_params(jax.random.PRNGKey(seed), jnp.float32)
    topo = initialize_mesh(TopologyConfig(),
                           devices=jax.devices()[:devices], force=True)
    data = [{"input_ids": np.asarray(row)} for row in
            tokens_of(seed + 10, rows=rows * devices)]
    engine, _, loader, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, training_data=data,
        config=dict(ds or DS), topology=topo, seed=seed)
    return engine, model, iter(loader)


def test_the_bias_follows_the_rule_and_the_optimizer_leaves_it_alone(bench):
    """Over several steps: the bias the engine carries is the reference's
    rule on the loads the step counted (no gradient, no weight decay — 0.1
    here would shrink it every step —, no clipping, no loss scale), the
    counters add up, nothing is dropped, and the trained parameters DO
    move."""
    _, reference = bench
    engine, model, batches = engine_of()
    ref = reference.Reference(sizes_of(4, 4))
    cfg = model.config
    before = jax.device_get(engine.state.params["moe_layers"]["router"])
    bias = np.zeros((cfg.num_expert_layers, cfg.n_routed_experts))
    seen = np.zeros_like(bias)
    for step in range(4):
        engine.train_batch(next(batches))
        state = jax.device_get(engine.state.model_state)
        loads = np.asarray(state["pairs_routed"]) - seen
        seen += loads
        assert (loads.sum(axis=-1) == 2 * S * cfg.num_experts_per_tok).all()
        bias = np.asarray(ref.next_bias(bias, loads), np.float64)
        np.testing.assert_allclose(state["router_bias"], bias, atol=1e-7)
        assert int(state["steps"]) == step + 1
        np.testing.assert_array_equal(
            state["pairs_computed"], np.asarray(state["pairs_routed"])[:, 4:8])
    assert np.abs(bias).max() > 0.0019          # it moved, step after step
    after = jax.device_get(engine.state.params["moe_layers"]["router"])
    assert np.abs(after["kernel"] - before["kernel"]).max() > 0
    report = engine.report_model_state()
    assert report["steps"] == 4 and 0.1 < report["moe_pairs_held_share"] < 0.5
    assert abs(report["router_bias_abs_max"] - np.abs(bias).max()) < 1e-7
    assert np.isfinite(report["main_loss"]) and report["mtp_loss"] > 0
    with pytest.raises(NotImplementedError, match="train_batch"):
        engine.backward(next(batches))
    engine.close()


def test_micro_batches_add_their_loads_before_the_sign(bench):
    """gradient_accumulation_steps 2: one update of the bias a step, from
    the loads of both micro-batches added up; the loss terms are means."""
    _, reference = bench
    ds = dict(DS, gradient_accumulation_steps=2)
    engine, model, batches = engine_of(ds=ds)
    ref = reference.Reference(sizes_of(4, 4))
    batch = next(batches)
    rows = batch["input_ids"].shape[0]
    assert rows % 2 == 0
    engine.train_batch(batch)
    state = jax.device_get(engine.state.model_state)
    cfg = model.config
    loads = np.asarray(state["pairs_routed"])
    # both micro-batches' pairs, every row of the step's batch
    assert (loads.sum(axis=-1) == rows * S * cfg.num_experts_per_tok).all()
    np.testing.assert_allclose(
        state["router_bias"],
        np.asarray(ref.next_bias(np.zeros_like(loads, np.float32), loads)),
        atol=1e-7)
    assert int(state["steps"]) == 1 and 4 < float(state["main_loss"]) < 7
    engine.close()


def test_a_save_and_a_load_restore_the_state(tmp_path):
    engine, model, batches = engine_of()
    for _ in range(2):
        engine.train_batch(next(batches))
    saved = jax.device_get(engine.state.model_state)
    engine.save_checkpoint(str(tmp_path), tag="two")
    engine.train_batch(next(batches))
    moved = jax.device_get(engine.state.model_state)
    assert (moved["router_bias"] != saved["router_bias"]).any()
    engine.close()
    fresh, _, batches = engine_of(seed=1)
    fresh.load_checkpoint(str(tmp_path), tag="two")
    got = jax.device_get(fresh.state.model_state)
    for key in saved:
        np.testing.assert_array_equal(got[key], saved[key])
    fresh.train_batch(next(batches))            # and the step goes on
    assert int(jax.device_get(fresh.state.model_state["steps"])) == 3
    fresh.close()


def test_a_data_parallel_mesh_sums_the_loads_before_the_sign(bench):
    """Four data shards, each routing its own two sequences: the bias is
    the rule on the loads of all eight — summed over the data axis BEFORE
    the sign — which one device given the same eight sequences counts too;
    a shard's own loads would give another bias."""
    _, reference = bench
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    four, model, batches = engine_of(devices=4, rows=2)
    batch = next(batches)
    assert batch["input_ids"].shape == (8, S)
    four.train_batch(batch)
    got = jax.device_get(four.state.model_state)
    four.close()
    one, _, _ = engine_of(devices=1, ds=dict(
        DS, train_micro_batch_size_per_gpu=8))
    one.train_batch(jax.device_get(batch))
    want = jax.device_get(one.state.model_state)
    one.close()
    cfg = model.config
    assert (np.asarray(got["pairs_routed"]).sum(axis=-1)
            == 8 * S * cfg.num_experts_per_tok).all()
    # bf16 noise may move a near-tie between the two programs: the loads
    # agree but for a handful of pairs, and the bias wherever the load is
    # not within them of the mean
    diff = np.abs(np.asarray(got["pairs_routed"], np.int64)
                  - np.asarray(want["pairs_routed"], np.int64))
    assert diff.sum() <= 0.01 * np.asarray(want["pairs_routed"]).sum()
    ref = reference.Reference(sizes_of(4, 4))
    loads = np.asarray(got["pairs_routed"])
    np.testing.assert_allclose(
        got["router_bias"],
        np.asarray(ref.next_bias(np.zeros_like(loads, np.float32), loads)),
        atol=1e-7)
    np.testing.assert_array_equal(got["pairs_computed"], loads[:, 4:8])
    # a shard alone (the first two sequences) gives another bias
    alone, _, _ = engine_of(devices=1)
    alone.train_batch({"input_ids": jax.device_get(batch["input_ids"])[:2]})
    own = jax.device_get(alone.state.model_state)["router_bias"]
    alone.close()
    assert (np.sign(own) != np.sign(got["router_bias"])).any()


# --------------------------------------------------------------------- #
# (6) a model that declares no such state
# --------------------------------------------------------------------- #
def test_a_model_without_state_compiles_the_step_it_always_did():
    """Mistral-shaped ``CausalLM``: the engine carries ``None``, the step's
    jaxpr is the one the parent's ``_loss_and_grads`` (copied below) gives,
    and three steps' losses are the same to the bit."""
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
    from deepspeed_tpu.runtime.activation_checkpointing import \
        checkpointing as act

    def build(parent: bool):
        model = CausalLM(TransformerConfig.tiny(remat=True))
        params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
        topo = initialize_mesh(TopologyConfig(), devices=jax.devices()[:1],
                               force=True)
        data = [{"input_ids": np.asarray(r)} for r in tokens_of(3, rows=8)]
        engine, _, loader, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, training_data=data,
            config=dict(DS), topology=topo, seed=0)
        if parent:
            def loss_and_grads(params, batch, rng, scaler_state,
                               constrain=True, model_state=None):
                def scaled_loss(p32):
                    with jax.named_scope("zero/gather_params"):
                        p = jax.tree.map(
                            lambda x: x.astype(engine.compute_dtype), p32)
                    with act.engine_memory(*engine._device_memory):
                        out = engine.loss_fn(p, batch, rng)
                    loss = out[0] if isinstance(out, tuple) else out
                    return engine.loss_scaler.scale_loss(
                        loss.astype(jnp.float32), scaler_state), loss

                grads, loss = jax.grad(scaled_loss, has_aux=True)(params)
                grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
                if constrain:
                    grads = engine._constrain_grads(grads)
                return loss, grads, None

            engine._loss_grads_counted = loss_and_grads
        return engine, iter(loader)

    runs = []
    for parent in (False, True):
        engine, batches = build(parent)
        assert engine.state.model_state is None
        first = next(batches)
        losses = [float(engine.train_batch(first))]
        text = str(jax.make_jaxpr(engine._build_train_batch_fn())(
            engine.state, first))
        losses += [float(engine.train_batch(next(batches)))
                   for _ in range(2)]
        runs.append((text, losses))
        engine.close()
    assert runs[0][0] == runs[1][0]
    assert runs[0][1] == runs[1][1]


def test_the_checkpoint_rule_names_this_layers_values():
    """PR 48/63's rule (``checkpointing.layer_policy``) chooses among THIS
    layer's values: the queries, the expanded k/v, the flash kernel's own,
    the residual, the shared expert's gate and up, the sorted pairs' rows.
    Outside an engine's step (no memory report) a layer keeps nothing; with
    room for everything it keeps every name; a tight budget keeps the
    dearest seconds a byte first — the kernel's output before the pairs'
    rows, whose FLOPs are a sixteenth of their bytes."""
    from deepspeed_tpu.moe.dropless import PAIR_ROW_NAMES
    from deepspeed_tpu.ops.transformer.flash_attention import (LSE_NAME,
                                                               OUT_NAME)
    from deepspeed_tpu.runtime.activation_checkpointing import \
        checkpointing as act

    cfg = J.JoyAIFlashConfig(experts_held=16, vocab_size=16160, num_layers=5,
                             attn_impl="flash")
    tensors, reserve = J._remat_layout(cfg, 2, 4096, 2)
    names = [n for t in tensors for n in t.names]
    assert set(names) == {J.Q_NAME, J.KV_NAME, OUT_NAME, LSE_NAME,
                          "attn_residual", *J.SHARED_NAMES, *PAIR_ROW_NAMES}
    by_name = {t.names[0]: t for t in tensors}
    assert by_name[J.KV_NAME].bytes == 8192 * 32 * 256 * 2
    assert by_name[PAIR_ROW_NAMES[0]].bytes == 65536 * 768 * 2
    assert reserve >= 3 * 8192 * 16160 * 4          # one head's logits
    assert act.select_saved(tensors, 7, 0) == ()
    assert set(act.select_saved(tensors, 7, 1 << 40)) == set(names)
    tight = act.select_saved(tensors, 7, 7 * by_name[OUT_NAME].bytes + 1)
    assert OUT_NAME in tight and not set(PAIR_ROW_NAMES) & set(tight)
    # through the model: no engine, so the policy saves nothing and the loss
    # is the un-checkpointed model's
    model, plain = model_of(remat=True), model_of()
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    tokens = tokens_of(9)
    a = jax.grad(lambda p: model.loss_fn(p, {"input_ids": tokens}, None)[0])(
        params)
    b = jax.grad(lambda p: plain.loss_fn(p, {"input_ids": tokens}, None)[0])(
        params)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(x, y, atol=1e-6, rtol=1e-4)
    with pytest.raises(ValueError, match="remat_policy"):
        model_of(remat=True, remat_policy="no_such_policy").loss_fn(
            params, {"input_ids": tokens}, None)


def test_serving_raises_by_name():
    with pytest.raises(NotImplementedError, match="joyai_flash"):
        model_of().serving_family()
