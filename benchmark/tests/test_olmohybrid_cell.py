"""What PR 34 added: the Olmo-Hybrid configuration (one of four pipeline
stages of a dense hybrid, cut in depth alone), its rollout cell, the Gated
DeltaNet decode kernel's counts at widths that tile no lane, the plain
reference, and the two metrics that read what is new."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import flops, flops_gdn, manifest
from reference import olmo_hybrid as reference

sessions = manifest.load_module("generators", "sessions")
MAN = manifest.manifest()
BIG_SEED = 2 ** 31 + 54321
CONFIG = "olmo-hybrid-7b-depth8"
CELL = "olmohybrid7b-serve-rollouts"
TRAFFIC = "sessions-128-rollout"
ACCEPTED = ["mistral7b-train-1chip", "mistral7b-serve-decode",
            "mistral7b-serve-prefill", "mixtral8x7b-train-zero3-4chip",
            "mistral7b-serve-decode-longctx", "xing4-29b-serve-sessions",
            "qwen3next-80b-serve-sessions"]
DECODE = ["mistral7b-serve-decode", "mistral7b-serve-decode-longctx",
          "xing4-29b-serve-sessions", "qwen3next-80b-serve-sessions", CELL]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def traffic(name=TRAFFIC):
    with open(os.path.join(manifest.BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_the_manifest_gained_one_configuration_and_one_cell():
    """The successor of ``test_qwen3next_cell.py``'s test of the same name,
    which pins seven cells and fails since this one (a file that exists is
    not this PR's to edit)."""
    assert [w["name"] for w in MAN["workloads"]] == ACCEPTED + [CELL]
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) == 1
    cell = manifest.cell(MAN, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    entry = MAN["configs"][-1]
    assert entry["name"] == CONFIG and len(MAN["configs"]) == 6
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == ("https://huggingface.co/allenai/"
                               "Olmo-Hybrid-7B/blob/main/config.json")


def test_the_configuration_keeps_every_published_width():
    """Depth is the only cut: every other key is the catalog's, nested
    groups whole."""
    config = manifest.config_of(MAN, CONFIG)
    published = dict(
        model_type="olmo_hybrid", vocab_size=100352, hidden_size=3840,
        intermediate_size=11008, num_attention_heads=30,
        num_key_value_heads=30, hidden_act="silu",
        max_position_embeddings=65536, attention_bias=False,
        rms_norm_eps=1e-06, tie_word_embeddings=False,
        linear_num_key_heads=30, linear_num_value_heads=30,
        linear_key_head_dim=96, linear_value_head_dim=192,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
        rope_parameters={"rope_theta": None})
    for key, value in published.items():
        assert config[key] == value, key
    assert config["layer_types"] == (["linear_attention"] * 3
                                     + ["full_attention"]) * 8
    assert config["num_hidden_layers"] == 8
    assert config["published"] == {"num_hidden_layers": 32}
    assert config["reduced"] == ["num_hidden_layers"]
    assert "head_dim" not in config
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Olmo-Hybrid-7B")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert config[key] == value or key == "num_hidden_layers", key
    assert set(config["assumed"]) >= {
        "head_dim", "block", "qk_norm", "rotary", "linear_keys",
        "fused_projections", "state_dtype", "stored_heads", "weights", "eos"}
    assert "four pipeline stages" in config["deployment"]
    assert "four times" in config["deployment"]
    assert config["serving"] == dict(
        max_seqs=128, max_tokens=512, max_ctx=2304, block_size=64,
        prefix_cache=False, kv_reserve_bytes=2 ** 31, max_queue=128)
    assert config["system"] == "lib.olmohybrid_system"
    tol = config["tolerances"]
    for key in ("logits_rel_l2", "decode_gap_rms", "group_within_share",
                "served_within_share", "served_turn_within_share"):
        assert isinstance(tol[key], float) and key + "_why" in tol, key


def test_the_parameter_and_byte_arithmetic():
    """ISSUE 34's count, again: a linear layer 88.75M + the MLP 126.81M, an
    attention layer 58.98M + the MLP, the embedding and the head; and the
    bytes a sequence owns in both pools."""
    c = manifest.config_of(MAN, CONFIG)
    D, F, V = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    H, dk, dv = c["linear_num_value_heads"], c["linear_key_head_dim"], \
        c["linear_value_head_dim"]
    K = c["linear_conv_kernel_dim"]
    linear = D * (2 * H * dk + 2 * H * dv) + H * dv * D + 2 * D * H \
        + K * (2 * H * dk + H * dv)
    attention = 4 * D * D
    mlp = 3 * D * F
    assert round(linear / 1e6, 2) == 88.75 and round(mlp / 1e6, 2) == 126.81
    assert round(attention / 1e6, 2) == 58.98
    stage = 6 * (linear + mlp) + 2 * (attention + mlp) + 2 * V * D
    assert round(stage / 1e9, 2) == 2.44 and round(2 * stage / 1e9, 2) == 4.87
    whole = 24 * (linear + mlp) + 8 * (attention + mlp) + 2 * V * D
    assert round(whole / 1e9, 2) == 7.43
    # lib/flops.py counts the MODEL's 30 heads: 15,360 B a cached token a
    # layer; the pool stores 32 (16,384 B): the padding is the program's
    assert flops.kv_row_bytes(c) == 2 * 30 * 128 * 2 == 15360
    state = 6 * (H * dk * dv * 4 + (K - 1) * (2 * H * dk + H * dv) * 2)
    assert state == 6 * (2211840 + 69120) == 13685760
    block = 2 * 64 * 64 * 128 * 2
    assert block == 2097152
    assert f"{state / 1e6:.2f} MB" in c["assumed"]["state_dtype"]


def test_gdn_decode_counts_at_widths_that_tile_no_lane():
    """``lib/flops_gdn`` takes the widths from the configuration's
    ``linear_*`` keys: one layer's call at the cell's shape is 128 sequences
    x 30 heads x a [96, 192] float32 state, read once and written once —
    real values; what a layout pads is the program's cost."""
    model = manifest.config_of(MAN, CONFIG)
    assert flops_gdn.state_values(model) == 30 * 96 * 192 == 552960
    state = 128 * 2 * 552960 * 4                    # 128 x 2 x 2,211,840
    vectors = 128 * 30 * (2 * 96 + 2 * 192 + 2) * 4
    assert flops_gdn.gdn_decode_bytes(model, 128) == state + vectors
    assert abs(flops_gdn.gdn_decode_bytes(model, 128) / 819e9 - 0.702e-3) \
        < 0.001e-3                                  # the issue's 702 us
    per_head = 7 * 96 * 192 + 3 * 192
    assert flops_gdn.gdn_decode_flops(model, 128) == 128 * 30 * per_head
    assert flops_gdn.gdn_decode_flops(model, 1) \
        < flops_gdn.gdn_decode_bytes(model, 1)


def test_every_seed_offers_the_same_work_in_another_order():
    job = traffic()
    assert job["kind"] == "sessions" and job["sessions"] == 128
    assert job["document_tokens"] == dict(dist="loguniform", min=256,
                                          max=1024)
    assert job["question_tokens"] == dict(dist="uniform", min=16, max=64)
    assert job["answer_tokens"] == dict(dist="uniform", min=384, max=1152)
    docs = sessions.document_lengths(job)
    assert docs == sessions.document_lengths(job)      # schedule_seed alone
    assert len(docs) == 128 and all(256 <= d <= 1024 for d in docs)
    for index in (0, 1, 5):
        a = sessions.round_of(job, index, 1)
        b = sessions.round_of(job, index, BIG_SEED)
        assert a == sessions.round_of(job, index, 1)
        assert a != b and sorted(a) == sorted(b) and len(a) == 128
        assert all(16 <= q <= 64 and 384 <= ans <= 1152 for q, ans in a)


def test_the_cell_fits_its_configuration():
    job = traffic()
    serving = manifest.config_of(MAN, CONFIG)["serving"]
    longest = job["document_tokens"]["max"] + job["question_tokens"]["max"] \
        + job["answer_tokens"]["max"]
    assert longest <= serving["max_ctx"]
    assert job["sessions"] == serving["max_seqs"] == serving["max_queue"]


def test_the_new_metrics_and_the_list_edits():
    per_layer = {m["name"]: m for m in MAN["per_layer"]}
    rate, tpot = "serve_tokens_per_s", "tpot_p50_ms"
    for metric, source, layer in (
            ("phase_share.mlp.decode", "device_trace", "model"),
            ("state_pad_share.decode", "program_span", "serve engine")):
        entry = per_layer[metric]
        assert entry["workloads"] == [CELL], metric
        assert (entry["moves"], entry["source"], entry["layer"]) == \
            (rate, source, layer), metric
        manifest.load_module("readers", manifest.metric_of(metric)["reader"])
    assert [m["name"] for m in MAN["per_layer"]][-2:] == [
        "phase_share.mlp.decode", "state_pad_share.decode"]
    assert manifest.metric_of("state_pad_share.decode")["args"] == dict(
        span="engine/window_account", value="state_pad_share", stat="mean")
    # the cell joined, at the end, every list the Qwen3-Next cell is on but
    # the experts'
    qwen = "qwen3next-80b-serve-sessions"
    for group in ("end_to_end", "per_layer"):
        for m in MAN[group]:
            listed = m.get("workloads", [])
            if m["name"] in ("phase_share.moe.decode", "moe_load_max_share"):
                assert CELL not in listed, m["name"]
            elif qwen in listed:
                assert listed[-1] == CELL and listed.count(CELL) == 1, \
                    m["name"]
    for metric in ("cache_entries_added", "tpu_client_s"):
        assert per_layer[metric]["workloads"] == ACCEPTED + [CELL]
    for metric in ("gdn_decode_roofline", "kernel_share.gdn_decode",
                   "phase_share.gdn.decode", "state_fill_mean.decode"):
        assert per_layer[metric]["workloads"] == [qwen, CELL], metric
    judged = {m["name"] for m in manifest.metrics_for(MAN, CELL,
                                                      "end_to_end")}
    assert judged == {rate, tpot, "setup_s"}
    mine = manifest.metrics_for(MAN, CELL, "per_layer")
    assert all(m["moves"] in judged for m in mine)
    assert not {"phase_share.hc.decode", "mla_decode_roofline",
                "prefix_hit_token_share", "phase_share.moe.decode"} \
        & {m["name"] for m in mine}


def test_tpot_is_judged_in_exactly_the_five_decode_cells():
    """What ``test_qwen3next_cell.py::
    test_tpot_is_judged_in_exactly_the_four_decode_cells`` guarded before
    this cell joined the list."""
    by_name = {m["name"]: m for m in MAN["end_to_end"]}
    assert by_name["tpot_p50_ms"]["workloads"] == DECODE
    assert by_name["serve_tokens_per_s"]["workloads"] == DECODE
    assert (by_name["tpot_p50_ms"]["bound"],
            by_name["serve_tokens_per_s"]["bound"]) == (0.03, 0.015)


def test_the_system_module_has_what_the_generator_asks_for():
    import importlib

    module = importlib.import_module("lib.olmohybrid_system")
    assert all(callable(getattr(module, name)) for name in (
        "build", "check_against_reference", "check_served"))
    assert "olmo" not in open(sessions.__file__).read().lower()
    config = manifest.config_of(MAN, CONFIG)
    hf = module.published(config, rehearsal=False)
    assert hf["rope_parameters"] == {"rope_theta": None}
    assert hf["num_hidden_layers"] == 8 and len(hf["layer_types"]) == 32
    toy = module.published(config, rehearsal=True)
    assert toy["hidden_size"] == 96 and toy["linear_value_head_dim"] == 64


# ---- the plain reference ---------------------------------------------------
TINY = dict(vocab_size=64, hidden_size=48, intermediate_size=80,
            num_hidden_layers=4, num_attention_heads=6,
            num_key_value_heads=6, rms_norm_eps=1e-6,
            linear_num_key_heads=6, linear_num_value_heads=6,
            linear_key_head_dim=8, linear_value_head_dim=16,
            linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
            rope_parameters={"rope_theta": None})


def _linear_weights(seed=0):
    rng = np.random.default_rng(seed)
    D, H, dk, dv, K = 48, 6, 8, 16, 4
    Kd, Vd = H * dk, H * dv
    return {"w_qkvg": rng.normal(size=(D, 2 * Kd + 2 * Vd)) / D ** 0.5,
            "w_ba": rng.normal(size=(D, 2 * H)) / D ** 0.5,
            "conv": rng.normal(size=(K, 2 * Kd + Vd)) / 2,
            "A_log": np.log(rng.uniform(0.05, 1.0, size=H)),
            "dt_bias": rng.uniform(-1, 1, size=H),
            "gnorm": 1 + 0.3 * rng.normal(size=dv),
            "w_o": rng.normal(size=(Vd, D)) / Vd ** 0.5}


def test_a_linear_layer_agrees_with_a_token_by_token_hand_computation():
    """One Gated DeltaNet mixer of the reference against loops in numpy
    float64 written from ISSUE 34's equations: three convolutions with zeros
    before the sequence, ``beta = 2 sigmoid``, the delta rule a head a
    token, the gated norm a head."""
    w = _linear_weights()
    S, D, H, dk, dv, K = 9, 48, 6, 8, 16, 4
    Kd, Vd = H * dk, H * dv
    x = np.random.default_rng(1).normal(size=(S, D))
    got = np.asarray(reference.gated_delta_net(
        jnp.asarray(x, jnp.float32), w, TINY))

    sigmoid = lambda z: 1 / (1 + np.exp(-z))            # noqa: E731
    silu = lambda z: z * sigmoid(z)                     # noqa: E731
    proj = x @ w["w_qkvg"]
    gate = proj[:, 2 * Kd + Vd:].reshape(S, H, dv)
    ba = x @ w["w_ba"]
    mixed = np.zeros((S, 2 * Kd + Vd))
    for t in range(S):
        for j in range(K):
            src = t - (K - 1) + j
            if src >= 0:
                mixed[t] += w["conv"][j] * proj[src, :2 * Kd + Vd]
    mixed = silu(mixed)
    want = np.zeros((S, D))
    state = np.zeros((H, dk, dv))
    for t in range(S):
        out = np.zeros((H, dv))
        for h in range(H):
            q = mixed[t, h * dk:(h + 1) * dk]
            k = mixed[t, Kd + h * dk:Kd + (h + 1) * dk]
            v = mixed[t, 2 * Kd + h * dv:2 * Kd + (h + 1) * dv]
            q = q / np.sqrt(np.sum(q * q) + 1e-6) / np.sqrt(dk)
            k = k / np.sqrt(np.sum(k * k) + 1e-6)
            beta = 2 * sigmoid(ba[t, h])
            g = -np.exp(w["A_log"][h]) * np.log1p(
                np.exp(ba[t, H + h] + w["dt_bias"][h]))
            state[h] *= np.exp(g)
            state[h] += np.outer(k, beta * (v - state[h].T @ k))
            o = state[h].T @ q
            o = o / np.sqrt(np.mean(o * o) + 1e-6) * w["gnorm"]
            out[h] = o * silu(gate[t, h])
        want[t] = out.reshape(Vd) @ w["w_o"]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # beta runs to 2: without the factor it is another function
    halved = np.asarray(reference.gated_delta_net(
        jnp.asarray(x, jnp.float32), w, TINY, "beta_not_doubled"))
    assert np.abs(halved - want).max() > 100 * np.abs(got - want).max()


@pytest.mark.parametrize("mutation", reference.MUTATIONS)
def test_every_mutation_moves_the_reference(mutation):
    rng = np.random.default_rng(2)
    D, F, V, H = 48, 80, 64, 6
    attn = {"w_q": rng.normal(size=(D, D)) / D ** 0.5,
            "w_k": rng.normal(size=(D, D)) / D ** 0.5,
            "w_v": rng.normal(size=(D, D)) / D ** 0.5,
            "q_norm": 1 + 0.3 * rng.normal(size=D),
            "k_norm": 1 + 0.3 * rng.normal(size=D),
            "w_o": rng.normal(size=(D, D)) / D ** 0.5}

    def mlp():
        return {"w_gate": rng.normal(size=(D, F)) / D ** 0.5,
                "w_up": rng.normal(size=(D, F)) / D ** 0.5,
                "w_down": rng.normal(size=(F, D)) / F ** 0.5,
                "mixer_norm": 1 + 0.3 * rng.normal(size=D),
                "mlp_norm": 1 + 0.3 * rng.normal(size=D)}

    layers = [dict(_linear_weights(3), **mlp()), dict(attn, **mlp())]
    weights = {"embedding": rng.normal(size=(V, D)),
               "norm": 1 + 0.3 * rng.normal(size=D),
               "head": rng.normal(size=(D, V)) / D ** 0.5,
               "layers": [lambda w=w: w for w in layers]}
    row = jnp.asarray(rng.integers(1, V, size=21), jnp.int32)
    positions = [list(range(5, 21))]
    plain = np.asarray(reference.Reference(TINY).logits(
        [row], weights, positions)[0])
    other = np.asarray(reference.Reference(TINY, mutation).logits(
        [row], weights, positions)[0])
    assert np.isfinite(plain).all() and plain.shape == (16, V)
    assert np.linalg.norm(other - plain) / np.linalg.norm(plain) > 0.02, \
        mutation
