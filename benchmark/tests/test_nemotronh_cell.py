"""What PR 60 added: the Nemotron-3-Super configuration (chip 0 of 4 of stage
0 of 8: one whole period of the pattern at published widths), its agents
cell, the state-space-dual decode kernel's roofline counts and the metrics
that read the new scopes and kernels.  The cell and its metrics are asserted
PRESENT, not last: a later PR appends behind them."""
import json
import os

import pytest

from lib import flops, flops_ssd, manifest

sessions = manifest.load_module("generators", "sessions")
MAN = manifest.manifest()
BIG_SEED = 2 ** 31 + 12345
CONFIG = "nemotron-3-super-120b-a12b-depth11-ep4"
CELL = "nemotron3super-serve-agents"
TRAFFIC = "sessions-128-agents"
SIBLING = "qwen3next-80b-serve-sessions"
NEW = {"phase_share.ssd.decode": ("serve_scope_share", "serve_tokens_per_s"),
       "phase_share.moe_latent.decode": ("serve_scope_share",
                                         "serve_tokens_per_s"),
       "kernel_share.ssd_decode": ("kernel_time_share", "tpot_p50_ms"),
       "ssd_decode_roofline": ("ssd_decode_roofline", "tpot_p50_ms"),
       "kernel_share.moe_gmm.decode": ("kernel_time_share", "tpot_p50_ms")}


def traffic():
    with open(os.path.join(manifest.BENCH, "traffic", TRAFFIC + ".json")) as f:
        return json.load(f)


def test_the_manifest_holds_the_configuration_and_its_one_cell():
    cell = manifest.cell(MAN, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    assert [w["name"] for w in MAN["workloads"]
            if w["config"] == CONFIG] == [CELL]
    (entry,) = [c for c in MAN["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers",
        "mtp_hybrid_override_pattern"]
    assert entry["source"] == ("https://huggingface.co/nvidia/NVIDIA-"
                               "Nemotron-3-Super-120B-A12B-BF16/blob/main/"
                               "config.json")


def test_the_configuration_keeps_every_published_width():
    """Depth, the pattern, the experts held, the vocabulary slice and the
    MTP keys are the cut; every other number is the catalog's, and the share
    has keys of its own."""
    config = manifest.config_of(MAN, CONFIG)
    published = dict(
        chunk_size=128, conv_kernel=4, expand=2, head_dim=128,
        hidden_size=4096, intermediate_size=2688, layer_norm_epsilon=1e-05,
        mamba_head_dim=64, mamba_num_heads=128,
        max_position_embeddings=262144, moe_intermediate_size=2688,
        moe_latent_size=1024, moe_shared_expert_intermediate_size=5376,
        n_group=1, n_groups=8, n_shared_experts=1, norm_eps=1e-05,
        num_attention_heads=32, num_experts_per_tok=22,
        num_key_value_heads=2, partial_rotary_factor=1, rope_theta=10000,
        routed_scaling_factor=5, ssm_state_size=128, topk_group=1,
        time_step_floor=0.0001, time_step_max=0.1, time_step_min=0.001,
        mlp_hidden_act="relu2", mamba_hidden_act="silu", use_conv_bias=True,
        use_bias=False, model_type="nemotron_h")
    for key, value in published.items():
        assert config[key] == value, key
    whole = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
             "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    assert config["published"] == {
        "num_hidden_layers": 88, "hybrid_override_pattern": whole,
        "n_routed_experts": 512, "vocab_size": 131072,
        "num_nextn_predict_layers": 1, "mtp_hybrid_override_pattern": "*E"}
    assert len(whole) == 88
    assert (whole.count("M"), whole.count("E"), whole.count("*")) \
        == (40, 40, 8)
    # one whole period: 5 M : 5 E : 1 * = the published 40 : 40 : 8
    assert config["hybrid_override_pattern"] == whole[:11] == "MEMEMEM*EME"
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["num_nextn_predict_layers"]) \
        == (11, 128, 32768, 0)
    assert "mtp_hybrid_override_pattern" not in config
    assert config["reduced"] == list(config["published"])
    assert (config["ep_size"], config["ep_rank"]) == (4, 0)
    assert config["n_routed_experts"] * config["ep_size"] == 512
    for said in ("120.67B", "4.648B parameters = 9.30 GB", "32 chips",
                 "8 pipeline stages", "chip 0 of stage 0",
                 "1,024-wide latents"):
        assert said in config["deployment"], said
    assert set(config["assumed"]) >= {"positions", "mtp", "state_dtype",
                                      "fused_projection_columns", "weights",
                                      "eos", "context"}
    assert "balanced_router" in config["assumed"]["weights"]
    serving = config["serving"]
    assert serving == dict(max_seqs=serving["max_seqs"], max_tokens=512,
                           max_ctx=2240, block_size=64, prefix_cache=False,
                           kv_reserve_bytes=2 ** 31,
                           max_queue=serving["max_seqs"])
    assert serving["max_seqs"] == traffic()["sessions"]
    assert serving["max_seqs"] in (128, 64)     # ISSUE 60's one fall-back
    assert config["system"] == "lib.nemotronh_system"
    tol = config["tolerances"]
    assert 0 < tol["logits_rel_l2"] < 0.2
    assert 0.9 < tol["served_turn_within_share"] \
        <= tol["served_within_share"] < 1


def test_the_parameter_count_is_the_stated_one():
    """By hand from the published widths: 109.64M a Mamba-2 layer, 35.66M
    an attention layer, 54.53M + 5.505M an expert an expert layer."""
    c = manifest.config_of(MAN, CONFIG)
    D, Ci = c["hidden_size"], c["mamba_num_heads"] * c["mamba_head_dim"]
    Cc = flops_ssd.conv_channels(c)
    assert (Ci, Cc) == (8192, 10240)
    H = c["mamba_num_heads"]
    mamba = D + D * (Ci + Cc + H) + (c["conv_kernel"] + 1) * Cc + 3 * H \
        + Ci + Ci * D
    attn = D + 2 * D * c["num_attention_heads"] * c["head_dim"] \
        + 2 * D * c["num_key_value_heads"] * c["head_dim"]
    R, F = c["moe_latent_size"], c["moe_intermediate_size"]
    expert = 2 * R * F
    moe = D + D * 512 + 512 + 2 * D * R \
        + 2 * D * c["moe_shared_expert_intermediate_size"]
    assert round(mamba / 1e6, 2) == 109.64
    assert round(attn / 1e6, 2) == 35.66
    assert round(expert / 1e6, 3) == 5.505
    assert round(moe / 1e6, 2) == 54.53
    whole = 40 * mamba + 8 * attn + 40 * (moe + 512 * expert) \
        + 2 * 131072 * D + D
    assert round(whole / 1e9, 2) == 120.67
    active = 40 * mamba + 8 * attn + 40 * (moe + 22 * expert) \
        + 2 * 131072 * D
    assert round(active / 1e9, 2) == 12.77
    here = 5 * mamba + attn + 5 * (moe + 128 * expert) \
        + 2 * c["vocab_size"] * D + D
    assert round(here / 1e9, 3) == 4.648


def test_every_seed_offers_the_same_work_in_another_order():
    job = traffic()
    assert job["kind"] == "sessions" and job["sessions"] in (128, 64)
    assert job["document_tokens"] == {"dist": "loguniform", "min": 256,
                                      "max": 1024}
    assert job["question_tokens"] == {"dist": "uniform", "min": 16,
                                      "max": 64}
    assert job["answer_tokens"] == {"dist": "uniform", "min": 256,
                                    "max": 1024}
    others = [json.load(open(os.path.join(manifest.BENCH, "traffic", f)))
              for f in os.listdir(os.path.join(manifest.BENCH, "traffic"))
              if f != TRAFFIC + ".json"]
    assert job["schedule_seed"] not in [o.get("schedule_seed")
                                        for o in others]
    assert sessions.document_lengths(job) == sessions.document_lengths(job)
    for index in range(3):
        a = sessions.round_of(job, index, 1)
        b = sessions.round_of(job, index, BIG_SEED)
        assert sorted(a) == sorted(b) and a != b
    # the longest turn fits the configuration's context
    config = manifest.config_of(MAN, CONFIG)
    assert 1024 + 64 + 1024 + config["serving"]["block_size"] \
        <= config["serving"]["max_ctx"]


def test_the_cell_reports_the_siblings_metrics_but_the_deltanet_ones():
    by_name = {m["name"]: m for m in MAN["per_layer"]}
    for m in MAN["end_to_end"]:
        if m["name"] in ("serve_tokens_per_s", "tpot_p50_ms"):
            assert CELL in m["workloads"]
    mine = {m["name"] for m in MAN["per_layer"]
            if CELL in m.get("workloads", [])}
    theirs = {m["name"] for m in MAN["per_layer"]
              if SIBLING in m.get("workloads", [])}
    assert theirs - mine == {"kernel_share.gdn_decode",
                             "gdn_decode_roofline", "phase_share.gdn.decode"}
    assert mine - theirs == set(NEW)
    assert {"paged_decode_roofline", "kernel_share.paged_decode.decode",
            "phase_share.moe.decode", "moe_load_max_share",
            "hbm_peak_gib.decode"} <= mine
    for name, (reader, moves) in NEW.items():
        entry = by_name[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == moves
        assert entry["source"] == "device_trace"
        assert manifest.metric_of(name)["reader"] == reader
    assert by_name["ssd_decode_roofline"]["unit"] == "%"
    assert by_name["ssd_decode_roofline"]["better"] == "higher"


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """On the parent the program has no ``ssd_decode`` kernel and no
    ``attention/ssd_`` scope: the readers return None and do not raise."""
    reader = manifest.load_module("readers", "ssd_decode_roofline")
    args = manifest.metric_of("ssd_decode_roofline")["args"]
    assert reader.read({"trace": None, "peaks": None}, args) is None
    empty = {"device": {"d0": [("fusion.1", 0, 10, "fusion", "fusion", 10)]},
             "host": [("bench/window", 0, 100)]}
    run = {"trace": empty, "peaks": object(), "slice": (0.0, 1.0),
           "samples": {"decode_log": [(0.5, 128, 1000)]},
           "sizes": {"mamba_num_heads": 128}}
    assert reader.read(run, args) is None


def test_one_call_of_the_kernel_by_hand():
    """A layer of a 128-wide step: 128 x (2 x 4 MiB of state + the vectors)
    = 1.08 GB, 1.3 ms at 819 GB/s; 0.6 FLOP a byte, so the bytes bound it."""
    c = manifest.config_of(MAN, CONFIG)
    assert flops_ssd.state_values(c) == 128 * 64 * 128 == 1048576
    vectors = (2 * 8192 + 128 + 2 * 8 * 128) * 4
    assert flops_ssd.ssd_decode_bytes(c, 1) == 2 * 4194304 + vectors
    assert flops_ssd.ssd_decode_bytes(c, 128) == pytest.approx(1.0829e9,
                                                               rel=1e-3)
    # the carry (3 x 10,240 bf16 in and out) is another kernel's: 1.5%
    assert 2 * 3 * flops_ssd.conv_channels(c) * 2 \
        < 0.015 * flops_ssd.ssd_decode_bytes(c, 1)
    assert flops_ssd.ssd_decode_flops(c, 1) == 128 * (5 * 64 * 128 + 3 * 64)
    assert flops_ssd.ssd_decode_flops(c, 128) \
        / flops_ssd.ssd_decode_bytes(c, 128) < 1.0
    # a sequence's state over the 5 layers, and a cached token of the one
    # attention layer (the paged reader counts ONE layer a call)
    assert 5 * (flops_ssd.state_values(c) * 4
                + 3 * flops_ssd.conv_channels(c) * 2) == 21_278_720
    assert flops.kv_row_bytes(c) == 1024
    assert flops.decode_attention_bytes(dict(c, num_hidden_layers=1),
                                        128 * 900) == 128 * 900 * 1024
