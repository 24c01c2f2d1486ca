"""What PR 32 added: the Qwen3-Next configuration (a chip's share of a
6-stage x 4-chip deployment), its sessions cell, the Gated DeltaNet decode
kernel's roofline counts and the metrics that read the new spans."""
import json
import os

import pytest

from lib import flops_gdn, manifest

sessions = manifest.load_module("generators", "sessions")
MAN = manifest.manifest()
BIG_SEED = 2 ** 31 + 12345
CONFIG = "qwen3-next-80b-a3b-depth8-ep4"
CELL = "qwen3next-80b-serve-sessions"
TRAFFIC = "sessions-128-longout"
ACCEPTED = ["mistral7b-train-1chip", "mistral7b-serve-decode",
            "mistral7b-serve-prefill", "mixtral8x7b-train-zero3-4chip",
            "mistral7b-serve-decode-longctx", "xing4-29b-serve-sessions"]
DECODE = ["mistral7b-serve-decode", "mistral7b-serve-decode-longctx",
          "xing4-29b-serve-sessions", CELL]


def traffic(name=TRAFFIC):
    with open(os.path.join(manifest.BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_the_manifest_gained_one_configuration_and_one_cell():
    assert [w["name"] for w in MAN["workloads"]] == ACCEPTED + [CELL]
    cell = manifest.cell(MAN, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    entry = MAN["configs"][-1]
    assert entry["name"] == CONFIG and len(MAN["configs"]) == 5
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/Qwen/Qwen3-Next-80B-"
                               "A3B-Instruct/blob/main/config.json")


def test_the_configuration_keeps_every_published_width():
    """Depth, the experts held and the vocabulary slice are the cut; every
    other number is the catalog's, and the share has keys of its own."""
    config = manifest.config_of(MAN, CONFIG)
    published = dict(
        decoder_sparse_step=1, full_attention_interval=4, head_dim=256,
        hidden_size=2048, intermediate_size=5120, linear_conv_kernel_dim=4,
        linear_key_head_dim=128, linear_num_key_heads=16,
        linear_num_value_heads=32, linear_value_head_dim=128,
        max_position_embeddings=262144, moe_intermediate_size=512,
        num_attention_heads=16, num_experts_per_tok=10,
        num_key_value_heads=2, partial_rotary_factor=0.25,
        rms_norm_eps=1e-06, rope_theta=10000000,
        shared_expert_intermediate_size=512)
    for key, value in published.items():
        assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (8, 128, 37984)
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512, "vocab_size": 151936}
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (config["ep_size"], config["ep_rank"]) == (4, 0)
    assert config["num_experts"] * config["ep_size"] == 512
    assert config["vocab_size"] * 4 == 151936
    assert "6" in config["deployment"] and "24 chips" in config["deployment"]
    assert "more than its share" in config["deployment"]
    assert set(config["assumed"]) >= {"fused_projection_columns",
                                      "state_dtype", "mtp", "weights"}
    serving = config["serving"]
    assert serving == dict(max_seqs=64, max_tokens=512, max_ctx=3200,
                           block_size=64, prefix_cache=False,
                           kv_reserve_bytes=2 ** 31, max_queue=64)
    assert "max_queue" in config["serving_why"]
    assert config["system"] == "lib.qwen3next_system"


def test_every_seed_offers_the_same_work_in_another_order():
    job = traffic()
    # ISSUE 32's one permitted fall-back was taken: 128 sessions -> 64
    assert job["kind"] == "sessions" and job["sessions"] == 64
    docs = sessions.document_lengths(job)
    assert docs == sessions.document_lengths(job)      # schedule_seed alone
    assert len(docs) == 64 and all(256 <= d <= 1024 for d in docs)
    for index in (0, 1, 5):
        a = sessions.round_of(job, index, 1)
        b = sessions.round_of(job, index, BIG_SEED)
        assert a == sessions.round_of(job, index, 1)
        assert a != b and sorted(a) == sorted(b) and len(a) == 64
        assert all(16 <= q <= 64 and 512 <= ans <= 2048 for q, ans in a)


def test_the_cell_fits_its_configuration():
    job = traffic()
    serving = manifest.config_of(MAN, CONFIG)["serving"]
    longest = job["document_tokens"]["max"] + job["question_tokens"]["max"] \
        + job["answer_tokens"]["max"]
    assert longest <= serving["max_ctx"]
    assert job["sessions"] == serving["max_seqs"] == serving["max_queue"]


def test_gdn_decode_counts_against_a_hand_count():
    """One layer's call at the cell's shape: 128 sequences x 32 heads x a
    [128, 128] float32 state, read once and written once."""
    model = manifest.config_of(MAN, CONFIG)
    assert flops_gdn.state_values(model) == 32 * 128 * 128 == 524288
    state = 128 * 2 * 524288 * 4                    # 536,870,912
    vectors = 128 * 32 * (4 * 128 + 2) * 4          # q k v o + two gates
    assert flops_gdn.gdn_decode_bytes(model, 128) == state + vectors
    assert abs(flops_gdn.gdn_decode_bytes(model, 128) / 819e9 - 0.667e-3) \
        < 0.01e-3                                   # the issue's 0.67 ms
    per_head = 7 * 128 * 128 + 3 * 128
    assert flops_gdn.gdn_decode_flops(model, 128) == 128 * 32 * per_head
    # under one FLOP a byte: the bytes bound it on any chip
    assert flops_gdn.gdn_decode_flops(model, 1) \
        < flops_gdn.gdn_decode_bytes(model, 1)


def test_the_new_metrics_and_the_list_edits():
    per_layer = {m["name"]: m for m in MAN["per_layer"]}
    rate, tpot = "serve_tokens_per_s", "tpot_p50_ms"
    for metric, moves, source in (
            ("kernel_share.gdn_decode", tpot, "device_trace"),
            ("gdn_decode_roofline", tpot, "device_trace"),
            ("phase_share.gdn.decode", rate, "device_trace"),
            ("state_fill_mean.decode", rate, "program_span")):
        entry = per_layer[metric]
        assert entry["workloads"] == [CELL], metric
        assert (entry["moves"], entry["source"]) == (moves, source), metric
        spec = manifest.metric_of(metric)
        manifest.load_module("readers", spec["reader"])
    assert per_layer["gdn_decode_roofline"]["unit"] == "%"
    assert [m["name"] for m in MAN["per_layer"]][-4:] == [
        "kernel_share.gdn_decode", "gdn_decode_roofline",
        "phase_share.gdn.decode", "state_fill_mean.decode"]
    for metric in ("decode_batch_occupancy", "compiles_in_window.decode",
                   "kv_fill_peak.decode", "kv_fill_mean.decode",
                   "idle_share.decode", "hbm_peak_gib.decode",
                   "tpot_p95_ms.decode", "sched_own_share.decode",
                   "idle_in_drain.decode", "idle_unowned.decode",
                   "idle_in_dispatch.decode", "requests_unfinished.decode",
                   "phase_share.moe.decode", "moe_load_max_share",
                   "prefill_time_share.decode",
                   "kernel_share.paged_decode.decode",
                   "paged_decode_roofline"):
        assert per_layer[metric]["workloads"][-1] == CELL, metric
        assert per_layer[metric]["workloads"].count(CELL) == 1
    # the two set-up metrics list the accepted cells and the new one
    for metric in ("cache_entries_added", "tpu_client_s"):
        assert per_layer[metric]["workloads"] == ACCEPTED + [CELL]
    # every per-layer metric of the cell moves an end-to-end metric it
    # reports; the Xing-only and prefix metrics stay out
    judged = {m["name"] for m in manifest.metrics_for(MAN, CELL,
                                                      "end_to_end")}
    assert judged == {rate, tpot, "setup_s"}
    mine = manifest.metrics_for(MAN, CELL, "per_layer")
    assert all(m["moves"] in judged for m in mine)
    assert not {"phase_share.hc.decode", "mla_decode_roofline",
                "prefix_hit_token_share"} & {m["name"] for m in mine}


def test_tpot_is_judged_in_exactly_the_four_decode_cells():
    """What ``test_sessions_cells.py::
    test_tpot_is_judged_in_the_three_decode_cells_only`` guarded before this
    cell joined the list (that test names three cells and fails since; a
    file that exists is not this PR's to edit)."""
    by_name = {m["name"]: m for m in MAN["end_to_end"]}
    assert by_name["tpot_p50_ms"]["workloads"] == DECODE
    assert by_name["serve_tokens_per_s"]["workloads"] == DECODE
    for cell in DECODE:
        kind = traffic(manifest.cell(MAN, cell)["traffic"])["kind"]
        assert kind in ("closed_loop", "sessions"), cell
    assert "tpot_p50_ms.prefill" in {m["name"] for m in MAN["per_layer"]}


def test_the_system_module_has_what_the_generator_asks_for():
    import importlib

    module = importlib.import_module("lib.qwen3next_system")
    assert all(callable(getattr(module, name)) for name in (
        "build", "check_against_reference", "check_served"))
    source = open(sessions.__file__).read()
    assert "qwen" not in source.lower()
    plan = module.check_plan(module.CHECK_PROMPT + module.SINGLES
                             + module.WINDOWS, 512)
    assert plan["chunk_ends"] == 3 and module.CHECK_PROMPT % 64
    assert len(plan["positions"]) == 3 + module.SINGLES + module.WINDOWS
