"""What PR 41 added: the LongCat-Flash configuration (chip 0 of a 7-stage x
32-chip deployment), its chat sessions cell, the two metrics that read the
identity experts, and the cell's CPU rehearsal."""
import contextlib
import io
import json
import os
import re

from lib import flops_mla, manifest

sessions = manifest.load_module("generators", "sessions")
MAN = manifest.manifest()
BIG_SEED = 2 ** 31 + 12345
CONFIG = "longcat-flash-chat-depth4-ep32"
CELL = "longcatflash-serve-chat"
TRAFFIC = "sessions-128-chat"
#: the catalog row's ``config`` (guides/model-configs/architectures.jsonl,
#: LongCat-Flash-Chat), every number and string of it
CATALOG = dict(
    attention_bias=False, vocab_size=131072, hidden_size=6144,
    ffn_hidden_size=12288, expert_ffn_hidden_size=2048, num_layers=28,
    num_attention_heads=64, kv_lora_rank=512, q_lora_rank=1536,
    qk_rope_head_dim=64, v_head_dim=128, qk_nope_head_dim=128,
    mla_scale_q_lora=True, mla_scale_kv_lora=True, routed_scaling_factor=6,
    n_routed_experts=512, max_position_embeddings=131072,
    rms_norm_eps=1e-05, rope_theta=10000000, attention_method="MLA",
    zero_expert_num=256, zero_expert_type="identity", moe_topk=12)
WIDTH = re.compile("(_dim$|_rank$|hidden_size$|head_dim|topk)")


def traffic(name=TRAFFIC):
    with open(os.path.join(manifest.BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_the_manifest_takes_the_cell():
    cell = manifest.cell(MAN, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and "32x" in cell["why"]
    (entry,) = [c for c in MAN["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/meituan-longcat/"
                               "LongCat-Flash-Chat/blob/main/config.json")
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert [c["file"] for c in MAN["configs"]].count(entry["file"]) == 1
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) == 1


def test_the_configuration_keeps_every_published_width():
    """Depth, the experts held and the vocabulary slice are the cut; every
    other key is the catalog's, and the share has keys of its own."""
    config = manifest.config_of(MAN, CONFIG)
    reduced = config["reduced"]
    assert reduced == ["num_layers", "n_routed_experts", "vocab_size"]
    assert not any(WIDTH.search(key) for key in reduced)
    for key, value in CATALOG.items():
        if key in reduced:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value and \
                type(config[key]) is type(value), key
    assert set(config["published"]) == set(reduced)
    assert (config["num_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (4, 16, 16384)
    assert config["system"] == "lib.longcat_system"
    (entry,) = [c for c in MAN["configs"] if c["name"] == CONFIG]
    assert config["source"] == entry["source"]
    assert set(config["assumed"]) >= {"mla_scales", "rope", "head", "router",
                                      "weights"}
    tolerances = config["tolerances"]
    assert all(k.endswith("_why") or k + "_why" in tolerances
               for k in tolerances)


def test_reduced_published_and_deployment_agree_with_the_parameter_count():
    from deepspeed_tpu.models.longcat_flash import LongCatFlashLM

    config = manifest.config_of(MAN, CONFIG)
    ep, published = config["ep_size"], config["published"]
    assert (ep, config["ep_rank"]) == (32, 0)
    # floors: >= 4 layers, >= 8 routed experts, >= 1/8 vocabulary
    assert config["num_layers"] >= 4 and config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    assert config["n_routed_experts"] * ep == published["n_routed_experts"]
    stages = published["num_layers"] // config["num_layers"]
    assert stages * config["num_layers"] == published["num_layers"]
    text = config["deployment"]
    assert f"{stages} x {ep} = {stages * ep} chips" in text     # 7 x 32 = 224
    assert "more than their share" in text
    assert "No code stands in for the absent chips" in text
    model = LongCatFlashLM.from_hf_config(
        {k: v for k, v in config.items()
         if isinstance(v, (int, float, bool, str))})
    cfg = model.config
    D, F, Fe = cfg.hidden_size, cfg.ffn_hidden_size, cfg.expert_ffn_hidden_size
    mla = (D * cfg.q_lora_rank + cfg.q_lora_rank
           + cfg.q_lora_rank * cfg.num_heads * cfg.qk_head_dim
           + D * cfg.latent_dim + cfg.kv_lora_rank
           + cfg.kv_lora_rank * cfg.num_heads
           * (cfg.qk_nope_head_dim + cfg.v_head_dim)
           + cfg.num_heads * cfg.v_head_dim * D)
    assert round(mla / 1e6, 2) == 90.57 and "90.57M" in text
    assert round(3 * D * F / 1e6, 2) == 226.49 and "226.49M" in text
    assert round(3 * D * Fe / 1e6, 2) == 37.75 and "37.75M" in text
    outside = 2 * (mla + 3 * D * F + 2 * D) + D * 768 + 768
    assert round(outside / 1e6, 1) == 638.9 and "638.9M" in text
    total = 4 * (outside + 16 * 3 * D * Fe) + 2 * cfg.vocab_size * D + D
    assert model.num_params() == total
    assert round(total / 1e9, 2) == 5.17 and "5.17B" in text
    assert round(2 * total / 1e9, 2) == 10.35 and "10.35 GB" in text
    family = model.serving_family()
    assert family.page_layers == 8 and "8 latent page layers" in text
    # a cached token, as stored and as the roofline reader counts it
    assert family.page_layers * family.row.width * 2 == 10240
    assert "10,240 bytes" in config["serving_why"]
    assert flops_mla.latent_row_values(config) == 576
    # 64 heads: 121 FLOP a byte read, half of the v5e's ridge of 240
    intensity = flops_mla.mla_decode_flops(config, 1) \
        / flops_mla.mla_decode_bytes(config, 1)
    assert round(intensity) == 121


def test_every_seed_offers_the_same_work_in_another_order():
    job = traffic()
    assert job["kind"] == "sessions" and job["sessions"] == 128
    assert job["schedule_seed"] == 20260927
    assert job["document_tokens"] == {"dist": "loguniform", "min": 768,
                                      "max": 3072}
    assert job["question_tokens"] == {"dist": "uniform", "min": 16, "max": 64}
    assert job["answer_tokens"] == {"dist": "uniform", "min": 256,
                                    "max": 1024}
    docs = sessions.document_lengths(job)
    assert docs == sessions.document_lengths(job)      # schedule_seed alone
    assert len(docs) == 128 and all(768 <= d <= 3072 for d in docs)
    assert 1500 < sum(docs) / len(docs) < 1800          # mean ~1,660
    for index in (0, 1, 5):
        a = sessions.round_of(job, index, 1)
        b = sessions.round_of(job, index, BIG_SEED)
        assert a == sessions.round_of(job, index, 1)
        assert a != b and sorted(a) == sorted(b) and len(a) == 128
        assert all(16 <= q <= 64 and 256 <= ans <= 1024 for q, ans in a)


def test_the_cell_fits_its_configuration():
    job = traffic()
    serving = manifest.config_of(MAN, CONFIG)["serving"]
    assert serving == dict(max_seqs=128, max_tokens=512, max_ctx=4224,
                           block_size=64, prefix_cache=True,
                           kv_reserve_bytes=2 ** 31, max_queue=128)
    longest = job["document_tokens"]["max"] + job["question_tokens"]["max"] \
        + job["answer_tokens"]["max"]
    assert longest + serving["block_size"] == serving["max_ctx"]
    assert job["sessions"] == serving["max_seqs"] == serving["max_queue"]


def test_the_new_metrics_and_the_list_edits():
    per_layer = {m["name"]: m for m in MAN["per_layer"]}
    rate, tpot = "serve_tokens_per_s", "tpot_p50_ms"
    for metric, source, layer in (
            ("moe_identity_pair_share", "program_span", "serve engine"),
            ("phase_share.moe_route.decode", "device_trace", "model")):
        entry = per_layer[metric]
        assert CELL in entry["workloads"], metric
        assert (entry["moves"], entry["source"], entry["layer"]) == \
            (rate, source, layer), metric
        spec = manifest.metric_of(metric)
        manifest.load_module("readers", spec["reader"])
    spec = manifest.metric_of("moe_identity_pair_share")
    assert spec == {"reader": "program_span_stat", "args": {
        "span": "engine/window_account", "value": "moe_identity_pair_share",
        "stat": "mean"}}
    scope = re.compile(manifest.metric_of(
        "phase_share.moe_route.decode")["args"]["scope"])
    assert scope.search("layers/moe/route") and scope.search("moe/identity")
    assert not scope.search("layers/moe/experts")
    for metric in ("decode_batch_occupancy", "compiles_in_window.decode",
                   "kv_fill_peak.decode", "kv_fill_mean.decode",
                   "idle_share.decode", "hbm_peak_gib.decode",
                   "tpot_p95_ms.decode", "sched_own_share.decode",
                   "idle_in_drain.decode", "idle_unowned.decode",
                   "idle_in_dispatch.decode", "requests_unfinished.decode",
                   "kernel_share.mla_decode", "mla_decode_roofline",
                   "phase_share.moe.decode", "phase_share.mlp.decode",
                   "moe_load_max_share", "prefix_hit_token_share",
                   "prefill_time_share.decode", "compile_ms_in_window.decode",
                   "cache_entries_added", "tpu_client_s", "setup_trace_s",
                   "setup_programs_compiled"):
        assert per_layer[metric]["workloads"].count(CELL) == 1, metric
    judged = {m["name"] for m in manifest.metrics_for(MAN, CELL,
                                                      "end_to_end")}
    assert judged == {rate, tpot, "setup_s"}
    mine = manifest.metrics_for(MAN, CELL, "per_layer")
    assert all(m["moves"] in judged for m in mine)
    assert not {"phase_share.hc.decode", "paged_decode_roofline",
                "gdn_decode_roofline", "state_fill_mean.decode"} \
        & {m["name"] for m in mine}
    # a share of a roofline the cell reports is the shared latent kernel's
    assert [m["name"] for m in mine if "roofline" in m["name"]] == \
        ["mla_decode_roofline"]


def test_the_system_module_has_what_the_generator_asks_for():
    import importlib

    module = importlib.import_module("lib.longcat_system")
    assert all(callable(getattr(module, name)) for name in (
        "prepare", "build", "check_against_reference", "check_served"))
    source = open(sessions.__file__).read()
    assert "longcat" not in source.lower()
    n = module.CHECK_DOC + module.CHECK_QUESTION
    plan = module.check_plan(n, module.CHECK_DOC, 512)
    assert module.CHECK_DOC % 64 and module.CHECK_DOC % 512
    assert plan["chunk_ends"] == 5
    # the reference shares no code with the program
    path = os.path.join(manifest.BENCH, "reference", "longcat_flash.py")
    text = open(path).read()
    assert not re.search(r"^\s*(from|import)\s+(deepspeed_tpu|lib)\b", text,
                         re.M)
    assert 'default_matmul_precision("highest")' in text


def test_the_cpu_rehearsal_runs_to_a_correct_line():
    """The whole cell at toy widths on the CPU backend: both checks, the
    window, the metrics that need no device trace."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(manifest.BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", CELL, "--seed", str(BIG_SEED),
                       "--seconds", "3", "--trace", "0", "--cpu-rehearsal"])
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert {"serve_tokens_per_s", "tpot_p50_ms", "setup_s"} \
        <= set(line["metrics"])
    checks = line["checks"]
    assert set(checks["groups"]) == {"prefill", "mixed", "singles", "windows",
                                     "grafted"}
    assert checks["graft_ok"] and checks["served"]["ok"]
    assert checks["served"]["grafted_ok"] and checks["served"]["tokens"] > 0
    also = line["also"]
    assert also["requests_unfinished.decode"]["value"] == 0
    assert also["prefix_hit_token_share"]["value"] > 0.5
    assert 0.0 < also["moe_identity_pair_share"]["value"] < 1.0
    assert 0.0 < also["moe_load_max_share"]["value"] <= 1.0
