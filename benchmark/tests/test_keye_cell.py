"""What PR 46 added: the Keye-VL-2.0 configuration (chip 0 of an 8-stage x
8-chip deployment), its long-document sessions cell, the four metrics that
read the sparse-attention path, ``lib/flops_sparse``, and the cell's CPU
rehearsal."""
import contextlib
import io
import json
import os
import re

from lib import flops_sparse, manifest

sessions = manifest.load_module("generators", "sessions")
MAN = manifest.manifest()
BIG_SEED = 2 ** 31 + 54321
CONFIG = "keye-vl-2.0-30b-a3b-depth6-ep8"
CELL = "keyevl2-serve-longdoc"
TRAFFIC = "sessions-20x35k"
#: the catalog row's ``config`` (guides/model-configs/architectures.jsonl,
#: Keye-VL-2.0-30B-A3B), every number, string and group of it
CATALOG = dict(
    attention_bias=False, decoder_sparse_step=1, head_dim=128,
    hidden_act="silu", hidden_size=2048, intermediate_size=6144,
    max_position_embeddings=262144, max_window_layers=48, mlp_only_layers=[],
    model_type="KeyeVL2", moe_intermediate_size=768, norm_topk_prob=True,
    num_attention_heads=32, num_experts=128, num_experts_per_tok=8,
    num_hidden_layers=48, num_key_value_heads=4, num_local_experts=128,
    rms_norm_eps=1e-06,
    rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default",
                  "type": "default"},
    rope_theta=10000000,
    sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
               "q_chunk_size": 512, "topk": 2048},
    sliding_window=None, tie_word_embeddings=False, use_sliding_window=False,
    vocab_size=151936)
WIDTH = re.compile("(_dim$|_rank$|hidden_size$|intermediate_size$|head_dim"
                   "|topk|per_tok)")


def traffic(name=TRAFFIC):
    with open(os.path.join(manifest.BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_the_manifest_takes_the_cell():
    cell = manifest.cell(MAN, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and "1/8" in cell["why"]
    (entry,) = [c for c in MAN["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts"]
    assert entry["source"] == ("https://huggingface.co/Kwai-Keye/"
                               "Keye-VL-2.0-30B-A3B/blob/main/config.json")
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert [c["file"] for c in MAN["configs"]].count(entry["file"]) == 1
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) == 1
    assert MAN["workloads"][-1]["name"] == CELL     # appended, not inserted


def test_the_configuration_keeps_every_published_width():
    """Depth and the experts held are the cut; every other key is the
    catalog's (the nested groups whole), and the share has keys of its
    own."""
    config = manifest.config_of(MAN, CONFIG)
    reduced = config["reduced"]
    assert reduced == ["num_hidden_layers", "num_experts"]
    assert not any(WIDTH.search(key) for key in reduced)
    for key, value in CATALOG.items():
        if key in reduced:
            assert config["published"][key] == value
            assert config[key] < value
        else:
            assert config[key] == value, key
    assert set(config["published"]) == set(reduced)
    assert (config["ep_size"], config["ep_rank"]) == (8, 0)
    assert config["num_experts"] * config["ep_size"] == 128
    # the flat copies that run.py's `sizes` carries to lib/flops_sparse
    sa = config["sa_config"]
    assert (config["indexer_head_dim"], config["indexer_num_heads"],
            config["indexer_topk"]) == (sa["indexer_head_dim"],
                                        sa["indexer_num_heads"], sa["topk"])
    assumed = config["assumed"]
    assert set(assumed) >= {"qk_norm", "indexer_input", "indexer_rope",
                            "score", "ties", "chunks", "index_cache", "rope",
                            "head", "weights"}
    for key in ("qk_norm", "indexer_rope", "chunks"):
        assert "Alternative" in assumed[key], key
    assert "LOWER position" in assumed["ties"]
    assert "vision tower" in config["not_built"].lower()
    assert config["system"] == "lib.keye_system"


def test_reduced_published_and_deployment_agree_with_the_parameter_count():
    from deepspeed_tpu.models.keye_vl import KeyeVLLM
    from lib.keye_system import published

    config = manifest.config_of(MAN, CONFIG)
    model = KeyeVLLM.from_hf_config(published(config, False))
    cfg = model.config
    assert (cfg.num_layers, cfg.experts_held, cfg.num_experts,
            cfg.expert_offset) == (6, 16, 128, 0)
    assert cfg.mrope_section == (16, 24, 24) and cfg.topk == 2048
    n = model.num_params()
    D, V = 2048, 151936
    attention = 2 * D * 4096 + 2 * D * 512
    indexer = D * 1024 + D * 64 + D * 16
    router, expert = D * 128, 3 * D * 768
    norms = 6 * (2 * D + 2 * 128) + D
    by_hand = 6 * (attention + indexer + router + 16 * expert) + 2 * V * D \
        + norms
    assert n == by_hand
    assert round(n / 1e9, 3) == 1.204 and "1.204B" in config["deployment"]
    assert "2.41 GB" in config["deployment"]
    # the whole model: 48 layers of 128 experts
    whole = 48 * (attention + indexer + router + 128 * expert) + 2 * V * D
    assert round(whole / 1e9, 1) == 30.6 and "30.6B" in config["deployment"]
    for said in ("8 x 8 = 64 chips", "eight pipeline stages of 6 layers",
                 "20 x 8 / 128 = 1.25", "an eighth of its share"):
        assert said in config["deployment"], said
    family = model.serving_family()
    assert family.page_layers == 6 and family.row.index.topk == 2048
    # a cached token: 6 x (2,048 + 128) bytes
    token = 6 * 2 * (8 * 128 + family.row.index.dim)
    assert token == 13056 and "13,056" in config["serving_why"]


def test_flops_sparse_against_a_count_by_hand():
    config = manifest.config_of(MAN, CONFIG)
    assert flops_sparse.index_row_bytes(config) == 128
    assert flops_sparse.kv_row_bytes(config) == 2048
    # below topk: every row is read; above it: 2,048 of them
    assert flops_sparse.sparse_query_bytes(config, 1000) == \
        1000 * 128 + 1000 * 2048
    assert flops_sparse.sparse_query_bytes(config, 35500) == \
        35500 * 128 + 2048 * 2048
    # 20 queries at 35.5k in 6 layers: ~1.05 GB where the dense walk of the
    # same pages would move 8.7 GB
    step = 20 * 6 * flops_sparse.sparse_query_bytes(config, 35500)
    assert 1.0e9 < step < 1.1e9
    assert flops_sparse.sparse_bytes(config, 20 * 6 * 35500,
                                     20 * 6 * 2048) == step
    assert flops_sparse.index_flops(config, 1000) == 1000 * 16 * 130
    assert flops_sparse.core_flops(config, 1000) == 1000 * 32 * 512
    assert flops_sparse.core_flops(config, 35500) == 2048 * 32 * 512


def test_every_seed_offers_the_same_work_in_another_order():
    job = traffic()
    assert job["kind"] == "sessions" and job["sessions"] == 20
    assert job["schedule_seed"] == 20260930
    assert job["document_tokens"] == {"dist": "loguniform", "min": 16384,
                                      "max": 65536}
    assert job["question_tokens"] == {"dist": "uniform", "min": 16, "max": 64}
    assert job["answer_tokens"] == {"dist": "uniform", "min": 256,
                                    "max": 1024}
    docs = sessions.document_lengths(job)
    assert docs == sessions.document_lengths(job)      # schedule_seed alone
    assert len(docs) == 20 and all(16384 <= d <= 65536 for d in docs)
    assert min(docs) > 4 * 2048                         # every query selects
    for index in (0, 1, 5):
        a = sessions.round_of(job, index, 1)
        b = sessions.round_of(job, index, BIG_SEED)
        assert a == sessions.round_of(job, index, 1)
        assert a != b and sorted(a) == sorted(b) and len(a) == 20
        assert all(16 <= q <= 64 and 256 <= ans <= 1024 for q, ans in a)


def test_the_cell_fits_its_configuration():
    job = traffic()
    serving = manifest.config_of(MAN, CONFIG)["serving"]
    assert {k: serving[k] for k in ("max_seqs", "max_tokens", "max_ctx",
                                    "block_size", "prefix_cache",
                                    "max_queue")} == dict(
        max_seqs=20, max_tokens=512, max_ctx=66688, block_size=64,
        prefix_cache=True, max_queue=64)
    longest = job["document_tokens"]["max"] + job["question_tokens"]["max"] \
        + job["answer_tokens"]["max"]
    assert longest + serving["block_size"] == serving["max_ctx"]
    assert job["sessions"] == serving["max_seqs"]
    # the documents once, and every session's longest turn, in the pool a
    # v5e leaves (16 GB less weights and reserve, 13,056 B a token)
    docs = sessions.document_lengths(job)
    held = sum(docs) + 20 * (64 + 1024 + 64)
    pool = (15.75 * 2 ** 30 - 2.41e9 - serving["kv_reserve_bytes"]) // 13056
    assert held < 0.92 * pool


def test_the_new_metrics_and_the_list_edits():
    per_layer = {m["name"]: m for m in MAN["per_layer"]}
    rate, tpot = "serve_tokens_per_s", "tpot_p50_ms"
    new = (("phase_share.sparse_index.decode", "device_trace", "model", rate),
           ("phase_share.sparse_attend.decode", "device_trace", "model",
            rate),
           ("sparse_select_share", "program_span", "serve engine", rate),
           ("sparse_decode_roofline", "device_trace", "kernels, serve", tpot))
    assert [m["name"] for m in MAN["per_layer"][-4:]] == [n[0] for n in new]
    for metric, source, layer, moves in new:
        entry = per_layer[metric]
        assert entry["workloads"] == [CELL], metric
        assert (entry["moves"], entry["source"], entry["layer"]) == \
            (moves, source, layer), metric
        spec = manifest.metric_of(metric)
        manifest.load_module("readers", spec["reader"])
    assert per_layer["sparse_decode_roofline"]["unit"] == "%"
    assert manifest.metric_of("sparse_select_share") == {
        "reader": "program_span_stat", "args": {
            "span": "engine/window_account", "value": "sparse_select_share",
            "stat": "mean"}}
    index = re.compile(manifest.metric_of(
        "phase_share.sparse_index.decode")["args"]["scope"])
    attend = re.compile(manifest.metric_of(
        "phase_share.sparse_attend.decode")["args"]["scope"])
    core = "layers/attention/core/attention/"
    for scope in ("index_qk", "index_score", "index_select"):
        assert index.search(core + scope) and not attend.search(core + scope)
    assert index.search("layers/attention/index_qk")
    for scope in ("sparse_read", "sparse_core"):
        assert attend.search(core + scope) and not index.search(core + scope)
    assert not index.search("layers/attention/qkv")
    assert not attend.search("layers/attention/out")
    from readers import sparse_decode_roofline as reader

    for scope in ("index_qk", "index_score", "index_select", "sparse_read",
                  "sparse_core"):
        assert reader.SCOPES.search(core + scope)
    assert not reader.SCOPES.search("layers/moe/experts")
    # a program without the counters (the parent): no value, no error
    assert reader.read({"trace": None, "peaks": None, "sizes": {}}, {}) is None
    for metric in ("decode_batch_occupancy", "compiles_in_window.decode",
                   "kv_fill_peak.decode", "kv_fill_mean.decode",
                   "idle_share.decode", "hbm_peak_gib.decode",
                   "tpot_p95_ms.decode", "sched_own_share.decode",
                   "idle_in_drain.decode", "idle_unowned.decode",
                   "idle_in_dispatch.decode", "requests_unfinished.decode",
                   "phase_share.moe.decode", "moe_load_max_share",
                   "prefix_hit_token_share", "prefill_time_share.decode",
                   "compile_ms_in_window.decode", "cache_entries_added",
                   "tpu_client_s", "setup_trace_s", "setup_lower_s",
                   "setup_compile_s", "setup_cache_load_s",
                   "setup_programs_compiled", "setup_engine_init_s"):
        assert per_layer[metric]["workloads"][-1] == CELL, metric
        assert per_layer[metric]["workloads"].count(CELL) == 1, metric
    judged = {m["name"] for m in manifest.metrics_for(MAN, CELL,
                                                      "end_to_end")}
    assert judged == {rate, tpot, "setup_s"}
    mine = manifest.metrics_for(MAN, CELL, "per_layer")
    assert all(m["moves"] in judged for m in mine)
    # the window's programs hold no dense K/V walk at these contexts: the
    # kernels' own metrics are not the cell's
    assert not {"paged_decode_roofline", "kernel_share.paged_decode.decode",
                "mla_decode_roofline", "gdn_decode_roofline",
                "phase_share.hc.decode", "state_fill_mean.decode"} \
        & {m["name"] for m in mine}
    assert [m["name"] for m in mine if "roofline" in m["name"]] == \
        ["sparse_decode_roofline"]


def test_the_system_module_has_what_the_generator_asks_for():
    import importlib

    module = importlib.import_module("lib.keye_system")
    assert all(callable(getattr(module, name)) for name in (
        "prepare", "build", "check_against_reference", "check_served"))
    source = open(sessions.__file__).read()
    assert "keye" not in source.lower()
    n = module.CHECK_DOC + module.CHECK_QUESTION
    plan = module.check_plan(n, module.CHECK_DOC, 512)
    assert module.CHECK_DOC % 64 and module.CHECK_DOC % 512
    assert module.CHECK_DOC > 2048 > module.CHECK_SHORT
    assert plan["chunk_ends"] == 10
    # the reference shares no code with the program
    path = os.path.join(manifest.BENCH, "reference", "keye_vl.py")
    text = open(path).read()
    assert not re.search(r"^\s*(from|import)\s+(deepspeed_tpu|lib)\b", text,
                         re.M)
    assert 'default_matmul_precision("highest")' in text
    assert "stable=True" in text and "approx" not in text
    # the program's selection is exact too
    ops = open(os.path.join(manifest.ROOT, "deepspeed_tpu", "inference", "v2",
                            "kernels", "sparse_ops.py")).read()
    assert "approx_max_k" not in ops and "approx_min_k" not in ops


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
    assert set(checks["groups"]) == {"prefill", "short", "mixed", "singles",
                                     "windows", "grafted"}
    assert checks["graft_ok"] and checks["served"]["ok"]
    assert checks["served"]["grafted_ok"] and checks["served"]["tokens"] > 0
    assert checks["index_select_overlap"] > 0.99
    also = line["also"]
    assert also["requests_unfinished.decode"]["value"] == 0
    assert also["prefix_hit_token_share"]["value"] > 0.5
    assert 0.0 < also["moe_load_max_share"]["value"] <= 1.0
    # toy: topk 16 of contexts of 60-130
    assert 0.1 < also["sparse_select_share"]["value"] < 0.4
