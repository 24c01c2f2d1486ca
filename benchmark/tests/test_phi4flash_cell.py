"""What PR 55 added: the Phi-4-mini-flash-reasoning configuration (the whole
model on one chip, nothing cut), its long-answer reasoning cell, the metrics
that read its scan, memory units, window and shared reads, and the cell's
CPU rehearsal.  Manifest MEMBERSHIP, not position."""
import contextlib
import io
import json
import os
import re

import numpy as np

from lib import flops_phi4flash, manifest

sessions = manifest.load_module("generators", "sessions")
MAN = manifest.manifest()
BIG_SEED = 2 ** 31 + 12345
CONFIG = "phi-4-mini-flash-reasoning"
CELL = "phi4flash-serve-reasoning"
TRAFFIC = "sessions-64-reasoning"
#: the catalog row's ``config`` (guides/model-configs/architectures.jsonl,
#: Phi-4-mini-flash-reasoning), every number and string of it
CATALOG = dict(
    embd_pdrop=0, hidden_act="silu", hidden_size=2560,
    intermediate_size=10240, layer_norm_eps=1e-05,
    max_position_embeddings=262144, mb_per_layer=2, model_type="phi4flash",
    num_attention_heads=40, num_hidden_layers=32, num_key_value_heads=20,
    resid_pdrop=0, sliding_window=512, tie_word_embeddings=True,
    mlp_bias=False, lm_head_bias=False, vocab_size=200064)
NEW = ("phase_share.ssm.decode", "phase_share.gmu.decode",
       "phase_share.attn_window.decode", "phase_share.attn_shared.decode",
       "window_rows_held_mean.decode", "diff_decode_roofline")


def traffic(name=TRAFFIC):
    with open(os.path.join(manifest.BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_the_manifest_takes_the_cell():
    cell = manifest.cell(MAN, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and "nothing cut" in cell["why"]
    (entry,) = [c for c in MAN["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == []
    assert entry["source"] == ("https://huggingface.co/microsoft/"
                               "Phi-4-mini-flash-reasoning/blob/main/"
                               "config.json")
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert [c["file"] for c in MAN["configs"]].count(entry["file"]) == 1
    assert [w["config"] for w in MAN["workloads"]].count(CONFIG) == 1
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) == 1


def test_the_configuration_is_the_published_one_whole():
    config = manifest.config_of(MAN, CONFIG)
    assert config["reduced"] == [] and config["published"] == CATALOG
    for key, value in CATALOG.items():
        assert config[key] == value and type(config[key]) is type(value), key
    assert config["system"] == "lib.phi4flash_system"
    assert set(config["assumed"]) >= {
        "scan_widths", "biases", "layout", "gmu", "head_pairing", "lambda",
        "positions", "state_dtype", "stored_rows", "window_ring", "weights"}
    tolerances = config["tolerances"]
    assert all(k.endswith("_why") or k + "_why" in tolerances
               or k == "served_turn_within_share" for k in tolerances)


def test_the_deployment_agrees_with_the_parameter_count():
    from deepspeed_tpu.models.phi4_flash import Phi4FlashLM

    config = manifest.config_of(MAN, CONFIG)
    model = Phi4FlashLM.from_hf_config(
        {k: v for k, v in config.items()
         if isinstance(v, (int, float, bool, str))})
    D, F, Ci, R, N = 2560, 10240, 5120, 160, 16
    norm = 2 * D
    mlp = D * 2 * F + F * D + norm
    scan = D * 2 * Ci + 4 * Ci + Ci + Ci * (R + 2 * N) + R * Ci + Ci \
        + Ci * N + Ci + Ci * D + norm
    attn = D * (D + 2 * 1280) + (D + 2 * 1280) + 4 * 64 + 128 + D * D + D \
        + norm
    cross = D * D + D + 4 * 64 + 128 + D * D + D + norm
    gmu = D * Ci + Ci * D + norm
    total = 200064 * D + 32 * mlp + 9 * scan + 9 * attn + 7 * cross \
        + 7 * gmu + norm
    assert model.num_params() == total
    assert round(total / 1e9, 2) == 3.85 and "3.85B" in config["deployment"]
    assert round(2 * total / 1e9, 1) == 7.7 and "7.7 GB" in config["deployment"]
    assert round(mlp / 1e6, 1) == 78.6 and round(scan / 1e6, 1) == 41.2
    assert round(attn / 1e6, 1) == 19.7 and round(cross / 1e6, 1) == 13.1
    family = model.serving_family()
    assert family.page_layers == 1 and family.page_readers == (8,)
    assert (family.state.num_layers, family.window.num_layers) == (9, 8)
    # a cached token as the roofline counts it and as the pool stores it
    assert flops_phi4flash.row_bytes(config) == 5120
    assert int(np.prod(family.row.token_shape)) * 2 == 8192
    assert "5,120 B" in config["assumed"]["stored_rows"] \
        and "8,192 B" in config["assumed"]["stored_rows"]


def test_flops_and_bytes_against_hand_counts():
    config = manifest.config_of(MAN, CONFIG)
    assert flops_phi4flash.layer_counts(config) == {
        "scan": 9, "window": 8, "full": 1, "memory": 7, "cross": 7}
    assert flops_phi4flash.layer_counts(dict(num_hidden_layers=8)) == {
        "scan": 3, "window": 2, "full": 1, "memory": 1, "cross": 1}
    # a row: 10 K rows + 10 V rows of 128 values of 2 bytes
    assert flops_phi4flash.diff_decode_bytes(config, 1) == 10 * 2 * 128 * 2
    # 20 pairs of query heads, each 2 scores of 64 and 2 sums of 128
    assert flops_phi4flash.diff_decode_flops(config, 1) \
        == 20 * 2 * (2 * 64 + 2 * 128)
    # 3 FLOP a byte: far under the v5e's ridge of 240, the bytes bound it
    assert flops_phi4flash.diff_decode_flops(config, 7) \
        / flops_phi4flash.diff_decode_bytes(config, 7) == 3.0
    # a window layer's call at 64 sequences of 1,900 tokens reads 512 each
    assert flops_phi4flash.diff_decode_bytes(config, 64 * 512) == 167772160
    assert flops_phi4flash.scan_state_values(config) == 5120 * 16
    assert flops_phi4flash.ssm_decode_bytes(config, 64) \
        == 64 * 2 * 5120 * 16 * 4
    assert flops_phi4flash.ssm_decode_flops(config, 1) == 7 * 5120 * 16


def test_the_reference_scan_against_a_numpy_loop():
    """``reference/phi4_flash.selective_scan`` (jax, ``lax.scan``) against
    the recurrence written out token by token in numpy, float64."""
    import jax.numpy as jnp

    from reference import phi4_flash as ref

    rng = np.random.default_rng(0)
    S, D, Ci, N, R, K = 19, 12, 24, 4, 3, 4
    w = {"w_in": rng.normal(size=(D, 2 * Ci)) / D ** 0.5,
         "conv": rng.normal(size=(K, Ci)) / 2, "conv_b": rng.normal(size=Ci),
         "w_x": rng.normal(size=(Ci, R + 2 * N)) / Ci ** 0.5,
         "w_dt": rng.normal(size=(R, Ci)), "b_dt": rng.normal(size=Ci),
         "A_log": rng.normal(size=(Ci, N)), "D": rng.normal(size=Ci),
         "w_out": rng.normal(size=(Ci, D)) / Ci ** 0.5}
    h = rng.normal(size=(S, D))
    out, y, gated = ref.selective_scan(jnp.asarray(h, jnp.float32), w)

    silu = lambda x: x / (1 + np.exp(-x))  # noqa: E731
    uz = h @ w["w_in"]
    u, z = uz[:, :Ci], uz[:, Ci:]
    state, ys = np.zeros((Ci, N)), []
    for t in range(S):
        c = sum(w["conv"][j] * u[t - (K - 1) + j] for j in range(K)
                if t - (K - 1) + j >= 0)
        c = silu(c + w["conv_b"])
        rbc = c @ w["w_x"]
        delta = np.log1p(np.exp(rbc[:R] @ w["w_dt"] + w["b_dt"]))
        B, C = rbc[R:R + N], rbc[R + N:]
        state = np.exp(delta[:, None] * -np.exp(w["A_log"])) * state \
            + (delta * c)[:, None] * B[None, :]
        ys.append(state @ C + w["D"] * c)
    ys = np.stack(ys)
    np.testing.assert_allclose(np.asarray(y), ys, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(out), (ys * silu(z)) @ w["w_out"],
                               rtol=2e-4, atol=2e-4)
    # the roles a depth of 32 gives, and of the tests' 8
    c32 = dict(num_hidden_layers=32)
    assert [ref.role_of(l, c32) for l in (0, 1, 15, 16, 17, 18, 19, 31)] == [
        "scan", "window", "window", "scan_hands", "full", "memory", "cross",
        "cross"]
    assert [ref.role_of(l, dict(num_hidden_layers=8)) for l in range(8)] == [
        "scan", "window", "scan", "window", "scan_hands", "full", "memory",
        "cross"]


def test_every_seed_offers_the_same_work_in_another_order():
    job = traffic()
    assert job["kind"] == "sessions" and job["sessions"] == 64
    assert job["document_tokens"] == {"dist": "loguniform", "min": 128,
                                      "max": 2048}
    assert job["question_tokens"] == {"dist": "uniform", "min": 16, "max": 64}
    assert job["answer_tokens"] == {"dist": "uniform", "min": 1024,
                                    "max": 2560}
    docs = sessions.document_lengths(job)
    assert docs == sessions.document_lengths(job)      # schedule_seed alone
    assert len(docs) == 64 and all(128 <= d <= 2048 for d in docs)
    for index in (0, 1, 5):
        a = sessions.round_of(job, index, 1)
        b = sessions.round_of(job, index, BIG_SEED)
        assert a == sessions.round_of(job, index, 1)
        assert a != b and sorted(a) == sorted(b) and len(a) == 64
        assert all(16 <= q <= 64 and 1024 <= ans <= 2560 for q, ans in a)


def test_the_cell_fits_its_configuration():
    job = traffic()
    serving = manifest.config_of(MAN, CONFIG)["serving"]
    assert serving == dict(max_seqs=64, max_tokens=512, max_ctx=4800,
                           block_size=64, prefix_cache=False,
                           kv_reserve_bytes=2 ** 31, max_queue=64)
    longest = job["document_tokens"]["max"] + job["question_tokens"]["max"] \
        + job["answer_tokens"]["max"]
    assert longest + 2 * serving["block_size"] == serving["max_ctx"]
    assert job["sessions"] == serving["max_seqs"] == serving["max_queue"]


def test_the_new_metrics_and_the_list_edits():
    per_layer = {m["name"]: m for m in MAN["per_layer"]}
    rate, tpot = "serve_tokens_per_s", "tpot_p50_ms"
    for metric in NEW:
        entry = per_layer[metric]
        assert entry["workloads"] == [CELL], metric
        spec = manifest.metric_of(metric)
        manifest.load_module("readers", spec["reader"])
    for metric, scope, hit, miss in (
            ("phase_share.ssm.decode", "(^|/)attention/ssm_",
             "layers/attention/ssm_scan", "layers/attention/shared"),
            ("phase_share.gmu.decode", "(^|/)attention/gmu",
             "layers/attention/gmu", "layers/mlp"),
            ("phase_share.attn_window.decode", "(^|/)attention/window",
             "layers/attention/window", "layers/attention/shared"),
            ("phase_share.attn_shared.decode", "(^|/)attention/shared",
             "layers/attention/shared", "layers/attention/window")):
        assert manifest.metric_of(metric) == {
            "reader": "serve_scope_share", "args": {"scope": scope}}
        assert re.search(scope, hit) and not re.search(scope, miss)
        assert (per_layer[metric]["moves"], per_layer[metric]["layer"]) == \
            (rate, "model")
    assert manifest.metric_of("window_rows_held_mean.decode") == {
        "reader": "program_span_stat", "args": {
            "span": "engine/window_account", "value": "window_rows_held",
            "stat": "mean"}}
    assert per_layer["diff_decode_roofline"]["moves"] == tpot
    assert per_layer["diff_decode_roofline"]["layer"] == "kernels, serve"
    for metric in ("decode_batch_occupancy", "compiles_in_window.decode",
                   "kv_fill_peak.decode", "kv_fill_mean.decode",
                   "idle_share.decode", "hbm_peak_gib.decode",
                   "tpot_p95_ms.decode", "sched_own_share.decode",
                   "idle_in_drain.decode", "idle_unowned.decode",
                   "idle_in_dispatch.decode", "requests_unfinished.decode",
                   "window_ahead_share.decode", "prefill_time_share.decode",
                   "phase_share.mlp.decode", "state_fill_mean.decode",
                   "compile_ms_in_window.decode", "cache_entries_added",
                   "tpu_client_s", "setup_trace_s", "setup_lower_s",
                   "setup_compile_s", "setup_cache_load_s",
                   "setup_programs_compiled", "setup_engine_init_s"):
        assert per_layer[metric]["workloads"].count(CELL) == 1, metric
    judged = {m["name"] for m in manifest.metrics_for(MAN, CELL,
                                                      "end_to_end")}
    assert judged == {rate, tpot, "setup_s"}
    mine = manifest.metrics_for(MAN, CELL, "per_layer")
    assert all(m["moves"] in judged for m in mine)
    # its reader counts a uniform full-context K/V walk: not this cell's
    assert not {"paged_decode_roofline", "kernel_share.paged_decode.decode",
                "gdn_decode_roofline"} & {m["name"] for m in mine}
    assert [m["name"] for m in mine if "roofline" in m["name"]] == \
        ["diff_decode_roofline"]


def test_the_roofline_reads_the_programs_counters():
    """One traced window of 64 riders at a context of 1,900: 8 window
    layers read 512 rows a rider a step, 8 readers the whole context."""
    import types

    reader = manifest.load_module("readers", "diff_decode_roofline")
    config = manifest.config_of(MAN, CONFIG)
    steps, riders, ctx = 8, 64, 1900
    window_rows = riders * steps * 512
    page_rows = riders * sum(ctx + t for t in range(1, steps + 1))
    seconds = 0.1
    spans = types.SimpleNamespace(records=[])
    run = {"trace": {"device": {"d0": [
        ("_decode_paged_kernel", 1.0e9 + i, seconds / 128 * 1e9, "", "", 1.0)
        for i in range(128)]}, "window": (0.0, 3.0)},
        "peaks": types.SimpleNamespace(hbm_bytes_per_s=819e9,
                                       bf16_flops=197e12),
        "sizes": {k: v for k, v in config.items()
                  if isinstance(v, (int, float, bool))},
        "slice": (0.0, 10.0), "spans": spans, "window": (0.0, 10.0)}
    from lib import program_trace

    ring = [("engine/window_account", 1.0, 0.001,
             {"window_rows_read": window_rows, "page_rows_read": page_rows})]
    real = program_trace.ring, reader.trace.kernel_seconds
    program_trace.ring = lambda run: ring
    reader.trace.kernel_seconds = lambda trace, pattern: {
        "seconds": seconds, "calls": 128.0}
    try:
        share = reader.read(run, {"pattern": "_decode_paged_kernel"})
        least = 8 * (window_rows + page_rows) * 5120 / 819e9
        assert abs(share - 100 * least / seconds) < 1e-9
        assert 0 < share < 100
        ring[0][3].pop("page_rows_read")
        assert reader.read(run, {"pattern": "x"}) is None   # the parent
    finally:
        program_trace.ring, reader.trace.kernel_seconds = real


def test_the_system_module_has_what_the_generator_asks_for():
    import importlib

    module = importlib.import_module("lib.phi4flash_system")
    assert all(callable(getattr(module, name)) for name in (
        "prepare", "build", "check_against_reference", "check_served"))
    source = open(sessions.__file__).read()
    assert "phi4" not in source.lower()
    path = os.path.join(manifest.BENCH, "reference", "phi4_flash.py")
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
    assert set(checks["groups"]) == {
        "prefill", "mixed", "singles", "windows", "reused_slot", "boundary",
        "boundary_windows"}
    assert checks["slot_reused"] and checks["served"]["ok"]
    also = line["also"]
    assert also["requests_unfinished.decode"]["value"] == 0
    # (read off the windows DRAINED inside the 3 s: a loaded machine may
    # drain none)
    if "state_fill_mean.decode" in also:
        assert also["state_fill_mean.decode"]["value"] == 1.0
        assert also["window_rows_held_mean.decode"]["value"] <= 16
