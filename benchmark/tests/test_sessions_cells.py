"""What PR 28 added: the ``sessions`` traffic kind, the six-cell manifest,
and the latent-attention roofline counts."""
import json
import os

import pytest

from lib import flops_mla, manifest

sessions = manifest.load_module("generators", "sessions")
MAN = manifest.manifest()
BIG_SEED = 2 ** 31 + 12345
LONG, XING = "mistral7b-serve-decode-longctx", "xing4-29b-serve-sessions"


def traffic(name):
    with open(os.path.join(manifest.BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["sessions-28x3k", "sessions-64x13k"])
def test_every_seed_offers_the_same_multiset(name):
    job = traffic(name)
    docs = sessions.document_lengths(job)
    assert docs == sessions.document_lengths(job)      # schedule_seed alone
    assert len(docs) == job["sessions"]
    lo, hi = job["document_tokens"]["min"], job["document_tokens"]["max"]
    assert all(lo <= d <= hi for d in docs)
    for index in (0, 1, 5):
        a = sessions.round_of(job, index, 1)
        b = sessions.round_of(job, index, BIG_SEED)
        assert a == sessions.round_of(job, index, 1)
        assert a != b and sorted(a) == sorted(b)
        assert len(a) == job["sessions"]
        q, ans = job["question_tokens"], job["answer_tokens"]
        assert all(q["min"] <= x <= q["max"] and ans["min"] <= y <= ans["max"]
                   for x, y in a)


def test_the_cells_fit_their_configurations():
    """Longest document + question + answer inside ``max_ctx``, and the
    reservations inside what ISSUE 28 reckoned of the pool."""
    for cell, pool_tokens in ((LONG, 110_700), (XING, 1_030_000)):
        w = manifest.cell(MAN, cell)
        job = traffic(w["traffic"])
        serving = manifest.config_of(MAN, w["config"])["serving"]
        longest = job["document_tokens"]["max"] \
            + job["question_tokens"]["max"] + job["answer_tokens"]["max"]
        assert longest <= serving["max_ctx"]
        assert job["sessions"] <= serving["max_seqs"]
        docs = sum(sessions.document_lengths(job))
        turns = job["sessions"] * (
            job["question_tokens"]["max"] + job["answer_tokens"]["max"]
            + serving["block_size"])
        assert 0.7 * pool_tokens <= docs + turns <= 0.95 * pool_tokens


def test_manifest_has_six_cells_and_the_new_metrics():
    names = [w["name"] for w in MAN["workloads"]]
    assert len(names) == 6 and names[-2:] == [LONG, XING]
    assert all(manifest.cell(MAN, n)["chips"] == 1 for n in (LONG, XING))
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    assert e2e["serve_tokens_per_s"]["workloads"] == [
        "mistral7b-serve-decode", LONG, XING]
    per_layer = {m["name"]: m for m in MAN["per_layer"]}
    # ISSUE 28, section 6: metric -> (moves, cells)
    rate, tpot = "serve_tokens_per_s", "tpot_p50_ms"
    for metric, moves, cells in (
            ("kernel_share.mla_decode", tpot, [XING]),
            ("mla_decode_roofline", tpot, [XING]),
            ("phase_share.moe.decode", rate, [XING]),
            ("phase_share.hc.decode", rate, [XING]),
            ("moe_load_max_share", rate, [XING]),
            ("prefix_hit_token_share", rate, [XING]),
            ("prefill_time_share.decode", rate, [LONG, XING])):
        assert per_layer[metric]["workloads"] == cells, metric
        assert per_layer[metric]["moves"] == moves, metric
    # the decode cells' own metrics are read in both new cells; the K/V page
    # walk's in the Mistral one alone; no twin under another name
    for metric in ("kv_fill_peak.decode", "kv_fill_mean.decode",
                   "idle_share.decode", "idle_unowned.decode",
                   "idle_in_drain.decode", "idle_in_dispatch.decode",
                   "hbm_peak_gib.decode", "compiles_in_window.decode",
                   "sched_own_share.decode", "requests_unfinished.decode",
                   "tpot_p95_ms.decode", "decode_batch_occupancy"):
        assert per_layer[metric]["workloads"][-2:] == [LONG, XING], metric
    for metric in ("paged_decode_roofline",
                   "kernel_share.paged_decode.decode"):
        assert per_layer[metric]["workloads"][-1] == LONG
    assert not [n for n in per_layer if n.endswith((".sessions",
                                                    ".observed"))]
    # every per-layer metric of a cell moves an end-to-end metric it reports
    for cell in (LONG, XING):
        judged = {m["name"] for m in manifest.metrics_for(MAN, cell,
                                                          "end_to_end")}
        for m in manifest.metrics_for(MAN, cell, "per_layer"):
            assert m["moves"] in judged, (cell, m["name"])
    config = manifest.config_of(MAN, "xing4.0-29b-a4b-depth5")
    assert config["n_routed_experts"] == 64 and config["vocab_size"] == 131072
    assert set(config["assumed"]) >= {"hc_embedding", "hc_readout",
                                      "rope_pairs", "sinkhorn_eps",
                                      "hc_flat_norm"}


def test_tpot_is_judged_in_the_three_decode_cells_only():
    """What ``test_manifest.py::test_tpot_is_judged_in_the_decode_cell_only``
    guarded before the two session cells joined the list (that test names
    the one decode cell and fails since; a file that exists is not this
    PR's to edit): the median gap between tokens is judged where decode is
    the cell's work, and never in the prefill cell (several modes there,
    ledger PR 22) or in a training cell."""
    by_name = {m["name"]: m for m in MAN["end_to_end"]}
    assert by_name["tpot_p50_ms"]["workloads"] == [
        "mistral7b-serve-decode", LONG, XING]
    for cell in by_name["tpot_p50_ms"]["workloads"]:
        traffic_kind = traffic(manifest.cell(MAN, cell)["traffic"])["kind"]
        assert traffic_kind in ("closed_loop", "sessions"), cell
    per_layer = {m["name"] for m in MAN["per_layer"]}
    assert "tpot_p50_ms.prefill" in per_layer


def test_the_generator_knows_no_model():
    """The configuration names the module that builds its served system;
    the shared generator imports it by that name and tests no family."""
    config = manifest.config_of(MAN, "xing4.0-29b-a4b-depth5")
    assert config["system"] == "lib.xing4_system"
    assert "system" not in manifest.config_of(MAN, "mistral-7b-v0.1-depth16")
    source = open(sessions.__file__).read()
    assert "xing" not in source.lower() and "model_type" not in source
    import importlib

    module = importlib.import_module(config["system"])
    assert all(callable(getattr(module, name)) for name in (
        "build", "check_against_reference", "check_served"))


def test_the_balanced_bias_evens_the_loads_and_decides_the_selection():
    """Experts of unlike popularity (router columns scaled 0.6-1.4): without
    a bias the fullest takes many times the emptiest's pairs; with the
    balanced one every expert is within a few percent of even on the
    calibration batch and within sampling on a fresh one, and most tokens
    take another set of experts than without it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lib import xing4_system

    rng = np.random.default_rng(0)
    T, E, k = 4096, 64, 4
    scale = rng.uniform(*xing4_system.POPULARITY, size=E)

    def scores():
        return jnp.asarray(1 / (1 + np.exp(-rng.normal(size=(T, E)) * scale)),
                           jnp.float32)

    def loads(s, b):
        idx = np.asarray(jax.lax.top_k(s + b, k)[1])
        return np.bincount(idx.ravel(), minlength=E) / (T * k / E), idx

    calib, fresh = scores(), scores()
    bias = jax.jit(lambda s, b: xing4_system.balance_bias(s, b, k))(
        calib, jnp.zeros((E,)))
    assert abs(float(jnp.mean(bias))) < 1e-6
    unbiased, picked_without = loads(fresh, 0.0)
    assert unbiased.max() > 5 * unbiased.min()
    on_calib, _ = loads(calib, bias)
    assert 0.97 < on_calib.min() and on_calib.max() < 1.03
    on_fresh, picked_with = loads(fresh, bias)
    assert 0.75 < on_fresh.min() and on_fresh.max() < 1.3
    same = np.mean([set(a) == set(b) for a, b in zip(picked_with,
                                                     picked_without)])
    assert same < 0.5


def test_latent_decode_counts_by_hand():
    """One cached token of Xing4.0-29B-A4B, one layer: 512 + 64 values of 2
    bytes read once for all 32 heads; per head a 576-long score and a
    512-long weighted sum, 2 FLOPs a multiply-add."""
    model = manifest.config_of(MAN, "xing4.0-29b-a4b-depth5")
    assert flops_mla.latent_row_values(model) == 576
    assert flops_mla.mla_decode_bytes(model, 1) == 1152
    assert flops_mla.mla_decode_flops(model, 1) == 32 * (576 + 512) * 2 == 69632
    # 60 FLOP/byte: under the v5e's ridge (197e12 / 819e9 = 240), so the
    # roof of the decode kernel is the HBM bandwidth
    intensity = flops_mla.mla_decode_flops(model, 1) \
        / flops_mla.mla_decode_bytes(model, 1)
    assert intensity == pytest.approx(60.4, abs=0.1) and intensity < 240
    # a 64-wide step over 13.3k-token contexts: 0.98 GB a layer
    assert flops_mla.mla_decode_bytes(model, 64 * 13_300) == \
        pytest.approx(0.98e9, rel=0.01)
    assert flops_mla.mla_prefill_flops(model, 512, 1000) == \
        512 * 1000 * 69632


def test_scope_share_over_several_programs(tmp_path):
    """A serving slice runs several programs, whose names do not sort as
    their starts do: an operation's scope is looked up in the program whose
    module event covers it."""
    from lib import program_trace

    MS = 1e6
    op = lambda name, a, b: (name, a * MS, (b - a) * MS, name,  # noqa: E731
                             "fusion", (b - a) * MS)
    run = {"trace": {
        "device": {"/device:TPU:0": [
            op("fusion.1", 0, 30), op("fusion.2", 30, 40),      # decode
            op("fusion.1", 50, 60),                             # prefill
            op("fusion.1", 70, 90), op("fusion.2", 90, 100)]},  # decode
        "host": [("bench/step_decode", 0.0, 100 * MS)]}}
    program_trace.preload(
        xplane={"host": [], "dir": str(tmp_path), "modules": {
            "/device:TPU:0": sorted([      # by name, as lib/program_trace has
                ("jit_serve_decode_s64x8", 0.0, 45 * MS),
                ("jit_serve_prefill_t16", 48 * MS, 14 * MS),
                ("jit_serve_decode_s64x8", 65 * MS, 35 * MS)])}},
        scopes={"jit_serve_decode_s64x8": {
            "fusion.1": "while/body/layers/while/body/moe/experts",
            "fusion.2": "while/body/layers/while/body/hc/mix"},
            "jit_serve_prefill_t16": {"fusion.1": "layers/attention/mla_q"}})
    reader = manifest.load_module("readers", "serve_scope_share")
    try:
        assert reader.read(run, {"scope": "(^|/)moe/"}) == \
            pytest.approx(50 / 80)
        assert reader.read(run, {"scope": "(^|/)hc/"}) == \
            pytest.approx(20 / 80)
        table = json.load(open(tmp_path / "serve_scopes.json"))
        assert table["device_s_by_scope"]["layers/attention/mla_q"] == \
            pytest.approx(0.010)
        assert table["programs_in_the_slice"] == table["programs_with_text"]
    finally:
        reader._ROWS = None
        program_trace.preload()


def test_the_served_sample_is_held_to_the_reference():
    """``check_served`` at toy widths, with the reference's own greedy
    continuations as "what the window served": all of it passes; one turn
    whose tokens are somebody else's (a wrong slot, a wrong page table)
    fails by the per-turn floor, though the sample as a whole is within."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.xing4 import Xing4LM
    from lib import model as model_lib, xing4_system
    from reference.xing4 import Reference

    config = manifest.config_of(MAN, "xing4.0-29b-a4b-depth5")
    hf = xing4_system.published(config, True)
    model = Xing4LM.from_hf_config(hf, max_seq_len=256)
    params = model_lib.init_params(model, 5, jnp.bfloat16)
    ref = Reference(hf)
    weights = xing4_system.reference_weights(params)
    job = {"question_tokens": {"max": 8}, "answer_tokens": {"max": 8}}
    rng = np.random.default_rng(3)

    def greedy(prompt, n, width=128):
        produced = []
        for _ in range(n):
            seq = prompt + produced
            row = jnp.asarray(seq + [0] * (width - len(seq)), jnp.int32)
            logits = ref.logits([row], weights, [[len(seq) - 1]])[0]
            produced.append(int(np.argmax(np.asarray(logits[0]))))
        return produced

    turns = []
    for session, document in ((0, 40), (1, 56), (2, 72)):
        doc = rng.integers(1, hf["vocab_size"], size=document).tolist()
        for _ in range(2):
            prompt = doc + rng.integers(1, hf["vocab_size"], size=6).tolist()
            turns.append({"session": session, "document": document,
                          "prompt": prompt, "produced": greedy(prompt, 8),
                          "grafted": document // 8 * 8})

    def check(turns):
        engine = types.SimpleNamespace(
            config=types.SimpleNamespace(max_tokens=32, block_size=8),
            params=params,
            kv=types.SimpleNamespace(pages=jnp.zeros((1,))))
        ctx = types.SimpleNamespace(config=config, devices=jax.devices()[:1])
        return xing4_system.check_served(
            ctx, {"engine": engine, "ref_model": ref}, turns, job)

    good = check(turns)
    assert good["ok"] and good["tokens"] == 24 and good["within_share"] == 1.0
    assert [t["session"] for t in good["turns"]] == [0, 0, 1]
    swapped = [dict(t) for t in turns]
    swapped[3]["produced"] = rng.integers(1, hf["vocab_size"],
                                          size=8).tolist()
    bad = check(swapped)
    tol = config["tolerances"]
    assert not bad["ok"]
    assert bad["turn_within_share_min"] < tol["served_turn_within_share"]
    assert bad["within_share"] >= 0.6
    assert not check([dict(t, grafted=0) for t in turns])["ok"]
    assert not check([])["ok"]
