"""BENCHMARK.json against the contract's rules that need no chip."""
import json
import os
import re

import pytest

from lib import manifest

MAN = manifest.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = MAN["end_to_end"] + MAN["per_layer"]


def cells_of(metric):
    return set(metric.get("workloads") or
               [w["name"] for w in MAN["workloads"]])


def test_keys_are_exactly_the_contracts():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    for m in MAN["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in MAN["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) \
        < 64 * 1024


def test_names_units_and_whys():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    for m in METRICS:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for entry in MAN["workloads"] + MAN["configs"]:
        assert NAME.match(entry["name"])
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for w in MAN["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    for m in MAN["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200


def test_every_moves_is_reported_wherever_the_layer_metric_is():
    end_to_end = {m["name"]: m for m in MAN["end_to_end"]}
    for m in MAN["per_layer"]:
        assert m["moves"] in end_to_end, m["name"]
        assert cells_of(m) <= cells_of(end_to_end[m["moves"]]), m["name"]


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    for w in MAN["workloads"]:
        e2e = [m["name"] for m in MAN["end_to_end"] if w["name"] in cells_of(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(w["name"] in cells_of(m) for m in MAN["per_layer"])


def test_files_exist_and_configs_state_their_cut():
    pairs = set()
    for w in MAN["workloads"]:
        assert os.path.isfile(os.path.join(
            manifest.BENCH, "traffic", w["traffic"] + ".json"))
        kind = manifest.traffic_of(w["traffic"])["kind"]
        assert os.path.isfile(os.path.join(manifest.BENCH, "generators",
                                           kind + ".py"))
        assert w["config"] in [c["name"] for c in MAN["configs"]]
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(MAN["workloads"])
    for c in MAN["configs"]:
        assert c["file"].startswith("benchmark/")
        body = manifest.config_of(MAN, c["name"])
        assert body["reduced"] == c["reduced"] and body["source"] == c["source"]
        for key in ("deployment", "assumed", "reference", "tolerances"):
            assert body[key]
        for key in c["reduced"]:
            assert body[key] != body["published"][key]
            assert not re.search(r"(_dim$|_rank$|_size$|head_dim|experts_per_tok)", key)
        assert any(w["config"] == c["name"] for w in MAN["workloads"])


def test_every_metric_has_a_reader():
    """``metrics/<name>.json`` says how the number is read and nothing
    else: names, units, bounds and cells are BENCHMARK.json's alone."""
    for m in METRICS:
        spec = manifest.metric_of(m["name"])
        assert set(spec) <= {"reader", "args"}, m["name"]
        assert os.path.isfile(os.path.join(manifest.BENCH, "readers",
                                           spec["reader"] + ".py"))


def test_traffic_files_say_nothing_about_the_engine():
    """What to warm comes from the engine and the scheduler, so a traffic
    file lists no programs, buckets or window lengths."""
    for w in MAN["workloads"]:
        for key in manifest.traffic_of(w["traffic"]):
            assert not re.search(r"warm|bucket|window", key), (w["traffic"],
                                                               key)


def test_at_most_one_four_chip_cell():
    assert all(w["chips"] in (1, 4) for w in MAN["workloads"])
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) <= 1


def test_tpot_is_judged_in_the_decode_cell_only():
    by_name = {m["name"]: m for m in MAN["end_to_end"]}
    assert by_name["tpot_p50_ms"]["workloads"] == ["mistral7b-serve-decode"]
    prefill = [m["name"] for m in MAN["end_to_end"]
               if "mistral7b-serve-prefill" in cells_of(m)]
    assert not any("tpot" in name for name in prefill)


def test_run_py_names_no_cell_configuration_traffic_or_metric():
    with open(os.path.join(manifest.BENCH, "run.py")) as f:
        source = f.read()
    names = [w["name"] for w in MAN["workloads"]] \
        + [w["traffic"] for w in MAN["workloads"]] \
        + [c["name"] for c in MAN["configs"]] \
        + [m["name"] for m in METRICS]
    whole = r"(?<![A-Za-z0-9_.\-]){}(?![A-Za-z0-9_.\-])"
    assert not [n for n in names
                if re.search(whole.format(re.escape(n)), source)]
