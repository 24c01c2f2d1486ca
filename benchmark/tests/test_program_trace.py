"""The readers that read what the program recorded about itself
(``lib/program_trace``): on hand-made intervals, and on two small samples
cut from traced chip runs of PR 25 (``tests/data/program_*.json``, made by
``tools/cut_program_sample.py``: the decode cell's is ``--skip-ms 398 --ms
64``, one fused window and one prefill step; the four-chip cell's
``--skip-ms 100 --ms 160``) where the sums they must keep are checked
against the readers the benchmark already had."""
import json
import os

import pytest

from lib import manifest, program_trace, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e6        # ns


def reader(name):
    return manifest.load_module("readers", name)


@pytest.fixture(autouse=True)
def fresh():
    program_trace.preload()
    yield
    program_trace.preload()


def sample(name):
    with open(os.path.join(DATA, name)) as f:
        raw = json.load(f)
    reduced = {"device": {k: [tuple(op) for op in v]
                          for k, v in raw["trace"]["device"].items()},
               "host": [tuple(sp) for sp in raw["trace"]["host"]]}
    program_trace.preload(
        xplane={"host": [tuple(sp) for sp in raw["program_host"]],
                "modules": {k: [tuple(m) for m in v]
                            for k, v in raw["modules"].items()}},
        scopes=raw["scopes"] or None)
    return {"trace": reduced}


# ---- the ring ---------------------------------------------------------------
RING = [     # name, t0, dur, attrs, tid
    ("serve/step", 10.0, 4.0, {"kind": "prefill"}, 1),
    ("serve/admit", 10.1, 0.4, {"preempted": 1, "admitted": 2}, 1),
    ("engine/put", 11.0, 2.0, {}, 1),
    ("engine/put_pack", 11.1, 0.5, {}, 1),        # nested: counted once
    ("serve/step", 14.0, 2.0, {"kind": "decode"}, 1),
    ("engine/decode_dispatch", 14.5, 1.0, {}, 1),
    ("serve/admit", 15.0, 0.1, {"preempted": 2, "admitted": 0}, 1),
    ("serve/queue_wait", 9.0, 1.0, {"uid": 7}, 1),       # ends in window
    ("serve/queue_wait", 11.0, 3.0, {"uid": 8}, 1),
    ("serve/queue_wait", 5.0, 2.0, {"uid": 6}, 1),       # ended before it
    ("serve/step", 30.0, 5.0, {"kind": "decode"}, 1),    # after the window
    ("serve/retire", 12.0, 0.001, {"uid": 8, "state": "failed",
                                   "reason": "nan"}, 1),
    ("serve/retire", 31.0, 0.001, {"uid": 9, "state": "shed",
                                   "reason": "draining"}, 1),      # after it
]
RUN = {"window": (10.0, 20.0), "trace": None}


def test_span_share_is_spans_less_what_runs_under_them():
    program_trace.preload(ring=RING)
    share = reader("program_span_share").read(
        RUN, {"spans": ["serve/step"], "minus": ["engine/"]})
    assert share == pytest.approx((4.0 + 2.0 - 2.0 - 1.0) / 10.0)
    assert reader("program_span_share").read(
        RUN, {"spans": ["serve/step"]}) == pytest.approx(0.6)


def test_span_share_clips_at_the_window():
    program_trace.preload(ring=[("serve/step", 8.0, 4.0, {}, 1),
                                ("engine/put", 9.0, 2.0, {}, 1)])
    share = reader("program_span_share").read(
        RUN, {"spans": ["serve/step"], "minus": ["engine/"]})
    assert share == pytest.approx((2.0 - 1.0) / 10.0)


@pytest.mark.parametrize("args,want", [
    ({"span": "serve/queue_wait", "stat": 50}, 2000.0),
    ({"span": "serve/queue_wait", "stat": "mean"}, 2000.0),
    ({"span": "serve/admit", "value": "preempted", "stat": "sum"}, 3.0),
    ({"span": "serve/admit", "value": "blocked", "stat": "sum"}, 0.0),
    ({"span": "serve/no_such_span", "stat": "sum"}, None),
    ({"span": "serve/retire", "stat": "count"}, 1.0),
    ({"span": "serve/no_such_span", "stat": "count"}, None),
    ({"span": "serve/no_such_span", "stat": "count",
      "beside": "serve/step"}, 0.0),
])
def test_span_stat(args, want):
    program_trace.preload(ring=RING)
    got = reader("program_span_stat").read(RUN, args)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("cell", ["decode", "prefill"])
def test_requests_unfinished_counts_what_ends_in_the_window(cell):
    """One ``serve/retire`` inside the window, one after it; a clean run
    reads 0; a program that records no ``serve/step`` reads nothing."""
    args = manifest.metric_of("requests_unfinished." + cell)["args"]
    read = reader("program_span_stat").read
    program_trace.preload(ring=RING)
    assert read(RUN, args) == 1.0
    assert read({"window": (20.0, 40.0)}, args) == 1.0
    program_trace.preload(ring=[sp for sp in RING
                                if sp[0] != "serve/retire"])
    assert read(RUN, args) == 0.0
    program_trace.preload(ring=[("engine/train_batch", 11.0, 1.0, {}, 1)])
    assert read(RUN, args) is None


@pytest.mark.parametrize("name,args", [
    ("program_span_share", {"spans": ["serve/step"]}),
    ("program_span_stat", {"span": "serve/admit", "stat": "sum"}),
    ("idle_in_spans", {"unowned": True}),
    ("scope_time_share", {"scope": "mlp", "over": "busy"}),
])
def test_a_program_without_the_tracer_reads_nothing(name, args):
    """The parent commit: no ring, no program span in the profile, no
    registered step text.  None, and no exception."""
    run = {"window": (0.0, 1.0), "trace": None}
    assert reader(name).read(run, args) is None
    run["trace"] = {"device": {"/device:TPU:0": [
        ("fusion.1", 0.0, 5.0, "fusion.1", "fusion", 5.0)]},
        "host": [("bench/step", 0.0, 10.0)]}
    program_trace.preload(xplane={"host": [], "modules": {}})
    assert reader(name).read(run, args) is None


# ---- idle time by program span ------------------------------------------------
def hand_trace():
    """Slice 0-100 ms; the device runs 10-30 and 50-90: idle 0-10, 30-50,
    90-100."""
    op = lambda name, a, b: (name, a * MS, (b - a) * MS, name,  # noqa: E731
                             "fusion", (b - a) * MS)
    return {"device": {"/device:TPU:0": [op("fusion.1", 10, 30),
                                         op("fusion.2", 50, 90)]},
            "host": [("bench/step_decode", 0.0, 100 * MS)]}


def test_idle_goes_to_the_innermost_span():
    host = [("serve/step", 5 * MS, 90 * MS),            # 5-95
            ("serve/window", 25 * MS, 30 * MS),         # 25-55
            ("engine/window_wait", 28 * MS, 7 * MS),    # 28-35: idle 30-35
            ("engine/window_fetch", 35 * MS, 5 * MS)]   # 35-40: idle 35-40
    idle = program_trace.idle_by_span(hand_trace(), host)
    by = {k: v / MS for k, v in idle["by_span_ns"].items()}
    assert by["engine/window_wait"] == pytest.approx(5)
    assert by["engine/window_fetch"] == pytest.approx(5)
    assert by["serve/window"] == pytest.approx(10)       # 40-50
    assert by["serve/step"] == pytest.approx(5 + 5)      # 5-10, 90-95
    assert by[""] == pytest.approx(5 + 5)                # 0-5, 95-100
    assert sum(by.values()) == pytest.approx(idle["idle_ns"] / MS) \
        == pytest.approx(40)
    assert idle["leaf_ns"] / MS == pytest.approx(10)     # wait + fetch
    program_trace.preload(xplane={"host": host, "modules": {}})
    run = {"trace": hand_trace()}
    read = reader("idle_in_spans").read
    assert read(run, {"spans": ["engine/window_wait",
                                "engine/window_fetch"]}) == pytest.approx(0.1)
    assert read(run, {"unowned": True}) == pytest.approx(0.1)


# ---- device time by scope -------------------------------------------------------
def test_scope_share_by_module_scope_and_opcode():
    op = lambda name, a, b, code: (name, a * MS, (b - a) * MS,  # noqa: E731
                                   name, code, (b - a) * MS)
    run = {"trace": {
        "device": {"/device:TPU:0": [
            op("fusion.1", 0, 40, "fusion"), op("all-reduce.1", 40, 60,
                                                "all-reduce"),
            op("all-gather.2", 60, 70, "all-gather"),
            op("fusion.1", 80, 90, "fusion")]},      # another program's
        "host": [("bench/train_step", 0.0, 100 * MS)]}}
    program_trace.preload(
        xplane={"host": [], "modules": {"/device:TPU:0": [
            ("jit_step_fn", 0.0, 75 * MS), ("jit_other", 78 * MS, 15 * MS)]}},
        scopes={"jit_step_fn": {
            "fusion.1": "layers/while/body/mlp",
            "all-reduce.1": "layers/while/body/mlp/moe/dispatch",
            "all-gather.2": "layers/while/body/attention"}})
    read = reader("scope_time_share").read
    coll = "^(all-reduce|all-gather)"
    assert read(run, {"scope": "(^|/)mlp(/|$)", "over": "busy"}) \
        == pytest.approx(60 / 80)
    assert read(run, {"scope": "(^|/)moe/(route|dispatch|combine)(/|$)",
                      "opcode": coll, "over": "slice"}) \
        == pytest.approx(20 / 100)
    assert read(run, {"scope": ".", "opcode": coll, "over": "slice",
                      "not_scope": "(^|/)moe/"}) == pytest.approx(10 / 100)


# ---- recorded samples -------------------------------------------------------------
def test_decode_sample_idle_adds_up_to_idle_share():
    run = sample("program_decode_slice.json")
    idle = program_trace.idle_by_span(run["trace"],
                                      program_trace.xplane(run)["host"])
    share = reader("idle_share").read(run, {})
    assert sum(idle["by_span_ns"].values()) / idle["slice_ns"] \
        == pytest.approx(share, abs=1e-9)
    read = reader("idle_in_spans").read
    owned = read(run, {"spans": sorted(k for k in idle["by_span_ns"] if k)})
    assert owned + read(run, {"unowned": True}) == pytest.approx(share,
                                                                 abs=1e-9)
    drain = manifest.metric_of("idle_in_drain.decode")["args"]
    assert 0.0 < read(run, drain) <= owned
    # the names the program gives its kernels
    labels = {op[3] for op in run["trace"]["device"]["/device:TPU:0"]}
    assert any(label.startswith("paged_decode/pallas(") for label in labels)
    assert not any(label.startswith("closed_call/") for label in labels)


def test_four_chip_sample_collectives_have_owners():
    run = sample("program_zero3_slice.json")
    exposed = trace.collective_exposed(run["trace"])
    share = exposed["exposed_s"] / exposed["window_s"]
    read = reader("scope_time_share").read
    moe = read(run, manifest.metric_of("collective_share.moe")["args"])
    zero = read(run, manifest.metric_of("collective_share.zero")["args"])
    assert moe > 0 and zero > 0
    assert moe + zero <= share + 1e-9
    assert moe + zero >= 0.95 * share
    phases = [read(run, manifest.metric_of("phase_share." + p)["args"])
              for p in ("attention", "mlp", "head_loss", "optimizer")]
    assert all(p is not None for p in phases) and sum(phases) <= 1.0 + 1e-9
