"""The step account of ISSUE 58: ``program_step_stalls`` and
``program_span_attr_share`` (new), ``program_span_stat`` and
``idle_in_spans`` (data only) on a ring and a profile given by
``program_trace.preload``; each of the seventeen new metrics' entry — found
BY NAME — its file and its cells."""
import json
import os
import sys

import pytest

from lib import manifest, program_trace

MAN = manifest.manifest()


def cells_of(end_to_end):
    entry, = [m for m in MAN["end_to_end"] if m["name"] == end_to_end]
    return entry["workloads"]


DECODE = cells_of("serve_tokens_per_s")
TRAIN = cells_of("train_tokens_per_s")
PREFILL = cells_of("ttft_mean_ms")
SPAN = "program_span"
KINDS = {"decode": ("serve_tokens_per_s", DECODE, "serve/step"),
         "prefill": ("ttft_mean_ms", PREFILL, "serve/step"),
         "train": ("train_tokens_per_s", TRAIN, "engine/train_batch")}
NEW = {}        # name -> (unit, layer, kind, source)
for kind in KINDS:
    NEW[f"stall_s.{kind}"] = ("s", "end to end, observed", kind, SPAN)
    NEW[f"stall_wait_s.{kind}"] = ("s", "device", kind, SPAN)
    NEW[f"gc_ms_in_window.{kind}"] = (
        "ms", "train engine" if kind == "train" else "serve engine", kind,
        SPAN)
for kind in ("decode", "train"):
    NEW[f"stall_client_s.{kind}"] = ("s", "end to end, observed", kind, SPAN)
for reason in ("finisher", "free_row", "queued"):
    NEW[f"window_held_share.{reason}"] = ("ratio", "serve scheduler",
                                          "decode", SPAN)
TRAIN_IDLE = {"idle_in_wait.train": "device",
              "idle_in_host.train": "train engine",
              "idle_unowned.train": "device"}
for name, layer in TRAIN_IDLE.items():
    NEW[name] = ("ratio", layer, "train", "device_trace")


def reader(name):
    return manifest.load_module("readers", name)


def value(name, run):
    spec = manifest.metric_of(name)
    return reader(spec["reader"]).read(run, spec.get("args", {}))


@pytest.fixture(autouse=True)
def fresh():
    program_trace.preload()
    yield
    program_trace.preload()


@pytest.fixture()
def cell_dir(tmp_path, monkeypatch):
    """The checkout's root in ``tmp_path`` and a command line that names a
    cell: where ``stall_account.json`` goes."""
    monkeypatch.setattr(program_trace, "ROOT", str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload=some-cell"])
    return tmp_path / ".bench_trace" / "some-cell"


# ---- a recorded ring: the window is [100, 151) ------------------------------
def decode_step(t0, wait, steps=8, held_by="", tail=0.002, launch=0.001,
                gc=0.0, facts=None, compile_s=0.0):
    """One decode step of one thread: dispatch, then the drain of a window
    of ``steps``; ``gc``: a collector's pause directly under the step."""
    t = t0 + 0.001
    rows = [("serve/window", t, 0.0, {"steps": steps, "n_seqs": 64,
                                      "ahead": int(not held_by),
                                      "held_by": held_by}, 1),
            ("engine/decode_dispatch", t, launch + 0.001,
             {"key": f"64x{steps}"}, 1),
            ("engine/decode_launch", t + 0.001, launch, {}, 1)]
    if compile_s:
        rows.append(("compile/backend", t + 0.001, compile_s,
                     {"program": "jit_serve_decode_s64x8"}, 1))
    t += launch + 0.001
    rows.append(("engine/window_wait", t, wait, {"steps": steps}, 1))
    t += wait
    rows.append(("serve/window_apply", t, tail, {}, 1))
    t += tail
    rows[0] = rows[0][:2] + (t - rows[0][1],) + rows[0][3:]
    if gc:
        rows.append(("engine/host_gc", t, gc, {"generation": 2}, 1))
        t += gc
    attrs = dict(kind="decode", **(facts or {}))
    return [("serve/step", t0, t - t0 + 0.001, attrs, 1)] + rows


def prefill_step(t0, dur=0.060, bucket=256, drained=None):
    rows = [("serve/prefill", t0 + 0.001, dur - 0.002, {}, 1),
            ("engine/put", t0 + 0.001, dur - 0.01, {"bucket": bucket}, 1),
            ("serve/logits_fetch", t0 + dur - 0.008, 0.005, {}, 1)]
    if drained:     # the window in flight, waited out at the top of the step
        rows = [("engine/window_wait", t0, drained[1],
                 {"steps": drained[0]}, 1)] + [
            (n, t + drained[1], d, a, tid) for n, t, d, a, tid in rows]
        dur += drained[1]
    return [("serve/step", t0, dur, {"kind": "prefill"}, 1)] + rows


def ring(steps, gap=0.002):
    """Steps laid end to end from 100.0, ``gap`` apart (a number, or one a
    step)."""
    out, t = [], 100.0
    for i, make in enumerate(steps):
        rows = make(t)
        out += rows
        t = rows[0][1] + rows[0][2] + (gap[i] if isinstance(gap, list)
                                       else gap)
    return sorted(out, key=lambda sp: sp[1])


def run_of():
    return {"window": (100.0, 151.0), "trace": None}


QUIET = [lambda t: decode_step(t, 0.180)] * 20 \
    + [lambda t: prefill_step(t)] * 4 \
    + [lambda t: decode_step(t, 0.020, steps=1, held_by="finisher")] * 6


def test_a_clean_window_reads_zero(cell_dir):
    program_trace.preload(ring=ring(QUIET))
    run = run_of()
    for name in ("stall_s.decode", "stall_wait_s.decode",
                 "stall_client_s.decode"):
        assert value(name, run) == 0.0
    with open(cell_dir / "stall_account.json") as f:
        account = json.load(f)
    for name in ("stall_s.prefill", "stall_wait_s.prefill"):
        assert value(name, run) == 0.0
    assert account["stalls"] == [] and account["steps"] == 30
    assert account["classes"]["decode steps=8 drained=8"]["n"] == 20
    assert account["classes"]["prefill bucket=256 fetched"]["median_s"] \
        == pytest.approx(0.060)
    assert account["gaps"]["n"] == 29


@pytest.mark.parametrize("how,owner,part", [
    (dict(wait=5.180), "engine/window_wait", "wait"),       # below the host
    (dict(wait=0.180, launch=5.001), "engine/decode_launch", "host"),
    (dict(wait=0.180, gc=5.0), "engine/host_gc", "host"),   # the collector
    (dict(wait=0.180, tail=5.002), "serve/window_apply", "host"),
])
def test_the_innermost_span_that_holds_the_excess_owns_it(cell_dir, how,
                                                          owner, part,
                                                          capsys):
    facts = {"cpu_s": 0.004, "nvcsw": 3, "nivcsw": 0}
    steps = list(QUIET)
    steps[7] = lambda t: decode_step(t, facts=facts, **how)
    program_trace.preload(ring=ring(steps))
    run = run_of()
    assert value("stall_s.decode", run) == pytest.approx(5.0, abs=1e-6)
    assert value("stall_wait_s.decode", run) == pytest.approx(
        5.0 if part == "wait" else 0.0, abs=1e-6)
    assert value("stall_client_s.decode", run) == 0.0
    with open(cell_dir / "stall_account.json") as f:
        (entry,) = json.load(f)["stalls"]
    assert entry["owner"] == owner and entry["what"] == "step"
    assert entry["class"] == "decode steps=8 drained=8"
    assert (entry["key"], entry["steps"]) == ("64x8", 8)
    assert entry["offset_s"] == pytest.approx(7 * 0.188, abs=1e-6)
    assert entry["median_s"] == pytest.approx(0.186, abs=1e-6)
    assert {k: entry[k] for k in facts} == facts
    assert entry["host_gc_s"] == pytest.approx(how.get("gc", 0.0))
    err = capsys.readouterr().err
    assert err.count("program_step_stalls: step at +") == 1   # once a run
    assert f"lost in {owner}" in err and "nvcsw=3" in err


def test_host_code_under_no_span_is_the_steps_own():
    def slow(t):
        rows = decode_step(t, 0.180)
        return [rows[0][:2] + (rows[0][2] + 0.4,) + rows[0][3:]] + rows[1:]

    steps = list(QUIET)
    steps[3] = slow
    program_trace.preload(ring=ring(steps))
    run = run_of()
    assert value("stall_s.decode", run) == pytest.approx(0.4)
    assert value("stall_wait_s.decode", run) == 0.0
    assert run["step_stalls"]["serve/step"]["stalls"][0]["owner"] \
        == "serve/step"


def test_a_compile_inside_a_stalled_step_is_named(cell_dir):
    steps = list(QUIET)
    steps[2] = lambda t: decode_step(t, 0.180, launch=0.9, compile_s=0.85)
    program_trace.preload(ring=ring(steps))
    assert value("stall_s.decode", run_of()) == pytest.approx(0.899)
    with open(cell_dir / "stall_account.json") as f:
        (entry,) = json.load(f)["stalls"]
    assert entry["compiles"] == [{"name": "compile/backend", "dur_s": 0.85,
                                  "program": "jit_serve_decode_s64x8"}]


def test_a_gap_is_the_clients_and_the_prefill_cell_leaves_gaps_out():
    gaps = [0.002] * 30
    gaps[11] = 2.002
    program_trace.preload(ring=ring(QUIET, gap=gaps))
    run = run_of()
    assert value("stall_s.decode", run) == pytest.approx(2.0)
    assert value("stall_client_s.decode", run) == pytest.approx(2.0)
    assert value("stall_wait_s.decode", run) == 0.0
    (entry,) = run["step_stalls"]["serve/step"]["stalls"]
    assert (entry["what"], entry["owner"]) == ("gap", "client")
    assert value("stall_s.prefill", run) == 0.0     # ``gaps: false``: an
    found = run["step_stalls"]["serve/step, no gaps"]    # account of its own
    assert found["stalls"] == [] and not found["gaps"]["judged"]


def test_a_drain_inside_a_prefill_step_is_a_class_not_a_stall():
    """Since PR 53 a window may be waited out at the top of ANY step: a
    prefill step that drains eight decode steps is not a slow prefill."""
    steps = list(QUIET)
    for i in (21, 22, 23):
        steps[i] = lambda t: prefill_step(t, drained=(8, 0.170))
    program_trace.preload(ring=ring(steps))
    run = run_of()
    assert value("stall_s.decode", run) == 0.0
    classes = run["step_stalls"]["serve/step"]["classes"]
    assert classes["prefill bucket=256 drained=8 fetched"]["n"] == 3


def test_a_chunk_that_only_enqueues_is_not_the_chunk_that_fetches():
    """A prompt's last chunk waits for every chunk enqueued before it."""
    def chunk(t):       # no ``serve/logits_fetch``: the put returns at once
        return [("serve/step", t, 0.002, {"kind": "prefill"}, 1),
                ("engine/put", t + 0.0005, 0.001, {"bucket": 512}, 1)]

    steps = [lambda t: decode_step(t, 0.180)] * 6 + (
        [chunk] * 4 + [lambda t: prefill_step(t, dur=0.180, bucket=512)]) * 3
    program_trace.preload(ring=ring(steps))
    run = run_of()
    assert value("stall_s.decode", run) == 0.0
    classes = run["step_stalls"]["serve/step"]["classes"]
    assert classes["prefill bucket=512"]["n"] == 12
    assert classes["prefill bucket=512 fetched behind=2048"]["n"] == 3


def test_a_request_s_life_is_not_the_threads_time():
    """``serve/first_token`` (an explicit start and duration) may lie
    inside the step that admitted its request: it owns nothing."""
    def slow(t):
        rows = decode_step(t, 0.180, launch=0.9)
        return rows + [("serve/first_token", t + 0.0005, 0.95, {"uid": 3}, 1)]

    steps = list(QUIET)
    steps[4] = slow
    program_trace.preload(ring=ring(steps))
    run = run_of()
    assert value("stall_s.decode", run) == pytest.approx(0.899)
    (entry,) = run["step_stalls"]["serve/step"]["stalls"]
    assert entry["owner"] == "engine/decode_launch"


def test_a_rare_shape_borrows_its_kinds_largest_median():
    steps = list(QUIET)
    steps[5] = lambda t: decode_step(t, 0.090, steps=4)         # alone
    steps[6] = lambda t: decode_step(t, 0.560, steps=2)         # alone, slow
    program_trace.preload(ring=ring(steps))
    run = run_of()
    # 0.566 s against 3 x 0.186 s (the 8-step class): 0.380 s over it
    assert value("stall_s.decode", run) == pytest.approx(0.380, abs=1e-6)
    found = run["step_stalls"]["serve/step"]
    assert found["classes"]["decode steps=4 drained=4"]["judged_by"] \
        == "decode steps=8 drained=8"
    assert found["unjudged"] == 0
    (entry,) = found["stalls"]
    assert entry["class"] == "decode steps=2 drained=2"


def test_a_kind_with_no_class_of_three_is_not_judged():
    steps = [lambda t: decode_step(t, 0.180)] * 5 \
        + [lambda t: prefill_step(t, dur=9.0)]
    program_trace.preload(ring=ring(steps))
    run = run_of()
    assert value("stall_s.decode", run) == 0.0
    assert run["step_stalls"]["serve/step"]["unjudged"] == 1


def train_ring(waits):
    out, t = [], 100.0
    for i, wait in enumerate(waits):
        out += [("engine/train_batch", t, 0.004 + wait, {"step": i + 1}, 7),
                ("engine/dispatch", t + 0.001, 0.002, {}, 7),
                ("engine/step_wait", t + 0.0035, wait, {}, 7),
                ("engine/post_step", t + 0.0045 + wait, 0.001, {}, 7)]
        t += 0.006 + wait
    return out


def test_the_train_steps_are_one_class_and_the_wait_is_the_devices():
    waits = [0.250] * 12
    waits[4] = 2.750
    program_trace.preload(ring=train_ring(waits))
    run = run_of()
    assert value("stall_s.train", run) == pytest.approx(2.5)
    assert value("stall_wait_s.train", run) == pytest.approx(2.5)
    assert value("stall_client_s.train", run) == 0.0
    (entry,) = run["step_stalls"]["engine/train_batch"]["stalls"]
    assert (entry["owner"], entry["class"]) == ("engine/step_wait", "step")
    assert value("stall_s.decode", run) is None     # no ``serve/step`` here


@pytest.mark.parametrize("name", sorted(n for n in NEW
                                        if n.startswith("stall_")))
def test_what_a_program_lacks_gives_no_value(name, monkeypatch):
    run = run_of()
    program_trace.preload(ring=None)                    # no tracer at all
    assert value(name, run) is None
    program_trace.preload(ring=[("serve/queue_wait", 101.0, 1.0, {}, 1)])
    assert value(name, run) is None                     # no such step span
    step = KINDS[NEW[name][2]][2]
    early = [(step, 50.0 + i, 0.5, {"kind": "decode"}, 1) for i in range(5)]
    program_trace.preload(ring=early)
    assert value(name, run_of()) == 0.0     # steps, none in the window
    from deepspeed_tpu.telemetry import get_tracer

    late = [(step, 120.0 + i, 0.5, {"kind": "decode"}, 1) for i in range(5)]
    program_trace.preload(ring=late)
    monkeypatch.setattr(get_tracer(), "dropped", 3)
    assert value(name, run_of()) is None    # the ring lost the window's start


# ---- the two data-only readers ----------------------------------------------
HELD = ["first"] + [""] * 5 + ["finisher"] * 2 + ["queued", "prefilling"]


def test_held_shares_and_the_share_ahead_add_up_to_at_most_one():
    steps = [lambda t, h=h: decode_step(t, 0.180, held_by=h) for h in HELD]
    program_trace.preload(ring=ring(steps))
    run = run_of()
    shares = {r: value(f"window_held_share.{r}", run)
              for r in ("finisher", "free_row", "queued")}
    assert shares == {"finisher": 0.2, "free_row": 0.0, "queued": 0.2}
    ahead = value("window_ahead_share.decode", run)
    assert ahead == 0.5 and sum(shares.values()) + ahead == \
        pytest.approx(0.9)                  # the rest: ``first``
    assert value("stall_s.decode", run) == 0.0
    assert run["step_stalls"]["serve/step"]["windows_held_by"] == {
        "first": 1, "ahead": 5, "finisher": 2, "queued": 1, "prefilling": 1}


def test_a_program_that_does_not_say_why_gives_no_share():
    rows = ring([lambda t: decode_step(t, 0.180)] * 4)
    parent = [sp[:3] + ({k: v for k, v in sp[3].items() if k != "held_by"},)
              + sp[4:] for sp in rows]
    program_trace.preload(ring=parent)
    assert value("window_held_share.free_row", run_of()) is None
    assert value("window_ahead_share.decode", run_of()) == 1.0
    program_trace.preload(ring=[sp for sp in rows if sp[1] > 100.3])
    assert value("window_held_share.free_row",
                 {"window": (100.0, 100.2)}) is None    # no window in it


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_collectors_pauses_in_the_window(kind):
    spec = manifest.metric_of(f"gc_ms_in_window.{kind}")
    step = KINDS[kind][2]
    assert spec == {"reader": "program_span_stat", "args": {
        "span": "engine/host_gc", "value": "dur_ms", "stat": "sum",
        "beside": step}}
    rows = [(step, 100.0 + i, 0.9, {}, 1) for i in range(4)] + [
        ("engine/host_gc", 99.0, 0.5, {"generation": 2}, 1),    # before it
        ("engine/host_gc", 100.2, 0.03, {"generation": 0}, 1),
        ("engine/host_gc", 102.1, 0.07, {"generation": 2}, 1)]
    program_trace.preload(ring=sorted(rows, key=lambda sp: sp[1]))
    assert value(f"gc_ms_in_window.{kind}", run_of()) == pytest.approx(100.0)
    program_trace.preload(ring=[sp for sp in rows if sp[0] == step])
    assert value(f"gc_ms_in_window.{kind}", run_of()) == 0.0    # the parent
    program_trace.preload(ring=[sp for sp in rows if sp[0] != step])
    assert value(f"gc_ms_in_window.{kind}", run_of()) is None


# ---- a traced run: what the device did under a stall ------------------------
MS = 1e6            # the profile's clock is in ns
AHEAD = 7.25        # profile time 0 is ring time 100 + AHEAD


def traced(waits, slice_s, ops):
    """A train run whose profile starts ``AHEAD`` s into the window and
    holds the steps from then on as host events, with the device's ``ops``
    [(label, start_s, dur_s)] on the profile's clock."""
    rows = train_ring(waits)
    host = sorted(
        (n, (t - 100.0 - AHEAD) * 1e9 - 2e3, d * 1e9 + 4e3) for n, t, d, _, _
        in rows if n.startswith("engine/") and t >= 100.0 + AHEAD)
    program_trace.preload(ring=rows, xplane={"host": host, "modules": {}})
    device = sorted((label, a * 1e9, d * 1e9, label, "fusion", d * 1e9)
                    for label, a, d in ops)
    return {"window": (100.0, 151.0), "slice": (100.0 + AHEAD,
                                                100.0 + AHEAD + slice_s),
            "trace": {"device": {"/device:TPU:0": device},
                      "host": [("bench/train_step", 0.0, slice_s * 1e9)]}}


def test_a_stall_in_the_traced_slice_says_what_the_device_did(cell_dir):
    """Steps of 0.254 s from 100.0; the slice opens inside the 29th (which
    has no event in the profile: the ring's and the profile's steps are
    paired one on), the 31st waits 2 s more, the device idle but for one
    0.3 s operation."""
    waits = [0.250] * 40
    waits[30] = 2.250
    at = sum(0.006 + w for w in waits[:30])         # the 31st, from 100.0
    run = traced(waits, 3.0, [("fusion.7", at - AHEAD + 0.004, 0.300),
                              ("fusion.2", at - AHEAD - 0.200, 0.150)])
    assert value("stall_s.train", run) == pytest.approx(2.0)
    (entry,) = run["step_stalls"]["engine/train_batch"]["stalls"]
    assert entry["owner"] == "engine/step_wait"
    assert entry["device_overlap_s"] == pytest.approx(2.254, abs=1e-4)
    assert entry["device_busy_s"] == pytest.approx(0.300, abs=1e-4)
    assert entry["device_idle_s"] == pytest.approx(1.954, abs=1e-4)
    assert entry["device_longest_op"] == {
        "label": "fusion.7", "opcode": "fusion",
        "dur_s": pytest.approx(0.300)}
    with open(cell_dir / "stall_account.json") as f:
        assert json.load(f)["stalls"][0]["device_busy_s"] == \
            pytest.approx(0.300, abs=1e-4)


def test_a_stall_cut_by_the_slices_end_is_read_as_far_as_it_was_traced():
    waits = [0.250] * 40
    waits[30] = 2.250
    at = sum(0.006 + w for w in waits[:30])
    run = traced(waits, at - AHEAD + 1.0, [("fusion.7", 0.0, 60.0)])
    assert value("stall_s.train", run) == pytest.approx(2.0)
    (entry,) = run["step_stalls"]["engine/train_batch"]["stalls"]
    assert entry["device_overlap_s"] == pytest.approx(1.0, abs=1e-4)
    assert entry["device_busy_s"] == pytest.approx(1.0, abs=1e-4)
    assert entry["device_idle_s"] == pytest.approx(0.0, abs=1e-4)


@pytest.mark.parametrize("how", ["outside the slice", "not traced",
                                 "clocks do not line up", "no step event"])
def test_no_device_account_where_nothing_overlaps(how):
    waits = [0.250] * 40
    waits[3 if how == "outside the slice" else 30] = 2.250
    run = traced(waits, 3.0, [("fusion.7", 0.0, 3.0)])
    if how == "not traced":
        run["trace"] = None
    elif how == "clocks do not line up":    # a step the profile lacks
        extra = program_trace.xplane(run)
        steps = [ev for ev in extra["host"] if ev[0] == "engine/train_batch"]
        extra["host"].remove(steps[1])
        waits[31] = 0.300                   # (and no two steps alike)
        program_trace.preload(ring=train_ring(waits), xplane=extra)
    elif how == "no step event":            # the parent's profile
        program_trace.preload(ring=train_ring(waits),
                              xplane={"host": [], "modules": {}})
    assert value("stall_s.train", run) == pytest.approx(2.0)
    (entry,) = run["step_stalls"]["engine/train_batch"]["stalls"]
    assert not [k for k in entry if k.startswith("device_")]


# ---- the train cell's idle, split by what the host was doing ----------------
def test_the_three_train_idle_files_sum_to_the_idle_share():
    """Two steps (1-100 and 107-206 ms) in a slice of 220; the device runs
    8-98 and 112-204.  Its 38 idle ms: 2 under the waits (98-99, 204-205),
    20 under the host's work in the step (the step's own 1 ms before each
    dispatch and after each wait, the dispatches 2-8 and 108-112, each
    ``post_step`` and the collector's pause after it), 16 under no span."""
    op = lambda name, a, b: (name, a * MS, (b - a) * MS, name,  # noqa: E731
                             "fusion", (b - a) * MS)
    trace = {"device": {"/device:TPU:0": [op("fusion.1", 8, 98),
                                          op("fusion.2", 112, 204)]},
             "host": [("bench/train_step", 0.0, 220 * MS)]}
    host = []
    for t in (1, 107):
        host += [("engine/train_batch", t * MS, 99 * MS),
                 ("engine/dispatch", (t + 1) * MS, 6 * MS),
                 ("engine/step_wait", (t + 8) * MS, 90 * MS),
                 ("engine/post_step", (t + 99) * MS, 2 * MS),
                 ("engine/host_gc", (t + 101) * MS, 1 * MS)]
    program_trace.preload(xplane={"host": sorted(host, key=lambda e: e[1]),
                                  "modules": {}})
    run = {"window": (0.0, 1.0), "trace": trace}
    got = {name: value(name, run) for name in TRAIN_IDLE}
    share = value("idle_share.train", run)
    assert share == pytest.approx((220 - 90 - 92) / 220)
    assert got["idle_in_wait.train"] == pytest.approx(2 / 220)
    assert got["idle_in_host.train"] == pytest.approx(20 / 220)
    assert got["idle_unowned.train"] == pytest.approx(16 / 220)
    assert sum(got.values()) == pytest.approx(share, abs=1e-12)


def test_the_parents_profile_gives_the_wait_no_value_and_the_rest_what_it_has():
    """No ``engine/step_wait`` event: its idle is the step's own (the
    parent: 1-2, the dispatch 2-8, 98-99), and a profile with no program
    span at all reads nothing."""
    op = ("fusion.1", 8 * MS, 90 * MS, "fusion.1", "fusion", 90 * MS)
    run = {"window": (0.0, 1.0), "trace": {
        "device": {"/device:TPU:0": [op]},
        "host": [("bench/train_step", 0.0, 100 * MS)]}}
    program_trace.preload(xplane={"host": [
        ("engine/train_batch", 1 * MS, 98 * MS),
        ("engine/dispatch", 2 * MS, 6 * MS)], "modules": {}})
    assert value("idle_in_wait.train", run) == 0.0   # (the reader that is
    # there gives 0, not none, for a span that a profile with spans lacks)
    assert value("idle_in_host.train", run) == pytest.approx(8 / 100)
    assert value("idle_unowned.train", run) == pytest.approx(2 / 100)
    program_trace.preload(xplane={"host": [], "modules": {}})
    for name in TRAIN_IDLE:
        assert value(name, run) is None


# ---- the manifest -----------------------------------------------------------
def test_there_are_seventeen():
    assert len(NEW) == 17


@pytest.mark.parametrize("name", sorted(NEW))
def test_entry_file_and_cells(name):
    unit, layer, kind, source = NEW[name]
    moves, cells, _ = KINDS[kind]
    entry, = [m for m in MAN["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": unit, "better": "lower",
        "source": source, "layer": layer, "moves": moves,
        "workloads": cells}
    spec = manifest.metric_of(name)
    assert set(spec) == {"reader", "args"}
    assert os.path.isfile(os.path.join(manifest.BENCH, "readers",
                                       spec["reader"] + ".py"))
    # every listed cell reports the end-to-end metric the entry moves
    judged, = [m for m in MAN["end_to_end"] if m["name"] == moves]
    assert set(cells) <= set(judged["workloads"])
    for cell in cells:
        assert entry in manifest.metrics_for(MAN, cell, "per_layer")


def test_the_new_readers_read_no_bench_span():
    for name in ("program_step_stalls", "program_span_attr_share"):
        with open(os.path.join(manifest.BENCH, "readers", name + ".py")) as f:
            text = f.read()
        assert "bench/" not in text and "serve_system" not in text
