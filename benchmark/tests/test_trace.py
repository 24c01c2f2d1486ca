"""Interval-union busy share, own times and gap attribution, on a small
trace recorded on the chip (tests/data) and on hand-made intervals.  The
recorded numbers are checked against a brute-force raster, not against
themselves."""
import os

import numpy as np
import pytest

from lib import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace_decode_slice.json")


@pytest.fixture(scope="module")
def recorded():
    return trace.load_json(DATA)


def raster(intervals, lo, hi, step=1000.0):
    """Covered nanoseconds by sampling every ``step`` ns."""
    grid = np.arange(lo, hi, step)
    hit = np.zeros(len(grid), bool)
    for start, end in intervals:
        hit |= (grid >= start) & (grid < end)
    return hit


def test_union_subtract_clip_by_hand():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 3), (6, 9)]) == \
        [(0, 3), (5, 9)]
    assert trace.total([(0, 3), (5, 9)]) == 7
    assert trace.subtract([(0, 10)], [(2, 3), (5, 9)]) == \
        [(0, 2), (3, 5), (9, 10)]
    assert trace.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert trace.clip([(0, 4), (6, 8)], 2, 7) == [(2, 4), (6, 7)]


def test_overlapping_lanes_are_not_counted_twice():
    # a while covers its body's operations: summing durations gives 16
    ops = [("while.1", 0.0, 10.0, "while.1", "while", 0.0),
           ("fusion.1", 0.0, 4.0, "fusion.1", "fusion", 4.0),
           ("fusion.2", 4.0, 2.0, "fusion.2", "fusion", 2.0)]
    assert trace.total(trace.op_intervals(ops)) == 10.0
    assert trace.self_times([op[:5] for op in ops]) == [4.0, 4.0, 2.0]


def test_busy_share_of_the_recorded_slice(recorded):
    busy = trace.busy(recorded)
    lo, hi = trace.window_of(recorded)
    ops = recorded["device"]["/device:TPU:0"]
    brute = raster([(s, s + d) for _, s, d, *_ in ops], lo, hi)
    assert busy["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert busy["busy_s"] == pytest.approx(brute.sum() * 1000.0 / 1e9,
                                           rel=2e-3)
    assert 0.5 < busy["busy_s"] / busy["window_s"] < 1.0
    # the operations' own times add up to the busy time: nothing is nested
    # twice, nothing is lost
    own = sum(op[5] for op in ops if lo <= op[1] and op[1] + op[2] <= hi)
    assert own / 1e9 == pytest.approx(busy["busy_s"], rel=5e-3)


def test_gaps_go_to_the_innermost_host_span(recorded):
    lo, hi = trace.window_of(recorded)
    ops = recorded["device"]["/device:TPU:0"]
    gaps = dict(map(tuple, trace.idle_gaps(recorded)))
    busy = trace.busy(recorded)
    assert sum(gaps.values()) == pytest.approx(
        busy["window_s"] - busy["busy_s"], rel=1e-6)
    idle = ~raster([(s, s + d) for _, s, d, *_ in ops], lo, hi)
    grid = np.arange(lo, hi, 1000.0)

    def inside(name):
        hit = np.zeros(len(grid), bool)
        for n, s, d in recorded["host"]:
            if n == name:
                hit |= (grid >= s) & (grid < s + d)
        return hit

    # the dispatch span lies inside the step span: idle time under it is
    # the dispatch's, the rest of the step's idle time is the step's
    dispatch = idle & inside("bench/decode_dispatch")
    assert gaps["bench/decode_dispatch"] == pytest.approx(
        dispatch.sum() * 1e-6, rel=0.02)
    drain = idle & inside("bench/window_drain") & ~dispatch
    assert gaps["bench/window_drain"] == pytest.approx(
        drain.sum() * 1e-6, rel=0.05, abs=2e-5)
    assert "_none_" in gaps     # before the step span began, nothing claims


def test_kernel_time_and_labels(recorded):
    got = trace.kernel_seconds(
        recorded, r"pallas\(4\)->bf16\[\d+,\d+,\d+\]$")
    ops = recorded["device"]["/device:TPU:0"]
    calls = [op for op in ops if op[3].startswith("closed_call/pallas(4)")]
    assert got["calls"] == len(calls) and len(calls) % 16 == 0
    assert got["seconds"] == pytest.approx(sum(op[2] for op in calls) / 1e9)
    assert trace.kernel_seconds(recorded, "no_such_kernel") is None
    top = trace.time_by_label(recorded, top=3)
    assert top[0][0].startswith("closed_call/pallas(4)")


def test_hlo_text_is_parsed_into_name_opcode_and_label():
    text = ('%attention.103 = (bf16[4,32,2048,128]{3,2,1,0:T(8,128)(2,1)}, '
            'f32[4,32,2048,8]{3,2,1,0}) custom-call(bf16[4,32,2048,128]{3,2,'
            '1,0:T(8,128)(2,1)} %a, bf16[4,32,2048,128]{3,2,1,0} %b, '
            'bf16[4,32,2048,128]{3,2,1,0} %c), '
            'custom_call_target="tpu_custom_call", frontend_attributes={}')
    assert trace.parse_hlo(text) == (
        "attention.103", "custom-call",
        "(bf16[4,32,2048,128],f32[4,32,2048,8])", 3)
    assert trace.label_of(text)[2] == \
        "attention/pallas(3)->(bf16[4,32,2048,128],f32[4,32,2048,8])"
    assert trace.label_of(
        "%fusion.102 = bf16[64,4096]{1,0} fusion(bf16[64,4096]{1,0} %x), "
        "kind=kLoop")[1:] == ("fusion", "fusion.102")
    assert trace.label_of(
        "%all-gather-done.3 = bf16[8,128]{1,0} all-gather-done("
        "(bf16[2,128]{1,0}, bf16[8,128]{1,0}) %s)")[2] == "all-gather-done"
    assert trace.label_of("jit_step") == ("jit_step", "", "jit_step")


def test_collectives_own_time_is_exposed_time():
    ops = [("while.1", 0.0, 100.0, "while.1", "while", 10.0),
           ("fusion.1", 0.0, 60.0, "fusion.1", "fusion", 60.0),
           ("all-gather-done.1", 60.0, 30.0, "all-gather-done",
            "all-gather-done", 30.0)]
    tr = {"device": {"/device:TPU:0": ops}, "host": []}
    out = trace.collective_exposed(tr)
    assert out["exposed_s"] == pytest.approx(30e-9)
    assert out["window_s"] == pytest.approx(100e-9)
