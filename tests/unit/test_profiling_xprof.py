"""xprof/Chrome-trace parser: device-time attribution on the checked-in
mini trace fixture (profiling/xprof_parse.py)."""
import gzip
import json
import os
import shutil

import pytest

from deepspeed_tpu.profiling.xprof_parse import (attribute_device_time,
                                                 categorize_op,
                                                 find_trace_files,
                                                 format_device_table)

pytestmark = pytest.mark.profiling

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "mini_xprof.trace.json")


class TestCategorize:
    @pytest.mark.parametrize("name,cat", [
        ("fusion.1", "compute"),
        ("dot.42", "compute"),
        ("all-reduce.7", "communication"),
        ("all-gather.3", "communication"),
        ("reduce-scatter.11", "communication"),
        ("collective-permute.2", "communication"),
        ("all-to-all.5", "communication"),
        ("infeed.0", "host_transfer"),
        ("copy-start.1", "host_transfer"),
    ])
    def test_category(self, name, cat):
        assert categorize_op(name) == cat


class TestFixtureAttribution:
    def test_device_lane_detected(self):
        rep = attribute_device_time(FIXTURE)
        assert rep["device_lanes"] == ["/device:TPU:0"]
        assert rep["files"] == [FIXTURE]

    def test_category_durations_exact(self):
        rep = attribute_device_time(FIXTURE)
        # fixture durations are µs: compute 4000+2000+3000, comm 1500+500,
        # transfer 250; host lanes excluded from the device buckets
        assert rep["categories"]["compute"] == pytest.approx(9000e-6)
        assert rep["categories"]["communication"] == pytest.approx(2000e-6)
        assert rep["categories"]["host_transfer"] == pytest.approx(250e-6)
        assert rep["device_time_s"] == pytest.approx(11250e-6)
        assert rep["host_time_s"] == pytest.approx(10000e-6)

    def test_top_ops_aggregated_and_sorted(self):
        rep = attribute_device_time(FIXTURE)
        top = rep["top_ops"]
        assert top[0]["op"] == "fusion.1"           # 4000+2000 aggregated
        assert top[0]["calls"] == 2
        assert top[0]["total_s"] == pytest.approx(6000e-6)
        comm = [r for r in top if r["category"] == "communication"]
        assert {r["op"] for r in comm} == {"all-reduce.7", "all-gather.3"}
        # percentages are of attributed device time
        assert top[0]["pct"] == pytest.approx(100.0 * 6000 / 11250, abs=0.1)

    def test_format_table_mentions_lane_and_ops(self):
        rep = attribute_device_time(FIXTURE)
        text = "\n".join(format_device_table(rep))
        assert "/device:TPU:0" in text
        assert "all-reduce.7" in text
        assert "communication" in text


class TestDiscoveryAndFormats:
    def test_finds_gz_in_nested_dir(self, tmp_path):
        # xprof layout: <dir>/plugins/profile/<run>/<host>.trace.json.gz
        nested = tmp_path / "plugins" / "profile" / "2026_01_01"
        nested.mkdir(parents=True)
        with open(FIXTURE, "rb") as f:
            raw = f.read()
        with gzip.open(nested / "host0.trace.json.gz", "wb") as f:
            f.write(raw)
        files = find_trace_files(str(tmp_path))
        assert len(files) == 1 and files[0].endswith(".trace.json.gz")
        rep = attribute_device_time(str(tmp_path))
        assert rep["categories"]["communication"] == pytest.approx(2000e-6)

    def test_host_only_trace_falls_back_to_host_lanes(self, tmp_path):
        trace = {"traceEvents": [
            {"ph": "M", "pid": 5, "name": "process_name",
             "args": {"name": "/host:CPU"}},
            {"ph": "X", "pid": 5, "tid": 1, "ts": 0, "dur": 1000,
             "name": "some python work"},
        ]}
        p = tmp_path / "trace.json"
        p.write_text(json.dumps(trace))
        rep = attribute_device_time(str(p))
        assert rep["device_lanes"] == []
        assert rep["categories"]["compute"] == pytest.approx(1000e-6)
        assert rep["top_ops"][0]["op"] == "some python work"

    def test_corrupt_file_skipped(self, tmp_path):
        good = tmp_path / "a.trace.json"
        shutil.copy(FIXTURE, good)
        (tmp_path / "b.trace.json").write_text("{not json")
        rep = attribute_device_time(str(tmp_path))
        assert rep["device_time_s"] == pytest.approx(11250e-6)

    def test_empty_dir(self, tmp_path):
        rep = attribute_device_time(str(tmp_path))
        assert rep["files"] == []
        assert rep["top_ops"] == []
        assert "no duration events" in "\n".join(format_device_table(rep))


class TestOverlappingLanes:
    """A TPU device process has a "Steps", an "XLA Modules" and an "XLA Ops"
    lane over the same time, and a ``while`` on the ops lane covers its
    body's operations: device time is a union, an operation's time its own."""

    def _write(self, tmp_path, lanes=("Steps", "XLA Modules", "XLA Ops")):
        events = [
            {"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 2, "name": "process_name",
             "args": {"name": "/host:CPU"}},
        ]
        for tid, lane in enumerate(lanes, start=10):
            events.append({"ph": "M", "pid": 1, "tid": tid,
                           "name": "thread_name", "args": {"name": lane}})
        tid = {lane: t for t, lane in enumerate(lanes, start=10)}
        X = lambda lane, name, ts, dur: {  # noqa: E731
            "ph": "X", "pid": 1, "tid": tid[lane], "ts": ts, "dur": dur,
            "name": name}
        if "Steps" in tid:
            events.append(X("Steps", "3", 0, 10000))
        if "XLA Modules" in tid:
            events.append(X("XLA Modules", "jit_step_fn(1)", 0, 10000))
        ops = lanes[-1]
        events += [X(ops, "while.1", 0, 6000),          # covers its body
                   X(ops, "fusion.1", 0, 2500), X(ops, "fusion.1", 3000, 2500),
                   X(ops, "all-reduce.7", 6000, 1500),
                   X(ops, "fusion.2", 8000, 2000)]       # idle 7500-8000
        p = tmp_path / "lanes.trace.json"
        p.write_text(json.dumps({"traceEvents": events}))
        return str(p)

    def test_device_time_is_the_ops_lane_union(self, tmp_path):
        rep = attribute_device_time(self._write(tmp_path))
        assert rep["device_time_s"] == pytest.approx(9500e-6)   # not 29500
        assert rep["categories"]["communication"] == pytest.approx(1500e-6)
        assert rep["categories"]["compute"] == pytest.approx(8000e-6)

    def test_an_operation_keeps_its_own_time(self, tmp_path):
        rep = attribute_device_time(self._write(tmp_path))
        by_op = {r["op"]: r for r in rep["top_ops"]}
        assert by_op["fusion.1"]["total_s"] == pytest.approx(5000e-6)
        assert by_op["while.1"]["total_s"] == pytest.approx(1000e-6)
        assert "3" not in by_op and "jit_step_fn(1)" not in by_op
        assert sum(r["total_s"] for r in rep["top_ops"]) \
            == pytest.approx(rep["device_time_s"])

    def test_without_an_ops_lane_lanes_are_united(self, tmp_path):
        rep = attribute_device_time(
            self._write(tmp_path, lanes=("Steps", "TensorCore")))
        assert rep["device_time_s"] == pytest.approx(10000e-6)  # not 19500
