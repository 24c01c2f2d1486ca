"""Cross-run regression tracking: metric extraction from bench JSON and
telemetry run dirs, the median-baseline verdict logic, and the
``dstpu-telemetry --compare`` CLI (exit code 3 flags a regression)."""
import json
import os

import pytest

from deepspeed_tpu.telemetry.regression import (compare_runs,
                                                current_metrics_from_path,
                                                extract_bench_metrics,
                                                extract_run_metrics,
                                                format_compare, load_history)

pytestmark = pytest.mark.telemetry

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def bench_doc(step_time=1.0, mfu=0.4, tokens=1000.0, exposed=None):
    extra = {"mfu": mfu, "step_time_s": step_time}
    if exposed is not None:
        extra["exposed_comm_fraction"] = exposed
    return {"n": 1, "cmd": "bench", "rc": 0,
            "parsed": {"metric": "zero_train_tokens_per_sec_per_chip",
                       "value": tokens, "unit": "tokens/s/chip",
                       "extra": extra}}


def write_history(d, step_times, **kw):
    for n, st in enumerate(step_times, start=1):
        with open(os.path.join(d, f"BENCH_r{n:02d}.json"), "w") as f:
            json.dump(bench_doc(step_time=st, tokens=1000.0 / st, **kw), f)


class TestExtraction:
    def test_bench_json(self):
        m = extract_bench_metrics(bench_doc(step_time=2.0, mfu=0.3,
                                            exposed=0.12))
        assert m == {"step_time_s": 2.0, "mfu": 0.3,
                     "tokens_per_sec_per_chip": 1000.0,
                     "exposed_comm_fraction": 0.12}

    def test_parsed_null_extracts_empty(self):
        # the real archive has TPU-unavailable runs with parsed: null
        assert extract_bench_metrics({"n": 1, "parsed": None, "rc": 1}) == {}

    def test_run_dir_summary(self):
        summary = {
            "step_breakdown": [
                {"phase": "engine/dispatch", "count": 4, "mean_s": 0.4},
                {"phase": "engine/train_batch", "count": 4, "mean_s": 0.5},
            ],
            "profile": {"roofline_gauges": {"mfu": 0.37}},
            "overlap": {"exposed_comm_fraction": 0.08},
        }
        m = extract_run_metrics(summary)
        assert m == {"step_time_s": 0.5, "mfu": 0.37,
                     "exposed_comm_fraction": 0.08}

    def test_current_from_telemetry_dir(self, tmp_path):
        run = tmp_path / "run"
        run.mkdir()
        events = [{"ts": 1.0, "kind": "run_start"}]
        for i in range(3):
            events.append({"ts": 2.0 + i, "kind": "span",
                           "name": "engine/train_batch",
                           "start_s": float(i), "dur_s": 0.25, "depth": 0,
                           "parent": None, "tid": 1})
        with open(run / "events.jsonl", "w") as f:
            for e in events:
                f.write(json.dumps(e) + "\n")
        m = current_metrics_from_path(str(run))
        assert m["step_time_s"] == pytest.approx(0.25)

    def test_real_repo_history_loads(self):
        """Whatever BENCH_r*.json archive sits at the repo root must parse.
        (The pre-growth-phase archive was deleted in PR 21 — CPU runs under
        a device metric's name — so today this is the empty-history path;
        the driver's ledger will repopulate it.)"""
        entries = load_history(REPO_ROOT)
        assert isinstance(entries, list)
        assert all(isinstance(e["metrics"], dict) for e in entries)


class TestVerdicts:
    def test_regression_flagged_in_bad_direction(self, tmp_path):
        write_history(tmp_path, [1.0, 1.1, 0.9])
        history = load_history(str(tmp_path))
        report = compare_runs({"step_time_s": 2.0, "mfu": 0.2}, history,
                              threshold=0.15)
        assert report["verdict"] == "regression"
        assert set(report["regressions"]) == {"step_time_s", "mfu"}
        assert report["metrics"]["step_time_s"]["baseline"] == 1.0
        assert report["metrics"]["step_time_s"]["delta"] == pytest.approx(1.0)

    def test_improvement_is_not_a_regression(self, tmp_path):
        write_history(tmp_path, [1.0, 1.0, 1.0])
        history = load_history(str(tmp_path))
        report = compare_runs(
            {"step_time_s": 0.5, "tokens_per_sec_per_chip": 5000.0}, history)
        assert report["verdict"] == "ok"
        assert report["regressions"] == []

    def test_within_threshold_ok(self, tmp_path):
        write_history(tmp_path, [1.0, 1.0, 1.0])
        report = compare_runs({"step_time_s": 1.1},
                              load_history(str(tmp_path)), threshold=0.15)
        assert report["verdict"] == "ok"

    def test_no_history_verdict(self, tmp_path):
        report = compare_runs({"step_time_s": 1.0},
                              load_history(str(tmp_path)))
        assert report["verdict"] == "no-history"

    def test_unusable_history_skipped_and_counted(self, tmp_path):
        write_history(tmp_path, [1.0, 1.0])
        with open(tmp_path / "BENCH_r09.json", "w") as f:
            json.dump({"n": 9, "parsed": None}, f)
        report = compare_runs({"step_time_s": 1.0},
                              load_history(str(tmp_path)))
        assert report["history_total"] == 3
        assert report["history_usable"] == 2

    def test_zero_baseline_still_flags_regression(self, tmp_path):
        """Fully-overlapped history (exposed_comm_fraction 0.0 everywhere)
        must still flag a run that exposes comm — a 0 baseline cannot be a
        free pass for lower-is-better metrics."""
        write_history(tmp_path, [1.0, 1.0], exposed=0.0)
        report = compare_runs(
            {"exposed_comm_fraction": 0.5, "step_time_s": 1.0},
            load_history(str(tmp_path)), threshold=0.15)
        assert report["verdict"] == "regression"
        assert report["regressions"] == ["exposed_comm_fraction"]
        # the infinite off-zero delta must serialize as null, not the
        # non-standard JSON token Infinity (jq/JSON.parse would reject it)
        assert report["metrics"]["exposed_comm_fraction"]["delta"] is None
        json.loads(json.dumps(report, allow_nan=False))
        assert "inf%" in format_compare(report)

    def test_median_baseline_shrugs_off_one_outlier(self, tmp_path):
        """One broken historical run (10x step time) must not move the
        bar: the median stays at the healthy value and a healthy current
        run passes."""
        write_history(tmp_path, [1.0, 1.0, 1.0, 10.0])
        report = compare_runs({"step_time_s": 1.05},
                              load_history(str(tmp_path)), threshold=0.15)
        assert report["metrics"]["step_time_s"]["baseline"] == 1.0
        assert report["verdict"] == "ok"

    def test_format_compare_readable(self, tmp_path):
        write_history(tmp_path, [1.0])
        report = compare_runs({"step_time_s": 3.0},
                              load_history(str(tmp_path)))
        text = format_compare(report, history_dir=str(tmp_path))
        assert "REGRESSED" in text and "verdict: REGRESSION" in text


class TestCompareCLI:
    """In-process through summary.main (a subprocess per case would cost a
    jax import each; the real executable is smoke-driven by
    tools/check_telemetry_cli.py / test_telemetry_live_cli.py)."""

    @staticmethod
    def run_main(capsys, *args):
        from deepspeed_tpu.telemetry.summary import main

        rc = main(list(args))
        return rc, capsys.readouterr().out

    def test_cli_flags_synthetic_regression(self, tmp_path, capsys):
        """Acceptance: --compare reports a regression verdict against
        BENCH_r*.json history, with exit code 3 for CI."""
        hist = tmp_path / "hist"
        hist.mkdir()
        write_history(hist, [0.5, 0.55, 0.45])
        cur = tmp_path / "current.json"
        with open(cur, "w") as f:
            json.dump(bench_doc(step_time=2.0, tokens=250.0), f)
        rc, out = self.run_main(capsys, str(cur), "--compare", str(hist))
        assert rc == 3, out
        assert "verdict: REGRESSION" in out
        assert "step_time_s" in out

    def test_cli_clean_run_exits_zero(self, tmp_path, capsys):
        hist = tmp_path / "hist"
        hist.mkdir()
        write_history(hist, [0.5, 0.55, 0.45])
        cur = tmp_path / "current.json"
        with open(cur, "w") as f:
            json.dump(bench_doc(step_time=0.5, tokens=2000.0), f)
        rc, out = self.run_main(capsys, str(cur), "--compare", str(hist))
        assert rc == 0, out
        assert "verdict: OK" in out

    def test_cli_json_report(self, tmp_path, capsys):
        hist = tmp_path / "hist"
        hist.mkdir()
        write_history(hist, [0.5])
        cur = tmp_path / "current.json"
        with open(cur, "w") as f:
            json.dump(bench_doc(step_time=0.5, tokens=2000.0), f)
        rc, out = self.run_main(capsys, str(cur), "--compare", str(hist),
                                "--json")
        assert rc == 0
        report = json.loads(out)
        assert report["verdict"] == "ok"
        assert report["metrics"]["step_time_s"]["current"] == 0.5

    def test_cli_nothing_comparable_exits_two(self, tmp_path, capsys):
        empty = tmp_path / "empty.json"
        with open(empty, "w") as f:
            json.dump({"parsed": None}, f)
        rc, out = self.run_main(capsys, str(empty), "--compare",
                                str(tmp_path))
        assert rc == 2
        assert "no comparable metrics" in out

    def test_cli_missing_history_exits_two(self, tmp_path, capsys):
        """A mistyped HISTORY_DIR must not read as a green gate: verdict
        no-history is exit 2, never 0."""
        cur = tmp_path / "current.json"
        with open(cur, "w") as f:
            json.dump(bench_doc(step_time=0.5), f)
        rc, out = self.run_main(capsys, str(cur), "--compare",
                                str(tmp_path / "nope"))
        assert rc == 2
        assert "verdict: NO-HISTORY" in out
