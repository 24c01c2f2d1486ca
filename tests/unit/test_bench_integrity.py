"""Perf-measurement integrity gates: no physically impossible number may
reach a result line, and every result line names the device it ran on."""
import io
import json
import os
import sys
from contextlib import redirect_stdout

# bench.py lives at the repo root, two levels up from this file
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
import bench  # noqa: E402
import pytest

pytestmark = pytest.mark.core


def _emit(*args, **kw):
    buf = io.StringIO()
    with redirect_stdout(buf):
        bench.emit(*args, **kw)
    return json.loads(buf.getvalue())


class TestEmitGates:
    def test_tflops_above_peak_rejected(self):
        d = _emit("flash_attention_tflops", 3831.6, "TFLOP/s", 19.45,
                  {"seq_len": 2048})
        assert d["value"] == 0.0 and d["vs_baseline"] == 0.0
        assert "rejected" in d["extra"]["error"]
        assert d["extra"]["rejected_value"] == 3831.6

    def test_plausible_tflops_passes(self):
        d = _emit("flash_attention_tflops", 0.5, "TFLOP/s", 0.003,
                  {"seq_len": 256})
        assert d["value"] == 0.5 and "error" not in d["extra"]

    def test_impossible_mfu_rejected(self):
        d = _emit("zero_train_tokens_per_sec_per_chip", 99999.0,
                  "tokens/s/chip", 3.0, {"mfu": 1.5})
        assert d["value"] == 0.0 and d["extra"]["mfu"] == 0.0
        assert d["extra"]["rejected_mfu"] == 1.5
        # the device is named as JAX reports it, and nothing from an
        # earlier run rides along
        dev = bench.jax.devices()
        assert (d["platform"], d["device_kind"], d["device_count"]) == \
            (dev[0].platform, dev[0].device_kind, len(dev))
        assert set(d["extra"]) == {"mfu", "error", "rejected_mfu"}
