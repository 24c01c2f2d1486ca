"""Roofline model: device-spec lookup, report math, gauge publishing
(profiling/roofline.py)."""
import pytest

from deepspeed_tpu.profiling.roofline import (CPU_FALLBACK, DeviceSpec,
                                              device_spec,
                                              format_roofline_line,
                                              peak_flops_per_chip,
                                              publish_gauges, roofline_report,
                                              spec_for_kind)
from deepspeed_tpu.telemetry.metrics import MetricsRegistry

pytestmark = pytest.mark.profiling


class FakeDevice:
    def __init__(self, kind, platform="tpu"):
        self.device_kind = kind
        self.platform = platform


class TestDeviceSpec:
    @pytest.mark.parametrize("kind,peak", [
        ("TPU v4", 275e12),
        ("TPU v5 lite", 197e12),
        ("TPU v5p", 459e12),
        ("TPU v6 lite", 918e12),
    ])
    def test_known_kinds(self, kind, peak):
        assert device_spec(FakeDevice(kind)).peak_flops == peak

    def test_cpu_fallback(self):
        spec = device_spec(FakeDevice("Zen9", platform="cpu"))
        assert spec.peak_flops == CPU_FALLBACK.peak_flops
        # labelled as what it is wherever it is printed
        assert spec.kind == "Zen9 (cpu fallback peaks)"

    @pytest.mark.parametrize("resolve", [
        lambda: device_spec(FakeDevice("TPU v99")),
        lambda: device_spec(FakeDevice("v99 lite")),   # tpu by platform
        lambda: spec_for_kind("TPU v99"),
    ])
    def test_unknown_tpu_kind_raises(self, resolve):
        """A device that is not in the table is an error, not a default —
        neither "assume v5e" nor the CPU numbers under its name."""
        with pytest.raises(KeyError, match="no peaks for TPU device kind"):
            resolve()

    def test_local_device_resolves(self):
        # conftest pins the cpu backend — must hit the CPU fallback
        assert peak_flops_per_chip() == CPU_FALLBACK.peak_flops

    def test_ridge_point(self):
        spec = DeviceSpec("x", peak_flops=100e12, hbm_bandwidth=1e12)
        assert spec.ridge_intensity == pytest.approx(100.0)


class TestReport:
    SPEC = DeviceSpec("test-chip", peak_flops=100e12, hbm_bandwidth=1e12)

    def test_mfu_and_bandwidth(self):
        # 1e12 flops in 0.1 s on a 100 TF chip = 10 TF/s = 10% MFU
        rep = roofline_report(1e12, 25e9, 0.1, spec=self.SPEC)
        assert rep["achieved_tflops"] == pytest.approx(10.0)
        assert rep["mfu"] == pytest.approx(0.1)
        assert rep["hbm_gbps"] == pytest.approx(250.0)
        assert rep["hbm_utilization"] == pytest.approx(0.25)
        assert rep["arithmetic_intensity"] == pytest.approx(40.0)

    def test_bound_classification(self):
        # ridge = 100 flops/B: AI 40 → memory-bound; AI 200 → compute-bound
        assert roofline_report(1e12, 25e9, 0.1,
                               spec=self.SPEC)["bound"] == "memory"
        assert roofline_report(1e12, 5e9, 0.1,
                               spec=self.SPEC)["bound"] == "compute"

    def test_multi_device_split(self):
        rep1 = roofline_report(8e12, 8e9, 0.1, n_devices=1, spec=self.SPEC)
        rep8 = roofline_report(8e12, 8e9, 0.1, n_devices=8, spec=self.SPEC)
        assert rep8["achieved_tflops"] == pytest.approx(
            rep1["achieved_tflops"] / 8)

    def test_format_line(self):
        line = format_roofline_line(roofline_report(1e12, 25e9, 0.1,
                                                    spec=self.SPEC))
        assert "MFU 10.0%" in line
        assert "test-chip" in line
        assert "memory-bound" in line


class TestGauges:
    def test_publish(self):
        reg = MetricsRegistry()
        rep = roofline_report(1e12, 25e9, 0.1, spec=TestReport.SPEC)
        publish_gauges(reg, rep)
        assert reg.gauge("roofline/mfu").value(
            device="test-chip") == pytest.approx(0.1)
        assert reg.gauge("roofline/achieved_tflops").value(
            device="test-chip") == pytest.approx(10.0)
        names = reg.names()
        assert "roofline/hbm_utilization" in names
        assert "roofline/peak_tflops" in names
