"""Overlap subsystem wiring: the config block (shorthands + legacy
``overlap_comm``), accelerator XLA-flag plumbing (safe no-op on CPU),
profiler-driven auto mode, the ``overlap/*`` gauges, and the
``dstpu-telemetry`` exposed-comm / %-of-peak rendering.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.overlap import auto as overlap_auto
from deepspeed_tpu.runtime.overlap import xla_flags as overlap_flags
from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

pytestmark = pytest.mark.overlap

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class TestOverlapConfig:
    def test_default_disabled(self):
        cfg = DeepSpeedConfig({})
        assert not cfg.overlap.enabled

    def test_auto_shorthand(self):
        cfg = DeepSpeedConfig({"overlap": "auto"})
        assert cfg.overlap.enabled and cfg.overlap.mode == "auto"

    def test_bool_shorthand(self):
        cfg = DeepSpeedConfig({"overlap": True})
        assert cfg.overlap.enabled and cfg.overlap.mode == "manual"

    def test_block_form(self):
        cfg = DeepSpeedConfig({"overlap": {
            "enabled": True, "bucket_bytes": 123, "xla_flags": False}})
        assert cfg.overlap.bucket_bytes == 123
        assert not cfg.overlap.xla_flags

    def test_legacy_overlap_comm_enables(self):
        cfg = DeepSpeedConfig({"zero_optimization": {"stage": 2,
                                                     "overlap_comm": True}})
        assert cfg.overlap.enabled

    def test_explicit_block_wins_over_legacy(self):
        cfg = DeepSpeedConfig({
            "zero_optimization": {"stage": 2, "overlap_comm": True},
            "overlap": {"enabled": False}})
        assert not cfg.overlap.enabled

    def test_bad_mode_rejected(self):
        with pytest.raises(Exception, match="manual|auto"):
            DeepSpeedConfig({"overlap": {"enabled": True, "mode": "turbo"}})


class TestXlaFlagWiring:
    def test_cpu_accelerator_is_noop(self):
        from deepspeed_tpu.accelerator.cpu_accelerator import CPUAccelerator

        before = os.environ.get("LIBTPU_INIT_ARGS")
        assert CPUAccelerator().apply_xla_flags(["--x=1"]) is False
        assert os.environ.get("LIBTPU_INIT_ARGS") == before

    def test_tpu_accelerator_merges_dedup(self, monkeypatch):
        from deepspeed_tpu.accelerator.tpu_accelerator import TPUAccelerator

        monkeypatch.setenv("LIBTPU_INIT_ARGS",
                           "--xla_tpu_enable_latency_hiding_scheduler=false")
        acc = TPUAccelerator()
        assert acc.apply_xla_flags(overlap_flags.overlap_flag_set()) is True
        args = os.environ["LIBTPU_INIT_ARGS"].split()
        # user's explicit setting of the same flag wins (no duplicate)
        lhs = [a for a in args if "latency_hiding_scheduler" in a]
        assert lhs == ["--xla_tpu_enable_latency_hiding_scheduler=false"]
        assert any("async_collective_fusion" in a for a in args)

    def test_configure_noop_on_cpu(self):
        cfg = DeepSpeedConfig({"overlap": True}).overlap
        from deepspeed_tpu.accelerator.cpu_accelerator import CPUAccelerator

        assert overlap_flags.configure_xla_overlap_flags(
            cfg, accelerator=CPUAccelerator()) is False

    def test_configure_respects_disabled(self):
        cfg = DeepSpeedConfig({"overlap": {"enabled": True,
                                           "xla_flags": False}}).overlap
        assert overlap_flags.configure_xla_overlap_flags(cfg) is False

    def test_raw_request_detection(self):
        req = overlap_flags.raw_overlap_flags_requested
        assert req({"overlap": "auto"})
        assert req({"overlap": True})
        assert req({"zero_optimization": {"overlap_comm": True}})
        assert not req({})
        assert not req({"overlap": {"enabled": True, "xla_flags": False}})

    def test_extra_flags_appended(self):
        cfg = DeepSpeedConfig({"overlap": {
            "enabled": True,
            "xla_extra_flags": ["--xla_custom=1"]}}).overlap
        assert "--xla_custom=1" in overlap_flags.overlap_flag_set(cfg)


class TestAutoTune:
    def test_no_trace_size_heuristic(self):
        d = overlap_auto.autotune(None, grad_bytes=64 << 20,
                                  target_buckets=8)
        assert d.deferred and d.exposed_comm_fraction is None
        assert d.bucket_bytes == 8 << 20

    def test_comm_heavy_defers(self):
        report = {"categories": {"compute": 0.7, "communication": 0.3,
                                 "host_transfer": 0.0}}
        d = overlap_auto.autotune(report, grad_bytes=1 << 30)
        assert d.deferred
        assert abs(d.exposed_comm_fraction - 0.3) < 1e-9

    def test_compute_bound_disables_deferred(self):
        report = {"categories": {"compute": 0.99, "communication": 0.001,
                                 "host_transfer": 0.0}}
        d = overlap_auto.autotune(report, grad_bytes=1 << 30)
        assert not d.deferred

    def test_bucket_clamps(self):
        assert overlap_auto.size_targeted_bucket(0, 8) == \
            overlap_auto.AUTO_MIN_BUCKET
        assert overlap_auto.size_targeted_bucket(1e15, 1) == \
            overlap_auto.AUTO_MAX_BUCKET


def _run_engine_with_telemetry(tmp_path, overlap, steps=2, gas=2):
    topo = initialize_mesh(TopologyConfig(), force=True)
    cfg = TransformerConfig.tiny(use_flash=False)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": gas,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 2},
                "bf16": {"enabled": True},
                "overlap": overlap,
                "telemetry": {"enabled": True,
                              "output_dir": str(tmp_path)}},
        topology=topo)
    rng = np.random.default_rng(0)
    batch = {"input_ids": jnp.asarray(
        rng.integers(0, 64, size=(16 * gas, 32)), jnp.int32)}
    for _ in range(steps):
        eng.train_batch(batch)
    return eng


class TestGaugesAndSummary:
    def test_gauges_autotune_and_summary_line(self, tmp_path):
        """One instrumented auto-mode explicit-wire run covers every
        telemetry acceptance surface: the overlap/* gauges in the metrics
        snapshot, the size-heuristic auto-tune (decision + event), and the
        rendered exposed-comm line in the run summary."""
        # one step: the tune fires in the first post-step hook, and no
        # second step means no re-compile against the tuned settings here
        # (that path runs in the slow selection and the bench sweep)
        eng = _run_engine_with_telemetry(
            tmp_path, {"enabled": True, "mode": "auto",
                       "explicit_wire": True}, steps=1)
        names = {m["name"] for m in eng.telemetry.metrics.snapshot()}
        assert "overlap/deferred" in names
        assert "overlap/bucket_bytes" in names
        assert "overlap/bucket_count" in names
        assert "overlap/deferred_steps" in names
        steps = eng.telemetry.metrics.counter("overlap/deferred_steps").value()
        assert steps >= 1
        # auto mode: the size heuristic tuned without a trace
        assert eng.overlap.last_decision is not None
        assert eng.overlap.bucket_bytes >= overlap_auto.AUTO_MIN_BUCKET
        eng.close()
        events = [json.loads(l) for l in
                  open(os.path.join(tmp_path, "events.jsonl"))]
        assert any(e.get("kind") == "overlap_autotune" for e in events)
        from deepspeed_tpu.telemetry.summary import (format_summary,
                                                     summarize_run)

        s = summarize_run(os.path.join(tmp_path, "events.jsonl"))
        assert s["overlap"], "no overlap/* gauges in summary"
        text = format_summary(s)
        assert "exposed comm" in text
        assert "deferred reduction on" in text

    def test_comm_table_pct_peak(self):
        from deepspeed_tpu.telemetry.summary import comm_table

        metrics = [
            {"name": "comm/calls", "labels": {"op": "all_reduce"},
             "value": 4},
            {"name": "comm/bytes", "labels": {"op": "all_reduce"},
             "sum": 4e9, "mean": 1e9, "max": 1e9},
            {"name": "comm/busbw_gbps", "labels": {"op": "all_reduce"},
             "mean": 100.0},
        ]
        rows = comm_table(metrics, device_kind="TPU v5e")
        # v5e ICI peak 200 GB/s → 100 GB/s achieved = 50% of peak
        assert abs(rows[0]["busbw_pct_peak"] - 50.0) < 1e-6
        # unknown device: column degrades to None, table survives
        rows = comm_table(metrics, device_kind=None)
        assert rows[0]["busbw_pct_peak"] is None

    def test_interconnect_peaks_table(self):
        from deepspeed_tpu.profiling.roofline import (interconnect_peak,
                                                      spec_for_kind)

        assert interconnect_peak("TPU v5p") == 600e9
        assert interconnect_peak("TPU v4") == 300e9
        assert spec_for_kind("weird chip").ici_bandwidth == 10e9  # fallback
        assert spec_for_kind("TPU v6 lite").kind == "TPU v6 lite"


class TestTooling:
    def test_overlap_package_lint_clean(self):
        """tools/check_no_bare_print.py covers runtime/overlap/ — the
        new package must not print outside CLI seams."""
        lint = os.path.join(REPO_ROOT, "tools", "check_no_bare_print.py")
        pkg = os.path.join(REPO_ROOT, "deepspeed_tpu", "runtime", "overlap")
        proc = subprocess.run([sys.executable, lint, pkg],
                              capture_output=True, text=True)
        assert proc.returncode == 0, proc.stdout

    def test_overlap_marker_registered(self):
        ini = os.path.join(REPO_ROOT, "tests", "pytest.ini")
        with open(ini) as f:
            content = f.read()
        assert "overlap:" in content
