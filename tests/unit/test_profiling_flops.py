"""Flops profiler: profile_fn hardening against jax-version drift, the
start_profile cost-source fix, and engine.train_step_cost (profiling/
flops_profiler/profiler.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.profiling.flops_profiler.profiler import (
    FlopsProfiler, compiled_cost_stats, num_params, profile_fn)
from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

from .simple_model import init_mlp_params, mlp_loss_fn, random_batch

pytestmark = pytest.mark.profiling


def make_engine(gas=1, micro=4, extra=None):
    topo = initialize_mesh(TopologyConfig(), force=True)
    config = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 0},
    }
    if extra:
        config.update(extra)
    params = init_mlp_params(jax.random.PRNGKey(0), hidden=16)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=mlp_loss_fn, model_parameters=params, config=config,
        topology=topo)
    return engine


class TestProfileFn:
    def test_matmul_has_flops_and_all_keys(self):
        stats = profile_fn(lambda a, b: a @ b,
                           jnp.ones((32, 64)), jnp.ones((64, 16)))
        assert stats["flops"] > 0
        for key in ("flops", "bytes_accessed", "transcendentals",
                    "peak_memory_bytes"):
            assert key in stats
            assert isinstance(stats[key], float)

    def test_accepts_shape_structs(self):
        stats = profile_fn(lambda a: jnp.tanh(a).sum(),
                           jax.ShapeDtypeStruct((128,), jnp.float32))
        assert stats["transcendentals"] >= 0


class _FakeCompiled:
    """Stub covering the jax-version drift matrix."""

    def __init__(self, cost, mem="missing"):
        self._cost = cost
        self._mem = mem

    def cost_analysis(self):
        if isinstance(self._cost, Exception):
            raise self._cost
        return self._cost

    def memory_analysis(self):
        if self._mem == "missing":
            raise AttributeError("memory_analysis not provided")
        return self._mem


class _PartialMem:
    temp_size_in_bytes = 100
    # argument/output size attrs deliberately absent


class TestCompiledCostStatsHardening:
    def test_list_returning_cost_analysis(self):
        stats = compiled_cost_stats(_FakeCompiled(
            [{"flops": 42.0, "bytes accessed": 7.0}]))
        assert stats["flops"] == 42.0
        assert stats["bytes_accessed"] == 7.0

    def test_empty_list(self):
        stats = compiled_cost_stats(_FakeCompiled([]))
        assert stats["flops"] == 0.0

    def test_none_cost_analysis(self):
        stats = compiled_cost_stats(_FakeCompiled(None))
        assert stats == {"flops": 0.0, "bytes_accessed": 0.0,
                         "transcendentals": 0.0, "peak_memory_bytes": 0.0}

    def test_raising_cost_analysis(self):
        stats = compiled_cost_stats(_FakeCompiled(RuntimeError("no backend")))
        assert stats["flops"] == 0.0

    def test_missing_memory_analysis_returns_zero_key(self):
        stats = compiled_cost_stats(_FakeCompiled({"flops": 1.0}))
        assert stats["peak_memory_bytes"] == 0.0

    def test_partial_memory_analysis_fields(self):
        stats = compiled_cost_stats(
            _FakeCompiled({"flops": 1.0}, mem=_PartialMem()))
        assert stats["peak_memory_bytes"] == 100.0

    def test_negative_unknown_flops_clamped(self):
        stats = compiled_cost_stats(_FakeCompiled({"flops": -1.0}))
        assert stats["flops"] == 0.0

    def test_garbage_values_tolerated(self):
        stats = compiled_cost_stats(_FakeCompiled({"flops": "nan?"}))
        assert stats["flops"] == 0.0


class TestEngineStepCost:
    def test_none_before_first_step(self):
        eng = make_engine()
        assert eng.train_step_cost() is None

    def test_cost_after_step_and_cached(self):
        eng = make_engine()
        batch = random_batch(eng.train_batch_size())
        eng.train_batch(batch)
        stats = eng.train_step_cost()
        assert stats is not None and stats["flops"] > 0
        assert stats["flops_per_device"] == pytest.approx(
            stats["flops"] / eng.topology.world_size())
        # scan-aware traced count must be part of the reconciliation
        assert stats["flops"] >= stats["flops_traced"]
        assert eng.train_step_cost() is stats     # cached per shape

    def test_gas_scan_multiplied(self):
        """XLA counts a scan body once; the reconciled figure must scale
        with gradient-accumulation trip count."""
        e1 = make_engine(gas=1, micro=4)
        e4 = make_engine(gas=4, micro=4)
        b1 = random_batch(e1.train_batch_size())
        b4 = random_batch(e4.train_batch_size())
        e1.train_batch(b1)
        e4.train_batch(b4)
        f1 = e1.train_step_cost()["flops"]
        f4 = e4.train_step_cost()["flops"]
        assert f4 > 2.5 * f1   # 4 micro steps of the same micro size


class TestFlopsProfilerStartProfile:
    def test_start_profile_reports_real_flops(self):
        """Regression: start_profile used to read a never-populated
        ``_cached_cost`` attribute and silently report 0 FLOPs."""
        eng = make_engine()
        eng.train_batch(random_batch(eng.train_batch_size()))
        prof = FlopsProfiler(ds_engine=eng)
        prof.start_profile()
        assert prof.flops > 0
        assert prof.params == num_params(eng.state.params)
        prof.stop_profile()
        assert prof.latency > 0
        assert prof.get_total_flops(as_string=True).endswith("FLOPS")

    def test_profile_engine_step_flat_batch(self):
        eng = make_engine(gas=2, micro=4)
        flat = random_batch(eng.train_batch_size())
        stats = FlopsProfiler(ds_engine=eng).profile_engine_step(flat)
        assert stats["flops"] > 0
        assert stats["params"] == num_params(eng.state.params)

    def test_print_model_profile_no_engine_data(self, capsys):
        prof = FlopsProfiler()
        msg = prof.print_model_profile(detailed=False)
        assert "flops profiler" in msg
