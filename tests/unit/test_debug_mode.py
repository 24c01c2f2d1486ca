"""Determinism / NaN-check debug mode (SURVEY §5's explicit TPU ask)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

pytestmark = pytest.mark.core


def _engine(debug, seed=0):
    topo = initialize_mesh(TopologyConfig(), force=True)
    cfg = TransformerConfig.tiny(use_flash=False)
    model = CausalLM(cfg)
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(jax.random.PRNGKey(seed)),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 1},
                "bf16": {"enabled": True},
                "debug": debug},
        topology=topo)
    return eng


def _batch():
    rng = np.random.default_rng(0)
    return {"input_ids": jnp.asarray(rng.integers(0, 64, size=(16, 16)),
                                     jnp.int32)}


class TestDebugMode:
    @pytest.mark.slow
    def test_deterministic_runs_bitwise_identical(self):
        try:
            losses = []
            for _ in range(2):
                eng = _engine({"deterministic": True})
                losses.append([float(eng.train_batch(_batch()))
                               for _ in range(3)])
            assert losses[0] == losses[1], losses
        finally:
            jax.config.update("jax_default_matmul_precision", None)

    @pytest.mark.slow  # 14s: checked-mode recompiles; test_nan_check_off_tolerates keeps the path in tier-1
    def test_nan_check_raises_on_poisoned_params(self):
        try:
            eng = _engine({"nan_check": True})
            eng.train_batch(_batch())          # healthy step passes
            # poison with the checker off (full_like(nan) itself trips it)
            jax.config.update("jax_debug_nans", False)
            poisoned = jax.tree_util.tree_map_with_path(
                lambda p, x: jnp.full_like(x, jnp.nan)
                if "embed" in str(p) else x, eng.state.params)
            jax.block_until_ready(poisoned)
            jax.config.update("jax_debug_nans", True)
            eng.state = eng.state.replace(params=poisoned)
            with pytest.raises((RuntimeError, FloatingPointError)):
                eng.train_batch(_batch())
        finally:
            jax.config.update("jax_debug_nans", False)

    @pytest.mark.slow
    def test_nan_check_off_tolerates(self):
        """Without the flag the engine's NaN-safe grad zeroing keeps going
        (the production behavior the debug mode exists to override) — the
        SAME poisoned state that raises under nan_check trains on here."""
        eng = _engine({})
        eng.train_batch(_batch())
        poisoned = jax.tree_util.tree_map_with_path(
            lambda p, x: jnp.full_like(x, jnp.nan)
            if "embed" in str(p) else x, eng.state.params)
        eng.state = eng.state.replace(params=poisoned)
        eng.train_batch(_batch())   # no raise: tolerated by design
        assert not getattr(eng.config, "debug_nan_check")

    @pytest.mark.slow
    def test_xprof_trace_step(self, tmp_path):
        """comms_logger.xprof_step writes a device trace for that step
        (device-time attribution; reference CUDA-event comms timing)."""
        import glob
        import os

        import deepspeed_tpu
        from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
        from deepspeed_tpu.runtime.topology import (
            TopologyConfig,
            initialize_mesh,
        )

        topo = initialize_mesh(TopologyConfig(), force=True)
        cfg = TransformerConfig.tiny(use_flash=False)
        model = CausalLM(cfg)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=model.init_params(jax.random.PRNGKey(0)),
            config={"train_micro_batch_size_per_gpu": 2,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 1},
                    "bf16": {"enabled": True},
                    "comms_logger": {"enabled": True, "xprof_step": 1,
                                     "xprof_dir": str(tmp_path)}},
            topology=topo)
        for _ in range(3):
            eng.train_batch(_batch())
        assert glob.glob(os.path.join(str(tmp_path), "**", "*"),
                         recursive=True), "no xprof trace written"

    def test_unknown_debug_key_raises(self):
        with pytest.raises(ValueError, match="unknown debug config"):
            _engine({"determinstic": True})   # the typo a user would make
