"""Twin-Flow fractional optimizer-state offload.

Reference: offload_config.py ``ratio`` + blogs/deepspeed-offloadpp — a
``ratio`` fraction of optimizer-state BYTES lives on the host, the rest in
HBM, split WITHIN each leaf (not all-or-nothing per leaf).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh
from deepspeed_tpu.runtime.zero.twin_flow import TwinFlowState

pytestmark = pytest.mark.core


def _engine(offload=None, stage=2):
    topo = initialize_mesh(TopologyConfig(), force=True)
    cfg = TransformerConfig.tiny(use_flash=False)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    zconf = {"stage": stage}
    if offload:
        zconf["offload_optimizer"] = offload
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": zconf,
                "bf16": {"enabled": True}},
        topology=topo)
    return eng


def _batch(n=16):
    rng = np.random.default_rng(0)
    return {"input_ids": jnp.asarray(rng.integers(0, 64, size=(n, 32)),
                                     jnp.int32)}


class TestTwinFlow:
    def test_ratio_governs_host_byte_fraction(self):
        for ratio in (0.3, 0.7):
            eng = _engine({"device": "cpu", "ratio": ratio})
            dev_b, host_b = eng._twin_flow_bytes()
            frac = host_b / (dev_b + host_b)
            assert abs(frac - ratio) < 0.05, \
                f"ratio={ratio}: host byte fraction {frac:.3f}"

    def test_state_is_split_and_leaf_shapes_partition(self):
        eng = _engine({"device": "cpu", "ratio": 0.5})
        st = eng.state.opt_state
        assert isinstance(st, TwinFlowState)
        # every host leaf complements its dev sibling along ONE split axis
        # (at ratio 0.5 the halves are shape-equal — zero differing axes)
        for d, h in zip(jax.tree.leaves(st.dev), jax.tree.leaves(st.host)):
            if h.ndim == 0:   # scalar placeholder: leaf not split
                continue
            diff = [i for i in range(d.ndim) if d.shape[i] != h.shape[i]]
            assert len(diff) <= 1
            assert h.size > 0 and d.size > 0  # genuinely split, not moved

    @pytest.mark.slow  # 15s: full twin-flow step; test_stage3_composes remains the tier-1 representative
    def test_step_parity_with_no_offload(self):
        batch = _batch()
        tf = _engine({"device": "cpu", "ratio": 0.3})
        base = _engine()
        lt = [float(tf.train_batch(batch)) for _ in range(5)]
        lb = [float(base.train_batch(batch)) for _ in range(5)]
        np.testing.assert_allclose(lt, lb, rtol=1e-4, atol=1e-4)

    def test_stage3_composes(self):
        eng = _engine({"device": "cpu", "ratio": 0.5}, stage=3)
        batch = _batch()
        losses = [float(eng.train_batch(batch)) for _ in range(3)]
        assert losses[-1] < losses[0]

    def test_ratio_one_keeps_whole_tree_offload(self):
        """ratio=1.0 (default) stays on the classic whole-state host path —
        state keeps the inner optax structure."""
        eng = _engine({"device": "cpu", "ratio": 1.0})
        assert not isinstance(eng.state.opt_state, TwinFlowState)
        batch = _batch()
        assert float(eng.train_batch(batch)) > 0

    @pytest.mark.skipif(jax.default_backend() != "tpu",
                        reason="pinned_host memory kinds need the TPU backend")
    def test_host_memory_kind_on_tpu(self):
        eng = _engine({"device": "cpu", "ratio": 0.5})
        kinds = {getattr(l.sharding, "memory_kind", None)
                 for l in jax.tree.leaves(eng.state.opt_state.host)
                 if l.ndim}
        assert kinds == {"pinned_host"}
        kinds_dev = {getattr(l.sharding, "memory_kind", None)
                     for l in jax.tree.leaves(eng.state.opt_state.dev)}
        assert "pinned_host" not in kinds_dev
