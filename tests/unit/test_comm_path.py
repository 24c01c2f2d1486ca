"""Explicit-comm train path: ZeRO++ quantized wires + sparse gradients
(reference: runtime/comm/coalesced_collectives.py:31, engine.py:2636).

The zero_quantized_* / sparse_gradients
config keys must actually change the wire, verified both by numerics and by
inspecting the compiled step for int8 collectives.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

pytestmark = pytest.mark.comm


def _engine(stage, zero_extra=None, top_extra=None, seed=0):
    topo = initialize_mesh(TopologyConfig(), force=True)
    cfg = TransformerConfig.tiny(use_flash=False)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    conf = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": stage, **(zero_extra or {})},
        "bf16": {"enabled": True},
    }
    conf.update(top_extra or {})
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=conf, topology=topo)
    return eng


def _batch(n=16, s=32):
    rng = np.random.default_rng(0)
    return {"input_ids": jnp.asarray(rng.integers(0, 64, size=(n, s)), jnp.int32)}


def _losses(eng, batch, steps=5):
    return [float(eng.train_batch(batch)) for _ in range(steps)]


def _step_hlo(eng, batch):
    """Lowered HLO text of the engine's train step."""
    fn = eng._build_train_batch_fn()
    return fn.lower(eng.state, batch).as_text()


class TestQuantizedGradients:
    @pytest.mark.slow
    def test_convergence_close_to_baseline(self):
        batch = _batch()
        base = _losses(_engine(2), batch)
        quant = _losses(_engine(2, {"zero_quantized_gradients": True,
                                    "zeropp_loco": True}), batch)
        assert abs(base[-1] - quant[-1]) < 0.3
        assert quant[-1] < quant[0] - 1.0  # actually trains

    def test_wire_is_int8(self):
        """qgZ must put int8 (packed int4) on the wire; baseline must not."""
        batch = _batch()
        hlo_q = _step_hlo(_engine(2, {"zero_quantized_gradients": True}), batch)
        int8_wire = [l for l in hlo_q.splitlines()
                     if ("all_to_all" in l or "all_gather" in l) and "xi8>" in l]
        assert int8_wire, "no int8 collective found in qgZ step"
        hlo_b = _step_hlo(_engine(2), batch)
        assert not any(("all_to_all" in l or "all_gather" in l) and "xi8>" in l
                       for l in hlo_b.splitlines())

    @pytest.mark.slow  # 10s; LoCo coverage continues in test_comm_path_quant
    def test_loco_error_state_updates(self):
        eng = _engine(2, {"zero_quantized_gradients": True, "zeropp_loco": True})
        batch = _batch()
        assert eng.state.comm_error is not None
        eng.train_batch(batch)
        err_norm = float(sum(jnp.sum(jnp.abs(e))
                             for e in jax.tree.leaves(eng.state.comm_error)))
        assert err_norm > 0.0  # residuals accumulated


class TestQuantizedWeights:
    # threshold 0 so the tiny model's params actually shard (default 100k
    # would leave everything replicated — qwZ has nothing to gather then)
    _ZC = {"zero_quantized_weights": True,
           "stage3_param_persistence_threshold": 0}

    @pytest.mark.slow
    def test_stage3_qwz_trains(self):
        batch = _batch()
        base = _losses(_engine(3, {"stage3_param_persistence_threshold": 0}),
                       batch)
        qwz = _losses(_engine(3, dict(self._ZC)), batch)
        assert abs(base[-1] - qwz[-1]) < 0.3
        assert qwz[-1] < qwz[0] - 1.0

    def test_qwz_allgather_is_int8(self):
        batch = _batch()
        hlo = _step_hlo(_engine(3, dict(self._ZC)), batch)
        assert any("all_gather" in l and "xi8>" in l for l in hlo.splitlines()), \
            "no int8 all_gather found in qwZ step"


class TestSparseGradients:
    @pytest.mark.slow
    def test_matches_dense_exchange(self):
        """Sparse (indices, values) embedding exchange is exact: every
        touched row is covered by the batch's token ids."""
        batch = _batch()
        base = _losses(_engine(2), batch)
        sparse = _losses(_engine(2, top_extra={"sparse_gradients": True}), batch)
        np.testing.assert_allclose(base, sparse, atol=2e-3)

    def test_gather_based_wire(self):
        batch = _batch()
        hlo = _step_hlo(_engine(2, top_extra={"sparse_gradients": True}), batch)
        assert "all_gather" in hlo  # rows+ids allgather replaces dense psum


def _engine_on(stage, zero_extra=None, top_extra=None, **tdims):
    topo = initialize_mesh(TopologyConfig(**tdims), force=True)
    cfg = TransformerConfig.tiny(use_flash=False)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    conf = {"train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": stage, **(zero_extra or {})},
            "bf16": {"enabled": True}}
    conf.update(top_extra or {})
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=conf, topology=topo)
    return eng


class TestExplicitCommModelParallel:
    """ZeRO++ wires under Megatron TP (reference
    docs/_tutorials/zeropp.md:13 — ZeRO++ runs under model parallelism).

    The step is a PARTIAL-manual shard_map: manual over the data axes only,
    tensor/seq stay Auto so XLA keeps inserting the model-parallel
    collectives inside the per-shard compute."""


    def test_qgz_loco_converges_on_dp_tp_mesh(self):
        batch = _batch(n=8)
        eng_b = _engine_on(2, tensor=2)
        eng_q = _engine_on(2, {"zero_quantized_gradients": True,
                               "zeropp_loco": True}, tensor=2)
        lb = [float(eng_b.train_batch(batch)) for _ in range(5)]
        lq = [float(eng_q.train_batch(batch)) for _ in range(5)]
        assert abs(lb[-1] - lq[-1]) < 0.3
        assert lq[-1] < lq[0] - 1.0


    def test_qgz_wire_is_int8_and_tp_allreduce_remains(self):
        batch = _batch(n=8)
        eng = _engine_on(2, {"zero_quantized_gradients": True}, tensor=2)
        fn = eng._build_train_batch_fn()
        low = fn.lower(eng.state, batch)
        # manual wire: int8 all_to_all in the stablehlo (pre-partitioning)
        assert any(("all_to_all" in l or "all_gather" in l) and "xi8>" in l
                   for l in low.as_text().splitlines()), \
            "no int8 collective in qgZ step under TP"
        # TP matmul partials reduce over the Auto tensor axis — GSPMD inserts
        # that all-reduce at partitioning time, so check the COMPILED module
        assert "all-reduce" in low.compile().as_text(), \
            "TP all-reduce missing — tensor axis no longer Auto?"


    def test_stage3_qwz_trains_under_tp(self):
        batch = _batch(n=8)
        eng = _engine_on(3, {"zero_quantized_weights": True,
                             "stage3_param_persistence_threshold": 0},
                         tensor=2)
        losses = [float(eng.train_batch(batch)) for _ in range(3)]
        assert losses[-1] < losses[0]

    @pytest.mark.slow
    def test_qgz_composes_with_sequence_parallelism(self):
        """seq stays Auto: XLA reduces grads over the seq shards inside the
        body at full precision; the quantized wire covers the data hop."""
        batch = _batch(n=8)
        eng_q = _engine_on(2, {"zero_quantized_gradients": True,
                               "zeropp_loco": True}, seq=2)
        eng_b = _engine_on(2, seq=2)
        lq = [float(eng_q.train_batch(batch)) for _ in range(4)]
        lb = [float(eng_b.train_batch(batch)) for _ in range(4)]
        assert abs(lq[-1] - lb[-1]) < 0.3

    def test_stage3_rejects_seq_sharded_params(self):
        eng = _engine_on(3, {"zero_quantized_weights": True,
                             "stage3_param_persistence_threshold": 0}, seq=2)
        with pytest.raises(ValueError, match="data axes only"):
            eng.train_batch(_batch(n=8))

    def test_rejects_pipeline_mesh(self):
        eng = _engine_on(2, {"zero_quantized_gradients": True}, pipe=2)
        with pytest.raises(ValueError, match="pipeline"):
            eng.train_batch(_batch(n=8))

    @pytest.mark.slow
    def test_gas_accumulation_under_explicit_comm(self):
        topo = initialize_mesh(TopologyConfig(), force=True)
        cfg = TransformerConfig.tiny(use_flash=False)
        model = CausalLM(cfg)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=model.init_params(jax.random.PRNGKey(0)),
            config={"train_micro_batch_size_per_gpu": 2,
                    "gradient_accumulation_steps": 2,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 2,
                                          "zero_quantized_gradients": True},
                    "bf16": {"enabled": True}},
            topology=topo)
        losses = _losses(eng, _batch(n=32), steps=3)
        assert losses[-1] < losses[0]


class TestImperativeWireParity:
    """Reference engine.py:2048-2085: the explicit-comm
    wires must also apply on the imperative backward()/step() API —
    local-grad accumulation per data shard, ONE exchange at the boundary."""

    def _run(self, zero_extra, steps=5, gas=2, **tdims):
        topo = initialize_mesh(TopologyConfig(**tdims), force=True)
        cfg = TransformerConfig.tiny(use_flash=False)
        model = CausalLM(cfg)
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=model.init_params(jax.random.PRNGKey(0)),
            config={"train_micro_batch_size_per_gpu": 2,
                    "gradient_accumulation_steps": gas,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 2, **zero_extra},
                    "bf16": {"enabled": True}},
            topology=topo)
        rng = np.random.default_rng(3)
        mbs = [{"input_ids": jnp.asarray(rng.integers(0, 64, size=(8, 32)),
                                         jnp.int32)} for _ in range(gas)]
        losses = []
        for _ in range(steps):
            for mb in mbs:
                loss = eng.backward(mb)
            eng.step()
            losses.append(float(loss))
        return eng, losses

    @pytest.mark.slow
    def test_qgz_loco_converges_and_matches_fused(self):
        # slow: multi-step convergence duplicated by the fused-path
        # convergence test; the fast boundary/wire assertions below keep
        # the imperative path covered in the default selection
        _, lq = self._run({"zero_quantized_gradients": True,
                           "zeropp_loco": True})
        _, lb = self._run({})
        assert lq[-1] < lq[0] - 0.5          # trains
        assert abs(lq[-1] - lb[-1]) < 0.3    # close to the fused wire

    @pytest.mark.slow  # 12s at tier-1 profile; the wire-parity class keeps faster cases in tier-1
    def test_wire_fires_at_boundary_not_backward(self):
        from deepspeed_tpu.runtime.comm_path import (build_explicit_micro_fn,
                                                     build_explicit_step_fn)

        eng, _ = self._run({"zero_quantized_gradients": True}, steps=1)
        batch = _batch(n=8)
        mtxt = build_explicit_micro_fn(eng).lower(eng.state, batch).as_text()
        stxt = build_explicit_step_fn(eng).lower(eng.state).as_text()
        int8 = lambda t: any(("all_to_all" in l or "all_gather" in l)
                             and "xi8>" in l for l in t.splitlines())
        assert not int8(mtxt), "backward() must not exchange grads"
        assert int8(stxt), "step() boundary must carry the int8 wire"

    @pytest.mark.slow
    def test_loco_errors_update_on_imperative_step(self):
        eng, _ = self._run({"zero_quantized_gradients": True,
                            "zeropp_loco": True}, steps=2)
        err_norm = float(sum(jnp.sum(jnp.abs(e))
                             for e in jax.tree.leaves(eng.state.comm_error)))
        assert err_norm > 0.0
