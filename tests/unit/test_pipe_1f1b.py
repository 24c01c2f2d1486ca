"""1F1B pipeline schedule (reference: runtime/pipe/schedule.py:189
``TrainSchedule``) — grads from the interleaved fwd/bwd loop must match
autodiff through the GPipe scan exactly, with O(pp) in-flight memory.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.runtime.pipe import PipelinedCausalLM
from deepspeed_tpu.runtime.pipe.engine import (
    pipeline_lm_loss,
    pipeline_lm_loss_1f1b,
)
from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

pytestmark = pytest.mark.core


def _setup(pp, tp=1, seq=16, num_layers=4, remat=False):
    topo = initialize_mesh(TopologyConfig(pipe=pp, tensor=tp), force=True)
    cfg = dataclasses.replace(TransformerConfig.tiny(use_flash=False),
                              num_layers=num_layers, remat=remat)
    model = PipelinedCausalLM(cfg, topology=topo)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    dp = 8 // (pp * tp)
    tokens = {"input_ids": jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(8 * dp, seq)), jnp.int32)}
    return topo, cfg, params, tokens


class TestOneFOneB:
    @pytest.mark.parametrize("pp,tp", [
        # 31s at tier-1 profile; the 1f1b subsystem keeps
        # test_interleaved_v2_loss_smoke + test_pipe_general as its
        # in-budget CPU-sim representatives
        pytest.param(2, 1, marks=pytest.mark.slow),
        pytest.param(4, 1, marks=pytest.mark.slow),
        pytest.param(2, 2, marks=pytest.mark.slow),
    ])
    def test_grads_match_gpipe_autodiff(self, pp, tp):
        """The hand-scheduled fwd/bwd loop IS the derivative: its grads must
        equal jax.grad through the GPipe scan leaf-for-leaf."""
        topo, cfg, params, batch = _setup(pp, tp=tp)
        num_micro = 4
        rng = jax.random.PRNGKey(0)

        loss_1f1b, grads_1f1b = pipeline_lm_loss_1f1b(
            params, batch, cfg, topo, rng, num_micro)
        loss_gpipe, grads_gpipe = jax.value_and_grad(
            lambda p: pipeline_lm_loss(p, batch, cfg, topo, rng, num_micro))(
                params)

        np.testing.assert_allclose(float(loss_1f1b), float(loss_gpipe),
                                   rtol=1e-5)
        flat1, _ = jax.tree.flatten_with_path(grads_1f1b)
        flat2, _ = jax.tree.flatten_with_path(grads_gpipe)
        for (path, g1), (_, g2) in zip(flat1, flat2):
            np.testing.assert_allclose(
                np.asarray(g1), np.asarray(g2), atol=1e-5, rtol=1e-4,
                err_msg=f"grad mismatch at {jax.tree_util.keystr(path)}")


    def test_memory_beats_gpipe_without_remat(self):
        """Compiled peak temp of the 1F1B step
        stays below GPipe-without-remat at equal microbatches — the input
        ring is O(pp) while the autodiff scan saves O(num_micro) residuals."""
        pp, num_micro = 2, 8
        topo, cfg, params, batch = _setup(pp, seq=32, remat=False)
        rng = jax.random.PRNGKey(0)

        def temp_bytes(fn):
            lowered = jax.jit(fn).lower(params)
            mem = lowered.compile().memory_analysis()
            if mem is None:
                pytest.skip("backend exposes no memory_analysis")
            return mem.temp_size_in_bytes

        t_1f1b = temp_bytes(lambda p: pipeline_lm_loss_1f1b(
            p, batch, cfg, topo, rng, num_micro)[1])
        t_gpipe = temp_bytes(lambda p: jax.grad(
            lambda q: pipeline_lm_loss(q, batch, cfg, topo, rng, num_micro))(p))
        assert t_1f1b < t_gpipe, (t_1f1b, t_gpipe)

    @pytest.mark.parametrize("V", [
        pytest.param(2, marks=pytest.mark.slow),
        pytest.param(4, marks=pytest.mark.slow),
    ])
    def test_interleaved_virtual_stages_grads_match(self, V):
        """Interleaved schedule (V chunks/rank on the same physical ring)
        must produce the SAME grads as plain 1F1B/GPipe."""
        pp = 2
        topo, cfg, params, batch = _setup(pp, num_layers=2 * V)
        num_micro = 4
        rng = jax.random.PRNGKey(0)
        loss_v, grads_v = pipeline_lm_loss_1f1b(
            params, batch, cfg, topo, rng, num_micro, virtual_stages=V)
        loss_g, grads_g = jax.value_and_grad(
            lambda p: pipeline_lm_loss(p, batch, cfg, topo, rng, num_micro))(
                params)
        np.testing.assert_allclose(float(loss_v), float(loss_g), rtol=1e-5)
        flat1, _ = jax.tree.flatten_with_path(grads_v)
        flat2, _ = jax.tree.flatten_with_path(grads_g)
        for (path, g1), (_, g2) in zip(flat1, flat2):
            np.testing.assert_allclose(
                np.asarray(g1), np.asarray(g2), atol=1e-5, rtol=1e-4,
                err_msg=f"grad mismatch at {jax.tree_util.keystr(path)}")

    def test_interleaved_v2_loss_smoke(self):
        """Fast default-suite guard on the V>1 path (the exhaustive grads
        and engine-parity checks are slow-marked): one interleaved V=2
        loss evaluation must match plain 1F1B exactly."""
        pp = 2
        topo, cfg, params, batch = _setup(pp, num_layers=4)
        rng = jax.random.PRNGKey(0)
        loss_v, _ = pipeline_lm_loss_1f1b(
            params, batch, cfg, topo, rng, 4, virtual_stages=2)
        loss_1, _ = pipeline_lm_loss_1f1b(
            params, batch, cfg, topo, rng, 4)
        np.testing.assert_allclose(float(loss_v), float(loss_1), rtol=1e-5)

    def test_interleaved_bubble_shrinks(self):
        """Schedule arithmetic under the phase-split scan: warmup/drain
        ticks cost half a tick (F-only / B-only bodies), so total stage-time
        is (M·V + pp - 1)/V and idle (bubble) stage-time is (pp-1)/V —
        strictly decreasing in V, the textbook interleaving win."""
        pp, M = 4, 8
        bubbles = []
        for V in (1, 2, 4):
            vpp = V * pp
            off_max = M - 1 if V == 1 else (M // pp - 1) * vpp + pp - 1
            warm = drain = vpp - 1            # half-cost ticks
            steady = off_max + 1              # full-cost ticks
            total_stage_time = (warm / 2 + steady + drain / 2) / V
            bubbles.append(total_stage_time - M)
        np.testing.assert_allclose(
            bubbles, [(pp - 1) / V for V in (1, 2, 4)], rtol=1e-9)
        assert bubbles == sorted(bubbles, reverse=True)

    def test_bubble_tick_count(self):
        """Round-5 phase-split schedule: the tick loop is THREE scans —
        warmup (pp-1 F-only ticks: no rank has a valid backward before
        t = pp-1), steady (M full F+B ticks), drain (pp-1 B-only ticks) —
        totalling the same T = M + 2(pp-1) tick positions, but the fill and
        drain ticks cost half a tick each, so the bubble is (pp-1)
        full-tick equivalents out of M + pp - 1 (the textbook 1F1B bubble)
        instead of 2(pp-1).  Asserted from the traced jaxpr."""
        from deepspeed_tpu.utils.jaxpr_utils import scan_lengths

        pp, num_micro = 4, 8
        topo, cfg, params, batch = _setup(pp)
        rng = jax.random.PRNGKey(0)
        lengths = scan_lengths(lambda p: pipeline_lm_loss_1f1b(
            p, batch, cfg, topo, rng, num_micro)[0], params)
        warm = drain = pp - 1
        steady = num_micro
        for want, what in ((warm, "warmup/drain"), (steady, "steady")):
            assert want in lengths, \
                f"no scan of length {want} ({what}) in 1F1B jaxpr; " \
                f"scans={lengths}"
        # the old single full-length scan must be gone
        assert (num_micro + 2 * pp - 2) not in lengths, lengths


class TestEngine1F1B:
    def _build(self, schedule, pp=2, gas=4):
        topo = initialize_mesh(TopologyConfig(pipe=pp), force=True)
        cfg = TransformerConfig.tiny(use_flash=False)
        model = PipelinedCausalLM(cfg, topology=topo)
        params = model.init_params(jax.random.PRNGKey(0))
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config={"train_micro_batch_size_per_gpu": 2,
                    "gradient_accumulation_steps": gas,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "pipeline": {"schedule": schedule},
                    "zero_optimization": {"stage": 1}},
            topology=topo)
        return engine

    @pytest.mark.slow
    def test_1f1b_trains_and_matches_gpipe(self):
        rng = np.random.default_rng(0)
        batch = {"input_ids": jnp.asarray(
            rng.integers(0, 256, size=(32, 16)), jnp.int32)}
        e1 = self._build("1f1b")
        e2 = self._build("gpipe")
        l1 = [float(e1.train_batch(batch)) for _ in range(4)]
        l2 = [float(e2.train_batch(batch)) for _ in range(4)]
        np.testing.assert_allclose(l1, l2, rtol=2e-4)
        assert l1[-1] < l1[0]


class TestPrepermutedVirtualStages:
    """The engine keeps layers in interleave_order layout (no per-step
    cross-pipe permute); checkpoints stay canonical."""

    def _engine(self, V, pp=2):
        topo = initialize_mesh(TopologyConfig(pipe=pp), force=True)
        cfg = dataclasses.replace(TransformerConfig.tiny(use_flash=False),
                                  num_layers=4)
        model = PipelinedCausalLM(cfg, topology=topo)
        params = model.init_params(jax.random.PRNGKey(0))
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config={"train_micro_batch_size_per_gpu": 2,
                    "gradient_accumulation_steps": 4,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "pipeline": {"schedule": "1f1b", "virtual_stages": V},
                    "zero_optimization": {"stage": 0}},
            topology=topo)
        return engine

    @pytest.mark.slow
    def test_engine_loss_parity_v2_vs_v1(self):
        rng = np.random.default_rng(0)
        batch = {"input_ids": jnp.asarray(
            rng.integers(0, 256, size=(16, 16)), jnp.int32)}
        e1, e2 = self._engine(1), self._engine(2)
        l1 = [float(e1.train_batch(batch)) for _ in range(3)]
        l2 = [float(e2.train_batch(batch)) for _ in range(3)]
        np.testing.assert_allclose(l1, l2, rtol=2e-4)

    @pytest.mark.slow
    def test_checkpoint_is_canonical_across_layouts(self):
        import tempfile

        rng = np.random.default_rng(1)
        batch = {"input_ids": jnp.asarray(
            rng.integers(0, 256, size=(16, 16)), jnp.int32)}
        e2 = self._engine(2)
        assert e2._vs_order is not None   # state IS interleaved
        for _ in range(2):
            e2.train_batch(batch)
        d = tempfile.mkdtemp()
        e2.save_checkpoint(d, tag="v")
        ref = float(e2.eval_batch(batch))
        # reload into a V=1 engine: canonical order must make this exact
        e1 = self._engine(1)
        e1.load_checkpoint(d, tag="v")
        np.testing.assert_allclose(float(e1.eval_batch(batch)), ref,
                                   rtol=1e-5, atol=1e-5)
        # and back into a V=2 engine (re-permute on load)
        e2b = self._engine(2)
        e2b.load_checkpoint(d, tag="v")
        np.testing.assert_allclose(float(e2b.eval_batch(batch)), ref,
                                   rtol=1e-5, atol=1e-5)
        e2b.train_batch(batch)   # resumed interleaved state still trains
