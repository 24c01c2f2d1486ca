"""Flagship model tests: e2e training, TP equivalence, remat."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

pytestmark = pytest.mark.core


def tiny_batch(batch=8, seq=32, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": jnp.asarray(
        rng.integers(0, vocab, size=(batch, seq)), jnp.int32)}


def build(topo_cfg=TopologyConfig(), zero_stage=0, remat=False, micro=1, seed=0):
    topo = initialize_mesh(topo_cfg, force=True)
    model = CausalLM(TransformerConfig.tiny(remat=remat, use_flash=False))
    params = model.init_params(jax.random.PRNGKey(seed))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": micro,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": zero_stage}},
        topology=topo)
    return engine


class TestCausalLM:
    def test_forward_shapes(self):
        model = CausalLM(TransformerConfig.tiny(use_flash=False))
        params = model.init_params(jax.random.PRNGKey(0))
        logits = model(params, tiny_batch()["input_ids"])
        assert logits.shape == (8, 32, 256)

    @pytest.mark.slow

    def test_train_loss_decreases(self):
        engine = build()
        batch = tiny_batch(engine.train_batch_size())
        losses = [float(engine.train_batch(batch)) for _ in range(10)]
        assert losses[-1] < losses[0]
        assert np.isfinite(losses).all()

    @pytest.mark.slow

    def test_tp_matches_dp(self):
        """TP=2 mesh must produce the same loss trajectory as pure DP."""
        e_dp = build(TopologyConfig())
        e_tp = build(TopologyConfig(tensor=2))
        batch = tiny_batch(e_dp.train_batch_size())
        tp_batch = tiny_batch(e_tp.train_batch_size())
        l_dp = [float(e_dp.train_batch(batch)) for _ in range(3)]
        l_tp = [float(e_tp.train_batch(tp_batch)) for _ in range(3)]
        # same data prefix (tp batch is half the rows of dp batch) → compare
        # instead with identical global batch: rebuild dp engine at micro=0.5 not
        # possible; so just check TP runs and loss is finite + decreasing
        assert l_tp[-1] < l_tp[0]

    @pytest.mark.slow

    def test_tp_numerics_match_exactly(self):
        """Same global batch under TP=2 vs DP-only: losses must agree."""
        e_dp = build(TopologyConfig(), micro=2)          # dp=8  → global 16
        e_tp = build(TopologyConfig(tensor=2), micro=4)  # dp=4  → global 16
        batch = tiny_batch(16)
        for _ in range(2):
            l_dp = float(e_dp.train_batch(batch))
            l_tp = float(e_tp.train_batch(batch))
        np.testing.assert_allclose(l_dp, l_tp, rtol=1e-4)

    @pytest.mark.slow

    def test_zero3_with_tp(self):
        engine = build(TopologyConfig(tensor=2), zero_stage=3)
        batch = tiny_batch(engine.train_batch_size())
        l0 = float(engine.train_batch(batch))
        l5 = None
        for _ in range(5):
            l5 = float(engine.train_batch(batch))
        assert l5 < l0

    def test_remat(self):
        engine = build(remat=True)
        batch = tiny_batch(engine.train_batch_size())
        assert np.isfinite(float(engine.train_batch(batch)))

    def test_seq_parallel_runs(self):
        engine = build(TopologyConfig(seq=2))
        batch = tiny_batch(engine.train_batch_size())
        l0 = float(engine.train_batch(batch))
        assert np.isfinite(l0)

    @pytest.mark.parametrize("topo_cfg,batch", [
        (TopologyConfig(tensor=2), 4),     # data=4 x tensor=2
        (TopologyConfig(), 3),             # 3 rows do not divide data=8
    ])
    def test_kernels_on_a_mesh_match_xla(self, monkeypatch, topo_cfg, batch):
        """The Pallas kernels inside a multi-device jitted step: Mosaic
        kernels cannot be partitioned by GSPMD (the chip's compiler refuses
        the whole step), so flash attention and the fused RMSNorm-matmul run
        under ``shard_kernel`` on each device's batch rows / tensor-parallel
        heads.  Loss and grads must equal the kernels-off model's; a batch
        the data axis does not divide is computed whole on every shard."""
        import dataclasses

        from jax.sharding import NamedSharding, PartitionSpec as P

        from deepspeed_tpu.kernels import fused_collective_matmul as fcm
        from deepspeed_tpu.models.transformer import lm_loss, partition_specs

        # resolve_impl("auto") reads jax.default_backend(): steer the fused
        # kernel onto its (interpreted) Pallas path for this test
        monkeypatch.setattr(fcm, "resolve_impl", lambda impl="auto": "pallas")
        topo = initialize_mesh(topo_cfg, force=True)
        on = TransformerConfig.tiny(attn_impl="flash", fused_rmsnorm="on",
                                    remat=True)
        off = dataclasses.replace(on, attn_impl="xla", fused_rmsnorm="off")
        params = jax.device_put(
            CausalLM(on).init_params(jax.random.PRNGKey(0)),
            jax.tree.map(lambda s: NamedSharding(topo.mesh, s),
                         partition_specs(on),
                         is_leaf=lambda x: isinstance(x, P)))
        toks = tiny_batch(batch, seq=128)["input_ids"]
        if batch % topo.dims["data"] == 0:
            toks = jax.device_put(toks, NamedSharding(topo.mesh,
                                                      topo.batch_spec()))
        grad = lambda cfg: jax.jit(jax.value_and_grad(  # noqa: E731
            lambda p: lm_loss(p, {"input_ids": toks}, cfg)))(params)
        (l_on, g_on), (l_off, g_off) = grad(on), grad(off)
        np.testing.assert_allclose(float(l_on), float(l_off), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(g_on), jax.tree.leaves(g_off)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-5)

    def test_num_params_and_flops(self):
        model = CausalLM(TransformerConfig.tiny())
        assert model.num_params() > 0
        assert model.flops_per_token() > 0
