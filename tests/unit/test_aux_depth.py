"""Aux-subsystem depth: DataAnalyzer,
autotuner experiment scheduler/persistence, compression scheduler +
head/channel pruning + layer reduction, flops per-module tree."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.core


class TestDataAnalyzer:
    def _dataset(self, n=40):
        rng = np.random.default_rng(0)
        return [{"input_ids": rng.integers(0, 32, size=rng.integers(4, 20))}
                for _ in range(n)]

    def test_map_reduce_single_worker(self, tmp_path):
        from deepspeed_tpu.runtime.data_pipeline.data_analyzer import (
            CurriculumMetricIndex,
            DataAnalyzer,
            metric_seqlen,
        )

        ds = self._dataset()
        an = DataAnalyzer(ds, str(tmp_path), ["seqlen"], [metric_seqlen],
                          num_buckets=4)
        an.run_map()
        outs = an.run_reduce()
        assert "seqlen" in outs
        idx = CurriculumMetricIndex(str(tmp_path), "seqlen")
        # every sample is in exactly one bucket
        assert sum(len(b) for b in idx.buckets) == len(ds)
        # difficulty admission is monotone
        easy = idx.samples_up_to_difficulty(8)
        hard = idx.samples_up_to_difficulty(100)
        assert len(easy) < len(hard) == len(ds)
        for i in easy:
            assert len(ds[i]["input_ids"]) <= 8

    def test_distributed_workers_match_single(self, tmp_path):
        from deepspeed_tpu.runtime.data_pipeline.data_analyzer import (
            DataAnalyzer,
            DistributedDataAnalyzer,
            metric_seqlen,
        )

        ds = self._dataset()
        single = tmp_path / "single"
        multi = tmp_path / "multi"
        a1 = DataAnalyzer(ds, str(single), ["seqlen"], [metric_seqlen])
        a1.run_map()
        a1.run_reduce()
        a2 = DistributedDataAnalyzer(ds, str(multi), ["seqlen"],
                                     [metric_seqlen], num_workers=3)
        a2.run_map_reduce()
        v1 = np.load(single / "seqlen_sample_to_metric.npy")
        v2 = np.load(multi / "seqlen_sample_to_metric.npy")
        np.testing.assert_array_equal(v1, v2)

    def test_sampler_from_analysis_end_to_end(self, tmp_path):
        """The full offline-curriculum pipeline: analyze → reduce → sample
        by scheduled difficulty (reference DataAnalyzer + DeepSpeedDataSampler)."""
        from deepspeed_tpu.runtime.data_pipeline.curriculum_scheduler import (
            CurriculumScheduler,
        )
        from deepspeed_tpu.runtime.data_pipeline.data_analyzer import (
            DataAnalyzer,
            metric_seqlen,
        )
        from deepspeed_tpu.runtime.data_pipeline.data_sampler import (
            DeepSpeedDataSampler,
        )

        ds = self._dataset()
        an = DataAnalyzer(ds, str(tmp_path), ["seqlen"], [metric_seqlen])
        an.run_map()
        an.run_reduce()
        sched = CurriculumScheduler({
            "curriculum_type": "seqlen", "min_difficulty": 6,
            "max_difficulty": 20, "schedule_type": "fixed_linear",
            "schedule_config": {"total_curriculum_step": 10,
                                "difficulty_step": 1}})
        sampler = DeepSpeedDataSampler.from_analysis(
            str(tmp_path), "seqlen", micro_batch_size=2,
            data_parallel_rank=0, data_parallel_size=1, curriculum=sched)
        first = next(iter(sampler))
        # the first scheduled step only admits short samples
        assert all(len(ds[i]["input_ids"]) <= 6 for i in first), \
            [len(ds[i]["input_ids"]) for i in first]

    def test_vocab_rarity_metric(self):
        from deepspeed_tpu.runtime.data_pipeline.data_analyzer import (
            metric_vocab_rarity,
        )

        freq = np.array([100.0, 1.0])
        fn = metric_vocab_rarity(freq)
        rare = fn({"input_ids": np.array([1, 1])})
        common = fn({"input_ids": np.array([0, 0])})
        assert rare > common


class TestExperimentScheduler:
    def test_persistence_and_resume(self, tmp_path):
        from deepspeed_tpu.autotuning.autotuner import Experiment
        from deepspeed_tpu.autotuning.scheduler import ExperimentScheduler

        exps = [Experiment(name=f"t{i}", config_patch={"x": i})
                for i in range(3)]
        calls = []

        def run_fn(patch):
            calls.append(patch["x"])
            if patch["x"] == 1:
                raise RuntimeError("simulated OOM")
            return float(patch["x"] * 10)

        sched = ExperimentScheduler(str(tmp_path))
        sched.run(exps, run_fn)
        assert calls == [0, 1, 2]
        best = sched.best()
        assert best["best"] == "t2" and best["best_metric"] == 20.0
        t1_dirs = [d for d in os.listdir(tmp_path) if d.startswith("t1-")]
        assert len(t1_dirs) == 1  # trial dir keyed name-confighash
        assert os.path.exists(tmp_path / t1_dirs[0] / "metrics.json")

        # resume: successful trials cached, the FAILED one retries (errors
        # are often transient — busy TPU runtime)
        calls.clear()
        exps2 = [Experiment(name=f"t{i}", config_patch={"x": i})
                 for i in range(3)]
        sched2 = ExperimentScheduler(str(tmp_path))
        sched2.run(exps2, run_fn)
        assert calls == [1]
        assert exps2[2].metric_value == 20.0

        # changed search space under the SAME experiment name must re-run,
        # not return the stale metric recorded for a different config_patch
        calls.clear()
        exps3 = [Experiment(name="t2", config_patch={"x": 7})]
        sched3 = ExperimentScheduler(str(tmp_path))
        sched3.run(exps3, run_fn)
        assert calls == [7] and exps3[0].metric_value == 70.0

        # cache_errors=True: nothing re-runs at all
        calls.clear()
        exps3 = [Experiment(name=f"t{i}", config_patch={"x": i})
                 for i in range(3)]
        ExperimentScheduler(str(tmp_path), cache_errors=True).run(exps3, run_fn)
        assert calls == []


class TestCompressionDepth:
    def test_head_and_channel_pruning(self):
        from deepspeed_tpu.compression.compress import (
            apply_compression,
            init_compression,
        )

        rng = np.random.default_rng(0)
        w = rng.normal(size=(8, 4 * 4))          # D=8, H=4 heads of hd=4
        w[:, :4] *= 10                            # head 0 dominant
        params = {"q_proj": {"kernel": jnp.asarray(w, jnp.float32)},
                  "mlp": {"kernel": jnp.asarray(rng.normal(size=(8, 6)),
                                                jnp.float32)}}
        cfg = {
            "head_pruning": {"shared_parameters": {"enabled": True,
                                                   "num_heads": 4},
                             "different_groups": {
                                 "g": {"params": {"dense_ratio": 0.25},
                                       "modules": ["q_proj*"]}}},
            "channel_pruning": {"shared_parameters": {"enabled": True},
                                "different_groups": {
                                    "g": {"params": {"dense_ratio": 0.5},
                                          "modules": ["mlp*"]}}},
        }
        params, spec = init_compression(params, cfg)
        out = apply_compression(params, spec)
        q = np.asarray(out["q_proj"]["kernel"])
        assert np.all(q[:, :4] != 0)              # dominant head kept
        assert np.all(q[:, 4:] == 0)              # 3 of 4 heads pruned
        m = np.asarray(out["mlp"]["kernel"])
        assert (np.sum(np.any(m != 0, axis=0))) == 3  # half the channels

    def test_head_pruning_stacked_layers(self):
        """Stacked [L, D, H*hd] kernels (this repo's transformer layout)
        get an independent head mask per layer."""
        from deepspeed_tpu.compression.compress import head_mask

        rng = np.random.default_rng(1)
        w = rng.normal(size=(2, 8, 4 * 4))
        w[0, :, :4] *= 10       # layer 0: head 0 dominant
        w[1, :, 12:] *= 10      # layer 1: head 3 dominant
        mask = np.asarray(head_mask(jnp.asarray(w, jnp.float32), 0.25, 4))
        out = w * mask
        assert np.all(out[0, :, :4] != 0) and np.all(out[0, :, 4:] == 0)
        assert np.all(out[1, :, 12:] != 0) and np.all(out[1, :, :12] == 0)

    def test_activation_quantizer_consumer(self):
        from deepspeed_tpu.compression.compress import (
            activation_quantizer,
            init_compression,
        )

        params = {"fc1": {"kernel": jnp.ones((4, 4))}}
        cfg = {"activation_quantization": {
            "shared_parameters": {"enabled": True},
            "different_groups": {"g": {"params": {"bits": 8},
                                       "modules": ["fc1*"]}}}}
        _, spec = init_compression(params, cfg)
        aq = activation_quantizer(spec, "fc1.kernel")
        x = jnp.asarray(np.random.default_rng(0).normal(size=(8,)), jnp.float32)
        assert float(jnp.max(jnp.abs(aq(x) - x))) < 0.05
        ident = activation_quantizer(spec, "nonexistent")
        np.testing.assert_array_equal(np.asarray(ident(x)), np.asarray(x))

    def test_layer_reduction(self):
        from deepspeed_tpu.compression.compress import init_compression

        params = {"layers": {"w": jnp.arange(8 * 4).reshape(8, 4) * 1.0},
                  "embed": {"e": jnp.ones((16, 4))}}
        cfg = {"layer_reduction": {"enabled": True, "teacher_layer": [0, 3, 7]}}
        out, _ = init_compression(params, cfg)
        assert out["layers"]["w"].shape[0] == 3
        np.testing.assert_allclose(np.asarray(out["layers"]["w"][1]),
                                   np.arange(12, 16))
        assert out["embed"]["e"].shape == (16, 4)  # non-layer arrays untouched

    def test_scheduler_gates_methods(self):
        from deepspeed_tpu.compression.compress import init_compression
        from deepspeed_tpu.compression.scheduler import CompressionScheduler

        params = {"w": jnp.ones((4, 4))}
        cfg = {
            "weight_quantization": {"shared_parameters": {"enabled": True,
                                                          "schedule_offset": 0},
                                    "different_groups": {
                                        "g": {"params": {"start_bits": 8},
                                              "modules": ["*"]}}},
            "sparse_pruning": {"shared_parameters": {"enabled": True,
                                                     "schedule_offset": 100},
                               "different_groups": {
                                   "g": {"params": {"dense_ratio": 0.5},
                                         "modules": ["*"]}}},
        }
        _, spec = init_compression(params, cfg)
        sched = CompressionScheduler(spec, cfg)
        early = sched.spec_at(10)
        assert early["w"].quantize_bits == 8
        assert early["w"].sparse_ratio is None        # not yet scheduled
        late = sched.spec_at(100)
        assert late["w"].sparse_ratio == 0.5

    def test_activation_quantization(self):
        from deepspeed_tpu.compression.compress import quantize_activation

        x = jnp.asarray(np.random.default_rng(0).normal(size=(16,)),
                        jnp.float32)
        y = quantize_activation(x, bits=8)
        assert float(jnp.max(jnp.abs(y - x))) < 0.05
        g = jax.grad(lambda x: jnp.sum(quantize_activation(x, 8)))(x)
        np.testing.assert_allclose(np.asarray(g), 1.0)  # STE


class TestFlopsTree:
    def test_per_module_breakdown(self):
        from deepspeed_tpu.models.transformer import TransformerConfig
        from deepspeed_tpu.profiling.flops_profiler.profiler import (
            format_profile_tree,
            model_profile_tree,
        )

        cfg = TransformerConfig.tiny()
        tree = model_profile_tree(cfg, measured_total=1e9)
        assert "embed" in tree and "lm_head" in tree
        layers = tree[f"layers (x{cfg.num_layers})"]
        assert layers["params"] > 0 and "attention" in layers["children"]
        pcts = [m["pct"] for k, m in tree.items() if k != "_total"]
        assert abs(sum(pcts) - 100.0) < 1e-6
        lines = format_profile_tree(tree)
        assert any("attention" in l for l in lines)

    def test_moe_tree_counts_routed_flops(self):
        from deepspeed_tpu.models.transformer import TransformerConfig
        from deepspeed_tpu.profiling.flops_profiler.profiler import (
            model_profile_tree,
        )

        dense = model_profile_tree(TransformerConfig.tiny())
        moe = model_profile_tree(TransformerConfig.tiny_moe())
        l_dense = dense[f"layers (x2)"]
        l_moe = moe[f"layers (x2)"]
        # MoE params grow with E but active flops only with top-k
        assert l_moe["params"] > l_dense["params"] * 2
        assert l_moe["flops"] < l_dense["flops"] * 4
