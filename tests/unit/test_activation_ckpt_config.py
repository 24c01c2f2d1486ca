"""Activation-checkpointing config wiring (the DS-JSON
``activation_checkpointing`` block must change the compiled program, not
parse into dead knobs).

Reference: deepspeed/runtime/activation_checkpointing/checkpointing.py:948,
1029 — configure() + checkpoint() drive execution; here the policy flows
config → engine → models' jax.checkpoint policy via named residuals.
"""
import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as ac
from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

pytestmark = pytest.mark.core


def _engine(act_ckpt=None):
    topo = initialize_mesh(TopologyConfig(), force=True)
    cfg = TransformerConfig(vocab_size=256, hidden_size=128,
                            intermediate_size=256, num_layers=4, num_heads=4,
                            num_kv_heads=4, max_seq_len=256, remat=True,
                            use_flash=False)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    config = {"train_micro_batch_size_per_gpu": 2,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
              "bf16": {"enabled": True}}
    if act_ckpt:
        config["activation_checkpointing"] = act_ckpt
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=config, topology=topo)
    return eng


def _compiled(eng):
    batch = {"input_ids": jnp.zeros((16, 256), jnp.int32)}
    return eng._build_train_batch_fn().lower(eng.state, batch).compile()


class TestActivationCheckpointingConfig:
    def teardown_method(self):
        ac.reset()

    def test_configure_flows_from_engine_init(self):
        _engine({"partition_activations": True})
        assert ac.partition_activations_enabled()
        assert ac.active()
        # an engine WITHOUT the block must not clobber the active policy
        _engine()
        assert ac.active()
        ac.reset()
        assert not ac.active()


    def test_partition_activations_changes_compiled_memory(self):
        """The toggle must measurably change execution: saving the named
        (mesh-sharded) residuals trades recompute FLOPs for live memory."""
        base = _compiled(_engine())
        part = _compiled(_engine({"partition_activations": True}))
        mem_b, mem_p = base.memory_analysis(), part.memory_analysis()
        if mem_b is None or mem_p is None:
            import pytest

            pytest.skip("backend exposes no memory_analysis")
        assert mem_p.temp_size_in_bytes != mem_b.temp_size_in_bytes, (
            "partition_activations must change the compiled memory plan "
            f"(both {mem_b.temp_size_in_bytes})")
        cost_b = base.cost_analysis()
        cost_p = part.cost_analysis()
        assert cost_p.get("flops", 0) < cost_b.get("flops", 0), (
            "saved residuals must cut recompute flops: "
            f"{cost_p.get('flops')} vs {cost_b.get('flops')}")

    def test_cpu_checkpointing_selects_offload_policy(self):
        ac.reset()
        ac.configure(checkpoint_in_cpu=True)
        pol = ac.get_policy()
        assert pol is not jax.checkpoint_policies.nothing_saveable
        assert ac.active()

    def test_policy_names_match_model_annotations(self):
        """The names the policies select must be the names the model tags —
        a rename on either side silently reverts to full recompute."""
        import inspect

        from deepspeed_tpu.models import transformer

        src = inspect.getsource(transformer)
        for name in ac.RESIDUAL_NAMES:
            assert f'"{name}"' in src, f"model no longer tags {name!r}"
