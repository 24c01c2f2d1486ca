#!/usr/bin/env python3
"""Compile a train cell's step at its real size for a described v5e:2x2,
without the chip, and print ``memory_analysis()`` per chip.

    python3 benchmark/tools/rehearse_compile.py --workload <train cell> [--micro-batch 1 2 4 8]

This is how a train traffic file's ``micro_batch_per_chip`` is chosen: the
largest whose arguments + temporaries fit the chip's 15.75 GiB with room for
the reference check.  The step is the engine's recipe (bf16 cast of fp32
masters, ``value_and_grad`` of the model's loss, clipping, AdamW) laid out
by the engine's own ZeRO plan — ``deepspeed_tpu.initialize()`` itself
places arrays, which a described device cannot hold.  A compile that passes
is not a chip run.
"""
from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

from lib import manifest, model as model_lib  # noqa: E402


def for_the_chip():
    """Steer the seams that read ``jax.default_backend()`` (cpu here) onto
    their device branch, as tests/unit/test_chip_compile.py does."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from deepspeed_tpu.accelerator import real_accelerator
    from deepspeed_tpu.accelerator.tpu_accelerator import TPUAccelerator
    from deepspeed_tpu.inference.v2.kernels import ragged_ops
    from deepspeed_tpu.kernels import fused_collective_matmul as fcm
    from deepspeed_tpu.ops.adam import fused_adam
    from deepspeed_tpu.ops.transformer import flash_attention as fa

    for mod in (fa, fcm, ragged_ops, fused_adam):
        mod._interpret = lambda: False
    fcm.resolve_impl = lambda impl="auto": "pallas" if impl == "auto" \
        else impl
    real_accelerator._ACCELERATOR = TPUAccelerator()
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()


def compile_step(cell, config, traffic, micro_batch: int):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding

    from deepspeed_tpu.models.transformer import CausalLM
    from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh
    from deepspeed_tpu.runtime.zero.sharding import ZeroShardingPlan

    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chips = cell["chips"]
    topo = initialize_mesh(TopologyConfig(),
                           devices=list(desc.devices[:chips]), force=True)
    sizes = model_lib.sizes_of(config, rehearsal=False)
    cfg = model_lib.transformer_config(sizes, traffic["seq_len"],
                                       **traffic.get("model_options", {}))
    model = CausalLM(cfg)
    ds = traffic["ds_config"]
    opt = ds["optimizer"]["params"]
    tx = optax.chain(optax.clip_by_global_norm(ds["gradient_clipping"]),
                     optax.adamw(opt["lr"], weight_decay=opt["weight_decay"]))
    p_abs = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    plan = ZeroShardingPlan(topo, ds["zero_optimization"]["stage"],
                            base_specs=model.partition_specs)
    o_abs = jax.eval_shape(tx.init, p_abs)
    place = lambda tree, sh: jax.tree.map(  # noqa: E731
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sh)
    params = place(p_abs, plan.param_shardings(p_abs))
    opt_state = place(o_abs, plan.opt_state_shardings(o_abs, p_abs))

    def step(params, opt_state, tokens):
        def loss_fn(p32):
            p = jax.tree.map(lambda x: x.astype(jnp.bfloat16), p32)
            return model.loss_fn(p, {"input_ids": tokens}, None)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    tokens = jax.ShapeDtypeStruct(
        (micro_batch * chips, traffic["seq_len"]), jnp.int32,
        sharding=NamedSharding(topo.mesh, topo.batch_spec()))
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt_state, tokens).compile()
    return compiled.memory_analysis()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--micro-batch", type=int, nargs="*")
    args = ap.parse_args()
    man = manifest.manifest()
    cell = manifest.cell(man, args.workload)
    config = manifest.config_of(man, cell["config"])
    traffic = manifest.traffic_of(cell["traffic"])
    for_the_chip()
    gib = 2.0 ** 30
    for mb in args.micro_batch or [traffic["micro_batch_per_chip"]]:
        try:
            mem = compile_step(cell, config, traffic, mb)
        except Exception as exc:  # noqa: BLE001 — the compiler's refusal is the result
            print(f"micro_batch {mb}: refused: {str(exc)[:400]}")
            continue
        args_b, tmp_b, out_b = (mem.argument_size_in_bytes,
                                mem.temp_size_in_bytes,
                                mem.output_size_in_bytes)
        alias = getattr(mem, "alias_size_in_bytes", 0)
        print(f"micro_batch {mb}: arguments {args_b / gib:.2f} GiB, "
              f"temporaries {tmp_b / gib:.2f} GiB, outputs "
              f"{out_b / gib:.2f} GiB (aliased {alias / gib:.2f}), "
              f"live peak ~{(args_b + tmp_b + out_b - alias) / gib:.2f} GiB "
              f"per chip", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
