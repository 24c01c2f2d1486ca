#!/usr/bin/env python3
"""``rehearse_compile.py`` for a train cell whose configuration names its
``system`` module: compile the step at its real size for a described
v5e:2x2, without the chip, and print ``memory_analysis()`` per chip.

    python3 benchmark/tools/rehearse_compile_system.py --workload <cell> [--micro-batch 1 2]

The step is the engine's recipe (bf16 cast of the fp32 masters, the gradient
of the model's loss with the state it declares, clipping, AdamW, the model's
own rule on its state) with the model built by ``<system>.model_of``, and
the engine's account of its memory handed to the model's checkpoint policy
(``engine_memory``) as the engine hands it: the described chip's 15.75 GiB
and 18 bytes a parameter.  A compile that passes is not a chip run.
"""
from __future__ import annotations

import argparse
import importlib
import math
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

from lib import manifest  # noqa: E402

#: a v5e chip's ``bytes_limit`` as the backend reports it (PERF.md)
BYTES_LIMIT = int(15.75 * 2 ** 30)


def for_the_chip():
    """``rehearse_compile.py``'s seams, and the grouped matmul's."""
    from deepspeed_tpu.moe import dropless
    from tools import rehearse_compile

    rehearse_compile.for_the_chip()
    dropless._on_tpu = lambda: True


def compile_step(cell, config, traffic, micro_batch: int):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec

    from deepspeed_tpu.runtime.activation_checkpointing import \
        checkpointing as ac
    from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh
    from deepspeed_tpu.runtime.zero.sharding import ZeroShardingPlan

    system = importlib.import_module(config["system"])
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chips = cell["chips"]
    topo = initialize_mesh(TopologyConfig(),
                           devices=list(desc.devices[:chips]), force=True)
    model = system.model_of(system.sizes_of(config, False),
                            traffic["seq_len"],
                            **traffic.get("model_options", {}))
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
    replicated = NamedSharding(topo.mesh, PartitionSpec())
    m_abs = jax.eval_shape(model.init_model_state)
    model_state = place(m_abs, jax.tree.map(lambda _: replicated, m_abs))
    n = sum(math.prod(x.shape) for x in jax.tree.leaves(p_abs))
    state_bytes = 18 * n // (chips if ds["zero_optimization"]["stage"] else 1)

    def step(params, opt_state, model_state, tokens):
        def loss_fn(p32):
            p = jax.tree.map(lambda x: x.astype(jnp.bfloat16), p32)
            with ac.engine_memory(BYTES_LIMIT, state_bytes):
                return model.loss_fn(p, {"input_ids": tokens}, None,
                                     model_state)

        (loss, counted), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, \
            model.next_model_state(model_state, counted), loss

    tokens = jax.ShapeDtypeStruct(
        (micro_batch * chips, traffic["seq_len"]), jnp.int32,
        sharding=NamedSharding(topo.mesh, topo.batch_spec()))
    compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
        params, opt_state, model_state, tokens).compile()
    return compiled.memory_analysis(), n


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
    from deepspeed_tpu.telemetry import get_tracer

    gib = 2.0 ** 30
    for mb in args.micro_batch or [traffic["micro_batch_per_chip"]]:
        try:
            mem, n = compile_step(cell, config, traffic, mb)
        except Exception as exc:  # noqa: BLE001 — the compiler's refusal is the result
            print(f"micro_batch {mb}: refused: {str(exc)[:600]}")
            continue
        args_b, tmp_b, out_b = (mem.argument_size_in_bytes,
                                mem.temp_size_in_bytes,
                                mem.output_size_in_bytes)
        alias = getattr(mem, "alias_size_in_bytes", 0)
        saved = [r.attrs.get("saved") for r in get_tracer().records()
                 if r.name == "train/remat_layout"]
        print(f"micro_batch {mb}: {n / 1e6:.1f} M parameters, arguments "
              f"{args_b / gib:.2f} GiB, temporaries {tmp_b / gib:.2f} GiB, "
              f"outputs {out_b / gib:.2f} GiB (aliased {alias / gib:.2f}), "
              f"live peak ~{(args_b + tmp_b + out_b - alias) / gib:.2f} GiB "
              f"per chip; a layer keeps {saved[-1] if saved else None}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
