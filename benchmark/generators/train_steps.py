"""Traffic kind ``train_steps``: optimizer steps back to back on seeded
batches, through ``deepspeed_tpu.initialize()`` and ``engine.train_batch()``.

Parameters (the traffic file): ``seq_len``, ``micro_batch_per_chip``,
``distinct_batches`` (the seeded dataset, cycled), ``ds_config`` (what the
user hands ``initialize``), ``model_options`` (TransformerConfig options the
job sets, e.g. remat).  Every step ends in ``block_until_ready`` of its loss,
as a loop that logs its loss does.
"""
from __future__ import annotations

import math
import time
from typing import Dict

from lib import model as model_lib
from lib.profile import TraceSlice
from reference.decoder import Reference

REHEARSAL = dict(seq_len=128, micro_batch_per_chip=2)


def pre_jax(ctx) -> None:
    """libtpu reads LIBTPU_INIT_ARGS once, when the client is created:
    ``deepspeed_tpu.initialize()`` applies a config's overlap flags first
    thing for that reason, and the benchmark looks at the devices before."""
    from deepspeed_tpu.runtime.overlap.xla_flags import configure_from_raw

    configure_from_raw(ctx.traffic["ds_config"])


def _build(ctx, sizes: Dict, job: Dict):
    import numpy as np

    import deepspeed_tpu
    import jax.numpy as jnp
    from deepspeed_tpu.models.transformer import CausalLM
    from deepspeed_tpu.runtime.dataloader import RepeatingLoader
    from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

    devices = ctx.devices
    topo = initialize_mesh(TopologyConfig(), devices=list(devices),
                           force=True)
    cfg = model_lib.transformer_config(sizes, job["seq_len"],
                                       **ctx.traffic.get("model_options", {}))
    model = CausalLM(cfg)
    params = model_lib.init_params(model, ctx.seed, jnp.float32)
    global_batch = job["micro_batch_per_chip"] * len(devices)
    rng = np.random.default_rng(ctx.seed)
    n_rows = global_batch * ctx.traffic["distinct_batches"]
    dataset = [{"input_ids": rng.integers(
        0, cfg.vocab_size, size=job["seq_len"]).astype(np.int32)}
        for _ in range(n_rows)]
    ds_config = dict(ctx.traffic["ds_config"])
    ds_config["train_micro_batch_size_per_gpu"] = job["micro_batch_per_chip"]
    engine, _, loader, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, training_data=dataset,
        config=ds_config, topology=topo, seed=ctx.seed % (2 ** 31))
    del params                      # the engine owns the placed fp32 master
    return engine, model, cfg, iter(RepeatingLoader(loader)), global_batch


def _check_against_reference(ctx, engine, cfg, sizes, batch) -> Dict:
    """Before the first step: the reference's loss of this batch from the
    initial parameters, and its logits of row 0 against the program's
    default path (kernels on) on the same bf16 parameters."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.transformer import forward

    tokens = batch["input_ids"]
    dev0 = ctx.devices[0]
    host_tokens = np.asarray(tokens)
    rows = [jax.device_put(row, dev0) for row in host_tokens]
    weights = model_lib.reference_weights(engine.state.params, dev0)
    ref_losses, kept, routing = Reference(sizes).run(rows, weights,
                                                     keep_logits=1)
    del weights
    ref_logits = np.asarray(kept[0], np.float32)
    del kept
    # one row a chip, so that the batch axis still divides over the mesh
    got = np.asarray(jax.jit(lambda p, t: forward(
        jax.tree.map(lambda x: x.astype(jnp.bfloat16), p), t, cfg))(
        engine.state.params, tokens[:len(ctx.devices)])[0], np.float32)
    checks = model_lib.logits_agreement(
        got, ref_logits, ctx.config["tolerances"]["logits_rel_l2"])
    checks["reference_balance"] = routing["balance"]
    checks["reference_loss"] = float(np.mean(ref_losses)) + float(
        sizes.get("router_aux_loss_coef", 0.0)) * routing["balance"]
    # the reference drops no token; the program's experts each take at most
    # ceil(pairs / experts x capacity factor) of a step's (token, choice)
    # pairs and drop the rest (moe/sharded_moe.py).  By the reference's
    # routing of this batch, how many pairs would not fit?
    over = 0
    for loads in routing["expert_loads"]:
        capacity = math.ceil(sum(loads) / len(loads)
                             * cfg.moe_capacity_factor)
        over += sum(max(n - capacity, 0) for n in loads)
        checks["expert_capacity"] = capacity
        checks["max_expert_load"] = max(loads)
    checks["pairs_over_capacity"] = over
    return checks


def run(ctx) -> Dict:
    import jax
    import numpy as np

    job = dict(ctx.traffic)
    if ctx.rehearsal:
        job.update(REHEARSAL)
    sizes = model_lib.sizes_of(ctx.config, ctx.rehearsal)
    tol = ctx.config["tolerances"]
    spans = ctx.spans

    with spans.span("bench/setup_build"):
        engine, model, cfg, batches, global_batch = _build(ctx, sizes, job)
    first = next(batches)
    with spans.span("bench/setup_reference"):
        checks = _check_against_reference(ctx, engine, cfg, sizes, first)
    with spans.span("bench/setup_warm"):
        loss0 = float(engine.train_batch(first))    # compiles, or loads
        float(engine.train_batch(next(batches)))    # a relayout would show
    step_fn = engine._compiled["train_batch"]
    compiles_before = step_fn._cache_size()

    checks["step0_loss"] = loss0
    checks["loss_abs_diff"] = abs(loss0 - checks["reference_loss"])
    # a position whose router probabilities tie within bf16 rounding takes
    # another expert than the float32 reference: such positions are counted,
    # and a configuration without experts admits none
    correct = (checks["loss_abs_diff"] <= tol["loss_abs"]
               and checks["pairs_over_capacity"] == 0
               and checks["logits_rel_l2_median"] <= tol["logits_rel_l2"]
               and checks["logits_share_over_tol"]
               <= tol.get("routing_flip_share", 0.0)
               and checks["logits_finite"])

    tokens_per_step = global_batch * job["seq_len"]
    losses, step_s = [], []
    t_start = time.perf_counter()
    deadline = t_start + ctx.seconds
    tracer = TraceSlice(ctx.trace, ctx.trace_dir, spans, t_start, ctx.seconds)
    while True:
        now = time.perf_counter()
        if now >= deadline:
            break
        tracer.maybe_start(now)
        batch = next(batches)
        with spans.span("bench/train_step"):
            loss = engine.train_batch(batch)
            jax.block_until_ready(loss)
        step_s.append(time.perf_counter() - now)
        losses.append(float(loss))
    t_end = time.perf_counter()
    tracer.stop()

    failed = sum(1 for x in losses if not math.isfinite(x))
    correct = correct and failed == 0 and len(losses) > 0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in ctx.devices)
    engine.close()
    return {
        "correct": bool(correct), "checks": checks,
        "attempted": len(losses), "failed": failed,
        "window": (t_start, t_end), "trace": tracer.reduced, "slice": tracer.slice,
        "memory_peak_bytes": int(peak),
        "facts": {
            "tokens": tokens_per_step * len(losses),
            "chips": len(ctx.devices),
            "tokens_per_step": tokens_per_step,
            "global_batch": global_batch, "seq_len": job["seq_len"],
            "compiles_in_window": step_fn._cache_size() - compiles_before,
            "first_loss": losses[0] if losses else None,
            "last_loss": losses[-1] if losses else None,
            "params": model.num_params(),
        },
        "samples": {"step_s": step_s},
    }
