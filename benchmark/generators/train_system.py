"""Traffic kind ``train_system``: optimizer steps back to back on seeded
batches, through ``deepspeed_tpu.initialize()`` and ``engine.train_batch()``,
of the system the CONFIGURATION names.

The trained system is built by the module the configuration file names under
``system`` (as ``generators/sessions.py`` finds a served one): ``build(ctx,
job)`` gives an object with ``engine``, ``batches`` (an endless iterator of
global batches), ``global_batch`` and ``params``;
``before_first_step(ctx, built, batch)`` returns the checks that need the
initial parameters, ``after_first_step(ctx, built, checks, loss0)`` the
verdict once the first step has run.  This file knows no model.

Parameters (the traffic file): ``seq_len``, ``micro_batch_per_chip``,
``distinct_batches`` (the seeded dataset, cycled), ``ds_config`` (what the
user hands ``initialize``), ``model_options`` (options the job sets, e.g.
remat).  Every step ends in ``block_until_ready`` of its loss, as a loop that
logs its loss does; the facts and samples are ``generators/train_steps``'s,
so the train readers read this kind unchanged.
"""
from __future__ import annotations

import importlib
import math
import time
from typing import Dict

from lib.profile import TraceSlice

REHEARSAL = dict(seq_len=128, micro_batch_per_chip=2)


def pre_jax(ctx) -> None:
    """Before the TPU client exists: a program that lacks what the system
    module builds from stops here, at once and non-zero (``require``); and
    libtpu reads LIBTPU_INIT_ARGS once, when the client is created."""
    system = importlib.import_module(ctx.config["system"])
    if hasattr(system, "require"):
        system.require()
    from deepspeed_tpu.runtime.overlap.xla_flags import configure_from_raw

    configure_from_raw(ctx.traffic["ds_config"])


def run(ctx) -> Dict:
    import jax

    job = dict(ctx.traffic)
    if ctx.rehearsal:
        job.update(REHEARSAL)
    system = importlib.import_module(ctx.config["system"])
    spans = ctx.spans

    with spans.span("bench/setup_build"):
        built = system.build(ctx, job)
    engine, batches = built.engine, built.batches
    first = next(batches)
    with spans.span("bench/setup_reference"):
        checks = system.before_first_step(ctx, built, first)
    with spans.span("bench/setup_warm"):
        loss0 = float(engine.train_batch(first))    # compiles, or loads
    with spans.span("bench/setup_reference"):
        correct = system.after_first_step(ctx, built, checks, loss0)
    with spans.span("bench/setup_warm"):
        float(engine.train_batch(next(batches)))    # a relayout would show
    step_fn = engine._compiled["train_batch"]
    compiles_before = step_fn._cache_size()

    tokens_per_step = built.global_batch * job["seq_len"]
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
    engine.close()      # the model's own state is read here, once
    return {
        "correct": bool(correct), "checks": checks,
        "attempted": len(losses), "failed": failed,
        "window": (t_start, t_end), "trace": tracer.reduced,
        "slice": tracer.slice, "memory_peak_bytes": int(peak),
        "facts": {
            "tokens": tokens_per_step * len(losses),
            "chips": len(ctx.devices),
            "tokens_per_step": tokens_per_step,
            "global_batch": built.global_batch, "seq_len": job["seq_len"],
            "compiles_in_window": step_fn._cache_size() - compiles_before,
            "first_loss": losses[0] if losses else None,
            "last_loss": losses[-1] if losses else None,
            "params": built.params,
        },
        "samples": {"step_s": step_s},
    }
