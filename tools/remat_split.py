#!/usr/bin/env python3
"""What a checkpointed layer saves, set by set: a train cell's step alone on
the chip (``deepspeed_tpu.initialize()`` + ``train_batch`` at the cell's
shapes) with the saved set forced, against the rule's own choice.

    chiprun --chips 1 --timeout 1500 -- python3 tools/remat_split.py
    python3 tools/remat_split.py --cpu-rehearsal     # toy sizes, no timing claim

Sets: ``none`` (``nothing_saveable``, the program before PR 48), ``gate_up``,
``attention`` (q/k/v, the flash kernel's output and row statistics, the
post-attention residual), for the experts' cell ``rows`` (the grouped
matmuls' rows) and ``stacks`` (the three gathered expert stacks), ``all``, and
``rule`` (nothing forced: what ``checkpointing.layer_policy`` picks from the
device's ``bytes_limit``, with its ``train/remat_layout`` record).  A set
forces what the cell names of it.  One process a set — ``peak_bytes_in_use``
is a process's high-water mark — started one after the other by a parent that
never touches JAX.  Each prints one JSON line: ms a step (median of
``--steps`` after two warm ones, each ending in ``block_until_ready``), the
device's peak, ``memory_analysis()`` of the compiled step, the first loss and
the parameters' norm after the steps (the sets must agree to rounding).
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]

ATTENTION = ("q_proj", "k_proj", "v_proj", "flash_out", "flash_lse",
             "attn_residual")
GATE_UP = ("gate_proj", "up_proj")
ROWS = ("expert_gate_rows", "expert_up_rows", "expert_down_rows")
STACKS = ("expert_gate_whole", "expert_up_whole", "expert_down_whole")
SETS = {"none": (), "gate_up": GATE_UP, "attention": ATTENTION, "rows": ROWS,
        "stacks": STACKS, "all": GATE_UP + ATTENTION + ROWS + STACKS,
        "rule": None}
TOY = dict(seq_len=128, micro_batch_per_chip=2)


def one_set(args) -> dict:
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import CausalLM
    from deepspeed_tpu.runtime.activation_checkpointing import \
        checkpointing as ac
    from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh
    from deepspeed_tpu.telemetry import get_tracer
    from deepspeed_tpu.utils.compile_cache import configure_compile_cache
    from lib import manifest, model as model_lib

    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.cpu_rehearsal:
        sys.exit("no TPU here: run through chiprun, or --cpu-rehearsal")
    configure_compile_cache()
    man = manifest.manifest()
    cell = manifest.cell(man, args.workload)
    job = dict(manifest.traffic_of(cell["traffic"]))
    if args.cpu_rehearsal:
        job.update(TOY)
    sizes = model_lib.sizes_of(manifest.config_of(man, cell["config"]),
                               args.cpu_rehearsal)
    devices = devices[:cell["chips"]]
    topo = initialize_mesh(TopologyConfig(), devices=list(devices),
                           force=True)
    cfg = model_lib.transformer_config(sizes, job["seq_len"],
                                       **job.get("model_options", {}))
    model = CausalLM(cfg)
    forced = SETS[args.set]
    if forced is not None:
        ac.select_saved = lambda tensors, layers, budget: tuple(
            n for t in tensors for n in t.names if n in forced)
    rng = np.random.default_rng(args.seed)
    global_batch = job["micro_batch_per_chip"] * len(devices)
    batches = [{"input_ids": jnp.asarray(rng.integers(
        0, cfg.vocab_size, size=(global_batch, job["seq_len"])), jnp.int32)}
        for _ in range(4)]
    ds_config = dict(job["ds_config"],
                     train_micro_batch_size_per_gpu=job["micro_batch_per_chip"])
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=ds_config, topology=topo, seed=args.seed,
        model_parameters=model_lib.init_params(model, args.seed, jnp.float32))
    t0 = time.perf_counter()
    losses = [float(engine.train_batch(batches[i % 4])) for i in range(2)]
    warm_s = time.perf_counter() - t0
    steps = 3 if args.cpu_rehearsal else args.steps
    step_s = []
    for i in range(steps):
        t = time.perf_counter()
        jax.block_until_ready(engine.train_batch(batches[i % 4]))
        step_s.append(time.perf_counter() - t)
    stats = [d.memory_stats() or {} for d in devices]
    mem = engine._compiled["train_batch"].lower(
        engine.state, batches[0]).compile().memory_analysis()
    layout = [r.attrs for r in get_tracer().records()
              if r.name == "train/remat_layout"]
    norm = float(jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in
                              jax.tree.leaves(engine.state.params))))
    return {
        "set": args.set, "workload": args.workload, "seed": args.seed,
        "platform": devices[0].platform, "chips": len(devices),
        "step_ms": statistics.median(step_s) * 1e3,
        "step_ms_min": min(step_s) * 1e3, "warm_s": warm_s,
        "peak_bytes_in_use": max(s.get("peak_bytes_in_use", 0) for s in stats),
        "bytes_limit": max(s.get("bytes_limit", 0) for s in stats),
        "device_memory": engine._device_memory,
        "analysis": None if mem is None else {
            "arguments": mem.argument_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes,
            "outputs": mem.output_size_in_bytes,
            "aliased": mem.alias_size_in_bytes},
        "loss0": losses[0], "loss1": losses[1], "param_norm": norm,
        "layout": layout[-1] if layout else None, "layout_records": len(layout),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="mistral7b-train-1chip")
    ap.add_argument("--set", choices=list(SETS))
    ap.add_argument("--sets", nargs="*", default=list(SETS))
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--out", default="chiprun_out/remat_split.jsonl")
    args = ap.parse_args()
    if args.set:
        print(json.dumps(one_set(args)), flush=True)
        return 0
    os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)), exist_ok=True)
    rc = 0
    for name in args.sets:
        cmd = [sys.executable, os.path.abspath(__file__), "--set", name,
               "--workload", args.workload, "--steps", str(args.steps),
               "--seed", str(args.seed)]
        if args.cpu_rehearsal:
            cmd.append("--cpu-rehearsal")
        done = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        line = (done.stdout.strip().splitlines() or [""])[-1]
        if done.returncode or not line.startswith("{"):
            # a set the device cannot hold is a result too
            line = json.dumps({"set": name, "workload": args.workload,
                               "failed": done.returncode,
                               "stderr": done.stderr[-1500:]})
            rc = 1
        print(line, flush=True)
        with open(os.path.join(ROOT, args.out), "a") as f:
            f.write(line + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
