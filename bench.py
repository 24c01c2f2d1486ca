"""Benchmark modes for the chip (``DSTPU_BENCH_MODE``; default ``train``).

Prints ONE JSON line to stdout: ``{"metric", "value", "unit",
"vs_baseline", "platform", "device_kind", "device_count", "extra"}`` — every
result names the device it ran on, as JAX reports it.  Progress goes to
stderr.  The default mode measures training tokens/sec/chip through the
``deepspeed_tpu.initialize()`` engine (bf16, flash attention, remat);
``vs_baseline`` is MFU / 0.50 (BASELINE.md).

A mode that measures a device needs one: with no TPU the process exits
non-zero and prints no result.  There is no fall-back to the CPU and no
number from an earlier run is pasted into the output.  The backend is
initialised once, in this process (a chip belongs to one process).  A bench
that raises, or whose result carries a bench-level ``error``, exits
non-zero.

Two things do run on the CPU, and say so:

  * ``pipeline`` and ``fleet_sweep`` only COUNT on an 8-device CPU mesh
    (schedule ticks; scheduling-plane packing over the real router) — their
    result line says ``"platform": "cpu"``;
  * ``DSTPU_BENCH_FORCE_CPU=1`` is the explicit tiny-size rehearsal the
    ``tools/check_*_sweep.py`` plumbing gates use: the metric is emitted as
    ``cpu_rehearsal/<metric>`` with ``"cpu_rehearsal": true``, so a CPU
    number never appears under a device metric's name.

Env knobs: DSTPU_BENCH_LAYERS / HIDDEN / SEQ / BATCH / STEPS,
DSTPU_BENCH_MODE (train | flash_sweep | serving | serving_load |
decode_sweep | overlap_sweep | comm_sweep | kernel_sweep | ...); serving
modes also read DSTPU_BENCH_CTX (context length), DSTPU_BENCH_CHUNK
(splitfuse chunk) and DSTPU_BENCH_SEQS (decode batch width); decode_sweep
reads DSTPU_BENCH_SWEEP_SEQS / DSTPU_BENCH_SWEEP_CTX (comma lists).
DSTPU_BENCH_TELEMETRY=<dir> enables the telemetry subsystem for the train
bench (events.jsonl + trace.json + metrics.prom; see bin/dstpu-telemetry).
"""
from __future__ import annotations

import json
import os
import sys
import time

MODE = os.environ.get("DSTPU_BENCH_MODE", "train")
#: modes that count schedule properties on a CPU mesh and never touch a chip
CPU_MESH_MODES = ("pipeline", "fleet_sweep")
#: the explicit CPU rehearsal (never entered because a device is missing)
CPU_REHEARSAL = os.environ.get("DSTPU_BENCH_FORCE_CPU") == "1"

if MODE in CPU_MESH_MODES or CPU_REHEARSAL:
    # must be set before jax is imported
    os.environ["JAX_PLATFORMS"] = "cpu"
    if MODE in CPU_MESH_MODES + ("overlap_sweep", "comm_sweep"):
        _f = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in _f:
            os.environ["XLA_FLAGS"] = \
                (_f + " --xla_force_host_platform_device_count=8").strip()

import jax
import jax.numpy as jnp
import numpy as np


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


def peak_flops_per_chip() -> float:
    """bf16 peak of this device from THE peaks table
    (``deepspeed_tpu/profiling/roofline.py``); an unknown TPU kind raises."""
    from deepspeed_tpu.profiling.roofline import peak_flops_per_chip as peak

    return peak()


def env_int(name, default):
    return int(os.environ.get(name, default))


_FAILED = False          # set by emit(): a bench-level error was reported


def emit(metric, value, unit, vs_baseline, extra):
    global _FAILED
    extra = dict(extra)
    # ---- physical-plausibility gate: no >peak number may reach a result
    # line with a normal-looking vs_baseline ------------------------------ #
    peak_tf = peak_flops_per_chip() / 1e12
    if unit == "TFLOP/s" and value > peak_tf:
        extra["error"] = (f"measurement rejected: {value} TFLOP/s exceeds "
                          f"chip peak {peak_tf:.0f} — a timing artifact, "
                          f"not fast code")
        extra["rejected_value"] = value
        value, vs_baseline = 0.0, 0.0
    if isinstance(extra.get("mfu"), (int, float)) and extra["mfu"] > 1.0:
        extra["error"] = (f"measurement rejected: MFU {extra['mfu']} > 1 is "
                          f"physically impossible — timing artifact")
        extra["rejected_mfu"] = extra["mfu"]
        extra["mfu"] = 0.0
        value, vs_baseline = 0.0, 0.0
    if "error" in extra:
        _FAILED = True
    devices = jax.devices()
    line = {
        "metric": f"cpu_rehearsal/{metric}" if CPU_REHEARSAL else metric,
        "value": value, "unit": unit, "vs_baseline": vs_baseline,
        "platform": devices[0].platform,
        "device_kind": str(devices[0].device_kind),
        "device_count": len(devices),
        "extra": extra,
    }
    if CPU_REHEARSAL:
        line["cpu_rehearsal"] = True
    print(json.dumps(line), flush=True)


def run_train_bench(on_tpu: bool) -> None:
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
    from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

    if on_tpu:
        hidden = env_int("DSTPU_BENCH_HIDDEN", 2048)
        heads = env_int("DSTPU_BENCH_HEADS", max(hidden // 128, 1))
        cfg = TransformerConfig(
            vocab_size=32000,
            hidden_size=hidden,
            intermediate_size=hidden * 11 // 4,
            num_layers=env_int("DSTPU_BENCH_LAYERS", 12),
            num_heads=heads, num_kv_heads=max(heads // 2, 1),
            max_seq_len=env_int("DSTPU_BENCH_SEQ", 2048),
            remat=True,
            remat_policy=os.environ.get("DSTPU_BENCH_REMAT_POLICY",
                                        "nothing_saveable"),
            use_flash=True)
        batch_size = env_int("DSTPU_BENCH_BATCH", 8)
        seq = cfg.max_seq_len
        steps = env_int("DSTPU_BENCH_STEPS", 10)
        warmup = 2
    else:  # CPU smoke mode
        cfg = TransformerConfig.tiny(use_flash=False)
        batch_size, seq, steps, warmup = 4, 128, 3, 1

    topo = initialize_mesh(TopologyConfig(), force=True)
    n_chips = topo.world_size()
    model = CausalLM(cfg)
    log(f"initializing {model.num_params()/1e6:.0f}M-param model "
        f"(layers={cfg.num_layers} hidden={cfg.hidden_size} seq={seq})")
    params = model.init_params(jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    log("params ready; building engine")

    zero_conf = {"stage": env_int("DSTPU_BENCH_ZERO_STAGE",
                                  3 if n_chips > 1 else 0)}
    offload_ratio = float(os.environ.get("DSTPU_BENCH_OFFLOAD", "0"))
    if offload_ratio > 0:
        # Twin-Flow: stream `ratio` of the optimizer state from pinned host
        # memory through the update — the capacity dial that lets a 2B+
        # model train on one 16GB chip (and the first on-chip exercise of
        # the pinned-host path)
        zero_conf["offload_optimizer"] = {"device": "cpu",
                                          "ratio": offload_ratio}
    ds_config = {
        "train_micro_batch_size_per_gpu": max(batch_size // n_chips, 1),
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 3e-4, "weight_decay": 0.1}},
        "gradient_clipping": 1.0,
        "zero_optimization": zero_conf,
        "bf16": {"enabled": True},
    }
    telemetry_dir = os.environ.get("DSTPU_BENCH_TELEMETRY")
    if telemetry_dir:
        # full observability run: JSONL events + Chrome trace + metrics.prom
        # under $DSTPU_BENCH_TELEMETRY, summarized by bin/dstpu-telemetry
        ds_config["telemetry"] = {"enabled": True, "output_dir": telemetry_dir}
        # ... plus performance attribution: per-module cost tree + roofline
        # gauges (profile fires on warmup step 1, off the timed window) and
        # an xprof device trace for the summary's device-time breakdown
        ds_config["profiling"] = {
            "enabled": True, "roofline_interval": 1,
            "flops_profiler": {"enabled": True, "profile_step": 1}}
        ds_config["comms_logger"] = {
            "enabled": True, "xprof_step": 1,
            "xprof_dir": os.path.join(telemetry_dir, "xprof")}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=ds_config,
        topology=topo)

    rng = np.random.default_rng(0)
    batch = {"input_ids": jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(engine.train_batch_size(), seq)),
        jnp.int32)}

    log("compiling + warmup")
    t_compile = time.perf_counter()
    for i in range(warmup):
        loss = engine.train_batch(batch)
        jax.block_until_ready(loss)
        log(f"warmup step {i} done ({time.perf_counter()-t_compile:.1f}s)")

    log(f"timing {steps} steps")
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    tokens = engine.train_batch_size() * seq * steps
    tok_per_sec_chip = tokens / dt / n_chips
    # MFU numerator: the flops profiler's XLA cost analysis of the compiled
    # step (per-device — the post-SPMD module has local shapes), falling
    # back to the 6N+attention hand formula only when cost analysis is
    # unavailable on this backend
    step_flops = 0.0
    mfu_source = "analytic"
    try:
        stats = engine.train_step_cost()
        if stats and stats.get("flops_per_device"):
            step_flops = stats["flops_per_device"]
            mfu_source = "flops_profiler"
    except Exception as exc:  # noqa: BLE001
        log(f"profiler step cost unavailable ({str(exc)[:120]}); "
            f"falling back to analytic flops")
    if step_flops:
        mfu = step_flops / (dt / steps) / peak_flops_per_chip()
        flops_per_token = step_flops * n_chips / (engine.train_batch_size() * seq)
    else:
        # 6N params-flops + 12*L*D*S attention-flops per token, ×1.33 remat
        attn = 12 * cfg.num_layers * cfg.hidden_size * seq
        flops_per_token = model.flops_per_token() + 3 * attn
        mfu = tok_per_sec_chip * flops_per_token / peak_flops_per_chip()
    log(f"done: {tok_per_sec_chip:.0f} tok/s/chip, mfu={mfu:.3f} "
        f"(flops source: {mfu_source})")

    extra = {
        "mfu": round(mfu, 4),
        "mfu_flops_source": mfu_source,
        "flops_per_token": round(flops_per_token, 1),
        "flops_per_step_per_device": step_flops,
        "model_params": model.num_params(),
        "loss": float(loss),
        "chips": n_chips,
        "seq_len": seq,
        "step_time_s": round(dt / steps, 4),
        "device": str(getattr(jax.devices()[0], "device_kind", "cpu")),
        "backend": jax.default_backend(),
    }
    if telemetry_dir:
        engine.close()  # flush events.jsonl / trace.json / metrics.prom
        log(f"telemetry written to {telemetry_dir} "
            f"(summarize: bin/dstpu-telemetry {telemetry_dir})")
    emit("zero_train_tokens_per_sec_per_chip", round(tok_per_sec_chip, 1),
         "tokens/s/chip", round(mfu / 0.50, 4), extra)


def _stepwise_decode_probe(eng, uids, seed_tokens, steps) -> float:
    """Host-driven put() decode probe: one forward + host argmax round trip
    per generated token — the overhead axis the fused device-resident loop
    removes.  One warmup put() (compile) then ``steps`` timed single-token
    steps; returns tok/s.  Shared by the serving, serving_load and
    decode_sweep modes so the fused-vs-stepwise comparison measures the
    same loop everywhere."""
    n = len(uids)
    cur = [int(t) for t in seed_tokens]
    logits = eng.put(uids, [[t] for t in cur])                   # compile
    cur = [int(t) for t in np.asarray(jnp.argmax(logits[:n], axis=-1))]
    t0 = time.perf_counter()
    for _ in range(steps):
        logits = eng.put(uids, [[t] for t in cur])
        cur = [int(t) for t in np.asarray(jnp.argmax(logits[:n], axis=-1))]
    return n * steps / (time.perf_counter() - t0)


def _kv_point_stats(engines) -> dict:
    """KV heat columns for a sweep grid point, summed over the point's
    engine(s): peak live pages, cold fraction at the tightest configured
    age threshold, and the physical bytes radix prefix sharing saved.
    Engines built with ``track_page_heat=False`` contribute zeros."""
    peak = live = cold = saved = 0
    for eng in engines:
        snap = eng.memory_snapshot() or {}
        peak += int(snap.get("peak_live_pages") or 0)
        live += int(snap.get("live_pages") or 0)
        saved += int(snap.get("prefix_shared_bytes_saved") or 0)
        cp = snap.get("cold_pages") or {}
        if cp:
            cold += int(cp[min(cp, key=int)])
    return {"kv_peak_pages": peak,
            "kv_cold_frac": round(cold / live, 3) if live else 0.0,
            "prefix_shared_bytes_saved": saved}


def run_serving_bench(on_tpu: bool) -> None:
    """Paged vs gather serving attention throughput micro-bench: prefill +
    decode tokens/s at DSTPU_BENCH_CTX context.

    No toy budgets: the decode batch defaults to
    DSTPU_BENCH_SEQS=16 concurrent sequences on TPU — single-sequence
    decode measures launch latency, not the serving operating point.  The
    emitted window records fused vs stepwise decode and TTFT p50/p95."""
    import deepspeed_tpu  # noqa: F401
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2,
        RaggedInferenceEngineConfig,
    )
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
    from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

    initialize_mesh(TopologyConfig(), force=True)
    ctx = env_int("DSTPU_BENCH_CTX", 8192 if on_tpu else 512)
    chunk = env_int("DSTPU_BENCH_CHUNK", 512 if on_tpu else 64)
    decode_steps = env_int("DSTPU_BENCH_STEPS", 32 if on_tpu else 4)
    n_seqs = env_int("DSTPU_BENCH_SEQS", 16 if on_tpu else 2)
    if on_tpu:
        cfg = TransformerConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_layers=8, num_heads=16, num_kv_heads=8, max_seq_len=ctx,
            use_flash=True)
    else:
        cfg = TransformerConfig(vocab_size=256, hidden_size=64,
                                intermediate_size=128, num_layers=2,
                                num_heads=4, num_kv_heads=2, max_seq_len=ctx,
                                use_flash=False)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    uids = list(range(n_seqs))
    # capacity: warmup window + timed fused window + stepwise loop all extend
    # the same sequences, so leave 3·decode_steps of ctx headroom
    prompt_len = ctx - 3 * decode_steps - 2
    prompts = {u: rng.integers(1, cfg.vocab_size, size=prompt_len).tolist()
               for u in uids}

    results = {}
    for impl in ("paged", "gather"):
        try:
            eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
                max_tokens=chunk, max_seqs=n_seqs, max_ctx=ctx, block_size=64,
                attn_impl=impl))
            # prefill in splitfuse chunks, serially admitted: seq u's TTFT is
            # the wall-clock from bench start to its first generated token
            t0 = time.perf_counter()
            seeds, ttfts = [], []
            for u in uids:
                pos = 0
                while pos < prompt_len:
                    logits = eng.put([u], [prompts[u][pos:pos + chunk]])
                    pos += chunk
                seeds.append(int(jnp.argmax(logits[0])))
                ttfts.append(time.perf_counter() - t0)
            prefill_t = time.perf_counter() - t0
            # decode: the FUSED on-device loop (one compiled program for the
            # whole window — sampling on device, no host round trip per
            # token), plus the host-driven put() loop for comparison
            # (launch-latency bound)
            toks = eng.decode_batch(uids, seeds, decode_steps)  # compile
            t0 = time.perf_counter()
            toks = eng.decode_batch(uids, [int(t) for t in toks[-1]],
                                    decode_steps)
            decode_t = time.perf_counter() - t0
            stepwise = _stepwise_decode_probe(eng, uids, toks[-1],
                                              decode_steps)
            eng.flush(uids)
            fused = n_seqs * decode_steps / decode_t
            ttfts_s = sorted(ttfts)
            results[impl] = {
                "prefill_tok_s": round(n_seqs * prompt_len / prefill_t, 1),
                "decode_tok_s": round(fused, 2),
                "decode_stepwise_tok_s": round(stepwise, 2),
                "fused_vs_stepwise": round(fused / stepwise, 2),
                "ttft_p50_ms": round(ttfts_s[len(ttfts_s) // 2] * 1e3, 1),
                "ttft_p95_ms": round(ttfts_s[min(len(ttfts_s) - 1,
                                     int(len(ttfts_s) * 0.95))] * 1e3, 1),
            }
            log(f"{impl}: prefill {results[impl]['prefill_tok_s']} tok/s, "
                f"decode {results[impl]['decode_tok_s']} tok/s fused / "
                f"{results[impl]['decode_stepwise_tok_s']} stepwise "
                f"@ctx={ctx} seqs={n_seqs}")
        except Exception as exc:  # noqa: BLE001
            results[impl] = {"error": str(exc)[-200:]}
            log(f"{impl}: FAILED {str(exc)[:160]}")

    paged = results.get("paged", {}).get("decode_tok_s", 0.0) or 0.0
    gather = results.get("gather", {}).get("decode_tok_s", 0.0) or 0.0
    extra = {"ctx": ctx, "chunk": chunk, "n_seqs": n_seqs,
             "results": results, "backend": jax.default_backend()}
    failed = [impl for impl, r in results.items() if "error" in r]
    if failed:
        extra["error"] = f"attention path(s) {failed} raised"
    emit("serving_decode_tokens_per_sec", paged, "tokens/s",
         round(paged / gather, 3) if gather else 0.0, extra)


def run_serving_load_bench(on_tpu: bool) -> None:
    """FastGen-style load benchmark (BASELINE's north-star
    serving metric): N concurrent request streams through the continuous-
    batching engine → req/s + p50/p95 TTFT + SLA-miss rate.

    Two phases per the engine's real serving loop:
      1. admission/prefill — schedule() packs SplitFuse chunks (pending
         decodes first, then prompt chunks up to the token budget) through
         put(); each stream's TTFT is the wall-clock from benchmark start to
         its first generated token.
      2. decode — once every stream is decoding, fused decode_batch windows
         (device-resident multi-step loop) carry all streams to completion.

    Reference methodology: blogs/deepspeed-fastgen/README.md:163 (SLA-curve
    benchmark over concurrent clients); the engine analogue is
    deepspeed/inference/v2/engine_v2.py put/query/flush + MII scheduling.

    Env: DSTPU_BENCH_CONC (streams), DSTPU_BENCH_CTX, DSTPU_BENCH_PROMPT,
    DSTPU_BENCH_DECODE (tokens per stream), DSTPU_BENCH_CHUNK (token budget),
    DSTPU_BENCH_SLA_MS (TTFT SLA threshold, default 2000)."""
    import deepspeed_tpu  # noqa: F401
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2,
        RaggedInferenceEngineConfig,
    )
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
    from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

    initialize_mesh(TopologyConfig(), force=True)
    conc = env_int("DSTPU_BENCH_CONC", 16 if on_tpu else 4)
    ctx = env_int("DSTPU_BENCH_CTX", 8192 if on_tpu else 256)
    prompt_len = env_int("DSTPU_BENCH_PROMPT",
                         min(1024, ctx // 2) if on_tpu else 48)
    decode_n = env_int("DSTPU_BENCH_DECODE", 64 if on_tpu else 16)
    chunk = env_int("DSTPU_BENCH_CHUNK", 512 if on_tpu else 32)
    sla_ms = float(os.environ.get("DSTPU_BENCH_SLA_MS", "2000"))
    if on_tpu:
        # ~1B-param config: bench at the operating point, not a toy
        # shape
        cfg = TransformerConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_layers=16, num_heads=16, num_kv_heads=8, max_seq_len=ctx,
            use_flash=True)
    else:
        cfg = TransformerConfig(vocab_size=256, hidden_size=64,
                                intermediate_size=128, num_layers=2,
                                num_heads=4, num_kv_heads=2, max_seq_len=ctx,
                                use_flash=False)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    # KV pool sized to the workload, not max_seqs*max_ctx (64 streams at a
    # full 8k budget would be a 30GB+ pool; actual use is prompt+decode)
    # headroom: fused windows overshoot the leader by up to 31 tokens and
    # the stepwise probe appends a few more
    per_seq_blocks = -(-(prompt_len + decode_n + 64) // 64) + 1
    eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
        max_tokens=chunk, max_seqs=conc, max_ctx=ctx, block_size=64,
        num_blocks=(conc + 1) * per_seq_blocks,
        attn_impl=os.environ.get("DSTPU_BENCH_ATTN", "paged")))
    log(f"load bench: {model.num_params()/1e6:.0f}M params, {conc} streams, "
        f"prompt {prompt_len}, decode {decode_n}, chunk {chunk}, ctx {ctx}")

    rng = np.random.default_rng(0)
    uids = list(range(conc))
    prompts = {u: rng.integers(1, cfg.vocab_size, size=prompt_len).tolist()
               for u in uids}

    # warmup: compile the put step and the decode window on a throwaway uid
    w = eng.put([conc], [prompts[0][:chunk]])
    eng.decode_batch([conc], [int(jnp.argmax(w[0]))], steps=8)
    eng.flush([conc])
    jax.block_until_ready(eng.kv.pages)

    pending = {u: list(prompts[u]) for u in uids}
    produced = {u: [] for u in uids}
    ttft = {}
    t0 = time.perf_counter()

    # ---- phase 1: admission + SplitFuse prefill (TTFT clock) ------------ #
    while len(ttft) < conc:
        batch = eng.schedule({u: t for u, t in pending.items() if t})
        logits = eng.put([u for u, _ in batch], [t for _, t in batch])
        toks = np.asarray(jnp.argmax(logits[:len(batch)], axis=-1))
        now = time.perf_counter()
        for row, (uid, chnk) in enumerate(batch):
            pending[uid] = pending[uid][len(chnk):]
            if pending[uid]:
                continue                      # mid-prompt chunk
            tok = int(toks[row])
            produced[uid].append(tok)
            if uid not in ttft:
                ttft[uid] = now - t0
            pending[uid] = [tok]
    prefill_done = time.perf_counter()

    # ---- phase 2: fused decode windows until EVERY stream completes
    # (laggards that prefilled late drive the loop; the leader overshooting
    # a few tokens is extra measured work, not missing work).  The window
    # size is FIXED so the loop compiles once and every later window rides
    # the compile cache + device-resident metadata resume; the steady-state
    # fused tok/s excludes the first (compiling) window. ------------------ #
    win = min(32, max(8, decode_n // 2))
    window_times = []
    while min(len(produced[u]) for u in uids) < decode_n:
        seeds = [produced[u][-1] for u in uids]
        tw = time.perf_counter()
        toks = eng.decode_batch(uids, seeds, win)
        window_times.append(time.perf_counter() - tw)
        for col, u in enumerate(uids):
            produced[u].extend(int(t) for t in toks[:, col])
    total_t = time.perf_counter() - t0
    steady = window_times[1:] or window_times
    decode_fused_tok_s = (len(steady) * win * conc / sum(steady)
                          if steady else 0.0)
    # stepwise put() probe (outside the timed request window)
    probe_steps = 4
    decode_stepwise_tok_s = _stepwise_decode_probe(
        eng, uids, [produced[u][-1] for u in uids], probe_steps)
    eng.flush(uids)
    lens = sorted(len(p) for p in produced.values())
    assert lens[0] >= decode_n, f"stream under-served: {lens[0]}<{decode_n}"

    ttfts = sorted(ttft.values())
    p50 = ttfts[len(ttfts) // 2] * 1e3
    p95 = ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.95))] * 1e3
    req_s = conc / total_t
    out_tok_s = sum(len(p) for p in produced.values()) / total_t
    sla_miss = sum(1 for t in ttfts if t * 1e3 > sla_ms) / len(ttfts)
    log(f"load: {req_s:.3f} req/s, {out_tok_s:.1f} out tok/s, "
        f"TTFT p50 {p50:.0f}ms p95 {p95:.0f}ms, sla_miss {sla_miss:.2f}")
    # north star: FastGen serves Llama-2-70B at 1.36 req/s on 4×A100-80G
    # (blogs/deepspeed-fastgen/README.md:139); vs_baseline is req/s per chip
    # against that bar scaled by nothing — an honest absolute comparison is
    # impossible across model sizes, so report req/s with the workload shape
    # in extra and track round-over-round movement instead.
    emit("serving_requests_per_sec", round(req_s, 3), "req/s",
         round(req_s / 1.36, 3),
         {"concurrency": conc, "prompt_len": prompt_len,
          "decode_tokens": decode_n, "chunk": chunk, "ctx": ctx,
          "ttft_p50_ms": round(p50, 1), "ttft_p95_ms": round(p95, 1),
          "sla_ms": sla_ms, "sla_miss_rate": round(sla_miss, 3),
          "output_tok_per_sec": round(out_tok_s, 1),
          "decode_tok_s_fused": round(decode_fused_tok_s, 1),
          "decode_tok_s_stepwise": round(decode_stepwise_tok_s, 1),
          "fused_vs_stepwise": round(
              decode_fused_tok_s / decode_stepwise_tok_s, 2)
          if decode_stepwise_tok_s else 0.0,
          "decode_resume_hits": eng.decode_resume_hits,
          "tokens_per_stream_min_max": [lens[0], lens[-1]],
          "prefill_phase_s": round(prefill_done - t0, 2),
          "total_s": round(total_t, 2),
          "model_params": model.num_params(),
          "attn_impl": eng.config.attn_impl,
          "backend": jax.default_backend()})


def run_decode_sweep(on_tpu: bool) -> None:
    """DSTPU_BENCH_MODE=decode_sweep — paged-vs-gather × seqs × ctx decode
    grid for kernel tuning (CPU-safe).

    Context is FABRICATED (KV blocks allocated, pages filled with random
    values) so the sweep measures decode, not prefill: a prefill of every
    grid point would dominate the sweep's wall clock and add nothing to
    decode tuning.  Per point it times a fused device-resident decode
    window in the steady state (second window, device-side metadata resume)
    and a short stepwise put() loop (one host round trip per token) — the
    two axes the serving fast path optimizes.

    Spec-dec axis: per grid point the sweep also measures speculative
    decoding (drafter ∈ {off, ngram} × K ∈ DSTPU_BENCH_SPEC_K, default
    2,4,8) on the paged engine.  'off' is the vanilla fused window already
    measured; for 'ngram' the point first runs a vanilla warmup window so
    the stream settles into the model's own repetition (tiny greedy
    streams are attractor-heavy — the repetition-rich serving workload
    spec-dec targets) and the drafter has history to match, then times
    verify windows end to end (drafting + verify pass).  Reported per
    point: acceptance rate and effective-vs-vanilla tok/s; grid minima /
    maxima land in the emitted extra.

    Env: DSTPU_BENCH_SWEEP_SEQS / DSTPU_BENCH_SWEEP_CTX (comma lists),
    DSTPU_BENCH_STEPS (fused window length), DSTPU_BENCH_SPEC_K."""
    import deepspeed_tpu  # noqa: F401
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2,
        RaggedInferenceEngineConfig,
    )
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
    from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

    initialize_mesh(TopologyConfig(), force=True)

    def env_list(name, default):
        raw = os.environ.get(name)
        return [int(x) for x in raw.split(",")] if raw else default

    # CPU floor: below ~4 seqs / 512 ctx every impl is dispatch-noise-bound
    # on the sim and the comparison measures nothing
    seqs_grid = env_list("DSTPU_BENCH_SWEEP_SEQS",
                         [8, 16, 32] if on_tpu else [4, 8])
    ctx_grid = env_list("DSTPU_BENCH_SWEEP_CTX",
                        [1024, 8192] if on_tpu else [512, 1024])
    steps = env_int("DSTPU_BENCH_STEPS", 32 if on_tpu else 16)
    probe_steps = min(steps, 8 if on_tpu else 4)
    spec_ks = env_list("DSTPU_BENCH_SPEC_K", [2, 4, 8])
    # spec engine KV budget per K (the model's max_seq_len must cover it):
    # bucket-warmup run of 2k+4 steps (+ up to k overshoot) plus the timed
    # run of `steps` (+ up to k overshoot) — all extending the SAME
    # sequences across the K loop inside _decode_sweep_spec_point
    spec_extra = sum(steps + 4 * k + 8 for k in spec_ks) + 32
    max_ctx_pt = max(ctx_grid) + 2 * steps + probe_steps + 18 + spec_extra
    if on_tpu:
        cfg = TransformerConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_layers=8, num_heads=16, num_kv_heads=8,
            max_seq_len=max_ctx_pt, use_flash=True)
    else:
        cfg = TransformerConfig(vocab_size=256, hidden_size=64,
                                intermediate_size=128, num_layers=2,
                                num_heads=4, num_kv_heads=2,
                                max_seq_len=max_ctx_pt, use_flash=False)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))

    table = []
    for n_seqs in seqs_grid:
        for ctx in ctx_grid:
            point = {"seqs": n_seqs, "ctx": ctx}
            for impl in ("paged", "gather"):
                try:
                    budget = ctx + 2 * steps + probe_steps + 18
                    eng = InferenceEngineV2(
                        model, params, RaggedInferenceEngineConfig(
                            max_tokens=max(64, n_seqs), max_seqs=n_seqs,
                            max_ctx=budget, block_size=64,
                            num_blocks=n_seqs * -(-budget // 64) + 2,
                            attn_impl=impl))
                    uids = list(range(n_seqs))
                    sm = eng.state_manager
                    for u in uids:             # fabricate ctx tokens of KV
                        seq = sm.get_or_create_sequence(u)
                        assert sm.maybe_allocate_kv(seq, ctx), "pool sized"
                        seq.in_flight_tokens = ctx
                        seq.post_forward()
                    pages = eng.kv.pages
                    eng.kv.update((jax.random.normal(
                        jax.random.PRNGKey(1), pages.shape, jnp.float32)
                        * 0.1).astype(pages.dtype))
                    seeds = [1] * n_seqs
                    toks = eng.decode_batch(uids, seeds, steps)  # compile
                    t0 = time.perf_counter()
                    toks = eng.decode_batch(uids, [int(t) for t in toks[-1]],
                                            steps)
                    fused_t = time.perf_counter() - t0
                    stepwise = _stepwise_decode_probe(eng, uids, toks[-1],
                                                      probe_steps)
                    point.update(_kv_point_stats([eng]))
                    eng.flush(uids)
                    point[impl] = {
                        "fused_tok_s":
                            round(n_seqs * steps / fused_t, 2),
                        "stepwise_tok_s": round(stepwise, 2),
                    }
                except Exception as exc:  # noqa: BLE001
                    point[impl] = {"error": str(exc)[-200:]}
                    log(f"seqs={n_seqs} ctx={ctx} {impl}: FAILED "
                        f"{str(exc)[:160]}")
            pf = point.get("paged", {}).get("fused_tok_s")
            gf = point.get("gather", {}).get("fused_tok_s")
            ps = point.get("paged", {}).get("stepwise_tok_s")
            if pf and gf:
                point["paged_vs_gather"] = round(pf / gf, 3)
            if pf and ps:
                point["fused_vs_stepwise"] = round(pf / ps, 2)
            # ---- spec-dec axis: drafter ∈ {off=vanilla fused, ngram} × K.
            # vanilla fused tok/s is data-independent (same ops per step),
            # so the grid point's fused window is the honest baseline for
            # the repetition-heavy spec workload at the same ctx; compare
            # like lowerings: paged on TPU, gather (XLA) on the CPU sim —
            # interpret-mode Pallas is a correctness tool, not a perf path
            base_impl = "paged" if on_tpu else "gather"
            base = point.get(base_impl, {}).get("fused_tok_s")
            if base:
                try:
                    point["spec"] = _decode_sweep_spec_point(
                        model, n_seqs, ctx, steps, spec_ks, base, base_impl)
                except Exception as exc:  # noqa: BLE001
                    point["spec"] = {"error": str(exc)[-200:]}
                    log(f"seqs={n_seqs} ctx={ctx} spec: FAILED "
                        f"{str(exc)[:160]}")
            # ---- host-tier axis: swap on/off at an undersized pool (once
            # per ctx — the scenario's pool is sized by ctx, not n_seqs)
            if n_seqs == seqs_grid[0]:
                try:
                    point["swap"] = _decode_sweep_swap_point(
                        model, params, ctx, base_impl)
                except Exception as exc:  # noqa: BLE001
                    point["swap"] = {"error": str(exc)[-200:]}
                    log(f"ctx={ctx} swap: FAILED {str(exc)[:160]}")
            table.append(point)
            log(f"seqs={n_seqs} ctx={ctx}: paged {pf} vs gather {gf} "
                f"fused tok/s (x{point.get('paged_vs_gather', '?')}), "
                f"fused/stepwise x{point.get('fused_vs_stepwise', '?')}")
            sw = point.get("swap") or {}
            if "swap_on" in sw:
                log(f"  host tier ctx={ctx}: off "
                    f"{sw['swap_off']['tok_s']} vs on "
                    f"{sw['swap_on']['tok_s']} tok/s, hit_rate "
                    f"{sw['swap_on']['swap_hit_rate']}, avoided "
                    f"{sw['swap_on']['avoided_recompute_tokens']} tokens, "
                    f"streams_equal={sw['streams_equal']}")
            for kk, sp in sorted((point.get("spec") or {}).items()):
                if isinstance(sp, dict) and "acceptance_rate" in sp:
                    log(f"  spec ngram k={sp['k']}: acceptance "
                        f"{sp['acceptance_rate']}, effective "
                        f"{sp['effective_tok_s']} tok/s "
                        f"(x{sp['effective_vs_vanilla']} vs vanilla fused)")

    swap_pts = [p["swap"] for p in table
                if isinstance(p.get("swap"), dict) and "swap_on" in p["swap"]]
    swap_summary = {}
    if swap_pts:
        hits = [sp["swap_on"]["swap_hit_rate"] for sp in swap_pts
                if sp["swap_on"].get("swap_hit_rate") is not None]
        swap_summary = {
            "swap_points": len(swap_pts),
            "swap_min_hit_rate": round(min(hits), 4) if hits else None,
            "swap_avoided_recompute_tokens": sum(
                int(sp["swap_on"].get("avoided_recompute_tokens") or 0)
                for sp in swap_pts),
            "swap_streams_equal_everywhere": all(
                sp.get("streams_equal") for sp in swap_pts),
        }
        log(f"host tier: {swap_summary['swap_points']} A/B points, min "
            f"hit_rate {swap_summary['swap_min_hit_rate']}, avoided "
            f"{swap_summary['swap_avoided_recompute_tokens']} recompute "
            f"tokens, streams_equal_everywhere="
            f"{swap_summary['swap_streams_equal_everywhere']}")
    ratios = [p["paged_vs_gather"] for p in table if "paged_vs_gather" in p]
    overhead = [p["fused_vs_stepwise"] for p in table
                if "fused_vs_stepwise" in p]
    best = max((p.get("paged", {}).get("fused_tok_s") or 0.0 for p in table),
               default=0.0)
    spec_pts = [sp for p in table for sp in (p.get("spec") or {}).values()
                if isinstance(sp, dict) and "acceptance_rate" in sp]
    spec_summary = {}
    if spec_pts:
        evv = [sp["effective_vs_vanilla"] for sp in spec_pts]
        acc = [sp["acceptance_rate"] for sp in spec_pts]
        spec_summary = {
            "spec_points": len(spec_pts),
            "spec_min_acceptance": round(min(acc), 4),
            "spec_max_acceptance": round(max(acc), 4),
            "spec_min_effective_vs_vanilla": round(min(evv), 3),
            "spec_max_effective_vs_vanilla": round(max(evv), 3),
            # the acceptance bar: some (drafter, K) point must BEAT the
            # vanilla fused window on the repetition-heavy workload
            "spec_beats_vanilla_somewhere": max(evv) > 1.0,
        }
        log(f"spec-dec: effective-vs-vanilla in "
            f"[{spec_summary['spec_min_effective_vs_vanilla']}, "
            f"{spec_summary['spec_max_effective_vs_vanilla']}], "
            f"acceptance in [{spec_summary['spec_min_acceptance']}, "
            f"{spec_summary['spec_max_acceptance']}]")
    emit("serving_decode_sweep_tok_per_s", best, "tokens/s",
         round(min(ratios), 3) if ratios else 0.0,
         {"sweep": table, "steps": steps, "probe_steps": probe_steps,
          "paged_beats_gather_everywhere":
              bool(ratios) and min(ratios) > 1.0,
          "min_paged_vs_gather": round(min(ratios), 3) if ratios else None,
          "min_fused_vs_stepwise":
              round(min(overhead), 2) if overhead else None,
          "spec_ks": spec_ks, **spec_summary, **swap_summary,
          "backend": jax.default_backend()})


def _decode_sweep_swap_point(model, params, ctx, impl):
    """Host-tier A/B at one grid point (decode_sweep helper).

    An undersized KV pool forces the lifecycle scheduler to preempt a
    low-priority stream under a higher-priority burst; with the tier OFF
    the resume is a prefill recompute, with the tier ON it is a
    swap-out/swap-in (H2D copy + page-table patch).  Streams must match
    bit-exactly between the arms; the swap columns report what the tier
    bought (hit rate, recompute tokens avoided) and what it cost (A/B
    wall-clock tok/s)."""
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2,
        RaggedInferenceEngineConfig,
    )
    from deepspeed_tpu.inference.v2.lifecycle import (LifecycleScheduler,
                                                      ServeRequest)

    bs = 8
    vic_prompt = min(max(ctx // 16, 24), 48)
    vic_new = 16
    comp_prompt, comp_new = vic_prompt // 2, 12
    vic_blocks = -(-(vic_prompt + vic_new) // bs)
    comp_blocks = -(-(comp_prompt + comp_new) // bs)
    # the victim plus four competitors fit, the fifth forces a preemption
    pool = vic_blocks + 4 * comp_blocks + 1

    def run(tier_mb):
        eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
            max_tokens=32, max_seqs=8,
            max_ctx=vic_prompt + vic_new + bs, block_size=bs,
            num_blocks=pool, dtype=jnp.float32, attn_impl=impl,
            host_tier_mb=tier_mb))
        sched = LifecycleScheduler(eng, max_queue=64, window_steps=4,
                                   kv_high_watermark=0.5)
        t0 = time.perf_counter()
        sched.submit(ServeRequest(
            uid=0, prompt=[(7 * i) % 250 + 1 for i in range(vic_prompt)],
            max_new_tokens=vic_new, priority=0))
        sched.step()
        sched.step()
        for uid in range(1, 6):
            sched.submit(ServeRequest(
                uid=uid,
                prompt=[(uid * 13 + i) % 250 + 1 for i in range(comp_prompt)],
                max_new_tokens=comp_new, priority=1))
        sched.run_until_idle()
        wall = time.perf_counter() - t0
        streams = {u: list(sched.request(u).produced) for u in range(6)}
        toks = sum(len(v) for v in streams.values())
        stats = eng.kv_swap.stats() if eng.kv_swap is not None else {}
        return {
            "tok_s": round(toks / wall, 2),
            "preempted": sched.counters.get("serving/preempted", 0),
            "swap_out": sched.counters.get("serving/swap_out", 0),
            "swap_in": sched.counters.get("serving/swap_in", 0),
            "swap_hit_rate": stats.get("hit_rate"),
            "avoided_recompute_tokens":
                stats.get("avoided_recompute_tokens", 0),
        }, streams

    off, off_streams = run(0.0)
    on, on_streams = run(8.0)
    return {"swap_off": off, "swap_on": on,
            "streams_equal": off_streams == on_streams,
            "pool_blocks": pool}


def _decode_sweep_spec_point(model, n_seqs, ctx, steps, spec_ks,
                             vanilla_fused_tok_s, base_impl):
    """One grid point's spec-dec measurements (decode_sweep helper).

    The spec workload is REPETITION-HEAVY by construction — a periodic
    prompt prefilled for real (chunked ``put``; a few forwards per
    sequence, cheap even on the CPU sim) so the greedy continuation is
    itself repetitive, the serving shape speculative decoding targets
    (templated text, code, self-repeating generations).  Fabricated
    random KV would measure the drafter against an arbitrary stream and
    report only the rejection floor.  Per K the n-gram drafter runs
    verify windows timed end to end (host drafting + ragged verify pass
    + accept/rollback); a short warmup run first compiles the verify
    bucket so tok/s excludes XLA compile, mirroring how the vanilla
    point times its second window.
    """
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2,
        RaggedInferenceEngineConfig,
    )
    from deepspeed_tpu.inference.v2.speculative import (
        NGramDrafter,
        speculative_decode,
    )

    budget = ctx + sum(steps + 4 * k + 8 for k in spec_ks) + 32
    chunk = 256
    eng = InferenceEngineV2(model, model.init_params(jax.random.PRNGKey(0)),
                            RaggedInferenceEngineConfig(
        max_tokens=max(chunk, n_seqs * (max(spec_ks) + 1)), max_seqs=n_seqs,
        max_ctx=budget, block_size=64,
        num_blocks=n_seqs * -(-budget // 64) + 2, attn_impl=base_impl))
    uids = list(range(n_seqs))
    prompt = ([17, 29, 142, 77] * -(-ctx // 4))[:ctx]
    hist, cur = {}, {}
    for u in uids:
        logits = None
        for i in range(0, ctx, chunk):
            logits = eng.put([u], [prompt[i:i + chunk]])
        cur[u] = int(jnp.argmax(logits[0]))
        hist[u] = list(prompt) + [cur[u]]

    out = {}
    for k in spec_ks:
        drafter = NGramDrafter()
        # bucket warmup: draft length ramps from 0 to k as history
        # accumulates, so run enough steps that the FULL-k verify bucket
        # compiles here, keeping XLA compile out of the timed windows
        warm_out, _ = speculative_decode(
            eng, drafter, uids, [cur[u] for u in uids],
            [hist[u] for u in uids], steps=2 * k + 4, k=k)
        for u in uids:
            hist[u].extend(warm_out[u])
            cur[u] = hist[u][-1]
        _, stats = speculative_decode(
            eng, drafter, uids, [cur[u] for u in uids],
            [hist[u] for u in uids], steps=steps, k=k)
        wall = stats["draft_s"] + stats["verify_s"]
        eff = stats["emitted"] / wall if wall > 0 else 0.0
        out[f"k{k}"] = {
            "k": k, "drafter": "ngram",
            "acceptance_rate": stats["acceptance_rate"],
            "windows": stats["windows"],
            "effective_tok_s": round(eff, 2),
            "vanilla_fused_tok_s": round(vanilla_fused_tok_s, 2),
            "effective_vs_vanilla": round(eff / vanilla_fused_tok_s, 3)
            if vanilla_fused_tok_s else 0.0,
            "draft_overhead_frac": round(stats["draft_s"] / wall, 4)
            if wall > 0 else 0.0,
        }
    eng.flush(uids)
    return out


def run_flash_sweep(on_tpu: bool) -> None:
    """Sweep flash-attention block sizes; one JSON line with the best config
    and the full table in extra (recorded for kernel tuning)."""
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    B, H, hd = 4, 16, 128
    S = env_int("DSTPU_BENCH_SEQ", 2048 if on_tpu else 256)
    steps = env_int("DSTPU_BENCH_STEPS", 20 if on_tpu else 2)
    blocks = [128, 256, 512, 1024] if on_tpu else [128, 256]
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(key, (B, S, H, hd), jnp.bfloat16)
    v = jax.random.normal(key, (B, S, H, hd), jnp.bfloat16)

    results = []
    for bq in blocks:
        for bk in blocks:
            if bq > S or bk > S:
                continue
            # Device-side loop with the output CHAINED into the next step's
            # query: a host loop of identical dispatches can be deduplicated
            # or pipelined by the runtime (it once measured a >peak "3.8
            # PFLOP/s" artifact), while the data dependence forces each of
            # the `steps` kernels to actually execute back-to-back.
            def sweep_fn(q, k, v, bq=bq, bk=bk):
                def body(_, qq):
                    o = flash_attention(qq, k, v, causal=True,
                                        block_q=bq, block_k=bk)
                    return o.astype(qq.dtype)
                return jax.lax.fori_loop(0, steps, body, q)

            fn = jax.jit(sweep_fn)
            try:
                jax.block_until_ready(fn(q, k, v))  # compile
                t0 = time.perf_counter()
                jax.block_until_ready(fn(q, k, v))
                dt = (time.perf_counter() - t0) / steps
            except Exception as exc:  # noqa: BLE001
                log(f"bq={bq} bk={bk}: FAILED {str(exc)[:120]}")
                continue
            # causal ≈ half the 4*B*H*S²*hd matmul flops (fwd: QK^T + PV)
            flops = 2 * B * H * S * S * hd
            tflops = flops / dt / 1e12
            results.append({"block_q": bq, "block_k": bk,
                            "ms": round(dt * 1e3, 3),
                            "tflops": round(tflops, 1)})
            log(f"bq={bq} bk={bk}: {dt*1e3:.2f} ms, {tflops:.1f} TF/s")
    if not results:
        emit("flash_attention_tflops", 0.0, "TFLOP/s", 0.0,
             {"error": "all configs failed", "seq_len": S})
        return
    best = max(results, key=lambda r: (r["tflops"], -r["ms"]))
    emit("flash_attention_tflops", best["tflops"], "TFLOP/s",
         round(best["tflops"] / (peak_flops_per_chip() / 1e12), 4),
         {"best": best, "sweep": results, "seq_len": S,
          "backend": jax.default_backend()})


def run_pipeline_bench(on_tpu: bool) -> None:
    """Pipeline bubble measurement: pp=2 schedules on the 8-device
    CPU-sim mesh.

    Method: the bubble is a STATIC schedule property — the lockstep tick
    scan's trip count in the compiled program (runtime/pipe/engine.py:
    gpipe T=M+pp-1 fwd ticks; 1f1b T=M+2(pp-1) full ticks; interleaved V:
    T=off_max+2(V*pp-1)+1 at 1/V per-tick cost).  The bench verifies the
    modeled T appears as a scan length in the actual jaxpr and reports
    bubble = 1 - ideal_ticks/T.  Wall clock per step is recorded as
    secondary trend data only: on the CPU-sim mesh, runtime dispatch
    overhead dominates the constant term, so a wall-clock fit cannot
    resolve 1-3 ticks of bubble (measured: fit intercept ~10-15 ticks).

    Runs on the CPU-sim mesh by design: the number is a schedule property
    counted from the program, not a kernel throughput claim."""
    import dataclasses

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import TransformerConfig
    from deepspeed_tpu.runtime.pipe.module import PipelinedCausalLM
    from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

    pp = env_int("DSTPU_BENCH_PP", 2)
    seq = env_int("DSTPU_BENCH_SEQ", 64)
    steps = env_int("DSTPU_BENCH_STEPS", 3)
    M = env_int("DSTPU_BENCH_MICRO", 8)
    n_dev = len(jax.devices())
    if n_dev < pp * 2:
        emit("pipeline_bubble_fraction", 0.0, "fraction", 0.0,
             {"error": f"need >= {pp*2} devices, have {n_dev} "
                       "(run with xla_force_host_platform_device_count)"})
        return
    cfg = dataclasses.replace(
        TransformerConfig.tiny(use_flash=False),
        num_layers=env_int("DSTPU_BENCH_LAYERS", 8), hidden_size=128,
        intermediate_size=256, num_heads=4, num_kv_heads=4, max_seq_len=seq)
    rng = np.random.default_rng(0)

    from deepspeed_tpu.utils.jaxpr_utils import scan_lengths

    results = {}
    for name, sched_cfg, v in (("gpipe", {"schedule": "gpipe"}, 1),
                               ("1f1b", {"schedule": "1f1b"}, 1),
                               ("1f1b_v2", {"schedule": "1f1b",
                                            "virtual_stages": 2}, 2)):
        topo = initialize_mesh(TopologyConfig(pipe=pp), force=True)
        model = PipelinedCausalLM(cfg, topology=topo)
        params = model.init_params(jax.random.PRNGKey(0))
        dp = n_dev // pp
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config={"train_micro_batch_size_per_gpu": 2,
                    "gradient_accumulation_steps": M,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "pipeline": sched_cfg,
                    "zero_optimization": {"stage": 0}},
            topology=topo)
        batch = {"input_ids": jnp.asarray(
            rng.integers(0, cfg.vocab_size, size=(2 * M * dp, seq)),
            jnp.int32)}
        # ---- exact: the tick scan's static trip count ----------------- #
        # the tick scan is the (only) one whose length grows with M; the
        # layer scan is M-independent.  Per-tick work is one microbatch
        # through this rank's layers (1/v of them for interleaved V).
        lens = scan_lengths(
            lambda b: eng._build_train_batch_fn()(eng.state, b), batch)
        vpp = v * pp
        if name == "gpipe":
            # single fwd scan of M+pp-1 ticks (bwd replays it reversed)
            expect = [M + pp - 1]
            bubble = (pp - 1) / (M + pp - 1)
        else:
            # round-5 phase-split: warmup (vpp-1 F-only) + steady
            # (off_max+1 F+B) + drain (vpp-1 B-only); fill/drain ticks
            # cost half, so bubble time = (pp-1)/V full ticks over
            # M + (pp-1)/V  ->  fraction (pp-1)/(M*V + pp - 1)
            off_max = (M // pp - 1) * vpp + pp - 1 if v > 1 else M - 1
            expect = [vpp - 1, off_max + 1]
            bubble = (pp - 1) / (M * v + pp - 1)
        found = all(x in lens for x in expect)
        # ---- secondary: wall clock (CPU-sim; runtime overhead dominates
        # the constant term, recorded for trend only) ------------------- #
        wall = None
        if steps > 0:
            loss = eng.train_batch(batch)
            jax.block_until_ready(loss)
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = eng.train_batch(batch)
            jax.block_until_ready(loss)
            wall = (time.perf_counter() - t0) / steps
        results[name] = {
            "tick_scan_lengths_model": expect,
            "tick_scans_found_in_program": found,
            "all_scan_lengths": sorted(set(lens)),
            "bubble_fraction": round(bubble, 4),
            "wall_ms_per_step": round(wall * 1e3, 1) if wall else None,
        }
        log(f"{name}: scans={expect} (found={found}) bubble={bubble:.3f}")
    emit("pipeline_bubble_fraction",
         results["1f1b"]["bubble_fraction"], "fraction",
         round(results["1f1b_v2"]["bubble_fraction"] /
               max(results["1f1b"]["bubble_fraction"], 1e-9), 3),
         {"pp": pp, "micro_batches": M, "schedules": results, "seq": seq,
          "backend": jax.default_backend(),
          "note": "bubble from the compiled tick-scan trip count "
                  "(static schedule property); vs_baseline = V2/V1 "
                  "bubble ratio"})


def run_offload_bench(on_tpu: bool) -> None:
    """ZeRO-Offload / Twin-Flow step throughput: relative step time of
    pinned-host optimizer state (ratio 1.0) and Twin-Flow ratio 0.5 vs the
    all-HBM baseline — the first real validation of the host-stream step
    (the offload path had only ever run its no-op CPU branch)."""
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
    from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

    if on_tpu:
        cfg = TransformerConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_layers=8, num_heads=16, num_kv_heads=8, max_seq_len=1024,
            remat=True, use_flash=True)
        batch, steps = 8, 6
    else:
        cfg = TransformerConfig.tiny(use_flash=False)
        batch, steps = 4, 2

    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    data = {"input_ids": jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(batch, cfg.max_seq_len)),
        jnp.int32)}
    results = {}
    for name, offload in (("hbm", None),
                          ("host_ratio_1.0", {"device": "cpu", "ratio": 1.0}),
                          ("twinflow_0.5", {"device": "cpu", "ratio": 0.5})):
        topo = initialize_mesh(TopologyConfig(), force=True)
        zconf = {"stage": 2}
        if offload:
            zconf["offload_optimizer"] = offload
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model,
            # fresh buffers per engine: the train step donates its state, so
            # a shared params tree would be consumed by the first variant
            model_parameters=jax.tree.map(jnp.array, params),
            config={"train_micro_batch_size_per_gpu": batch,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                    "zero_optimization": zconf, "bf16": {"enabled": True}},
            topology=topo)
        loss = eng.train_batch(data)          # compile + warmup
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = eng.train_batch(data)
        jax.block_until_ready(loss)
        dt = (time.perf_counter() - t0) / steps
        results[name] = round(dt * 1e3, 1)
        log(f"offload={name}: {dt*1e3:.1f} ms/step")
    base = results.get("hbm", 1.0)
    emit("offload_step_ms", results.get("host_ratio_1.0", 0.0), "ms/step",
         round(base / max(results.get("host_ratio_1.0", 1e9), 1e-9), 4),
         {"results_ms": results, "model_params": model.num_params(),
          "backend": jax.default_backend()})


def run_overlap_sweep(on_tpu: bool) -> None:
    """Comm/compute overlap sweep (runtime/overlap/): step time per overlap
    config — eager baseline, deferred fused reduction, and the explicit
    hand-written wire with per-leaf vs bucketed exchange.  The headline is
    the best overlapped config's ms/step; vs_baseline is eager/best (>1 =
    overlap wins).  On CPU this measures schedule/launch-count effects on
    the 8-virtual-device sim — wire volume is identical by construction
    (grads are bit-exact across configs, test-asserted)."""
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
    from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

    if on_tpu:
        cfg = TransformerConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_layers=8, num_heads=16, num_kv_heads=8, max_seq_len=1024,
            remat=True, use_flash=True)
        batch, gas, steps = 8, 4, 6
    else:
        cfg = TransformerConfig.tiny(use_flash=False)
        batch, gas, steps = 2, 2, 2

    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    # global batch = micro * gas * dp (the default topology is pure DP)
    rows = batch * gas * max(len(jax.devices()), 1)
    data = {"input_ids": jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(rows, cfg.max_seq_len)),
        jnp.int32)}
    sweep = (
        ("eager", None),
        ("deferred", {"enabled": True, "bucket_bytes": 0}),
        ("explicit_per_leaf", {"enabled": True, "explicit_wire": True,
                               "bucket_bytes": 0}),
        ("explicit_bucketed", {"enabled": True, "explicit_wire": True,
                               "bucket_bytes": 4 * 1024 * 1024}),
    )
    results = {}
    for name, overlap in sweep:
        topo = initialize_mesh(TopologyConfig(), force=True)
        conf = {"train_micro_batch_size_per_gpu": batch,
                "gradient_accumulation_steps": gas,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "zero_optimization": {"stage": 2},
                "bf16": {"enabled": True}}
        if overlap is not None:
            conf["overlap"] = overlap
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model,
            model_parameters=jax.tree.map(jnp.array, params),
            config=conf, topology=topo)
        loss = eng.train_batch(data)          # compile + warmup
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = eng.train_batch(data)
        jax.block_until_ready(loss)
        dt = (time.perf_counter() - t0) / steps
        results[name] = round(dt * 1e3, 2)
        log(f"overlap={name}: {dt*1e3:.2f} ms/step "
            f"(deferred={eng._deferred_active})")
    eager = results.get("eager", 0.0)
    overlapped = {k: v for k, v in results.items() if k != "eager"}
    best_name = min(overlapped, key=overlapped.get) if overlapped else "eager"
    best = overlapped.get(best_name, eager)
    emit("overlap_step_ms", best, "ms/step",
         round(eager / max(best, 1e-9), 4),
         {"results_ms": results, "best_config": best_name,
          "gas": gas, "model_params": model.num_params(),
          "backend": jax.default_backend(),
          "n_devices": len(jax.devices())})


def run_comm_sweep(on_tpu: bool) -> None:
    """DSTPU_BENCH_MODE=comm_sweep — flat-vs-2hop × wire-format ×
    bucket-size grid over the production gradient-exchange seam
    (``runtime/comm/hierarchical.exchange_leaves`` / ``two_hop_allreduce``
    — the same functions comm_path's explicit wire calls), CPU-safe on the
    8-virtual-device sim like overlap_sweep/decode_sweep.

    Per point: ms/step of the jitted shard_map exchange plus
    predicted-vs-measured collective operand bytes (measured = jaxpr
    inspection via ``fused_wire.wire_ops``; predicted =
    ``hierarchical.predict_operand_bytes``).  The CollectiveAlgoSelector
    then picks a config twice per bucket size — analytically from the
    roofline table, and re-tuned from the measured table — and the emitted
    extra records whether the re-tuned pick is the measured-fastest
    (``selector_agrees``; the check_comm_sweep gate asserts it).

    Env: DSTPU_BENCH_SWEEP_MB (payload, default 8), DSTPU_BENCH_SWEEP_ALGOS,
    DSTPU_BENCH_SWEEP_WIRES, DSTPU_BENCH_SWEEP_BUCKETS_MB (comma lists),
    DSTPU_BENCH_SWEEP_STEPS, DSTPU_BENCH_SWEEP_SHARD (intra-slice size of
    the simulated 2-slice mesh), DSTPU_BENCH_SWEEP_FRAC (exposed-comm
    fraction fed to the analytic selection, default 0.5)."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.runtime.comm import hierarchical as hier
    from deepspeed_tpu.runtime.comm.fused_wire import (
        fused_quantized_allreduce, wire_ops)
    from deepspeed_tpu.runtime.comm_path import loco_partition_size
    from deepspeed_tpu.runtime.topology import (DATA, DATA_OUTER,
                                                TopologyConfig,
                                                compat_shard_map,
                                                initialize_mesh)
    from deepspeed_tpu.telemetry.metrics import MetricsRegistry

    n_dev = len(jax.devices())
    shard = env_int("DSTPU_BENCH_SWEEP_SHARD", max(n_dev // 2, 1))
    if n_dev > 1 and shard > 0 and n_dev % shard == 0 and n_dev // shard > 1:
        # simulate a 2-slice job: data_outer crosses "DCN"
        topo = initialize_mesh(TopologyConfig(zero_shard_size=shard),
                               force=True)
        topo.set_cross_slice_axes((DATA_OUTER,))
    else:
        topo = initialize_mesh(TopologyConfig(), force=True)
    data_axes = tuple(a for a in (DATA_OUTER, DATA) if topo.dims[a] > 1)
    if not data_axes:
        emit("comm_sweep_exchange_ms", 0.0, "ms/step", 0.0,
             {"error": "comm_sweep needs a multi-device mesh "
                       f"(found {n_dev} devices)"})
        return
    intra, inter = hier.hop_axes(topo, data_axes)
    n_i = int(np.prod([topo.dims[a] for a in intra])) if intra else 1
    n_x = int(np.prod([topo.dims[a] for a in inter])) if inter else 1
    n = n_i * n_x
    log(f"comm_sweep mesh {dict(topo.dims)} intra={intra}({n_i}) "
        f"inter={inter}({n_x})")

    mb = float(os.environ.get("DSTPU_BENCH_SWEEP_MB", "8"))
    total = max(int(mb * (1 << 20) / 4), 8192)
    # transformer-ish leaf mix: one big stacked-layer leaf, a few medium,
    # many small norm/bias leaves
    sizes = [total // 2, total // 4] + [total // 16] * 3 + \
        [max(total // 64, 256)] * 4
    rng_l = np.random.default_rng(0)
    leaves = [jnp.asarray(rng_l.normal(size=(s,)), jnp.float32)
              for s in sizes]
    payload = sum(int(x.size) * 4 for x in leaves)

    algos = [a for a in os.environ.get(
        "DSTPU_BENCH_SWEEP_ALGOS", "flat,2hop,fused_gemm").split(",") if a]
    if not (intra and inter):
        algos = [a for a in algos if a != "2hop"]
    wires = [w for w in os.environ.get(
        "DSTPU_BENCH_SWEEP_WIRES", "fp,int8,int4_loco").split(",") if w]
    buckets = [int(float(b) * (1 << 20)) for b in os.environ.get(
        "DSTPU_BENCH_SWEEP_BUCKETS_MB", "1,4").split(",") if b]
    steps = env_int("DSTPU_BENCH_SWEEP_STEPS", 3)
    manual = set(data_axes)

    def build(algo, wire, bucket):
        bits = hier.WIRE_BITS[wire]
        if wire == "int4_loco":
            errors = []
            for x in leaves:
                if algo == "2hop":
                    wlen, slen = hier.two_hop_loco_sizes(int(x.size), n_i,
                                                         n_x)
                else:
                    wlen = int(x.size)
                    slen = loco_partition_size(int(x.size), n)
                errors.append((jnp.zeros((wlen,), jnp.float32),
                               jnp.zeros((slen,), jnp.float32)))

            def body(ls, errs):
                outs, new_errs = [], []
                for g, (ew, es) in zip(ls, errs):
                    if algo == "2hop":
                        out, ne, nse = hier.two_hop_allreduce(
                            g, intra, inter, wire_bits=bits,
                            error=ew, server_error=es)
                    else:
                        out, ne, nse = fused_quantized_allreduce(
                            g, data_axes, bits=bits, error=ew,
                            server_error=es)
                    outs.append(out)
                    new_errs.append((ne, nse))
                return outs, new_errs

            mapped = compat_shard_map(
                body, mesh=topo.mesh, in_specs=(P(), P()),
                out_specs=(P(), P()), manual_axes=manual)
            return mapped, (leaves, errors)

        def body(ls):
            outs, _ = hier.exchange_leaves(
                ls, data_axes, intra, inter, algo, bits,
                bucket_bytes=bucket, n=n)
            return outs

        mapped = compat_shard_map(body, mesh=topo.mesh, in_specs=(P(),),
                                  out_specs=P(), manual_axes=manual)
        return mapped, (leaves,)

    points = []
    for algo in algos:
        for wire in wires:
            if algo == "fused_gemm" and wire == "int4_loco":
                # LoCo residual state rides the flat/2hop wires; the
                # fused-gemm epilogue schedule carries fp and int8 edges
                continue
            # the LoCo wire runs per-leaf (residual state per leaf), so
            # bucket size never reaches its program — measure it once and
            # record bucket_bytes=0 (bucket-independent) instead of
            # re-compiling an identical computation per bucket size
            for bucket in ([0] if wire == "int4_loco" else buckets):
                try:
                    mapped, args = build(algo, wire, bucket)
                    fn = jax.jit(mapped)
                    traced = jax.make_jaxpr(mapped)(*args)
                    measured_bytes = sum(
                        o["bytes"] for o in wire_ops(traced))
                    out = fn(*args)          # compile + warmup
                    jax.block_until_ready(out)
                    t0 = time.perf_counter()
                    for _ in range(steps):
                        out = fn(*args)
                    jax.block_until_ready(out)
                    dt = (time.perf_counter() - t0) / steps
                except Exception as exc:  # noqa: BLE001 — record, keep going
                    log(f"comm_sweep point {algo}/{wire}/{bucket}: "
                        f"FAILED {exc!r}")
                    points.append({"algo": algo, "wire": wire,
                                   "bucket_bytes": bucket,
                                   "error": str(exc)[-200:]})
                    continue
                predicted_bytes = hier.predict_operand_bytes(
                    payload, algo, wire, n_i, n_x)["total"]
                points.append({
                    "algo": algo, "wire": wire, "bucket_bytes": bucket,
                    "ms": round(dt * 1e3, 3),
                    "measured_wire_bytes": int(measured_bytes),
                    "predicted_wire_bytes": int(predicted_bytes),
                })
                log(f"comm_sweep {algo}/{wire} bucket={bucket>>20}MiB: "
                    f"{dt*1e3:.2f} ms, wire bytes measured="
                    f"{measured_bytes} predicted={int(predicted_bytes)}")

    ok = [p for p in points if "ms" in p]
    if not ok:
        emit("comm_sweep_exchange_ms", 0.0, "ms/step", 0.0,
             {"error": "every sweep point failed", "points": points})
        return

    sel = hier.CollectiveAlgoSelector.from_topology(
        topo, data_axes, allow_quantized=("int8" in wires),
        allow_loco=("int4_loco" in wires),
        allow_fused_gemm=("fused_gemm" in algos))
    frac = float(os.environ.get("DSTPU_BENCH_SWEEP_FRAC", "0.5"))
    selections = []
    for bucket in buckets:
        # bucket-independent (bucket_bytes=0, the per-leaf LoCo wire)
        # points join every bucket's table
        tbl = {f"{p['algo']}/{p['wire']}": p["ms"] for p in ok
               if p["bucket_bytes"] in (bucket, 0)}
        if not tbl:
            continue
        analytic = sel.select(bucket, exposed_comm_fraction=frac)
        retuned = sel.select(bucket, measured_ms=tbl)
        fastest = min(tbl, key=tbl.get)
        selections.append({
            "bucket_bytes": bucket,
            "analytic": f"{analytic.algo}/{analytic.wire}",
            "retuned": f"{retuned.algo}/{retuned.wire}",
            "measured_fastest": fastest,
            "selector_agrees":
                f"{retuned.algo}/{retuned.wire}" == fastest,
            "measured_ms": tbl,
        })

    # publish the re-tuned choice the way the overlap manager does
    reg = MetricsRegistry()
    final = selections[-1] if selections else None
    if final is not None:
        algo, wire = final["retuned"].split("/")
        reg.gauge("comm/algo_2hop").set(1.0 if algo == "2hop" else 0.0)
        reg.gauge("comm/algo_fused_gemm").set(
            1.0 if algo == "fused_gemm" else 0.0)
        reg.gauge("comm/wire_bits").set(float(hier.WIRE_BITS[wire]))
        reg.gauge("comm/predicted_exchange_ms").set(
            float(sel.predict_ms(final["bucket_bytes"], algo, wire)))
        reg.gauge("comm/predicted_wire_bytes").set(
            float(sel.predict_wire_bytes(final["bucket_bytes"], algo,
                                         wire)))

    base = min((p["ms"] for p in ok
                if p["algo"] == "flat" and p["wire"] == "fp"),
               default=None)
    best = min(ok, key=lambda p: p["ms"])
    emit("comm_sweep_exchange_ms", best["ms"], "ms/step",
         round((base or best["ms"]) / max(best["ms"], 1e-9), 4),
         {"points": points, "selections": selections,
          "payload_bytes": payload,
          "mesh": {k: int(v) for k, v in topo.dims.items()},
          "intra": list(intra), "inter": list(inter),
          "comm_gauges": reg.gauge_values(),
          "best_config": f"{best['algo']}/{best['wire']}",
          "backend": jax.default_backend(), "n_devices": n_dev})


def run_kernel_sweep(on_tpu: bool) -> None:
    """DSTPU_BENCH_MODE=kernel_sweep — per-kernel %-of-peak rooflines for
    the four Pallas kernel families (flash attention, decode paged
    attention, the PR-9 fused quantized wire, the fused-gemm matmul) on
    fabricated inputs, so kernel numbers come from ONE enforced table
    instead of ad-hoc per-mode timings (an earlier flash_sweep timing was
    rejected as implausible: above the chip's peak).

    Off-TPU the Pallas kernels run in interpreter mode (decode uses its
    dense bit-compatible lowering), so CPU-sim %-of-peak is a
    plumbing/structure gate against the CPU fallback peaks, not a speed
    claim — the on-chip run of the SAME table is the trustworthy number
    (ROADMAP S1/S4: not measured yet).  Emits the table in ``extra.kernels``
    plus the published ``kernels/*`` gauges; enforced tier-1 by
    ``tools/check_kernel_sweep.py``.

    Env: DSTPU_BENCH_KERNELS (comma subset of
    flash,decode_paged,fused_wire,fused_gemm), DSTPU_BENCH_KERNEL_STEPS.
    """
    from deepspeed_tpu.inference.v2.kernels.ragged_ops import (
        decode_attend_dense, decode_paged_attention)
    from deepspeed_tpu.kernels.fused_collective_matmul import (
        matmul_costs, rmsnorm_matmul, shard_major_matmul)
    from deepspeed_tpu.ops.quantizer.quantizer import (quant_pack_wire,
                                                       unpack_dequant_wire)
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention
    from deepspeed_tpu.profiling.roofline import (device_spec,
                                                  format_kernel_table,
                                                  kernel_roofline_report,
                                                  publish_kernel_gauges)
    from deepspeed_tpu.telemetry.metrics import MetricsRegistry

    steps = env_int("DSTPU_BENCH_KERNEL_STEPS", 2)
    wanted = [k for k in os.environ.get(
        "DSTPU_BENCH_KERNELS",
        "flash,decode_paged,fused_wire,fused_gemm").split(",") if k]
    rng = np.random.default_rng(0)
    spec = device_spec()

    def fab(shape, dtype=jnp.float32):
        return jnp.asarray(rng.normal(size=shape), dtype)

    # (name, build) — build returns (jitted_fn, args, flops, bytes); tiny
    # CPU-sim shapes (the gate budget is ~60 s incl. interpret overhead),
    # real shapes on TPU
    if on_tpu:
        B, S, H, KV, hd = 4, 2048, 16, 8, 128
        GM, GK, GN = 4096, 4096, 4096
        wire_elems = 16 << 20
        dS, dctx, dps, dNB = 16, 1024, 64, 16
    else:
        B, S, H, KV, hd = 1, 256, 2, 2, 64
        GM, GK, GN = 256, 256, 256
        wire_elems = 1 << 18
        dS, dctx, dps, dNB = 4, 128, 32, 4

    def build_flash():
        q = fab((B, S, H, hd))
        k = fab((B, S, KV, hd))
        v = fab((B, S, KV, hd))
        fn = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128))
        flops = 2.0 * B * H * S * S * hd * 2 * 0.5       # QKᵀ+PV, causal
        bytes_ = 4.0 * (q.size + k.size + v.size + q.size)
        return fn, (q, k, v), flops, bytes_

    def build_decode():
        pool = dS * dNB + 1
        pages = fab((pool, dps, 2 * KV, hd))
        q = fab((dS, H, hd))
        lens = jnp.full((dS,), dctx, jnp.int32)
        table = jnp.arange(1, dS * dNB + 1, dtype=jnp.int32
                           ).reshape(dS, dNB)
        kern = decode_paged_attention if on_tpu else decode_attend_dense
        fn = jax.jit(lambda q, p, ln, t: kern(q, p, ln, t,
                                              num_kv_heads=KV))
        flops = 4.0 * H * hd * dctx * dS
        bytes_ = 4.0 * dS * dctx * 2 * KV * hd           # the page walk
        return fn, (q, pages, lens, table), flops, bytes_

    def build_wire():
        x = fab((wire_elems,))

        def roundtrip(x):
            w, s = quant_pack_wire(x, 8, 256)
            return unpack_dequant_wire(w, s, 8)

        fn = jax.jit(roundtrip)
        flops = 4.0 * wire_elems                         # scale+round+mul
        bytes_ = 4.0 * wire_elems * 2 + wire_elems       # f32 in/out + wire
        return fn, (x,), flops, bytes_

    def build_gemm():
        x = fab((GM, GK))
        w = fab((GK, GN))
        sc = fab((GK,))
        # the fused-gemm family: shard-major epilogue matmul + the fused
        # RMSNorm+matmul — timed kernel-only (the exchange edge is the
        # comm_sweep's subject; this row answers "is the producing kernel
        # at peak")
        fn = jax.jit(lambda x, sc, w: rmsnorm_matmul(x, sc, w, 1e-5,
                                                     impl="pallas")
                     + shard_major_matmul(x, w, 4))
        flops, bytes_ = matmul_costs(GM, GK, GN)
        return fn, (x, sc, w), 2 * flops, 2 * bytes_

    builders = {"flash": build_flash, "decode_paged": build_decode,
                "fused_wire": build_wire, "fused_gemm": build_gemm}
    reg = MetricsRegistry()
    table = {}
    reports = []
    for name in wanted:
        if name not in builders:
            log(f"kernel_sweep: unknown kernel {name!r} skipped")
            continue
        try:
            fn, args, flops, bytes_ = builders[name]()
            out = fn(*args)                  # compile + warmup
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(steps):
                out = fn(*args)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / steps
        except Exception as exc:  # noqa: BLE001 — record, keep going
            log(f"kernel_sweep {name}: FAILED {exc!r}")
            table[name] = {"error": str(exc)[-200:]}
            continue
        report = kernel_roofline_report(name, flops, bytes_, dt, spec=spec)
        report["ms"] = round(dt * 1e3, 3)
        publish_kernel_gauges(reg, report)
        reports.append(report)
        table[name] = {k: (round(v, 4) if isinstance(v, float) else v)
                       for k, v in report.items()}
        log(f"kernel_sweep {name}: {dt*1e3:.2f} ms, "
            f"{report['pct_peak_flops']:.3f}% flops peak, "
            f"{report['pct_peak_hbm']:.3f}% HBM peak")
    for line in format_kernel_table(reports):
        log(line)

    headline = max((r["pct_peak_flops"] for r in reports), default=0.0)
    # labelled kernels/* gauges (gauge_values() is label-free-only)
    gauges = sorted({m["name"] for m in reg.snapshot()
                     if str(m.get("name", "")).startswith("kernels/")})
    emit("kernel_sweep_pct_peak", round(headline, 3), "%peak",
         0.0 if not on_tpu else round(headline / 50.0, 4), {
             "kernels": table,
             "kernel_gauges": gauges,
             "device_kind": spec.kind,
             "interpret_mode": not on_tpu,
             "steps": steps,
             "backend": jax.default_backend(),
             "note": ("CPU-sim: interpreter-mode kernels vs fallback "
                      "peaks — a structure/plumbing gate, not a speed "
                      "claim" if not on_tpu else
                      "on-chip per-kernel %-of-peak")})


def run_fleet_sweep(on_tpu: bool) -> None:
    """DSTPU_BENCH_MODE=fleet_sweep — tok/s vs replica count (1/2/3) on
    the CPU sim over the REAL fleet tier: an in-process ``RouterServer``
    + ``FleetRouter`` HTTP front over real ``ServingServer`` replicas
    (tiny model), concurrent blocking clients.  Per point the bench
    reports aggregate tok/s and the per-segment TTFT-decomposition
    medians pulled from the new request-trace store (queue_wait /
    admission / prefill / compile / decode_window …), plus a tracing-
    overhead measurement: steady-state decode tok/s with the store at
    default sampling vs tracing off, same warmed engines — the bound the
    acceptance bar (<2%) is judged against.  CPU-sim numbers measure the
    SCHEDULING plane (window packing, router fan-out, HTTP), not kernels;
    scaling linearity is the signal."""
    import itertools
    import threading
    import urllib.error
    import urllib.request

    import jax.random as jrandom

    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2,
        RaggedInferenceEngineConfig,
    )
    from deepspeed_tpu.inference.v2.lifecycle import (
        LifecycleScheduler,
        ServeRequest,
    )
    from deepspeed_tpu.inference.v2.server import ServingServer
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
    from deepspeed_tpu.serving.fleet import FleetRouter, RouterServer
    from deepspeed_tpu.telemetry.tracing import (
        RequestTraceStore,
        install_trace_store,
    )

    n_requests = int(os.environ.get("DSTPU_BENCH_FLEET_REQUESTS", "24"))
    max_new = int(os.environ.get("DSTPU_BENCH_FLEET_TOKENS", "24"))
    cfg = TransformerConfig.tiny(use_flash=False)
    model = CausalLM(cfg)
    params = model.init_params(jrandom.PRNGKey(0))

    def mk_replica():
        eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
            max_tokens=64, max_seqs=8, max_ctx=96, block_size=8,
            dtype=jnp.float32, attn_impl="gather"))
        sched = LifecycleScheduler(eng, window_steps=4, max_queue=64)
        return ServingServer(sched, port=0, bind="127.0.0.1").start(), eng

    def post(port, body, timeout=600):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())

    prompts = [[3 + i % 7, 5 + i % 5, 7 + i % 3, 11] for i in
               range(n_requests)]
    points = []
    for n_rep in (1, 2, 3):
        install_trace_store(RequestTraceStore(sample_every=1))
        made = [mk_replica() for _ in range(n_rep)]
        replicas = [srv for srv, _ in made]
        rep_engines = [eng for _, eng in made]
        router = FleetRouter(poll_s=0.2)
        for i, r in enumerate(replicas):
            router.add_replica(f"127.0.0.1:{r.port}", name=f"r{i}")
        rs = RouterServer(router, port=0, bind="127.0.0.1").start()
        try:
            def client(results, i):
                for attempt in (0, 1):      # one retry: a reset under
                    try:                    # thundering-herd accept is
                        results[i] = post(  # load, not a bench failure
                            rs.port, {"prompt": prompts[i],
                                      "max_new_tokens": max_new})
                        return
                    except Exception:  # noqa: BLE001
                        if attempt:
                            raise

            def wave():
                # per-wave result list, captured by this wave's threads:
                # a client orphaned past the join timeout must write its
                # late response into ITS wave's list, not a later tally
                results = [None] * n_requests
                threads = [threading.Thread(target=client,
                                            args=(results, i),
                                            daemon=True)
                           for i in range(n_requests)]
                t0 = time.perf_counter()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=600)
                wall = time.perf_counter() - t0
                return (wall,
                        sum(len(r.get("tokens") or [])
                            for r in results if r),
                        sum(1 for r in results
                            if r and r.get("state") == "finished"))

            # warm waves compile every bucket this concurrency pattern
            # touches — two of them, because balancing shifts the
            # per-replica concurrency between waves and a replica only
            # owns all its decode seq-buckets once it has seen a full
            # set; a FRESH store then isolates the measured waves'
            # decomposition from warmup compile spans.  Best-of-3 so one
            # stray bucket compile cannot poison a point.
            wave()
            wave()
            store = RequestTraceStore(sample_every=1)
            install_trace_store(store)
            wall, toks, ok = min((wave() for _ in range(3)),
                                 key=lambda w: w[0])
            decomp = {k: round((v.get("p50_s") or 0.0) * 1e3, 3)
                      for k, v in store.segment_summary().items()}
            point = {"replicas": n_rep, "requests": n_requests,
                     "finished": ok, "tok_per_s": round(toks / wall, 2),
                     "wall_s": round(wall, 3),
                     "ttft_decomp_p50_ms": decomp,
                     **_kv_point_stats(rep_engines)}
            points.append(point)
            log(f"fleet_sweep {n_rep} replica(s): {point['tok_per_s']} "
                f"tok/s ({ok}/{n_requests} finished) decomp={decomp}")
        finally:
            rs.stop()
            for r in replicas:
                r.stop()
            install_trace_store(None)

    # ---- autoscale axis: per-tenant QoS + the dstpu-fleet controller -- #
    # A rate-limited "bulk" tenant floods a QoS router while an unmetered
    # "interactive" tenant trickles; an in-process FleetController
    # (identical tick logic to bin/dstpu-fleet, thread-backed spawner)
    # scales 1→2 under the backlog and back to 1 when idle.  Per-tenant
    # shed-rate and replica-count gauges flow through dstpu-telemetry
    # (router._publish_gauges / controller._publish).
    autoscale = None
    if os.environ.get("DSTPU_BENCH_FLEET_AUTOSCALE", "1") != "0":
        from deepspeed_tpu.serving.fleet import (FleetController,
                                                 QoSAdmission, SLOTarget,
                                                 TenantClass,
                                                 view_from_scrape)
        from deepspeed_tpu.telemetry import Telemetry, set_telemetry

        tel = Telemetry(output_dir=os.environ.get(
            "DSTPU_TELEMETRY_DIR", "telemetry_bench_fleet"))
        set_telemetry(tel)

        class _InprocClient:
            def __init__(self, r):
                self.r = r

            def scrape(self):
                return view_from_scrape(self.r.health()[1])

            def register(self, url, role="decode", name=None):
                self.r.add_replica(url, role=role, name=name)
                return {}

            def deregister(self, name):
                self.r.remove_replica(name)
                return {}

        class _ThreadSpawner:
            def __init__(self):
                self.srvs, self.stopped = {}, set()

            def spawn(self, name):
                srv, _ = mk_replica()
                self.srvs[name] = srv
                return f"127.0.0.1:{srv.port}"

            def drain(self, name):
                srv = self.srvs.get(name)
                if srv is not None and name not in self.stopped:
                    self.stopped.add(name)
                    threading.Thread(target=srv.stop,
                                     daemon=True).start()

            def alive(self, name):
                return name in self.srvs and name not in self.stopped

            def forget(self, name):
                self.srvs.pop(name, None)
                self.stopped.discard(name)

            def owned(self):
                return list(self.srvs)

            def stop_all(self):
                for name, srv in list(self.srvs.items()):
                    if name not in self.stopped:
                        srv.stop()
                self.srvs.clear()

        qos = QoSAdmission(classes=[
            TenantClass("bulk", priority=-1, rate=60.0, burst=120.0)])
        seed, _ = mk_replica()
        router = FleetRouter(poll_s=0.2, qos=qos)
        router.add_replica(f"127.0.0.1:{seed.port}", name="seed")
        rs = RouterServer(router, port=0, bind="127.0.0.1").start()
        spawner = _ThreadSpawner()
        ctl = FleetController(
            _InprocClient(router), spawner,
            slo=SLOTarget(ttft_p95_s=1e9, drain_high_s=0.01,
                          drain_low_s=10.0, min_replicas=1,
                          max_replicas=2, hysteresis_up=1,
                          hysteresis_down=2, cooldown_s=0.5),
            poll_s=0.2)
        n_bulk, n_inter = 40, 6
        sheds = {"bulk": 0, "interactive": 0}
        replica_counts = []
        try:
            def tenant_client(tenant, i):
                try:
                    post(rs.port, {"prompt": prompts[i % n_requests],
                                   "max_new_tokens": 8,
                                   "tenant": tenant})
                except urllib.error.HTTPError as e:
                    if e.code == 429:
                        sheds[tenant] += 1
                    e.read()
                except Exception:  # noqa: BLE001 — load, not the measure
                    pass

            threads = [threading.Thread(
                target=tenant_client,
                args=("bulk" if i < n_bulk else "interactive", i),
                daemon=True)
                for i in range(n_bulk + n_inter)]
            for t in threads:
                t.start()
            t_end = time.monotonic() + 20.0
            while (any(t.is_alive() for t in threads)
                   and time.monotonic() < t_end):
                ctl.tick()
                replica_counts.append(
                    ctl.last_view.live if ctl.last_view else 0)
                time.sleep(0.2)
            for t in threads:
                t.join(timeout=30)
            # idle ticks: the controller should now scale back down
            for _ in range(30):
                action = ctl.tick()
                replica_counts.append(
                    ctl.last_view.live if ctl.last_view else 0)
                if action == "scale_down" or \
                        ctl.counters["fleet/controller_scale_downs"]:
                    break
                time.sleep(0.2)
            tenants = router.health()[1].get("tenants") or {}
            autoscale = {
                "replica_count_min": min(replica_counts or [0]),
                "replica_count_max": max(replica_counts or [0]),
                "scale_ups": int(
                    ctl.counters["fleet/controller_scale_ups"]),
                "scale_downs": int(
                    ctl.counters["fleet/controller_scale_downs"]),
                "tenant_shed_rate": {
                    t: row.get("shed_rate")
                    for t, row in sorted(tenants.items())},
                "client_429s": dict(sheds),
            }
            log(f"fleet_sweep autoscale: replicas "
                f"{autoscale['replica_count_min']}→"
                f"{autoscale['replica_count_max']} "
                f"(ups={autoscale['scale_ups']} "
                f"downs={autoscale['scale_downs']}) "
                f"shed_rate={autoscale['tenant_shed_rate']}")
        finally:
            rs.stop()
            spawner.stop_all()
            seed.stop()
            tel.close()
            set_telemetry(None)

    # ---- tracing overhead: steady-state decode, store on vs off ------- #
    n_oh_streams, n_oh_tokens = 8, 192
    uid_seq = itertools.count(1000)

    def sched_run(eng, store):
        install_trace_store(store)
        try:
            from deepspeed_tpu.telemetry.tracing import TraceContext

            s = LifecycleScheduler(eng, window_steps=8, max_queue=16)
            uids = [next(uid_seq) for _ in range(n_oh_streams)]
            for i, uid in enumerate(uids):
                s.submit(ServeRequest(
                    uid=uid, prompt=[3 + i, 5, 7],
                    max_new_tokens=n_oh_tokens,
                    trace=TraceContext.mint() if store else None))
            t0 = time.perf_counter()
            s.run_until_idle()
            wall = time.perf_counter() - t0
            toks = sum(len(s.request(u).produced) for u in uids)
            return toks / wall
        finally:
            install_trace_store(None)

    eng_oh = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
        max_tokens=64, max_seqs=8, max_ctx=256, block_size=8,
        dtype=jnp.float32, attn_impl="gather"))
    sched_run(eng_oh, None)                         # warm the buckets
    # interleave off/on with the starting order flipped each round, then
    # compare medians — the per-window span cost is μs against ~ms
    # windows, far below run-to-run scheduler noise, so ordering bias
    # must cancel rather than masquerade as (negative) overhead
    offs, ons = [], []
    for rnd in range(3):
        pair = [(offs, None), (ons, RequestTraceStore(sample_every=10))]
        for sink, store in (pair if rnd % 2 == 0 else pair[::-1]):
            sink.append(sched_run(eng_oh, store))
    off = sorted(offs)[len(offs) // 2]
    on = sorted(ons)[len(ons) // 2]
    overhead_pct = round((off - on) / off * 100.0, 2) if off > 0 else None
    log(f"fleet_sweep tracing overhead: off={off:.1f} on={on:.1f} tok/s "
        f"({overhead_pct}%)")

    # ---- goodput ledger: same decode, ledger on vs off ---------------- #
    # identical interleaved-median methodology; the on-rounds also yield
    # a ledger snapshot (the decode windows land in compute/compile), so
    # the sweep reports BOTH the accounting overhead and the accounting
    from deepspeed_tpu.telemetry.goodput import (GoodputLedger,
                                                 install_goodput_ledger)

    def ledger_run(eng, with_ledger):
        ledger = GoodputLedger(component="bench") if with_ledger else None
        install_goodput_ledger(ledger)
        try:
            tps = sched_run(eng, None)
        finally:
            install_goodput_ledger(None)
        return tps, (ledger.snapshot() if ledger is not None else None)

    g_offs, g_ons = [], []
    g_snap = None
    for rnd in range(3):
        pair = [(g_offs, False), (g_ons, True)]
        for sink, with_ledger in (pair if rnd % 2 == 0 else pair[::-1]):
            tps, snap = ledger_run(eng_oh, with_ledger)
            sink.append(tps)
            if snap is not None:
                g_snap = snap
    g_off = sorted(g_offs)[len(g_offs) // 2]
    g_on = sorted(g_ons)[len(g_ons) // 2]
    goodput_overhead_pct = round((g_off - g_on) / g_off * 100.0, 2) \
        if g_off > 0 else None
    log(f"fleet_sweep goodput ledger overhead: off={g_off:.1f} "
        f"on={g_on:.1f} tok/s ({goodput_overhead_pct}%) "
        f"goodput_fraction="
        f"{g_snap['goodput_fraction'] if g_snap else None}")

    # ---- memory plane: same decode, page-heat tracking on vs off ------ #
    # interleaved-median A-B between two otherwise-identical engines; the
    # heat tracker is pure host-side bookkeeping so the bound is <1%.
    # eng_oh already tracks heat (the config default) — it is the ON arm.
    eng_mem_off = InferenceEngineV2(model, params,
                                    RaggedInferenceEngineConfig(
                                        max_tokens=64, max_seqs=8,
                                        max_ctx=256, block_size=8,
                                        dtype=jnp.float32,
                                        attn_impl="gather",
                                        track_page_heat=False))
    sched_run(eng_mem_off, None)                    # warm the buckets
    m_offs, m_ons = [], []
    for rnd in range(3):
        pair = [(m_offs, eng_mem_off), (m_ons, eng_oh)]
        for sink, eng_ in (pair if rnd % 2 == 0 else pair[::-1]):
            sink.append(sched_run(eng_, None))
    m_off = sorted(m_offs)[len(m_offs) // 2]
    m_on = sorted(m_ons)[len(m_ons) // 2]
    mem_overhead_pct = round((m_off - m_on) / m_off * 100.0, 2) \
        if m_off > 0 else None
    m_snap = eng_oh.memory_snapshot() or {}
    log(f"fleet_sweep memory plane overhead: off={m_off:.1f} "
        f"on={m_on:.1f} tok/s ({mem_overhead_pct}%) "
        f"peak_pages={m_snap.get('peak_live_pages')} "
        f"touches={m_snap.get('touches_total')}")

    # ---- host tier off: unchanged-behavior check ---------------------- #
    # an engine with host_tier_mb=0 (the default) must build no tier, no
    # swap manager, and produce the SAME streams as the default-config
    # engine — the tier must cost nothing when it is off
    eng_tier_off = InferenceEngineV2(model, params,
                                     RaggedInferenceEngineConfig(
                                         max_tokens=64, max_seqs=8,
                                         max_ctx=256, block_size=8,
                                         dtype=jnp.float32,
                                         attn_impl="gather",
                                         host_tier_mb=0.0))

    def stream_probe(eng):
        s = LifecycleScheduler(eng, window_steps=8, max_queue=16)
        for i in range(4):
            s.submit(ServeRequest(uid=5000 + i, prompt=[3 + i, 5, 7],
                                  max_new_tokens=32))
        s.run_until_idle()
        return [list(s.request(5000 + i).produced) for i in range(4)]

    tier_off_unchanged = (
        eng_tier_off.host_tier is None and eng_tier_off.kv_swap is None
        and stream_probe(eng_tier_off) == stream_probe(eng_oh))
    log(f"fleet_sweep host tier off unchanged: {tier_off_unchanged}")

    # headline = the MEAN over the sweep points — a regression at ANY
    # replica count must move it (max() would hide a regression at a
    # non-best point); scaling efficiency stays last-vs-first
    headline = (sum(p["tok_per_s"] for p in points) / len(points)
                if points else 0.0)
    base = points[0]["tok_per_s"] if points else 0.0
    last = points[-1]["tok_per_s"] if points else 0.0
    scaling = round(last / base / len(points), 3) if base else 0.0
    emit("fleet_sweep_tok_per_s", headline, "tokens/s", scaling, {
        "points": points,
        "scaling_efficiency_3x": scaling,
        "tracing_overhead_pct": overhead_pct,
        "trace_decode_tok_per_s": {"off": round(off, 2),
                                   "on": round(on, 2)},
        "goodput": {
            "overhead_pct": goodput_overhead_pct,
            "decode_tok_per_s": {"off": round(g_off, 2),
                                 "on": round(g_on, 2)},
            "goodput_fraction": (g_snap or {}).get("goodput_fraction"),
            "categories": (g_snap or {}).get("categories"),
            "conserved": (g_snap or {}).get("conserved"),
        },
        "memory": {
            "overhead_pct": mem_overhead_pct,
            "decode_tok_per_s": {"off": round(m_off, 2),
                                 "on": round(m_on, 2)},
            "kv_peak_pages": m_snap.get("peak_live_pages"),
            "kv_touches": m_snap.get("touches_total"),
            "prefix_shared_bytes_saved":
                m_snap.get("prefix_shared_bytes_saved"),
        },
        "host_tier_off_unchanged": tier_off_unchanged,
        "autoscale": autoscale,
        "requests": n_requests, "max_new_tokens": max_new,
        "note": "CPU-sim scheduling-plane bench over the real router; "
                "tok/s measures window packing + HTTP fan-out, not "
                "kernels",
    })


RUNNERS = {
    "train": run_train_bench,
    "flash_sweep": run_flash_sweep,
    "serving": run_serving_bench,
    "serving_load": run_serving_load_bench,
    "decode_sweep": run_decode_sweep,
    "pipeline": run_pipeline_bench,
    "offload": run_offload_bench,
    "overlap_sweep": run_overlap_sweep,
    "comm_sweep": run_comm_sweep,
    "fleet_sweep": run_fleet_sweep,
    "kernel_sweep": run_kernel_sweep,
}


def main() -> int:
    if MODE not in RUNNERS:
        raise SystemExit(f"bench.py: unknown DSTPU_BENCH_MODE {MODE!r}; "
                         f"one of {sorted(RUNNERS)}")
    from deepspeed_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    # the ONE backend initialisation, in this process: no probing child (a
    # chip belongs to one process) and no retry on another platform
    devices = jax.devices()
    platform = devices[0].platform
    log(f"mode={MODE} platform={platform} "
        f"device_kind={devices[0].device_kind} devices={len(devices)}")
    if platform != "tpu" and not (MODE in CPU_MESH_MODES or CPU_REHEARSAL):
        raise SystemExit(
            f"bench.py: mode {MODE!r} measures a device and JAX reports "
            f"platform {platform!r}; there is no CPU fall-back "
            f"(DSTPU_BENCH_FORCE_CPU=1 is the explicit tiny-size rehearsal)")
    RUNNERS[MODE](platform == "tpu")
    return 1 if _FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
