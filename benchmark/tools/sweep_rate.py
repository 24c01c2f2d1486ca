#!/usr/bin/env python3
"""Find an open-loop cell's knee ONCE, when the cell is defined: the same
schedule generator at several fixed rates, one window each, in one process
(one build, one warm-up).  The rate written into the traffic file is then
about four fifths of the highest rate at which time to first token does not
grow from the first half of the window to the second and the queue drains.

    python3 benchmark/tools/sweep_rate.py --workload <open-loop cell> --rates 4 5 6 7 8 --seconds 20
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

from lib import manifest, model as model_lib, stats  # noqa: E402
from lib import serve_system as ss  # noqa: E402
from lib.spans import Spans  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from deepspeed_tpu.utils.compile_cache import configure_compile_cache

    man = manifest.manifest()
    cell = manifest.cell(man, args.workload)
    traffic = manifest.traffic_of(cell["traffic"])
    gen = manifest.load_module("generators", traffic["kind"])
    devs = jax.devices()
    if not args.cpu_rehearsal and devs[0].platform != "tpu":
        raise SystemExit("sweep_rate: no TPU")
    configure_compile_cache()
    ctx = types.SimpleNamespace(
        cell=cell, config=manifest.config_of(man, cell["config"]),
        traffic=traffic, seed=args.seed, trace=False, seconds=args.seconds,
        rehearsal=args.cpu_rehearsal, spans=Spans(), trace_dir="",
        devices=devs[:cell["chips"]])
    job = dict(traffic)
    if args.cpu_rehearsal:
        job.update(gen.REHEARSAL)
    system = ss.build(ctx, model_lib.sizes_of(ctx.config, ctx.rehearsal))
    print(json.dumps(ss.check_against_reference(ctx, system)), flush=True)
    ss.warm(ctx, system, gen.decode_widths(system))
    print(json.dumps({"setup_spans": ctx.spans.by_name(
        float("-inf"), float("inf"))}), flush=True)
    ss.instrument(system["engine"], ctx.spans)
    for rate in args.rates:
        out = gen.measure(ctx, system, dict(job, rate_per_s=rate),
                          args.seconds)
        ttft = out["samples"]["ttft_ms"]
        f = out["facts"]
        print(json.dumps({
            "rate_per_s": rate, "requests": out["attempted"],
            "failed": out["failed"],
            "prompt_tokens_per_s": f["prompt_tokens_offered"] / args.seconds,
            "ttft_mean_ms": sum(ttft) / max(len(ttft), 1),
            "ttft_p50_ms": stats.percentile(ttft, 50),
            "ttft_p95_ms": stats.percentile(ttft, 95),
            "ttft_mean_first_half_ms": f["ttft_mean_first_half_ms"],
            "ttft_mean_second_half_ms": f["ttft_mean_second_half_ms"],
            "drain_s": f["drain_s"], "max_waiting": f["max_waiting"],
            "compiles": f["compiles_in_window"],
            "decode_windows": f["decode_windows"],
            "decode_rows": f["decode_rows"],
            "kv_fill_peak": f["kv_fill_peak"],
            "kv_fill_mean": f["kv_fill_mean"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
