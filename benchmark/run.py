#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's system, warms the shapes its traffic uses, checks the
outputs against the plain reference outside the window, measures for
``--seconds``, and prints ONE JSON object as the last line of stdout:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and, with
``--trace 1``, ``breakdown``).  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics.  Set-up (the fact
``setup_seconds``) runs from this file's first line to the window's start,
less the seconds inside the one call that starts the TPU runtime (the fact
``client_seconds``, reported apart).

With no TPU, or fewer chips than the cell asks for, it exits non-zero and
prints no result.  ``--cpu-rehearsal`` (toy widths on the CPU backend, the
line labelled ``"platform": "cpu"``) is for the builder's rehearsal only.

Everything that belongs to one cell is data found by name: the cell in
``BENCHMARK.json``, its configuration in ``benchmark/configs``, its traffic
in ``benchmark/traffic`` (whose ``kind`` names a module in
``benchmark/generators``), each metric in ``benchmark/metrics`` (whose
``reader`` names a module in ``benchmark/readers``).
"""
from __future__ import annotations

import time

T0 = time.perf_counter()        # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]     # lib/, reference/ and deepspeed_tpu

from lib import manifest, peaks, trace as trace_lib  # noqa: E402
from lib.spans import Spans  # noqa: E402


def cache_entries(path) -> int:
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for n in os.listdir(path) if not n.startswith("."))


def devices_for(chips: int, rehearsal: bool):
    import jax

    devs = jax.devices()
    if not rehearsal and devs[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: JAX reports platform {devs[0].platform!r}, not a "
            f"TPU; no number is taken off the chip (--cpu-rehearsal is the "
            f"explicit toy run)")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"reports {len(devs)}")
    return devs[:chips]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)

    man = manifest.manifest()
    cell = manifest.cell(man, args.workload)
    traffic = manifest.traffic_of(cell["traffic"])
    generator = manifest.load_module("generators", traffic["kind"])
    ctx = types.SimpleNamespace(
        cell=cell, config=manifest.config_of(man, cell["config"]),
        traffic=traffic, seed=args.seed, trace=bool(args.trace),
        seconds=float(args.seconds if args.seconds is not None
                      else man["run_seconds"]),
        rehearsal=args.cpu_rehearsal, spans=Spans(),
        trace_dir=os.path.join(ROOT, ".bench_trace", cell["name"]))

    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()
    if hasattr(generator, "pre_jax"):
        generator.pre_jax(ctx)

    import logging

    import deepspeed_tpu  # noqa: F401 — a bare directory fails here, loudly
    from deepspeed_tpu.utils.compile_cache import configure_compile_cache
    ctx.spans.records.append(("bench/setup_import", T0,
                              time.perf_counter() - T0))

    # stdout carries the result line; the library's log goes to stderr
    for handler in logging.getLogger("deepspeed_tpu").handlers:
        if isinstance(handler, logging.StreamHandler):
            handler.setStream(sys.stderr)

    # the TPU runtime starts here (libtpu, the PJRT client): 7-11 s on a
    # v5e host without transparent hugepages, another figure on another
    # machine, and nothing a change to the program can move.  It is timed
    # apart (``client_seconds``) and is NOT part of ``setup_seconds``.
    with ctx.spans.span("bench/setup_client"):
        ctx.devices = devices_for(cell["chips"], args.cpu_rehearsal)
    client_s = ctx.spans.total("bench/setup_client")
    # $JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.jax_cache
    cache_dir = configure_compile_cache()
    entries_before = cache_entries(cache_dir)

    out = generator.run(ctx)

    t_start, t_end = out["window"]
    dev0 = ctx.devices[0]
    run = {
        "facts": dict(out["facts"], setup_seconds=t_start - T0 - client_s,
                      client_seconds=client_s,
                      cache_entries_new=cache_entries(cache_dir)
                      - entries_before),
        "samples": out["samples"], "window": (t_start, t_end),
        "window_s": t_end - t_start, "trace": out.get("trace"),
        "slice": out.get("slice") or (t_start, t_end), "spans": ctx.spans,
        "sizes": {k: v for k, v in ctx.config.items()
                  if isinstance(v, (int, float, bool))},
        "memory_peak_bytes": out["memory_peak_bytes"],
        "peaks": None if args.cpu_rehearsal
        else peaks.peaks_for(str(dev0.device_kind)),
    }
    def read(group):
        found = {}
        for entry in manifest.metrics_for(man, cell["name"], group):
            spec = manifest.metric_of(entry["name"])
            reader = manifest.load_module("readers", spec["reader"])
            value = reader.read(run, spec.get("args", {}))
            if value is not None:
                found[entry["name"]] = {"value": float(value),
                                        "unit": entry["unit"]}
        return found

    group = "per_layer" if args.trace else "end_to_end"
    metrics = read(group)

    device = {"platform": dev0.platform, "kind": str(dev0.device_kind),
              "count": len(ctx.devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace and out.get("trace") is not None:
        busy = trace_lib.busy(out["trace"])
        if busy is not None:
            device.update(busy)
        line["breakdown"] = {
            "device_ops": trace_lib.time_by_label(out["trace"]),
            "idle_gaps": trace_lib.idle_gaps(out["trace"])}
    # beyond the contract's keys, for whoever reads the line by hand: the
    # other group's metrics that this run can give (no trace: no device ones)
    line["also"] = read("end_to_end" if args.trace else "per_layer")
    line["workload"] = cell["name"]
    line["seed"] = args.seed
    line["window_s"] = run["window_s"]
    line["checks"] = out["checks"]
    line["facts"] = {k: v for k, v in run["facts"].items()
                     if isinstance(v, (int, float)) or v is None}
    line["setup_spans"] = {
        k[len("bench/"):]: round(v, 3) for k, v in
        ctx.spans.by_name(float("-inf"), t_start).items()}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
