#!/usr/bin/env python
"""Smoke-check the comm_sweep bench + CollectiveAlgoSelector end to end on
the CPU sim.

Like ``check_serving_smoke.py`` for the serving stack: chip runs are rare,
so the hierarchical/quantized collective sweep could rot
(an import error in the fused wire, a broken shard_map spec, a selector
regression) without any silicon window noticing.  Runs
``DSTPU_BENCH_MODE=comm_sweep`` as a subprocess with a tiny grid and
asserts, from the emitted JSON:

  * the sweep ran end-to-end (>= 4 successful grid points, flat AND 2hop
    present, quantized AND fp wires present);
  * the selector picked a config per bucket and its measured re-tune picks
    the measured-fastest config (``selector_agrees``);
  * the ``comm/*`` gauges were published (algo/wire/predicted ms+bytes);
  * predicted collective operand bytes are within a factor of the
    jaxpr-measured bytes for every point (the cost model tracks reality).

Usage: ``python tools/check_comm_sweep.py``.  Exit status 1 lists what
broke.  Enforced from ``tests/unit/test_comm_sweep_smoke.py`` the same way
the no-bare-print lint is.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: tiny but representative grid: both algorithms, a quantized and the fp
#: wire, one bucket size — ~6 jitted exchanges on the 8-device CPU sim
GATE_ENV = {
    "DSTPU_BENCH_MODE": "comm_sweep",
    "DSTPU_BENCH_FORCE_CPU": "1",
    "DSTPU_BENCH_SWEEP_MB": "2",
    "DSTPU_BENCH_SWEEP_STEPS": "2",
    "DSTPU_BENCH_SWEEP_WIRES": "fp,int8",
    "DSTPU_BENCH_SWEEP_BUCKETS_MB": "1",
}

#: cost model vs jaxpr-measured operand bytes: padding, scale sidecars and
#: the leaf mix make small-payload predictions coarse, but an order-of-
#: magnitude miss means the model (or the byte counter) broke
BYTES_FACTOR = 4.0


def run_sweep(extra_env=None):
    env = dict(os.environ)
    env.update(GATE_ENV)
    env.update(extra_env or {})
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py")],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=REPO_ROOT)
    result = None
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                result = json.loads(line)
            except json.JSONDecodeError:
                continue
    return proc, result


def check_sweep(check, result):
    extra = (result or {}).get("extra") or {}
    if result is None:
        check("bench emitted a JSON result line", False)
        return
    check("no bench-level error", "error" not in extra,
          extra.get("error"))
    points = extra.get("points") or []
    ok = [p for p in points if "ms" in p]
    check("grid ran >= 4 points", len(ok) >= 4,
          f"{len(ok)} ok of {len(points)}: {points}")
    check("no failed grid points",
          all("error" not in p for p in points),
          [p for p in points if "error" in p])
    algos = {p["algo"] for p in ok}
    wires = {p["wire"] for p in ok}
    check("both algorithms swept", {"flat", "2hop"} <= algos, algos)
    check("fp and a quantized wire swept",
          "fp" in wires and (wires & {"int8", "int4_loco"}), wires)

    sels = extra.get("selections") or []
    check("selector produced a per-bucket choice", bool(sels), extra)
    for s in sels:
        check(f"selector re-tune picks measured-fastest "
              f"(bucket={s.get('bucket_bytes')})",
              bool(s.get("selector_agrees")), s)
        check("analytic selection present", bool(s.get("analytic")), s)

    gauges = extra.get("comm_gauges") or {}
    for key in ("comm/algo_2hop", "comm/wire_bits",
                "comm/predicted_exchange_ms", "comm/predicted_wire_bytes"):
        check(f"gauge published: {key}", key in gauges, sorted(gauges))

    for p in ok:
        meas, pred = p.get("measured_wire_bytes"), \
            p.get("predicted_wire_bytes")
        plausible = (meas and pred
                     and pred / BYTES_FACTOR <= meas <= pred * BYTES_FACTOR)
        check(f"predicted-vs-measured bytes within {BYTES_FACTOR}x "
              f"({p['algo']}/{p['wire']})", bool(plausible),
              f"measured={meas} predicted={pred}")


def main() -> int:
    failures = []

    def check(name, ok, detail=None):
        status = "ok" if ok else "FAIL"
        line = f"[{status}] {name}" + \
            (f" — {detail}" if detail and not ok else "")
        print(line)
        if not ok:
            failures.append(name)

    proc, result = run_sweep()
    if proc.returncode != 0:
        check("bench.py exited 0", False, proc.stderr[-500:])
    check_sweep(check, result)

    if failures:
        print(f"\n{len(failures)} check(s) failed")
        return 1
    print("\ncomm_sweep smoke: all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
