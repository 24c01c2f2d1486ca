#!/usr/bin/env python
"""Smoke-check the kernel_sweep bench end to end on the CPU sim.

The per-kernel %-of-peak table is the artifact that makes kernel numbers
trustworthy (an earlier flash_sweep timing emitted 3831 TFLOP/s on a
197 TFLOP/s chip and was rejected as a timing artifact).  This gate keeps
the table's PLUMBING honest between chip runs: it runs
``DSTPU_BENCH_MODE=kernel_sweep`` as a subprocess on interpreter-mode
kernels and asserts, from the emitted JSON:

  * all four kernel families ran (flash, decode_paged, fused_wire,
    fused_gemm) with no per-kernel errors;
  * every row carries finite, physically-plausible roofline numbers
    (0 < %-of-peak < 100 against the labelled CPU fallback peaks — an interpreted
    kernel beating chip peak is exactly the class of artifact the gate
    exists to reject);
  * compute-vs-memory bound classification is sane (flash/fused_gemm
    compute-bound, decode/wire memory-bound — the analytic AI model holds);
  * the ``kernels/*`` gauges were published (the dstpu-telemetry section's
    source);
  * the subprocess stays inside the ~60 s budget (tier-1 rides a tight
    870 s total — see ROADMAP).

Usage: ``python tools/check_kernel_sweep.py``.  Exit status 1 lists what
broke.  Enforced from ``tests/unit/test_kernel_sweep_smoke.py`` the same
way the comm_sweep gate is.
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GATE_ENV = {
    "DSTPU_BENCH_MODE": "kernel_sweep",
    "DSTPU_BENCH_FORCE_CPU": "1",
    "DSTPU_BENCH_KERNEL_STEPS": "2",
}

EXPECTED = ("flash", "decode_paged", "fused_wire", "fused_gemm")
#: compute- vs memory-bound expectation per family at the sweep's shapes
BOUND = {"flash": "compute", "fused_gemm": "compute",
         "decode_paged": "memory", "fused_wire": "memory"}
#: subprocess wall budget (seconds) — overridable for slow CI boxes
BUDGET_S = float(os.environ.get("DSTPU_KERNEL_SWEEP_BUDGET_S", "60"))


def run_sweep():
    env = dict(os.environ)
    env.update(GATE_ENV)
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py")],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=REPO_ROOT)
    wall = time.time() - t0
    result = None
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                result = json.loads(line)
            except json.JSONDecodeError:
                continue
    return proc, result, wall


def check_sweep(check, result, wall):
    if result is None:
        check("bench emitted a JSON result line", False)
        return
    extra = result.get("extra") or {}
    check("no bench-level error", "error" not in extra, extra.get("error"))
    check(f"subprocess within the {BUDGET_S:.0f}s budget",
          wall < BUDGET_S, f"took {wall:.1f}s")
    kernels = extra.get("kernels") or {}
    for name in EXPECTED:
        row = kernels.get(name)
        check(f"kernel ran: {name}", isinstance(row, dict), kernels.keys())
        if not isinstance(row, dict):
            continue
        check(f"{name}: no error", "error" not in row, row.get("error"))
        if "error" in row:
            continue
        for key in ("ms", "tflops", "hbm_gbps", "pct_peak_flops",
                    "pct_peak_hbm", "arithmetic_intensity"):
            v = row.get(key)
            finite = isinstance(v, (int, float)) and math.isfinite(v)
            check(f"{name}: {key} finite", finite, f"{key}={v!r}")
        for key in ("pct_peak_flops", "pct_peak_hbm"):
            v = row.get(key)
            # >100% of peak is physically impossible — the artifact class
            # this gate exists to reject (the flash_sweep incident)
            ok = isinstance(v, (int, float)) and 0.0 < v < 100.0
            check(f"{name}: 0 < {key} < 100", ok, f"{key}={v!r}")
        check(f"{name}: {BOUND[name]}-bound per the AI model",
              row.get("bound") == BOUND[name],
              f"bound={row.get('bound')!r} "
              f"ai={row.get('arithmetic_intensity')!r}")
        check(f"{name}: ms > 0",
              isinstance(row.get("ms"), (int, float)) and row["ms"] > 0,
              row.get("ms"))

    gauges = extra.get("kernel_gauges") or []
    for key in ("kernels/pct_peak_flops", "kernels/pct_peak_hbm",
                "kernels/tflops", "kernels/hbm_gbps"):
        check(f"gauge published: {key}", key in gauges, gauges)


def main() -> int:
    failures = []

    def check(name, ok, detail=None):
        status = "ok" if ok else "FAIL"
        line = f"[{status}] {name}" + \
            (f" — {detail}" if detail is not None and not ok else "")
        print(line)
        if not ok:
            failures.append(name)

    proc, result, wall = run_sweep()
    if proc.returncode != 0:
        check("bench.py exited 0", False, proc.stderr[-500:])
    check_sweep(check, result, wall)

    if failures:
        print(f"\n{len(failures)} check(s) failed")
        return 1
    print(f"\nkernel_sweep smoke: all checks passed ({wall:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
