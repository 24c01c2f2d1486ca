#!/usr/bin/env python3
"""Cut a small sample out of the last traced run of a cell, for
``benchmark/tests/test_program_trace.py``: the first ``--ms`` milliseconds
after ``--skip-ms`` of the traced slice, with the device operations as
``lib/trace.load_xplane`` reduces them, the program's host spans, the "XLA
Modules" line and — where the run left ``scopes.json`` beside its profile —
the name scopes of the instructions that occur.

    python3 benchmark/tools/cut_program_sample.py <cell> out.json [--ms 40] [--skip-ms 0]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import program_trace, trace as trace_lib  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("out")
    ap.add_argument("--ms", type=float, default=40.0)
    ap.add_argument("--skip-ms", type=float, default=0.0)
    args = ap.parse_args()
    trace_dir = os.path.join(program_trace.ROOT, ".bench_trace", args.cell)
    path = trace_lib.find_xplane(trace_dir)
    reduced = trace_lib.load_xplane(path)
    extra = program_trace.xplane({"trace": reduced})
    lo = trace_lib.window_of(reduced)[0] + args.skip_ms * 1e6
    hi = lo + args.ms * 1e6
    keep = lambda start, dur: lo <= start and start + dur <= hi  # noqa: E731
    device = {k: [op for op in v if keep(op[1], op[2])]
              for k, v in reduced["device"].items()}
    names = {op[0] for ops in device.values() for op in ops}
    scopes = {}
    scopes_path = os.path.join(os.path.dirname(path), "scopes.json")
    if os.path.exists(scopes_path):
        with open(scopes_path) as f:
            scopes = {mod: {k: v for k, v in table.items() if k in names}
                      for mod, table in json.load(f).items()}
    sample = {
        "trace": {"device": device,
                  "host": [sp for sp in reduced["host"]
                           if keep(sp[1], sp[2])]},
        "program_host": [sp for sp in extra["host"] if keep(sp[1], sp[2])],
        "modules": {k: [m for m in v if m[1] < hi and m[1] + m[2] > lo]
                    for k, v in extra["modules"].items()},
        "scopes": scopes}
    with open(args.out, "w") as f:
        json.dump(sample, f)
    print("cut", args.out, sum(len(v) for v in device.values()), "ops",
          len(sample["program_host"]), "program spans",
          sum(len(v) for v in scopes.values()), "scoped instructions")
    return 0


if __name__ == "__main__":
    sys.exit(main())
