#!/usr/bin/env python3
"""Print the shape of a profiler trace: planes, lines, and for each device
line the heaviest events with their stats.  For a person deciding how
``lib/trace.py`` should name what it finds.

    python3 benchmark/tools/dump_trace.py <dir-or-xplane.pb> [--cut out.json --ms 40]

``--cut`` writes the first ``--ms`` milliseconds after the first device
operation, reduced as ``lib/trace.load_xplane`` reduces it, for the tests.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import trace as trace_lib  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("--cut")
    ap.add_argument("--ms", type=float, default=40.0)
    args = ap.parse_args()
    import jax

    path = args.path if args.path.endswith(".pb") \
        else trace_lib.find_xplane(args.path)
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            if not plane.name.startswith("/device:"):
                continue
            by_name = {}
            for ev in events:
                rec = by_name.setdefault(ev.name, [0.0, 0, ev])
                rec[0] += ev.duration_ns
                rec[1] += 1
            for name, (ns, calls, ev) in sorted(
                    by_name.items(), key=lambda kv: -kv[1][0])[:25]:
                stats = {k: (v if not isinstance(v, str) else v[:300])
                         for k, v in ev.stats}
                print(f"    {ns / 1e6:10.3f} ms x{calls:<5} {name}  {stats}")
    reduced = trace_lib.load_xplane(path)
    print("BUSY", trace_lib.busy(reduced))
    print("BY LABEL", trace_lib.time_by_label(reduced, top=25))
    print("GAPS", trace_lib.idle_gaps(reduced))
    if args.cut:
        first = min(ops[0][1] for ops in reduced["device"].values() if ops)
        hi = first + args.ms * 1e6
        cut = {"device": {k: [op for op in v if first <= op[1] < hi]
                          for k, v in reduced["device"].items()},
               "host": [sp for sp in reduced["host"]
                        if first <= sp[1] < hi]}
        trace_lib.save_json(cut, args.cut)
        print("CUT", args.cut, sum(len(v) for v in cut["device"].values()),
              len(cut["host"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
