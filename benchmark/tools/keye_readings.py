#!/usr/bin/env python3
"""Both readings of the Keye-VL cell's tolerances, on the chip, for one seed:

    python3 benchmark/tools/keye_readings.py --seed <n> [--seconds 51] [--cpu-rehearsal]

Runs the cell ``keyevl2-serve-longdoc`` once, as ``benchmark/run.py`` does
(its result line is printed first), and beside every reading of the SYSTEM
(bfloat16, through ``InferenceEngineV2``) against the float32 reference takes
the reading of the REFERENCE against itself under two controls, at the set-up
check's positions and on the served sample (``checks.served.controls`` of the
result line): ``int8`` — its bfloat16 weights first rounded to int8
(symmetric, 127 steps to the largest value of each output channel) — and
``dense`` — the selection taken out (every query attends to its whole causal
context).  Both must come out as not correct: the second shows that the
comparison can tell whether the timed path selects at all.  Also prints the
ring's last ``attn/sparse_layout`` and ``engine/window_account`` records.
The limits in the configuration file lie between the readings (PERF.md
section 6, PR 46)."""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

CONTROLS = {"int8": ("round", "int8"), "dense": ("mutation", "dense")}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()

    import run
    from lib import keye_system
    from tools.xing4_readings import setup_reading

    setup_controls = {}
    prepare = keye_system.prepare

    def prepare_and_read(ctx):
        system = prepare(ctx)
        for name, control in CONTROLS.items():
            try:
                setup_controls[name] = setup_reading(
                    system["reference"](control), system["ref"],
                    ctx.config["tolerances"])
            except Exception as exc:            # noqa: BLE001
                setup_controls[name] = {"error": repr(exc)[-300:]}
            print(json.dumps({name: setup_controls[name]}), file=sys.stderr,
                  flush=True)
        return system

    keye_system.prepare = prepare_and_read
    keye_system.CONTROLS = CONTROLS
    argv = ["--workload", "keyevl2-serve-longdoc", "--seed",
            str(args.seed), "--seconds", str(args.seconds), "--trace", "0"]
    rc = run.main(argv + (["--cpu-rehearsal"] if args.cpu_rehearsal else []))

    from deepspeed_tpu.telemetry import get_tracer

    ring = {}
    for record in get_tracer().records():
        if record.name in ("attn/sparse_layout", "engine/window_account"):
            ring[record.name] = record.attrs
    print(json.dumps({"seed": args.seed, "setup_controls": setup_controls,
                      "ring": ring}, default=str), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
