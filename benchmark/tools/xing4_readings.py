#!/usr/bin/env python3
"""Both readings of the Xing4 cell's tolerances, on the chip, for one seed:

    python3 benchmark/tools/xing4_readings.py --seed <n> [--seconds 51] [--cpu-rehearsal]

Runs the cell ``xing4-29b-serve-sessions`` once, as ``benchmark/run.py`` does
(its result line is printed first), and beside every reading of the SYSTEM
(bfloat16, through ``InferenceEngineV2``) against the float32 reference takes
the reading of the REFERENCE against itself with its bfloat16 weights first
rounded to a format below the one the configuration states:

* ``fp8``: float8 e4m3's three mantissa bits (``FP8``);
* ``int8``: symmetric, 127 steps to the largest value of each output channel.

1. the set-up check's positions (``lib/xing4_system.check_against_
   reference``): per position ``||low - f32|| / ||f32||`` and the gap of the
   rounded reference's greedy token in the float32 logits;
2. the served sample (``check_served``): the same gap at every sampled
   position of the turns the window finished (``checks.served.controls`` of
   the result line).

A control must come out as not correct.  Prints the result line, then one
JSON object ``{"seed", "setup_controls"}``.  The limits in the configuration
file lie between the two readings (PERF.md section 6, PR 28)."""
from __future__ import annotations

import argparse
import json
import os
import sys

#: (exponent bits, mantissa bits): float8 e4m3's three mantissa bits with
#: bfloat16's exponent range kept — what per-tensor-scaled fp8 weights give.
#: With e4m3's own four exponent bits most of a weight matrix drawn at
#: 1/sqrt(3584) lies under the smallest normal number and is flushed.
FP8 = (8, 3)
CONTROLS = {"fp8": FP8, "int8": "int8"}

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]


def setup_reading(low, ref, tol) -> dict:
    import numpy as np

    from lib import model as model_lib

    rels, gaps = [], []
    for got, want in zip(low, ref):
        for g, r in zip(got, want):
            rels.append(model_lib.rel_l2(g, r))
            gaps.append(float(r.max() - r[int(np.argmax(g))])
                        / float(np.sqrt(np.mean(r ** 2))))
    return {"logits_rel_l2_lower_quartile": float(np.percentile(rels, 25)),
            "logits_rel_l2_median": float(np.median(rels)),
            "logits_rel_l2_min": float(np.min(rels)),
            "share_over_logits_rel_l2": float(np.mean(
                np.asarray(rels) > tol["logits_rel_l2"])),
            "decode_gap_rms_median": float(np.median(gaps)),
            "share_over_decode_gap_rms": float(np.mean(
                np.asarray(gaps) > tol["decode_gap_rms"]))}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()

    import run
    from lib import xing4_system

    setup_controls = {}
    prepare = xing4_system.prepare

    def prepare_and_read(ctx):
        system = prepare(ctx)
        for name, fmt in CONTROLS.items():
            setup_controls[name] = setup_reading(
                system["reference"](fmt), system["ref"],
                ctx.config["tolerances"])
            print(json.dumps({name: setup_controls[name]}), file=sys.stderr,
                  flush=True)
        return system

    xing4_system.prepare = prepare_and_read
    xing4_system.CONTROLS = CONTROLS
    argv = ["--workload", "xing4-29b-serve-sessions", "--seed",
            str(args.seed), "--seconds", str(args.seconds), "--trace", "0"]
    rc = run.main(argv + (["--cpu-rehearsal"] if args.cpu_rehearsal else []))
    print(json.dumps({"seed": args.seed, "setup_controls": setup_controls}),
          flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
