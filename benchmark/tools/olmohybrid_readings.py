#!/usr/bin/env python3
"""Both readings of the Olmo-Hybrid cell's tolerances, and its two
assumption controls, on the chip:

    chiprun --chips 1 --timeout 2400 -- python3 benchmark/tools/olmohybrid_readings.py --seed N

One whole run of the cell (set-up check, the 51 s window, the served check),
with the served check also reading the reference's own greedy tokens when
its weights are rounded to int8 and to float8 (e4m3), when ``beta`` is NOT
doubled and when the block is pre-norm, and the set-up check's positions
read the same way: each of the four must fail by one of the cell's limits.
Prints the cell's result line, then one JSON line of the set-up controls.
Not part of a benchmark run."""
import json
import os
import runpy
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

from lib import model as model_lib  # noqa: E402
from lib import olmohybrid_system as system  # noqa: E402
from lib.xing4_system import _gaps  # noqa: E402

CONTROLS = {"int8": ("round", "int8"), "float8_e4m3": ("round", (4, 3)),
            "beta_not_doubled": ("mutation", "beta_not_doubled"),
            "pre_norm": ("mutation", "pre_norm")}


def main():
    seed = sys.argv[sys.argv.index("--seed") + 1] if "--seed" in sys.argv \
        else "1"
    system.CONTROLS.update(CONTROLS)
    setup = {}
    prepare = system.prepare

    def prepare_with_controls(ctx):
        out = prepare(ctx)
        import numpy as np

        for name, control in CONTROLS.items():
            try:
                other = out["reference"](control)
                rels = [model_lib.rel_l2(a, b) for low, full in
                        zip(other, out["ref"]) for a, b in zip(low, full)]
                setup[name] = {
                    "best": float(np.min(rels)),
                    "quartile": float(np.percentile(rels, 25)),
                    "median": float(np.median(rels)),
                    "gap_median": float(np.median(_gaps(
                        np.concatenate(out["ref"]),
                        np.concatenate([np.argmax(r, axis=1)
                                        for r in other]))))}
            except Exception as exc:            # noqa: BLE001
                setup[name] = {"error": repr(exc)[-300:]}
        return out

    system.prepare = prepare_with_controls
    sys.modules["lib.olmohybrid_system"] = system
    sys.argv = ["benchmark/run.py", "--workload",
                "olmohybrid7b-serve-rollouts", "--seed", seed, "--seconds",
                "51", "--trace", "0"]
    try:
        runpy.run_path(os.path.join(HERE, "run.py"), run_name="__main__")
    except SystemExit:
        pass
    print(json.dumps({"setup_controls": setup}), flush=True)


if __name__ == "__main__":
    main()
