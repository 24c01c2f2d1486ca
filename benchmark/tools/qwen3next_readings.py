#!/usr/bin/env python3
"""Both readings of the Qwen3-Next cell's tolerances, on the chip:

    chiprun --chips 1 --timeout 2400 -- python3 benchmark/tools/qwen3next_readings.py --seed N

One whole run of the cell (set-up check, the 51 s window, the served check),
with the served check also reading the reference's own greedy tokens when
its weights are rounded to int8 and to float8 (e4m3), and the set-up check's
positions read the same way: a lower precision must fail by one of the
cell's limits.  Prints the cell's result line, then one JSON line of the
set-up controls.  Not part of a benchmark run."""
import json
import os
import runpy
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

from lib import model as model_lib  # noqa: E402
from lib import qwen3next_system as system  # noqa: E402

FORMATS = {"int8": "int8", "float8_e4m3": (4, 3)}


def main():
    seed = sys.argv[sys.argv.index("--seed") + 1] if "--seed" in sys.argv \
        else "1"
    system.CONTROLS.update(FORMATS)
    setup = {}
    prepare = system.prepare

    def prepare_with_controls(ctx):
        out = prepare(ctx)
        import numpy as np

        for name, fmt in FORMATS.items():
            try:
                rounded = out["reference"](fmt)
                rels = [model_lib.rel_l2(a, b) for low, full in
                        zip(rounded, out["ref"]) for a, b in zip(low, full)]
                setup[name] = {
                    "best": float(np.min(rels)),
                    "quartile": float(np.percentile(rels, 25)),
                    "median": float(np.median(rels)),
                    "gap_median": float(np.median(system._gaps(
                        np.concatenate(out["ref"]),
                        np.concatenate([np.argmax(r, axis=1)
                                        for r in rounded]))))}
            except Exception as exc:            # noqa: BLE001
                setup[name] = {"error": repr(exc)[-300:]}
        return out

    system.prepare = prepare_with_controls
    sys.modules["lib.qwen3next_system"] = system
    sys.argv = ["benchmark/run.py", "--workload",
                "qwen3next-80b-serve-sessions", "--seed", seed, "--seconds",
                "51", "--trace", "0"]
    try:
        runpy.run_path(os.path.join(HERE, "run.py"), run_name="__main__")
    except SystemExit:
        pass
    print(json.dumps({"setup_controls": setup}), flush=True)


if __name__ == "__main__":
    main()
