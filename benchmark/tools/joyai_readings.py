#!/usr/bin/env python3
"""Both readings of the JoyAI-LLM-Flash training cell's tolerances on the
chip:

    chiprun --chips 1 --timeout 3000 -- python3 benchmark/tools/joyai_readings.py --seed N [--seconds S]

One whole run of the cell; its set-up comparison also reads the REFERENCE
with its weights rounded to float8 (e4m3, the nearest precision below the
bf16 the configuration states) and to float8 e5m2, held to the float32
reference exactly as the system is: loss terms, both heads' logits per
position, the named leaves' gradients.  Those are the first readings, which
must come out as not correct; the system's own numbers are the cell's
``checks``.  Prints the cell's result line (``checks.controls`` holds the
readings).  Not part of a benchmark run."""
import os
import runpy
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

from lib import joyai_system as system  # noqa: E402


def _arg(name, default):
    return sys.argv[sys.argv.index(name) + 1] if name in sys.argv else default


def main():
    import jax.numpy as jnp

    system.CONTROLS.update(float8_e4m3=jnp.float8_e4m3fn,
                           float8_e5m2=jnp.float8_e5m2)
    sys.argv = ["benchmark/run.py", "--workload",
                _arg("--workload", "joyaiflash-train-mtp-1chip"), "--seed",
                _arg("--seed", "1"), "--seconds", _arg("--seconds", "5"),
                "--trace", "0"] + (["--cpu-rehearsal"]
                                   if "--cpu-rehearsal" in sys.argv else [])
    runpy.run_path(os.path.join(HERE, "run.py"), run_name="__main__")


if __name__ == "__main__":
    main()
