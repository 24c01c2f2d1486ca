#!/usr/bin/env python3
"""Both readings of the Nemotron-3-Super cell's tolerances on the chip:

    chiprun --chips 1 --timeout 3000 -- python3 benchmark/tools/nemotronh_readings.py --seed N [--seconds S]

One whole run of the cell (set-up check, the window, the served check), with
the served check also reading the reference's own greedy tokens when its
weights are rounded to int8, and the set-up check's positions read the same
way (``setup_controls``): the first reading, which must fail.  Then the
SYSTEM again with the Mamba-2 state KEPT IN BFLOAT16 (the state kind's array
dtype patched, a second engine on the first one's parameters after its pools
are given back), through the same set-up check (``bf16_state``): the control
PR 55 found cannot be separated at the logits; what it reads is reported, no
limit is bent to it.  Prints the cell's result line, then one JSON line of
the two.  Not part of a benchmark run."""
import json
import os
import runpy
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

from lib import model as model_lib  # noqa: E402
from lib import nemotronh_system as system  # noqa: E402
from lib.xing4_system import _gaps  # noqa: E402

CONTROLS = {"int8": ("round", "int8")}


def _arg(name, default):
    return sys.argv[sys.argv.index(name) + 1] if name in sys.argv else default


def main():
    seed, seconds = _arg("--seed", "1"), _arg("--seconds", "51")
    system.CONTROLS.update(CONTROLS)
    setup, kept = {}, {}
    prepare, served = system.prepare, system.check_served

    def prepare_with_controls(ctx):
        out = prepare(ctx)
        import numpy as np

        kept.update(ctx=ctx, prepared={k: out[k] for k in (
            "cfg", "model", "check_rows", "check_plan", "ref", "serving",
            "param_bytes", "make_reference")})
        for name, control in CONTROLS.items():
            try:
                other = out["reference"](control)
                rels = [model_lib.rel_l2(a, b) for low, ref in
                        zip(other, out["ref"]) for a, b in zip(low, ref)]
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

    def served_then_keep(ctx, sys_, turns, job):
        kept["params"] = sys_["engine"].params
        kept["pools"] = sys_["engine"].kv.arrays() \
            + sys_["engine"].state_pool.arrays
        return served(ctx, sys_, turns, job)

    system.prepare = prepare_with_controls
    system.check_served = served_then_keep
    sys.modules["lib.nemotronh_system"] = system
    sys.argv = ["benchmark/run.py", "--workload",
                "nemotron3super-serve-agents", "--seed", seed, "--seconds",
                seconds, "--trace", "0"]
    try:
        runpy.run_path(os.path.join(HERE, "run.py"), run_name="__main__")
    except SystemExit:
        pass
    print(json.dumps({"setup_controls": setup,
                      "bf16_state": bf16_state(kept)}), flush=True)


def bf16_state(kept):
    """The set-up check of a second engine whose SSD state is bfloat16."""
    try:
        import jax.numpy as jnp

        from deepspeed_tpu.models.serving import SSDState

        for array in kept["pools"]:     # the served check gives them back
            if not array.is_deleted():
                array.delete()
        arrays = SSDState.arrays
        SSDState.arrays = lambda self, dtype: tuple(
            (shape, jnp.bfloat16) for shape, _ in arrays(self, dtype))
        try:
            sys_ = dict(kept["prepared"], params=kept["params"],
                        reference=None)
            sys_ = system.build(kept["ctx"], sys_)
            assert sys_["engine"].state_pool.arrays[0].dtype == jnp.bfloat16
            out = system.check_against_reference(kept["ctx"], sys_)
        finally:
            SSDState.arrays = arrays
        return {"ok": out["ok"], "logits_rel_l2": out["logits_rel_l2"],
                "logits_rel_l2_median": out["logits_rel_l2_median"],
                "groups": {k: {"median": g["median"], "over": g["over"],
                               "n": g["n"], "best": min(g["each"]),
                               "worst": max(g["each"])}
                           for k, g in out["groups"].items()}}
    except Exception as exc:            # noqa: BLE001
        return {"error": repr(exc)[-400:]}


if __name__ == "__main__":
    main()
