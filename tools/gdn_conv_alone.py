#!/usr/bin/env python3
"""The Gated DeltaNet convolution-with-carry of a DECODE batch, alone on the
chip: a layer's call at a serving cell's shape, in each form, against the
bytes it has to move.

    chiprun --chips 1 -- python3 tools/gdn_conv_alone.py
    python3 tools/gdn_conv_alone.py --cpu-rehearsal   # toy sizes, no timing claim

Forms: ``ragged`` (``gdn_ops.causal_conv_ragged`` + SiLU, what a decode batch
ran before PR 35), ``kernel`` (``gdn_ops.causal_conv_step``), ``xla`` (the
same contract in plain ``jax.numpy``: one row gather, one shift, one row
scatter).  Each runs ``--iters`` times inside ONE jitted ``fori_loop`` whose
carry is the pool, layer after layer, as the serving window runs it: the
pool keeps the loop's tiling, and what the program pays once at its entry
and exit (the re-tiling of a ``[N, 3, C]`` pool) is spread over the
iterations.  Bytes: a row's carry read and written, its input read, its
output written in float32.  Prints one JSON line a (shape, form).
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]   # lib/peaks.py

#: (rows, channels, state layers): the two recurrent cells' decode batches
SHAPES = {"olmohybrid7b": (128, 11520, 6), "qwen3next-80b": (64, 8192, 6)}
K = 4


def forms():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.kernels import gdn_ops

    def ragged(x, w, pool, rows, keep):
        R = x.shape[0]
        out, pool = gdn_ops.causal_conv_ragged(
            x, w, pool, rows, seq_of_token=jnp.arange(R, dtype=jnp.int32),
            q_offset=jnp.arange(R, dtype=jnp.int32),
            q_len=jnp.ones((R,), jnp.int32), fresh=~keep)
        return jax.nn.silu(out), pool

    def xla(x, w, pool, rows, keep):
        old = jnp.where(keep[:, None, None], pool[rows], 0)
        xf, wf, of = (a.astype(jnp.float32) for a in (x, w, old))
        out = wf[-1] * xf
        for d in range(1, wf.shape[0]):
            out = out + wf[-1 - d] * of[:, -d]
        new = jnp.concatenate([old[:, 1:], x[:, None].astype(pool.dtype)], 1)
        return jax.nn.silu(out), pool.at[rows].set(new)

    return {"ragged": ragged, "kernel": gdn_ops.causal_conv_step, "xla": xla}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=600)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--out", default="chiprun_out/pr35/gdn_conv_alone.jsonl")
    args = ap.parse_args()
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from lib import peaks

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        sys.exit("no TPU here: run through chiprun, or --cpu-rehearsal")
    # the rehearsal's times are no device's: its roofline column means nothing
    hbm = 819e9 if args.cpu_rehearsal else \
        peaks.peaks_for(str(dev.device_kind)).hbm_bytes_per_s
    shapes = {"toy": (4, 256, 2)} if args.cpu_rehearsal else SHAPES
    iters = 3 if args.cpu_rehearsal else args.iters
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    lines = []
    for cell, (R, C, layers) in shapes.items():
        ks = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        x = jax.random.normal(ks[0], (R, C), jnp.float32).astype(jnp.bfloat16)
        w = jax.random.normal(ks[1], (K, C), jnp.float32).astype(jnp.bfloat16)
        N = layers * R + 1
        slots = jax.random.permutation(ks[2], R).astype(jnp.int32)
        keep = jnp.arange(R) % 16 != 0          # a fresh row now and then
        nbytes = R * (2 * (K - 1) * C * 2 + C * 2 + C * 4)
        ref = None
        for name, fn in forms().items():
            def loop(pool, x, w, fn=fn):
                def body(i, carry):
                    pool, acc = carry
                    out, pool = fn(x, w, pool, slots + (i % layers) * R, keep)
                    return pool, acc + out[:, :128]
                return jax.lax.fori_loop(
                    0, iters, body, (pool, jnp.zeros((R, 128), jnp.float32)))

            run = jax.jit(loop, donate_argnums=(0,))
            pool = jax.random.normal(ks[3], (N, K - 1, C),
                                     jnp.float32).astype(jnp.bfloat16)
            pool, acc = jax.block_until_ready(run(pool, x, w))   # compiles
            times = []
            for _ in range(3):
                t0 = time.perf_counter()
                pool, acc = jax.block_until_ready(run(pool, x, w))
                times.append((time.perf_counter() - t0) / iters)
            got = (jax.device_get(acc), jax.device_get(pool[:-1]))
            if ref is None:
                ref = got
            us = min(times) * 1e6
            lines.append(dict(
                cell=cell, form=name, rows=R, channels=C, pool_rows=N,
                iters=iters, us_a_call=us, bytes_a_call=nbytes,
                roof_us=nbytes / hbm * 1e6,
                roofline_pct=100 * nbytes / hbm / min(times),
                us_each_of_3=[t * 1e6 for t in times],
                # the same calls on the same pool: every form must agree
                out_max_abs_diff=float(abs(got[0] - ref[0]).max()),
                pool_equal=bool((got[1] == ref[1]).all()),
                platform=dev.platform, device_kind=dev.device_kind))
            print(json.dumps(lines[-1]), flush=True)
    with open(args.out, "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
