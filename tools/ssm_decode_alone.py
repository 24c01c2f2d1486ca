#!/usr/bin/env python3
"""The selective scan's one-token update and its convolution-with-carry of a
DECODE batch, alone on the chip: a layer's calls at the Phi-4-mini-flash
cell's shape (64 rows, ``[16, 5120]`` float32 states and ``[3, 5120]``
carries in pools of 9 x 64 + 1 slots), against the bytes they have to move.

    chiprun --chips 1 -- python3 tools/ssm_decode_alone.py
    python3 tools/ssm_decode_alone.py --cpu-rehearsal   # toy sizes, no timing claim

Forms of the update: ``xla`` (the gather / update / scatter a decode batch
ran before PR 56, kept here as the reference), ``kernel``
(``ssm_ops.ssm_decode``), and the kernel's plumbing with this file's
switches: ``copies`` (every state block fetched and written back, no
arithmetic) and ``arith`` (the arithmetic on ONE resident state block: the
block index never changes, so nothing is copied after the first step).
Forms of the convolution: ``xla`` (before PR 56) and ``kernel``
(``gdn_ops.causal_conv_step`` with the bias).  Each runs ``--iters`` times
inside ONE jitted ``fori_loop`` whose carry is the pool, layer after layer,
as the serving window runs it.  Bytes: a row's state (or carry) read and
written, its inputs read, its output written.  One JSON line a form.
"""
import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]   # lib/peaks.py

#: rows, channels, state values a channel, taps, scan layers
SHAPE = (64, 5120, 16, 4, 9)


def scan_forms():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deepspeed_tpu.inference.v2.kernels import ssm_ops

    def xla(x, delta, Bm, Cm, A, D, pool, rows, keep):
        S0 = jnp.where(keep[:, None, None], pool[rows], 0.0)
        a, b = ssm_ops._terms(x, delta, Bm, A)
        S1 = a * S0 + b
        return ssm_ops._readout(S1, x, Cm, D), pool.at[rows].set(S1)

    def copies_body(rows_ref, keep_ref, x_ref, dt_ref, b_ref, c_ref, a_ref,
                    d_ref, s_ref, y_ref, s_out_ref, *, rb):
        i = pl.program_id(1) % rb
        s_out_ref[...] = s_ref[...]
        y_ref[pl.ds(i, 1), :] = x_ref[pl.ds(i, 1), :]

    def split(part):
        """``ssm_ops.ssm_decode``'s call with one switch."""
        def call(x, delta, Bm, Cm, A, D, pool, rows, keep):
            R, C = x.shape
            N = A.shape[0]
            cb = ssm_ops._channel_block(N, C)
            rb = min(R, 8)
            row = pl.BlockSpec((rb, cb), lambda j, r, *_: (r // rb, j))
            col = pl.BlockSpec((1, N, 1), lambda j, r, *_: (r, 0, 0))
            state = pl.BlockSpec(
                (1, N, cb), (lambda j, r, rows, keep: (rows[r], 0, j))
                if part == "copies" else (lambda j, r, *_: (0, 0, j)))
            body = copies_body if part == "copies" \
                else ssm_ops._ssm_decode_kernel
            return pl.pallas_call(
                functools.partial(body, rb=rb),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=2, grid=(C // cb, R),
                    in_specs=[row, row, col, col,
                              pl.BlockSpec((N, cb), lambda j, r, *_: (0, j)),
                              pl.BlockSpec((1, cb), lambda j, r, *_: (0, j)),
                              state],
                    out_specs=[row, state]),
                out_shape=[jax.ShapeDtypeStruct((R, C), jnp.float32),
                           pltpu.HBM(pool.shape, pool.dtype)],
                input_output_aliases={8: 1},
                interpret=ssm_ops._interpret(), name=f"ssm_split_{part}",
            )(rows, keep.astype(jnp.int32), x, delta, Bm[:, :, None],
              Cm[:, :, None], A, D[None], pool)
        return call

    return {"xla": xla, "kernel": ssm_ops.ssm_decode,
            "copies": split("copies"), "arith": split("arith")}


def conv_forms():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.kernels import gdn_ops

    def xla(x, w, bias, pool, rows, keep):
        K = w.shape[0]
        xf, wf = x.astype(jnp.float32), w.astype(jnp.float32)
        carry = jnp.where(keep[:, None, None], pool[rows], 0
                          ).astype(jnp.float32)
        out = wf[K - 1][None] * xf + jnp.einsum("kc,rkc->rc", wf[:K - 1],
                                                carry)
        new = jnp.concatenate([carry[:, 1:], xf[:, None]], axis=1)
        return jax.nn.silu(out + bias.astype(jnp.float32)[None]), \
            pool.at[rows].set(new.astype(pool.dtype))

    def kernel(x, w, bias, pool, rows, keep):
        return gdn_ops.causal_conv_step(x, w, pool, rows, keep, bias)

    return {"xla": xla, "kernel": kernel}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=900)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--out", default="chiprun_out/pr56/ssm_decode_alone.jsonl")
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
    R, C, N, K, layers = (4, 256, 16, 4, 2) if args.cpu_rehearsal else SHAPE
    iters = 4 if args.cpu_rehearsal else args.iters
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 12)
    f32, bf16 = jnp.float32, jnp.bfloat16
    slots = jax.random.permutation(ks[0], R).astype(jnp.int32)
    keep = jnp.arange(R) % 16 != 0              # a fresh row now and then
    M = layers * R + 1
    x = jax.random.normal(ks[1], (R, C), f32)
    scan_in = (x, jax.nn.softplus(jax.random.normal(ks[2], (R, C)) - 1.0),
               jax.random.normal(ks[3], (R, N)),
               jax.random.normal(ks[4], (R, N)),
               -jax.random.uniform(ks[5], (N, C), minval=0.05, maxval=4.0),
               jax.random.normal(ks[6], (C,)))
    conv_in = (x.astype(bf16),
               (jax.random.normal(ks[7], (K, C)) / 2).astype(bf16),
               (jax.random.normal(ks[8], (C,)) / 3).astype(bf16))
    cases = [
        ("scan", scan_forms(), scan_in,
         jax.random.normal(ks[9], (M, N, C), f32),
         # the state read and written, x / delta read, y written, B / C
         R * (2 * N * C * 4 + 3 * C * 4 + 2 * N * 4),
         # 'copies' and 'arith' do not compute the update
         ("xla", "kernel")),
        ("conv", conv_forms(), conv_in,
         jax.random.normal(ks[10], (M, K - 1, C), f32).astype(bf16),
         R * (2 * (K - 1) * C * 2 + C * 2 + C * 4), ("xla", "kernel")),
    ]
    lines = []
    for what, forms, inputs, pool0, nbytes, agree in cases:
        ref = None
        for name, fn in forms.items():
            def loop(pool, inputs, fn=fn):
                def body(i, carry):
                    pool, acc = carry
                    out, pool = fn(*inputs, pool, slots + (i % layers) * R,
                                   keep)
                    return pool, acc + out[:, :128]
                return jax.lax.fori_loop(
                    0, iters, body, (pool, jnp.zeros((R, 128), f32)))

            run = jax.jit(loop, donate_argnums=(0,))
            pool, acc = jax.block_until_ready(run(pool0 + 0, inputs))
            times = []
            for _ in range(3):
                t0 = time.perf_counter()
                pool, acc = jax.block_until_ready(run(pool, inputs))
                times.append((time.perf_counter() - t0) / iters)
            line = dict(
                what=what, form=name, rows=R, channels=C, pool_rows=M,
                iters=iters, us_a_call=min(times) * 1e6,
                bytes_a_call=nbytes, roof_us=nbytes / hbm * 1e6,
                roofline_pct=100 * nbytes / hbm / min(times),
                us_each_of_3=[t * 1e6 for t in times],
                platform=dev.platform, device_kind=dev.device_kind)
            if name in agree:
                # the same calls on the same pool: the forms must agree
                got = (jax.device_get(acc).astype("float64"),
                       jax.device_get(pool[:-1]).astype("float64"))
                ref = ref or got
                line.update(
                    out_max_rel_diff=float(
                        abs(got[0] - ref[0]).max() / abs(ref[0]).max()),
                    pool_max_abs_diff=float(abs(got[1] - ref[1]).max()))
            lines.append(line)
            print(json.dumps(line), flush=True)
    with open(args.out, "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
