#!/usr/bin/env python3
"""Time the Gated DeltaNet decode kernel ALONE on the chip at the cell's
shape (a layer's call: 128 rows, 32 heads, a [128, 128] float32 state a
head, a pool of 6 x 128 + 1 slots), against its bytes (``lib/flops_gdn``),
for a few sizes of the block of heads a grid step takes; and one layer's
chunked prefill of a 512-token batch.

    chiprun --chips 1 -- python3 benchmark/tools/gdn_decode_alone.py

Prints one JSON line.  Not part of a benchmark run."""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.inference.v2.kernels import gdn_ops  # noqa: E402
from lib import flops_gdn, peaks  # noqa: E402

MODEL = dict(linear_num_value_heads=32, linear_key_head_dim=128,
             linear_value_head_dim=128)
ROWS, LAYERS, SLOTS, CALLS = 128, 6, 128, 30


def main():
    H, dk, dv = 32, 128, 128
    peak = peaks.peaks_for(str(jax.devices()[0].device_kind))
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    pool = jax.random.normal(ks[0], (LAYERS * SLOTS + 1, H, dk, dv),
                             jnp.float32) * 0.1
    q = gdn_ops.l2norm(jax.random.normal(ks[1], (ROWS, H, dk))) / dk ** 0.5
    k = gdn_ops.l2norm(jax.random.normal(ks[2], (ROWS, H, dk)))
    v = jax.random.normal(ks[3], (ROWS, H, dv))
    alpha = jax.random.uniform(ks[4], (ROWS, H), minval=0.5, maxval=0.99)
    beta = jax.random.uniform(ks[5], (ROWS, H), minval=0.1, maxval=0.9)
    rows = 2 * SLOTS + jnp.arange(ROWS, dtype=jnp.int32)   # state layer 2
    least = flops_gdn.gdn_decode_bytes(MODEL, ROWS) / peak.hbm_bytes_per_s
    out = {"bytes_a_call": flops_gdn.gdn_decode_bytes(MODEL, ROWS),
           "least_us": least * 1e6, "decode": {}}
    for hb in (8, 16, 32):
        fn = jax.jit(lambda pool, hb=hb: gdn_ops.gdn_decode(
            q, k, v, alpha, beta, pool, rows, heads_per_step=hb),
            donate_argnums=(0,))
        try:
            run = lambda p: fn(p)[::-1]                     # noqa: E731
            pool2 = jnp.copy(pool)
            o = run(pool2)
            jax.block_until_ready(o)
            t0 = time.perf_counter()
            for _ in range(CALLS):
                o = run(o[0])
            jax.block_until_ready(o)
            s = (time.perf_counter() - t0) / CALLS
            out["decode"][hb] = {"us": s * 1e6,
                                 "roofline_pct": 100 * least / s}
            del o
        except Exception as exc:                            # noqa: BLE001
            out["decode"][hb] = {"error": repr(exc)[-300:]}
    # one layer's chunked prefill: one sequence of 512 tokens, 8 chunks
    T = 512
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    qp = gdn_ops.l2norm(jax.random.normal(ks[0], (T, H, dk))) / dk ** 0.5
    kp = gdn_ops.l2norm(jax.random.normal(ks[1], (T, H, dk)))
    vp = jax.random.normal(ks[2], (T, H, dv))
    gp = -jax.random.uniform(ks[3], (T, H), minval=0.01, maxval=0.7)
    bp = jax.random.uniform(ks[4], (T, H), minval=0.1, maxval=0.9)
    q_len = jnp.zeros((ROWS,), jnp.int32).at[0].set(T)
    cu = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(q_len)])
    fresh = jnp.ones((ROWS,), bool)
    fn = jax.jit(lambda pool: gdn_ops.gdn_chunk_prefill(
        qp, kp, vp, gp, bp, pool, rows, cu_q_lens=cu, q_len=q_len,
        fresh=fresh)[::-1], donate_argnums=(0,))
    o = fn(pool)
    jax.block_until_ready(o)
    t0 = time.perf_counter()
    for _ in range(10):
        o = fn(o[0])
    jax.block_until_ready(o)
    out["chunk_prefill_512_us"] = (time.perf_counter() - t0) / 10 * 1e6
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
