#!/usr/bin/env python3
"""Time the Gated DeltaNet decode kernel ALONE on the chip for a state whose
widths tile neither sublanes nor lanes (default: Olmo-Hybrid's 30 heads of
``[96, 192]``, 128 rows, a pool of 6 x 128 + 1 slots), in each layout the
state kind could store it in, with what each pool REALLY holds
(``memory_stats()`` before and after, and the arrays' on-device size) and its
time against the bytes of real values (``lib/flops_gdn``).  Then one layer's
chunked prefill of a 512-token batch through each layout, and the K/V page
walk of the cell (30 query heads on a pool that stores a token in 32).

    chiprun --chips 1 -- python3 benchmark/tools/gdn_decode_layouts.py

Prints one JSON line.  Not part of a benchmark run (``gdn_decode_alone.py``
is the same at Qwen3-Next's ``[32, 128, 128]``)."""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.inference.v2.kernels import gdn_ops, ragged_ops  # noqa: E402
from lib import flops_gdn, peaks  # noqa: E402

CALLS = 30


def in_use():
    return (jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0)


def timed(fn, first, calls=CALLS):
    o = fn(first)
    jax.block_until_ready(o)
    t0 = time.perf_counter()
    for _ in range(calls):
        o = fn(o[0])
    jax.block_until_ready(o)
    return (time.perf_counter() - t0) / calls, o


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", type=int, default=30)
    ap.add_argument("--key-dim", type=int, default=96)
    ap.add_argument("--value-dim", type=int, default=192)
    ap.add_argument("--rows", type=int, default=128)
    ap.add_argument("--layers", type=int, default=6)
    a = ap.parse_args()
    H, dk, dv, R = a.heads, a.key_dim, a.value_dim, a.rows
    model = dict(linear_num_value_heads=H, linear_key_head_dim=dk,
                 linear_value_head_dim=dv)
    peak = peaks.peaks_for(str(jax.devices()[0].device_kind))
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = gdn_ops.l2norm(jax.random.normal(ks[1], (R, H, dk))) / dk ** 0.5
    k = gdn_ops.l2norm(jax.random.normal(ks[2], (R, H, dk)))
    v = jax.random.normal(ks[3], (R, H, dv))
    alpha = jax.random.uniform(ks[4], (R, H), minval=0.5, maxval=0.99)
    beta = jax.random.uniform(ks[5], (R, H), minval=0.2, maxval=1.8)
    rows = 2 * R + jnp.arange(R, dtype=jnp.int32)          # state layer 2
    n = a.layers * R + 1
    least = flops_gdn.gdn_decode_bytes(model, R) / peak.hbm_bytes_per_s
    out = {"bytes_a_call": flops_gdn.gdn_decode_bytes(model, R),
           "least_us": least * 1e6, "layouts": {}}
    # one sequence of 512 tokens, 8 chunks: a layer's chunked prefill
    T = 512
    kp = jax.random.split(jax.random.PRNGKey(1), 5)
    qp = gdn_ops.l2norm(jax.random.normal(kp[0], (T, H, dk))) / dk ** 0.5
    kk = gdn_ops.l2norm(jax.random.normal(kp[1], (T, H, dk)))
    vp = jax.random.normal(kp[2], (T, H, dv))
    gp = -jax.random.uniform(kp[3], (T, H), minval=0.01, maxval=0.7)
    bp = jax.random.uniform(kp[4], (T, H), minval=0.2, maxval=1.8)
    q_len = jnp.zeros((R,), jnp.int32).at[0].set(T)
    cu = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(q_len)])
    fresh = jnp.ones((R,), bool)
    jax.block_until_ready((q, k, v, alpha, beta, qp, kk, vp, gp, bp))
    reference = None
    for name, P in (("plain", 1), ("pairs", 2)):
        entry = out["layouts"][name] = {}
        if H % P or (P * dv) % 128 and P > 1:
            entry["error"] = "not a layout of these widths"
            continue
        shape = (n, H // P, dk, P * dv)
        try:
            before = in_use()
            # the same states in every layout
            pool = jax.jit(lambda: jax.vmap(
                lambda S: gdn_ops.pack_state(S, P * dv))(
                    0.1 * jax.random.normal(ks[0], (n, H, dk, dv),
                                            jnp.float32)))()
            jax.block_until_ready(pool)
            assert pool.shape == shape
            entry.update(
                values_bytes=4 * n * H * dk * dv,
                held_bytes=in_use() - before,
                on_device_bytes=int(pool.on_device_size_in_bytes()))
            fn = jax.jit(lambda pool: gdn_ops.gdn_decode(
                q, k, v, alpha, beta, pool, rows)[::-1], donate_argnums=(0,))
            s, o = timed(fn, pool)
            entry.update(us=s * 1e6, roofline_pct=100 * least / s)
            got = jax.device_get(o[1])
            if reference is None:
                reference = got
            else:       # the layouts are the same function of the inputs
                entry["max_abs_diff_to_plain"] = float(
                    abs(got - reference).max())
            # T = (I + A)^-1 by squarings (beta <= 1) and by substitution
            for name_, sub in (("chunk_prefill_512_us", False),
                               ("chunk_prefill_512_substitution_us", True)):
                fn = jax.jit(lambda pool, sub=sub: gdn_ops.gdn_chunk_prefill(
                    qp, kk, vp, gp, bp, pool, rows, cu_q_lens=cu,
                    q_len=q_len, fresh=fresh, substitution=sub)[::-1],
                    donate_argnums=(0,))
                s, o = timed(fn, o[0], 10)
                entry[name_] = s * 1e6
            del o, pool
        except Exception as exc:                            # noqa: BLE001
            entry["error"] = repr(exc)[-400:]
    # the convolution's carry: [K - 1, channels] in the serving dtype
    C = 2 * H * dk + H * dv
    before = in_use()
    carry = jnp.zeros((n, 3, C), jnp.bfloat16)
    jax.block_until_ready(carry)
    out["carry"] = {"values_bytes": n * 3 * C * 2,
                    "held_bytes": in_use() - before,
                    "on_device_bytes": int(carry.on_device_size_in_bytes())}
    del carry
    # the K/V page walk of the cell: 128 rows, 30 heads of 128 stored in 32,
    # contexts 600-1,700 (mean ~1,000), one layer's pages
    PAGE, NB, KV, hd = 64, 36, 30, 128
    ctx = jax.random.randint(jax.random.PRNGKey(2), (R,), 600, 1700)
    table = jnp.arange(R * NB, dtype=jnp.int32).reshape(R, NB)
    qd = jax.random.normal(ks[1], (R, KV, hd), jnp.bfloat16)
    moved = float(jnp.sum(ctx)) * 2 * KV * hd * 2
    pages = jax.random.normal(ks[2], (R * NB + 1, PAGE, 2 * 32, hd),
                              jnp.bfloat16)
    fn = jax.jit(lambda pages: (pages, ragged_ops.decode_paged_attention(
        qd, pages, ctx, table, num_kv_heads=KV)), donate_argnums=(0,))
    s, _ = timed(fn, pages)
    out["paged_decode_stored_32"] = {
        "us": s * 1e6, "model_bytes": moved,
        "roofline_pct": 100 * moved / peak.hbm_bytes_per_s / s}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
