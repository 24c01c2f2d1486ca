#!/usr/bin/env python3
"""The sparse-attention path of ``kernels/sparse_ops.py`` alone on the chip at
the Keye cell's shapes, step by step: score (XLA's gather form, the page walk
with its body left out, the walk), select (threshold, then the positions),
read, attend; the whole decode call; a prefill chunk; the in-place append.

    chiprun --chips 1 -- python3 tools/sparse_decode_split.py
    python3 tools/sparse_decode_split.py --cpu-rehearsal     # toy sizes
    python3 tools/sparse_decode_split.py --compile-only      # v5e compiler

A decode step's call (``--seqs`` sequences of log-uniform 16k-64k tokens, 32
query / 4 K/V heads of 128, 16 index heads of 64, ``topk`` 2,048, 64-token
pages, a pool of ``--layers`` page layers, random page ids) runs ``--iters``
times; every form is one jitted function of the same pools.  ``score`` is
``_index_scores`` (what the chip ran until PR 47 and the CPU still does),
``score_kernel`` is ``index_score_paged``, ``score_copies`` the same walk
with every copy and no arithmetic (what a one-page descriptor costs);
``whole`` is ``_decode_sparse`` as the chip runs it.  Bytes a form must move
(``lib/flops_sparse``) over its time is its share of the HBM roof.  One JSON
line a form.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--seqs", type=int, default=20)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--forms", default="")
    ap.add_argument("--score-pages", type=int, default=0)
    ap.add_argument("--kernel-pages", type=int, default=0)
    ap.add_argument("--radix-bits", type=int, default=0)
    args = ap.parse_args()
    if args.cpu_rehearsal or args.compile_only:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from deepspeed_tpu.inference.v2.kernels import sparse_ops as so

    if args.score_pages:
        so._SCORE_PAGES = args.score_pages
    if args.kernel_pages:
        so._KERNEL_PAGES = args.kernel_pages
    if args.radix_bits:
        so._RADIX_BITS = args.radix_bits
    toy = args.cpu_rehearsal
    H, KV, hd, Hi, di, ps = (4, 2, 32, 4, 16, 8) if toy \
        else (32, 4, 128, 16, 64, 64)
    topk = 16 if toy else 2048
    lo, hi = (40, 150) if toy else (16384, 65536)
    max_ctx = 160 if toy else 66688
    S, L = (4 if toy else args.seqs), args.layers
    T = 16 if toy else 512
    rng = np.random.default_rng(args.seed)
    ctx = np.exp(rng.uniform(np.log(lo), np.log(hi), S)).astype(np.int32)
    NB = -(-max_ctx // ps)
    nb = int(sum(-(-int(c) // ps) for c in ctx)) + 1
    table = np.zeros((S, NB), np.int32)
    free = rng.permutation(nb)
    at = 0
    for s, c in enumerate(ctx):
        n = -(-int(c) // ps)
        table[s, :n] = free[at:at + n]
        at += n
    pages = L * nb + 1
    dt = jnp.float32 if toy else jnp.bfloat16
    shapes = dict(
        kv=((pages, ps, 2 * KV, hd), dt), ix=((pages, ps // 2, 2 * di), dt),
        q=((S, H, hd), dt), qi=((S, Hi, di), dt), w=((S, Hi), dt),
        qT=((T, H, hd), dt), qiT=((T, Hi, di), dt), wT=((T, Hi), dt),
        k=((T, KV, hd), dt), ki=((T, di), dt),
        ctx=((S,), jnp.int32), table=((S, NB), jnp.int32))

    scale = hd ** -0.5
    def score(a):
        return so._index_scores(a["qi"], a["w"], a["ix"], a["table"],
                                jnp.max(a["ctx"]))

    def score_kernel(a):
        return so.index_score_paged(a["qi"], a["w"], a["ix"], a["ctx"],
                                    a["table"])

    def score_copies(a):
        """The walk with its body left out: every copy, no arithmetic."""
        def no_body(q, w, keys, Hi):
            zero = jnp.zeros((1, keys.shape[0]), jnp.float32)
            return zero, zero
        body, so._score_chunk = so._score_chunk, no_body
        try:
            return score_kernel(a)
        finally:
            so._score_chunk = body

    def ordered(a):
        sc = score(a)
        live = jnp.arange(sc.shape[-1])[None, :] < a["ctx"][:, None]
        return so._ordered(sc, live)

    def threshold(a):
        thr, need = so._kth_largest(ordered(a), topk)
        return thr, need

    def select(a):
        return so._select(ordered(a), topk)

    def compact(a):
        return so._compact(select(a), topk)

    def topk_sort(a):
        sc = score(a)
        live = jnp.arange(sc.shape[-1])[None, :] < a["ctx"][:, None]
        return lax.top_k(jnp.where(live, sc, -jnp.inf), topk)[1]

    def read(a):
        pos, _ = compact(a)
        page = jnp.take_along_axis(a["table"], pos // ps, axis=1)
        return a["kv"][page, pos % ps]

    def whole(a):
        return so._decode_sparse(a["q"], a["qi"], a["w"], a["kv"], a["ix"],
                                 a["ctx"], a["table"], scale=scale,
                                 num_kv_heads=KV, topk=topk)

    def prefill(a, n_ctx):
        cu = jnp.asarray([0, T] + [T] * (S - 1), jnp.int32)
        ctx1 = jnp.asarray([n_ctx] + [0] * (S - 1), jnp.int32)
        return so._ragged_sparse(a["qT"], a["qiT"], a["wT"], a["kv"],
                                 a["ix"], ctx1, a["table"], cu, scale=scale,
                                 num_kv_heads=KV, topk=topk)

    def prefill_score(a, n_ctx):
        return so._index_scores(a["qiT"], a["wT"], a["ix"], a["table"][0],
                                jnp.asarray(n_ctx))

    def prefill_select(a, n_ctx):
        sc = prefill_score(a, n_ctx)
        at = n_ctx - T + jnp.arange(T)
        causal = jnp.arange(sc.shape[-1])[None, :] <= at[:, None]
        return so._select(so._ordered(sc, causal), topk)

    def append_loop(a):
        """Ten appends with the pools as a loop's carry, the arguments
        donated: in place, as in a serving step (LAST: it eats the pools)."""
        def one(i, pools):
            tok = jnp.arange(T) + i
            return so.indexed_append(pools, a["k"], a["k"], a["ki"],
                                     a["table"][0, tok // ps], tok % ps)
        return lax.fori_loop(0, 10, one, (a["kv"], a["ix"]))

    big = int(ctx.max())
    mid = int(np.sort(ctx)[S // 2])
    forms = {
        "score": score, "score_copies": score_copies,
        "score_kernel": score_kernel, "ordered": ordered,
        "threshold": threshold, "select": select, "compact": compact, "read": read, "whole": whole,
        "topk_sort": topk_sort,
        "prefill_score@mid": lambda a: prefill_score(a, mid),
        "prefill_select@mid": lambda a: prefill_select(a, mid),
        "prefill@mid": lambda a: prefill(a, mid),
        "prefill@big": lambda a: prefill(a, big),
        "append_loop": append_loop,
    }
    if args.forms:
        forms = {k: forms[k] for k in args.forms.split(",")}

    total_ctx = int(ctx.sum())
    sel = int(np.minimum(ctx, topk).sum())
    need = dict.fromkeys(("score", "score_copies", "score_kernel"),
                         total_ctx * di * 2)
    need.update(read=sel * 2 * KV * hd * 2,
                whole=total_ctx * di * 2 + sel * 2 * KV * hd * 2)

    if args.compile_only:
        so._interpret = lambda: False       # the chip's branch, its kernel
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])
        structs = {k: jax.ShapeDtypeStruct(sh, d, sharding=one)
                   for k, (sh, d) in shapes.items()}
        for name, fn in forms.items():
            t0 = time.time()
            compiled = jax.jit(fn).lower(structs).compile()
            mem = compiled.memory_analysis()
            print(json.dumps({"form": name, "compile_s": time.time() - t0,
                              "temp_bytes": mem.temp_size_in_bytes}),
                  flush=True)
        return

    key = jax.random.PRNGKey(args.seed)
    arrays = {}
    for i, (k, (sh, d)) in enumerate(shapes.items()):
        if d != jnp.int32:
            arrays[k] = jax.random.normal(jax.random.fold_in(key, i), sh, d)
    arrays.update(ctx=jnp.asarray(ctx), table=jnp.asarray(table))
    jax.block_until_ready(arrays)
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                      "seqs": S, "ctx_total": total_ctx, "ctx_max": big,
                      "ctx_mid": mid, "pool_pages": pages}), flush=True)
    if "compact" in forms and "topk_sort" in forms:
        pos, count = jax.jit(compact)(arrays)
        best = np.sort(np.asarray(jax.jit(topk_sort)(arrays)), axis=-1)
        print(json.dumps({"compact_equals_topk_sort": bool(
            (np.asarray(pos) == best).all()),
            "count_min": int(np.asarray(count).min())}), flush=True)
    if "score" in forms and "score_kernel" in forms:
        want = np.asarray(jax.jit(score)(arrays))
        got = np.asarray(jax.jit(score_kernel)(arrays))
        live = np.arange(got.shape[1])[None, :] < ctx[:, None]
        print(json.dumps({"score_kernel_max_abs_diff": float(np.abs(np.where(
            live, got - want[:, :got.shape[1]], 0)).max()),
            "score_max_abs": float(np.abs(np.where(
                live, want[:, :got.shape[1]], 0)).max()),
            "beyond_ctx_all_zero": bool((got[~live] == 0).all())}),
            flush=True)
    for name, fn in forms.items():
        if name == "append_loop":
            compiled = jax.jit(fn, donate_argnums=0).lower(arrays).compile()
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(arrays))
            print(json.dumps({"form": name, "us_an_append": (
                time.perf_counter() - t0) * 1e6 / 10}), flush=True)
            continue
        jitted = jax.jit(fn)
        t0 = time.perf_counter()
        out = jax.block_until_ready(jitted(arrays))
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = jitted(arrays)
        jax.block_until_ready(out)
        us = (time.perf_counter() - t0) / args.iters * 1e6
        line = {"form": name, "us": us, "first_s": first}
        if name in need and dev.platform == "tpu":
            line["roofline_share"] = need[name] / 819e9 / (us * 1e-6)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
