#!/usr/bin/env python3
"""The K/V decode kernel alone on the chip at the Phi-4-mini-flash cell's
shape, in each STORED FORM of a token whose 20 combined rows of 128 tile no
sublane tile, split into its copies and its arithmetic.

    chiprun --chips 1 -- python3 tools/paged_decode_forms.py [--parent .checkout_parent]
    python3 tools/paged_decode_forms.py --cpu-rehearsal   # toy sizes, no timing claim

A decode step's calls (64 sequences, 40 query heads in 10 groups of 4,
64-token pages, bf16) run ``--iters`` times inside ONE jitted ``fori_loop``
over one pool of the cell's size, each call's queries hanging on the call
before it.  Two shapes: ``page`` (the one page layer,
4,800 blocks, read eight times a step; contexts drawn as the cell's traffic
file draws its riders': task context + question + a uniform share of the
answer) and ``ring`` (the eight window layers' rings of 512 rows, each seen
as 8 pages of its own, 65 slots a layer).  Pools:

- ``padded``: the parent's form, ``KVRow.tiled(10, 128)``: a token in 16 +
  16 rows of 128 (8,192 B), rows 10-15 and 26-31 zeros;
- ``lanes``: ``KVRow.packed(10, 128)``: 2 K rows and 2 V rows of 640, five
  heads along the lanes of each (5,120 B);
- ``flat``: the 20 rows as they are, a page ``[64 * 20, 128]`` (whole 16-row
  tiles) and the pair load at a stride of 10 words — the tree's kernel body
  under this file's own call (no row kind of the program stores this form).

Forms ``<pool>@<part>`` (``lanes@whole@6``: at most 6 pages a chunk): ``whole``
is ``ragged_ops.decode_paged_attention`` of this checkout; ``copies`` and
``arith`` are THE SAME body with this file's switches put into its source
(the walk with its compute taken out; the compute on whatever lies in the
chunk buffer, no copy started or waited for); ``parent``: the same function
of ``--parent``'s ``ragged_ops.py`` on the padded pool.  ``whole`` outputs are
compared bit for bit with ``padded@whole``'s, and with ``decode_attend_dense``.
``append@<pool>@<tokens>``: ``paged_kv_append`` of a decode batch's 64 rows or
a prefill chunk's 512, the pool in the loop's carry.

Bytes: the MODEL's 5,120 B a cached token (``roofline_pct``), and what the
pool stores (``streamed_pct``); the roof is the HBM's.  One JSON line a
(shape, form).
"""
import argparse
import contextlib
import functools
import importlib.util
import inspect
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]   # lib/peaks.py

KV, G, HD, PS = 10, 4, 128, 64
H = KV * G
SEQS, MAX_CTX, WINDOW, RING_SLOTS, READS = 64, 4800, 512, 65, 8
TRAFFIC = "sessions-64-reasoning"
FORMS = ["padded@whole", "parent", "lanes@whole", "lanes@whole@6",
         "lanes@whole@2", "padded@copies", "lanes@copies", "padded@arith",
         "lanes@arith", "flat@whole", "flat@copies", "flat@arith",
         "append@padded@64", "append@lanes@64", "append@padded@512",
         "append@lanes@512"]


def contexts(n, rng):
    """A decode step's contexts mid-window: every session's task context
    and question, and a uniform share of its answer."""
    from lib import serve_system

    with open(os.path.join(ROOT, "benchmark", "traffic",
                           TRAFFIC + ".json")) as f:
        spec = json.load(f)
    doc, msg, ans = (serve_system.lengths(spec[k], n, rng) for k in (
        "document_tokens", "question_tokens", "answer_tokens"))
    return [d + m + int(rng.uniform(0, a)) for d, m, a in zip(doc, msg, ans)]


def load_parent(parent):
    from deepspeed_tpu.inference.v2.kernels import ragged_ops

    spec = importlib.util.spec_from_file_location(
        ragged_ops.__name__ + "_parent", os.path.join(
            parent, os.path.relpath(ragged_ops.__file__, ROOT)))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def switched(mod, part):
    """``mod._decode_paged_kernel`` with one switch put into its source."""
    if part == "whole":
        return mod._decode_paged_kernel
    src = inspect.getsource(mod._decode_paged_kernel)
    old, new, n = {
        "copies": ("            compute(c, slot)\n", "", 1),
        "arith": ("for dma in page_dmas(seq, c, slot, p):",
                  "for dma in ():", 2)}[part]
    assert src.count(old) == n, (part, old, src.count(old))
    src = src.replace(old, new)
    scope = dict(vars(mod))
    exec(compile(src, f"<{part} of {mod.__file__}>", "exec"), scope)
    return scope["_decode_paged_kernel"]


@contextlib.contextmanager
def body(mod, part):
    """``mod.decode_paged_attention`` traces the switched body meanwhile."""
    was, mod._decode_paged_kernel = mod._decode_paged_kernel, \
        switched(mod, part)
    try:
        yield
    finally:
        mod._decode_paged_kernel = was


def flat_call(mod, q, pages, kv_lens, page_table, *, scale, P):
    """The tree's kernel body on pages ``[PS * 2 KV, 128]``: the parent's
    pair load with a stride of ``KV`` = 10 words, from a buffer declared
    flat (``[.., PS, 20, 128]`` would be padded to 32 rows in VMEM)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, NB = page_table.shape
    P = min(P, NB)
    kernel = functools.partial(
        mod._decode_paged_kernel, scale=scale, ps=PS, P=P, KV=KV, G=G, NB=NB,
        alibi=None, alibi_scaled=False, hpg=2, pairs=1)
    NG, R = KV // 2, 2 * G
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S,),
            in_specs=[pl.BlockSpec((1, H, HD), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, HD), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, 1, P, PS * 2 * KV, HD), pages.dtype),
                pltpu.SemaphoreType.DMA((2, 1, P)),
                pltpu.VMEM((NG, R, HD), jnp.float32),
                pltpu.VMEM((NG, R, 128), jnp.float32),
                pltpu.VMEM((NG, R, 128), jnp.float32),
                pltpu.SMEM((2,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, HD), q.dtype),
        interpret=jax.default_backend() != "tpu",
        name="paged_decode",
    )(kv_lens.astype(jnp.int32), page_table.astype(jnp.int32), q, pages)


def pool_of(kind, rows):
    """``rows`` [pages, PS, 2 KV, HD] (K heads first) in a pool's form."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.serving import KVRow

    if kind == "flat":
        return rows.reshape(rows.shape[0], PS * 2 * KV, HD)
    if kind == "lanes":
        return rows.reshape(rows.shape[:2] + KVRow.packed(KV, HD).token_shape)
    stored = KVRow.tiled(KV, HD).stored
    pad = ((0, 0), (0, 0), (0, stored - KV), (0, 0))
    return jnp.concatenate([jnp.pad(rows[:, :, :KV], pad),
                            jnp.pad(rows[:, :, KV:], pad)], axis=2)


def apart(out, ref):
    """The kernel's bf16 output against the float32 reference rounded to
    bf16, element by element."""
    import numpy as np

    out, ref = (np.asarray(a, np.float32) for a in (out, ref))
    diff = np.abs(out - ref)
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(ref), 1e-30))) - 7)
    return dict(finite=bool(np.isfinite(out).all()),
                max_abs_diff=float(diff.max()),
                within_1_ulp_share=float((diff <= ulp).mean()),
                allclose=bool(np.allclose(out, ref, rtol=2.0 ** -6,
                                          atol=1e-2)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--parent", default=None,
                    help="a checkout whose ragged_ops.py is timed beside ours")
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--shapes", default="page,ring")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--out",
                    default="chiprun_out/pr59/paged_decode_forms.jsonl")
    args = ap.parse_args()
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2.kernels import ragged_ops
    from lib import peaks

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        sys.exit("no TPU here: run through chiprun, or --cpu-rehearsal")
    # the rehearsal's times are no device's: its roofline column means nothing
    hbm = 819e9 if args.cpu_rehearsal else \
        peaks.peaks_for(str(dev.device_kind)).hbm_bytes_per_s
    iters = 1 if args.cpu_rehearsal else args.iters
    parent = load_parent(args.parent) if args.parent else None
    scale = HD ** -0.5
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    lines = []

    def emit(line):
        line.update(platform=dev.platform, device_kind=dev.device_kind)
        lines.append(line)
        print(json.dumps(line), flush=True)

    for shape in args.shapes.split(","):
        rng = np.random.default_rng(args.seed)
        S, reads = SEQS, READS
        ctx = contexts(S, rng)
        if args.cpu_rehearsal:            # toy: 4 rows, an empty one
            S, reads = 4, 2
            ctx = [min(c, 300) for c in ctx[:S]]
            ctx[2] = 0
        if shape == "page":
            # ONE page layer, as full as the cell's (its blocks dealt out in
            # a random order), read ``reads`` times a step
            NB = (320 if args.cpu_rehearsal else MAX_CTX) // PS
            blocks = [-(-c // PS) for c in ctx]
            per_layer = 16 if args.cpu_rehearsal else MAX_CTX
            ids = rng.permutation(per_layer)[:sum(blocks)].astype(np.int32)
            table, at = np.zeros((S, NB), np.int32), 0
            for s, n in enumerate(blocks):
                table[s, :n], at = ids[at:at + n], at + n
            layer_step, pages = 0, per_layer + 1
        else:
            # ``reads`` window layers, a sequence's ring in each: 8 pages of
            # its own; min(ctx, window) live rows
            window = 128 if args.cpu_rehearsal else WINDOW
            slots = S + 1 if args.cpu_rehearsal else RING_SLOTS
            NB = window // PS
            ctx = [min(c, window) for c in ctx]
            slot = rng.permutation(slots)[:S]
            table = (slot[:, None] * NB + np.arange(NB)[None]).astype(np.int32)
            layer_step, pages = slots * NB, reads * slots * NB + NB
        ks = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        q = jax.random.normal(ks[0], (S, H, HD), jnp.float32) \
            .astype(jnp.bfloat16)
        # NaN wherever no walk may read: the blocks no sequence owns in the
        # first layer and the rows of a context's last page behind its end
        nan = np.zeros((pages, PS), bool)
        nan[:pages - 1 if shape == "page" else layer_step] = True
        nan[table[[s for s in range(S) for _ in range(-(-ctx[s] // PS))],
                  [b for s in range(S) for b in range(-(-ctx[s] // PS))]]] \
            = False
        for s, c in enumerate(ctx):
            if c % PS:
                nan[table[s, c // PS], c % PS:] = True
        rows = jnp.where(jnp.asarray(nan)[:, :, None, None], jnp.nan,
                         jax.random.normal(ks[1], (pages, PS, 2 * KV, HD),
                                           jnp.bfloat16))
        kvl, table = jnp.asarray(ctx, jnp.int32), jnp.asarray(table)
        model_bytes = 2 * KV * HD * 2 * sum(ctx)        # one call's
        dense = jnp.concatenate([ragged_ops.decode_attend_dense(
            q[i:i + 8], pool_of("lanes", rows), kvl[i:i + 8], table[i:i + 8],
            num_kv_heads=KV, scale=scale) for i in range(0, S, 8)])
        first, pools = None, {}

        def pool(kind):
            # one pool on the device at a time beside ``rows``
            if kind not in pools:
                pools.clear()
                pools[kind] = jax.block_until_ready(pool_of(kind, rows))
            return pools[kind]

        for name in args.forms.split(","):
            if name.startswith("append@"):
                if shape != "page":
                    continue
                _, kind, T = name.split("@")
                T = int(T)
                new = jax.random.normal(ks[2], (2, T, KV, HD), jnp.bfloat16)
                where = jax.random.permutation(ks[3], (pages - 1) * PS)[:T]

                def loop(pages_, new, where):
                    def step(i, p):
                        at = (where + i * 7) % ((pages - 1) * PS)
                        return ragged_ops.paged_kv_append(
                            p, new[0], new[1], at // PS, at % PS)
                    return jax.lax.fori_loop(0, iters * reads, step, pages_)

                operands = (pool(kind), new, where)
                run = jax.jit(loop, donate_argnums=0).lower(
                    *operands).compile()
                pools.clear()
                held, times = operands[0], []
                del operands
                for _ in range(4):          # the first: warm
                    t0 = time.perf_counter()
                    held = jax.block_until_ready(run(held, new, where))
                    times.append((time.perf_counter() - t0) / iters / reads)
                del held
                emit(dict(shape=shape, form=name, tokens=T, iters=iters,
                          us_a_call=min(times[1:]) * 1e6,
                          us_each_of_3=[t * 1e6 for t in times[1:]]))
                continue
            kind, part, *chunk = (name + "@whole").split("@")[:3] \
                if name != "parent" else ("padded", "whole")
            chunk = int(chunk[0]) if chunk and chunk[0].isdigit() else 8
            mod = parent if name == "parent" else ragged_ops
            if mod is None:
                continue
            if kind == "flat":
                fn = functools.partial(flat_call, mod, scale=scale, P=chunk)
            else:
                fn = functools.partial(
                    mod.decode_paged_attention, num_kv_heads=KV, scale=scale,
                    pages_per_chunk=chunk)

            def loop(q, pages_, kvl, table, fn=fn):
                # every call's queries hang on the call before (the eight
                # reads of ONE page layer are equal calls otherwise: XLA
                # keeps one of them, and hoists it out of the loop)
                def step(i, q):
                    for r in range(reads):
                        out = fn(q, pages_, kvl, table + r * layer_step)
                        q = q + out * jnp.zeros((), out.dtype)
                    return q
                return jax.lax.fori_loop(0, iters, step, q)

            operands = (q, pool(kind), kvl, table)
            n_rec = len(ragged_ops.get_tracer().records())
            try:
                with body(mod, part):
                    run = jax.jit(loop).lower(*operands).compile()
                    one = jax.jit(fn).lower(*operands).compile() \
                        if part == "whole" else None
            except Exception as exc:  # noqa: BLE001 — the compiler's words
                emit(dict(shape=shape, form=name, refused=repr(exc)[-1500:]))
                continue
            layout = [r.attrs for r in ragged_ops.get_tracer().records()[n_rec:]
                      if r.name == "attn/decode_layout"]
            jax.block_until_ready(run(*operands))
            times = []
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(run(*operands))
                times.append((time.perf_counter() - t0) / iters / reads)
            stored = operands[1].size // (pages * PS) * 2
            line = dict(
                shape=shape, form=name, rows=S, reads=reads,
                ctx_mean=sum(ctx) / S, ctx_min=min(ctx), ctx_max=max(ctx),
                iters=iters, us_a_call=min(times) * 1e6,
                row_bytes=stored, bytes_a_call=model_bytes,
                roof_us=model_bytes / hbm * 1e6,
                roofline_pct=100 * model_bytes / hbm / min(times),
                streamed_pct=100 * model_bytes * stored / (2 * KV * HD * 2)
                / hbm / min(times),
                us_each_of_3=[t * 1e6 for t in times],
                layout=layout[-1] if layout else None)
            if one is not None:
                out = one(*operands)
                first = out if first is None else first
                line["bit_equal_to_first"] = bool(jnp.array_equal(out, first))
                line.update(apart(out, dense))
            emit(line)
    with open(args.out, "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
