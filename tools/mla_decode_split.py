#!/usr/bin/env python3
"""The latent (MLA) decode kernel alone on the chip, at both latent cells'
shapes, split into its copies and its arithmetic.

    chiprun --chips 1 -- python3 tools/mla_decode_split.py [--parent .checkout_parent]
    python3 tools/mla_decode_split.py --cpu-rehearsal   # toy sizes, no timing claim

A decode step's calls (LongCat-Flash: 128 x 64 heads over ~2k-token
contexts, 8 page layers; Xing4: 64 x 32 heads over ~13k, 5 page layers) run
``--iters`` times inside ONE jitted ``fori_loop`` over one page pool of the
cell's size, the contexts drawn as the cell's traffic file draws them
(history + message + a uniform share of the answer).  Forms:

- ``tree``: ``mla_ops.mla_paged_decode`` of this checkout; ``parent``: the
  same function of ``--parent``'s ``mla_ops.py`` (the two outputs are
  compared bit for bit; ``tree`` is also compared with
  ``mla_attend_dense``, element by element);
- this file's own copy of the kernel body with switches, ``<part>@<h>``:
  ``whole`` / ``copies`` (the walk with its compute removed) / ``arith``
  (the compute on one resident chunk, no copies), ``h`` = 1 with the
  first-chunk hand-over across grid steps, 0 without; ``whole+full`` /
  ``arith+full``: full chunks scored without the two selects that only a
  context's last chunk needs.

Bytes: ``2·W`` a cached token; the roof is the HBM's.  One JSON line a
(shape, form).
"""
import argparse
import functools
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]   # lib/peaks.py

W, R, PS = 640, 512, 64
#: sequences, heads, page layers, table width (blocks), softmax scale, traffic
SHAPES = {
    "longcatflash-serve-chat": (128, 64, 8, 4224 // PS, 192 ** -0.5,
                                "sessions-128-chat"),
    "xing4-29b-serve-sessions": (64, 32, 5, 25088 // PS, 0.1447,
                                 "sessions-64x13k"),
}
FORMS = ["parent", "tree", "whole@0", "whole@1", "copies@0", "copies@1",
         "arith@0", "whole+full@1", "arith+full@0"]


def contexts(traffic, n, rng):
    """A decode step's contexts mid-window: every session's history and
    message, and a uniform share of its answer."""
    from lib import serve_system

    with open(os.path.join(ROOT, "benchmark", "traffic",
                           traffic + ".json")) as f:
        spec = json.load(f)
    doc, msg, ans = (serve_system.lengths(spec[k], n, rng) for k in (
        "document_tokens", "question_tokens", "answer_tokens"))
    return [d + m + int(rng.uniform(0, a)) for d, m, a in zip(doc, msg, ans)]


def split_call(part, handover, full):
    """``mla_ops.mla_paged_decode`` with this file's copy of its body."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deepspeed_tpu.inference.v2.kernels.mla_ops import _dot_nt
    from deepspeed_tpu.inference.v2.kernels.ragged_ops import _NEG_INF, _cdiv

    def kernel(kvl_ref, pt_ref, q_ref, pages_ref, o_ref, bufs, sems, acc,
               m_scr, l_scr, carry, *, scale, P, NB):
        s, S = pl.program_id(0), pl.num_programs(0)
        kvl = kvl_ref[s]
        CH = P * PS
        nch = _cdiv(kvl, CH)
        H = q_ref.shape[1]

        def page_needed(seq, page_idx):
            return page_idx * PS < kvl_ref[seq]

        def chunk_dma(seq, c, slot, p):
            pid = pt_ref[seq, jnp.minimum(c * P + p, NB - 1)]
            return pltpu.make_async_copy(
                pages_ref.at[pid], bufs.at[slot, p], sems.at[slot, p])

        def start_chunk(seq, c, slot):
            for p in range(P):
                @pl.when(page_needed(seq, c * P + p))
                def _():
                    chunk_dma(seq, c, slot, p).start()

        def wait_chunk(seq, c, slot):
            for p in range(P):
                @pl.when(page_needed(seq, c * P + p))
                def _():
                    chunk_dma(seq, c, slot, p).wait()

        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

        @pl.when(s == 0)
        def _():
            carry[0] = 0
            if part == "arith":          # one resident chunk, fetched once
                for slot in range(2):
                    for p in range(P):
                        dma = pltpu.make_async_copy(
                            pages_ref.at[pt_ref[0, 0]], bufs.at[slot, p],
                            sems.at[slot, p])
                        dma.start()
                        dma.wait()
        fetched = carry[0] == 1
        slot0 = jnp.where(fetched, carry[1], 0)
        carry[0] = 0

        @pl.when(kvl > 0)
        def _walk():
            if part != "arith":
                @pl.when(jnp.logical_not(fetched))
                def _():
                    start_chunk(s, 0, slot0)

            def compute(c, slot, tail):
                rows = bufs[slot].reshape(CH, -1)
                if tail:
                    col_ok = jax.lax.broadcasted_iota(
                        jnp.int32, (CH, 1), 0) + c * CH < kvl
                    rows = jnp.where(col_ok, rows, 0)
                s_mat = _dot_nt(q_ref[0], rows) * scale
                if tail:
                    k_pos = c * CH + jax.lax.broadcasted_iota(
                        jnp.int32, (H, CH), 1)
                    s_mat = jnp.where(k_pos < kvl, s_mat, _NEG_INF)
                m_prev = m_scr[:, :1]
                m_new = jnp.maximum(
                    m_prev, jnp.max(s_mat, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p_mat = jnp.exp(s_mat - m_new)
                l_scr[:] = jnp.broadcast_to(
                    alpha * l_scr[:, :1]
                    + jnp.sum(p_mat, axis=1, keepdims=True), l_scr.shape)
                acc[:] = acc[:] * alpha + jnp.dot(
                    p_mat.astype(rows.dtype), rows[:, :R],
                    preferred_element_type=jnp.float32)
                m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

            def body(state):
                c, slot = state
                if part != "arith":
                    @pl.when(c + 1 < nch)
                    def _prefetch():
                        start_chunk(s, c + 1, 1 - slot)

                    if handover:
                        nxt = jnp.minimum(s + 1, S - 1)

                        @pl.when((c + 1 == nch) & (s + 1 < S)
                                 & (kvl_ref[nxt] > 0))
                        def _next_seq():
                            start_chunk(nxt, 0, 1 - slot)
                            carry[0] = 1
                            carry[1] = 1 - slot

                    wait_chunk(s, c, slot)
                if part != "copies":
                    if full:
                        whole = (c + 1) * CH <= kvl
                        pl.when(whole)(lambda: compute(c, slot, False))
                        pl.when(jnp.logical_not(whole))(
                            lambda: compute(c, slot, True))
                    else:
                        compute(c, slot, True)
                return c + 1, 1 - slot

            jax.lax.while_loop(lambda st: st[0] < nch, body,
                               (jnp.int32(0), slot0))

        l = l_scr[:, :1]
        o_ref[0] = (acc[:] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

    def call(q, pages, kv_lens, page_table, *, rank, scale,
             pages_per_chunk=8):
        S, H, _ = q.shape
        NB = page_table.shape[1]
        P = min(pages_per_chunk, NB)
        return pl.pallas_call(
            functools.partial(kernel, scale=scale, P=P, NB=NB),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(S,),
                in_specs=[pl.BlockSpec((1, H, W), lambda s, *_: (s, 0, 0)),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((1, H, rank), lambda s, *_: (s, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((2, P, PS, W), pages.dtype),
                    pltpu.SemaphoreType.DMA((2, P)),
                    pltpu.VMEM((H, rank), jnp.float32),
                    pltpu.VMEM((H, 128), jnp.float32),
                    pltpu.VMEM((H, 128), jnp.float32),
                    pltpu.SMEM((2,), jnp.int32)]),
            out_shape=jax.ShapeDtypeStruct((S, H, rank), q.dtype),
            interpret=jax.default_backend() != "tpu",
            name="mla_decode_split",
        )(kv_lens.astype(jnp.int32), page_table.astype(jnp.int32), q, pages)

    return call


def form_call(name, parent):
    """The callable of one form; None where ``--parent`` was not given."""
    from deepspeed_tpu.inference.v2.kernels import mla_ops

    if name == "tree":
        return mla_ops.mla_paged_decode
    if name == "parent":
        if not parent:
            return None
        spec = importlib.util.spec_from_file_location(
            mla_ops.__name__ + "_parent", os.path.join(
                parent, os.path.relpath(mla_ops.__file__, ROOT)))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.mla_paged_decode
    part, h = name.split("@")
    return split_call(part.removesuffix("+full"), h == "1",
                      part.endswith("+full"))


def apart(out, ref):
    """The kernel's bf16 output against the float32 reference rounded to
    bf16, element by element (PR 37's account of the K/V kernel)."""
    import numpy as np

    out, ref = (np.asarray(a, np.float32) for a in (out, ref))
    diff = np.abs(out - ref)
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(ref), 1e-30))) - 7)
    return dict(finite=bool(np.isfinite(out).all()),
                max_abs_diff=float(diff.max()),
                bit_equal_share=float((diff == 0).mean()),
                within_1_ulp_share=float((diff <= ulp).mean()),
                allclose=bool(np.allclose(out, ref, rtol=2.0 ** -7,
                                          atol=6e-3)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=40)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--parent", default=None,
                    help="a checkout whose mla_ops.py is timed beside ours")
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--cells", default=",".join(SHAPES))
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--out", default="chiprun_out/pr45/mla_decode_split.jsonl")
    args = ap.parse_args()
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2.kernels import mla_ops
    from lib import peaks

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        sys.exit("no TPU here: run through chiprun, or --cpu-rehearsal")
    # the rehearsal's times are no device's: its roofline column means nothing
    hbm = 819e9 if args.cpu_rehearsal else \
        peaks.peaks_for(str(dev.device_kind)).hbm_bytes_per_s
    iters = 1 if args.cpu_rehearsal else args.iters
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    lines = []
    for cell in args.cells.split(","):
        S, H, layers, NB, scale, traffic = SHAPES[cell]
        rng = np.random.default_rng(args.seed)
        ctx = contexts(traffic, S, rng)
        if args.cpu_rehearsal:            # toy: 4 rows, 2 layers, an empty row
            S, layers, NB = 4, 2, 12
            ctx = [min(c, 700) for c in ctx[:S]]
            ctx[2] = 0
        # a page layer as full as the cells' pools (0.8), its blocks dealt
        # out in a random order; table entries past a context are not read
        blocks = [-(-c // PS) for c in ctx]
        per_layer = int(sum(blocks) / 0.8)
        ids = rng.permutation(per_layer)[:sum(blocks)].astype(np.int32)
        table, at = np.zeros((S, NB), np.int32), 0
        for s, n in enumerate(blocks):
            table[s, :n], at = ids[at:at + n], at + n
        ks = jax.random.split(jax.random.PRNGKey(args.seed), 2)
        q = jax.random.normal(ks[0], (S, H, W), jnp.float32) \
            .astype(jnp.bfloat16)
        # NaN wherever no walk may read: the blocks no sequence owns and the
        # rows of a context's last page behind its end
        nan = np.ones((per_layer, PS), bool)
        nan[ids] = False
        for s, c in enumerate(ctx):
            if c % PS:
                nan[table[s, c // PS], c % PS:] = True
        base = jnp.where(jnp.asarray(nan)[:, :, None], jnp.nan,
                         jax.random.normal(ks[1], (per_layer, PS, W),
                                           jnp.bfloat16))
        # every page layer holds the same values: only their addresses differ
        pages = jnp.tile(base, (layers, 1, 1))
        del base
        kvl, table = jnp.asarray(ctx, jnp.int32), jnp.asarray(table)
        nbytes = 2 * W * sum(ctx)         # one page layer's call
        kw = dict(rank=R, scale=scale)

        def dense(layer):                  # 8 sequences at a time: [8, C, W]
            outs = [mla_ops.mla_attend_dense(
                q[i:i + 8, None], pages, table[i:i + 8] + layer * per_layer,
                jnp.minimum(kvl[i:i + 8], 1), kvl[i:i + 8], **kw)[:, 0]
                for i in range(0, S, 8)]
            return jnp.concatenate(outs).astype(q.dtype)

        first = None
        for name in args.forms.split(","):
            fn = form_call(name, args.parent)
            if fn is None:
                continue

            def loop(q, pages, kvl, table, fn=fn):
                def body(i, acc):
                    for layer in range(layers):
                        out = fn(q, pages, kvl, table + layer * per_layer,
                                 **kw)
                        acc = acc + out[:, 0, :128].astype(jnp.float32)
                    return acc
                return jax.lax.fori_loop(
                    0, iters, body, jnp.zeros((S, 128), jnp.float32))

            run = jax.jit(loop)
            jax.block_until_ready(run(q, pages, kvl, table))     # compiles
            times = []
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(run(q, pages, kvl, table))
                times.append((time.perf_counter() - t0) / iters / layers)
            line = dict(
                cell=cell, form=name, rows=S, heads=H, page_layers=layers,
                ctx_mean=sum(ctx) / S, ctx_min=min(ctx), ctx_max=max(ctx),
                grid_steps=sum(c > 0 for c in ctx), iters=iters,
                us_a_call=min(times) * 1e6,
                us_a_grid_step=min(times) * 1e6 / S,
                bytes_a_call=nbytes, roof_us=nbytes / hbm * 1e6,
                roofline_pct=100 * nbytes / hbm / min(times),
                us_each_of_3=[t * 1e6 for t in times],
                platform=dev.platform, device_kind=dev.device_kind)
            if name in ("tree", "parent", "whole@0", "whole@1",
                        "whole+full@1"):
                # the last page layer's call, every row and head
                out = jax.jit(functools.partial(fn, **kw))(
                    q, pages, kvl, table + (layers - 1) * per_layer)
                first = out if first is None else first
                line["bit_equal_to_first"] = bool(jnp.array_equal(out, first))
                if name == "tree":
                    line.update(apart(out, dense(layers - 1)))
            lines.append(line)
            print(json.dumps(line), flush=True)
    with open(args.out, "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
