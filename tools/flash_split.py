#!/usr/bin/env python3
"""The three flash-attention kernels alone on the chip, at the two train
cells' per-chip shapes, kernel by kernel and block size by block size.

    chiprun --chips 1 -- python3 tools/flash_split.py --parent .checkout_parent
    python3 tools/flash_split.py --cpu-rehearsal    # toy sizes, no timing claim
    python3 tools/flash_split.py --compile-only     # the TPU compiler's verdict, no chip

One jitted call makes the forward and both backward kernels
(``jax.vjp`` of ``flash_attention._flash_bhsd`` on bf16 ``[B, H, S, hd]``
inputs, K and V already repeated to the query heads as the model hands them
over, causal).  ``--iters`` calls run under the profiler and each kernel's
device time is read from the trace with the patterns of the benchmark's own
``flash_roofline`` metric (``benchmark/metrics/flash_roofline.json``), so a
kernel is found here exactly where a traced run of a cell finds it.  Forms:

- ``parent``: the kernels of ``--parent``'s ``flash_attention.py`` (skipped
  without ``--parent``);
- ``tree``: this checkout's;
- ``tree+T``: this checkout's with ``_dot_tn`` (``pT . do``, ``dsT . q`` in
  the dkv kernel) as a transposed copy and a plain dot.

``parent`` and ``tree+T`` run at every pair of ``--default-blocks`` (the
model's ``flash_block_q`` x ``flash_block_k``, and 256x512, the model's
before PR 54), ``tree`` at those and at every pair of ``--blocks``.  One JSON line a (shape, form, block pair): microseconds a
call for each kernel, its share of the MXU's bf16 peak on
``benchmark/lib/flops.py``'s counts (``fwd``: the forward's two products;
``bwd``: the algorithm's five against dq + dkv together; ``dq`` / ``dkv``:
the three / four products each kernel executes), the roofline share as the
benchmark's reader computes it, and row 0's outputs against float32 XLA
attention of the same bf16-rounded inputs (relative L2).
"""
import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]   # lib/, readers/

#: the cells' per-chip attention calls: [batch, heads, sequence, head_dim]
SHAPES = {"mistral7b-train-1chip": (4, 32, 2048, 128),
          "mixtral8x7b-train-zero3-4chip": (2, 32, 2048, 128)}
TOY = (1, 2, 256, 128)
SWEEP = [f"{q}x{k}" for q in (128, 256, 512) for k in (256, 512, 1024)]
KERNELS = ("fwd", "dq", "dkv")


def load_form(name, parent):
    """The flash module of one form; None where ``--parent`` was not given."""
    from deepspeed_tpu.ops.transformer import flash_attention as fa

    if name == "tree":
        return fa
    path = fa.__file__
    if name == "parent":
        if not parent:
            return None
        path = os.path.join(parent, os.path.relpath(fa.__file__, ROOT))
    spec = importlib.util.spec_from_file_location(
        f"{fa.__name__}_{name.replace('+', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if name == "tree+T":
        import jax.numpy as jnp

        mod._dot_tn = lambda a, b: jnp.dot(
            a.T, b, preferred_element_type=jnp.float32)
    return mod


def make_step(mod, scale, bq, bk):
    import jax

    def step(q, k, v, do):
        out, pull = jax.vjp(
            lambda q, k, v: mod._flash_bhsd(q, k, v, scale, True, bq, bk),
            q, k, v)
        return (out,) + pull(do)
    return jax.jit(step)


def reference(q, k, v, do, scale):
    """Float32 XLA attention of batch row 0 and its gradients."""
    import jax
    import jax.numpy as jnp

    def attend(q, k, v):
        s = jnp.einsum("hqd,hkd->hqk", q, k,
                       precision=jax.lax.Precision.HIGHEST) * scale
        keep = jnp.tril(jnp.ones(s.shape[-2:], bool))
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    f32 = [x[0].astype(jnp.float32) for x in (q, k, v, do)]
    out, pull = jax.vjp(attend, *f32[:3])
    return (out,) + pull(f32[3])


def rel_l2(got, want):
    import numpy as np

    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def measure(step, inputs, want, iters):
    """Row 0 against the reference, and the whole call on the host's clock."""
    import jax

    t0 = time.perf_counter()
    got = jax.block_until_ready(step(*inputs))
    row = {"first_call_s": time.perf_counter() - t0}
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        row[f"rel_l2.{name}"] = rel_l2(g[0], w)
    t0 = time.perf_counter()
    for _ in range(iters):
        got = step(*inputs)
    jax.block_until_ready(got)
    row["wall_us"] = (time.perf_counter() - t0) / iters * 1e6
    return row


def kernel_times(step, inputs, iters, patterns):
    """Seconds a call of each kernel, from a profiler trace of ``iters``
    calls; the trace too (for the benchmark's reader)."""
    import jax
    from lib import trace

    tmp = tempfile.mkdtemp(prefix="flash_split_")
    try:
        with jax.profiler.trace(tmp):
            for _ in range(iters):
                out = step(*inputs)
            jax.block_until_ready(out)
        path = trace.find_xplane(tmp)
        tr = trace.load_xplane(path) if path else None
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if tr is None:
        return None, None
    times = {}
    for name, pattern in zip(KERNELS, patterns):
        got = trace.kernel_seconds(tr, pattern)
        if got is None:
            return None, tr
        times[name] = got["seconds"] / got["calls"]
    return times, tr


def shares(times, tr, shape, chip, reader_args):
    """Each kernel's time against ``flops.py``'s counts for ``shape``, and
    the roofline share by the benchmark's own reader."""
    from lib import flops
    from readers import flash_roofline

    B, H, S, hd = shape
    sizes = dict(hidden_size=H * hd, intermediate_size=0, head_dim=hd,
                 num_attention_heads=H, num_key_value_heads=H,
                 num_hidden_layers=1, vocab_size=0)
    f_fwd = flops.flash_fwd_flops(sizes, B, S)
    executed = dict(fwd=f_fwd, dq=1.5 * f_fwd, dkv=2.0 * f_fwd)
    row = {}
    for name in KERNELS:
        row[f"{name}_us"] = times[name] * 1e6
        row[f"{name}_mxu"] = executed[name] / times[name] / chip.bf16_flops
    row["sum_us"] = sum(times.values()) * 1e6
    row["bwd_mxu"] = flops.flash_bwd_flops(sizes, B, S) / (
        times["dq"] + times["dkv"]) / chip.bf16_flops
    row["flash_roofline"] = flash_roofline.read(
        dict(trace=tr, peaks=chip, sizes=sizes,
             facts=dict(global_batch=B, chips=1, seq_len=S)), reader_args)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--parent", default=None,
                    help="a checkout whose flash_attention.py is timed too")
    ap.add_argument("--forms", default="parent,tree,tree+T")
    ap.add_argument("--cells", default=",".join(SHAPES))
    ap.add_argument("--blocks", default=",".join(SWEEP))
    ap.add_argument("--default-blocks", default=None,
                    help="QxK,...; the model's flash_block_q x flash_block_k "
                         "and 256x512")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--out", default="chiprun_out/pr54/flash_split.jsonl")
    args = ap.parse_args()
    if args.cpu_rehearsal or args.compile_only:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer import TransformerConfig
    from lib import peaks

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not (args.cpu_rehearsal or args.compile_only):
        sys.exit("no TPU here: run through chiprun, --cpu-rehearsal or "
                 "--compile-only")
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "flash_roofline.json")) as f:
        reader_args = json.load(f)["args"]
    patterns = [reader_args["fwd"]] + reader_args["bwd"]
    chip = peaks.peaks_for(str(dev.device_kind)) if on_chip else None
    cfg = TransformerConfig.tiny()
    defaults = (args.default_blocks or
                f"{cfg.flash_block_q}x{cfg.flash_block_k},256x512").split(",")
    target = None
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        target = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    os.makedirs(os.path.dirname(args.out), exist_ok=True)

    lines = []
    for cell in args.cells.split(","):
        B, H, S, hd = TOY if args.cpu_rehearsal else SHAPES[cell]
        scale = hd ** -0.5
        if args.compile_only:
            inputs = [jax.ShapeDtypeStruct((B, H, S, hd), jnp.bfloat16,
                                           sharding=target)] * 4
        else:
            keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
            inputs = [jax.random.normal(key, (B, H, S, hd), jnp.float32)
                      .astype(jnp.bfloat16) for key in keys]
            want = reference(*inputs, scale)
        for form in args.forms.split(","):
            mod = load_form(form, args.parent)
            if mod is None:
                continue
            if not on_chip and args.compile_only:
                mod._interpret = lambda: False
            pairs = list(defaults)
            if form == "tree":
                pairs += [p for p in args.blocks.split(",") if p not in pairs]
            if args.cpu_rehearsal:
                pairs = pairs[:2]
            for pair in pairs:
                bq, bk = (int(x) for x in pair.split("x"))
                row = dict(cell=cell, shape=[B, H, S, hd], form=form,
                           block_q=bq, block_k=bk)
                step = make_step(mod, scale, min(bq, S), min(bk, S))
                try:
                    if args.compile_only:
                        t0 = time.perf_counter()
                        step.lower(*inputs).compile()
                        row.update(compiled=True,
                                   compile_s=time.perf_counter() - t0)
                    else:
                        row.update(measure(step, inputs, want,
                                           args.iters if on_chip else 1))
                        if on_chip:
                            times, tr = kernel_times(step, inputs,
                                                     args.iters, patterns)
                            row.update(shares(times, tr, (B, H, S, hd), chip,
                                              reader_args)
                                       if times else {"kernels_found": False})
                except Exception as exc:  # noqa: BLE001 — a pair Mosaic refuses
                    row["refused"] = f"{type(exc).__name__}: " \
                        + str(exc).strip().splitlines()[-1][:300]
                if not on_chip:
                    row["platform"] = dev.platform   # times here are no device's
                line = json.dumps(row)
                print(line, flush=True)
                lines.append(line)
    with open(args.out, "w") as f:
        f.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
