"""Traffic kind ``open_loop``: requests sent on a schedule whether or not
earlier ones have finished, each timed from when it was DUE.

Parameters: ``rate_per_s`` (fixed in the file, never searched for),
``arrivals`` ("poisson"), ``block_s``, ``prompt_tokens`` / ``output_tokens``
(length distributions), ``schedule_seed``.  An open loop can have any
number of sequences decoding at once, up to the engine's ``max_seqs``, so
the warm-up covers every decode width the engine compiles.

The schedule is one sample path of the arrival process, drawn from
``schedule_seed`` alone: exponential gaps at ``rate_per_s`` until the window
is full, each arrival with its lengths.  It is cut into blocks of
``block_s`` seconds, and ``--seed`` decides with which block the window
starts: the blocks follow each other in the same cyclic order.  So every
seed offers the same arrivals and sizes, bursts included, in another order,
and the queue each burst meets is the same but at the wrap-around (a free
permutation of the blocks moved ``ttft_p95_ms`` by 8% between seeds, five
times what two runs of one seed differ by: my chip runs, PR 23).  Token ids
and weights come from ``--seed``; ``schedule`` is a pure function of its
arguments.  After the window no request is sent, and the loop runs on until
every request due in the window has finished.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List

import numpy as np

from lib import model as model_lib
from lib import serve_system as ss
from lib.profile import TraceSlice

REHEARSAL = dict(rate_per_s=6.0, block_s=1.0,
                 prompt_tokens={"dist": "lognormal", "median": 24,
                                "sigma": 0.8, "min": 8, "max": 100},
                 output_tokens={"dist": "uniform", "min": 3, "max": 10})


def schedule(job: Dict, seconds: float, seed: int) -> List[tuple]:
    """[(due_s, prompt_len, output_len)] sorted by due time, due in
    [0, seconds)."""
    base = np.random.default_rng(job["schedule_seed"])
    n_max = int(job["rate_per_s"] * seconds * 2) + 16
    gaps = base.exponential(1.0 / job["rate_per_s"], size=n_max)
    due = np.cumsum(gaps)
    due = due[due < seconds]
    n = len(due)
    prompts = ss.lengths(job["prompt_tokens"], n, base)
    outputs = ss.lengths(job["output_tokens"], n, base)
    block = float(job["block_s"])
    n_blocks = max(1, math.ceil(seconds / block - 1e-9))
    edges = [min(i * block, seconds) for i in range(n_blocks)] + [seconds]
    shift = int(np.random.default_rng(seed).integers(n_blocks))
    moved, start = [], 0.0
    for old in np.roll(np.arange(n_blocks), -shift):
        lo, hi = edges[old], edges[old + 1]
        for t, p, o in zip(due, prompts, outputs):
            if lo <= t < hi:
                moved.append((float(t - lo + start), p, o))
        start += hi - lo        # the last block may be a short one
    moved.sort()
    return moved


def decode_widths(system: Dict) -> range:
    """Arrivals do not wait for answers: any width up to max_seqs."""
    return range(1, system["engine"].config.max_seqs + 1)


def measure(ctx, system: Dict, job: Dict, seconds: float) -> Dict:
    """One window of the schedule against a system already built and warm."""
    engine = system["engine"]
    loop = ss.Loop(ctx, system, np.random.default_rng(ctx.seed))
    plan = schedule(job, seconds, ctx.seed)
    traces_before = ss.traces(engine)
    t_start = time.perf_counter()
    deadline = t_start + seconds
    tracer = TraceSlice(ctx.trace, ctx.trace_dir, ctx.spans, t_start, seconds)
    sent = 0
    uid0 = int(t_start * 1e3) % 1_000_000 * 1000    # distinct across windows
    reqs: List[ss.Served] = []
    while sent < len(plan) or loop.busy:
        now = time.perf_counter()
        if now < deadline:
            tracer.maybe_start(now)
        else:
            tracer.end_slice()
        while sent < len(plan) and t_start + plan[sent][0] <= now:
            due, p, o = plan[sent]
            req = ss.Served(uid0 + sent, t_start + due, p, o)
            reqs.append(req)
            loop.submit(req)
            sent += 1
        if loop.busy:
            loop.step()
        elif sent < len(plan):
            with ctx.spans.span("bench/wait_arrival"):
                time.sleep(max(0.0, min(t_start + plan[sent][0]
                                        - time.perf_counter(), 0.002)))
    t_end = time.perf_counter()
    tracer.stop()
    compiles = ss.traces(engine) - traces_before

    finished = [r for r in reqs if r.state == "finished"]
    exact = all(r.counts and r.counts[-1] == r.want for r in finished)
    failed = len(reqs) - len(finished)
    samples = ss.request_metrics(reqs)
    samples["decode_log"] = loop.decode_log
    # a request that failed or was shed counts as the largest value
    worst = max(samples["ttft_ms"], default=0.0)
    samples["ttft_ms"] += [worst] * (len(reqs) - len(samples["ttft_ms"]))
    in_window = [r for r in reqs if r.first_token_t is not None
                 and r.first_token_t < deadline]
    prefill_tokens = sum(r.prompt_len for r in in_window)
    decode_tokens = sum(
        sum(1 for t, c0, c1 in zip(r.times[1:], r.counts, r.counts[1:])
            if t < deadline for _ in range(c1 - c0)) for r in reqs)
    half = t_start + seconds / 2
    early = [s for r, s in zip(reqs, samples["ttft_ms"]) if r.due < half]
    late = [s for r, s in zip(reqs, samples["ttft_ms"]) if r.due >= half]
    return {
        "exact": exact, "attempted": len(reqs), "failed": failed,
        "window": (t_start, min(t_end, deadline)), "trace": tracer.reduced, "slice": tracer.slice,
        "facts": {
            "completed": len(finished), "compiles_in_window": compiles,
            "decode_windows": loop.decode_windows,
            "decode_rows": loop.decode_rows,
            "max_seqs": engine.config.max_seqs,
            "prefill_tokens": prefill_tokens, "decode_tokens": decode_tokens,
            "prompt_tokens_offered": sum(p for _, p, _ in plan),
            "param_bytes": system["param_bytes"],
            "kv_blocks": system["num_blocks"],
            "kv_bytes": system["num_blocks"] * system["block_bytes"],
            "max_waiting": loop.max_waiting,
            **loop.kv_facts(),
            "decode_batch_occupancy": loop.decode_rows / max(
                loop.decode_windows * engine.config.max_seqs, 1),
            "prefill_token_share": prefill_tokens / max(
                prefill_tokens + decode_tokens, 1),
            "drain_s": max(0.0, t_end - deadline),
            "ttft_mean_first_half_ms": float(np.mean(early)) if early else None,
            "ttft_mean_second_half_ms": float(np.mean(late)) if late else None,
        },
        "samples": samples,
    }


def run(ctx) -> Dict:
    job = dict(ctx.traffic)
    if ctx.rehearsal:
        job.update(REHEARSAL)
    sizes = model_lib.sizes_of(ctx.config, ctx.rehearsal)
    system = ss.build(ctx, sizes)
    with ctx.spans.span("bench/setup_check"):
        checks = ss.check_against_reference(ctx, system)
    ss.warm(ctx, system, decode_widths(system))
    ss.instrument(system["engine"], ctx.spans)
    out = measure(ctx, system, job, ctx.seconds)
    peak = (ctx.devices[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
    out["correct"] = bool(checks["ok"] and out.pop("exact")
                          and out["failed"] == 0 and out["attempted"] > 0)
    out["checks"] = checks
    out["memory_peak_bytes"] = int(peak)
    return out
