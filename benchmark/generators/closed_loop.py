"""Traffic kind ``closed_loop``: ``clients`` callers, each sending its next
request when the last one returns (no think time).

Parameters: ``clients``, ``prompt_tokens`` and ``output_tokens`` (length
distributions, see ``lib/serve_system.lengths``), ``schedule_seed``.  With
no think time there are always ``clients`` requests in flight, and prefill
runs before decode, so a fused window is ``clients`` wide (or ``max_seqs``,
if that is less): the one decode width the warm-up covers.  The request list is made in rounds of ``clients``
requests: every round holds the same multiset of lengths (evenly spread
quantiles, paired by ``schedule_seed``), and ``--seed`` only changes the
order inside a round and the token ids.  So every seed offers the same work
in another order.
"""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

from lib import model as model_lib
from lib import serve_system as ss
from lib.profile import TraceSlice

REHEARSAL = dict(clients=4,
                 prompt_tokens={"dist": "loguniform", "min": 8, "max": 24},
                 output_tokens={"dist": "uniform", "min": 12, "max": 40})


def round_of(job: Dict, index: int, seed: int):
    """(prompt_len, output_len) pairs of round ``index``."""
    n = job["clients"]
    pairing = np.random.default_rng([job["schedule_seed"], index])
    prompts = ss.lengths(job["prompt_tokens"], n, pairing)
    outputs = ss.lengths(job["output_tokens"], n, pairing)
    order = np.random.default_rng([seed, index]).permutation(n)
    return [(prompts[i], outputs[i]) for i in order]


def run(ctx) -> Dict:
    job = dict(ctx.traffic)
    if ctx.rehearsal:
        job.update(REHEARSAL)
    sizes = model_lib.sizes_of(ctx.config, ctx.rehearsal)
    system = ss.build(ctx, sizes)
    engine = system["engine"]
    with ctx.spans.span("bench/setup_check"):
        checks = ss.check_against_reference(ctx, system)
    ss.warm(ctx, system, [min(job["clients"], engine.config.max_seqs)])
    ss.instrument(engine, ctx.spans)
    loop = ss.Loop(ctx, system, np.random.default_rng(ctx.seed))

    pending = []                    # requests not yet sent, next first
    rounds = 0

    def next_request(now: float, client: int) -> ss.Served:
        nonlocal rounds
        if not pending:
            base = rounds * job["clients"]
            pending.extend(
                ss.Served(base + i, now, p, o)
                for i, (p, o) in enumerate(round_of(job, rounds, ctx.seed)))
            rounds += 1
        req = pending.pop(0)
        req.due, req.client = now, client
        return req

    traces_before = ss.traces(engine)
    t_start = time.perf_counter()
    deadline = t_start + ctx.seconds
    tracer = TraceSlice(ctx.trace, ctx.trace_dir, ctx.spans, t_start,
                        ctx.seconds)
    for client in range(job["clients"]):
        loop.submit(next_request(t_start, client))
    handled = 0
    while True:
        now = time.perf_counter()
        if now >= deadline:
            break
        tracer.maybe_start(now)
        loop.step()
        now = time.perf_counter()
        for req in loop.done[handled:]:         # a caller got its answer
            loop.submit(next_request(now, req.client))
        handled = len(loop.done)
    t_end = time.perf_counter()
    tracer.stop()
    compiles = ss.traces(engine) - traces_before

    finished = [r for r in loop.done if r.state == "finished"]
    exact = all(r.counts and r.counts[-1] == r.want for r in finished)
    failed = len(loop.done) - len(finished)
    in_flight = list(loop.live.values())
    tokens_out = sum(r.counts[-1] for r in loop.done + in_flight if r.counts)
    decode_tokens = sum(max(r.counts[-1] - 1, 0)
                        for r in loop.done + in_flight if r.counts)
    prefill_tokens = sum(r.prompt_len for r in loop.done + in_flight
                         if r.counts)
    samples = ss.request_metrics(loop.done)
    samples["decode_log"] = loop.decode_log
    peak = (ctx.devices[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
    return {
        "correct": bool(checks["ok"] and exact and failed == 0
                        and len(finished) > 0),
        "checks": checks, "attempted": len(loop.done), "failed": failed,
        "window": (t_start, t_end), "trace": tracer.reduced, "slice": tracer.slice,
        "memory_peak_bytes": int(peak),
        "facts": {
            "output_tokens": tokens_out, "completed": len(finished),
            "compiles_in_window": compiles,
            "decode_windows": loop.decode_windows,
            "decode_rows": loop.decode_rows,
            "max_seqs": engine.config.max_seqs,
            "prefill_tokens": prefill_tokens, "decode_tokens": decode_tokens,
            "param_bytes": system["param_bytes"],
            "kv_blocks": system["num_blocks"],
            "kv_bytes": system["num_blocks"] * system["block_bytes"],
            "max_waiting": loop.max_waiting,
            **loop.kv_facts(),
            "decode_batch_occupancy": loop.decode_rows / max(
                loop.decode_windows * engine.config.max_seqs, 1),
            "prefill_token_share": prefill_tokens / max(
                prefill_tokens + decode_tokens, 1),
        },
        "samples": samples,
    }
