"""The serving system under test, built as a user builds it
(``InferenceEngineV2`` + ``LifecycleScheduler``, the program's defaults
except what the configuration file's ``serving`` lists), checked against the
plain reference, and warmed for the shapes one traffic mix uses.

The benchmark drives the scheduler in process, from one thread: submit what
is due, ``step()``, repeat.  A token's time is when the scheduler's
``tokens`` event fires, which is when its fused window (or its prefill) has
drained to the host and a streaming client could see it.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

from lib import model as model_lib
from reference.decoder import Reference

REHEARSAL_SERVING = dict(max_tokens=32, max_seqs=4, max_ctx=128,
                         block_size=8)
#: reference prompt: longer than one SplitFuse chunk, so the second chunk
#: attends to the first one's cached pages, and not a multiple of a block
CHECK_PROMPT = 643
CHECK_TAIL = 3          # last tokens fed one at a time, through the cache
CHECK_UID = 2_000_000_000


class Served:
    """One request as the benchmark sees it."""

    __slots__ = ("uid", "due", "submitted", "admitted", "prompt_len",
                 "want", "times", "counts", "state", "client", "preempted",
                 "sreq")

    def __init__(self, uid, due, prompt_len, want, client=None):
        self.uid, self.due, self.prompt_len, self.want = \
            uid, due, prompt_len, want
        self.client = client
        self.sreq = None                 # the scheduler's record of it
        self.submitted: Optional[float] = None
        self.admitted: Optional[float] = None    # left the waiting queue
        self.times: List[float] = []     # when tokens became visible
        self.counts: List[int] = []      # cumulative tokens at those times
        self.state = "new"
        self.preempted = 0

    @property
    def first_token_t(self) -> Optional[float]:
        return self.times[0] if self.times else None

    def tpot_s(self) -> Optional[float]:
        if not self.counts or self.counts[-1] < 2:
            return None
        return (self.times[-1] - self.times[0]) / (self.counts[-1] - 1)


def build(ctx, sizes: Dict) -> Dict:
    """Parameters, reference logits (before the KV pool takes the memory),
    engine and scheduler."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.lifecycle import LifecycleScheduler
    from deepspeed_tpu.models.transformer import CausalLM

    serving = dict(ctx.config["serving"])
    reserve = serving.pop("kv_reserve_bytes")
    if ctx.rehearsal:
        serving.update(REHEARSAL_SERVING)
    dev0 = ctx.devices[0]
    cfg = model_lib.transformer_config(sizes, serving["max_ctx"])
    model = CausalLM(cfg)
    with ctx.spans.span("bench/setup_params"):
        params = model_lib.init_params(model, ctx.seed + 1, jnp.bfloat16)
        jax.block_until_ready(params)
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))

    n_check = min(CHECK_PROMPT, serving["max_ctx"] - 8)
    check_prompt = np.random.default_rng(ctx.seed + 99).integers(
        1, cfg.vocab_size, size=n_check).astype(np.int32)
    with ctx.spans.span("bench/setup_reference"):
        _, kept, _ = Reference(sizes).run(
            [jax.device_put(check_prompt, dev0)],
            model_lib.reference_weights(params, dev0), keep_logits=1,
            last=CHECK_TAIL + 1)
        ref_tail = np.asarray(kept[0], np.float32)
        del kept

    # KV pool: what the parameters leave, less a reserve for the largest
    # step's temporaries and allocator slack (chip_smoke.py's sizing)
    bs = serving["block_size"]
    block_bytes = cfg.num_layers * bs * 2 * cfg.num_kv_heads \
        * cfg.head_dim * 2
    full_pool = serving["max_seqs"] * serving["max_ctx"] // bs
    stats = dev0.memory_stats() or {}
    if ctx.rehearsal or "bytes_limit" not in stats:
        num_blocks = full_pool
    else:
        free = stats["bytes_limit"] - stats["bytes_in_use"]
        num_blocks = int(min(full_pool, (free - reserve) // block_bytes))
    with ctx.spans.span("bench/setup_engine"):
        engine = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
            num_blocks=num_blocks, dtype=jnp.bfloat16, **serving))
        scheduler = LifecycleScheduler(engine, clock=time.perf_counter)
    return {"engine": engine, "scheduler": scheduler, "cfg": cfg,
            "model": model, "param_bytes": int(param_bytes),
            "num_blocks": num_blocks, "block_bytes": block_bytes,
            "check_prompt": check_prompt, "ref_tail": ref_tail,
            "serving": serving}


def check_against_reference(ctx, system: Dict) -> Dict:
    """Chunked prefill, then single tokens through the cache, then
    teacher-forced fused decode windows, each against the reference's
    logits at the same position."""
    import numpy as np

    engine = system["engine"]
    prompt = system["check_prompt"].tolist()
    ref = system["ref_tail"]                    # [CHECK_TAIL + 1, V]
    chunk = engine.config.max_tokens
    body = len(prompt) - CHECK_TAIL
    got = []
    for pos in range(0, body, chunk):
        logits = engine.put([CHECK_UID], [prompt[pos:min(pos + chunk, body)]])
    got.append(np.asarray(logits[0], np.float32))
    for tok in prompt[body:-1]:
        got.append(np.asarray(engine.put([CHECK_UID], [[tok]])[0],
                              np.float32))
    rels = [model_lib.rel_l2(g, r) for g, r in zip(got, ref)]
    finite = all(bool(np.isfinite(g).all()) for g in got)
    # the fused decode window returns tokens, not logits: its greedy token
    # after consuming the last prompt token must be one the reference ranks
    # within ``gap`` of its best logit (a wrong kernel picks an unrelated
    # token, whose logit lies several rms lower)
    tol = ctx.config["tolerances"]
    toks = engine.decode_batch([CHECK_UID], [prompt[-1]], 1)
    tok = int(toks[0, 0])
    gap = float(ref[-1].max() - ref[-1][tok])
    allowed = tol["decode_gap_rms"] * float(np.sqrt(np.mean(ref[-1] ** 2)))
    engine.flush([CHECK_UID])
    return {"logits_rel_l2": max(rels), "logits_rel_l2_each": rels,
            "logits_finite": finite, "decode_token_gap": gap,
            "decode_token_gap_allowed": allowed,
            "ok": finite and max(rels) <= tol["logits_rel_l2"]
            and gap <= allowed}


def warm(ctx, system: Dict, widths) -> None:
    """Run once every program this traffic can reach.  Nothing here is a
    list kept beside the traffic: the prefill token buckets are the
    engine's (``bucket_for`` over 1..max_tokens), the decode widths are the
    engine's sequence buckets of ``widths`` (the numbers of sequences the
    traffic can have decoding at once), and the window lengths are whatever
    the scheduler itself picks when a group of that width decodes
    2 x window_steps - 1 tokens: by its own rule the shortest remaining
    answer walks down through every length it has (8, 4, 2, 1 today)."""
    import numpy as np

    from deepspeed_tpu.inference.v2.lifecycle import ServeRequest

    engine, sched = system["engine"], system["scheduler"]
    c = engine.config
    vocab = system["cfg"].vocab_size
    rng = np.random.default_rng(ctx.seed + 7)
    buckets = sorted({engine.bucket_for(n, 1)[0]
                      for n in range(1, c.max_tokens + 1)})
    for t in buckets:
        with ctx.spans.span("bench/setup_warm_prefill"):
            toks = rng.integers(1, vocab, size=t).tolist()
            np.asarray(engine.put([CHECK_UID], [toks]))
            engine.flush([CHECK_UID])
    # the engine's own rounding of a decode batch's width; one group per
    # distinct bucket, as wide as the bucket
    groups = sorted({engine._seq_bucket(n) for n in widths})
    uid = CHECK_UID
    for n in groups:
        with ctx.spans.span("bench/setup_warm_decode"):
            per_seq = max(1, min(4, c.max_tokens // n))
            for _ in range(n):
                uid += 1
                sched.submit(ServeRequest(
                    uid=uid, max_new_tokens=2 * sched.window_steps,
                    prompt=rng.integers(1, vocab, size=per_seq).tolist()))
            sched.run_until_idle()
            if sched.pending:
                raise RuntimeError(f"warm-up group of {n} did not drain")


def traces(engine) -> int:
    return sum(engine.trace_counts.values())


def instrument(engine, spans) -> None:
    """The benchmark's spans around the calls into the engine, put on this
    engine object from outside: ``bench/engine_put`` (dispatch of a prefill
    forward), ``bench/decode_dispatch`` and ``bench/window_drain`` (the wait
    for a fused window's tokens)."""
    put, dispatch = engine.put, engine.decode_batch_async

    def traced_put(uids, tokens_list):
        with spans.span("bench/engine_put"):
            return put(uids, tokens_list)

    def traced_dispatch(*args, **kw):
        with spans.span("bench/decode_dispatch"):
            window = dispatch(*args, **kw)
        drain = window.tokens

        def traced_drain():
            with spans.span("bench/window_drain"):
                return drain()

        window.tokens = traced_drain
        return window

    engine.put = traced_put
    engine.decode_batch_async = traced_dispatch


class Loop:
    """Submit, step, record.  One thread."""

    def __init__(self, ctx, system: Dict, token_rng):
        from deepspeed_tpu.inference.v2 import lifecycle

        self.ServeRequest = lifecycle.ServeRequest
        self.State = lifecycle.RequestState
        self.ctx = ctx
        self.engine = system["engine"]
        self.sched = system["scheduler"]
        self.vocab = system["cfg"].vocab_size
        self.rng = token_rng
        self.live: Dict[int, Served] = {}
        self.done: List[Served] = []
        self.decode_windows = 0
        self.decode_rows = 0
        self.max_waiting = 0
        #: (host time, sequences, their summed context) per fused window
        self.decode_log: List[tuple] = []
        #: share of the KV pool's blocks allocated, after each step
        self.kv_used: List[float] = []

    def submit(self, req: Served) -> bool:
        prompt = self.rng.integers(1, self.vocab, size=req.prompt_len).tolist()
        req.submitted = time.perf_counter()
        self.live[req.uid] = req
        req.sreq = self.ServeRequest(uid=req.uid, prompt=prompt,
                                     max_new_tokens=req.want,
                                     on_event=self._on_event)
        verdict = self.sched.submit(req.sreq)
        if not verdict.admitted:
            req.state = "shed"
            self.done.append(self.live.pop(req.uid))
        return verdict.admitted

    def _on_event(self, event: str, sreq) -> None:
        req = self.live.get(sreq.uid)
        if req is None:
            return
        if event == "tokens":
            req.times.append(time.perf_counter())
            req.counts.append(len(sreq.produced))
        elif event == "preempted":      # re-queued, not over
            req.preempted += 1
        else:
            req.state = event
            self.done.append(self.live.pop(sreq.uid))

    def step(self) -> None:
        """One scheduler iteration.  What the scheduler holds is read from
        the requests' own public ``state`` (queued, prefill, decode), and
        the pool's filling from ``engine.kv_used_fraction()``."""
        states = [r.sreq.state for r in self.live.values()]
        decoding = states.count(self.State.DECODE)
        queued = states.count(self.State.QUEUED)
        self.max_waiting = max(self.max_waiting, queued)
        # prefill runs first whenever any is pending (lifecycle.step)
        kind = "prefill" if len(states) > decoding else "decode"
        windows = self.engine.decode_windows_dispatched
        t0 = time.perf_counter()
        with self.ctx.spans.span("bench/step_" + kind):
            self.sched.step()
        self.kv_used.append(self.engine.kv_used_fraction())
        if self.engine.decode_windows_dispatched > windows:
            self.decode_windows += 1
            self.decode_rows += decoding
            self.decode_log.append((t0, decoding, sum(
                r.prompt_len + r.counts[-1] for r in self.live.values()
                if r.counts)))
        for req in self.live.values():
            if req.admitted is None and \
                    req.sreq.state is not self.State.QUEUED:
                req.admitted = t0   # admitted by the step that began at t0

    def kv_facts(self) -> Dict:
        """Share of the KV pool's blocks allocated after each scheduler
        step: the most, and the mean over steps."""
        used = self.kv_used or [0.0]
        return {"kv_fill_peak": max(used),
                "kv_fill_mean": sum(used) / len(used)}

    @property
    def busy(self) -> bool:
        return self.sched.pending > 0


def lengths(spec: Dict, n: int, rng) -> List[int]:
    """``n`` lengths from a traffic file's distribution, as evenly spread
    quantiles (so every draw of ``n`` is the same multiset) in an order
    ``rng`` shuffles."""
    import numpy as np

    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "loguniform":
        vals = np.exp(np.log(spec["min"]) + u * (np.log(spec["max"])
                                                 - np.log(spec["min"])))
    elif kind == "uniform":
        vals = spec["min"] + u * (spec["max"] - spec["min"])
    elif kind == "lognormal":
        from statistics import NormalDist

        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        vals = np.clip(spec["median"] * np.exp(spec["sigma"] * z),
                       spec["min"], spec["max"])
    elif kind == "choice":
        values = spec["values"]
        vals = np.array([values[i * len(values) // n] for i in range(n)])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    out = np.rint(vals).astype(int)
    rng.shuffle(out)
    return out.tolist()


def request_metrics(reqs: List[Served]) -> Dict[str, List[float]]:
    """Per-request samples, in milliseconds."""
    ttft, tpot, lag, wait = [], [], [], []
    for r in reqs:
        if r.submitted is not None:
            lag.append((r.submitted - r.due) * 1e3)
            if r.admitted is not None:
                wait.append(max(r.admitted - r.submitted, 0.0) * 1e3)
        if r.first_token_t is not None:
            ttft.append((r.first_token_t - r.due) * 1e3)
        per_token = r.tpot_s() if r.state == "finished" else None
        if per_token is not None:
            tpot.append(per_token * 1e3)
    return {"ttft_ms": ttft, "tpot_ms": tpot, "gen_lag_ms": lag,
            "queue_wait_ms": wait}
