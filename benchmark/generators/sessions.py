"""Traffic kind ``sessions``: ``sessions`` callers, each holding one long
document and asking about it again and again (no think time).

Parameters: ``sessions``, ``document_tokens``, ``question_tokens`` and
``answer_tokens`` (length distributions, see ``lib/serve_system.lengths``),
``schedule_seed``.  A session owns one document, made from ``--seed``; its
length is fixed by ``schedule_seed`` alone.  A turn is ``document + a fresh
question``, answered with a drawn number of tokens.  Turn ``r`` of the
sessions is one round: every round holds the same multiset of (question,
answer) lengths (evenly spread quantiles, paired by ``schedule_seed``), and
``--seed`` only changes which session gets which pair and the token ids.  So
every seed offers the same work in another order.

Set-up: every session sends its first turn and the scheduler is stepped
until all of them are decoding.  Prefill has priority over decode, so no
decode window has run when the last document is in: the window opens on full
caches, every session at its first answer token.  In the window a session
whose answer completes sends its next turn at once.  With a prefix cache the
document is grafted from the trie and only the question is prefilled;
without one the document is prefilled again.

Tokens drained inside the window count for ``output_tokens``.  A turn's time
per output token is over the turns completed in the window; for a first
turn the clock starts when the window opens (its first token came out of
set-up, and nothing decoded until then).

The served system is built by the module the configuration file names under
``system`` (``build(ctx)``, ``check_against_reference(ctx, system)`` and,
where it has one, ``check_served(ctx, system, turns, job)``, which holds a
sample of the turns the window finished to the reference after the window);
a configuration that names none is built by ``lib/serve_system``
(``CausalLM``, ``reference/decoder.py``).  This file knows no model.
``Loop``, ``warm``, ``lengths`` and ``request_metrics`` are ``lib/
serve_system``'s, unchanged.
"""
from __future__ import annotations

import importlib
import time
from typing import Dict, List

import numpy as np

from lib import model as model_lib
from lib import serve_system as ss
from lib.profile import TraceSlice

REHEARSAL = dict(sessions=4,
                 document_tokens={"dist": "loguniform", "min": 48, "max": 96},
                 question_tokens={"dist": "uniform", "min": 4, "max": 12},
                 answer_tokens={"dist": "uniform", "min": 10, "max": 24})


def document_lengths(job: Dict) -> List[int]:
    return ss.lengths(job["document_tokens"], job["sessions"],
                      np.random.default_rng([job["schedule_seed"], 0]))


def round_of(job: Dict, index: int, seed: int):
    """(question_len, answer_len) of every session's turn ``index``."""
    n = job["sessions"]
    pairing = np.random.default_rng([job["schedule_seed"], 1 + index])
    questions = ss.lengths(job["question_tokens"], n, pairing)
    answers = ss.lengths(job["answer_tokens"], n, pairing)
    order = np.random.default_rng([seed, index]).permutation(n)
    return [(questions[i], answers[i]) for i in order]


class SessionLoop(ss.Loop):
    """``Loop`` whose requests bring their own prompt."""

    def submit(self, req: ss.Served, prompt: List[int]) -> bool:
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


def build(ctx) -> Dict:
    name = ctx.config.get("system")
    if name is None:
        system = ss.build(ctx, model_lib.sizes_of(ctx.config, ctx.rehearsal))
        system["check"] = ss.check_against_reference
        return system
    module = importlib.import_module(name)
    system = module.build(ctx)
    system["check"] = module.check_against_reference
    system["check_served"] = getattr(module, "check_served", None)
    return system


def run(ctx) -> Dict:
    job = dict(ctx.traffic)
    if ctx.rehearsal:
        job.update(REHEARSAL)
    system = build(ctx)
    engine = system["engine"]
    with ctx.spans.span("bench/setup_check"):
        checks = system["check"](ctx, system)
    n = job["sessions"]
    ss.warm(ctx, system, [min(n, engine.config.max_seqs)])
    ss.instrument(engine, ctx.spans)
    vocab = system["cfg"].vocab_size
    loop = SessionLoop(ctx, system, None)

    doc_rng = np.random.default_rng([ctx.seed, 7])
    documents = [doc_rng.integers(1, vocab, size=size).tolist()
                 for size in document_lengths(job)]
    question_rng = np.random.default_rng([ctx.seed, 8])
    rounds: Dict[int, list] = {}
    turn_of = [0] * n               # the next turn of each session
    turns: List[ss.Served] = []     # every turn sent, in order

    def next_turn(now: float, session: int) -> None:
        r = turn_of[session]
        if r not in rounds:
            rounds[r] = round_of(job, r, ctx.seed)
        q_len, a_len = rounds[r][session]
        turn_of[session] = r + 1
        prompt = documents[session] + question_rng.integers(
            1, vocab, size=q_len).tolist()
        req = ss.Served(len(turns), now, len(prompt), a_len, client=session)
        turns.append(req)
        loop.submit(req, prompt)

    # ---- set-up: every document in, every session at its first token -------
    with ctx.spans.span("bench/setup_sessions"):
        now = time.perf_counter()
        for session in range(n):
            next_turn(now, session)
        decode = loop.State.DECODE
        windows_before = engine.decode_windows_dispatched
        while any(r.sreq.state is not decode for r in loop.live.values()):
            loop.step()
        if engine.decode_windows_dispatched != windows_before:
            raise RuntimeError("a decode window ran during set-up")
        if len(loop.live) != n or loop.done:
            raise RuntimeError(
                f"set-up left {len(loop.live)} of {n} sessions decoding "
                f"({[r.state for r in loop.done]})")
    first_turns = list(loop.live.values())
    setup_kv = loop.kv_used[-1] if loop.kv_used else 0.0
    loop.kv_used.clear()            # the window's own filling from here
    loop.max_waiting = 0

    traces_before = ss.traces(engine)
    t_start = time.perf_counter()
    deadline = t_start + ctx.seconds
    for req in first_turns:
        # its first token came out of set-up; nothing has decoded since
        req.times, req.counts = [t_start], [1]
    tracer = TraceSlice(ctx.trace, ctx.trace_dir, ctx.spans, t_start,
                        ctx.seconds)
    handled = 0
    while True:
        now = time.perf_counter()
        if now >= deadline:
            break
        tracer.maybe_start(now)
        loop.step()
        now = time.perf_counter()
        for req in loop.done[handled:]:         # a session got its answer
            next_turn(now, req.client)
        handled = len(loop.done)
    t_end = time.perf_counter()
    tracer.stop()
    compiles = ss.traces(engine) - traces_before

    finished = [r for r in loop.done if r.state == "finished"]
    exact = all(r.counts and r.counts[-1] == r.want for r in finished)
    failed = len(loop.done) - len(finished)
    seen = [r for r in turns if r.counts]
    before_window = {id(r) for r in first_turns}
    tokens_out = sum(r.counts[-1] - (1 if id(r) in before_window else 0)
                     for r in seen)
    asked = [r for r in turns if id(r) not in before_window
             and r.admitted is not None]
    prompt_tokens = sum(r.prompt_len for r in asked)
    grafted = sum(r.sreq.prefix_hit_tokens for r in asked)
    samples = ss.request_metrics(loop.done)
    samples["decode_log"] = loop.decode_log
    peak = (ctx.devices[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
    if system.get("check_served"):
        # what the window itself served, held to the reference (after the
        # peak is read: the reference's memory is not the served system's)
        t_check = time.perf_counter()
        with ctx.spans.span("bench/served_check"):
            try:
                served = system["check_served"](ctx, system, [
                    {"session": r.client,
                     "document": len(documents[r.client]),
                     "prompt": r.sreq.prompt, "produced": r.sreq.produced,
                     "grafted": r.sreq.prefix_hit_tokens}
                    for r in finished if id(r) not in before_window], job)
            except Exception as exc:    # the line still reports the window
                served = {"ok": False, "error": repr(exc)[-400:]}
        served["seconds"] = time.perf_counter() - t_check
        checks = dict(checks, served=served,
                      ok=bool(checks["ok"] and served["ok"]))
    return {
        "correct": bool(checks["ok"] and exact and failed == 0
                        and len(finished) > 0),
        "checks": checks, "attempted": len(loop.done), "failed": failed,
        # the engine's programs give their text (for the readers of name
        # scopes) only while the engine lives: hold it until they have read
        "system": system,
        "window": (t_start, t_end), "trace": tracer.reduced,
        "slice": tracer.slice, "memory_peak_bytes": int(peak),
        "facts": {
            "output_tokens": tokens_out, "completed": len(finished),
            "turns_asked": len(asked),
            "compiles_in_window": compiles,
            "decode_windows": loop.decode_windows,
            "decode_rows": loop.decode_rows,
            "max_seqs": engine.config.max_seqs,
            "prefill_tokens": prompt_tokens - grafted,
            "prefix_hit_token_share": grafted / max(prompt_tokens, 1),
            "preemptions": sum(r.preempted for r in turns),
            "param_bytes": system["param_bytes"],
            "kv_blocks": system["num_blocks"],
            "kv_bytes": system["num_blocks"] * system["block_bytes"],
            "kv_fill_setup": setup_kv,
            "max_waiting": loop.max_waiting,
            **loop.kv_facts(),
            "decode_batch_occupancy": loop.decode_rows / max(
                loop.decode_windows * engine.config.max_seqs, 1),
        },
        "samples": samples,
    }

