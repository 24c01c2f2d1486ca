"""The LongCat-Flash serving system under test, built as a user builds it
(``LongCatFlashLM.from_hf_config`` on the configuration file's published keys
and the share's own, ``InferenceEngineV2`` + ``LifecycleScheduler`` with the
prefix cache on), and checked against ``reference/longcat_flash.py`` on what
the timed path produces: before the window the code paths one sequence at a
time (``check_against_reference``), after it a sample of the turns the window
itself served (``check_served``).  The configuration file names this module
under ``system``; ``generators/sessions.py`` imports it by that name.

The dictionary ``build`` returns has the keys ``lib/serve_system``'s ``warm``
and ``Loop`` read (``engine``, ``scheduler``, ``cfg``), so those are used
unchanged.
"""
from __future__ import annotations

import time
from typing import Dict, List

from lib import model as model_lib
from lib.xing4_system import _gaps, _group, balance_bias, check_plan
from reference.longcat_flash import Reference

REHEARSAL_SERVING = dict(max_tokens=32, max_seqs=4, max_ctx=256,
                         block_size=8, max_queue=4)
#: --cpu-rehearsal: toy widths through the same control flow
TOY = dict(vocab_size=512, hidden_size=64, ffn_hidden_size=128,
           expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
           q_lora_rank=16, kv_lora_rank=32, qk_nope_head_dim=16,
           qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=4,
           zero_expert_num=8, moe_topk=3, ep_size=4, ep_rank=0)

#: the check's seeded turn: a conversation of several 512-token chunks that
#: is no multiple of 64 (a page) nor of a chunk; a message after it
CHECK_DOC = 2387
CHECK_QUESTION = 40
MIXED = (150, 85)       # two more sequences, prefilled TOGETHER in one batch
TAIL = 3                # tokens each of the two is then fed, singly
CHECK_UID = 2_000_000_000
#: the served sample: quantiles of the finished turns ranked by length
SERVED_PICKS = (0.0, 0.5, 1.0)
#: formats below bfloat16 whose reading ``check_served`` adds to its own
#: (``tools/longcat_readings.py`` fills it; a benchmark run leaves it empty)
CONTROLS: Dict = {}
#: spread of the router outputs' popularity: the router's column of output e
#: (a real expert or an identity one) is scaled by a seeded factor in this
#: range, as a trained router's columns differ, and the selection bias is
#: what evens the loads again
POPULARITY = (0.6, 1.4)
#: the balancing rule's steps were sized for sigmoid scores (spread ~0.1); a
#: softmax score over 768 outputs is ~1/768, so the rule runs on the scores
#: times ``BALANCE_SPREAD / std(scores)`` and its bias is divided back (the
#: top k of ``c·s + c·b`` are those of ``s + b``)
BALANCE_SPREAD = 0.1

_BLOCK = {
    "in_norm": ("in_norm", "scale"), "w_dq": ("q_a_proj", "kernel"),
    "q_norm": ("q_a_norm", "scale"), "w_uq": ("q_b_proj", "kernel"),
    "w_dkv": ("kv_a_proj", "kernel"), "kv_norm": ("kv_a_norm", "scale"),
    "w_ukv": ("kv_b_proj", "kernel"), "w_o": ("o_proj", "kernel"),
    "post_norm": ("post_norm", "scale"), "w_gate": ("gate_proj", "kernel"),
    "w_up": ("up_proj", "kernel"), "w_down": ("down_proj", "kernel")}
_EXPERTS = {"e_gate": "gate", "e_up": "up", "e_down": "down"}


def published(config: Dict, rehearsal: bool) -> Dict:
    """The configuration file's ``config.json`` keys as run."""
    hf = {k: v for k, v in config.items()
          if isinstance(v, (int, float, bool, str))}
    if rehearsal:
        hf.update(TOY)
    return hf


def reference_weights(params, rounded_to=None) -> Dict:
    """The program's parameter tree as what ``reference.longcat_flash``
    takes, in the dtypes the program computes in (the reference casts at
    each use), a PIECE of a layer at a time (block 0, the experts, block 1:
    a whole layer is 2.5 GB and the reference runs beside 10 GB of weights).
    ``rounded_to`` names a format below bfloat16 that every bfloat16 matrix
    is rounded to first, the second reading of a tolerance: (exponent bits,
    mantissa bits) of a float format, or ``"int8"`` (symmetric, 127 steps to
    the largest value of each output channel).  ``reduce_precision`` and not
    a pair of casts: the TPU's compiler drops a cast down and back up
    (PR 28)."""
    import jax
    import jax.numpy as jnp

    def as_run(x):
        if rounded_to is None or x.dtype != jnp.bfloat16 or x.ndim < 2:
            return x
        if rounded_to == "int8":
            w = x.astype(jnp.float32)
            step = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
            return (jnp.round(w / step) * step).astype(jnp.bfloat16)
        return jax.lax.reduce_precision(x, *rounded_to)

    def at(x, *idx):
        for i in idx:
            x = jax.lax.dynamic_index_in_dim(x, i, keepdims=False)
        return as_run(x)

    blocks, router = params["layers"]["blocks"], params["layers"]["router"]
    block = jax.jit(lambda one, l: {
        k: at(one[a][b], l) for k, (a, b) in _BLOCK.items()})
    moe = jax.jit(lambda router, experts, l: dict(
        {k: at(experts[name], l) for k, name in _EXPERTS.items()},
        router=at(router["kernel"], l), router_bias=at(router["bias"], l)))
    outer = {"embedding": params["embed"]["embedding"],
             "norm": params["norm_f"]["scale"],
             "head": params["lm_head"]["kernel"]}
    if rounded_to is not None:
        outer = jax.jit(lambda t: jax.tree.map(as_run, t))(outer)
    return dict(outer, layers=[
        {"blocks": [lambda l=l, i=i: block(blocks[i], l) for i in (0, 1)],
         "moe": lambda l=l: moe(router, params["experts"], l)}
        for l in range(router["kernel"].shape[0])])


def balanced_router(params, ref: Reference, seed: int, n_tokens: int):
    """The seeded parameters with a router as training leaves one: outputs
    of unlike popularity (each output's column of the router scaled by a
    seeded factor in ``POPULARITY``) and a selection bias that evens the
    loads of ALL the router's outputs, balanced layer by layer on one seeded
    calibration sequence of ``n_tokens`` through the reference.  Even loads
    over 512 real and 256 identity outputs are the published operating
    point: a token takes 12 x 512 / 768 = 8 real experts on average (single
    tokens range over several), a third of the pairs are identity pairs, and
    the experts held here are loaded alike."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    layers = params["layers"]
    router = layers["router"]
    L, _, R = router["kernel"].shape
    lo, hi = POPULARITY
    popularity = jax.random.uniform(
        jax.random.PRNGKey((seed + 7) % (2 ** 31)), (L, 1, R), jnp.float32,
        lo, hi)
    router = dict(kernel=router["kernel"] * popularity,
                  bias=jnp.zeros((L, R), jnp.float32))
    params = dict(params, layers=dict(layers, router=router))
    c = ref.config
    row = np.random.default_rng(seed + 98).integers(
        1, c["vocab_size"], size=n_tokens).astype(np.int32)

    @jax.jit
    def balance(scores, bias):
        unit = BALANCE_SPREAD / jnp.std(scores)
        return balance_bias(scores * unit, bias * unit, c["moe_topk"]) / unit

    biases = ref.balanced_router_biases(row, reference_weights(params),
                                        balance)
    router = dict(router, bias=jnp.stack(biases))
    return dict(params, layers=dict(layers, router=router))


def prepare(ctx) -> Dict:
    """Model, parameters, the check's sequences and the reference's logits
    for them — what is made before the page pool takes the memory."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.longcat_flash import LongCatFlashLM

    serving = dict(ctx.config["serving"])
    if ctx.rehearsal:
        serving.update(REHEARSAL_SERVING)
    dev0 = ctx.devices[0]
    hf = published(ctx.config, ctx.rehearsal)
    model = LongCatFlashLM.from_hf_config(hf, max_seq_len=serving["max_ctx"])
    cfg = model.config
    ref_model = Reference(hf)
    # the check's two turns of one session: the same history, two messages
    n_doc = min(CHECK_DOC, serving["max_ctx"] * 5 // 8)
    n_q = min(CHECK_QUESTION, serving["max_ctx"] // 8)
    with ctx.spans.span("bench/setup_params"):
        params = model_lib.init_params(model, ctx.seed + 1, jnp.bfloat16)
        jax.block_until_ready(params)
    with ctx.spans.span("bench/setup_router"):
        # as long as a turn of the check, so that the reference's programs
        # compile once for both
        params = balanced_router(params, ref_model, ctx.seed, n_doc + n_q)
        jax.block_until_ready(params)
    rng = np.random.default_rng(ctx.seed + 99)
    draw = lambda n: rng.integers(  # noqa: E731
        1, cfg.vocab_size, size=n).astype(np.int32)
    doc = draw(n_doc)
    turns = [np.concatenate([doc, draw(n_q)]) for _ in range(2)]
    scale = 8 if ctx.rehearsal else 1
    mixed = [draw(max(n // scale, 3) + TAIL) for n in MIXED]
    plan = check_plan(len(turns[0]), n_doc, serving["max_tokens"])
    positions = plan["positions"] + [
        list(range(len(r) - 1 - TAIL, len(r))) for r in mixed]

    def reference(rounded_to=None):
        out = ref_model.logits(
            [jax.device_put(t, dev0) for t in turns + mixed],
            reference_weights(params, rounded_to), positions=positions)
        return [np.asarray(r, np.float32) for r in out]

    with ctx.spans.span("bench/setup_reference"):
        ref = reference()
    return {"cfg": cfg, "model": model, "params": params,
            "param_bytes": int(sum(x.nbytes
                                   for x in jax.tree.leaves(params))),
            "check_turns": turns, "check_mixed": mixed, "check_doc": n_doc,
            "check_plan": plan, "ref": ref, "reference": reference,
            "ref_model": ref_model, "serving": serving}


def build(ctx, system: Dict = None) -> Dict:
    """``prepare``, then the engine (its pool: what the parameters leave,
    less the reserve; a block is ``page_layers`` = 2 x ``num_layers`` latent
    pages) and the scheduler."""
    import math

    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.lifecycle import LifecycleScheduler

    system = system or prepare(ctx)
    serving = dict(system["serving"])
    reserve = serving.pop("kv_reserve_bytes")
    max_queue = serving.pop("max_queue")
    dev0 = ctx.devices[0]
    bs = serving["block_size"]
    family = system["model"].serving_family()
    block_bytes = family.page_layers * bs * math.prod(
        family.row.token_shape) * 2
    full_pool = serving["max_seqs"] * -(-serving["max_ctx"] // bs)
    stats = dev0.memory_stats() or {}
    if ctx.rehearsal or "bytes_limit" not in stats:
        num_blocks = full_pool
    else:
        free = stats["bytes_limit"] - stats["bytes_in_use"]
        num_blocks = int(min(full_pool, (free - reserve) // block_bytes))
    with ctx.spans.span("bench/setup_engine"):
        engine = InferenceEngineV2(
            system["model"], system.pop("params"),
            RaggedInferenceEngineConfig(num_blocks=num_blocks,
                                        dtype=jnp.bfloat16, **serving))
        # sessions.py submits every session's first turn at once: the queue
        # has to hold them all (the configuration's serving_why)
        scheduler = LifecycleScheduler(engine, max_queue=max_queue,
                                       clock=time.perf_counter)
    system.pop("reference")
    system.update(engine=engine, scheduler=scheduler, num_blocks=num_blocks,
                  block_bytes=block_bytes, serving=serving)
    return system


def check_against_reference(ctx, system: Dict) -> Dict:
    """What the timed path's programs produce against the reference's logits
    at the same positions, one sequence at a time, in five groups (= code
    paths): chunked prefill (every chunk's last position; the history is
    several chunks and no multiple of a page), a batch of chunks of two
    sequences together and then their next tokens, both in one batch, single
    tokens through both latent page layers of every layer, one-step fused
    decode windows (the greedy token's reference logit against the
    reference's best), and a re-asked turn whose history is grafted from the
    trie, with the tokens after it.

    A routed model has two modes of error: a position whose router picked
    the reference's outputs in every layer, and one where a near-tie of the
    top 12 of 768 fell the other way.  The limits are held by the bulk: the
    lower quartile over all logit positions, and in every group at least
    ``group_within_share`` of its positions; the share over the limit is
    reported (``routing_flip_share``), not bounded."""
    import numpy as np

    engine = system["engine"]
    tol = ctx.config["tolerances"]
    plan = system["check_plan"]
    cold, warm_turn = (t.tolist() for t in system["check_turns"])
    mixed_a, mixed_b = (r.tolist() for r in system["check_mixed"])
    ref_cold, ref_warm, ref_a, ref_b = system["ref"]
    chunk = engine.config.max_tokens
    body = plan["body"]
    rel = model_lib.rel_l2

    # ---- turn 0, cold: chunks, two more sequences, singles, windows ---------
    got = []
    for pos in range(0, body, chunk):
        logits = engine.put([CHECK_UID], [cold[pos:min(pos + chunk, body)]])
        got.append(np.asarray(logits[0], np.float32))
    pair = [CHECK_UID + 2, CHECK_UID + 3]
    both = [np.asarray(engine.put(pair, [mixed_a[:-TAIL], mixed_b[:-TAIL]]),
                       np.float32)]
    for i in range(TAIL, 0, -1):     # and their next tokens, both in a batch
        both.append(np.asarray(engine.put(pair, [[mixed_a[-i]],
                                                 [mixed_b[-i]]]), np.float32))
    both = np.stack(both)                                   # [1+TAIL, 2, V]
    engine.flush(pair)
    for tok in cold[body:body + plan["singles"]]:
        got.append(np.asarray(engine.put([CHECK_UID], [[tok]])[0],
                              np.float32))
    n_logits = plan["chunk_ends"] + plan["singles"]
    rels = [rel(g, r) for g, r in zip(got, ref_cold[:n_logits])]
    rels_mixed = [rel(both[i, 0], ref_a[i]) for i in range(1 + TAIL)] \
        + [rel(both[i, 1], ref_b[i]) for i in range(1 + TAIL)]
    finite = all(bool(np.isfinite(g).all()) for g in got) \
        and bool(np.isfinite(both).all())
    # the fused window returns tokens, not logits: the reference's logit of
    # the greedy token may lie below the reference's best by at most
    # decode_gap_rms x rms(reference logits)
    gaps = []
    for i, tok in enumerate(cold[body + plan["singles"]:]):
        out = int(engine.decode_batch([CHECK_UID], [tok], 1)[0, 0])
        row = ref_cold[n_logits + i]
        gaps.append(float(row.max() - row[out])
                    / float(np.sqrt(np.mean(row ** 2))))
    # ---- commit, flush, re-ask with the history grafted ----------------------
    seen = engine.state_manager.get_sequence(CHECK_UID).seen_tokens
    engine.commit_prefix(CHECK_UID, cold[:seen], allow_partial=True)
    engine.flush([CHECK_UID])
    grafted = engine.graft_prefix(CHECK_UID + 1, warm_turn)
    got1 = [np.asarray(engine.put(
        [CHECK_UID + 1], [warm_turn[grafted:plan["body1"]]])[0], np.float32)]
    for tok in warm_turn[plan["body1"]:]:
        got1.append(np.asarray(engine.put([CHECK_UID + 1], [[tok]])[0],
                               np.float32))
    rels1 = [rel(g, r) for g, r in zip(got1, ref_warm)]
    finite = finite and all(bool(np.isfinite(g).all()) for g in got1)
    engine.flush([CHECK_UID + 1])

    limit = tol["logits_rel_l2"]
    groups = {
        "prefill": _group(rels[:plan["chunk_ends"]], limit),
        "mixed": _group(rels_mixed, limit),
        "singles": _group(rels[plan["chunk_ends"]:], limit),
        "windows": _group(gaps, tol["decode_gap_rms"]),
        "grafted": _group(rels1, limit)}
    flips = sum(g["over"] for g in groups.values())
    positions = sum(g["n"] for g in groups.values())
    every = rels + rels_mixed + rels1
    quartile = float(np.percentile(every, 25))
    # the graft must have covered the history (less its last, partial block)
    graft_ok = grafted >= system["check_doc"] - engine.config.block_size
    ok = (finite and graft_ok and quartile <= limit
          and all(g["n"] - g["over"] >= tol["group_within_share"] * g["n"]
                  for g in groups.values()))
    return {"groups": groups, "logits_finite": finite, "grafted": grafted,
            "graft_ok": graft_ok, "positions": positions,
            "routing_flip_share": flips / positions,
            "logits_rel_l2": quartile,
            "logits_rel_l2_median": float(np.median(every)),
            "ok": bool(ok)}


def check_served(ctx, system: Dict, turns: List[Dict], job: Dict) -> Dict:
    """A sample of the turns the WINDOW served against the reference.

    ``turns``: the re-asked turns the window finished, in the order they
    finished, each ``session``, ``document`` (its length), ``prompt``,
    ``produced`` (the served tokens) and ``grafted`` (prompt tokens taken
    from the trie).  Every one of them was produced by the timed path: the
    scheduler's admission and graft, a SplitFuse prefill of the message and
    the history's last partial block, the fused decode windows at
    ``max_seqs`` live slots beside contexts of every length, through both
    page layers of every layer.  Of the turns ranked by length, those at
    ``SERVED_PICKS`` (shortest, median, longest) are run through the
    reference teacher-forced (``prompt + produced``, padded to one length so
    that the reference compiles once), and for every served token the
    reference's logit of it is held against the reference's best at that
    position (``_gaps``).

    A token counts as the reference's when the gap is within
    ``decode_gap_rms``; ``served_within_share`` of the sample must be, and
    ``served_turn_within_share`` of every sampled turn (a slot, a graft or a
    page table that is wrong is wrong for a whole turn).  The page pool is
    given back first: the reference needs its room, and the engine is not
    used after this."""
    import jax
    import numpy as np

    engine = system["engine"]
    tol = ctx.config["tolerances"]
    if not turns:
        return {"ok": False, "tokens": 0, "why": "no re-asked turn finished"}
    ranked = sorted(turns, key=lambda t: (len(t["prompt"])
                                          + len(t["produced"]), t["session"]))
    picks = []
    for quantile in SERVED_PICKS:
        turn = ranked[min(int(quantile * len(ranked)), len(ranked) - 1)]
        if not any(turn is p for p in picks):
            picks.append(turn)

    chunk = engine.config.max_tokens
    room = (job["document_tokens"]["max"] + job["question_tokens"]["max"]
            + job["answer_tokens"]["max"])
    padded = -(-room // chunk) * chunk
    rows, positions = [], []
    for turn in picks:
        seq = list(turn["prompt"]) + list(turn["produced"][:-1])
        rows.append(np.asarray(seq + [0] * (padded - len(seq)), np.int32))
        first = len(turn["prompt"]) - 1
        positions.append(list(range(first, first + len(turn["produced"]))))

    params = engine.params
    engine.kv.pages.delete()
    dev0 = ctx.devices[0]
    ref_model = system["ref_model"]

    def reference(rounded_to=None):
        """One pick at a time, the pieces' weights made by the same few
        programs for all of them."""
        weights = reference_weights(params, rounded_to)
        return [np.asarray(ref_model.logits(
            [jax.device_put(row, dev0)], weights, [pos])[0], np.float32)
            for row, pos in zip(rows, positions)]

    def reading(gaps_by_turn):
        flat = [g for gaps in gaps_by_turn for g in gaps]
        within = [float(np.mean(np.asarray(gaps) <= tol["decode_gap_rms"]))
                  for gaps in gaps_by_turn]
        return {"tokens": len(flat),
                "within_share": float(np.mean(
                    np.asarray(flat) <= tol["decode_gap_rms"])),
                "turn_within_share_min": min(within),
                "gap_mean": float(np.mean(flat)),
                "gap_median": float(np.median(flat)),
                "turns": [{"within_share": w, "tokens": len(g)}
                          for w, g in zip(within, gaps_by_turn)]}

    full = reference()
    out = reading([_gaps(r, t["produced"]) for r, t in zip(full, picks)])
    for entry, turn in zip(out["turns"], picks):
        entry.update(session=turn["session"], document=turn["document"],
                     prompt=len(turn["prompt"]), grafted=turn["grafted"])
    # a format below bfloat16 in place of the system: the greedy tokens of
    # the reference computed with its weights rounded, same positions
    for name, fmt in CONTROLS.items():
        try:
            out.setdefault("controls", {})[name] = reading(
                [_gaps(f, np.argmax(l, axis=1))
                 for f, l in zip(full, reference(fmt))])
        except Exception as exc:        # a control is the tool's, not the run's
            out["controls"][name] = {"error": repr(exc)[-300:]}
    block = engine.config.block_size
    out["grafted_ok"] = all(t["grafted"] >= t["document"] - block
                            for t in picks)
    out["ok"] = bool(
        out["grafted_ok"]
        and out["within_share"] >= tol["served_within_share"]
        and out["turn_within_share_min"] >= tol["served_turn_within_share"])
    return out
