"""The Keye-VL-2.0 serving system under test, built as a user builds it
(``KeyeVLLM.from_hf_config`` on the configuration file's published keys and
the share's own, ``InferenceEngineV2`` + ``LifecycleScheduler`` with the
prefix cache on), and checked against ``reference/keye_vl.py`` on what the
timed path produces: before the window the code paths one sequence at a time
(``check_against_reference``), after it a sample of the turns the window
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
from lib.xing4_system import _gaps, _group, check_plan
from reference.keye_vl import Reference

REHEARSAL_SERVING = dict(max_tokens=32, max_seqs=4, max_ctx=256,
                         block_size=8, max_queue=4)
#: --cpu-rehearsal: toy widths through the same control flow (``topk`` 16
#: under contexts of 60-160, so that sets are real)
TOY = dict(vocab_size=512, hidden_size=64, num_hidden_layers=2,
           num_attention_heads=4, num_key_value_heads=2, head_dim=32,
           num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32,
           ep_size=2, ep_rank=0,
           rope_scaling={"mrope_section": [4, 6, 6]},
           sa_config=dict(indexer_num_heads=4, indexer_head_dim=16,
                          indexer_num_kv_heads=1, topk=16, q_chunk_size=512,
                          kv_chunk_size=512))



def serving_dtype(rehearsal: bool):
    """bfloat16; float32 in the CPU rehearsal: at the toy ``topk`` of 16 ONE
    member of a set that bfloat16 rounding moves across the 16th score is a
    sixteenth of the attention's mass (at 2,048 it is a two-thousandth), and
    the rehearsal is there for the control flow."""
    import jax.numpy as jnp

    return jnp.float32 if rehearsal else jnp.bfloat16


#: the check's seeded turn: a document of several 512-token chunks beyond
#: ``topk`` that is no multiple of 64 (a page) nor of a chunk; a question
CHECK_DOC = 5003
CHECK_QUESTION = 40
#: one more prompt, WITHIN ``topk``: selection is the identity and the
#: programs take the dense K/V kernels
CHECK_SHORT = 1387
MIXED = (150, 85)       # two more sequences, prefilled TOGETHER in one batch
TAIL = 3                # tokens each of the two is then fed, singly
CHECK_UID = 2_000_000_000
#: the served sample: quantiles of the finished turns ranked by length
SERVED_PICKS = (0.0, 0.5, 1.0)
#: the sample's longest turn is cut to this many tokens of context (a turn
#: of 66k through six float32 layers is ~1e14 FLOPs)
SERVED_CAP = 32768
#: what ``check_served`` reads besides the system (``tools/keye_readings.py``
#: fills it; a benchmark run leaves it empty): name -> ("round", format) or
#: ("mutation", name of a mutation of the reference)
CONTROLS: Dict = {}

_LAYER = {
    "in_norm": ("in_norm", "scale"), "post_norm": ("post_norm", "scale"),
    "w_q": ("q_proj", "kernel"), "w_k": ("k_proj", "kernel"),
    "w_v": ("v_proj", "kernel"), "q_norm": ("q_norm", "scale"),
    "k_norm": ("k_norm", "scale"), "w_o": ("o_proj", "kernel"),
    "w_qi": ("index_q", "kernel"), "w_ki": ("index_k", "kernel"),
    "w_wi": ("index_w", "kernel"), "router": ("router", "kernel")}
_EXPERTS = {"e_gate": "gate", "e_up": "up", "e_down": "down"}


def published(config: Dict, rehearsal: bool) -> Dict:
    """The configuration file's ``config.json`` keys as run (``sa_config``
    and ``rope_scaling`` nested as published)."""
    hf = {k: v for k, v in config.items()
          if isinstance(v, (int, float, bool, str)) or k in (
              "sa_config", "rope_scaling", "mlp_only_layers")}
    if rehearsal:
        hf.update(TOY)
    hf["mrope_section"] = hf["rope_scaling"]["mrope_section"]
    return hf


def reference_weights(params, rounded_to=None) -> Dict:
    """The program's parameter tree as what ``reference.keye_vl`` takes, in
    the dtypes the program computes in (the reference casts at each use), a
    layer at a time.  ``rounded_to`` names a format below bfloat16 that
    every bfloat16 matrix is rounded to first, the second reading of a
    tolerance: ``"int8"`` (symmetric, 127 steps to the largest value of each
    output channel) or (exponent bits, mantissa bits) of a float format
    (``reduce_precision``: the TPU's compiler drops a cast down and back
    up, PR 28)."""
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

    def at(x, l):
        return as_run(jax.lax.dynamic_index_in_dim(x, l, keepdims=False))

    layer = jax.jit(lambda stack, experts, l: dict(
        {k: at(stack[a][b], l) for k, (a, b) in _LAYER.items()},
        **{k: at(experts[name], l) for k, name in _EXPERTS.items()}))
    outer = {"embedding": params["embed"]["embedding"],
             "norm": params["norm_f"]["scale"],
             "head": params["lm_head"]["kernel"]}
    if rounded_to is not None:
        outer = jax.jit(lambda t: jax.tree.map(as_run, t))(outer)
    stack, experts = params["layers"], params["experts"]
    return dict(outer, layers=[
        lambda l=l: layer(stack, experts, l)
        for l in range(stack["router"]["kernel"].shape[0])])


def _reference_of(hf, control):
    """(Reference, rounding) of the plain model or of a control."""
    kind, what = control or (None, None)
    return (Reference(hf, what if kind == "mutation" else None),
            what if kind == "round" else None)


def prepare(ctx) -> Dict:
    """Model, parameters, the check's sequences and the reference's logits
    for them — what is made before the page pool takes the memory."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.keye_vl import KeyeVLLM, forward

    serving = dict(ctx.config["serving"])
    if ctx.rehearsal:
        serving.update(REHEARSAL_SERVING)
    dev0 = ctx.devices[0]
    hf = published(ctx.config, ctx.rehearsal)
    model = KeyeVLLM.from_hf_config(hf, max_seq_len=serving["max_ctx"])
    cfg = model.config
    n_doc = min(CHECK_DOC, serving["max_ctx"] * 5 // 8)
    n_q = min(CHECK_QUESTION, serving["max_ctx"] // 8)
    n_short = CHECK_SHORT if CHECK_SHORT <= cfg.topk else cfg.topk * 2 // 3
    with ctx.spans.span("bench/setup_params"):
        params = model_lib.init_params(model, ctx.seed + 1,
                                       serving_dtype(ctx.rehearsal))
        jax.block_until_ready(params)
    rng = np.random.default_rng(ctx.seed + 99)
    draw = lambda n: rng.integers(  # noqa: E731
        1, cfg.vocab_size, size=n).astype(np.int32)
    doc = draw(n_doc)
    turns = [np.concatenate([doc, draw(n_q)]) for _ in range(2)]
    short = draw(n_short)
    scale = 8 if ctx.rehearsal else 1
    mixed = [draw(max(n // scale, 3) + TAIL) for n in MIXED]
    chunk = serving["max_tokens"]
    plan = check_plan(len(turns[0]), n_doc, chunk)
    short_ends = [min(pos + chunk, n_short) - 1
                  for pos in range(0, n_short, chunk)]
    positions = plan["positions"] + [short_ends] + [
        list(range(len(r) - 1 - TAIL, len(r))) for r in mixed]

    # the queries whose sets are compared: the tokens fed singly
    sets_at = plan["positions"][0][plan["chunk_ends"]:
                                   plan["chunk_ends"] + plan["singles"]]

    def reference(control=None, sets=False):
        ref_model, rounded_to = _reference_of(hf, control)
        out = ref_model.logits(
            [jax.device_put(t, dev0) for t in turns + [short] + mixed],
            reference_weights(params, rounded_to), positions=positions,
            sets_at=sets_at if sets else None)
        out, chosen = out if sets else (out, None)
        out = [np.asarray(r, np.float32) for r in out]
        return (out, np.asarray(chosen)) if sets else out

    with ctx.spans.span("bench/setup_reference"):
        ref, ref_sets = reference(sets=True)
        # the same queries' sets in the program's arithmetic (bfloat16
        # parameters and activations, float32 scores), whole-sequence
        n = len(turns[0])
        _, own_sets = jax.jit(lambda p, ids: forward(
            p, ids, jnp.broadcast_to(jnp.arange(n), (3, n)), cfg,
            sets_at=sets_at))(params, jax.device_put(turns[0], dev0))
        own_sets = np.asarray(own_sets)
        overlap = float((own_sets & ref_sets).sum() / max(own_sets.sum(), 1))
    return {"index_select_overlap": overlap,"cfg": cfg, "model": model, "params": params, "hf": hf,
            "param_bytes": int(sum(x.nbytes
                                   for x in jax.tree.leaves(params))),
            "check_turns": turns, "check_short": short,
            "check_mixed": mixed, "check_doc": n_doc,
            "check_plan": plan, "ref": ref, "reference": reference,
            "serving": serving}


def build(ctx, system: Dict = None) -> Dict:
    """``prepare``, then the engine (its pool: what the parameters leave,
    less the reserve; a block holds, in each of the ``num_layers`` page
    layers, 64 K/V rows and their 64 index keys) and the scheduler."""
    import math

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
    block_bytes = family.page_layers * bs * 2 * (
        math.prod(family.row.token_shape) + family.row.index.dim)
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
            RaggedInferenceEngineConfig(
                num_blocks=num_blocks, dtype=serving_dtype(ctx.rehearsal),
                **serving))
        scheduler = LifecycleScheduler(engine, max_queue=max_queue,
                                       clock=time.perf_counter)
    system.pop("reference")
    system.update(engine=engine, scheduler=scheduler, num_blocks=num_blocks,
                  block_bytes=block_bytes, serving=serving)
    return system


def check_against_reference(ctx, system: Dict) -> Dict:
    """What the timed path's programs produce against the reference's logits
    at the same positions, one sequence at a time, in six groups (= code
    paths): chunked prefill of a document beyond ``topk`` (every chunk's
    last position: from the fifth chunk on every query has a set of its
    own), a prompt within ``topk`` (the dense K/V kernels), a batch of
    chunks of two sequences together and then their next tokens, single
    tokens through the cache (score, select, read, attend), one-step fused
    decode windows (the greedy token's reference logit against the
    reference's best), and a re-asked turn whose document is grafted from
    the trie — K/V rows AND index keys — with the tokens after it.

    A routed model has two modes of error: a position whose router picked
    the reference's experts in every layer, and one where a near-tie fell
    the other way; and a sparse-attending one a third, a score near the
    ``topk``-th that fell on the other side (``index_select_overlap`` is
    reported by the served check's tool, not bounded).  The limits are held
    by the bulk: the lower quartile over all logit positions, and in every
    group at least ``group_within_share`` of its positions."""
    import numpy as np

    engine = system["engine"]
    tol = ctx.config["tolerances"]
    plan = system["check_plan"]
    cold, warm_turn = (t.tolist() for t in system["check_turns"])
    short = system["check_short"].tolist()
    mixed_a, mixed_b = (r.tolist() for r in system["check_mixed"])
    ref_cold, ref_warm, ref_short, ref_a, ref_b = system["ref"]
    chunk = engine.config.max_tokens
    body = plan["body"]
    rel = model_lib.rel_l2

    # ---- turn 0, cold: chunks, a short prompt, two more, singles, windows ---
    got = []
    for pos in range(0, body, chunk):
        logits = engine.put([CHECK_UID], [cold[pos:min(pos + chunk, body)]])
        got.append(np.asarray(logits[0], np.float32))
    got_short = []
    for pos in range(0, len(short), chunk):
        logits = engine.put([CHECK_UID + 4], [short[pos:pos + chunk]])
        got_short.append(np.asarray(logits[0], np.float32))
    engine.flush([CHECK_UID + 4])
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
    rels_short = [rel(g, r) for g, r in zip(got_short, ref_short)]
    rels_mixed = [rel(both[i, 0], ref_a[i]) for i in range(1 + TAIL)] \
        + [rel(both[i, 1], ref_b[i]) for i in range(1 + TAIL)]
    finite = all(bool(np.isfinite(g).all()) for g in got + got_short) \
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
    # ---- commit, flush, re-ask with the document grafted ---------------------
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
        "short": _group(rels_short, limit),
        "mixed": _group(rels_mixed, limit),
        "singles": _group(rels[plan["chunk_ends"]:], limit),
        "windows": _group(gaps, tol["decode_gap_rms"]),
        "grafted": _group(rels1, limit)}
    flips = sum(g["over"] for g in groups.values())
    positions = sum(g["n"] for g in groups.values())
    every = rels + rels_short + rels_mixed + rels1
    quartile = float(np.percentile(every, 25))
    # the graft must have covered the document (less its last, partial block)
    graft_ok = grafted >= system["check_doc"] - engine.config.block_size
    ok = (finite and graft_ok and quartile <= limit
          and all(g["n"] - g["over"] >= tol["group_within_share"] * g["n"]
                  for g in groups.values()))
    return {"groups": groups, "logits_finite": finite, "grafted": grafted,
            "graft_ok": graft_ok, "positions": positions,
            "flip_share": flips / positions,
            # reported, not bounded: the share of the program's sets (its
            # arithmetic, the tokens fed singly, every layer) that the
            # reference's sets hold
            "index_select_overlap": system["index_select_overlap"],
            "logits_rel_l2": quartile,
            "logits_rel_l2_median": float(np.median(every)),
            "ok": bool(ok)}


def check_served(ctx, system: Dict, turns: List[Dict], job: Dict) -> Dict:
    """A sample of the turns the WINDOW served against the reference.

    ``turns``: the re-asked turns the window finished, in the order they
    finished, each ``session``, ``document`` (its length), ``prompt``,
    ``produced`` (the served tokens) and ``grafted`` (prompt tokens taken
    from the trie).  Every one of them was produced by the timed path: the
    scheduler's admission and graft (K/V rows and index keys), a SplitFuse
    prefill of the question and the document's last partial block under the
    set's mask, the fused decode windows at ``max_seqs`` live slots beside
    contexts of every length, each query scoring its whole context and
    reading its own 2,048 rows.  Of the turns ranked by length, those at
    ``SERVED_PICKS`` (shortest, median, longest) are run through the
    reference teacher-forced (``prompt + produced``), and for every served
    token the reference's logit of it is held against the reference's best
    at that position (``_gaps``).  A pick longer than ``SERVED_CAP`` tokens
    gives way to the longest turn within it (the reference's cost grows
    with the square).

    A token counts as the reference's when the gap is within
    ``decode_gap_rms``; ``served_within_share`` of the sample must be, and
    ``served_turn_within_share`` of every sampled turn.  The page pool is
    given back first: the reference needs its room, and the engine is not
    used after this."""
    import jax
    import numpy as np

    engine = system["engine"]
    tol = ctx.config["tolerances"]
    if not turns:
        return {"ok": False, "tokens": 0, "why": "no re-asked turn finished"}
    size = lambda t: len(t["prompt"]) + len(t["produced"])  # noqa: E731
    ranked = sorted(turns, key=lambda t: (size(t), t["session"]))
    within = [t for t in ranked if size(t) <= SERVED_CAP] or ranked[:1]
    picks = []
    for quantile in SERVED_PICKS:
        turn = ranked[min(int(quantile * len(ranked)), len(ranked) - 1)]
        if size(turn) > SERVED_CAP:
            turn = within[-1]
        if not any(turn is p for p in picks):
            picks.append(turn)

    chunk = engine.config.max_tokens
    rows, positions = [], []
    for turn in picks:
        seq = list(turn["prompt"]) + list(turn["produced"][:-1])
        # whole chunks: two picks of one padded length compile once
        padded = -(-len(seq) // (4 * chunk)) * 4 * chunk
        rows.append(np.asarray(seq + [0] * (padded - len(seq)), np.int32))
        first = len(turn["prompt"]) - 1
        positions.append(list(range(first, first + len(turn["produced"]))))

    params = engine.params
    for array in engine.kv.arrays():
        array.delete()
    dev0 = ctx.devices[0]
    hf = system["hf"]

    def reference(control=None):
        ref_model, rounded_to = _reference_of(hf, control)
        weights = reference_weights(params, rounded_to)
        return [np.asarray(ref_model.logits(
            [jax.device_put(row, dev0)], weights, [pos])[0], np.float32)
            for row, pos in zip(rows, positions)]

    def reading(gaps_by_turn):
        flat = [g for gaps in gaps_by_turn for g in gaps]
        share = [float(np.mean(np.asarray(gaps) <= tol["decode_gap_rms"]))
                 for gaps in gaps_by_turn]
        return {"tokens": len(flat),
                "within_share": float(np.mean(
                    np.asarray(flat) <= tol["decode_gap_rms"])),
                "turn_within_share_min": min(share),
                "gap_mean": float(np.mean(flat)),
                "gap_median": float(np.median(flat)),
                "turns": [{"within_share": w, "tokens": len(g)}
                          for w, g in zip(share, gaps_by_turn)]}

    full = reference()
    out = reading([_gaps(r, t["produced"]) for r, t in zip(full, picks)])
    for entry, turn in zip(out["turns"], picks):
        entry.update(session=turn["session"], document=turn["document"],
                     prompt=len(turn["prompt"]), grafted=turn["grafted"])
    # a control in place of the system: the greedy tokens of the reference
    # computed with its weights rounded, or with a piece of it changed,
    # at the same positions
    for name, control in CONTROLS.items():
        try:
            out.setdefault("controls", {})[name] = reading(
                [_gaps(f, np.argmax(l, axis=1))
                 for f, l in zip(full, reference(control))])
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
