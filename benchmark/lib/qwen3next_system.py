"""The Qwen3-Next serving system under test, built as a user builds it
(``Qwen3NextLM.from_hf_config`` on the configuration file's published keys
and the share's own, ``InferenceEngineV2`` + ``LifecycleScheduler``, prefix
cache off), and checked against ``reference/qwen3_next.py`` on what the timed
path produces: before the window the code paths one sequence at a time
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
from lib.xing4_system import _gaps, _group    # a group's reading; the gaps
from reference.qwen3_next import Reference

REHEARSAL_SERVING = dict(max_tokens=32, max_seqs=4, max_ctx=256,
                         block_size=8, max_queue=4)
#: --cpu-rehearsal: toy widths through the same control flow
TOY = dict(vocab_size=512, hidden_size=64, num_hidden_layers=4,
           num_attention_heads=4, num_key_value_heads=2, head_dim=32,
           linear_num_key_heads=2, linear_num_value_heads=4,
           linear_key_head_dim=16, linear_value_head_dim=16, num_experts=4,
           num_experts_per_tok=2, moe_intermediate_size=32,
           shared_expert_intermediate_size=32, intermediate_size=128)

#: the check's seeded sequence: a prompt of several 512-token chunks that is
#: no multiple of 64 (the chunked form's chunk) nor of a page
CHECK_PROMPT = 1387
MIXED = (150, 85)       # two more sequences, prefilled TOGETHER in one batch
SINGLES = 12            # tokens fed one at a time through slot + pages
WINDOWS = 12            # one-step fused decode windows, teacher-forced
REUSED = 97             # a fresh sequence in the slot the first one gave back
TAIL = 3                # tokens each of the two fed singly, TOGETHER, after
REUSED_TAIL = 7         # tokens of the fresh sequence fed singly
CHECK_UID = 2_000_000_000
#: the served sample: quantiles of the finished turns ranked by length
SERVED_PICKS = (0.0, 0.5, 1.0)
#: formats below bfloat16 whose reading ``check_served`` adds to its own
#: (``tools/qwen3next_readings.py`` fills it; a benchmark run leaves it empty)
CONTROLS: Dict = {}

_GDN = {"in_norm": ("in_norm", "scale"), "w_qkvz": ("qkvz", "kernel"),
        "w_ba": ("ba", "kernel"), "conv": ("conv", "kernel"),
        "A_log": ("A_log",), "dt_bias": ("dt_bias",),
        "gnorm": ("gnorm", "scale"), "w_o": ("o_proj", "kernel")}
_ATTN = {"in_norm": ("in_norm", "scale"), "w_q": ("q_proj", "kernel"),
         "w_k": ("k_proj", "kernel"), "w_v": ("v_proj", "kernel"),
         "q_norm": ("q_norm", "scale"), "k_norm": ("k_norm", "scale"),
         "w_o": ("o_proj", "kernel")}
_MOE = {"post_norm": ("post_norm", "scale"), "router": ("router", "kernel"),
        "s_gate": ("shared", "gate"), "s_up": ("shared", "up"),
        "s_down": ("shared", "down"), "s_gatew": ("shared_gate", "kernel")}
_EXPERTS = {"e_gate": "gate", "e_up": "up", "e_down": "down"}


def published(config: Dict, rehearsal: bool) -> Dict:
    """The configuration file's ``config.json`` keys as run."""
    hf = {k: v for k, v in config.items()
          if isinstance(v, (int, float, bool, str, list)) or v is None}
    if rehearsal:
        hf.update(TOY)
    return hf


def reference_weights(params, interval: int, rounded_to=None) -> Dict:
    """The program's parameter tree as what ``reference.qwen3_next`` takes,
    in the dtypes the program computes in (the reference casts at each use),
    one layer at a time.  ``rounded_to`` names a format below bfloat16 that
    every bfloat16 matrix is rounded to first, the second reading of a
    tolerance: (exponent bits, mantissa bits) of a float format, or
    ``"int8"`` (symmetric, 127 steps to the largest value of each output
    channel).  ``reduce_precision`` and not a pair of casts: the TPU's
    compiler drops a cast down and back up (PR 28)."""
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

    def leaf(tree, path):
        for key in path:
            tree = tree[key]
        return tree

    periods, experts = params["periods"], params["experts"]
    G = interval - 1

    @jax.jit
    def one(periods, experts, p, j):
        """Layer ``j`` of period ``p`` (``j == G``: the attention layer)."""
        def at(x, *idx):
            for i in idx:
                x = jax.lax.dynamic_index_in_dim(x, i, keepdims=False)
            return as_run(x)

        moe = {k: at(leaf(periods["moe"], path), p, j)
               for k, path in _MOE.items()}
        moe["s_gatew"] = moe["s_gatew"][:, 0]
        held = {k: at(experts[name], p * interval + j)
                for k, name in _EXPERTS.items()}
        return moe, held

    gdn = jax.jit(lambda periods, p, j: {
        k: as_run(leaf(periods["gdn"], path)[p, j])
        for k, path in _GDN.items()}, static_argnums=(1, 2))
    attn = jax.jit(lambda periods, p: {
        k: as_run(leaf(periods["attn"], path)[p])
        for k, path in _ATTN.items()}, static_argnums=(1,))

    def maker(p, j):
        def make():
            moe, held = one(periods, experts, p, j)
            mixer = gdn(periods, p, j) if j < G else attn(periods, p)
            return dict(mixer, **moe, **held)
        return make

    P = periods["attn"]["in_norm"]["scale"].shape[0]
    outer = {"embedding": params["embed"]["embedding"],
             "norm": params["norm_f"]["scale"],
             "head": params["lm_head"]["kernel"]}
    if rounded_to is not None:
        outer = jax.jit(lambda t: jax.tree.map(as_run, t))(outer)
    return dict(outer, layers=[maker(p, j) for p in range(P)
                               for j in range(interval)])


def prepare(ctx) -> Dict:
    """Model, parameters, the check's sequences and the reference's logits
    for them — what is made before the pools take the memory."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.qwen3_next import Qwen3NextLM

    serving = dict(ctx.config["serving"])
    if ctx.rehearsal:
        serving.update(REHEARSAL_SERVING)
    dev0 = ctx.devices[0]
    hf = published(ctx.config, ctx.rehearsal)
    model = Qwen3NextLM.from_hf_config(hf, max_seq_len=serving["max_ctx"])
    cfg = model.config
    ref_model = Reference(hf)
    with ctx.spans.span("bench/setup_params"):
        params = model_lib.init_params(model, ctx.seed + 1, jnp.bfloat16)
        jax.block_until_ready(params)
    scale = 1 if not ctx.rehearsal else 8
    n_prompt = min(CHECK_PROMPT // scale, serving["max_ctx"] * 3 // 4)
    rng = np.random.default_rng(ctx.seed + 99)
    draw = lambda n: rng.integers(  # noqa: E731
        1, cfg.vocab_size, size=n).astype(np.int32)
    rows = [draw(n_prompt + SINGLES + WINDOWS)] \
        + [draw(max(n // scale, 3) + TAIL) for n in MIXED] \
        + [draw(max(REUSED // scale, 3) + REUSED_TAIL)]
    plan = check_plan(len(rows[0]), serving["max_tokens"])
    positions = [plan["positions"]] + [
        list(range(len(r) - 1 - tail, len(r)))
        for r, tail in zip(rows[1:], (TAIL, TAIL, REUSED_TAIL))]

    def reference(rounded_to=None):
        out = ref_model.logits(
            [jax.device_put(r, dev0) for r in rows],
            reference_weights(params, cfg.full_attention_interval,
                              rounded_to), positions=positions)
        return [np.asarray(r, np.float32) for r in out]

    with ctx.spans.span("bench/setup_reference"):
        ref = reference()
    return {"cfg": cfg, "model": model, "params": params,
            "param_bytes": int(sum(x.nbytes
                                   for x in jax.tree.leaves(params))),
            "check_rows": rows, "check_plan": plan, "ref": ref,
            "reference": reference, "ref_model": ref_model,
            "serving": serving}


def build(ctx, system: Dict = None) -> Dict:
    """``prepare``, then the engine (its state pool: ``max_seqs`` slots; its
    page pool: what the parameters and the state pool leave, less the
    reserve) and the scheduler."""
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.lifecycle import LifecycleScheduler

    system = system or prepare(ctx)
    serving, cfg = dict(system["serving"]), system["cfg"]
    reserve = serving.pop("kv_reserve_bytes")
    max_queue = serving.pop("max_queue")
    dev0 = ctx.devices[0]
    bs = serving["block_size"]
    block_bytes = cfg.num_periods * bs * 2 * cfg.num_kv_heads * cfg.head_dim * 2
    state_bytes = (serving["max_seqs"] + 1) * cfg.state.slot_bytes(
        jnp.bfloat16)
    full_pool = serving["max_seqs"] * -(-serving["max_ctx"] // bs)
    stats = dev0.memory_stats() or {}
    if ctx.rehearsal or "bytes_limit" not in stats:
        num_blocks = full_pool
    else:
        free = stats["bytes_limit"] - stats["bytes_in_use"] - state_bytes
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
                  block_bytes=block_bytes, state_bytes=state_bytes,
                  serving=serving)
    return system


def check_plan(n: int, chunk: int) -> Dict:
    """Which positions of the first sequence the check compares: the last
    position of every prefill chunk of its body, then ``SINGLES`` tokens fed
    singly, then ``WINDOWS`` one-step fused windows."""
    singles = min(SINGLES, max(n // 8, 1))
    windows = min(WINDOWS, max(n // 8, 1))
    body = n - singles - windows
    chunk_ends = [min(pos + chunk, body) - 1 for pos in range(0, body, chunk)]
    return {"body": body, "singles": singles, "windows": windows,
            "chunk_ends": len(chunk_ends),
            "positions": chunk_ends + list(range(body, n))}


def check_against_reference(ctx, system: Dict) -> Dict:
    """What the timed path's programs produce against the reference's logits
    at the same positions, one sequence at a time, in five groups (= code
    paths): chunked prefill through slot and pages (every chunk's last
    position; the prompt is several chunks and no multiple of 64), a batch
    of chunks of two sequences together and then their next tokens, both in
    one batch, single tokens through slot + pages, one-step fused decode
    windows (the greedy token's reference logit against the reference's
    best), and a fresh sequence (a chunk, then single tokens) in a slot a
    flushed sequence gave back.

    A routed model has two modes of error: a position whose router picked
    the reference's experts in every layer, and one where a near-tie of the
    top ``k`` fell the other way.  The limits are held by the bulk: the
    lower quartile over all logit positions, and in every group at least
    ``group_within_share`` of its positions; the share over the limit is
    reported (``routing_flip_share``), not bounded."""
    import numpy as np

    engine = system["engine"]
    tol = ctx.config["tolerances"]
    plan = system["check_plan"]
    first, mixed_a, mixed_b, reused = (r.tolist()
                                       for r in system["check_rows"])
    ref_first, ref_a, ref_b, ref_reused = system["ref"]
    chunk = engine.config.max_tokens
    body = plan["body"]
    rel = model_lib.rel_l2
    sm = engine.state_manager

    got = []
    for pos in range(0, body, chunk):
        logits = engine.put([CHECK_UID], [first[pos:min(pos + chunk, body)]])
        got.append(np.asarray(logits[0], np.float32))
    first_slot = sm.get_sequence(CHECK_UID).slot
    # two more sequences prefilled TOGETHER (several sequences' chunks in one
    # flat batch), while the first one's state waits in its slot
    pair = [CHECK_UID + 1, CHECK_UID + 2]
    both = [np.asarray(engine.put(pair, [mixed_a[:-TAIL], mixed_b[:-TAIL]]),
                       np.float32)]
    for i in range(TAIL, 0, -1):     # and their next tokens, both in a batch
        both.append(np.asarray(engine.put(pair, [[mixed_a[-i]],
                                                 [mixed_b[-i]]]), np.float32))
    both = np.stack(both)                                       # [1+TAIL, 2, V]
    for tok in first[body:body + plan["singles"]]:
        got.append(np.asarray(engine.put([CHECK_UID], [[tok]])[0],
                              np.float32))
    n_logits = plan["chunk_ends"] + plan["singles"]
    rels = [rel(g, r) for g, r in zip(got, ref_first[:n_logits])]
    rels_mixed = [rel(both[i, 0], ref_a[i]) for i in range(1 + TAIL)] \
        + [rel(both[i, 1], ref_b[i]) for i in range(1 + TAIL)]
    finite = all(bool(np.isfinite(g).all()) for g in got) \
        and bool(np.isfinite(both).all())
    gaps = []
    for i, tok in enumerate(first[body + plan["singles"]:]):
        out = int(engine.decode_batch([CHECK_UID], [tok], 1)[0, 0])
        row = ref_first[n_logits + i]
        gaps.append(float(row.max() - row[out])
                    / float(np.sqrt(np.mean(row ** 2))))
    # flush, and a fresh sequence in the slot that came back: it must start
    # from zeros on the device, not from the last owner's state
    # (the first one's slot goes back last, so it is the next handed out)
    engine.flush([CHECK_UID + 1, CHECK_UID + 2, CHECK_UID])
    out = [np.asarray(engine.put([CHECK_UID + 3], [reused[:-REUSED_TAIL]])[0],
                      np.float32)]
    slot_reused = sm.get_sequence(CHECK_UID + 3).slot == first_slot
    for tok in reused[-REUSED_TAIL:]:
        out.append(np.asarray(engine.put([CHECK_UID + 3], [[tok]])[0],
                              np.float32))
    rels_reused = [rel(o, r) for o, r in zip(out, ref_reused)]
    finite = finite and all(bool(np.isfinite(o).all()) for o in out)
    engine.flush([CHECK_UID + 3])

    limit = tol["logits_rel_l2"]
    groups = {
        "prefill": _group(rels[:plan["chunk_ends"]], limit),
        "mixed": _group(rels_mixed, limit),
        "singles": _group(rels[plan["chunk_ends"]:], limit),
        "windows": _group(gaps, tol["decode_gap_rms"]),
        "reused_slot": _group(rels_reused, limit)}
    flips = sum(g["over"] for g in groups.values())
    positions = sum(g["n"] for g in groups.values())
    every = rels + rels_mixed + rels_reused
    quartile = float(np.percentile(every, 25))
    ok = (finite and slot_reused and quartile <= limit
          and all(g["n"] - g["over"] >= tol["group_within_share"] * g["n"]
                  for g in groups.values()))
    return {"groups": groups, "logits_finite": finite,
            "slot_reused": slot_reused, "positions": positions,
            "routing_flip_share": flips / positions,
            "logits_rel_l2": quartile,
            "logits_rel_l2_median": float(np.median(every)),
            "ok": bool(ok)}


def check_served(ctx, system: Dict, turns: List[Dict], job: Dict) -> Dict:
    """A sample of the turns the WINDOW served against the reference.

    ``turns``: the re-asked turns the window finished, in the order they
    finished, each ``session``, ``prompt`` and ``produced`` (the served
    tokens).  Every one of them was produced by the timed path: admission
    and a slot from the scheduler, the SplitFuse prefill of the whole turn
    beside other sequences' chunks, the fused decode windows at ``max_seqs``
    live slots.  Of the turns ranked by length, those at ``SERVED_PICKS``
    are run through the reference teacher-forced (``prompt + produced``,
    padded to one length so that the reference compiles once), and for every
    served token the reference's logit of it is held against the reference's
    best at that position (``_gaps``).

    A token counts as the reference's when the gap is within
    ``decode_gap_rms``; ``served_within_share`` of the sample must be, and
    ``served_turn_within_share`` of every sampled turn (a slot or a page
    table that is wrong is wrong for a whole turn).  Both pools are given
    back first: the reference needs their room, and the engine is not used
    after this."""
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
    for array in engine.state_pool.arrays:
        array.delete()
    dev0 = ctx.devices[0]
    ref_model = system["ref_model"]
    interval = system["cfg"].full_attention_interval

    def reference(rounded_to=None):
        weights = reference_weights(params, interval, rounded_to)
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
        entry.update(session=turn["session"], prompt=len(turn["prompt"]))
    # a format below bfloat16 in place of the system: the greedy tokens of
    # the reference computed with its weights rounded, same positions
    for name, fmt in CONTROLS.items():
        try:
            out.setdefault("controls", {})[name] = reading(
                [_gaps(f, np.argmax(l, axis=1))
                 for f, l in zip(full, reference(fmt))])
        except Exception as exc:        # a control is the tool's, not the run's
            out["controls"][name] = {"error": repr(exc)[-300:]}
    out["ok"] = bool(
        out["within_share"] >= tol["served_within_share"]
        and out["turn_within_share_min"] >= tol["served_turn_within_share"])
    return out
