"""The Xing4 serving system under test, built as a user builds it
(``Xing4LM.from_hf_config`` on the configuration file's published keys,
``InferenceEngineV2`` + ``LifecycleScheduler`` with the prefix cache on), and
checked against ``reference/xing4.py`` on what the timed path produces:
before the window the four code paths one sequence at a time
(``check_against_reference``), after it a sample of the tokens the window
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
from reference.xing4 import Reference

REHEARSAL_SERVING = dict(max_tokens=32, max_seqs=4, max_ctx=256,
                         block_size=8)
#: --cpu-rehearsal: toy widths through the same control flow
TOY = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
           moe_intermediate_size=32, num_hidden_layers=3,
           first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=48,
           kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2)
TOY_ROPE = dict(original_max_position_embeddings=32, factor=8)

#: the check's seeded turn: a document past original_max_position_embeddings
#: (YaRN's interpolated band), more than eight 512-token chunks, not a
#: multiple of a block; a question after it
CHECK_DOC = 4643
CHECK_QUESTION = 40
SINGLES = 16            # tokens fed one at a time through the latent cache
WINDOWS = 12            # one-step fused decode windows, teacher-forced
CHECK_UID = 2_000_000_000
#: the served sample: (quantile of the sessions ranked by document length,
#: how many of that session's last re-asked turns).  Two sequence shapes for
#: the reference to compile; it runs about 1,100 tokens a second, so the
#: longest documents are left to the page walk's own tests
SERVED_PICKS = ((0.0, 2), (0.5, 1))
#: formats below bfloat16 whose reading ``check_served`` adds to its own
#: (``tools/xing4_readings.py`` fills it; a benchmark run leaves it empty)
CONTROLS: Dict = {}
#: spread of the routed experts' popularity: the router's column of expert
#: e is scaled by a seeded factor in this range, as a trained router's
#: columns differ, and the selection bias is what evens the loads again
POPULARITY = (0.6, 1.4)

_LAYER_NAMES = {
    "attn_norm": ("attn_norm", "scale"), "w_dq": ("q_a_proj", "kernel"),
    "q_norm": ("q_a_norm", "scale"), "w_uq": ("q_b_proj", "kernel"),
    "w_dkv": ("kv_a_proj", "kernel"), "kv_norm": ("kv_a_norm", "scale"),
    "w_ukv": ("kv_b_proj", "kernel"), "w_o": ("o_proj", "kernel"),
    "mlp_norm": ("mlp_norm", "scale"), "w_gate": ("gate_proj", "kernel"),
    "w_up": ("up_proj", "kernel"), "w_down": ("down_proj", "kernel"),
    "router": ("router", "kernel"), "router_bias": ("router", "bias"),
    "e_gate": ("experts", "gate"), "e_up": ("experts", "up"),
    "e_down": ("experts", "down"), "s_gate": ("shared", "gate"),
    "s_up": ("shared", "up"), "s_down": ("shared", "down")}


def published(config: Dict, rehearsal: bool) -> Dict:
    """The configuration file's ``config.json`` keys as run."""
    hf = {k: v for k, v in config.items()
          if isinstance(v, (int, float, bool, str)) or k == "rope_scaling"}
    if rehearsal:
        hf.update(TOY)
        hf["rope_scaling"] = dict(hf["rope_scaling"], **TOY_ROPE)
    return hf


def reference_weights(params, rounded_to=None) -> Dict:
    """The program's parameter tree as what ``reference.xing4`` takes, in the
    dtypes the program computes in (the reference casts at each use).
    ``rounded_to`` names a format below bfloat16 that every bfloat16 matrix
    is rounded to first, the second reading of a tolerance (``tools/
    xing4_readings.py``): (exponent bits, mantissa bits) of a float format,
    or ``"int8"`` (symmetric, 127 steps to the largest value of each output
    channel, then held in bfloat16 as weight-only int8 is in front of a
    bfloat16 matmul).  ``reduce_precision`` and not a pair of casts: the TPU's
    compiler drops a cast down and back up (``xla_allow_excess_precision``;
    the first reading of PR 28 was 0.0)."""
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

    def makers(stack):
        depth = stack["attn_norm"]["scale"].shape[0]
        names = {k: v for k, v in _LAYER_NAMES.items() if v[0] in stack}

        def pick(x, i):
            return as_run(jax.lax.dynamic_index_in_dim(x, i, keepdims=False))

        one = jax.jit(lambda tree, i: dict(
            {k: pick(tree[a][b], i) for k, (a, b) in names.items()},
            **{hc: {k: pick(v, i) for k, v in tree[hc].items()}
               for hc in ("hc_attn", "hc_mlp")}))
        return [lambda i=i: one(stack, i) for i in range(depth)]

    layers = []
    for stack in ("dense_layers", "moe_layers"):
        if stack in params:
            layers += makers(params[stack])
    outer = {"embedding": params["embed"]["embedding"],
             "norm": params["norm_f"]["scale"],
             "head": params["lm_head"]["kernel"]}
    if rounded_to is not None:
        outer = jax.jit(lambda p: jax.tree.map(as_run, p))(outer)
    return dict(outer, layers=layers)


def balance_bias(scores, bias, k: int, rounds: int = 200):
    """The selection bias under which the top ``k`` of ``scores + bias``
    [S, E] load every expert alike: ``noaux_tc``'s own rule (after a batch
    the bias of an overloaded expert goes down, of an underloaded one up),
    run on one batch with proportional steps that shrink.  From ``bias``;
    returned with mean 0."""
    import jax
    import jax.numpy as jnp

    E = scores.shape[1]
    even = scores.shape[0] * k / E

    def body(i, b):
        _, idx = jax.lax.top_k(scores + b, k)
        load = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        return b - 0.02 * 0.99 ** i * (load / even - 1.0)

    b = jax.lax.fori_loop(0, rounds, body, bias.astype(jnp.float32))
    return b - jnp.mean(b)


def balanced_router(params, ref: Reference, seed: int, n_tokens: int):
    """The seeded parameters with a router as training leaves one: experts
    of unlike popularity (each expert's column of the router scaled by a
    seeded factor in ``POPULARITY``: unbiased, the fullest would take 25
    times the emptiest's pairs) and an ``e_score_correction_bias`` that
    evens their loads, balanced layer by layer on one seeded calibration
    sequence of ``n_tokens`` through the reference.  So the bias decides
    which experts a token takes (without it five tokens in six take another
    set) and a decode step streams the weights of nearly all 64."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    moe = params["moe_layers"]
    router = moe["router"]
    L, _, E = router["kernel"].shape
    lo, hi = POPULARITY
    popularity = jax.random.uniform(
        jax.random.PRNGKey((seed + 7) % (2 ** 31)), (L, 1, E), jnp.float32,
        lo, hi)
    router = dict(router, kernel=router["kernel"] * popularity,
                  bias=jnp.zeros((L, E), jnp.float32))
    params = dict(params, moe_layers=dict(moe, router=router))
    c = ref.config
    row = np.random.default_rng(seed + 98).integers(
        1, c["vocab_size"], size=n_tokens).astype(np.int32)
    balance = jax.jit(lambda s, b: balance_bias(
        s, b, c["num_experts_per_tok"]))
    biases = ref.balanced_router_biases(row, reference_weights(params),
                                        balance)
    router = dict(router, bias=jnp.stack(biases))
    return dict(params, moe_layers=dict(moe, router=router))


def prepare(ctx) -> Dict:
    """Model, parameters, the check's two turns and the reference's logits
    for them — what is made before the page pool takes the memory."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.xing4 import Xing4LM

    serving = dict(ctx.config["serving"])
    if ctx.rehearsal:
        serving.update(REHEARSAL_SERVING)
    dev0 = ctx.devices[0]
    hf = published(ctx.config, ctx.rehearsal)
    model = Xing4LM.from_hf_config(hf, max_seq_len=serving["max_ctx"])
    cfg = model.config
    # the check's two turns of one session: the same document, two questions
    n_doc = min(CHECK_DOC, serving["max_ctx"] * 3 // 4)
    n_q = min(CHECK_QUESTION, serving["max_ctx"] // 8)
    ref_model = Reference(hf)
    with ctx.spans.span("bench/setup_params"):
        params = model_lib.init_params(model, ctx.seed + 1, jnp.bfloat16)
        jax.block_until_ready(params)
    with ctx.spans.span("bench/setup_router"):
        # as long as a turn of the check, so that the reference's layers
        # compile once for both
        params = balanced_router(params, ref_model, ctx.seed, n_doc + n_q)
        jax.block_until_ready(params)
    rng = np.random.default_rng(ctx.seed + 99)
    doc = rng.integers(1, cfg.vocab_size, size=n_doc).astype(np.int32)
    turns = [np.concatenate([doc, rng.integers(
        1, cfg.vocab_size, size=n_q).astype(np.int32)]) for _ in range(2)]
    plan = check_plan(len(turns[0]), n_doc, serving["max_tokens"])

    def reference(rounded_to=None):
        out = ref_model.logits(
            [jax.device_put(t, dev0) for t in turns],
            reference_weights(params, rounded_to),
            positions=plan["positions"])
        return [np.asarray(r, np.float32) for r in out]

    with ctx.spans.span("bench/setup_reference"):
        ref = reference()
    return {"cfg": cfg, "model": model, "params": params,
            "param_bytes": int(sum(x.nbytes
                                   for x in jax.tree.leaves(params))),
            "check_turns": turns, "check_doc": n_doc, "check_plan": plan,
            "ref": ref, "reference": reference, "ref_model": ref_model,
            "serving": serving}


def build(ctx, system: Dict = None) -> Dict:
    """``prepare``, then the engine (its pool: what the parameters leave,
    less the reserve) and the scheduler."""
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.lifecycle import LifecycleScheduler

    system = system or prepare(ctx)
    serving, cfg = dict(system["serving"]), system["cfg"]
    reserve = serving.pop("kv_reserve_bytes")
    dev0 = ctx.devices[0]
    bs = serving["block_size"]
    block_bytes = cfg.num_layers * bs * cfg.latent_row * 2
    full_pool = serving["max_seqs"] * serving["max_ctx"] // bs
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
        scheduler = LifecycleScheduler(engine, clock=time.perf_counter)
    system.pop("reference")
    system.update(engine=engine, scheduler=scheduler, num_blocks=num_blocks,
                  block_bytes=block_bytes, serving=serving)
    return system


def check_plan(n: int, n_doc: int, chunk: int) -> Dict:
    """Which positions of the two turns the check compares.  Turn 0 (cold):
    the last position of every prefill chunk of its body, then ``SINGLES``
    tokens fed singly, then ``WINDOWS`` one-step fused windows.  Turn 1
    (its document grafted from the trie): the last position of the prefill
    of what the graft left, then ``SINGLES - 1`` tokens fed singly."""
    singles = min(SINGLES, max(n // 8, 1))
    windows = min(WINDOWS, max(n // 8, 1))
    body = n - singles - windows
    chunk_ends = [min(pos + chunk, body) - 1 for pos in range(0, body, chunk)]
    cold = chunk_ends + list(range(body, n))
    body1 = n - (singles - 1)
    return {"body": body, "singles": singles, "windows": windows,
            "chunk_ends": len(chunk_ends), "body1": body1,
            "positions": [cold, list(range(body1 - 1, n))]}


def _group(values: List[float], limit: float) -> Dict:
    import numpy as np

    return {"median": float(np.median(values)),
            "over": int(sum(v > limit for v in values)), "n": len(values),
            "each": [round(float(v), 5) for v in values]}


def check_against_reference(ctx, system: Dict) -> Dict:
    """What the timed path produces against the reference's logits at the
    same positions, in four groups: chunked prefill (every chunk's last
    position), single tokens through the latent cache, one-step fused decode
    windows (the greedy token's reference logit against the reference's
    best), and a re-asked turn whose document is grafted from the trie.

    A routed model has two modes of error: a position whose router picked
    the reference's experts in every layer, and one where a near-tie fell
    the other way (its logits are then another function's: 0.17-0.8 off).
    At these widths (the top 4 of 64 scores under a bias that evens them,
    four expert layers, bf16 activations) two positions in five are of the
    second kind, and the first kind's floor is what the flipped tokens of
    the CONTEXT do to it through attention.  So the limits are held by the
    bulk: the lower quartile over all logit positions, and in every group
    (= every code path) at least ``group_within_share`` of its positions.
    The flips are counted and reported (``routing_flip_share``); what bounds
    them is the served sample (``check_served``), on some 900 tokens."""
    import numpy as np

    engine = system["engine"]
    tol = ctx.config["tolerances"]
    plan = system["check_plan"]
    cold, warm_turn = (t.tolist() for t in system["check_turns"])
    ref_cold, ref_warm = system["ref"]
    chunk = engine.config.max_tokens
    body, n = plan["body"], len(cold)
    rel = model_lib.rel_l2

    # ---- turn 0, cold: chunks, singles, windows -----------------------------
    got = []
    for pos in range(0, body, chunk):
        logits = engine.put([CHECK_UID], [cold[pos:min(pos + chunk, body)]])
        got.append(np.asarray(logits[0], np.float32))
    for tok in cold[body:body + plan["singles"]]:
        got.append(np.asarray(engine.put([CHECK_UID], [[tok]])[0],
                              np.float32))
    n_logits = plan["chunk_ends"] + plan["singles"]
    rels = [rel(g, r) for g, r in zip(got, ref_cold[:n_logits])]
    finite = all(bool(np.isfinite(g).all()) for g in got)
    # the fused window returns tokens, not logits: the reference's logit of
    # the greedy token may lie below the reference's best by at most
    # decode_gap_rms x rms(reference logits)
    gaps = []
    for i, tok in enumerate(cold[body + plan["singles"]:]):
        out = int(engine.decode_batch([CHECK_UID], [tok], 1)[0, 0])
        row = ref_cold[n_logits + i]
        gaps.append(float(row.max() - row[out])
                    / float(np.sqrt(np.mean(row ** 2))))
    # ---- commit, flush, re-ask with the document grafted --------------------
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
        "singles": _group(rels[plan["chunk_ends"]:], limit),
        "windows": _group(gaps, tol["decode_gap_rms"]),
        "grafted": _group(rels1, limit)}
    flips = sum(g["over"] for g in groups.values())
    positions = sum(g["n"] for g in groups.values())
    quartile = float(np.percentile(rels + rels1, 25))
    # the graft must have covered the document (less its last, partial block)
    graft_ok = grafted >= system["check_doc"] - engine.config.block_size
    ok = (finite and graft_ok and quartile <= limit
          and all(g["n"] - g["over"] >= tol["group_within_share"] * g["n"]
                  for g in groups.values()))
    return {"groups": groups, "logits_finite": finite, "grafted": grafted,
            "graft_ok": graft_ok, "positions": positions,
            "routing_flip_share": flips / positions,
            "logits_rel_l2": quartile,
            "logits_rel_l2_median": float(np.median(rels + rels1)),
            "ok": bool(ok)}


def _gaps(logits, tokens) -> List[float]:
    """Per position: how far the reference's logit of ``tokens[i]`` lies
    under the reference's best, in units of rms(reference logits)."""
    import numpy as np

    rows = np.asarray(logits, np.float32)
    toks = np.asarray(tokens)
    chosen = rows[np.arange(len(toks)), toks]
    return ((rows.max(axis=1) - chosen)
            / np.sqrt(np.mean(rows ** 2, axis=1))).tolist()


def check_served(ctx, system: Dict, turns: List[Dict], job: Dict) -> Dict:
    """A sample of the tokens the WINDOW served against the reference.

    ``turns``: the re-asked turns the window finished, in the order they
    finished, each ``session``, ``document`` (its length), ``prompt``,
    ``produced`` (the served tokens) and ``grafted`` (prompt tokens taken
    from the trie).  Every one of them was produced by the timed path: the
    scheduler's admission and graft, a SplitFuse prefill of the question,
    the fused decode windows at ``max_seqs`` live slots beside contexts of
    every length.  Of the sessions ranked by document length, the turns
    ``SERVED_PICKS`` names are run through the reference teacher-forced
    (``prompt + produced``, padded to a length that depends on the document
    alone, so the reference compiles once per pick and not per seed), and
    for every served token the reference's logit of it is held against the
    reference's best at that position (``_gaps``).

    A token counts as the reference's when the gap is within
    ``decode_gap_rms``; ``served_within_share`` of the sample must be, and
    ``served_turn_within_share`` of every sampled turn (a slot or a page
    table that is wrong is wrong for a whole turn).  The page pool is given
    back first: the reference needs its room, and the engine is not used
    after this."""
    import jax
    import numpy as np

    engine = system["engine"]
    tol = ctx.config["tolerances"]
    by_session: Dict[int, List[Dict]] = {}
    for turn in turns:
        by_session.setdefault(turn["session"], []).append(turn)
    ranked = sorted(by_session, key=lambda s: (by_session[s][0]["document"],
                                               s))
    picks = []
    for quantile, count in SERVED_PICKS:
        if ranked:
            session = ranked[min(int(quantile * len(ranked)),
                                 len(ranked) - 1)]
            picks += by_session[session][-count:]
    if not picks:
        return {"ok": False, "tokens": 0, "why": "no re-asked turn finished"}

    room = job["question_tokens"]["max"] + job["answer_tokens"]["max"]
    chunk = engine.config.max_tokens
    rows, positions = [], []
    for turn in picks:
        seq = list(turn["prompt"]) + list(turn["produced"][:-1])
        padded = -(-(turn["document"] + room) // chunk) * chunk
        rows.append(np.asarray(seq + [0] * (padded - len(seq)), np.int32))
        first = len(turn["prompt"]) - 1
        positions.append(list(range(first, first + len(turn["produced"]))))

    params = engine.params
    engine.kv.pages.delete()
    dev0 = ctx.devices[0]
    ref_model = system["ref_model"]

    def reference(rounded_to=None):
        """One pick at a time (one sequence's streams on the device), the
        layers' weights made by the same few programs for all of them."""
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
                     grafted=turn["grafted"])
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
