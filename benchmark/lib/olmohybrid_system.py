"""The Olmo-Hybrid serving system under test, built as a user builds it
(``OlmoHybridLM.from_hf_config`` on the configuration file's published keys,
``InferenceEngineV2`` + ``LifecycleScheduler``, prefix cache off), and
checked against ``reference/olmo_hybrid.py`` on what the timed path
produces: before the window the code paths one sequence at a time
(``check_against_reference``: ``lib/qwen3next_system``'s, which reads only
the engine and the reference's logits), after it a sample of the turns the
window itself served (``check_served``).  The configuration file names this
module under ``system``; ``generators/sessions.py`` imports it by that name.

Both pools are large here, so ``build`` sizes them from what the two cache
kinds really STORE: a token's pages at the head count the row kind stores it
in (``family.row.token_shape``), a slot at the bytes the device holds for it
(``state_pool.slot_held_bytes``: tile padding included) — not from the
model's head counts.

The dictionary ``build`` returns has the keys ``lib/serve_system``'s ``warm``
and ``Loop`` read (``engine``, ``scheduler``, ``cfg``), so those are used
unchanged.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List

from lib import model as model_lib
from lib import qwen3next_system as q3     # the check's plan and its groups
from lib.xing4_system import _gaps
from reference.olmo_hybrid import Reference

REHEARSAL_SERVING = q3.REHEARSAL_SERVING
#: --cpu-rehearsal: toy widths through the same control flow (6 heads stored
#: in 8; values 64 wide: a head pair is a whole tile, the stored layout of
#: the published widths)
TOY = dict(vocab_size=512, hidden_size=96, intermediate_size=160,
           num_hidden_layers=4, num_attention_heads=6, num_key_value_heads=6,
           linear_num_key_heads=6, linear_num_value_heads=6,
           linear_key_head_dim=24, linear_value_head_dim=64)

#: the served sample: quantiles of the finished turns ranked by length
SERVED_PICKS = q3.SERVED_PICKS
#: other readings ``check_served`` adds to its own, by name: ``("round",
#: format)`` — the reference with its weights rounded to a format below
#: bfloat16 — or ``("mutation", name)`` — the reference with one line of the
#: equations read the other way.  ``tools/olmohybrid_readings.py`` fills it;
#: a benchmark run leaves it empty.
CONTROLS: Dict = {}

check_against_reference = q3.check_against_reference

_GDN = {"w_qkvg": ("qkvg", "kernel"), "w_ba": ("ba", "kernel"),
        "conv": ("conv", "kernel"), "A_log": ("A_log",),
        "dt_bias": ("dt_bias",), "gnorm": ("gnorm", "scale"),
        "w_o": ("o_proj", "kernel"), "mixer_norm": ("post_norm", "scale")}
_ATTN = {"w_q": ("q_proj", "kernel"), "w_k": ("k_proj", "kernel"),
         "w_v": ("v_proj", "kernel"), "q_norm": ("q_norm", "scale"),
         "k_norm": ("k_norm", "scale"), "w_o": ("o_proj", "kernel"),
         "mixer_norm": ("post_norm", "scale")}
_MLP = {"w_gate": ("gate", "kernel"), "w_up": ("up", "kernel"),
        "w_down": ("down", "kernel"), "mlp_norm": ("post_norm", "scale")}


def published(config: Dict, rehearsal: bool) -> Dict:
    """The configuration file's ``config.json`` keys as run."""
    hf = {k: v for k, v in config.items()
          if k == "rope_parameters"
          or isinstance(v, (int, float, bool, str, list)) or v is None}
    if rehearsal:
        hf.update(TOY)
    return hf


def reference_weights(params, period: int, rounded_to=None) -> Dict:
    """The program's parameter tree as what ``reference.olmo_hybrid`` takes,
    in the dtypes the program holds (the reference casts at each use), one
    layer at a time.  ``rounded_to`` names a format below bfloat16 that every
    bfloat16 matrix is rounded to first, the second reading of a tolerance:
    (exponent bits, mantissa bits) of a float format, or ``"int8"``
    (symmetric, 127 steps to the largest value of each output channel).
    ``reduce_precision`` and not a pair of casts: the TPU's compiler drops a
    cast down and back up (PR 28)."""
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

    periods = params["periods"]
    G = period - 1

    # a stack over periods for each position in the period (``periods["gdn"]
    # [j]``, ``periods["mlp"][j]``); ``p`` is traced, so every layer of a
    # kind shares one small program
    @jax.jit
    def pick(tree, p):
        return jax.tree.map(lambda x: as_run(
            jax.lax.dynamic_index_in_dim(x, p, keepdims=False)), tree)

    def named(tree, names):
        return {k: leaf(tree, path) for k, path in names.items()}

    def maker(p, j):
        def make():
            mixer = named(periods["gdn"][j], _GDN) if j < G \
                else named(periods["attn"], _ATTN)
            return pick(dict(mixer, **named(periods["mlp"][j], _MLP)), p)
        return make

    P = periods["attn"]["post_norm"]["scale"].shape[0]
    outer = {"embedding": params["embed"]["embedding"],
             "norm": params["norm_f"]["scale"],
             "head": params["lm_head"]["kernel"]}
    if rounded_to is not None:
        outer = jax.jit(lambda t: jax.tree.map(as_run, t))(outer)
    return dict(outer, layers=[maker(p, j) for p in range(P)
                               for j in range(period)])


def _reference_of(hf: Dict, params, period: int):
    """``reference(rows, positions, control=None)``: the reference's logits,
    or a control's (``CONTROLS``' values)."""
    import jax
    import numpy as np

    plain = Reference(hf)

    def reference(rows, positions, control=None):
        kind, what = control or (None, None)
        ref_model = Reference(hf, what) if kind == "mutation" else plain
        weights = reference_weights(params, period,
                                    what if kind == "round" else None)
        return [np.asarray(r, np.float32) for r in jax.block_until_ready(
            ref_model.logits(rows, weights, positions=positions))]

    return reference


def prepare(ctx) -> Dict:
    """Model, parameters, the check's sequences and the reference's logits
    for them — what is made before the pools take the memory."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    # first: a program without this family fails here, at once
    from deepspeed_tpu.models.olmo_hybrid import OlmoHybridLM

    serving = dict(ctx.config["serving"])
    if ctx.rehearsal:
        serving.update(REHEARSAL_SERVING)
    dev0 = ctx.devices[0]
    hf = published(ctx.config, ctx.rehearsal)
    model = OlmoHybridLM.from_hf_config(hf, max_seq_len=serving["max_ctx"])
    cfg = model.config
    with ctx.spans.span("bench/setup_params"):
        params = model_lib.init_params(model, ctx.seed + 1, jnp.bfloat16)
        jax.block_until_ready(params)
    scale = 1 if not ctx.rehearsal else 8
    n_prompt = min(q3.CHECK_PROMPT // scale, serving["max_ctx"] * 3 // 4)
    rng = np.random.default_rng(ctx.seed + 99)
    draw = lambda n: rng.integers(  # noqa: E731
        1, cfg.vocab_size, size=n).astype(np.int32)
    rows = [draw(n_prompt + q3.SINGLES + q3.WINDOWS)] \
        + [draw(max(n // scale, 3) + q3.TAIL) for n in q3.MIXED] \
        + [draw(max(q3.REUSED // scale, 3) + q3.REUSED_TAIL)]
    plan = q3.check_plan(len(rows[0]), serving["max_tokens"])
    positions = [plan["positions"]] + [
        list(range(len(r) - 1 - tail, len(r)))
        for r, tail in zip(rows[1:], (q3.TAIL, q3.TAIL, q3.REUSED_TAIL))]
    make_reference = _reference_of(hf, params, cfg.period)

    def reference(control=None):
        return make_reference([jax.device_put(r, dev0) for r in rows],
                              positions, control)

    with ctx.spans.span("bench/setup_reference"):
        ref = reference()
    return {"cfg": cfg, "model": model, "params": params,
            "param_bytes": int(sum(x.nbytes
                                   for x in jax.tree.leaves(params))),
            "check_rows": rows, "check_plan": plan, "ref": ref,
            "reference": reference, "make_reference": make_reference,
            "serving": serving}


def build(ctx, system: Dict = None) -> Dict:
    """``prepare``, then the engine (its state pool: ``max_seqs`` slots at
    what the device holds for one; its page pool: what the parameters and
    the state pool leave, less the reserve, at the stored row) and the
    scheduler."""
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.lifecycle import LifecycleScheduler
    from deepspeed_tpu.inference.v2.ragged.state_pool import slot_held_bytes

    system = system or prepare(ctx)
    serving = dict(system["serving"])
    reserve = serving.pop("kv_reserve_bytes")
    max_queue = serving.pop("max_queue")
    dev0 = ctx.devices[0]
    bs = serving["block_size"]
    family = system["model"].serving_family()
    block_bytes = family.page_layers * bs * math.prod(
        family.row.token_shape) * 2
    slot_bytes = slot_held_bytes(family.state, jnp.bfloat16)
    state_bytes = (serving["max_seqs"] + 1) * slot_bytes
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


def check_served(ctx, system: Dict, turns: List[Dict], job: Dict) -> Dict:
    """A sample of the turns the WINDOW served against the reference.

    ``turns``: the re-asked turns the window finished, in the order they
    finished, each ``session``, ``prompt`` and ``produced`` (the served
    tokens).  Every one of them was produced by the timed path: admission
    and a slot from the scheduler, the SplitFuse prefill of the whole turn
    beside other sequences' chunks, the fused decode windows at ``max_seqs``
    live slots.  Of the turns ranked by length, those at ``SERVED_PICKS``
    are run through the reference teacher-forced (``prompt + produced``,
    padded to one length so that the reference compiles once and makes each
    layer's float32 weights once for the three), and for every
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
    dev0 = ctx.devices[0]
    rows, positions = [], []
    for turn in picks:
        seq = list(turn["prompt"]) + list(turn["produced"][:-1])
        rows.append(jax.device_put(
            np.asarray(seq + [0] * (padded - len(seq)), np.int32), dev0))
        first = len(turn["prompt"]) - 1
        positions.append(list(range(first, first + len(turn["produced"]))))

    engine.kv.pages.delete()
    for array in engine.state_pool.arrays:
        array.delete()
    reference = system["make_reference"]

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

    full = reference(rows, positions)
    out = reading([_gaps(r, t["produced"]) for r, t in zip(full, picks)])
    for entry, turn in zip(out["turns"], picks):
        entry.update(session=turn["session"], prompt=len(turn["prompt"]))
    # a control in place of the system: its own greedy tokens, same positions
    for name, control in CONTROLS.items():
        try:
            out.setdefault("controls", {})[name] = reading(
                [_gaps(f, np.argmax(l, axis=1)) for f, l in zip(
                    full, reference(rows, positions, control))])
        except Exception as exc:        # a control is the tool's, not the run's
            out["controls"][name] = {"error": repr(exc)[-300:]}
    out["ok"] = bool(
        out["within_share"] >= tol["served_within_share"]
        and out["turn_within_share_min"] >= tol["served_turn_within_share"])
    return out
