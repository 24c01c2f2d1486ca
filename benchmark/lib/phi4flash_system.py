"""The Phi-4-mini-flash-reasoning serving system under test, built as a user
builds it (``Phi4FlashLM.from_hf_config`` on the configuration file's
published keys, ``InferenceEngineV2`` + ``LifecycleScheduler``, prefix cache
off), and checked against ``reference/phi4_flash.py`` on what the timed path
produces: before the window the code paths one sequence at a time
(``check_against_reference``: ``lib/qwen3next_system``'s five groups, which
read only the engine and the reference's logits, and one more here — single
tokens and one-step fused windows ACROSS the window boundary, which is the
ring's first wrap, and across its second), after it a sample of the turns
the window itself served (``lib/olmohybrid_system.check_served``).  The
configuration file names this module under ``system``;
``generators/sessions.py`` imports it by that name.

The pools: the state pool (a selective-scan state in 9 layers and a ring of
512 rows in 8 window layers a slot) is sized by ``max_seqs`` alone, at what
the device holds for a slot (``state_pool.slot_held_bytes``); the page pool
is ONE layer's and takes what is left, up to a whole ``max_ctx`` a sequence.
"""
from __future__ import annotations

import math
import time
from typing import Dict

from lib import model as model_lib
from lib import olmohybrid_system as oh     # check_served, the controls
from lib import qwen3next_system as q3      # the check's plan and its groups
from lib.xing4_system import _group
from reference.phi4_flash import Reference

REHEARSAL_SERVING = q3.REHEARSAL_SERVING
#: --cpu-rehearsal: toy widths through the same control flow (window 16: the
#: rehearsal's sequences still cross it)
TOY = dict(vocab_size=512, hidden_size=64, intermediate_size=96,
           num_hidden_layers=8, num_attention_heads=8, num_key_value_heads=4,
           sliding_window=16)
TOY_RING_PAGE = 8

SERVED_PICKS = oh.SERVED_PICKS
CONTROLS = oh.CONTROLS

#: the boundary group: a sequence prefilled up to ``k * window - BEFORE``,
#: then ``SINGLES`` tokens fed singly and ``WINDOWS`` one-step fused windows
#: that cross position ``k * window`` (``k`` = 1: the first token whose
#: window no longer holds position 0 = the ring's first wrap; 2: the second)
BEFORE, SINGLES, WINDOWS = 6, 12, 6
WRAPS = (1, 2)


def published(config: Dict, rehearsal: bool) -> Dict:
    """The configuration file's ``config.json`` keys as run."""
    hf = {k: v for k, v in config.items()
          if isinstance(v, (int, float, bool, str)) or v is None}
    if rehearsal:
        hf.update(TOY)
    return hf


def reference_weights(params, rounded_to=None) -> Dict:
    """The program's parameter tree as what ``reference.phi4_flash`` takes,
    in the dtypes the program holds (the reference casts at each use), one
    layer at a time.  ``rounded_to`` names a format below bfloat16 that
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

    def mlp_of(mlp):
        return {"ln2_w": mlp["ln"]["scale"], "ln2_b": mlp["ln"]["bias"],
                "w1": mlp["w1"]["kernel"], "w2": mlp["w2"]["kernel"]}

    def scan(p):
        return {"ln1_w": p["ln"]["scale"], "ln1_b": p["ln"]["bias"],
                "w_in": p["in_proj"]["kernel"], "conv": p["conv"]["kernel"],
                "conv_b": p["conv"]["bias"], "w_x": p["x_proj"]["kernel"],
                "w_dt": p["dt_proj"]["kernel"], "b_dt": p["dt_proj"]["bias"],
                # the program stores A_log channels-minor, as the state
                "A_log": jnp.swapaxes(p["A_log"], -1, -2), "D": p["D"],
                "w_out": p["out_proj"]["kernel"]}

    def attn(p):
        return {"ln1_w": p["ln"]["scale"], "ln1_b": p["ln"]["bias"],
                "w_qkv": p["wqkv"]["kernel"], "b_qkv": p["wqkv"]["bias"],
                "lam": p["lam"], "subln": p["subln"],
                "w_o": p["wo"]["kernel"], "b_o": p["wo"]["bias"]}

    def memory(p):
        return {"ln1_w": p["ln"]["scale"], "ln1_b": p["ln"]["bias"],
                "w_g": p["in_proj"]["kernel"], "w_o": p["out_proj"]["kernel"]}

    # ``i`` is traced: every layer of a kind shares one small program
    @jax.jit
    def pick(tree, i):
        return jax.tree.map(lambda x: as_run(
            jax.lax.dynamic_index_in_dim(x, i, keepdims=False)), tree)

    def maker(named, mlp, i):
        return lambda: pick(dict(named, **mlp_of(mlp)), i)

    layers = []
    for name, first in (("self", scan), ("mid", scan), ("cross", memory)):
        stack = params[name]
        for i in range(stack["first"]["ln"]["scale"].shape[0]):
            layers += [maker(first(stack["first"]), stack["mlp"][0], i),
                       maker(attn(stack["second"]), stack["mlp"][1], i)]
    outer = {"embedding": params["embed"]["embedding"],
             "norm_w": params["norm_f"]["scale"],
             "norm_b": params["norm_f"]["bias"]}
    if rounded_to is not None:
        outer = jax.jit(lambda t: jax.tree.map(as_run, t))(outer)
    return dict(outer, layers=layers)


def _reference_of(hf: Dict, params):
    """``reference(rows, positions, control=None)``: the reference's logits,
    or a control's (``CONTROLS``' values)."""
    import jax
    import numpy as np

    plain = Reference(hf)

    def reference(rows, positions, control=None):
        kind, what = control or (None, None)
        ref_model = Reference(hf, what) if kind == "mutation" else plain
        weights = reference_weights(params,
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
    from deepspeed_tpu.models.phi4_flash import Phi4FlashLM

    serving = dict(ctx.config["serving"])
    if ctx.rehearsal:
        serving.update(REHEARSAL_SERVING)
    dev0 = ctx.devices[0]
    hf = published(ctx.config, ctx.rehearsal)
    model = Phi4FlashLM.from_hf_config(
        hf, max_seq_len=serving["max_ctx"],
        **(dict(ring_page=TOY_RING_PAGE) if ctx.rehearsal else {}))
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
    # the boundary group's sequences, one a wrap of the ring
    starts = [k * cfg.sliding_window - BEFORE for k in WRAPS]
    wraps = [draw(n0 + SINGLES + WINDOWS) for n0 in starts]
    wrap_positions = [list(range(n0 - 1, n0 + SINGLES + WINDOWS))
                      for n0 in starts]
    make_reference = _reference_of(hf, params)

    def reference(control=None):
        return make_reference(
            [jax.device_put(r, dev0) for r in rows + wraps],
            positions + wrap_positions, control)

    with ctx.spans.span("bench/setup_reference"):
        ref = reference()
    return {"cfg": cfg, "model": model, "params": params,
            "param_bytes": int(sum(x.nbytes
                                   for x in jax.tree.leaves(params))),
            "check_rows": rows, "check_plan": plan, "ref": ref[:len(rows)],
            "wrap_rows": wraps, "wrap_starts": starts,
            "wrap_ref": ref[len(rows):],
            "reference": reference, "make_reference": make_reference,
            "serving": serving}


def build(ctx, system: Dict = None) -> Dict:
    """``prepare``, then the engine (its state pool: ``max_seqs`` slots at
    what the device holds for one — scan state, carry and eight rings; its
    page pool: what the parameters and the state pool leave, less the
    reserve, at the stored row, ONE page layer) and the scheduler."""
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
    slot_bytes = sum(slot_held_bytes(kind, jnp.bfloat16)
                     for kind in family.slot_kinds)
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
    # every request the scheduler is handed, for ``check_served``
    submitted, submit = [], scheduler.submit

    def keeping(request):
        submitted.append(request)
        return submit(request)

    scheduler.submit = keeping
    system.pop("reference")
    system.update(engine=engine, scheduler=scheduler, num_blocks=num_blocks,
                  submitted=submitted,
                  block_bytes=block_bytes, state_bytes=state_bytes,
                  serving=serving)
    return system


def check_served(ctx, system: Dict, turns, job: Dict) -> Dict:
    """``olmohybrid_system.check_served`` on every turn the window
    FINISHED, the sessions' first turns among them.  An answer here is
    1,024-2,560 tokens at ~27 ms a token: a session finishes about one turn
    in 51 s and a re-asked turn none, so the generator's own list (re-asked
    turns only) is empty.  A first turn is the timed path's too — a slot and
    pages from the scheduler, its context prefilled by SplitFuse beside the
    other sessions' chunks (in set-up), every token of its answer out of the
    window's 64-wide fused windows — so the sample is drawn from what the
    scheduler was handed (``build`` keeps it) and finished; the warm-up's
    and the set-up check's requests have uids of their own and stay out."""
    finished = [r for r in system["submitted"]
                if r.uid < q3.CHECK_UID and r.state.name == "FINISHED"
                and len(r.produced) == r.max_new_tokens]
    return oh.check_served(ctx, system, [
        {"session": r.uid, "prompt": r.prompt, "produced": r.produced}
        for r in finished], job)


def check_against_reference(ctx, system: Dict) -> Dict:
    """``qwen3next_system``'s five groups (chunked prefill of a prompt of
    several chunks — each later chunk reads the ring as the one before left
    it; two sequences prefilled together while the first one's state waits;
    single tokens; one-step fused windows; a fresh sequence in a reused slot
    and ring), then the ``boundary`` group: for each of the ring's first two
    wraps a sequence prefilled to six tokens before it, twelve tokens fed
    singly across it (logits) and six one-step fused windows behind them
    (the greedy token's gap), through slot, ring and pages."""
    import numpy as np

    out = q3.check_against_reference(ctx, system)
    engine = system["engine"]
    tol = ctx.config["tolerances"]
    chunk = engine.config.max_tokens
    rels, gaps, finite = [], [], True
    for k, (row, n0, ref) in enumerate(zip(
            system["wrap_rows"], system["wrap_starts"], system["wrap_ref"])):
        uid, row = q3.CHECK_UID + 10 + k, row.tolist()
        for pos in range(0, n0, chunk):
            logits = engine.put([uid], [row[pos:min(pos + chunk, n0)]])
        got = [np.asarray(logits[0], np.float32)]
        for tok in row[n0:n0 + SINGLES]:
            got.append(np.asarray(engine.put([uid], [[tok]])[0], np.float32))
        rels += [model_lib.rel_l2(g, r) for g, r in zip(got, ref)]
        finite = finite and all(bool(np.isfinite(g).all()) for g in got)
        for i, tok in enumerate(row[n0 + SINGLES:]):
            tok_out = int(engine.decode_batch([uid], [tok], 1)[0, 0])
            at = ref[1 + SINGLES + i]
            gaps.append(float(at.max() - at[tok_out])
                        / float(np.sqrt(np.mean(at ** 2))))
        engine.flush([uid])
    groups = {"boundary": _group(rels, tol["logits_rel_l2"]),
              "boundary_windows": _group(gaps, tol["decode_gap_rms"])}
    ok = finite and all(
        g["n"] - g["over"] >= tol["group_within_share"] * g["n"]
        for g in groups.values())
    out["groups"].update(groups)
    out["positions"] += sum(g["n"] for g in groups.values())
    out["logits_finite"] = bool(out["logits_finite"] and finite)
    out["ok"] = bool(out["ok"] and ok)
    return out
