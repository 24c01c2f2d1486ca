"""The Nemotron-H (latent experts) serving system under test, built as a user
builds it (``NemotronHLM.from_hf_config`` on the configuration file's
published keys and the share's own, ``InferenceEngineV2`` +
``LifecycleScheduler``, prefix cache off), and checked against
``reference/nemotron_h.py`` on what the timed path produces: before the
window the code paths one sequence at a time (``check_against_reference``),
after it a sample of the turns the window itself served (``check_served``).
The configuration file names this module under ``system``;
``generators/sessions.py`` imports it by that name.

The check's plan, its groups and the served sample are the sibling state
families' (``lib/qwen3next_system``, ``lib/olmohybrid_system``): only the
parameter tree, the reference, the router's making and the limits a group is
held to differ.  The router is
made as training leaves one, by ``lib/xing4_system.balanced_router``'s rule:
seeded column scales (experts of unlike popularity) and an
``e_score_correction_bias`` balanced layer by layer on one seeded
calibration sequence through the reference.
"""
from __future__ import annotations

from typing import Dict

from lib import model as model_lib
from lib import olmohybrid_system as oh     # build, check_served, controls
from lib import qwen3next_system as q3      # the check's plan and its groups
from lib.xing4_system import POPULARITY, _group, balance_bias
from reference.nemotron_h import Reference

REHEARSAL_SERVING = q3.REHEARSAL_SERVING
#: --cpu-rehearsal: toy widths through the same control flow
TOY = dict(vocab_size=512, hidden_size=64, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
           mamba_head_dim=32, n_groups=2, ssm_state_size=16, chunk_size=8,
           n_routed_experts=4, num_experts_per_tok=3,
           moe_intermediate_size=24, moe_latent_size=32,
           moe_shared_expert_intermediate_size=48, intermediate_size=24)

SERVED_PICKS = oh.SERVED_PICKS
CONTROLS = oh.CONTROLS

_MAMBA = {"norm": ("norm", "scale"), "w_in": ("in_proj", "kernel"),
          "conv": ("conv", "kernel"), "conv_b": ("conv", "bias"),
          "A_log": ("A_log",), "dt_bias": ("dt_bias",), "D": ("D",),
          "gnorm": ("gnorm", "scale"), "w_out": ("out_proj", "kernel")}
_ATTN = {"norm": ("norm", "scale"), "w_q": ("q_proj", "kernel"),
         "w_k": ("k_proj", "kernel"), "w_v": ("v_proj", "kernel"),
         "w_o": ("o_proj", "kernel")}
_MOE = {"norm": ("norm", "scale"), "router": ("router", "kernel"),
        "router_b": ("router", "bias"), "l_down": ("latent_down", "kernel"),
        "l_up": ("latent_up", "kernel"), "s_up": ("shared", "up"),
        "s_down": ("shared", "down")}
_NAMES = {"M": _MAMBA, "*": _ATTN, "E": _MOE}


def published(config: Dict, rehearsal: bool) -> Dict:
    """The configuration file's ``config.json`` keys as run."""
    hf = {k: v for k, v in config.items()
          if isinstance(v, (int, float, bool, str)) or v is None}
    if rehearsal:
        hf.update(TOY)
    return hf


def reference_weights(params, rounded_to=None) -> Dict:
    """The program's parameter tree as what ``reference.nemotron_h`` takes,
    in the dtypes the program holds (the reference casts at each use), one
    layer at a time, in the pattern's order (a unit ``ME`` of a stack is two
    layers).  ``rounded_to`` names a format below bfloat16 that every
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

    # ``i`` is traced: every layer of a kind shares one small program
    @jax.jit
    def pick(tree, i):
        return jax.tree.map(lambda x: as_run(
            jax.lax.dynamic_index_in_dim(x, i, keepdims=False)), tree)

    experts = params["experts"]

    def maker(kind, tree, i, e):
        named = {k: leaf(tree, path) for k, path in _NAMES[kind].items()}

        def make():
            w = pick(named, i)
            if kind == "E":
                held = pick({"e_up": experts["up"],
                             "e_down": experts["down"]}, e)
                w = dict(w, **held)
            return w
        return make

    layers, e = [], 0
    for stack in params["stacks"]:
        n = jax.tree.leaves(stack)[0].shape[0]
        for i in range(n):
            for kind in ("M", "*", "E"):      # a unit's order: M before E
                if kind in stack:
                    layers.append(maker(kind, stack[kind], i, e))
                    e += kind == "E"
    outer = {"embedding": params["embed"]["embedding"],
             "norm": params["norm_f"]["scale"],
             "head": params["lm_head"]["kernel"]}
    if rounded_to is not None:
        outer = jax.jit(lambda t: jax.tree.map(as_run, t))(outer)
    return dict(outer, layers=layers)


def balanced_router(params, ref: Reference, seed: int, n_tokens: int):
    """The seeded parameters with a router as training leaves one
    (``lib/xing4_system.balanced_router``'s rule): each expert's column of
    the router scaled by a seeded factor in ``POPULARITY`` and an
    ``e_score_correction_bias`` that evens the loads over ALL the router's
    experts, balanced layer by layer on one seeded calibration sequence of
    ``n_tokens`` through the reference (which adds the held experts'
    parts)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    lo, hi = POPULARITY
    keys = iter(jax.random.split(
        jax.random.PRNGKey((seed + 7) % (2 ** 31)), len(params["stacks"])))

    def scaled(stack, key):
        if "E" not in stack:
            return stack
        router = stack["E"]["router"]
        n, _, E = router["kernel"].shape
        popularity = jax.random.uniform(key, (n, 1, E), jnp.float32, lo, hi)
        router = dict(kernel=router["kernel"] * popularity,
                      bias=jnp.zeros((n, E), jnp.float32))
        return dict(stack, E=dict(stack["E"], router=router))

    stacks = [scaled(stack, next(keys)) for stack in params["stacks"]]
    params = dict(params, stacks=stacks)
    c = ref.config
    row = np.random.default_rng(seed + 98).integers(
        1, c["vocab_size"], size=n_tokens).astype(np.int32)
    balance = jax.jit(lambda s, b: balance_bias(
        s, b, c["num_experts_per_tok"]))
    biases = iter(ref.balanced_router_biases(row, reference_weights(params),
                                             balance))

    def biased(stack):
        if "E" not in stack:
            return stack
        router = stack["E"]["router"]
        n = router["bias"].shape[0]
        router = dict(router, bias=jnp.stack([next(biases)
                                              for _ in range(n)]))
        return dict(stack, E=dict(stack["E"], router=router))

    # a stack of n > 1 expert layers: its biases are consecutive layers'
    return dict(params, stacks=[biased(stack) for stack in stacks])


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
    """Model, parameters (with the balanced router), the check's sequences
    and the reference's logits for them — what is made before the pools take
    the memory."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    # first: a program without this family fails here, at once
    from deepspeed_tpu.models.nemotron_h import NemotronHLM

    serving = dict(ctx.config["serving"])
    if ctx.rehearsal:
        serving.update(REHEARSAL_SERVING)
    dev0 = ctx.devices[0]
    hf = published(ctx.config, ctx.rehearsal)
    model = NemotronHLM.from_hf_config(hf, max_seq_len=serving["max_ctx"])
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
    with ctx.spans.span("bench/setup_router"):
        # as long as the check's first sequence, so that the reference's
        # layers compile once for both
        params = balanced_router(params, Reference(hf), ctx.seed,
                                 len(rows[0]))
        jax.block_until_ready(params)
    plan = q3.check_plan(len(rows[0]), serving["max_tokens"])
    positions = [plan["positions"]] + [
        list(range(len(r) - 1 - tail, len(r)))
        for r, tail in zip(rows[1:], (q3.TAIL, q3.TAIL, q3.REUSED_TAIL))]
    make_reference = _reference_of(hf, params)

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
    the state pool leave, less the reserve) and the scheduler."""
    return oh.build(ctx, system or prepare(ctx))


def check_against_reference(ctx, system: Dict) -> Dict:
    """``lib/qwen3next_system.check_against_reference`` (the same sequences,
    code paths and groups), held to this cell's rule: the LOWER QUARTILE of
    all logit positions within ``logits_rel_l2`` (what separates the
    precisions: int8's quartile lies over it), and in every group (= code
    path) at least ``group_within_share`` of the positions within
    ``position_rel_l2``, a looser limit a position (what catches a wrong
    path: a stale state or a wrong slot reads 0.3-1.4 at EVERY position,
    while the bf16 system's positions 1,000 tokens into a sequence read
    0.05-0.12, over the quartile's limit).  The sibling cells use one limit
    for both; here the two readings lie too close for that (the
    configuration's ``tolerances``)."""
    out = q3.check_against_reference(ctx, system)
    tol = ctx.config["tolerances"]
    groups = {name: g if name == "windows"
              else _group(g["each"], tol["position_rel_l2"])
              for name, g in out["groups"].items()}
    ok = (out["logits_finite"] and out["slot_reused"]
          and out["logits_rel_l2"] <= tol["logits_rel_l2"]
          and all(g["n"] - g["over"] >= tol["group_within_share"] * g["n"]
                  for g in groups.values()))
    return dict(out, groups=groups, ok=bool(ok))


check_served = oh.check_served
