"""The JoyAI-LLM-Flash training cell's system: from the configuration file's
published keys to ``deepspeed_tpu.models.joyai_flash.JoyAIFlashLM`` under
``deepspeed_tpu.initialize()``, from the engine's parameter tree to the
plain reference's weights (``reference/joyai_flash.py``), and the comparison
that decides ``correct``.  ``generators/train_system.py`` calls ``build``,
``before_first_step`` and ``after_first_step``; it knows no model.

The comparison, at the timed sizes, on the bf16-rounded initial parameters:

(a) the engine's step-0 loss from ``train_batch`` and each of its two terms
    (the state the engine carries holds them) against the reference's;
(b) row 0's logits of BOTH heads from the program's default path (kernels
    on, bf16) against the reference's, per position, rel-L2: the median
    within ``logits_rel_l2``, the share of positions over it within
    ``routing_flip_share``;
(c) gradients of a named few leaves from the program's ``loss_fn`` (what the
    engine differentiates) against the reference's ``jax.grad`` with respect
    to those leaves only: rel-L2 within ``grads_rel_l2``, and within
    ``routed_grads_rel_l2`` for the two that only routed pairs reach (the
    router, one held expert);
(d) after step 0 the engine's selection bias against the reference's rule on
    the reference's loads: an entry's sign may differ only where the
    reference's load lies within the flipped pairs of the mean;
(e) pairs computed == pairs routed to the experts held, none dropped.
"""
from __future__ import annotations

import os
import types
from typing import Dict, List, Tuple

from lib import manifest
from lib import model as model_lib
from reference.joyai_flash import Reference

#: --cpu-rehearsal: toy widths through the same control flow
TOY = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
           moe_intermediate_size=32, num_attention_heads=4, q_lora_rank=48,
           kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, qk_head_dim=24, head_dim=8, num_hidden_layers=3,
           n_routed_experts=4, num_experts_per_tok=4)
TOY_ROUTER_OUTPUTS = 16

#: name -> dtype: readings of the REFERENCE with its weights rounded to a
#: lower precision, held to the float32 reference as the system is
#: (``tools/joyai_readings.py`` fills it; a benchmark run leaves it empty)
CONTROLS: Dict[str, object] = {}

_ATTN = {"attn_norm": ("attn_norm", "scale"), "q_a": ("q_a_proj", "kernel"),
         "q_a_norm": ("q_a_norm", "scale"), "q_b": ("q_b_proj", "kernel"),
         "kv_a": ("kv_a_proj", "kernel"), "kv_a_norm": ("kv_a_norm", "scale"),
         "kv_b": ("kv_b_proj", "kernel"), "o": ("o_proj", "kernel"),
         "mlp_norm": ("mlp_norm", "scale")}
_DENSE = {"w_gate": ("gate_proj", "kernel"), "w_up": ("up_proj", "kernel"),
          "w_down": ("down_proj", "kernel")}


def require() -> None:
    """A checkout whose program cannot train this family (the parent of the
    PR that added it) fails cleanly, before the chip is touched."""
    path = os.path.join(manifest.ROOT, "deepspeed_tpu", "models",
                        "joyai_flash.py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: this program has no {path}: it cannot "
                         f"train JoyAI-LLM-Flash")


def sizes_of(config: Dict, rehearsal: bool) -> Dict:
    """The configuration's numbers as run, the share's among them:
    ``router_outputs`` (the published expert count) beside
    ``n_routed_experts`` (the experts held) and ``ep_rank``."""
    sizes = {k: v for k, v in config.items()
             if isinstance(v, (int, float, bool, str)) or v is None}
    if rehearsal:
        sizes.update(TOY)
        sizes["router_outputs"] = TOY_ROUTER_OUTPUTS
    sizes["expert_offset"] = sizes["ep_rank"] * sizes["n_routed_experts"]
    return sizes


def model_of(sizes: Dict, seq_len: int, **options):
    from deepspeed_tpu.models.joyai_flash import JoyAIFlashLM

    hf = dict(sizes, n_routed_experts=sizes["router_outputs"])
    return JoyAIFlashLM.from_hf_config(
        hf, experts_held=sizes["n_routed_experts"],
        expert_offset=sizes["expert_offset"],
        mtp_loss_weight=sizes["mtp_loss_weight"],
        bias_update_rate=sizes["bias_update_rate"],
        max_seq_len=seq_len, **options)


def build(ctx, job: Dict) -> types.SimpleNamespace:
    import numpy as np

    import deepspeed_tpu
    import jax.numpy as jnp
    from deepspeed_tpu.runtime.dataloader import RepeatingLoader
    from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

    sizes = sizes_of(ctx.config, ctx.rehearsal)
    devices = ctx.devices
    topo = initialize_mesh(TopologyConfig(), devices=list(devices),
                           force=True)
    model = model_of(sizes, job["seq_len"],
                     **ctx.traffic.get("model_options", {}))
    params = model_lib.init_params(model, ctx.seed, jnp.float32)
    global_batch = job["micro_batch_per_chip"] * len(devices)
    rng = np.random.default_rng(ctx.seed)
    n_rows = global_batch * ctx.traffic["distinct_batches"]
    dataset = [{"input_ids": rng.integers(
        0, sizes["vocab_size"], size=job["seq_len"]).astype(np.int32)}
        for _ in range(n_rows)]
    ds_config = dict(ctx.traffic["ds_config"])
    ds_config["train_micro_batch_size_per_gpu"] = job["micro_batch_per_chip"]
    engine, _, loader, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, training_data=dataset,
        config=ds_config, topology=topo, seed=ctx.seed % (2 ** 31))
    del params                      # the engine owns the placed fp32 master
    return types.SimpleNamespace(engine=engine, model=model, sizes=sizes,
                 batches=iter(RepeatingLoader(loader)),
                 global_batch=global_batch, params=model.num_params())


# --------------------------------------------------------------------- #
# The program's tree as the reference's weights
# --------------------------------------------------------------------- #
def reference_weights(params, dtype=None) -> Dict:
    """The program's parameter tree as ``reference/joyai_flash.py`` takes it.
    ``dtype`` given: every value rounded to it and kept in it (the reference
    casts to float32 at each use, so bf16 storage holds the rounded values
    exactly at half the bytes)."""
    import jax

    def cast(x):
        return x if dtype is None else x.astype(dtype)

    def layer(stack, i, moe):
        w = {k: cast(stack[a][b][i]) for k, (a, b) in _ATTN.items()}
        if not moe:
            w.update({k: cast(stack[a][b][i]) for k, (a, b) in _DENSE.items()})
            return w
        w["router"] = cast(stack["router"]["kernel"][i])
        w["experts"] = {k: cast(v[i]) for k, v in stack["experts"].items()}
        w["shared"] = {k: cast(v[i]) for k, v in stack["shared"].items()}
        return w

    def depth(stack):
        return stack["attn_norm"]["scale"].shape[0]

    def convert(p):
        layers = []
        for name, moe in (("dense_layers", False), ("moe_layers", True)):
            if name in p:
                layers += [layer(p[name], i, moe)
                           for i in range(depth(p[name]))]
        out = {"embedding": cast(p["embed"]["embedding"]),
               "norm": cast(p["norm_f"]["scale"]),
               "head": cast(p["lm_head"]["kernel"]), "layers": layers}
        if "mtp" in p:
            m = p["mtp"]
            out["mtp"] = {"enorm": cast(m["enorm"]["scale"]),
                          "hnorm": cast(m["hnorm"]["scale"]),
                          "eh_proj": cast(m["eh_proj"]["kernel"]),
                          "layer": layer(m["layers"], 0, True),
                          "norm": cast(m["norm"]["scale"])}
        return out

    return jax.jit(convert)(params)


def checked_leaves(cfg) -> List[Tuple[Tuple, Tuple, Tuple, Tuple]]:
    """(name in the program's tree, index into that leaf, path in the
    reference's weights, index into that) of the few leaves whose gradients
    are compared: one
    expert layer's ``W_kva`` and ``W_qb``, its router, one held expert's
    down projection, ``W_eh`` and the head."""
    i = min(1, cfg.num_moe_layers - 1)          # the stack's second layer
    at = cfg.num_dense_layers + i               # its place in the model
    e = min(3, cfg.held - 1)
    return [
        (("moe_layers", "kv_a_proj", "kernel"), (i,),
         ("layers", at, "kv_a"), ()),
        (("moe_layers", "q_b_proj", "kernel"), (i,),
         ("layers", at, "q_b"), ()),
        (("moe_layers", "router", "kernel"), (i,),
         ("layers", at, "router"), ()),
        (("moe_layers", "experts", "down"), (i, e),
         ("layers", at, "experts", "down"), (e,)),
        (("mtp", "eh_proj", "kernel"), (), ("mtp", "eh_proj"), ()),
        (("lm_head", "kernel"), (), ("head",), ()),
    ]


def _get(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _put(tree, path, leaf):
    if not path:
        return leaf
    return {k: _put(v, path[1:], leaf) if k == path[0] else v
            for k, v in tree.items()}


def program_grads(model, params, batch, model_state, leaves):
    """Gradients of the program's ``loss_fn`` (the engine's recipe: float32
    masters cast to bf16, then the loss) with respect to the whole leaves
    named, every other parameter a constant."""
    import jax
    import jax.numpy as jnp

    names = sorted({leaf[0] for leaf in leaves})

    def loss_of(chosen, rest, batch, model_state):
        p = rest
        for name, leaf in zip(names, chosen):
            p = _put(p, name, leaf)
        p = jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)
        return model.loss_fn(p, batch, None, model_state)[0]

    # the batch is an ARGUMENT: closed over, its token ids would be constants
    # of the program and every seed would compile it again (38 s on the v5e)
    got = jax.jit(jax.grad(loss_of))([_get(params, n) for n in names], params,
                                     batch, model_state)
    return dict(zip(names, got))


# --------------------------------------------------------------------- #
# The comparison
# --------------------------------------------------------------------- #
def before_first_step(ctx, built, batch) -> Dict:
    """Everything that needs the INITIAL parameters: the reference's loss
    terms, loads and gradients over the whole first batch and its logits of
    row 0, then the program's logits of row 0 and its gradients."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.joyai_flash import logits as program_logits

    engine, model, sizes = built.engine, built.model, built.sizes
    cfg = model.config
    tol = ctx.config["tolerances"]
    dev0 = ctx.devices[0]
    tokens = batch["input_ids"]
    rows = [jax.device_put(row, dev0) for row in np.asarray(tokens)]
    bias0 = jnp.zeros((cfg.num_expert_layers, cfg.n_routed_experts),
                      jnp.float32)
    leaves = checked_leaves(cfg)

    # --- the reference, beside the engine's placed state ---------------
    ref = Reference(sizes, head_groups=1 if ctx.rehearsal else 4, remat=True)
    terms = jax.jit(lambda w, t: ref.loss_terms(w, t, bias0))
    paths = [leaf[2] for leaf in leaves]

    def reference_of(weights):
        """(main terms, mtp terms, loads, row 0's logits of both heads,
        gradients of the named leaves) over the whole batch."""
        mains, mtps, loads, logits = [], [], 0, None
        for i, row in enumerate(rows):
            main, mtp, out = terms(weights, row)
            mains.append(float(main))
            mtps.append(float(mtp))
            loads = loads + np.asarray(out["loads"], np.float64)
            if i == 0:
                logits = [np.asarray(out[k], np.float32)
                          for k in ("main_logits", "mtp_logits")]
            del out
        grads = ref.grads(weights, rows, bias0, paths)
        return mains, mtps, loads, logits, \
            {p: np.asarray(g, np.float32) for p, g in grads.items()}

    weights = jax.device_put(
        reference_weights(engine.state.params, jnp.bfloat16), dev0)
    mains, mtps, loads, ref_logits, ref_grads = reference_of(weights)
    del weights
    controls = {}
    for name, dtype in CONTROLS.items():
        low = jax.device_put(jax.tree.map(
            lambda x: x.astype(dtype).astype(jnp.bfloat16),
            reference_weights(engine.state.params, jnp.bfloat16)), dev0)
        c_main, c_mtp, _, c_logits, c_grads = reference_of(low)
        del low
        got = {"main_loss_abs_diff": abs(np.mean(c_main) - np.mean(mains)),
               "mtp_loss_abs_diff": abs(np.mean(c_mtp) - np.mean(mtps)),
               "grads_rel_l2": {".".join(map(str, p)): model_lib.rel_l2(
                   c_grads[p], ref_grads[p]) for p in paths}}
        for head, g, r in zip(("main", "mtp"), c_logits, ref_logits):
            got[head] = model_lib.logits_agreement(g, r, tol["logits_rel_l2"])
        controls[name] = got

    checks = {"reference_main_loss": float(np.mean(mains)),
              "reference_mtp_loss": float(np.mean(mtps))}
    checks["reference_loss"] = checks["reference_main_loss"] \
        + sizes["mtp_loss_weight"] * checks["reference_mtp_loss"]
    if controls:
        checks["controls"] = controls

    # --- (b) both heads' logits of row 0, the program's default path ----
    got = jax.jit(lambda p, t: program_logits(
        jax.tree.map(lambda x: x.astype(jnp.bfloat16), p), t, cfg))(
        engine.state.params, tokens[:len(ctx.devices)])
    for name, g, r in zip(("main", "mtp"), got, ref_logits):
        g = np.asarray(g[0], np.float32)
        if name == "mtp":       # the last position's input is id 0: no target
            g, r = g[:-1], r[:-1]
        for k, v in model_lib.logits_agreement(
                g, r, tol["logits_rel_l2"]).items():
            checks[f"{name}_{k}"] = v
    del got, ref_logits

    # --- (c) gradients of the named leaves ------------------------------
    grads = program_grads(model, engine.state.params, batch,
                          engine.state.model_state, leaves)
    # a leaf that only ROUTED pairs reach (a router, one held expert) moves
    # with every 8th-against-9th tie that falls the other way; the others
    # see all 8,192 tokens: a limit each
    worst = {"grads_rel_l2_max": 0.0, "routed_grads_rel_l2_max": 0.0}
    for name, index, path, ref_index in leaves:
        rel = model_lib.rel_l2(np.asarray(grads[name][index], np.float32),
                               ref_grads[path][ref_index])
        checks["grad_rel_l2." + ".".join(map(str, path + ref_index))] = rel
        kind = "routed_grads_rel_l2_max" if {"router", "experts"} & set(path) \
            else "grads_rel_l2_max"
        worst[kind] = max(worst[kind],
                          rel if np.isfinite(rel) else float("inf"))
    checks.update(worst)
    del grads
    built.reference_loads = loads
    return checks


def after_first_step(ctx, built, checks: Dict, loss0: float) -> bool:
    """(a), (d), (e) from the state the engine carries after step 0, and
    the verdict."""
    import jax
    import numpy as np

    sizes, cfg = built.sizes, built.model.config
    tol = ctx.config["tolerances"]
    state = jax.device_get(built.engine.state.model_state)
    checks["step0_loss"] = loss0
    checks["step0_main_loss"] = float(state["main_loss"])
    checks["step0_mtp_loss"] = float(state["mtp_loss"])
    checks["loss_abs_diff"] = abs(loss0 - checks["reference_loss"])
    checks["main_loss_abs_diff"] = abs(checks["step0_main_loss"]
                                       - checks["reference_main_loss"])
    checks["mtp_loss_abs_diff"] = abs(checks["step0_mtp_loss"]
                                      - checks["reference_mtp_loss"])

    # (d) the bias the step left against the reference's rule
    ref = Reference(sizes)
    ref_loads = built.reference_loads
    want = np.asarray(ref.next_bias(np.zeros_like(ref_loads), ref_loads))
    got = np.asarray(state["router_bias"], np.float64)
    routed = np.asarray(state["pairs_routed"], np.float64)
    flipped = np.abs(routed - ref_loads).sum(axis=-1, keepdims=True) / 2.0
    near = np.abs(ref_loads - ref_loads.mean(axis=-1, keepdims=True)) \
        <= flipped
    differs = np.sign(got) != np.sign(want)
    checks["bias_entries"] = int(got.size)
    checks["bias_sign_differs"] = int(differs.sum())
    checks["bias_sign_unexplained"] = int((differs & ~near).sum())
    checks["bias_step_abs_max"] = float(np.abs(got).max())
    checks["pairs_flipped_share"] = float(flipped.sum() / routed.sum())

    # (e) nothing dropped: what the grouped matmuls computed is what the
    # router sent to the experts held
    lo = sizes["expert_offset"]
    computed = np.asarray(state["pairs_computed"])
    sent = np.asarray(state["pairs_routed"])[:, lo:lo + cfg.held]
    checks["pairs_computed"] = int(computed.sum())
    checks["pairs_routed_to_held"] = int(sent.sum())
    checks["pairs_held_share"] = float(computed.sum() / routed.sum())

    flips = tol.get("routing_flip_share", 0.0)
    return bool(
        checks["loss_abs_diff"] <= tol["loss_abs"]
        and checks["main_loss_abs_diff"] <= tol["loss_abs"]
        and checks["mtp_loss_abs_diff"] <= tol["loss_abs"]
        and all(checks[f"{h}_logits_finite"]
                and checks[f"{h}_logits_rel_l2_median"] <= tol["logits_rel_l2"]
                and checks[f"{h}_logits_share_over_tol"] <= flips
                for h in ("main", "mtp"))
        and checks["grads_rel_l2_max"] <= tol["grads_rel_l2"]
        and checks["routed_grads_rel_l2_max"] <= tol["routed_grads_rel_l2"]
        and checks["bias_sign_unexplained"] == 0
        and abs(checks["bias_step_abs_max"] - sizes["bias_update_rate"])
        <= 1e-6
        and bool((computed == sent).all()))
