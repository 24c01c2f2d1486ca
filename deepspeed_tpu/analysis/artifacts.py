"""Build the *actual* production programs and hand their jaxprs to the
graph passes — the ``bin/dstpu-check`` sweep and the
``tools/check_graph_lint.py`` CI gate both run here.

"Actual" means the same builders the engines use, at tiny CPU-sim shapes:
the fused train step (``engine._build_train_batch_fn``), the PR-4
prefetched per-micro program (``comm_path.build_explicit_micro_fn``
— linted with ``gather_budget=0``, the GatherWindowCache invariant), the
serving prefill/decode/verify bucket programs
(``model_runner.build_ragged_step``/``build_decode_loop``/
``build_verify_step`` at the engine's real bucket shapes, both attention
impls), and the fused quantized collective wire
(``comm_path.quantized_allreduce`` under ``shard_map``).  Everything is
``jax.make_jaxpr`` only — no XLA compile — so the full sweep stays well
inside the 120 s gate budget.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

from .core import Finding, PassContext, run_graph_passes


@dataclasses.dataclass
class Artifact:
    name: str
    traced: object            # jax.make_jaxpr result
    ctx: PassContext


def _struct_of(tree):
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


# --------------------------------------------------------------------- #
# Serving engine buckets
# --------------------------------------------------------------------- #
def build_inference_artifacts(attn_impl: str = "gather",
                              ) -> List[Artifact]:
    """Prefill / fused-decode / spec-dec-verify programs of a tiny
    ``InferenceEngineV2`` at its real bucket shapes.  ``gather`` is the
    XLA lowering (the numerics oracle — fully analyzable); ``paged``
    additionally walks the Pallas kernel body."""
    import jax
    import jax.numpy as jnp

    from ..inference.v2.engine_v2 import (InferenceEngineV2,
                                          RaggedInferenceEngineConfig)
    from ..inference.v2.model_runner import (build_decode_loop,
                                             build_ragged_step,
                                             build_verify_step)
    from ..models.transformer import CausalLM, TransformerConfig

    cfg = TransformerConfig.tiny(use_flash=False)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
        max_tokens=16, max_seqs=4, max_ctx=64, block_size=8,
        dtype=jnp.float32, attn_impl=attn_impl, block_q=16,
        pages_per_chunk=2))
    params_struct = _struct_of(eng.params)
    pages = eng.kv.pages
    pages_struct = jax.ShapeDtypeStruct(pages.shape, pages.dtype)
    # real leaf shardings seed the replica-group pass (invar order:
    # params leaves, pages, meta[, rng] — matching make_jaxpr flattening)
    param_shardings = [getattr(leaf, "sharding", None)
                       for leaf in jax.tree.leaves(eng.params)]

    def arg_shardings(with_rng=False):
        return param_shardings + [getattr(pages, "sharding", None), None] \
            + ([None] if with_rng else [])

    def meta_struct(key):
        return jax.ShapeDtypeStruct((eng._meta_len(key),), jnp.int32)

    out: List[Artifact] = []
    # prefill bucket for an 8-token single-sequence put()
    pkey = eng.bucket_for(8, 1)
    step = build_ragged_step(eng.family, **eng._program_kw(pkey))
    out.append(Artifact(
        f"prefill[{attn_impl},bucket={pkey}]",
        jax.make_jaxpr(step)(params_struct, pages_struct,
                             meta_struct(pkey)),
        PassContext(artifact=f"prefill[{attn_impl}]",
                    arg_shardings=arg_shardings())))

    # fused decode window: 2 sequences, 4 steps, greedy
    s_b = eng._seq_bucket(2)
    dkey = (s_b, s_b)
    loop = build_decode_loop(
        eng.family, block_size=eng.config.block_size, steps=4,
        temperature=0.0, top_k=0, **eng._program_kw(dkey))
    rng_struct = _struct_of(jax.random.PRNGKey(0))
    out.append(Artifact(
        f"decode_loop[{attn_impl},bucket={dkey},steps=4]",
        jax.make_jaxpr(loop)(params_struct, pages_struct,
                             meta_struct(dkey), rng_struct),
        PassContext(artifact=f"decode_loop[{attn_impl}]",
                    arg_shardings=arg_shardings(with_rng=True))))

    # spec-dec verify window at the same bucket
    vstep = build_verify_step(eng.family, **eng._program_kw(dkey))
    out.append(Artifact(
        f"verify[{attn_impl},bucket={dkey}]",
        jax.make_jaxpr(vstep)(params_struct, pages_struct,
                              meta_struct(dkey)),
        PassContext(artifact=f"verify[{attn_impl}]",
                    arg_shardings=arg_shardings())))
    return out


# --------------------------------------------------------------------- #
# Training step (fused scan path)
# --------------------------------------------------------------------- #
def _tiny_train_engine(config_overrides: Optional[Dict] = None,
                       gas: int = 2):
    import jax

    import deepspeed_tpu
    from ..models.transformer import CausalLM, TransformerConfig
    from ..runtime.topology import TopologyConfig, initialize_mesh

    topo = initialize_mesh(TopologyConfig(), force=True)
    cfg = TransformerConfig.tiny(use_flash=False)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
    }
    config.update(config_overrides or {})
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=config, topology=topo)
    return eng, topo


def _batch_struct(eng, gas: int):
    import jax
    import jax.numpy as jnp

    micro_global = eng.train_micro_batch_size_per_gpu() * \
        max(eng.topology.get_data_parallel_world_size(), 1)
    shape = (gas, micro_global, 32) if gas > 1 else (micro_global, 32)
    return {"input_ids": jax.ShapeDtypeStruct(shape, jnp.int32)}


def build_train_artifact() -> Artifact:
    """The fused train step (scan over micro-batches + optimizer update)
    exactly as ``train_batch`` would jit it, with the engine's real state
    shardings seeding the replica-group pass."""
    import jax

    gas = 2
    eng, topo = _tiny_train_engine(gas=gas)
    fn = eng._build_train_batch_fn()
    state_struct = _struct_of(eng.state)
    batch = _batch_struct(eng, gas)
    traced = jax.make_jaxpr(fn)(state_struct, batch)
    shardings = [getattr(leaf, "sharding", None)
                 for leaf in jax.tree.leaves(eng.state)]
    shardings += [None] * len(jax.tree.leaves(batch))
    ctx = PassContext(artifact="train_step[zero2,gas=2]", mesh=topo.mesh,
                      arg_shardings=shardings)
    return Artifact(ctx.artifact, traced, ctx)


def build_prefetch_artifact() -> Artifact:
    """The PR-4 invariant program: the *pregathered* explicit-comm
    per-micro step under stage-3 quantized weight gather — must carry
    ZERO all-gathers (``gather_budget=0``); the once-per-window gather fn
    owns the wire."""
    import jax

    from ..runtime.comm_path import (build_explicit_micro_fn,
                                     build_param_gather_fn,
                                     make_explicit_grad_acc)

    eng, topo = _tiny_train_engine(
        gas=2,
        config_overrides={
            "zero_optimization": {"stage": 3, "zero_quantized_weights": True,
                                  "stage3_param_persistence_threshold": 0},
            "bf16": {"enabled": True},
            "overlap": {"enabled": True, "prefetch_params": True},
        })
    # the explicit path accumulates LOCAL per-data-shard grads (leading
    # [n_dp] axis) — mirror backward()'s lazy re-layout before tracing
    state = eng.state.replace(grad_acc=make_explicit_grad_acc(eng))
    gathered_struct = jax.eval_shape(build_param_gather_fn(eng),
                                     _struct_of(state.params))
    micro = build_explicit_micro_fn(eng, pregathered=True)
    traced = jax.make_jaxpr(micro)(_struct_of(state),
                                   _batch_struct(eng, gas=1),
                                   gathered_struct)
    ctx = PassContext(artifact="micro_pregathered[zero3,qwZ]",
                      mesh=topo.mesh, gather_budget=0)
    return Artifact(ctx.artifact, traced, ctx)


# --------------------------------------------------------------------- #
# Fused quantized collective wire
# --------------------------------------------------------------------- #
def build_fused_wire_artifact(bits: int = 4) -> Artifact:
    """The production fused quantize→exchange→dequantize allreduce traced
    under a full-manual shard_map on the 8-device sim mesh — the EQuARX
    wire the fused-wire-layout pass protects."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..runtime.comm_path import quantized_allreduce
    from ..runtime.topology import (DATA, TopologyConfig, compat_shard_map,
                                    initialize_mesh)

    topo = initialize_mesh(TopologyConfig(), force=True)

    def ex(x):
        out, _, _ = quantized_allreduce(x[0], (DATA,), bits=bits)
        return out[None]

    n = topo.mesh.shape[DATA]
    stacked = jax.ShapeDtypeStruct((n, 40, 8), jnp.float32)
    traced = jax.make_jaxpr(compat_shard_map(
        ex, topo.mesh, (P(DATA),), P(DATA), manual_axes={DATA}))(stacked)
    return Artifact(f"fused_wire[int{bits}]", traced,
                    PassContext(artifact=f"fused_wire[int{bits}]",
                                mesh=topo.mesh))


# --------------------------------------------------------------------- #
# Fused compute+collective matmul edges (PR 15, T3)
# --------------------------------------------------------------------- #
def build_fused_gemm_artifact(wire_bits: int = 0) -> Artifact:
    """The reduce-scatter epilogue matmul traced under shard_map on the
    8-device sim, linted with ``expect_fused_gemm``: every epilogue
    collective operand must chase to the producing pallas_call — the
    contract the fused-wire-layout pass's gemm extension enforces (the
    unfused matmul→psum_scatter composition is the fixture negative
    control)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..kernels.fused_collective_matmul import matmul_reduce_scatter
    from ..runtime.topology import (DATA, TopologyConfig, compat_shard_map,
                                    initialize_mesh)

    topo = initialize_mesh(TopologyConfig(), force=True)
    n = topo.mesh.shape[DATA]

    def ex(x, w):
        return matmul_reduce_scatter(x[0], w, (DATA,),
                                     wire_bits=wire_bits,
                                     impl="pallas")[None]

    traced = jax.make_jaxpr(compat_shard_map(
        ex, topo.mesh, (P(DATA), P()), P(DATA), manual_axes={DATA}))(
            jax.ShapeDtypeStruct((n, 8 * n, 32), jnp.float32),
            jax.ShapeDtypeStruct((32, 64), jnp.float32))
    wire = f"int{wire_bits}" if wire_bits else "fp"
    name = f"fused_gemm_epilogue[{wire}]"
    return Artifact(name, traced,
                    PassContext(artifact=name, mesh=topo.mesh,
                                extra={"expect_fused_gemm": True}))


# --------------------------------------------------------------------- #
# The sweep
# --------------------------------------------------------------------- #
_BUILDERS: Dict[str, Callable[[], List[Artifact]]] = {
    "inference": lambda: (build_inference_artifacts("gather") +
                          build_inference_artifacts("paged")),
    "train": lambda: [build_train_artifact()],
    "prefetch": lambda: [build_prefetch_artifact()],
    "fused_wire": lambda: [build_fused_wire_artifact(4),
                           build_fused_wire_artifact(8)],
    "fused_gemm": lambda: [build_fused_gemm_artifact(0),
                           build_fused_gemm_artifact(8)],
}


def builder_names() -> List[str]:
    return sorted(_BUILDERS)


def sweep(only: Optional[Sequence[str]] = None,
          log: Optional[Callable[[str], None]] = None,
          ):
    """Build every artifact group (or ``only`` the named ones) and run all
    graph passes over each.  Returns (findings, artifact_names)."""
    findings: List[Finding] = []
    names: List[str] = []
    for group in (only if only else builder_names()):
        if group not in _BUILDERS:
            raise KeyError(f"unknown artifact group {group!r}; known: "
                           f"{builder_names()}")
        for art in _BUILDERS[group]():
            if log is not None:
                log(f"lint {art.name}")
            findings.extend(run_graph_passes(art.traced, art.ctx))
            names.append(art.name)
    return findings, names
