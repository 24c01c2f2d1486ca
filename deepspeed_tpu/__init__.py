"""deepspeed_tpu: a TPU-native large-scale training & inference framework.

Provides the capabilities of the DeepSpeed reference framework
(`deepspeed/__init__.py:69,268,291,369`), re-designed for JAX/XLA/Pallas on
TPU device meshes: ZeRO via sharding, pipeline/tensor/expert/sequence
parallelism over a named mesh, Pallas kernels for the hot ops, and a
ragged-batching inference engine.
"""
from __future__ import annotations

from typing import Any, Optional

__version__ = "0.1.0"

from . import comm  # noqa: F401
from .accelerator import get_accelerator  # noqa: F401
from .runtime import zero  # noqa: F401
from .runtime.config import DeepSpeedConfig  # noqa: F401
from .runtime.topology import TopologyConfig, initialize_mesh  # noqa: F401


def initialize(
    args: Any = None,
    model: Any = None,
    optimizer: Any = None,
    model_parameters: Any = None,
    training_data: Any = None,
    lr_scheduler: Any = None,
    distributed_port: Optional[int] = None,
    mpu: Any = None,
    dist_init_required: Optional[bool] = None,
    collate_fn: Any = None,
    config: Any = None,
    config_params: Any = None,
    topology: Any = None,
    mesh_config: Optional["TopologyConfig"] = None,
    seed: int = 0,
):
    """Create a training engine (reference: ``deepspeed.initialize``,
    deepspeed/__init__.py:69).

    Returns ``(engine, optimizer, dataloader, lr_scheduler)`` like the
    reference.  ``model`` is a loss callable ``f(params, batch, rng) -> loss``
    or a flax module; ``model_parameters`` is the initial parameter pytree.
    """
    import importlib.util
    import json

    from .runtime.engine import DeepSpeedEngine

    config = config if config is not None else config_params
    if args is not None and getattr(args, "deepspeed_config", None):
        if config is not None:
            raise ValueError(
                "Not sure how to proceed: both args.deepspeed_config and the "
                "config argument were given (reference semantics: pass one)")
        config = args.deepspeed_config

    # Normalize to a dict once (DeepSpeedConfig instances keep their raw dict).
    if isinstance(config, str):
        with open(config) as f:
            config = json.load(f)
    raw_cfg = config.raw if isinstance(config, DeepSpeedConfig) else (config or {})

    # Overlap's latency-hiding-scheduler flags must land in the environment
    # BEFORE the first backend touch (libtpu reads LIBTPU_INIT_ARGS once at
    # client init) — i.e. before init_distributed/mesh building below.
    # Safe no-op on CPU and when the block doesn't ask for flags.
    from .runtime.overlap.xla_flags import configure_from_raw

    configure_from_raw(raw_cfg)

    from .utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    if dist_init_required is None or dist_init_required:
        comm.init_distributed(distributed_port=distributed_port)

    if topology is None and mpu is not None:
        # Megatron-style mpu object (reference: engine honors
        # mpu.get_*_parallel_group(); here we honor the sizes).
        tp = getattr(mpu, "get_tensor_model_parallel_world_size",
                     getattr(mpu, "get_model_parallel_world_size", lambda: 1))()
        pp = getattr(mpu, "get_pipeline_model_parallel_world_size", lambda: 1)()
        topology = initialize_mesh(TopologyConfig(tensor=tp, pipe=pp), force=True)

    if topology is None:
        if mesh_config is not None:
            topology = initialize_mesh(mesh_config, force=True)
        else:
            topology = _topology_from_env_or_config(raw_cfg)

    if isinstance(config, DeepSpeedConfig):
        ds_config = config
        if ds_config._topology is not topology:
            # Re-resolve batch sizes against the actual mesh.
            ds_config = DeepSpeedConfig(ds_config.raw, topology=topology)
    else:
        ds_config = DeepSpeedConfig(config, topology=topology)

    engine_cls = DeepSpeedEngine
    from .runtime.pipe.module import PipelinedCausalLM, PipelineModule

    if isinstance(model, (PipelineModule, PipelinedCausalLM)):
        from .runtime.pipe.engine import PipelineEngine

        engine_cls = PipelineEngine

    engine = engine_cls(
        model=model, config=ds_config, topology=topology,
        model_parameters=model_parameters, optimizer=optimizer,
        lr_scheduler=lr_scheduler, training_data=training_data,
        collate_fn=collate_fn, seed=seed)

    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def _topology_from_env_or_config(cfg: dict):
    """The elastic agent's re-planned mesh wins over config-derived degrees.

    A worker restarted with ``--allow-reshape`` carries the gang's actual
    capacity in ``DSTPU_ELASTIC_MESH_SHAPE`` — the DeepSpeed config still
    describes the LAUNCH-time world, so building from it would reconstruct
    the stale pre-shrink mesh (or fail outright on fewer chips).  Explicit
    ``topology=``/``mesh_config=``/``mpu=`` arguments still take precedence
    over both (the caller hand-wired a mesh on purpose)."""
    from .runtime.topology import topology_config_from_env
    from .utils.logging import log_dist

    env_cfg = topology_config_from_env()
    if env_cfg is None:
        return _topology_from_config(cfg)
    import jax
    import numpy as np

    devices = jax.devices()
    explicit = [env_cfg.pipe, env_cfg.data, env_cfg.expert, env_cfg.seq,
                env_cfg.tensor]
    if all(d > 0 for d in explicit):
        # the re-planned gang may be smaller than this host's visible pool
        # (CPU sim; or a worker seeing the full host while the agent planned
        # a subset): take the leading devices the plan needs
        needed = int(np.prod(explicit))
        if needed < len(devices):
            devices = devices[:needed]
    log_dist(f"elastic reshape: building mesh from DSTPU_ELASTIC_MESH_SHAPE "
             f"({env_cfg}) over {len(devices)} device(s); config-derived "
             f"parallel degrees are superseded for this incarnation",
             ranks=[0])
    return initialize_mesh(env_cfg, devices=devices, force=True)


def _topology_from_config(cfg: dict):
    """Derive mesh degrees from DeepSpeed config keys (sequence_parallel_size,
    tensor_parallel.autotp_size, pipeline.stages, moe ep_size)."""
    from .runtime.topology import get_topology

    tp = cfg.get("tensor_parallel", {}).get("autotp_size") or \
        cfg.get("tensor_parallel", {}).get("tp_size") or 1
    sp = cfg.get("sequence_parallel_size", 1)
    pp = cfg.get("pipeline", {}).get("stages", 1)
    ep = cfg.get("moe", {}).get("ep_size", 1)
    if tp == 1 and sp == 1 and pp == 1 and ep == 1:
        return get_topology()
    return initialize_mesh(
        TopologyConfig(pipe=pp, tensor=tp, seq=sp, expert=ep), force=True)


def init_distributed(dist_backend: str = "xla", **kwargs) -> None:
    """Reference: deepspeed/__init__.py:268 → comm.init_distributed."""
    comm.init_distributed(dist_backend=dist_backend, **kwargs)


def init_inference(model: Any = None, config: Any = None, **kwargs):
    """Create an inference engine (reference: deepspeed/__init__.py:291)."""
    import importlib.util

    if importlib.util.find_spec("deepspeed_tpu.inference.engine") is None:
        raise NotImplementedError(
            "deepspeed_tpu.inference is not available in this build")
    from .inference.engine import InferenceEngine

    return InferenceEngine(model=model, config=config, **kwargs)


def add_config_arguments(parser):
    """Reference: deepspeed/__init__.py:268 — CLI arg group."""
    group = parser.add_argument_group("DeepSpeed-TPU", "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed-TPU (helper flag)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to DeepSpeed-TPU json configuration")
    return parser
