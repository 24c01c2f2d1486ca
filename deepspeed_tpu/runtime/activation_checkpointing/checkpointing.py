"""Activation checkpointing (reference: runtime/activation_checkpointing/
checkpointing.py:124,377,488,704,948,1029).

On TPU every reference feature maps onto a ``jax.checkpoint`` policy:

  ====================================  =======================================
  reference knob                        TPU mechanism
  ====================================  =======================================
  ``checkpoint()`` (reentrant)          ``jax.checkpoint`` (remat) of the layer
  ``non_reentrant_checkpoint``          same — JAX remat is always functional
  ``partition_activations``             save residuals sharded over TP/SP axes
                                        (``checkpoint_policies`` + sharding
                                        constraints on saved values)
  ``cpu_checkpointing``                 ``offload_checkpoint_policy`` — saved
                                        residuals live in host memory
  ``contiguous_memory_optimization``    XLA's allocator already packs remat
                                        buffers; accepted as a no-op knob
  ``CudaRNGStatesTracker``              functional PRNG keys — dropout keys are
                                        split per call, replayed exactly under
                                        remat (no tracker needed)
  (none: the default since PR 48)       a checkpointed layer SAVES the named
                                        outputs of its kernels and matmuls and
                                        (PR 63) the expert weights ZeRO-3
                                        gathered for it, as many as the
                                        device's free memory holds, the
                                        dearest to make again first — FLOPs
                                        and a gather's bytes both in seconds
                                        (:func:`select_saved`,
                                        :func:`layer_policy`); with no memory
                                        report, or outside an engine's step:
                                        ``nothing_saveable``, full recompute.
                                        Each trained model lists its own
                                        layer's values (``models/
                                        transformer.py``; ``models/
                                        joyai_flash.py``: the expanded k/v,
                                        the sorted pairs' rows) and asks
                                        :func:`resolve_policy`
  ====================================  =======================================
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax

from ...telemetry.trace import get_tracer
from ...utils.logging import logger

_CONFIG = {
    "partition_activations": False,
    "contiguous_memory_optimization": False,
    "cpu_checkpointing": False,
    "number_checkpoints": None,
    "synchronize": False,
    "profile": False,
}


def configure(mpu_=None, deepspeed_config=None, partition_activations=None,
              contiguous_checkpointing=None, num_checkpoints=None,
              checkpoint_in_cpu=None, synchronize=None, profile=None):
    """Reference: checkpointing.py:1029 — set module-level policy flags."""
    if deepspeed_config is not None:
        ac = getattr(deepspeed_config, "activation_checkpointing", None)
        if ac is not None:
            _CONFIG["partition_activations"] = ac.partition_activations
            _CONFIG["contiguous_memory_optimization"] = ac.contiguous_memory_optimization
            _CONFIG["cpu_checkpointing"] = ac.cpu_checkpointing
            _CONFIG["number_checkpoints"] = ac.number_checkpoints
            _CONFIG["synchronize"] = ac.synchronize_checkpoint_boundary
            _CONFIG["profile"] = ac.profile
    for key, val in [("partition_activations", partition_activations),
                     ("contiguous_memory_optimization", contiguous_checkpointing),
                     ("number_checkpoints", num_checkpoints),
                     ("cpu_checkpointing", checkpoint_in_cpu),
                     ("synchronize", synchronize), ("profile", profile)]:
        if val is not None:
            _CONFIG[key] = val


def is_configured() -> bool:
    return True


#: ``jax.ad_checkpoint.checkpoint_name`` tags the models place on their
#: per-layer residual streams — the values the save/offload policies below
#: select by name (models/transformer.py layer()).
RESIDUAL_NAMES = ("attn_residual", "mlp_residual")


class Saveable(NamedTuple):
    """Named values of one checkpointed layer that pay off only together
    (a kernel's output and its row statistics), the bytes a device holds
    for them a layer, and the seconds the backward spends making them again
    when they are not saved — FLOPs at the MXU's peak, or for a gathered
    weight its bytes at the links' rate: one unit, so one sort decides.
    ``freed``: bytes of the caller's reserve that saving them gives back (a
    gathered weight that is kept is not gathered again)."""
    names: Tuple[str, ...]
    bytes: int
    seconds: float
    freed: int = 0


def select_saved(tensors: Sequence[Saveable], layers: int,
                 budget_bytes: int) -> Tuple[str, ...]:
    """The names a checkpointed layer saves under ``budget_bytes`` for all
    ``layers``: the entries taken by seconds a byte (a tie keeps the order
    given) up to the first that no longer fits — all of them where all
    fit, ``()`` where the best does not, which is ``nothing_saveable``."""
    saved, left = [], budget_bytes
    for t in sorted(tensors, key=lambda t: -t.seconds / max(t.bytes, 1)):
        left -= layers * t.bytes - t.freed
        if left < 0:
            break
        saved.extend(t.names)
    return tuple(saved)


#: (the device's ``bytes_limit``, the engine's state on it) while an
#: engine traces its model's loss; ``None`` anywhere else
_ENGINE_MEMORY: Optional[Tuple[int, int]] = None


@contextlib.contextmanager
def engine_memory(limit_bytes: int, state_bytes: int):
    """Trace-time scope the engine opens around its model's loss: what one
    device's memory holds at most (0 where the backend reports none) and
    what the engine itself keeps there through a step.  Both are fixed by
    shapes and shardings, so a model's saved set is the same on every run."""
    global _ENGINE_MEMORY
    outer, _ENGINE_MEMORY = _ENGINE_MEMORY, (limit_bytes, state_bytes)
    try:
        yield
    finally:
        _ENGINE_MEMORY = outer


def layer_policy(tensors: Sequence[Saveable], reserve_bytes: int,
                 layers: int):
    """The ``jax.checkpoint`` policy of a model's layer whose named values
    are ``tensors``: save what fits in the device's memory less the
    engine's state less ``reserve_bytes`` (the step's transients, from the
    caller's shapes).  A saved value is held once a layer and once more:
    the backward pass slices the layer it differentiates out of the
    ``[layers, ...]`` stacks into a copy (the TPU compiler's own account,
    PERF.md section 6, PR 48) — less what an entry says the reserve holds
    for it already (``Saveable.freed``).  Leaves one ``train/remat_layout``
    record a trace."""
    limit, state = _ENGINE_MEMORY or (0, 0)
    budget = max(0, limit - state - reserve_bytes)
    saved = select_saved(tensors, layers + 1, budget)
    get_tracer().record(
        "train/remat_layout", time.perf_counter(), 0.0, saved=saved,
        bytes_per_layer=sum(t.bytes for t in tensors
                            if set(t.names) <= set(saved)),
        layers=layers, budget_bytes=budget, state_bytes=state,
        reserve_bytes=reserve_bytes)
    if not saved:
        return jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint_policies.save_only_these_names(*saved)


def resolve_policy(name: str, layout: Callable[[], Tuple[Sequence[Saveable],
                                                          int]],
                   layers: int):
    """The ``jax.checkpoint`` policy of a model's checkpointed layers — the
    one rule every trained model asks (``models/transformer.py``, ``models/
    joyai_flash.py``): a DS-config ``activation_checkpointing`` block
    (partition_activations / cpu_checkpointing) overrides the model's own
    choice, the config toggle must change execution; ``"auto"`` is
    :func:`layer_policy` over the model's ``layout()`` (its layer's named
    values and the bytes the step needs beside them); any other ``name`` is
    a member of ``jax.checkpoint_policies``."""
    if active():
        return get_policy()
    if name == "auto":
        return layer_policy(*layout(), layers=layers)
    policy = getattr(jax.checkpoint_policies, name, None)
    if not callable(policy):
        valid = ["auto"] + [n for n in dir(jax.checkpoint_policies)
                            if not n.startswith("_")]
        raise ValueError(
            f"remat_policy={name!r} is not a "
            f"jax.checkpoint_policies member; valid: {valid}")
    return policy


def active() -> bool:
    """True when the DS config asked for a policy beyond plain recompute —
    the signal for model code to route its remat policy through
    :func:`get_policy` instead of its own ``cfg.remat_policy``."""
    return bool(_CONFIG["partition_activations"] or
                _CONFIG["cpu_checkpointing"])


def get_policy(policy_name: Optional[str] = None):
    """Map config → jax.checkpoint policy.

    - ``cpu_checkpointing`` → offload the named residuals to pinned host
      memory during the forward, fetch them back for the backward
      (reference :948's checkpoint-in-cpu, as an XLA memory-space move
      instead of an explicit D2H copy).
    - ``partition_activations`` → SAVE the named residuals instead of
      recomputing; the model constrains them sharded over the mesh's
      data/seq axes, so each device holds only its shard (the reference's
      TP-partitioned saved activations, expressed as sharding).
    - otherwise full recompute (``nothing_saveable``).
    """
    policies = jax.checkpoint_policies
    if policy_name:
        return getattr(policies, policy_name)
    if _CONFIG["cpu_checkpointing"]:
        try:
            return policies.save_and_offload_only_these_names(
                names_which_can_be_saved=[],
                names_which_can_be_offloaded=list(RESIDUAL_NAMES),
                offload_src="device", offload_dst="pinned_host")
        except Exception:  # older jax
            logger.warning("offload remat policy unavailable; saving on device")
            return policies.save_only_these_names(*RESIDUAL_NAMES)
    if _CONFIG["partition_activations"]:
        return policies.save_only_these_names(*RESIDUAL_NAMES)
    return policies.nothing_saveable


def checkpoint(function: Callable, *args, policy=None, prevent_cse: bool = True):
    """Reference: checkpointing.py:948 — remat ``function`` over ``args``.

    Returns the function outputs; gradients recompute the forward.
    """
    wrapped = jax.checkpoint(function, policy=policy or get_policy(),
                             prevent_cse=prevent_cse)
    return wrapped(*args)


def checkpoint_wrapper(function: Callable, policy=None) -> Callable:
    """Decorator form used by model code (per-layer remat)."""
    return jax.checkpoint(function, policy=policy or get_policy())


def partition_activations_enabled() -> bool:
    return bool(_CONFIG["partition_activations"])


class CheckpointFunction:
    """API-parity shim for the reference autograd.Function (:488)."""

    @staticmethod
    def apply(run_function, *args):
        return checkpoint(run_function, *args)


def model_parallel_cuda_manual_seed(seed: int):
    """Reference RNG tracker entry point (:124). Functional JAX PRNG needs no
    global tracker; provided for API compatibility."""
    return jax.random.PRNGKey(seed)


def reset():
    for k, v in [("partition_activations", False),
                 ("contiguous_memory_optimization", False),
                 ("cpu_checkpointing", False), ("number_checkpoints", None),
                 ("synchronize", False), ("profile", False)]:
        _CONFIG[k] = v
