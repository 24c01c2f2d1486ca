"""Latency-hiding scheduler / async-collective XLA flag wiring.

The structural levers in this package (deferred reduction, scanned-layer
prefetch) only create *opportunity*: dataflow-independent collectives.
Whether XLA actually runs them under compute is the latency-hiding
scheduler's call, and on TPU that scheduler plus async collective fusion
sit behind libtpu flags that must be set **before the backend client is
created** (libtpu reads ``LIBTPU_INIT_ARGS`` once at init).

``overlap.xla_flags`` (default on when overlap is enabled) applies the
flag set through the accelerator: the TPU accelerator merges them into
``LIBTPU_INIT_ARGS``; every other accelerator is a safe no-op (the CPU
backend has no libtpu and ignores the env entirely).  Selection uses
:func:`~deepspeed_tpu.accelerator.real_accelerator.peek_accelerator_name`,
which deliberately does *not* probe ``jax.devices()`` — probing would
itself initialize the backend and defeat the wiring.
"""
from __future__ import annotations

import os
from typing import List, Optional, Sequence

from ...accelerator.tpu_accelerator import LIBTPU_ENV
from ...utils.logging import logger

#: the overlap flag set (libtpu spellings): LHS + async collectives +
#: collective fusion, the combination T3-style schedules rely on
LHS_FLAGS: Sequence[str] = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_enable_async_all_gather=true",
    "--xla_enable_async_collective_permute=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
)


def overlap_flag_set(overlap_cfg=None) -> List[str]:
    """The flags :func:`configure_xla_overlap_flags` would apply."""
    flags = list(LHS_FLAGS)
    extra = list(getattr(overlap_cfg, "xla_extra_flags", []) or [])
    for f in extra:
        if f not in flags:
            flags.append(f)
    return flags


def backend_initialized() -> bool:
    """Best-effort: has a JAX backend client already been created?  (If we
    cannot tell, assume not — setting the env late is harmless, it just
    may not take effect for this process.)"""
    try:
        import sys

        if "jax" not in sys.modules:
            return False
        from jax._src import xla_bridge

        return bool(getattr(xla_bridge, "_backends", None))
    except Exception:  # noqa: BLE001 — introspection only
        return False


def configure_xla_overlap_flags(overlap_cfg=None,
                                accelerator=None) -> bool:
    """Apply the overlap flag set if the config asks for it.

    Returns True iff the accelerator actually recorded flags.  Call as
    early as possible (``deepspeed_tpu.initialize`` runs it before the
    mesh is built); a late call logs a warning and still sets the env so a
    respawned worker (elastic agent restart) picks it up.
    """
    if overlap_cfg is not None and not (
            getattr(overlap_cfg, "enabled", False)
            and getattr(overlap_cfg, "xla_flags", True)):
        return False
    if accelerator is None:
        from ...accelerator.real_accelerator import peek_accelerator

        accelerator = peek_accelerator()
    flags = overlap_flag_set(overlap_cfg)
    before = os.environ.get(LIBTPU_ENV)
    applied = accelerator.apply_xla_flags(flags)
    if applied:
        # a repeat call (a second engine in one process) adds nothing and
        # is not late; only flags that landed AFTER client creation are
        if backend_initialized() and os.environ.get(LIBTPU_ENV) != before:
            logger.warning(
                "overlap.xla_flags: JAX backend already initialized — the "
                "latency-hiding scheduler flags are recorded in the "
                "environment but only take effect for newly started "
                "processes (elastic-agent restarts pick them up)")
        logger.info(f"overlap: applied {len(flags)} XLA scheduler flag(s) "
                    f"via {accelerator.device_name()} accelerator")
    else:
        logger.debug(
            f"overlap.xla_flags: no-op on {accelerator.device_name()} "
            f"accelerator (flags are TPU/libtpu-specific)")
    return applied


def normalize_overlap_raw(raw_cfg: dict) -> dict:
    """Expand the ``overlap`` shorthands of a raw config dict to the block
    form (single source of truth — DeepSpeedConfig parses through this
    too): ``"auto"`` → auto mode, ``true`` → defaults, absent + legacy
    ``zero_optimization.overlap_comm`` → defaults, absent → disabled."""
    ov = raw_cfg.get("overlap", None)
    if isinstance(ov, str):
        return {"enabled": True, "mode": ov}
    if isinstance(ov, bool):
        return {"enabled": ov}
    if ov is None:
        legacy = bool((raw_cfg.get("zero_optimization") or {})
                      .get("overlap_comm"))
        return {"enabled": True} if legacy else {}
    return dict(ov)


def raw_overlap_flags_requested(raw_cfg: Optional[dict]) -> bool:
    """Does a *raw* config dict ask for overlap flag wiring?  Used by
    ``deepspeed_tpu.initialize`` before the full DeepSpeedConfig (which
    needs the topology) exists."""
    if not isinstance(raw_cfg, dict):
        return False
    ov = normalize_overlap_raw(raw_cfg)
    return bool(ov.get("enabled", False)) and bool(ov.get("xla_flags", True))


def configure_from_raw(raw_cfg: Optional[dict]) -> bool:
    """Pre-backend-init flag wiring from a raw config dict: builds the
    real OverlapConfig (so ``xla_extra_flags`` and knob validation apply)
    and delegates to :func:`configure_xla_overlap_flags`.  A malformed
    block is left for DeepSpeedConfig to reject with its own message."""
    if not raw_overlap_flags_requested(raw_cfg):
        return False
    from ..config import OverlapConfig

    try:
        cfg = OverlapConfig(**normalize_overlap_raw(raw_cfg))
    except Exception as e:  # noqa: BLE001 — DeepSpeedConfig re-raises later
        logger.debug(f"overlap.xla_flags: block failed to parse ({e}); "
                     f"deferring the error to DeepSpeedConfig")
        return False
    return configure_xla_overlap_flags(cfg)
