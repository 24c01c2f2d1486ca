"""Accelerator selection (reference analogue: accelerator/real_accelerator.py:51-240).

Selection order:
1. ``DS_ACCELERATOR`` env var ("tpu" | "cpu"), matching the reference's
   explicit-override semantics.
2. Probe the JAX default backend: tpu if any TPU device exists, else cpu.
"""
from __future__ import annotations

import os
from typing import Optional

from .abstract_accelerator import Accelerator
from .cpu_accelerator import CPUAccelerator
from .tpu_accelerator import TPUAccelerator

_ACCELERATOR: Optional[Accelerator] = None


def _probe() -> Accelerator:
    name = os.environ.get("DS_ACCELERATOR", "").lower()
    if name == "cpu":
        return CPUAccelerator()
    if name == "tpu":
        return TPUAccelerator()
    if name:
        raise ValueError(f"DS_ACCELERATOR={name!r} is not supported (tpu|cpu)")
    tpu = TPUAccelerator()
    try:
        if tpu.is_available():
            return tpu
        why = "jax.devices() lists no TPU"
    except RuntimeError as exc:   # jax's backend-initialization failure
        why = f"the TPU backend did not start: {exc}"
    # the library may run on the CPU (tests, host-side tools) — but it says
    # so, once, with the reason
    from ..utils.logging import logger

    logger.info(f"accelerator: using the CPU accelerator ({why})")
    return CPUAccelerator()


def peek_accelerator() -> Accelerator:
    """Accelerator guess WITHOUT touching ``jax.devices()``.

    Probing devices initializes the JAX backend, which is exactly what the
    pre-init flag wiring (``runtime/overlap/xla_flags.py``) must avoid —
    libtpu reads its flag env once at client creation.  Heuristics only:
    ``DS_ACCELERATOR`` wins; ``JAX_PLATFORMS=cpu`` forces cpu; otherwise a
    libtpu install means tpu.  The guess never replaces the probed global
    (``get_accelerator`` still decides for everything else).
    """
    name = os.environ.get("DS_ACCELERATOR", "").lower()
    if name == "cpu":
        return CPUAccelerator()
    if name == "tpu":
        return TPUAccelerator()
    platforms = os.environ.get("JAX_PLATFORMS", "").lower()
    if platforms and "tpu" not in platforms:
        return CPUAccelerator()
    import importlib.util

    for mod in ("libtpu", "jax_plugins.xla_tpu"):
        try:
            if importlib.util.find_spec(mod) is not None:
                return TPUAccelerator()
        except (ImportError, ValueError):
            continue
    return CPUAccelerator()


def get_accelerator() -> Accelerator:
    global _ACCELERATOR
    if _ACCELERATOR is None:
        _ACCELERATOR = _probe()
    return _ACCELERATOR


def set_accelerator(accel: Accelerator) -> None:
    global _ACCELERATOR
    _ACCELERATOR = accel
