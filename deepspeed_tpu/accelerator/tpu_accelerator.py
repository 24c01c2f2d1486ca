"""TPU accelerator runtime (reference analogue: accelerator/cuda_accelerator.py)."""
from __future__ import annotations

import os
from typing import Any, List

from .abstract_accelerator import Accelerator

#: env var libtpu reads (once, at client init) for XLA:TPU flags
LIBTPU_ENV = "LIBTPU_INIT_ARGS"


class TPUAccelerator(Accelerator):
    _name = "tpu"
    _communication_backend_name = "xla"

    def apply_xla_flags(self, flags: List[str]) -> bool:
        """Merge flags into ``LIBTPU_INIT_ARGS`` (deduplicated by flag
        name — an explicit user setting of the same flag wins)."""
        current = os.environ.get(LIBTPU_ENV, "").split()
        have = {f.split("=", 1)[0] for f in current}
        added = [f for f in flags if f.split("=", 1)[0] not in have]
        if added:
            os.environ[LIBTPU_ENV] = " ".join(current + added)
        return True

    def is_available(self) -> bool:
        """True when JAX reports a TPU device.  A backend that fails to
        start raises: the caller decides whether the CPU is acceptable
        (``real_accelerator._probe`` says so once; ``chip_smoke.py`` and
        ``benchmark/run.py`` ask JAX directly and never come through
        here)."""
        import jax

        return any(d.platform == "tpu" for d in jax.devices())

    def devices(self) -> List[Any]:
        import jax

        return [d for d in jax.devices() if d.platform == "tpu"]

    def local_devices(self) -> List[Any]:
        import jax

        return [d for d in jax.local_devices() if d.platform == "tpu"]

    def is_fp16_supported(self) -> bool:
        # TPUs compute in bf16; fp16 storage is supported but bf16 preferred.
        return False

    def supports_pallas(self) -> bool:
        return True
