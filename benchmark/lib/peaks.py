"""Published peaks of the chips the benchmark knows, keyed by JAX's
``device_kind``.  A device that is not here is an error, not a default."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    kind: str
    bf16_flops: float        # FLOP/s, dense bf16 matmul
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


#: Google Cloud documentation, "TPU v5e" (system architecture page): 197
#: TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": Peaks("TPU v5 lite", 197e12, 819e9, 16e9,
                         "Google Cloud TPU docs, 'TPU v5e'"),
    "TPU v5e": Peaks("TPU v5e", 197e12, 819e9, 16e9,
                     "Google Cloud TPU docs, 'TPU v5e'"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a row "
            f"with its source to benchmark/lib/peaks.py") from None
