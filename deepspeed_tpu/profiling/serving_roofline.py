"""Decode HBM-bandwidth rooflines for the serving engine.

Decode is memory-bound, not compute-bound: each generated token streams the
whole parameter set plus the sequence's cached KV through HBM for a handful
of flops per byte.  MFU is therefore the wrong lens — the honest
utilization number for a decode window is **achieved HBM bytes/s vs the
chip's peak**, broken down per kernel so a slow decode can be attributed to
the attention page walk, the weight stream, or the cache append.

The byte model is analytic (the same approach the PR-3 roofline takes for
flops): per decode step,

  * ``param_stream``     — every weight is read once per forward
    (batch-independent at decode batch sizes: the stream dominates until
    ``n_seqs`` approaches the arithmetic-intensity ridge);
  * ``decode_attention`` — each sequence reads K and V for its whole cached
    context from the page pool (the paged kernel's DMA traffic; the
    dense-gather oracle reads the padded budget instead, which is exactly
    why it loses);
  * ``kv_append``        — each sequence writes one new K/V row per layer.

:func:`decode_roofline_report` turns (bytes, seconds) into per-kernel GB/s
and %-of-peak via the device table in ``profiling/roofline.py``;
:func:`publish_decode_gauges` mirrors the report into ``serving/*`` gauges
so ``dstpu-telemetry`` renders the serving section.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from .roofline import DeviceSpec, device_spec


def decode_window_bytes(num_layers: int, num_kv_heads: int, head_dim: int,
                        kv_dtype_bytes: int, param_bytes: int,
                        n_seqs: int, steps: int,
                        mean_ctx: float) -> Dict[str, float]:
    """Analytic HBM bytes moved by one fused decode window, per kernel.

    ``mean_ctx`` is the average context length across sequences over the
    window (context grows by one per step, so callers typically pass
    ``ctx_at_window_start + steps / 2``).
    """
    kv_row = 2 * num_kv_heads * head_dim * kv_dtype_bytes
    return {
        "decode_attention": float(num_layers) * n_seqs * mean_ctx * kv_row
        * steps,
        "kv_append": float(num_layers) * n_seqs * kv_row * steps,
        "param_stream": float(param_bytes) * steps,
    }


def decode_roofline_report(bytes_by_kernel: Dict[str, float],
                           seconds: float, n_seqs: int, steps: int,
                           spec: Optional[DeviceSpec] = None
                           ) -> Dict[str, Any]:
    """Per-kernel and total decode HBM roofline for one window.

    The per-kernel %-of-peak uses the WINDOW's wall time for every kernel
    (kernels are not individually timed on-device), so each row reads as
    "this kernel alone moved X% of what the chip could have streamed in the
    window" — the rows sum to the total, and the total is the classic
    achieved-vs-peak bandwidth number.
    """
    spec = spec or device_spec()
    dt = max(float(seconds), 1e-12)
    total = float(sum(bytes_by_kernel.values()))
    kernels = {}
    for name, b in bytes_by_kernel.items():
        gbps = b / dt / 1e9
        kernels[name] = {
            "bytes": float(b),
            "hbm_gbps": gbps,
            "hbm_pct_peak": 100.0 * gbps * 1e9 / spec.hbm_bandwidth,
            "pct_of_window_bytes": 100.0 * b / total if total else 0.0,
        }
    tok_s = n_seqs * steps / dt
    return {
        "device_kind": spec.kind,
        "peak_hbm_gbps": spec.hbm_bandwidth / 1e9,
        "window_s": float(seconds),
        "n_seqs": int(n_seqs),
        "steps": int(steps),
        "decode_tok_per_s": tok_s,
        "hbm_gbps": total / dt / 1e9,
        "hbm_pct_peak": 100.0 * (total / dt) / spec.hbm_bandwidth,
        "bytes_total": total,
        "kernels": kernels,
    }


def publish_decode_gauges(metrics, report: Dict[str, Any]) -> None:
    """Mirror a decode roofline report into ``serving/*`` gauges (the
    telemetry summary's serving section reads these back)."""
    kind = str(report.get("device_kind", "?"))
    totals = {"decode_tok_per_s": "serving/decode_tok_per_s",
              "hbm_gbps": "serving/decode_hbm_gbps",
              "hbm_pct_peak": "serving/decode_hbm_pct_peak",
              "peak_hbm_gbps": "serving/peak_hbm_gbps",
              "window_s": "serving/decode_window_s"}
    for key, gauge in totals.items():
        v = report.get(key)
        if isinstance(v, (int, float)):
            metrics.gauge(gauge).set(float(v), device=kind)
    for name, row in (report.get("kernels") or {}).items():
        metrics.gauge("serving/kernel_hbm_gbps").set(
            float(row["hbm_gbps"]), kernel=name, device=kind)
        metrics.gauge("serving/kernel_hbm_pct_peak").set(
            float(row["hbm_pct_peak"]), kernel=name, device=kind)
