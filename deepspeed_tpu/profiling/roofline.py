"""Roofline model per device kind: peak flops, HBM bandwidth, and the
achieved-vs-peak report ("The Big Send-off", arXiv:2504.18658, uses the same
per-device rooflines to locate collective bottlenecks).

One table maps ``device_kind`` strings (as reported by ``jax.devices()``) to
bf16 peak flops and HBM bandwidth.  :func:`roofline_report` turns a step's
(flops, bytes, seconds) into achieved TFLOP/s, MFU, HBM utilization,
arithmetic intensity, and which side of the ridge the step sits on; the
engine publishes that through the telemetry metrics registry as
``roofline/*`` gauges (see ``bin/dstpu-telemetry``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional



@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Peak numbers for one device kind (bf16 matmul peak, HBM stream BW,
    aggregate inter-chip interconnect BW)."""

    kind: str
    peak_flops: float          # bf16 FLOP/s per chip
    hbm_bandwidth: float       # bytes/s per chip
    #: approximate aggregate ICI bytes/s per chip (all links, one
    #: direction) — the denominator for per-collective bus-bandwidth
    #: "% of peak" in the comm table
    ici_bandwidth: float = 0.0
    #: approximate DCN bytes/s per chip (cross-slice data-center network;
    #: the slow domain of the 2-hop hierarchical collectives).  Order of
    #: magnitude below ICI on every generation — which is exactly why the
    #: CollectiveAlgoSelector quantizes the inter-slice hop.
    dcn_bandwidth: float = 0.0
    #: approximate host<->device (PCIe) bytes/s per chip, one direction —
    #: the denominator for the host memory tier: optimizer-offload
    #: prefetch time, KV swap-in/out cost, and the ``overlap:"auto"``
    #: decision of what can live host-side without exposing transfer time
    host_bandwidth: float = 0.0

    @property
    def ridge_intensity(self) -> float:
        """Flops/byte above which the chip is compute-bound."""
        return self.peak_flops / max(self.hbm_bandwidth, 1.0)


#: THE peaks table — the engine's roofline gauges and the offline
#: summaries read it; there is no second copy.  Ordered: first
#: substring match against ``device_kind`` wins.  Sources: bf16 peak, HBM
#: bandwidth and ICI are the per-chip figures of the Google Cloud TPU
#: documentation's system-architecture pages ("TPU v6e", "TPU v5p",
#: "TPU v5e", "TPU v4", "TPU v3"; ICI converted from Gbit/s to bytes/s).
#: DCN and host (PCIe) bandwidths are not published per chip: they are
#: order-of-magnitude planning figures for the collective selector and
#: the offload planner, not roofline denominators.
DEVICE_SPECS = (
    DeviceSpec("TPU v6 lite", 918e12, 1640e9, 448e9, 25e9, 64e9),  # v6e
    DeviceSpec("TPU v6", 918e12, 1640e9, 448e9, 25e9, 64e9),
    DeviceSpec("TPU v5p", 459e12, 2765e9, 600e9, 25e9, 32e9),
    DeviceSpec("TPU v5 lite", 197e12, 819e9, 200e9, 12.5e9, 32e9),  # v5e
    DeviceSpec("TPU v5e", 197e12, 819e9, 200e9, 12.5e9, 32e9),
    DeviceSpec("TPU v4", 275e12, 1228e9, 300e9, 12.5e9, 16e9),
    DeviceSpec("TPU v3", 123e12, 900e9, 82e9, 6e9, 16e9),
)

#: stand-in for CPU runs ONLY (tests, host-side tools), so they produce
#: finite numbers instead of dividing by zero.  Its ``kind`` always carries
#: the words "cpu fallback peaks" wherever it is printed; no TPU ever
#: resolves to it.
CPU_FALLBACK = DeviceSpec("cpu (cpu fallback peaks)", 1e12, 100e9, 10e9,
                          1e9, 10e9)


def _resolve(kind: str, is_tpu: bool) -> DeviceSpec:
    """Table row for ``kind``; a TPU that is not in the table raises (a
    device without published peaks is an error, not a default); anything
    else is a CPU run and gets the labelled CPU fallback."""
    for spec in DEVICE_SPECS:
        if spec.kind.lower() in kind.lower():
            return dataclasses.replace(spec, kind=kind)
    if is_tpu:
        raise KeyError(
            f"no peaks for TPU device kind {kind!r} in "
            f"profiling/roofline.py DEVICE_SPECS — add the chip's published "
            f"figures with their source")
    return dataclasses.replace(CPU_FALLBACK,
                               kind=f"{kind} (cpu fallback peaks)")


def spec_for_kind(kind: str) -> DeviceSpec:
    """Spec from a ``device_kind`` string alone — no backend probe, so the
    offline tools (``dstpu-telemetry``'s comm table) can resolve peaks from
    a recorded run's metadata.  See :func:`_resolve` for unknown kinds."""
    return _resolve(str(kind), "tpu" in str(kind).lower())


def interconnect_peak(kind: str) -> float:
    """Aggregate ICI bytes/s per chip for a device-kind string."""
    return spec_for_kind(kind).ici_bandwidth


def host_transfer_seconds(nbytes: float,
                          spec: Optional[DeviceSpec] = None) -> float:
    """Predicted one-direction host<->device transfer time for ``nbytes``
    over PCIe — the swap-cost model: what a KV swap-in adds to a resume,
    and what an offload prefetch must hide under a step."""
    spec = spec or device_spec()
    return float(nbytes) / max(spec.host_bandwidth, 1.0)


def device_spec(device: Any = None) -> DeviceSpec:
    """Spec for ``device`` (default: first visible device).  An unknown TPU
    kind raises; non-TPU backends get the labelled CPU fallback."""
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = str(getattr(device, "device_kind", "cpu"))
    return _resolve(kind, getattr(device, "platform", "cpu") == "tpu"
                    or "tpu" in kind.lower())


def peak_flops_per_chip(device: Any = None) -> float:
    """bf16 peak FLOP/s for one chip (the MFU denominator)."""
    return device_spec(device).peak_flops


def roofline_report(flops: float, bytes_accessed: float, seconds: float,
                    n_devices: int = 1,
                    spec: Optional[DeviceSpec] = None) -> Dict[str, Any]:
    """Achieved-vs-peak summary for one step.

    ``flops``/``bytes_accessed`` are whole-program (all devices) per step;
    utilization is computed per chip.  Returns plain floats so the dict can
    land in a telemetry event or a metrics snapshot unmodified.
    """
    spec = spec or device_spec()
    n = max(int(n_devices), 1)
    dt = max(float(seconds), 1e-12)
    achieved = flops / dt / n                   # FLOP/s per chip
    hbm = bytes_accessed / dt / n               # bytes/s per chip
    ai = flops / max(bytes_accessed, 1.0)       # flops per byte
    return {
        "device_kind": spec.kind,
        "peak_tflops": spec.peak_flops / 1e12,
        "peak_hbm_gbps": spec.hbm_bandwidth / 1e9,
        "achieved_tflops": achieved / 1e12,
        "mfu": achieved / spec.peak_flops,
        "hbm_gbps": hbm / 1e9,
        "hbm_utilization": hbm / spec.hbm_bandwidth,
        "arithmetic_intensity": ai,
        "ridge_intensity": spec.ridge_intensity,
        "bound": "compute" if ai >= spec.ridge_intensity else "memory",
        "step_time_s": float(seconds),
        "flops_per_step": float(flops),
        "bytes_per_step": float(bytes_accessed),
        "n_devices": n,
    }


def publish_gauges(metrics, report: Dict[str, Any]) -> None:
    """Mirror a roofline report into ``roofline/*`` gauges (labelled by
    device kind) so Prometheus snapshots and the run summary see it."""
    kind = str(report.get("device_kind", "?"))
    for key in ("achieved_tflops", "mfu", "hbm_gbps", "hbm_utilization",
                "arithmetic_intensity", "peak_tflops", "step_time_s"):
        v = report.get(key)
        if isinstance(v, (int, float)):
            metrics.gauge(f"roofline/{key}").set(float(v), device=kind)


# --------------------------------------------------------------------- #
# Per-kernel rooflines (%-of-peak per kernel family — the kernel_sweep
# bench, the engine's decode-window publication, and the dstpu-telemetry
# "kernels" section all consume this one report shape)
# --------------------------------------------------------------------- #
def kernel_roofline_report(name: str, flops: float, bytes_accessed: float,
                           seconds: float,
                           spec: Optional[DeviceSpec] = None
                           ) -> Dict[str, Any]:
    """%-of-peak roofline for ONE kernel invocation (or a timed batch of
    identical invocations — pass summed flops/bytes and total seconds).

    Both peaks are reported: compute-bound kernels (flash, fused-gemm)
    read ``pct_peak_flops``; bandwidth-bound kernels (decode page walk,
    the quantized wire) read ``pct_peak_hbm``.  ``bound`` names which side
    of the ridge the kernel's arithmetic intensity puts it on — the
    honest denominator for "is this kernel fast".
    """
    spec = spec or device_spec()
    dt = max(float(seconds), 1e-12)
    ai = flops / max(bytes_accessed, 1.0)
    tflops = flops / dt / 1e12
    gbps = bytes_accessed / dt / 1e9
    return {
        "kernel": str(name),
        "device_kind": spec.kind,
        "tflops": tflops,
        "hbm_gbps": gbps,
        "pct_peak_flops": 100.0 * (flops / dt) / spec.peak_flops,
        "pct_peak_hbm": 100.0 * (bytes_accessed / dt) / spec.hbm_bandwidth,
        "arithmetic_intensity": ai,
        "bound": "compute" if ai >= spec.ridge_intensity else "memory",
        "seconds": float(seconds),
        "flops": float(flops),
        "bytes": float(bytes_accessed),
    }


def publish_kernel_gauges(metrics, report: Dict[str, Any]) -> None:
    """Mirror a per-kernel roofline into ``kernels/*`` gauges (labelled by
    kernel + device kind) — the same publication pattern as the
    ``serving/*`` decode gauges, rendered by ``dstpu-telemetry``'s
    kernels section."""
    kind = str(report.get("device_kind", "?"))
    kname = str(report.get("kernel", "?"))
    for key in ("tflops", "hbm_gbps", "pct_peak_flops", "pct_peak_hbm",
                "arithmetic_intensity"):
        v = report.get(key)
        if isinstance(v, (int, float)):
            metrics.gauge(f"kernels/{key}").set(float(v), kernel=kname,
                                                device=kind)


def format_kernel_table(reports) -> list:
    """Human lines for a set of per-kernel roofline reports (the
    kernel_sweep stderr trace and the telemetry summary share this)."""
    lines = [f"{'kernel':<24}{'TFLOP/s':>10}{'%flops':>8}{'GB/s':>10}"
             f"{'%hbm':>8}{'bound':>9}"]
    for r in reports:
        lines.append(
            f"{str(r.get('kernel', '?')):<24}"
            f"{r.get('tflops', 0.0):>10.3f}"
            f"{r.get('pct_peak_flops', 0.0):>7.2f}%"
            f"{r.get('hbm_gbps', 0.0):>10.2f}"
            f"{r.get('pct_peak_hbm', 0.0):>7.2f}%"
            f"{str(r.get('bound', '?')):>9}")
    return lines


def format_roofline_line(report: Dict[str, Any]) -> str:
    """One human line: the MFU headline the run summary and the profiler
    report both print."""
    return (f"roofline [{report['device_kind']}]: "
            f"{report['achieved_tflops']:.1f}/{report['peak_tflops']:.0f} "
            f"TFLOP/s/chip (MFU {report['mfu']*100:.1f}%), "
            f"HBM {report['hbm_gbps']:.0f} GB/s "
            f"({report['hbm_utilization']*100:.1f}%), "
            f"AI {report['arithmetic_intensity']:.1f} fl/B "
            f"(ridge {report['ridge_intensity']:.1f}) — "
            f"{report['bound']}-bound")
