"""Flops profiler (reference: profiling/flops_profiler/profiler.py:30).

The reference monkey-patches ``torch.nn.functional`` to count MACs per module.
The TPU-native equivalent is exact and non-invasive: JAX traces the model to a
jaxpr/HLO, and XLA's cost analysis reports flops/bytes for the *compiled*
program — including fusion effects the reference can't see.  Three layers:

  * :func:`profile_fn` — static analysis of any jittable fn (flops, bytes
    accessed, peak memory estimate) via ``compiled.cost_analysis()``,
    hardened against jax-version drift (list-shaped cost analysis, missing
    memory-analysis fields) — it returns ``0.0`` keys, never raises for an
    omitted field;
  * :class:`FlopsProfiler` — engine-integrated stateful profiler with the
    reference's start/stop/print API; flops come from the engine's cached
    compiled-step cost analysis (``engine.train_step_cost()``), latency from
    wall clock;
  * the report: a per-module cost tree from jaxpr named-scope attribution
    (``profiling/module_tree.py``) plus a roofline/MFU line
    (``profiling/roofline.py``), printed through the single
    :func:`emit_report` seam (the one place profiler output may ``print``;
    the no-bare-print lint allowlists exactly that function) and mirrored as
    a structured ``profile_report`` telemetry event.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from ...utils.logging import log_dist, logger


def compiled_cost_stats(compiled: Any) -> Dict[str, float]:
    """Flops/bytes/memory stats off a compiled executable, tolerating every
    known jax-version shape: ``cost_analysis()`` returning a dict, a
    [dict] list, ``None``, or raising; ``memory_analysis()`` missing
    entirely or lacking fields.  Every key is always present (0.0 when XLA
    omits the figure) so callers never need their own guards."""
    try:
        cost = compiled.cost_analysis()
    except Exception as e:  # noqa: BLE001 — backend-dependent availability
        logger.debug(f"cost_analysis unavailable: {e}")
        cost = None
    if isinstance(cost, (list, tuple)):  # older jax returns [dict]
        cost = cost[0] if cost else None
    if not isinstance(cost, dict):
        cost = {}

    def _pos(key: str) -> float:
        try:
            v = float(cost.get(key, 0.0))
        except (TypeError, ValueError):
            return 0.0
        return v if v > 0 else 0.0   # XLA reports -1 for "unknown"

    out = {
        "flops": _pos("flops"),
        "bytes_accessed": _pos("bytes accessed"),
        "transcendentals": _pos("transcendentals"),
    }
    mem = None
    try:
        mem = compiled.memory_analysis()
    except Exception as e:  # noqa: BLE001
        logger.debug(f"memory_analysis unavailable: {e}")
    out["peak_memory_bytes"] = float(
        getattr(mem, "temp_size_in_bytes", 0) +
        getattr(mem, "argument_size_in_bytes", 0) +
        getattr(mem, "output_size_in_bytes", 0)) if mem is not None else 0.0
    return out


def profile_fn(fn: Callable, *args, static_argnums=()) -> Dict[str, float]:
    """Compile ``fn`` and pull XLA cost analysis (AOT — never executes)."""
    lowered = jax.jit(fn, static_argnums=static_argnums).lower(*args)
    return compiled_cost_stats(lowered.compile())


def num_params(params: Any) -> int:
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(params)))


def emit_report(text: str, output_file: Optional[str] = None) -> None:
    """THE output seam for profiler reports.

    Rank 0 only (every output — a shared output_file must not collect one
    interleaved copy per host): prints to STDERR (the profiler runs inside
    training processes whose stdout may be a protocol, e.g. the one JSON
    line of ``benchmark/run.py``; the lint exempts ``emit_report`` by name — keep
    all profiler printing here), appends to ``output_file`` when given, and
    mirrors the report into the telemetry event log when one is active.
    """
    import sys

    from ...telemetry import emit_event

    rank = 0
    try:
        rank = jax.process_index()
    except Exception:  # noqa: BLE001 — uninitialized backend
        pass
    if rank != 0:
        return
    if output_file:
        with open(output_file, "a") as f:
            f.write(text + "\n")
    emit_event("profile_report_text", text=text)
    print(text, file=sys.stderr, flush=True)


class FlopsProfiler:
    """Engine-facing profiler with the reference API (start/stop/print)."""

    def __init__(self, model=None, ds_engine=None, recompute_fwd_factor: float = 0.0):
        self.model = model
        self.ds_engine = ds_engine
        self.recompute_fwd_factor = recompute_fwd_factor
        self.started = False
        self._t0 = 0.0
        self.latency = 0.0
        self.flops = 0.0                 # global program, per step
        self.flops_per_device = 0.0      # one chip's share (MFU numerator)
        self.bytes_accessed = 0.0        # per device (cost-analysis figure)
        self.params = 0

    def start_profile(self, ignore_list=None):
        """Arm the profiler: snapshot params and the compiled step's cost.

        The cost comes from ``engine.train_step_cost()`` — an AOT
        lower+compile of the *already-jitted* step fn, which hits XLA's
        executable cache after the first real step (measured ~50ms, not a
        recompile).  The old path read a ``_cached_cost`` attribute nothing
        ever wrote, silently reporting 0 FLOPs.
        """
        self.started = True
        self._t0 = time.perf_counter()
        if self.ds_engine is not None:
            self.params = num_params(self.ds_engine.state.params)
            try:
                stats = self.ds_engine.train_step_cost()
            except Exception as e:  # noqa: BLE001 — profiling is best-effort
                logger.warning(f"flops profiler: step cost unavailable: {e}")
                stats = None
            if stats:
                self._absorb_stats(stats)

    def _absorb_stats(self, stats: Dict[str, float]) -> None:
        self.flops = stats.get("flops", 0.0)
        self.flops_per_device = stats.get("flops_per_device", self.flops)
        self.bytes_accessed = stats.get(
            "bytes_accessed_per_device", stats.get("bytes_accessed", 0.0))

    def stop_profile(self):
        if self.started:
            self.latency = time.perf_counter() - self._t0
            self.started = False

    def get_total_flops(self, as_string: bool = False):
        return _fmt(self.flops, "FLOPS") if as_string else self.flops

    def get_total_params(self, as_string: bool = False):
        return _fmt(self.params, "") if as_string else self.params

    def get_total_duration(self, as_string: bool = False):
        return f"{self.latency:.3f} s" if as_string else self.latency

    def profile_engine_step(self, batch, pre_reshaped: bool = False) -> Dict[str, float]:
        """Cost analysis of the engine's compiled train step on ``batch``
        (a flat global batch unless ``pre_reshaped`` — the engine passes the
        [gas, micro, ...] view its step fn actually receives)."""
        eng = self.ds_engine
        assert eng is not None
        gas = eng.gradient_accumulation_steps()
        if gas > 1 and not pre_reshaped:
            batch = jax.tree.map(
                lambda x: x.reshape((gas, x.shape[0] // gas) + x.shape[1:]),
                batch)
        struct = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
        stats = dict(eng.train_step_cost(batch_struct=struct) or {})
        stats["params"] = num_params(eng.state.params)
        self._absorb_stats(stats)
        self.params = stats["params"]
        return stats

    # ---------------------------------------------------------------- #
    def _roofline(self) -> Optional[Dict[str, Any]]:
        if self.latency <= 0 or self.flops <= 0:
            return None
        from ..roofline import roofline_report

        # one chip's work against one chip's roofline
        return roofline_report(self.flops_per_device or self.flops,
                               self.bytes_accessed, self.latency,
                               n_devices=1)

    def _module_profile(self):
        if self.ds_engine is None:
            return None
        from ..module_tree import attribute_engine_step

        return attribute_engine_step(self.ds_engine)

    def print_model_profile(self, profile_step=1, module_depth=-1,
                            top_modules=0, detailed=True, output_file=None):
        """The reference's model-profile report: headline totals, the
        roofline/MFU line, and the per-module jaxpr cost tree.  Also emits a
        structured ``profile_report`` telemetry event so
        ``bin/dstpu-telemetry`` can reprint it offline."""
        from ...telemetry import emit_event

        lat = (f"latency={self.latency:.3f}s" if self.latency > 0 else
               "latency=n/a (warmup step — steady-state MFU is in the "
               "roofline/* gauges)")
        lines = [(f"flops profiler: params={_fmt(self.params, '')} "
                  f"flops/step={_fmt(self.flops, 'FLOPS')} "
                  f"MACs/step={_fmt(self.flops / 2, 'MACs')} {lat}")]
        roof = self._roofline()
        if roof is not None:
            from ..roofline import format_roofline_line

            lines.append(format_roofline_line(roof))
        rows = None
        if detailed:
            try:
                prof = self._module_profile()
            except Exception as e:  # noqa: BLE001 — report what we can
                logger.warning(f"per-module tree unavailable: {e}")
                prof = None
            if prof is not None:
                from ..module_tree import format_module_table

                lines.append("--- per-module cost tree ---")
                lines += format_module_table(prof, max_depth=module_depth,
                                             top_modules=top_modules)
                rows = prof.rows(max_depth=module_depth)
        msg = "\n".join(lines)
        emit_event("profile_report", step=profile_step, flops=self.flops,
                   params=self.params, latency_s=self.latency,
                   bytes_accessed=self.bytes_accessed, roofline=roof,
                   module_rows=rows)
        emit_report(msg, output_file=output_file)
        log_dist(f"flops profiler report emitted (step {profile_step})",
                 ranks=[0])
        return msg

    def end_profile(self):
        self.stop_profile()


def model_profile_tree(cfg, measured_total: float = 0.0,
                       seq_len: int = None) -> Dict[str, Any]:
    """Analytic per-module flops/params breakdown for a TransformerConfig-
    style model — the closed-form fallback when no engine/jaxpr is available
    (e.g. profiling a config that was never instantiated).  The jaxpr-based
    tree (``profiling/module_tree.py``) is the primary path.
    """
    D, F, L, V = (cfg.hidden_size, cfg.intermediate_size, cfg.num_layers,
                  cfg.vocab_size)
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    S = seq_len or cfg.max_seq_len
    E = getattr(cfg, "num_experts", 1)
    k = getattr(cfg, "moe_top_k", 2) if E > 1 else 1

    qkv_p = D * (H + 2 * KV) * hd
    o_p = H * hd * D
    attn_mm = 2 * 2 * S * H * hd            # QK^T + PV per token
    mlp_p = 3 * D * F * (E if E > 1 else 1)
    mlp_active = 3 * D * F * k              # routed experts actually used
    per_layer = {
        "attention": {
            "params": qkv_p + o_p,
            "flops": 2 * (qkv_p + o_p) + attn_mm,
        },
        "mlp" + (f" (moe x{E}, top-{k})" if E > 1 else ""): {
            "params": mlp_p,
            "flops": 2 * mlp_active,
        },
        "norms": {"params": 2 * D, "flops": 8 * D},
    }
    layer_flops = sum(m["flops"] for m in per_layer.values())
    tree = {
        "embed": {"params": V * D, "flops": 0},
        f"layers (x{L})": {
            "params": L * sum(m["params"] for m in per_layer.values()),
            "flops": L * layer_flops,
            "children": per_layer,
        },
        "lm_head": {"params": 0 if cfg.tie_embeddings else V * D,
                    "flops": 2 * V * D},
    }
    total_flops = sum(m["flops"] for m in tree.values())
    for m in tree.values():
        m["pct"] = 100.0 * m["flops"] / max(total_flops, 1)
        for c in m.get("children", {}).values():
            c["pct"] = 100.0 * c["flops"] / max(layer_flops, 1)
    tree["_total"] = {"analytic_fwd_flops_per_token": total_flops,
                      "measured_step_flops": measured_total}
    return tree


def format_profile_tree(tree: Dict[str, Any], indent: int = 2) -> list:
    lines = []
    for name, node in tree.items():
        if name == "_total":
            lines.append(f"analytic fwd flops/token: "
                         f"{_fmt(node['analytic_fwd_flops_per_token'], '')}")
            continue
        lines.append(" " * indent +
                     f"{name}: params={_fmt(node['params'], '')} "
                     f"flops/token={_fmt(node['flops'], '')} "
                     f"({node.get('pct', 0):.1f}%)")
        for cname, c in node.get("children", {}).items():
            lines.append(" " * indent * 2 +
                         f"{cname}: params={_fmt(c['params'], '')} "
                         f"({c.get('pct', 0):.1f}% of layer)")
    return lines


def get_model_profile(model_fn: Callable, args=(), kwargs=None, print_profile=True,
                      detailed=True, as_string=True):
    """Reference helper (profiler.py bottom): one-shot fn profile."""
    kwargs = kwargs or {}
    stats = profile_fn(lambda *a: model_fn(*a, **kwargs), *args)
    flops = stats["flops"]
    macs = flops / 2
    if print_profile:
        logger.info(f"flops={_fmt(flops, 'FLOPS')} macs={_fmt(macs, 'MACs')}")
    if as_string:
        return _fmt(flops, "FLOPS"), _fmt(macs, "MACs"), None
    return flops, macs, None


def _fmt(x: float, unit: str) -> str:
    for scale, suffix in [(1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")]:
        if abs(x) >= scale:
            return f"{x / scale:.2f} {suffix}{unit}"
    return f"{x:.2f} {unit}"
