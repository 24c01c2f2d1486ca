"""DeepSpeedEngine — the central training wrapper, TPU-native.

Reference analogue: ``deepspeed/runtime/engine.py:184`` (forward :1926,
backward :2085, step :2282, save/load checkpoint :2872-3756).

Architecture: the engine owns a functional :class:`EngineState` (params,
optimizer state, loss-scaler state, grad-accumulation buffer, RNG) laid out on
the device mesh according to the ZeRO stage's sharding plan
(:mod:`deepspeed_tpu.runtime.zero.sharding`).  Two execution paths:

  * **Fused path** — ``train_batch(batch)``: one jitted update covering all
    gradient-accumulation micro-steps via ``lax.scan``, loss scaling, global
    clipping, optimizer update, scheduler.  This is the fast path: XLA overlaps
    the ZeRO collectives (param allgather / grad reduce-scatter) with compute,
    which is what the reference's overlap_comm/prefetch machinery does by hand.
  * **Imperative path** — ``forward``/``backward``/``step`` matching the
    reference's micro-batch loop API: ``backward(batch)`` accumulates grads
    into the state buffer; ``step()`` applies the update only at the
    grad-accumulation boundary.

Mixed precision follows the bf16-optimizer design (runtime/bf16_optimizer.py):
fp32 master params in optimizer space, compute in ``config.dtype`` via cast at
forward entry, grads accumulated in fp32.
"""
from __future__ import annotations

import math
import os
import time
import weakref
from functools import partial
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax.sharding import NamedSharding, PartitionSpec

from ..accelerator import get_accelerator
from ..telemetry import emit_event
from ..telemetry.goodput import get_goodput_ledger, record_goodput
from ..telemetry.trace import NULL_SPAN, get_tracer, traced
from ..utils.logging import log_dist, logger
from ..utils.timer import SynchronizedWallClockTimer, ThroughputTimer
from .activation_checkpointing import checkpointing as _act_ckpt
from .config import DeepSpeedConfig
from .fault import injection as fault_injection
from .fp16.loss_scaler import LossScaler, LossScalerState, create_loss_scaler
from .lr_schedules import build_scheduler, get_schedule_fn
from .optimizer import build_optimizer
from .topology import MeshTopology, get_topology
from .zero.sharding import ZeroShardingPlan


@struct.dataclass
class EngineState:
    """All mutable training state, as one sharded pytree."""

    global_step: jnp.ndarray       # optimizer steps taken
    micro_step: jnp.ndarray        # micro batches seen
    skipped_steps: jnp.ndarray     # overflow-skipped optimizer steps
    params: Any                    # fp32 master params (sharded per plan)
    opt_state: Any
    scaler: LossScalerState
    grad_acc: Any                  # fp32 grad accumulation buffer (or None)
    rng: jax.Array
    comm_error: Any = None         # LoCo error feedback (explicit-comm path)
    #: state the model declares and the optimizer does NOT train (a routing
    #: bias the step balances, counters): replicated, updated inside the
    #: compiled step by the model's own rule, saved with a checkpoint
    model_state: Any = None


def _tree_zeros_like(tree, dtype=jnp.float32):
    return jax.tree.map(lambda x: jnp.zeros(x.shape, dtype), tree)


def _global_norm(tree):
    leaves = [jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves))) if leaves else jnp.zeros((), jnp.float32)


class DeepSpeedEngine:
    @traced("engine/init")      # placing the state; its compiles inside
    def __init__(
        self,
        model: Any,
        config: DeepSpeedConfig,
        topology: Optional[MeshTopology] = None,
        model_parameters: Any = None,
        optimizer: Any = None,
        lr_scheduler: Any = None,
        training_data: Any = None,
        collate_fn: Optional[Callable] = None,
        seed: int = 0,
        dont_change_device: bool = False,
    ):
        self.config = config
        self.topology = topology or get_topology()
        self.mesh = self.topology.mesh
        self.module = model

        # ---- telemetry (must precede the timers that feed it) --------- #
        # Installed process-globally so module-level instrumentation (comm
        # facade, monitor fan-out, fault counters, checkpoint engine) can
        # reach it; disabled = None, and every hot-path site guards on that.
        self.telemetry = None
        tcfg = getattr(config, "telemetry", None)
        if tcfg is not None and tcfg.enabled:
            from ..telemetry import Telemetry, set_telemetry

            self.telemetry = Telemetry.from_config(tcfg)
            set_telemetry(self.telemetry)
        self._host_step_calls = 0   # host-side step counter (no device sync)

        # ---- comm/compute overlap (config.overlap) -------------------- #
        # Effective settings + overlap/* gauges + the auto-mode re-tune
        # live in the manager; step builders (fused scan and comm_path)
        # consult it at trace time.
        from .overlap import OverlapManager
        from .overlap.prefetch import GatherWindowCache

        self.overlap = OverlapManager.from_config(config,
                                                  telemetry=self.telemetry)
        self._gather_cache = GatherWindowCache()
        self._deferred_active = False
        # slice model override (CPU sim / tests): which mesh axes cross a
        # DCN boundary — feeds the 2-hop hierarchical collectives
        csa = getattr(config.overlap, "cross_slice_axes", None)
        if csa:
            self.topology.set_cross_slice_axes(
                [a.strip() for a in str(csa).split(",") if a.strip()])

        self._timers = SynchronizedWallClockTimer(telemetry=self.telemetry)
        self.tput_timer = ThroughputTimer(
            batch_size=config.train_batch_size or 1,
            steps_per_output=config.steps_per_print,
            logging_fn=lambda m: log_dist(m, ranks=[0]),
            telemetry=self.telemetry)

        # ---- debug mode (SURVEY §5 determinism/NaN-check ask) --------- #
        # These toggle PROCESS-GLOBAL jax config (debug modes are process
        # properties, like the reference's env-driven sanitizers); call
        # DeepSpeedEngine.reset_debug_mode() to clear them.
        if getattr(config, "debug_deterministic", False):
            # bitwise-reproducible runs: pin matmul precision (XLA's TPU
            # default is already deterministic given fixed precision/seeds)
            jax.config.update("jax_default_matmul_precision", "highest")
            log_dist("debug.deterministic: matmul precision pinned to "
                     "highest (process-global); PRNG is counter-based",
                     ranks=[0])
        if getattr(config, "debug_nan_check", False):
            # raise at the op producing the first NaN instead of training on
            jax.config.update("jax_debug_nans", True)
            log_dist("debug.nan_check: jax_debug_nans enabled "
                     "(process-global)", ranks=[0])
        # graph lint (dstpu-check): run the registered jaxpr passes over
        # the train step jaxpr at first trace; "error" aborts BEFORE the
        # first dispatch — catch the GSPMD replica-group / 0×NaN classes
        # mechanically instead of bisecting a 4x-wrong tensor at runtime
        self._graph_lint_mode = getattr(config, "debug_graph_lint", False)
        self._graph_lint_done = False

        self.loss_fn = self._resolve_loss_fn(model)
        self.compute_dtype = config.dtype
        self.zero_stage = config.zero_config.stage
        self.plan = ZeroShardingPlan(
            self.topology, self.zero_stage,
            param_persistence_threshold=config.zero_config.param_persistence_threshold,
            base_specs=getattr(model, "partition_specs", None))

        # ---- params ------------------------------------------------- #
        params = model_parameters
        if params is None:
            params = getattr(model, "params", None)
        if params is None:
            raise ValueError("model_parameters (a pytree) is required")
        params = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params)

        # ---- optimizer + schedule ----------------------------------- #
        self.client_optimizer = optimizer
        self.lr_scheduler = lr_scheduler
        self._schedule_fn = self._resolve_schedule()
        self.optimizer = self._resolve_optimizer(optimizer)

        # ---- loss scaling ------------------------------------------- #
        self.loss_scaler: LossScaler = create_loss_scaler(config.fp16, self.compute_dtype)

        # ---- state layout + placement -------------------------------- #
        self.param_shardings = self.plan.param_shardings(params)
        params = jax.device_put(params, self.param_shardings)
        opt_shardings = self.plan.opt_state_shardings(
            jax.eval_shape(self.optimizer.init, params), params)
        # ZeRO-Offload: optimizer state lives in pinned host memory; XLA
        # streams it through the update (reference: cpu-Adam on host,
        # offload_config 'device: cpu').  ratio<1 = Twin-Flow (Offload++):
        # each state leaf is SPLIT along dim 0 — the leading (1-ratio)
        # fraction stays in HBM, the trailing ratio streams from pinned
        # host at step time (zero/twin_flow.py).
        self._twin_flow_bytes = None
        self._offload_prefetcher = None
        if config.zero_config.offload_optimizer_device() == "cpu":
            ratio = float(config.zero_config.offload_optimizer.ratio)
            if 0.0 < ratio < 1.0:
                from .zero.twin_flow import build_twin_flow

                self.optimizer, opt_shardings, self._twin_flow_bytes = \
                    build_twin_flow(self.optimizer, ratio, params, self.plan,
                                    self.mesh)
            else:
                opt_shardings = jax.tree.map(self._to_host_memory,
                                             opt_shardings)
            # offload_optimizer.pipeline_read: double-buffer the host
            # partition toward the device between steps (ZeRO-Infinity's
            # pipelined swap-in) so the H2D leg hides under fwd/bwd instead
            # of serializing before the sharded update.  A no-op on CPU sim
            # (bitwise-identity — the offload-vs-resident loss equality
            # test rides that).
            if config.zero_config.offload_optimizer is not None and \
                    config.zero_config.offload_optimizer.pipeline_read:
                from .swap_tensor.host_tier import HostOffloadPrefetcher

                self._offload_prefetcher = HostOffloadPrefetcher()
        opt_state = jax.jit(self.optimizer.init, out_shardings=opt_shardings)(params)

        gas = config.gradient_accumulation_steps
        grad_acc = None
        if gas > 1:
            grad_acc = jax.jit(
                partial(_tree_zeros_like, dtype=jnp.float32),
                out_shardings=jax.tree.map(
                    lambda s: NamedSharding(self.mesh, s), self.plan.grad_specs(params),
                    is_leaf=lambda x: isinstance(x, PartitionSpec)),
            )(params)

        # Explicit-comm path (ZeRO++ quantized wires / sparse grads): the
        # shard_map step in comm_path.py replaces XLA's inserted collectives.
        zc = config.zero_config
        # qwZ only matters at stage 3 (below it params are replicated — no
        # allgather exists to quantize); don't reroute training for a no-op.
        self._explicit_comm = bool(
            (zc.zero_quantized_weights and self.zero_stage >= 3)
            or zc.zero_quantized_gradients
            or getattr(config, "sparse_gradients_enabled", False)
            # overlap.explicit_wire: hand-written (deferred + bucketed)
            # exchanges replace the XLA-inserted collectives even without
            # quantized/sparse wire formats
            or (self.overlap.enabled and self.overlap.explicit_wire))
        if zc.zero_quantized_weights and self.zero_stage < 3:
            logger.warning("zero_quantized_weights ignored below ZeRO stage 3")
        # ---- model state the optimizer does not train ---------------- #
        # A model may declare ``init_model_state()`` and ``next_model_state(
        # state, counted)``: its ``loss_fn(params, batch, rng, state)`` then
        # returns ``(loss, counted)`` and the fused step maps (state, what
        # the step's micro-batches counted, added up) to the next state —
        # no gradient, no weight decay, no clipping, no loss scale, no host
        # sync.  A model that declares none compiles the program it always
        # did (``None`` has no leaves).
        self._model_rule = getattr(model, "next_model_state", None) \
            if hasattr(model, "init_model_state") else None
        if self._model_rule is not None and self._explicit_comm:
            raise NotImplementedError(
                "a model that declares init_model_state() trains through "
                "the fused train_batch() step only, not the explicit-comm "
                "path (quantized wires, sparse gradients, explicit_wire)")
        comm_error = None
        if zc.zero_quantized_gradients and getattr(zc, "zeropp_loco", False):
            from .comm.hierarchical import hop_axes, two_hop_loco_sizes
            from .comm_path import dp_axes_info, loco_partition_size

            axes, n_dp, dp_entry = dp_axes_info(self.topology)
            err_spec = PartitionSpec(dp_entry)

            # 2-hop LoCo (explicit overlap.hierarchical: "on" only — auto
            # never moves residual state between algorithms): the quantized
            # exchange runs on the intra-slice-reduced partition, so both
            # residuals live there (comm/hierarchical.two_hop_loco_sizes).
            intra, inter = hop_axes(self.topology, axes)
            loco_2hop = bool(self.overlap.enabled
                             and self.overlap.hierarchical == "on"
                             and intra and inter)
            n_i = int(np.prod([self.topology.dims[a] for a in intra])) \
                if intra else 1
            n_x = int(np.prod([self.topology.dims[a] for a in inter])) \
                if inter else 1

            # Two-level LoCo state (reference loco variant): stage-1 worker
            # residual per local contribution, stage-2 server residual per
            # reduced partition; leading axis = one row per DP rank.
            def _mk_error(x):
                numel = int(np.prod(x.shape))
                if loco_2hop:
                    worker, server = two_hop_loco_sizes(numel, n_i, n_x)
                    return {"worker": jnp.zeros((n_dp, worker), jnp.float32),
                            "server": jnp.zeros((n_dp, server), jnp.float32)}
                per = loco_partition_size(numel, n_dp)
                return {"worker": jnp.zeros((n_dp,) + x.shape, jnp.float32),
                        "server": jnp.zeros((n_dp, per), jnp.float32)}

            comm_error = jax.jit(
                lambda p: jax.tree.map(_mk_error, p),
                out_shardings=NamedSharding(self.mesh, err_spec),
            )(params)

        # The counters, the scaler and the RNG key are placed replicated on
        # the mesh, as the step returns them: left on one device they make
        # the SECOND train_batch call see new input shardings and compile
        # the whole step again (seen on four chips: two compiles per engine).
        replicated = NamedSharding(self.mesh, PartitionSpec())
        on_mesh = lambda tree: jax.device_put(tree, replicated)  # noqa: E731
        self.state = EngineState(
            global_step=on_mesh(jnp.zeros((), jnp.int32)),
            micro_step=on_mesh(jnp.zeros((), jnp.int32)),
            skipped_steps=on_mesh(jnp.zeros((), jnp.int32)),
            params=params,
            opt_state=opt_state,
            scaler=on_mesh(self.loss_scaler.init()),
            grad_acc=grad_acc,
            rng=on_mesh(jax.random.PRNGKey(seed)),
            comm_error=comm_error,
            model_state=None if self._model_rule is None
            else on_mesh(model.init_model_state()),
        )

        self._device_memory = self._account_device_memory()

        # ---- data ---------------------------------------------------- #
        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data, collate_fn=collate_fn)

        # ---- activation checkpointing policy (config → model remat) --- #
        # configure() sets the module-level policy the models' remat sites
        # consult at trace time, so a DS-JSON activation_checkpointing
        # block changes the compiled program (save-sharded / offload-to-
        # host residuals instead of full recompute).  Only an EXPLICIT
        # block applies — engines without one must not clobber another
        # engine's or a manual configure() call's policy.
        if getattr(config, "activation_checkpointing_explicit", False):
            _act_ckpt.configure(deepspeed_config=config)

        # ---- compiled steps ------------------------------------------ #
        self._compiled: Dict[str, Any] = {}
        self._losses: list = []
        self.monitor = self._configure_monitor()
        self.watchdog = self._configure_watchdog()

        # ---- performance attribution (config.profiling) --------------- #
        # Cached compiled-step cost analysis + the last batch's shapes feed
        # train_step_cost(); the straggler detector compares per-step wall
        # time across hosts through the telemetry registry.
        self._step_cost: Optional[Tuple[Any, Dict[str, float]]] = None
        self._step_jaxpr: Optional[Tuple[Any, Any]] = None  # (shape key, jaxpr)
        self._last_batch_struct = None
        self._last_batch_shardings = None
        self._roofline_spec = None
        pcfg = getattr(config, "profiling", None)
        self._profiling_on = bool(pcfg is not None and (
            pcfg.enabled or pcfg.flops_profiler.enabled))
        self._straggler = None
        if pcfg is not None and pcfg.enabled and pcfg.straggler_detection \
                and self.telemetry is not None:
            from ..profiling.straggler import StragglerDetector

            self._straggler = StragglerDetector.from_config(
                pcfg, telemetry=self.telemetry)

        # ---- live observability plane (config.telemetry.live) --------- #
        # Host 0 serves /metrics /healthz /events /summary beside the
        # training loop; non-zero hosts push compact snapshots to it; the
        # anomaly detector rides _post_step_logging on every host.  All of
        # it host-side — the server/pusher threads never touch device
        # state (they read _last_logged_step, a host mirror).
        self._anomaly = None
        self._live_server = None
        self._live_pusher = None
        self._last_logged_step: Optional[int] = None
        if self.telemetry is not None:
            self._configure_live_plane(tcfg)

        log_dist(
            f"engine ready: zero_stage={self.zero_stage} dtype={self.compute_dtype.__name__} "
            f"mesh={self.topology.dims} batch={config.train_batch_size} "
            f"micro={config.train_micro_batch_size_per_gpu} gas={gas}", ranks=[0])

    # ------------------------------------------------------------------ #
    # Resolution helpers
    # ------------------------------------------------------------------ #
    def _account_device_memory(self) -> Tuple[int, int]:
        """``(bytes_limit, state bytes)`` of one device, for the model's
        choice of what a checkpointed layer saves
        (``activation_checkpointing.checkpointing.engine_memory``): what the
        backend says the device holds at most (0 where it reports nothing:
        the CPU) and what this engine keeps there through a step — the
        placed state, the compute-dtype copy of the parameters a step casts
        and the gradients its backward pass returns in that type.  From
        shapes and shardings alone, so the same on every run."""
        def shard_bytes(x, itemsize=None):
            if getattr(x.sharding, "memory_kind", None) == "pinned_host":
                return 0
            return math.prod(x.sharding.shard_shape(x.shape)) * (
                itemsize or x.dtype.itemsize)

        device = next(d for d in self.mesh.devices.flat
                      if d.process_index == jax.process_index())
        limit = (device.memory_stats() or {}).get("bytes_limit", 0)
        placed = sum(shard_bytes(x) for x in jax.tree.leaves(self.state))
        per_step = 2 * sum(
            shard_bytes(x, jnp.dtype(self.compute_dtype).itemsize)
            for x in jax.tree.leaves(self.state.params))
        return limit, placed + per_step

    def _resolve_loss_fn(self, model) -> Callable:
        """Accept a loss callable, or a flax-like module with .apply.

        Convention (mirrors the reference's "module forward returns loss"):
        ``loss_fn(params, batch, rng) -> loss`` or ``(loss, aux)``.
        """
        if hasattr(model, "loss_fn"):
            return model.loss_fn
        if callable(model) and not hasattr(model, "apply"):
            return model
        if hasattr(model, "apply"):
            def fn(params, batch, rng):
                return model.apply({"params": params}, batch, rngs={"dropout": rng})

            return fn
        raise TypeError(f"cannot derive loss fn from model {type(model)}")

    def _resolve_schedule(self):
        cfg = self.config
        base_lr = (cfg.optimizer.params.get("lr", 1e-3) if cfg.optimizer else 1e-3)
        if cfg.scheduler and cfg.scheduler.type:
            return get_schedule_fn(cfg.scheduler.type, cfg.scheduler.params, base_lr=base_lr)
        return lambda step: jnp.asarray(base_lr, jnp.float32)

    def _resolve_optimizer(self, optimizer):
        import optax

        if optimizer is not None and not isinstance(optimizer, optax.GradientTransformation):
            raise TypeError("client optimizer must be an optax.GradientTransformation")
        if optimizer is not None:
            return optimizer
        cfg = self.config.optimizer
        if cfg is None:
            return build_optimizer("adam", {}, learning_rate=self._schedule_fn)
        params = dict(cfg.params)
        if cfg.type.lower() in ("onebitadam", "onebitlamb", "zerooneadam"):
            # The fused engine step runs outside shard_map: grads arrive
            # already globally averaged (XLA-inserted collectives), so the
            # 1-bit transforms must not attempt their own named-axis comm.
            params.setdefault("comm_axes", ())
        return build_optimizer(cfg.type, params, learning_rate=self._schedule_fn)

    def _to_host_memory(self, sharding):
        """NamedSharding → pinned_host memory kind (TPU only: the CPU backend's
        SPMD partitioner rejects host-placement annotations)."""
        if jax.default_backend() != "tpu":
            from ..utils.logging import warning_once

            warning_once("offload_optimizer device=cpu: pinned_host placement "
                         "needs the TPU backend; optimizer state stays in "
                         "device memory on this backend")
            return sharding
        try:
            return sharding.with_memory_kind("pinned_host")
        except Exception:
            return sharding

    def _configure_monitor(self):
        try:
            from ..monitor.monitor import MonitorMaster

            return MonitorMaster(self.config)
        except Exception:
            return None

    def _configure_watchdog(self):
        """Heartbeat thread over the step loop (``config.fault``): dumps the
        last step/phase when a step or collective exceeds the deadline."""
        fcfg = getattr(self.config, "fault", None)
        if fcfg is None or not fcfg.watchdog_enabled:
            return None
        from .fault.watchdog import Watchdog

        wd = Watchdog(deadline_s=fcfg.watchdog_deadline_s,
                      raise_on_timeout=fcfg.watchdog_raise)
        return wd.start()

    def _configure_live_plane(self, tcfg) -> None:
        """Anomaly detector + live HTTP server (host 0) + snapshot pusher
        (non-zero hosts) from ``config.telemetry.live``.  A port clash or
        bad push URL degrades to a warning — observability must never keep
        a training job from starting."""
        lcfg = getattr(tcfg, "live", None)
        if lcfg is None:
            return
        from ..telemetry.live import (AnomalyDetector,
                                      LiveObservabilityServer,
                                      SnapshotPusher)

        acfg = lcfg.anomaly
        if acfg.enabled:
            self._anomaly = AnomalyDetector.from_config(
                acfg, telemetry=self.telemetry, action_target=self)
        if not lcfg.enabled:
            return
        try:
            host_id = jax.process_index()
        except Exception:  # noqa: BLE001 — no distributed runtime yet
            host_id = 0
        step_fn = lambda: self._last_logged_step  # noqa: E731 — host mirror
        if host_id == 0:
            try:
                self._live_server = LiveObservabilityServer.from_config(
                    lcfg, self.telemetry, watchdog=self.watchdog,
                    anomaly=self._anomaly, host_id=host_id, step_fn=step_fn,
                    steps_this_process_fn=lambda: self._host_step_calls,
                ).start()
            except (OSError, OverflowError, ValueError) as e:
                logger.warning(f"live observability server failed to bind "
                               f"{lcfg.bind}:{lcfg.port}: {e!r}; live "
                               f"endpoints disabled for this run")
        else:
            push_url = lcfg.push_url or os.environ.get("DSTPU_LIVE_PUSH_URL")
            if push_url:
                from ..telemetry.live import publish_elastic_gauges
                from .fault.retry import RetryPolicy

                # this host's restart state must ride its pushed snapshots
                # (host 0 publishes its own at server start)
                publish_elastic_gauges(self.telemetry.metrics)
                self._live_pusher = SnapshotPusher(
                    self.telemetry, push_url, host_id, step_fn=step_fn,
                    interval_s=lcfg.push_interval_s,
                    retry_policy=RetryPolicy.from_config(
                        getattr(self.config, "fault", None))).start()
            else:
                logger.warning("telemetry.live enabled on a non-zero host "
                               "with no push_url (or DSTPU_LIVE_PUSH_URL); "
                               "this host's series stay local")

    def _heartbeat(self, phase: str, step: Optional[int] = None):
        """Watchdog ping.  ``step`` must be a HOST-side int callers already
        have — reading ``state.global_step`` here would force a device sync
        on the hot path; with step=None the watchdog keeps its last value."""
        if self.watchdog is not None:
            self.watchdog.ping(step=step, phase=phase)

    def _span(self, name: str, sync=None, **attrs):
        """Telemetry span, or the shared no-op when telemetry is disabled —
        keeps instrumentation inline on the hot path at the cost of one
        ``is None`` check."""
        if self.telemetry is None:
            return NULL_SPAN
        return self.telemetry.span(name, sync=sync, **attrs)

    def _fence_span(self, sp, value) -> None:
        """Honor ``config.telemetry.fence``: make span ``sp`` block on
        ``value`` at exit so it measures device execution, not dispatch.
        The sync target (loss / updated state) only exists mid-span, hence
        post-hoc rather than at span creation."""
        if self.telemetry is not None and self.telemetry.fence:
            sp.fence_on(value)

    def close(self):
        """Release host-side resources (watchdog thread) and flush
        observability sinks (monitor writers, telemetry exports); engine
        state and compiled functions stay usable.  A model's state the
        optimizer does not train is fetched here, once, and what the model
        says of it goes on the tracer (``train/model_state``)."""
        self.report_model_state()
        if self.watchdog is not None:
            self.watchdog.stop()
            self.watchdog = None
        if self._live_pusher is not None:
            # final snapshot, single attempt (never raises): host 0 being
            # gone is a common reason we're closing — don't burn the whole
            # retry backoff budget blocking shutdown
            self._live_pusher.push_now(retry=False)
            self._live_pusher.stop()
            self._live_pusher = None
        if self._live_server is not None:
            try:
                self._live_server.stop()
            except Exception as e:
                logger.warning(f"live server stop failed: {e!r}")
            self._live_server = None
        if self.monitor is not None:
            try:
                self.monitor.flush()
            except Exception as e:
                logger.warning(f"monitor flush on close failed: {e!r}")
        if self.telemetry is not None:
            from ..telemetry import get_telemetry, set_telemetry

            try:
                self.telemetry.close()
            except Exception as e:
                logger.warning(f"telemetry flush on close failed: {e!r}")
            if get_telemetry() is self.telemetry:
                set_telemetry(None)
            self.telemetry = None

    def report_model_state(self) -> Optional[Dict[str, Any]]:
        """One ``train/model_state`` record on the process-global tracer:
        the model's own account (``model_state_report``) of the state the
        step carries for it, fetched from the device now — the only sync it
        ever costs, so a caller asks after its window, not inside."""
        report = getattr(self.module, "model_state_report", None)
        if report is None or self.state.model_state is None:
            return None
        t0 = time.perf_counter()
        attrs = report(jax.device_get(self.state.model_state))
        get_tracer().record("train/model_state", t0,
                            time.perf_counter() - t0, **attrs)
        return attrs

    # ------------------------------------------------------------------ #
    # Introspection API (reference names)
    # ------------------------------------------------------------------ #
    @property
    def global_steps(self) -> int:
        return int(self.state.global_step)

    @property
    def skipped_steps(self) -> int:
        return int(self.state.skipped_steps)

    @property
    def micro_steps(self) -> int:
        return int(self.state.micro_step)

    @property
    def global_samples(self) -> int:
        return self.micro_steps * self.train_micro_batch_size_per_gpu() * \
            self.topology.get_data_parallel_world_size()

    def train_batch_size(self) -> int:
        return self.config.train_batch_size

    def train_micro_batch_size_per_gpu(self) -> int:
        return self.config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self) -> int:
        return self.config.gradient_accumulation_steps

    def zero_optimization_stage(self) -> int:
        return self.zero_stage

    def get_lr(self):
        return [float(self._schedule_fn(self.state.global_step))]

    def get_loss_scale(self) -> float:
        return float(self.state.scaler.scale)

    def is_gradient_accumulation_boundary(self) -> bool:
        gas = self.gradient_accumulation_steps()
        return (self.micro_steps % gas) == 0 and self.micro_steps > 0

    def timers(self, name):
        return self._timers(name)

    # ------------------------------------------------------------------ #
    # Performance attribution (config.profiling)
    # ------------------------------------------------------------------ #
    def train_step_cost(self, batch_struct=None) -> Optional[Dict[str, float]]:
        """Cost of the fused train step: flops, bytes accessed, peak memory —
        the profiler's and bench's MFU numerator.

        Two sources, reconciled:

          * a scan-aware jaxpr walk (``utils/jaxpr_utils.total_flops``) of
            the *global* logical program — XLA's own cost analysis counts a
            while-loop body ONCE (verified empirically), so it undercounts
            scanned-layer models by ~num_layers·gas; the traced count
            multiplies trip counts back in;
          * ``compiled.cost_analysis()`` of the post-SPMD *per-device*
            module (an AOT ``lower().compile()`` of the already-jitted step
            fn — hits XLA's executable cache after the first real step, not
            a recompile), whose bytes/peak-memory figures reflect fusion.

        ``flops``/``bytes_accessed`` are GLOBAL (logical program);
        ``flops_per_device``/``bytes_accessed_per_device`` are one chip's
        share (the MFU numerator); ``flops_traced``/
        ``flops_compiled_per_device`` record provenance.  Returns None when
        no batch shape is known yet.  Cached per batch shape.
        """
        struct = batch_struct if batch_struct is not None \
            else self._last_batch_struct
        if struct is None:
            return None
        key = tuple((tuple(l.shape), str(l.dtype))
                    for l in jax.tree.leaves(struct))
        if self._step_cost is not None and self._step_cost[0] == key:
            return self._step_cost[1]
        from ..profiling.flops_profiler.profiler import compiled_cost_stats
        from ..utils.jaxpr_utils import total_flops_of_jaxpr

        if "train_batch" not in self._compiled:
            self._compiled["train_batch"] = self._build_train_batch_fn()
        state_struct = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.state)
        n_dev = max(self.topology.world_size(), 1)
        with self._span("profiling/step_cost"):
            fn = self._compiled["train_batch"]
            compiled = fn.lower(state_struct, struct).compile()
            cstats = compiled_cost_stats(compiled)
            traced = 0.0
            try:
                jaxpr = jax.make_jaxpr(fn)(state_struct, struct).jaxpr
                # cached for the module-tree walk — tracing the full step
                # costs seconds on large models; one trace serves both
                self._step_jaxpr = (key, jaxpr)
                traced = float(total_flops_of_jaxpr(jaxpr))
            except Exception as e:  # noqa: BLE001 — e.g. shard_map paths
                logger.debug(f"traced flop count unavailable: {e}")
        # MFU convention: the numerator is LOGICAL model flops — the traced
        # global count (scan-aware, matmul-exact).  compiled*n_dev would
        # count replicated work (e.g. an unsharded optimizer update) once
        # per device and still miss loop trip counts; it is only the
        # fallback when tracing failed.
        flops_global = traced if traced > 0 else cstats["flops"] * n_dev
        bytes_global = cstats["bytes_accessed"] * n_dev
        stats = {
            "flops": flops_global,
            "flops_per_device": flops_global / n_dev,
            "bytes_accessed": bytes_global,
            "bytes_accessed_per_device": cstats["bytes_accessed"],
            "flops_traced": traced,
            "flops_compiled_per_device": cstats["flops"],
            "transcendentals": cstats["transcendentals"],
            "peak_memory_bytes": cstats["peak_memory_bytes"],
        }
        self._step_cost = (key, stats)
        return stats

    def compiled_step_text(self) -> Optional[str]:
        """The compiled train step as the device runs it (``as_text()`` of
        the same lowering, so the executable comes from XLA's cache): what
        ``profiling/xprof_parse.parse_hlo_scopes`` reads instruction scopes
        from.  None before the first ``train_batch``."""
        if "train_batch" not in self._compiled or \
                self._last_batch_struct is None:
            return None
        placed = lambda x, sh: jax.ShapeDtypeStruct(  # noqa: E731
            x.shape, x.dtype, sharding=sh)
        state = jax.tree.map(
            lambda x: placed(x, getattr(x, "sharding", None)), self.state)
        leaves, treedef = jax.tree.flatten(self._last_batch_struct)
        batch = jax.tree.unflatten(treedef, [
            placed(x, sh) for x, sh in zip(leaves,
                                           self._last_batch_shardings)])
        return self._compiled["train_batch"].lower(
            state, batch).compile().as_text()

    def _publish_roofline(self, step: int) -> None:
        """Roofline/MFU gauges for the current steady state (``roofline/*``
        in the metrics registry; surfaced by ``bin/dstpu-telemetry``)."""
        from ..profiling import roofline

        dt = getattr(self.tput_timer, "last_step_time", 0.0)
        if not dt:
            return
        try:
            stats = self.train_step_cost()
        except Exception as e:  # noqa: BLE001 — attribution is best-effort
            logger.debug(f"roofline: step cost unavailable: {e}")
            return
        if not stats or not stats.get("flops"):
            return
        if self._roofline_spec is None:
            self._roofline_spec = roofline.device_spec()
        # per-device figures vs one chip's roofline
        report = roofline.roofline_report(
            stats["flops_per_device"],
            stats.get("bytes_accessed_per_device", 0.0), dt,
            n_devices=1, spec=self._roofline_spec)
        report["step"] = step
        roofline.publish_gauges(self.telemetry.metrics, report)

    # ------------------------------------------------------------------ #
    # Data
    # ------------------------------------------------------------------ #
    def deepspeed_io(self, dataset, batch_size=None, collate_fn=None, num_local_io_workers=None,
                     data_sampler=None, route=None):
        from .dataloader import DeepSpeedDataLoader

        return DeepSpeedDataLoader(
            dataset,
            batch_size=batch_size or self.train_micro_batch_size_per_gpu(),
            collate_fn=collate_fn,
            topology=self.topology)

    # ------------------------------------------------------------------ #
    # Core math (shared by both paths)
    # ------------------------------------------------------------------ #
    def _loss_and_grads(self, params, batch, rng, scaler_state,
                        constrain=True):
        """One micro-batch: cast → forward → scaled backward → fp32 grads.

        ``constrain=False`` skips the ZeRO grad-sharding constraint — the
        overlap deferred path applies it one scan iteration later (the
        reduce-scatter it induces then overlaps the next micro-batch's
        compute) instead of inline.
        """
        loss, grads, _ = self._loss_grads_counted(params, batch, rng,
                                                  scaler_state, constrain)
        return loss, grads

    def _loss_grads_counted(self, params, batch, rng, scaler_state,
                            constrain=True, model_state=None):
        """:meth:`_loss_and_grads`, and what the forward counted for the
        model's own state (``None`` for a model that declares none: its
        ``loss_fn`` is called as it always was)."""

        def scaled_loss(p32):
            # the masters' cast: under ZeRO-3 what follows it is the gather
            with jax.named_scope("zero/gather_params"):
                p = jax.tree.map(lambda x: x.astype(self.compute_dtype), p32)
            with _act_ckpt.engine_memory(*self._device_memory):
                if model_state is None:
                    out = self.loss_fn(p, batch, rng)
                else:
                    out = self.loss_fn(p, batch, rng, model_state)
            loss = out[0] if isinstance(out, tuple) else out
            counted = None if model_state is None else out[1]
            return self.loss_scaler.scale_loss(loss.astype(jnp.float32), scaler_state), \
                (loss, counted)

        grads, (loss, counted) = jax.grad(scaled_loss, has_aux=True)(params)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        if constrain:
            grads = self._constrain_grads(grads)
        return loss, grads, counted

    def _next_model_state(self, state: EngineState, new_state: EngineState,
                          counted):
        """The model's rule on (its state, what the step counted); a step
        the dynamic loss scale skipped leaves it as it was."""
        if state.model_state is None:
            return new_state
        nxt = self._model_rule(state.model_state, counted)
        if self.loss_scaler.dynamic:
            skipped = new_state.skipped_steps != state.skipped_steps
            nxt = jax.tree.map(lambda n, o: jnp.where(skipped, o, n), nxt,
                               state.model_state)
        return new_state.replace(model_state=nxt)

    def _constrain_grads(self, grads):
        """Apply ZeRO-2/3 grad sharding (XLA lowers the psum into reduce-scatter)."""
        if self.zero_stage >= 2:
            specs = self.plan.grad_specs(grads)
            with jax.named_scope("zero/reduce_grads"):
                grads = jax.tree.map(
                    lambda g, s: jax.lax.with_sharding_constraint(g, NamedSharding(self.mesh, s)),
                    grads, specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
        return grads

    def _apply_update(self, state: EngineState, grads, grad_norm_scale=None,
                      unscale=True):
        """Unscale, clip, optimizer update, loss-scale update, skip-on-overflow.

        ``unscale=False`` when the caller already unscaled (the explicit-comm
        path unscales before the wire so LoCo residuals live in true units).
        """
        # one scope for everything after the backward pass, so a profile
        # can tell the update's device time from the model's
        with jax.named_scope("optimizer"):
            if unscale:
                grads = self.loss_scaler.unscale_grads(grads, state.scaler)
            if grad_norm_scale is not None:
                grads = jax.tree.map(lambda g: g * grad_norm_scale, grads)
            # prescale_gradients / gradient_predivide_factor (reference
            # engine.py:2501-2508): in DeepSpeed these only reorder the divide
            # around the allreduce and always net out to the exact DP mean.
            # Sharded autodiff already yields that exact mean, so both knobs are
            # numerical no-ops here — applying 1/f permanently would silently
            # shrink the effective LR for any ported config.
            overflow = self.loss_scaler.check_overflow(grads) \
                if self.loss_scaler.dynamic else jnp.zeros((), bool)

            clip = self.config.gradient_clipping
            if clip and clip > 0:
                with jax.named_scope("clip"):
                    gnorm = _global_norm(grads)
                    scale = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                    grads = jax.tree.map(lambda g: g * scale, grads)
            safe_grads = jax.tree.map(lambda g: jnp.where(jnp.isfinite(g), g, 0.0), grads)
            updates, new_opt = self.optimizer.update(safe_grads, state.opt_state, state.params)
            import optax

            new_params = optax.apply_updates(state.params, updates)
            # On overflow: keep old params/opt state, bump skipped counter.
            keep = lambda new, old: jax.tree.map(
                lambda n, o: jnp.where(overflow, o, n), new, old)
            new_params = keep(new_params, state.params)
            new_opt = keep(new_opt, state.opt_state)
            new_scaler = self.loss_scaler.update(state.scaler, overflow)
            return state.replace(
                params=new_params,
                opt_state=new_opt,
                scaler=new_scaler,
                global_step=state.global_step + jnp.where(overflow, 0, 1),
                skipped_steps=state.skipped_steps + jnp.where(overflow, 1, 0),
            )

    # ------------------------------------------------------------------ #
    # Fused path
    # ------------------------------------------------------------------ #
    def _build_train_batch_fn(self):
        if self._explicit_comm:
            from .comm_path import build_explicit_comm_step

            return build_explicit_comm_step(self)
        gas = self.gradient_accumulation_steps()
        # Deferred micro-batch reduction (overlap subsystem): park each
        # micro-batch's unconstrained grads in the scan carry and apply the
        # ZeRO sharding constraint one iteration later, so the reduce-
        # scatter it induces has a whole micro-batch of independent compute
        # to hide behind.  Same additions in the same order → bit-exact vs
        # the eager schedule (asserted by the overlap tests).  Below stage
        # 2 there is no grad-sharding collective to move, so eager stands.
        use_deferred = bool(self.overlap.enabled and self.overlap.deferred
                            and gas > 1 and self.zero_stage >= 2)
        self._deferred_active = use_deferred

        def step_fn(state: EngineState, batch):
            rng, sub = jax.random.split(state.rng)

            # what each micro-batch counted for the model's own state, added
            # up over the step (None where the model declares none)
            added = lambda stack: jax.tree.map(  # noqa: E731
                lambda x: x.sum(axis=0), stack)
            if gas == 1:
                loss, grads, counted = self._loss_grads_counted(
                    state.params, batch, sub, state.scaler,
                    model_state=state.model_state)
                mean_loss = loss
            elif use_deferred:
                from .overlap.deferred import DeferredAccumulator

                reducer = DeferredAccumulator(self._constrain_grads,
                                              _tree_zeros_like(state.params))

                def micro(carry, mb):
                    acc, pending, r = carry
                    r, r2 = jax.random.split(r)
                    loss, grads, counted = self._loss_grads_counted(
                        state.params, mb, r2, state.scaler, constrain=False,
                        model_state=state.model_state)
                    acc, pending = reducer.step((acc, pending), grads)
                    return (acc, pending, r), (loss, counted)

                zeros = self._constrain_grads(_tree_zeros_like(state.params))
                (acc, pending, _), (losses, counted) = jax.lax.scan(
                    micro, (zeros, _tree_zeros_like(state.params), sub),
                    batch)
                grads = reducer.flush((acc, pending))
                grads = jax.tree.map(lambda g: g / gas, grads)
                mean_loss, counted = losses.mean(), added(counted)
            else:
                # batch leaves: [gas, micro_global, ...]
                def micro(carry, mb):
                    acc, r = carry
                    r, r2 = jax.random.split(r)
                    loss, grads, counted = self._loss_grads_counted(
                        state.params, mb, r2, state.scaler,
                        model_state=state.model_state)
                    acc = jax.tree.map(jnp.add, acc, grads)
                    return (acc, r), (loss, counted)

                zeros = _tree_zeros_like(state.params)
                zeros = self._constrain_grads(zeros)
                (grads, _), (losses, counted) = jax.lax.scan(
                    micro, (zeros, sub), batch)
                grads = jax.tree.map(lambda g: g / gas, grads)
                mean_loss, counted = losses.mean(), added(counted)

            new_state = self._apply_update(state, grads)
            new_state = self._next_model_state(state, new_state, counted)
            new_state = new_state.replace(micro_step=state.micro_step + gas, rng=rng)
            return new_state, mean_loss

        donate = jax.jit(step_fn, donate_argnums=(0,))
        return donate

    def _run_graph_lint(self) -> None:
        """``config.debug.graph_lint``: trace the train step once and run
        every registered jaxpr pass over it (replica-group gather, masked
        NaN, fused wire, gather budget — analysis/graph_passes.py).

        Findings are logged, counted in ``analysis/findings`` and emitted
        as ``analysis/finding`` telemetry events (plus one
        ``analysis/graph_lint`` summary event); in ``"error"`` mode an
        error-severity finding raises :class:`~..analysis.GraphLintError`
        before the step is ever dispatched.  The trace is cached into
        ``self._step_jaxpr`` so ``train_step_cost``'s module-tree walk
        reuses it instead of re-tracing.
        """
        from ..analysis import (ERROR, GraphLintError, PassContext,
                                run_graph_passes, sort_findings)

        fn = self._compiled["train_batch"]
        state_struct = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.state)
        struct = self._last_batch_struct
        try:
            with self._span("analysis/graph_lint"):
                traced = jax.make_jaxpr(fn)(state_struct, struct)
                key = tuple((tuple(l.shape), str(l.dtype))
                            for l in jax.tree.leaves(struct))
                self._step_jaxpr = (key, traced.jaxpr)
                shardings = [getattr(leaf, "sharding", None)
                             for leaf in jax.tree.leaves(self.state)]
                shardings += [None] * len(jax.tree.leaves(struct))
                findings = sort_findings(run_graph_passes(
                    traced, PassContext(artifact="train_step",
                                        mesh=self.mesh,
                                        arg_shardings=shardings)))
        except Exception as e:  # noqa: BLE001 — a lint-machinery failure
            # is not a finding: report-only modes promise not to break
            # training, and error mode only raises on actual findings
            log_dist(f"graph_lint: train-step lint failed ({e}); "
                     f"training continues", ranks=[0])
            self._graph_lint_done = True
            return
        errors = [f for f in findings if f.severity == ERROR]
        tel = self.telemetry
        if tel is not None:
            for f in findings:
                tel.metrics.counter("analysis/findings").inc(
                    **{"pass": f.pass_name, "severity": f.severity})
                tel.event("analysis/finding", pass_name=f.pass_name,
                          severity=f.severity, message=f.message,
                          file=f.file, line=f.line, artifact=f.artifact)
            tel.event("analysis/graph_lint", artifact="train_step",
                      findings=len(findings), errors=len(errors),
                      mode=self._graph_lint_mode)
        for f in findings:
            log_dist(f"graph_lint: {f.render()}", ranks=[0])
        if errors and self._graph_lint_mode == "error":
            # deliberately NOT marking the lint done: a caller that
            # catches and retries train_batch must hit the abort again,
            # never dispatch the flagged program unlinted
            raise GraphLintError(
                f"debug.graph_lint: {len(errors)} error-severity finding(s) "
                f"in the train step jaxpr; first: {errors[0].render()}")
        self._graph_lint_done = True
        if not findings:
            log_dist("graph_lint: train step jaxpr clean", ranks=[0])

    def train_batch(self, batch) -> jnp.ndarray:
        """One full optimizer step over a global batch.

        ``batch`` leaves have leading dim ``train_batch_size`` (global);
        with gradient accumulation the engine reshapes to [gas, micro].
        """
        gas = self.gradient_accumulation_steps()
        if gas > 1:
            batch = jax.tree.map(
                lambda x: x.reshape((gas, x.shape[0] // gas) + x.shape[1:]), batch)
        # shapes feed train_step_cost() (profiler/bench MFU, roofline gauges)
        self._last_batch_struct = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
        if "train_batch" not in self._compiled:
            self._compiled["train_batch"] = self._build_train_batch_fn()
        if self._last_batch_shardings is None:
            # a profile of this step can be read by name scope: the text
            # is made (from the compile cache) only if somebody asks
            from ..profiling.xprof_parse import register_step_text

            self._last_batch_shardings = [
                getattr(x, "sharding", None) for x in jax.tree.leaves(batch)]
            ref = weakref.WeakMethod(self.compiled_step_text)
            register_step_text("train_batch", lambda: ref() and ref()())
        if self._graph_lint_mode and not self._graph_lint_done:
            self._run_graph_lint()
        self._heartbeat("train_batch")
        injector = fault_injection.get_injector()
        if injector is not None:   # don't pay the global_steps sync otherwise
            injector.inject("step", step=self.global_steps)
        # offload pipeline_read: issue the async H2D stage of the host
        # optimizer partition BEFORE dispatch so it lands under fwd/bwd;
        # identity on CPU sim / injected offload fault (update then reads
        # the host partition directly — correct, just unoverlapped)
        if self._offload_prefetcher is not None:
            staged = self._offload_prefetcher.arm(self.state.opt_state)
            if staged is not self.state.opt_state:
                self.state = self.state.replace(opt_state=staged)
        # Device-time attribution (reference: CUDA-event comms timing;
        # comms_logger.xprof_step): wrap ONE step in an xprof trace — per-op
        # device durations, collectives included.  A wrapper, not a separate
        # path: timers, NaN checks, and logging run as normal, and the
        # fired flag keeps an fp16 overflow-skipped step from re-tracing.
        cl = self.config.comms_logger
        trace_now = (cl.enabled and cl.xprof_step >= 0 and
                     not getattr(self, "_xprof_fired", False) and
                     cl.xprof_step == self.global_steps)
        import contextlib

        ctx = jax.profiler.trace(cl.xprof_dir) if trace_now \
            else contextlib.nullcontext()
        self._host_step_calls += 1
        # goodput: the ledger's step envelope opens here (the tput timer
        # skips warmup steps, so its last_step_time can't cover step 1 —
        # the compile step is exactly the one the ledger must not lose)
        self._goodput_step_t0 = time.perf_counter()
        # the step's boundaries go on the process-global tracer with or
        # without a hub: a profiler session then groups the device
        # operations by step and shows the host beside them
        tracer = get_tracer()
        self.tput_timer.start()
        if self.config.wall_clock_breakdown:
            self._timers("step").start()
        with tracer.step_span(self._host_step_calls,
                              name="engine/train_batch"):
            with ctx:
                with tracer.span("engine/dispatch") as sp:
                    self.state, loss = self._compiled["train_batch"](self.state, batch)
                    self._fence_span(sp, loss)
                if trace_now:
                    jax.block_until_ready(loss)
            if trace_now:
                self._xprof_fired = True
                if self.telemetry is not None:
                    # breadcrumb so the run summary can find + parse the
                    # captured trace for device-time attribution
                    self.telemetry.event(
                        "xprof_trace", dir=os.path.abspath(cl.xprof_dir),
                        step=cl.xprof_step)
                log_dist(f"comms_logger: xprof trace for step {cl.xprof_step} "
                         f"→ {cl.xprof_dir}", ranks=[0])
            # the fence inside the step span makes it cover device time, not
            # just Python dispatch; it is the step's one wait for the device,
            # so what is left of ``engine/train_batch`` is the host's own
            with tracer.span("engine/step_wait"):
                self.tput_timer.stop(sync=loss)
        if self.config.wall_clock_breakdown:
            self._timers("step").stop(sync=loss)
        if getattr(self.config, "debug_nan_check", False) and \
                not np.isfinite(float(loss)):
            raise RuntimeError(
                f"debug.nan_check: non-finite loss {float(loss)} at step "
                f"{self.global_steps} (note: fp16 dynamic loss scaling "
                f"intentionally overflows — use nan_check with bf16)")
        with tracer.span("engine/post_step"):
            self._post_step_logging(loss, batch)
        return loss

    def _post_step_logging(self, loss, batch):
        t_host0 = time.perf_counter()
        self._goodput_step_attribution()
        self._write_monitor_events(loss)
        step = self.global_steps
        self._last_logged_step = step   # host mirror for the live plane
        self._heartbeat("idle", step=step)   # reuse the sync we just paid for
        if self.telemetry is not None:
            with self._span("telemetry/memory_sample"):
                self.telemetry.memory.maybe_sample(step)
        if self._straggler is not None:
            dur = getattr(self.tput_timer, "last_step_time", 0.0)
            if dur > 0:
                with self._span("profiling/straggler_check"):
                    self._straggler.observe_step(step, dur)
        if self._anomaly is not None:
            # non-finite guard / loss-spike z-score / step-time regression;
            # action="abort" raises AnomalyAbort out of train_batch (by
            # design — the elastic agent restarts from the last good tag)
            dur = getattr(self.tput_timer, "last_step_time", 0.0)
            lval = float(loss)
            if self.loss_scaler.dynamic and not np.isfinite(lval):
                # fp16 dynamic scaling overflows BY DESIGN: the scaler
                # skipped the update and will self-heal — not an incident
                # (same carve-out debug.nan_check documents above)
                lval = None
            with self._span("telemetry/anomaly_check"):
                self._anomaly.observe(step, loss=lval,
                                      step_time_s=dur if dur > 0 else None)
        if self.overlap.enabled:
            with self._span("overlap/on_step"):
                self.overlap.on_step(self, self._deferred_active)
        pcfg = self.config.profiling
        if self._profiling_on and pcfg.enabled and pcfg.roofline and \
                self.telemetry is not None and step > 0 and \
                pcfg.roofline_interval > 0 and \
                step % pcfg.roofline_interval == 0:
            self._publish_roofline(step)
        cfg = self.config
        if cfg.steps_per_print and step > 0 and step % cfg.steps_per_print == 0:
            log_dist(f"step={step} loss={float(loss):.4f} "
                     f"lr={self.get_lr()[0]:.3e} "
                     f"loss_scale={self.get_loss_scale():.0f} "
                     f"samples/sec={self.tput_timer.avg_samples_per_sec():.1f}",
                     ranks=[0])
        if cfg.wall_clock_breakdown and step % cfg.steps_per_print == 0:
            self._timers.log(["forward", "backward", "step"])
        fp = cfg.flops_profiler
        if (fp.enabled or pcfg.enabled) and step == fp.profile_step:
            from ..profiling.flops_profiler.profiler import FlopsProfiler

            prof = FlopsProfiler(ds_engine=self,
                                 recompute_fwd_factor=fp.recompute_fwd_factor)
            try:
                # batch already carries the step fn's shapes ([gas, micro]
                # under grad accumulation — train_batch reshaped it)
                prof.profile_engine_step(batch, pre_reshaped=True)
                prof.latency = getattr(self.tput_timer, "last_step_time", 0.0) \
                    or self.tput_timer.total_elapsed_time / max(
                        self.tput_timer.global_step_count -
                        self.tput_timer.start_step, 1)
                prof.print_model_profile(
                    profile_step=step, module_depth=fp.module_depth,
                    top_modules=fp.top_modules, detailed=fp.detailed,
                    output_file=fp.output_file)
            except Exception as e:
                logger.warning(f"flops profile failed: {e}")
        # the logging body itself is host bookkeeping the device sat out
        record_goodput("host_sync", time.perf_counter() - t_host0)

    def _goodput_step_attribution(self) -> None:
        """Split the step wall just paid into the goodput ledger's books:
        the FIRST host call traced+compiled ``train_batch`` so its wall is
        ``compile``; steady-state steps split into ``exposed_comm`` (step
        wall x the overlap manager's measured exposed fraction) and
        ``compute`` (the remainder).  No-op when no ledger is installed."""
        ledger = get_goodput_ledger()
        if ledger is None:
            return
        t0 = getattr(self, "_goodput_step_t0", None)
        if t0 is None:
            return
        self._goodput_step_t0 = None     # one attribution per step
        dur = time.perf_counter() - t0
        if dur <= 0.0:
            return
        if self._host_step_calls <= 1:
            ledger.add("compile", dur)
            return
        exposed_frac = 0.0
        dec = getattr(self.overlap, "last_decision", None)
        if self.overlap.enabled and dec is not None and \
                dec.exposed_comm_fraction is not None:
            exposed_frac = min(max(float(dec.exposed_comm_fraction), 0.0),
                               1.0)
        if exposed_frac > 0.0:
            ledger.add("exposed_comm", dur * exposed_frac)
        ledger.add("compute", dur * (1.0 - exposed_frac))

    # ------------------------------------------------------------------ #
    # API-parity helpers
    # ------------------------------------------------------------------ #
    def compile(self, backend=None, compile_kwargs=None):
        """Reference engine.compile() (engine.py:3820).  Every step here is
        already jit-compiled; provided so callers can force ahead-of-time
        compilation of the fused step."""
        if "train_batch" not in self._compiled:
            self._compiled["train_batch"] = self._build_train_batch_fn()
        self._is_compiled = True
        return self

    @property
    def is_compiled(self) -> bool:
        return bool(getattr(self, "_is_compiled", False))

    @staticmethod
    def reset_debug_mode():
        """Clear the process-global debug toggles an engine's debug config
        enabled (deterministic matmul pinning + jax_debug_nans)."""
        jax.config.update("jax_debug_nans", False)
        jax.config.update("jax_default_matmul_precision", None)

    def no_sync(self):
        """Reference engine.no_sync(): skip grad allreduce between boundaries.
        The fused path only communicates at the optimizer step, so inside one
        ``train_batch`` there is nothing to suppress — returns a no-op ctx."""
        import contextlib

        return contextlib.nullcontext()

    def zero_grad(self):
        if self.state.grad_acc is not None:
            self.state = self.state.replace(
                grad_acc=jax.tree.map(jnp.zeros_like, self.state.grad_acc))

    def _write_monitor_events(self, loss):
        """Scalar fan-out: runs when any monitor writer OR telemetry is on
        (MonitorMaster routes every event through the telemetry registry, so
        telemetry alone still gets the scalar history)."""
        if self.monitor is None or not (
                getattr(self.monitor, "enabled", False)
                or self.telemetry is not None):
            return
        step = self.global_steps
        events = [("Train/Samples/train_loss", float(loss), self.global_samples),
                  ("Train/Samples/lr", self.get_lr()[0], self.global_samples)]
        if self.loss_scaler.dynamic:
            events.append(("Train/Samples/loss_scale", self.get_loss_scale(), self.global_samples))
        from ..monitor.monitor import fault_events

        events.extend(fault_events(step))
        self.monitor.write_events(events)

    # ------------------------------------------------------------------ #
    # Imperative path (reference API shape)
    # ------------------------------------------------------------------ #
    def _build_micro_fn(self):
        if self._explicit_comm:
            from .comm_path import build_explicit_micro_fn

            return build_explicit_micro_fn(self)

        def micro_fn(state: EngineState, batch):
            rng, sub = jax.random.split(state.rng)
            loss, grads = self._loss_and_grads(state.params, batch, sub, state.scaler)
            if state.grad_acc is not None:
                acc = jax.tree.map(jnp.add, state.grad_acc, grads)
            else:
                acc = grads
            return state.replace(grad_acc=acc, micro_step=state.micro_step + 1, rng=rng), loss

        return jax.jit(micro_fn, donate_argnums=(0,))

    def _build_step_fn(self):
        if self._explicit_comm:
            from .comm_path import build_explicit_step_fn

            return build_explicit_step_fn(self)
        gas = self.gradient_accumulation_steps()

        def step_fn(state: EngineState):
            grads = state.grad_acc
            new_state = self._apply_update(state, grads, grad_norm_scale=1.0 / gas)
            zeros = jax.tree.map(jnp.zeros_like, grads)
            return new_state.replace(grad_acc=zeros)

        return jax.jit(step_fn, donate_argnums=(0,))

    def forward(self, batch, rng: Optional[jax.Array] = None):
        """Loss-only forward (eval). For the training loop use backward()/step()."""
        if "forward" not in self._compiled:
            def fwd(params, batch, rng, model_state):
                p = jax.tree.map(lambda x: x.astype(self.compute_dtype), params)
                if model_state is None:
                    return self.loss_fn(p, batch, rng)
                return self.loss_fn(p, batch, rng, model_state)

            self._compiled["forward"] = jax.jit(fwd)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        return self._compiled["forward"](self.state.params, batch, rng,
                                         self.state.model_state)

    __call__ = forward

    def backward(self, batch, loss=None):
        """Compute+accumulate grads for one micro batch (fwd+bwd fused).

        Note: unlike the reference (which takes the loss tensor from a prior
        ``forward``), JAX differentiates the loss *function*, so backward takes
        the micro-batch. Returns the micro-batch loss.
        """
        if self._model_rule is not None:
            raise NotImplementedError(
                "a model that declares init_model_state() trains through "
                "train_batch(): backward()/step() carry no model state")
        if self.state.grad_acc is None and self.gradient_accumulation_steps() > 1 \
                and not self._explicit_comm:
            raise RuntimeError("grad accumulation buffer missing")
        if self.state.grad_acc is None or (
                self._explicit_comm and
                jax.tree.leaves(self.state.grad_acc)[0].ndim ==
                jax.tree.leaves(self.state.params)[0].ndim):
            # Allocate lazily for imperative use.  Explicit comm accumulates
            # LOCAL per-data-shard grads (leading [n_dp] axis, exchange at
            # the step() boundary); the fused path accumulates the already
            # XLA-reduced grads in param shape.
            if self._explicit_comm:
                from .comm_path import make_explicit_grad_acc

                acc = make_explicit_grad_acc(self)
            else:
                acc = _tree_zeros_like(self.state.params)
            self.state = self.state.replace(grad_acc=acc)
            self._compiled.pop("micro", None)
        # ZeRO-3 weight-gather prefetch (overlap subsystem): the gathered
        # full params are a pure function of params, which only change at
        # step() — gather once per accumulation window and reuse, so the
        # per-micro-step program carries no param all-gather.
        prefetch = (self._explicit_comm and self.zero_stage >= 3
                    and self.overlap.enabled and self.overlap.prefetch_params)
        if "micro" not in self._compiled:
            if prefetch:
                from .comm_path import (build_explicit_micro_fn,
                                        build_param_gather_fn)

                self._compiled["gather_full"] = build_param_gather_fn(self)
                self._compiled["micro"] = build_explicit_micro_fn(
                    self, pregathered=True)
            else:
                self._compiled["micro"] = self._build_micro_fn()
        self._heartbeat("backward")
        if self.config.wall_clock_breakdown:
            self._timers("backward").start()
        with self._span("engine/backward") as sp:
            if prefetch:
                full = self._gather_cache.get(
                    self.state.params, self._compiled["gather_full"])
                self.overlap.note_prefetch(self._gather_cache)
                self.state, loss = self._compiled["micro"](self.state, batch,
                                                           full)
            else:
                self.state, loss = self._compiled["micro"](self.state, batch)
            self._fence_span(sp, loss)
        if self.config.wall_clock_breakdown:
            self._timers("backward").stop(sync=loss)
        self._losses.append(loss)
        return loss

    def step(self):
        """Apply the optimizer at the grad-accumulation boundary (else no-op),
        mirroring reference step() semantics (engine.py:2282)."""
        if not self.is_gradient_accumulation_boundary():
            return
        if "step" not in self._compiled:
            self._compiled["step"] = self._build_step_fn()
        self._heartbeat("optimizer_step")
        with self._span("engine/optimizer_step") as sp:
            self.state = self._compiled["step"](self.state)
            self._fence_span(sp, self.state.global_step)
        # params changed: the prefetched gathered-params window is over
        self._gather_cache.invalidate()
        if self._losses:
            self._write_monitor_events(self._losses[-1])
            self._losses.clear()
        self._heartbeat("idle")

    def eval_batch(self, batch):
        out = self.forward(batch)
        return out[0] if isinstance(out, tuple) else out

    # ------------------------------------------------------------------ #
    # Checkpointing (orbax-backed; universal/reshardable by construction)
    # ------------------------------------------------------------------ #
    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[dict] = None, save_latest: bool = True,
                        exclude_frozen_parameters: bool = False):
        from .checkpoint_engine.orbax_checkpoint_engine import OrbaxCheckpointEngine

        tag = tag or f"global_step{self.global_steps}"
        self._heartbeat("checkpoint_save")
        engine = OrbaxCheckpointEngine(save_dir,
                                       fault_config=getattr(self.config, "fault", None))
        payload = {
            "state": self.state,
            "client_state": client_state or {},
            "lr_scheduler": (self.lr_scheduler.state_dict()
                             if hasattr(self.lr_scheduler, "state_dict") else None),
            "config": {"zero_stage": self.zero_stage,
                       "world_size": self.topology.world_size(),
                       "mesh": {k: int(v)
                                for k, v in self.topology.dims.items()}},
        }
        t_ckpt0 = time.perf_counter()
        with self._span("engine/save_checkpoint", tag=str(tag)):
            engine.save(payload, tag)
            if save_latest:
                engine.commit(tag)
        record_goodput("checkpoint", time.perf_counter() - t_ckpt0)
        self._heartbeat("idle")
        log_dist(f"saved checkpoint {save_dir}/{tag}", ranks=[0])
        return True

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_module_strict: bool = True, load_optimizer_states: bool = True,
                        load_lr_scheduler_states: bool = True,
                        load_module_only: bool = False):
        from .checkpoint_engine.orbax_checkpoint_engine import OrbaxCheckpointEngine

        self._heartbeat("checkpoint_load")
        engine = OrbaxCheckpointEngine(load_dir,
                                       fault_config=getattr(self.config, "fault", None))
        # Universal path: checkpoints carrying a layout manifest reshard
        # onto THIS engine's mesh (grow/shrink/re-split/zero restage) —
        # the planner validates structure and tensorstore range-reads only
        # the bytes each target shard needs.  Pre-universal checkpoints
        # fall back to the template-structure load below (same mesh only).
        from ..checkpoint.universal.loader import (NoLayoutError,
                                                   load_state_resharded)

        from .fault.manifest import CheckpointCorruptError

        payload = None
        try:
            with self._span("engine/load_checkpoint", tag=str(tag)):
                tag, restored, meta, plan = load_state_resharded(
                    engine, self.state, tag)
            payload = {"state": restored}
            payload.update(meta)
            if plan.reshaped:
                emit_event("checkpoint_reshard", tag=str(tag), dir=load_dir,
                           **plan.summary())
                log_dist(
                    f"resharded checkpoint {load_dir}/{tag}: "
                    f"{plan.source_mesh} -> {plan.target_mesh}, "
                    f"leaves {plan.counts()}, "
                    f"read {plan.total_read_bytes() / 1e6:.2f} MB", ranks=[0])
        except CheckpointCorruptError:
            if tag is not None:
                raise                      # explicit tag: never load elsewhere
            # resume-anything semantics: an empty/unrecoverable store means
            # "start fresh", exactly as the pre-universal path behaved
            logger.warning(f"no (valid) checkpoint found under {load_dir}")
            return None, {}
        except NoLayoutError:
            if tag is None:
                tag = engine.latest_tag()  # falls back to the newest VALID tag
            if tag is None:
                logger.warning(f"no (valid) checkpoint found under {load_dir}")
                return None, {}
            with self._span("engine/load_checkpoint", tag=str(tag)):
                payload = engine.load({"state": self.state, "client_state": None,
                                       "lr_scheduler": None, "config": None}, tag)
        restored = payload["state"]
        # The restored tree has the target's leaves in the target's order
        # (the graft walks the target) but an empty node comes back as a
        # bare list (optax's zero-field states: AdamW's decayed-weights
        # one), which is no prefix of the shardings below: take the live
        # state's own structure.
        restored = jax.tree.unflatten(jax.tree.structure(self.state),
                                      jax.tree.leaves(restored))
        # Re-place on this engine's target shardings (restore may commit
        # scalar leaves to a single device, which conflicts under jit).
        target = jax.tree.map(
            lambda cur: cur.sharding if isinstance(cur.sharding, NamedSharding)
            else self.topology.replicated(), self.state)
        restored = jax.device_put(restored, target)
        if load_module_only or not load_optimizer_states:
            self.state = self.state.replace(params=restored.params)
        else:
            self.state = restored
        self._gather_cache.invalidate()   # params changed under the cache
        if load_lr_scheduler_states and payload.get("lr_scheduler") and \
                hasattr(self.lr_scheduler, "load_state_dict"):
            self.lr_scheduler.load_state_dict(payload["lr_scheduler"])
        self._heartbeat("idle")
        log_dist(f"loaded checkpoint {load_dir}/{tag}", ranks=[0])
        return os.path.join(load_dir, str(tag)), payload.get("client_state", {})

    # ------------------------------------------------------------------ #
    # Memory observability (telemetry/memory.py MemoryLedger plumbing)
    # ------------------------------------------------------------------ #
    def register_memory_sources(self, ledger) -> None:
        """Attribute this engine's bytes to the
        :class:`~..telemetry.memory.MemoryLedger` buckets (training-side
        mirror of ``InferenceEngineV2.register_memory_sources``): params,
        the optimizer partition split into its device-resident
        (``optimizer_state``) and host-staged (``host_optimizer``) halves
        per the Twin-Flow byte split, and the deferred-reduction gradient
        accumulation buffer."""
        def _tree_bytes(tree) -> int:
            return int(sum(int(getattr(x, "nbytes", 0) or 0)
                           for x in jax.tree_util.tree_leaves(tree)))

        def _opt_split():
            total = _tree_bytes(self.state.opt_state)
            if self._twin_flow_bytes is not None:
                dev_b, host_b = self._twin_flow_bytes()
                return int(dev_b), int(host_b)
            if self.config.zero_config.offload_optimizer_device() == "cpu":
                return 0, total   # full offload: everything host-side
            return total, 0

        ledger.register_source(
            "params", lambda: _tree_bytes(self.state.params))
        ledger.register_source("optimizer_state", lambda: _opt_split()[0])
        ledger.register_source("host_optimizer", lambda: _opt_split()[1])
        ledger.register_source(
            "grad_acc", lambda: _tree_bytes(self.state.grad_acc))

    # ------------------------------------------------------------------ #
    # State offload (reference: engine.offload_states :3844 / reload_states
    # :3876 + runtime/zero/offload_states.py)
    # ------------------------------------------------------------------ #
    def offload_states(self, include=("optimizer",), device: str = "cpu",
                       nvme_path: Optional[str] = None, pin_memory: bool = True,
                       non_blocking: bool = False):
        """Move engine state off HBM: 'cpu' = host memory, 'nvme' = disk via
        the native aio engine."""
        self._offloaded = getattr(self, "_offloaded", {})
        for what in include:
            if what == "optimizer":
                tree = self.state.opt_state
            elif what in ("hp_params", "params"):
                tree = self.state.params
            else:
                raise ValueError(f"cannot offload {what!r}")
            if device == "nvme":
                from .swap_tensor.partitioned_param_swapper import AsyncTensorSwapper

                swapper = AsyncTensorSwapper(nvme_path or "/tmp/dstpu_swap")
                swapper.swap_out(what, tree, blocking=not non_blocking)
                self._offloaded[what] = ("nvme", swapper,
                                         jax.tree.map(lambda x: x.sharding, tree))
            else:
                cpu_dev = jax.devices("cpu")[0]
                host_tree = jax.device_put(tree, cpu_dev)
                self._offloaded[what] = ("cpu", host_tree,
                                         jax.tree.map(lambda x: x.sharding, tree))
            # drop device references so XLA frees HBM
            if what == "optimizer":
                self.state = self.state.replace(opt_state=None)
            else:
                self.state = self.state.replace(params=None)
            self._compiled.clear()

    def reload_states(self, non_blocking: bool = False):
        for what, (kind, store, shardings) in getattr(self, "_offloaded", {}).items():
            if kind == "nvme":
                tree = store.swap_in(what, shardings=shardings)
                store.cleanup()
            else:
                tree = jax.device_put(store, shardings)
            if what == "optimizer":
                self.state = self.state.replace(opt_state=tree)
            else:
                self.state = self.state.replace(params=tree)
        self._offloaded = {}
        self._compiled.clear()
        self._gather_cache.invalidate()

    # ------------------------------------------------------------------ #
    def get_fp32_state_dict(self):
        """Gather full (unsharded) fp32 params on host — the
        ``_zero3_consolidated_16bit_state_dict`` analogue (engine.py:3693)."""
        rep = jax.device_put(self.state.params,
                             jax.tree.map(lambda _: self.topology.replicated(),
                                          self.state.params))
        return jax.tree.map(np.asarray, rep)
