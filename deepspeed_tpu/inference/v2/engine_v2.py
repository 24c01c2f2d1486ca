"""Continuous-batching inference engine — FastGen on TPU.

Reference: ``InferenceEngineV2`` (inference/v2/engine_v2.py:30): ``put`` (:107)
runs one forward over a ragged batch, ``query`` (:158) exposes the scheduling
budget, ``can_schedule``/``SchedulingResult`` (:184) gate admission, ``flush``
(:242) evicts host state.  Dynamic SplitFuse (the MII scheduler policy) is
implemented in :meth:`schedule`: long prompts are split into token-budget
chunks and fused with pending decodes so every forward runs near the
compute-optimal token count.

TPU adaptation: the forward is ONE compiled program with static budgets
(max_tokens × max_seqs × max_ctx); the paged KV cache is donated through each
call (no allocation churn — the XLA equivalent of the reference's CUDA-graph
capture, engine.py:494).
"""
from __future__ import annotations

import dataclasses
import time
from enum import Enum
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...models.transformer import CausalLM
from ...runtime.fault.injection import InjectedNaN, inject
from ...telemetry.trace import get_tracer, traced
from ...utils.logging import log_dist, logger
from .model_runner import build_ragged_step
from .ragged.kv_cache import BlockedKVCache, KVCacheConfig
from .ragged.ragged_wrapper import RaggedBatchWrapper, pack_layout
from .ragged.sequence_descriptor import DSStateManager

#: every call into the engine is a span on the process-global tracer (and a
#: host event in a profiler trace): telemetry/trace.py says what one costs
_TRACER = get_tracer()


class SchedulingResult(Enum):
    Success = 0
    EngineSequenceLimitExceeded = 1
    BatchSequenceLimitExceeded = 2
    KVCacheLimitExceeded = 3
    SequenceTooLong = 4


@dataclasses.dataclass
class RaggedInferenceEngineConfig:
    """Reference: inference/v2/config_v2.py."""

    max_tokens: int = 256            # token budget per forward (SplitFuse chunk)
    max_seqs: int = 16
    max_ctx: int = 2048
    block_size: int = 64
    num_blocks: Optional[int] = None  # default: enough for max_seqs * max_ctx
    dtype: object = jnp.bfloat16
    #: "paged" = Pallas paged-attention kernel (blocked_flash equivalent);
    #: "gather" = dense page-gather reference path (numerics oracle).
    attn_impl: str = "paged"
    #: paged-kernel tuning: flat-token query tile and KV pages fetched per
    #: double-buffered DMA chunk (see kernels/ragged_ops.py)
    block_q: int = 128
    pages_per_chunk: int = 8
    #: compile-cache bucketing: pad each forward's token budget to the next
    #: power-of-two bucket instead of always padding to max_tokens.
    #: SplitFuse's variable chunk sizes then compile once per BUCKET
    #: (probe: ``engine.trace_counts``), and decode windows also bucket the
    #: seq axis so they run at a token budget near the live-sequence count
    #: instead of dragging max_tokens of padding through every MLP.
    bucket_tokens: bool = True
    min_token_bucket: int = 16
    #: on-device sampling default for fused decode: 0 = full-vocab
    #: categorical (or argmax at temperature 0), k>0 = top-k sampling
    top_k: int = 0
    #: dstpu-check graph lint: run the registered jaxpr passes over every
    #: freshly-built bucket program (prefill/decode/verify) — findings
    #: accumulate in ``engine.graph_lint_findings`` and emit ``analysis/*``
    #: telemetry events.  Advisory (never raises): serving keeps serving;
    #: the CI gate (tools/check_graph_lint.py) is where errors block.
    graph_lint: bool = False
    #: radix prefix KV reuse: committed prompt pages become a token trie
    #: (ragged/prefix_cache.py) that admission grafts from instead of
    #: recomputing shared prefixes — multi-tenant traffic with a common
    #: system prompt skips its prefill entirely.  Pages are refcounted;
    #: a grafted partial page is copied before the sequence's first append
    #: (copy-on-write), and cold cache pages evict on allocation pressure.
    prefix_cache: bool = False
    #: host-side KV page-heat tracking (ragged/page_heat.py): per-page
    #: last-touch window + touch count maintained from the block tables the
    #: engine already walks — zero device work, no retraces (the
    #: trace_counts probes are test-asserted unchanged).  Feeds the
    #: ``mem/*`` cold-set gauges and the dstpu-mem what-if-spill reports.
    track_page_heat: bool = True
    #: cold-set age thresholds (windows since last touch) published as
    #: ``mem/kv_cold_pages{age_windows=K}`` gauges
    heat_cold_thresholds: Tuple[int, ...] = (4, 16, 64)
    #: host-DRAM page tier capacity in MB (0 = tier off).  When on,
    #: KV-pressure preemption *swaps*: the victim's coldest contiguous
    #: page-prefix (ranked by heat age) is exported in kv_ship canonical
    #: rows to host memory, and resume grafts it back (H2D + page-table
    #: patch) instead of recomputing the prefill; prefix-cache evictions
    #: likewise spill shared full pages host-side.  Sized from the
    #: dstpu-mem what-if-spill tables (ragged/kv_swap.py).
    host_tier_mb: float = 0.0


class InferenceEngineV2:
    @traced("engine/init")      # the pools, the cast; its compiles inside
    def __init__(self, model: CausalLM, params,
                 config: Optional[RaggedInferenceEngineConfig] = None):
        from ...utils.compile_cache import configure_compile_cache

        configure_compile_cache()
        self.model = model
        self.cfg = model.config
        if not callable(getattr(model, "serving_family", None)):
            raise NotImplementedError(
                f"ragged serving needs a model that says what family it is "
                f"(serving_family(), models/serving.py: CausalLM, "
                f"UniversalCausalLM, Xing4LM); got {type(model).__name__}")
        #: what the model is to the serving path: the cached row, the layer
        #: stacks and their bodies (models/serving.ServingFamily)
        self.family = model.serving_family()
        self.config = config or RaggedInferenceEngineConfig()
        c = self.config
        #: a latent (MLA) page pool: one row a token, no K/V pair, no heads
        self.latent_kv = self.family.row.latent
        if self.latent_kv and c.host_tier_mb > 0:
            # kv_swap parks pages as kv_ship rows [L, n, 2*KV, hd]: a
            # latent page has no such shape, and a reshaped copy would be
            # silently wrong
            raise ValueError(
                "host_tier_mb > 0 (the host KV tier, ragged/kv_swap.py) is "
                "not supported with latent (MLA) pages: set host_tier_mb=0")
        #: an index key beside every K/V row (learned sparse attention,
        #: models/serving.IndexKey): the pool is the pair (rows, index keys)
        self.index_kv = self.family.row.index
        if self.index_kv is not None and c.host_tier_mb > 0:
            # kv_swap parks K/V rows alone: a restored prefix would be
            # scored against index keys of whatever held the block before
            raise ValueError(
                "host_tier_mb > 0 (the host KV tier, ragged/kv_swap.py) is "
                "not supported with index keys beside the K/V rows (sparse "
                "attention): the tier parks K/V rows without them — set "
                "host_tier_mb=0")
        #: per-sequence recurrent state of some layers (a Gated DeltaNet
        #: family): a state can be restored only at the token it was saved
        #: at, so what re-reads, parks or ships cached tokens is refused
        #: (a window ring in the slot is the same: the ring at an earlier
        #: token is gone)
        state = self.family.state or self.family.window
        if state is not None and (c.prefix_cache or c.host_tier_mb > 0):
            raise NotImplementedError(
                f"{'prefix_cache' if c.prefix_cache else 'host_tier_mb > 0'} "
                f"is not supported with recurrent state "
                f"({type(state).__name__}): a grafted or swapped-in prefix "
                f"has pages but no state to start from — state snapshots "
                f"are ROADMAP R5")
        num_blocks = c.num_blocks or (c.max_seqs * -(-c.max_ctx // c.block_size))
        self.state_manager = DSStateManager(
            num_blocks=num_blocks, block_size=c.block_size,
            state_slots=c.max_seqs if state is not None else 0)
        if c.prefix_cache:
            from .ragged.prefix_cache import RadixPrefixCache

            self.state_manager.prefix_cache = RadixPrefixCache(
                self.state_manager.allocator, c.block_size)
        self.kv = BlockedKVCache(KVCacheConfig(
            num_layers=self.family.page_layers, num_blocks=num_blocks,
            block_size=c.block_size, token_shape=self.family.row.token_shape,
            dtype=c.dtype,
            index_dim=self.index_kv.dim if self.index_kv else 0))
        #: the state pool beside the page pool: a slot a live sequence
        #: (``max_seqs`` of them), handed out by the state manager
        self.state_pool = None
        if state is not None:
            from .ragged.state_pool import StatePool

            self.state_pool = StatePool(self.family.slot_kinds, c.max_seqs,
                                        c.dtype)
        #: page-heat tracker (None = tracking off): observes the allocator
        #: so its live set mirrors the free list, ticked per forward below
        self.heat = None
        #: uid → tenant label for fractional per-tenant KV attribution
        #: (threaded from the lifecycle scheduler via ``set_tenant``)
        self._uid_tenants: Dict[int, str] = {}
        if c.track_page_heat:
            from .ragged.page_heat import PageHeatTracker

            self.heat = PageHeatTracker(
                self.state_manager.allocator, block_size=c.block_size,
                page_bytes=self.kv.mem_bytes() // num_blocks,
                cold_age_thresholds=c.heat_cold_thresholds)
            self.state_manager.allocator.heat = self.heat
        #: host-DRAM page tier + swap coordinator (None = tier off)
        self.host_tier = None
        self.kv_swap = None
        if c.host_tier_mb > 0:
            from ...runtime.swap_tensor.host_tier import HostPageTier
            from .ragged.kv_swap import KVSwapManager

            self.host_tier = HostPageTier(int(c.host_tier_mb * 1e6))
            self.kv_swap = KVSwapManager(self, self.host_tier)
            if self.state_manager.prefix_cache is not None:
                self.state_manager.prefix_cache.spill_fn = \
                    self.kv_swap.spill_prefix_node
        # Cast to serving dtype, EXCEPT router kernels: routing must run in
        # f32 so serving picks the same experts as the training forward — a
        # bf16 round-trip flips top-k selection on near-tie tokens.
        # The hyper-connection maps (keys ``hc_*``) are float32 too: they
        # are a few hundred thousand values and feed a Sinkhorn iteration.
        def _cast(path, x):
            keys = [str(getattr(k, "key", "")) for k in path]
            if any("router" in k or k.startswith("hc_") for k in keys):
                return jnp.asarray(x, jnp.float32)
            return jnp.asarray(x, c.dtype)

        self.params = jax.tree_util.tree_map_with_path(_cast, params)
        # TP-sharded params need the KV page pool pinned replicated through
        # the append scatter (GSPMD otherwise rewrites the row-set into a
        # summed per-replica-group scatter — see paged_kv_append); detect
        # once from the params' own shardings so plain single-device
        # serving never pays a constraint.
        self._kv_replicate = None
        for leaf in jax.tree_util.tree_leaves(self.params):
            sh = getattr(leaf, "sharding", None)
            if (isinstance(sh, jax.sharding.NamedSharding)
                    and sh.mesh.size > 1 and not sh.is_fully_replicated):
                self._kv_replicate = jax.sharding.NamedSharding(
                    sh.mesh, jax.sharding.PartitionSpec())
                break
        self._num_blocks = num_blocks
        #: per-bucket compiled programs + host-side batch builders; keys are
        #: (token_budget, seq_budget).  ``trace_counts`` is the retrace
        #: probe: it increments exactly when XLA traces a program, so a
        #: steady-state schedule must show one count per bucket touched.
        self._wrappers: Dict[Tuple[int, int], RaggedBatchWrapper] = {}
        self._steps: Dict[Tuple[int, int], object] = {}
        self._decode_loops: Dict = {}
        self._verify_steps: Dict[Tuple[int, int], object] = {}
        self.trace_counts: Dict[Tuple, int] = {}
        #: device-resident continuous-decode state: the advanced packed
        #: metadata returned by the last fused window, reusable by the next
        #: window with NO host repack / H2D upload (see decode_batch_async)
        self._decode_state: Optional[Dict] = None
        self.decode_resume_hits = 0
        #: the last window dispatched: one dispatched while it is undrained
        #: takes its own time from this one's drain (``DecodeWindow``)
        self._last_window: Optional["DecodeWindow"] = None
        #: monotonically increasing fused-window index — the ``step``
        #: passed to the ``decode_window`` fault-injection site (verify
        #: windows share the counter and the site, so the chaos harness
        #: covers spec-dec with no new injection grammar)
        self.decode_windows_dispatched = 0
        #: cumulative speculative-decoding accounting (drafted = candidate
        #: tokens scored, draft_accepted = candidates matching the target's
        #: greedy chain, emitted = tokens produced by verify windows —
        #: always >= windows, each window emits at least the seed's argmax)
        self.spec_windows = 0
        self.spec_drafted = 0
        self.spec_draft_accepted = 0
        self.spec_emitted = 0
        self._rng = jax.random.PRNGKey(0)
        self._param_bytes = sum(
            x.size * jnp.dtype(x.dtype).itemsize
            for x in jax.tree_util.tree_leaves(self.params))
        #: (n_seqs, steps, mean_ctx, duration_s, resumed, compiled) of the
        #: last drained decode window: ``last_decode_roofline`` is computed
        #: from it when somebody asks
        self._last_window_facts: Optional[Tuple] = None
        self._last_roofline: Optional[Tuple[Tuple, Dict]] = None
        #: dstpu-check findings accumulated by ``config.graph_lint`` (one
        #: lint per freshly-built bucket program; see _graph_lint_bucket)
        self.graph_lint_findings: List = []
        log_dist(f"InferenceEngineV2: blocks={num_blocks}×{c.block_size} "
                 f"budget={c.max_tokens}tok/{c.max_seqs}seq "
                 f"kv={self.kv.mem_bytes()/1e6:.0f}MB "
                 + (f"state={self.state_pool.mem_bytes()/1e6:.0f}MB "
                    if self.state_pool is not None else "") +
                 f"bucketing={'on' if c.bucket_tokens else 'off'}", ranks=[0])

    # ------------------------------------------------------------------ #
    # Compile-cache bucketing
    # ------------------------------------------------------------------ #
    def bucket_for(self, n_tokens: int, n_seqs: int) -> Tuple[int, int]:
        """(token, seq) budgets this batch compiles under: tokens round up
        to the next power-of-two bucket (SplitFuse chunk sizes vary every
        forward — THE retrace source), seqs stay at the engine budget
        (padded seqs carry zero tokens through a prefill, so seq-axis
        padding is nearly free and bucketing it would double the compile
        count for batches differing only in width)."""
        c = self.config
        if not c.bucket_tokens:
            return (c.max_tokens, c.max_seqs)
        t = max(c.min_token_bucket, 1)
        while t < n_tokens:
            t *= 2
        return (min(t, c.max_tokens), c.max_seqs)

    def _seq_bucket(self, n_seqs: int) -> int:
        """Decode windows DO bucket the seq axis: their flat token budget
        IS the seq count, so a pow-two seq bucket directly shrinks the
        compiled program (one token per sequence through every layer)."""
        c = self.config
        if not c.bucket_tokens:
            return c.max_seqs
        s = 1
        while s < n_seqs:
            s *= 2
        return min(s, c.max_seqs)

    def _wrapper_for(self, key: Tuple[int, int]) -> RaggedBatchWrapper:
        if key not in self._wrappers:
            self._wrappers[key] = RaggedBatchWrapper(
                key[0], key[1], self.config.max_ctx, self.config.block_size,
                pad_page=self.kv.config.pad_page_flag,
                pad_slot=self.state_pool and self.state_pool.pad_slot)
        return self._wrappers[key]

    def _cache(self):
        """What a compiled program takes, donated, as its second argument
        and returns: the page pool, or with recurrent state the pair (page
        pool, state pool)."""
        if self.state_pool is None:
            return self.kv.pages
        return self.kv.pages, self.state_pool.arrays

    def _cache_update(self, new) -> None:
        if self.state_pool is None:
            self.kv.update(new)
        else:
            self.kv.update(new[0])
            self.state_pool.update(new[1])

    def _counted(self, key, fn, name: str):
        """Wrap a traceable fn so each XLA trace bumps ``trace_counts[key]``
        (the Python body only runs while tracing — cache hits skip it).
        ``name`` becomes the compiled module's (``jit_<name>``), one per
        program, so a device profile's operations can be looked up in the
        program they belong to (:meth:`_register_program`)."""
        def wrapped(*args):
            self.trace_counts[key] = self.trace_counts.get(key, 0) + 1
            return fn(*args)

        wrapped.__name__ = wrapped.__qualname__ = name
        return wrapped

    def _register_program(self, name: str, store: str, key,
                          bucket: Tuple[int, int],
                          with_rng: bool = False) -> None:
        """Register the compiled program ``getattr(self, store)[key]``'s
        text for reading a profile by name scope
        (``profiling/xprof_parse``), as ``runtime/engine.py`` does for the
        train step.  The text is made (from the compile cache) only if
        somebody asks.  The registry outlives the engine, so the callable
        holds a weak reference and nothing else of it: the jitted program's
        closure holds the engine, its parameters and its page pool."""
        import weakref

        from ...profiling.xprof_parse import register_step_text

        ref = weakref.ref(self)
        n_meta = self._meta_len(bucket)

        def text():
            eng = ref()
            if eng is None:
                return None
            jitted = getattr(eng, store)[key]
            struct = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
                x.shape, x.dtype, sharding=getattr(x, "sharding", None))
            args = [jax.tree.map(struct, eng.params),
                    jax.tree.map(struct, eng._cache()),
                    jax.ShapeDtypeStruct((n_meta,), jnp.int32)]
            if with_rng:
                args.append(struct(eng._rng))
            return jitted.lower(*args).compile().as_text()

        register_step_text(f"serve/{name}@{id(self):x}", text)

    def _graph_lint_bucket(self, kind: str, key: Tuple[int, int], raw_fn,
                           with_rng: bool = False) -> None:
        """``config.graph_lint``: run the registered jaxpr passes over a
        freshly-built bucket program (the RAW traceable fn, so the
        ``trace_counts`` retrace probes never see the extra trace).
        Findings accumulate in ``graph_lint_findings`` and emit
        ``analysis/*`` telemetry — advisory only; the blocking enforcement
        lives in the CI gate."""
        if not self.config.graph_lint:
            return
        try:
            from ...analysis import PassContext, run_graph_passes
            from ...telemetry.hub import emit_event

            structs = [
                jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    self.params),
                jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    self._cache()),
                jax.ShapeDtypeStruct((self._meta_len(key),), jnp.int32),
            ]
            if with_rng:
                structs.append(jax.ShapeDtypeStruct(self._rng.shape,
                                                    self._rng.dtype))
            # seed the replica-group pass with the REAL leaf shardings
            # (TP-sharded params are exactly the paged_kv_append class)
            shardings = [getattr(leaf, "sharding", None)
                         for leaf in jax.tree_util.tree_leaves(self.params)]
            shardings += [getattr(x, "sharding", None)
                          for x in jax.tree.leaves(self._cache())] + [None]
            if with_rng:
                shardings.append(None)
            artifact = f"{kind}[{self.config.attn_impl},bucket={key}]"
            findings = run_graph_passes(
                jax.make_jaxpr(raw_fn)(*structs),
                PassContext(artifact=artifact, arg_shardings=shardings))
            self.graph_lint_findings.extend(findings)
            for f in findings:
                emit_event("analysis/finding", pass_name=f.pass_name,
                           severity=f.severity, message=f.message,
                           file=f.file, line=f.line, artifact=f.artifact)
                log_dist(f"graph_lint: {f.render()}", ranks=[0])
            emit_event("analysis/graph_lint", artifact=artifact,
                       findings=len(findings))
        except Exception as e:  # noqa: BLE001 — advisory by contract:
            # a lint-machinery failure must never fail the serving path
            log_dist(f"graph_lint: lint of {kind}{key} failed ({e}); "
                     f"serving continues", ranks=[0])

    def _meta_len(self, key: Tuple[int, int]) -> int:
        """Length of the packed metadata vector of the bucket ``key``."""
        return pack_layout(key[0], key[1],
                           self._wrapper_for(key).max_blocks,
                           self.state_pool is not None)["_total"][0]

    def _program_kw(self, key: Tuple[int, int]) -> Dict:
        """What model_runner's builders take for the bucket ``key``."""
        c = self.config
        return dict(max_q=key[0], max_seqs=key[1],
                    max_blocks=self._wrapper_for(key).max_blocks,
                    num_blocks=self._num_blocks, attn_impl=c.attn_impl,
                    block_q=c.block_q, pages_per_chunk=c.pages_per_chunk,
                    jit=False, kv_replicate=self._kv_replicate)

    def _step_for(self, key: Tuple[int, int]):
        if key not in self._steps:
            fn = build_ragged_step(self.family, **self._program_kw(key))
            self._graph_lint_bucket("prefill", key, fn)
            name = f"serve_prefill_t{key[0]}"
            self._steps[key] = jax.jit(self._counted(key, fn, name),
                                       donate_argnums=(1,))
            self._register_program(name, "_steps", key, key)
        return self._steps[key]

    def _verify_step_for(self, key: Tuple[int, int]):
        """Per-bucket compiled spec-dec verify pass (model_runner.
        build_verify_step); first use of a bucket compiles — returns
        (step, first_compile) so callers can flag compile-polluted wall
        times off the telemetry plane like decode windows do."""
        first = key not in self._verify_steps
        if first:
            from .model_runner import build_verify_step

            fn = build_verify_step(self.family, **self._program_kw(key))
            self._graph_lint_bucket("verify", key, fn)
            self._verify_steps[key] = jax.jit(
                self._counted(("verify",) + key, fn,
                              f"serve_verify_t{key[0]}"),
                donate_argnums=(1,))
        return self._verify_steps[key], first

    # ------------------------------------------------------------------ #
    # Admission control (reference :158-242)
    # ------------------------------------------------------------------ #
    def query(self, uid: int, max_request_tokens: int, max_request_seqs: int):
        """Return (max_length, free_blocks) budget info for a uid."""
        seq = self.state_manager.get_sequence(uid)
        seen = seq.seen_tokens if seq else 0
        return self.config.max_ctx - seen, self.state_manager.free_blocks

    def can_schedule(self, uids: Sequence[int],
                     lengths: Sequence[int]) -> SchedulingResult:
        if len(uids) > self.config.max_seqs:
            return SchedulingResult.BatchSequenceLimitExceeded
        blocks_needed = 0
        slots_needed = 0
        for uid, n in zip(uids, lengths):
            seq = self.state_manager.get_sequence(uid)
            slots_needed += seq is None or seq.slot is None
            seen = seq.seen_tokens if seq else 0
            if seen + n > self.config.max_ctx:
                return SchedulingResult.SequenceTooLong
            cur = seq.cur_allocated_blocks if seq else 0
            blocks_needed += max(-(-(seen + n) // self.config.block_size) - cur, 0)
        if blocks_needed > self.state_manager.free_blocks:
            return SchedulingResult.KVCacheLimitExceeded
        if self.state_pool is not None \
                and slots_needed > self.state_manager.free_slots:
            # every slot of the state pool is owned by a live sequence
            return SchedulingResult.EngineSequenceLimitExceeded
        return SchedulingResult.Success

    # ------------------------------------------------------------------ #
    # Core forward (reference put :107)
    # ------------------------------------------------------------------ #
    def put(self, uids: Sequence[int],
            tokens_list: Sequence[Sequence[int]]) -> jnp.ndarray:
        """One forward over the given sequence chunks → last-token logits
        [n_seqs, vocab] in input order."""
        n_tokens = sum(len(t) for t in tokens_list)
        with _TRACER.span("engine/put", tokens=n_tokens,
                          n_seqs=len(uids)) as sp:
            with _TRACER.span("engine/put_pack"):
                verdict = self.can_schedule(uids,
                                            [len(t) for t in tokens_list])
                if verdict != SchedulingResult.Success:
                    raise RuntimeError(f"cannot schedule batch: {verdict}")
                self._decode_state = None  # host forward invalidates meta
                bucket = self.bucket_for(n_tokens, len(uids))
                sp.set(bucket=bucket[0])
                wrapper = self._wrapper_for(bucket)
                wrapper.clear()
                for uid, toks in zip(uids, tokens_list):
                    seq = self.state_manager.get_or_create_sequence(uid)
                    ok = self.state_manager.maybe_allocate_kv(seq, len(toks))
                    assert ok, "allocator raced"  # can_schedule checked
                    wrapper.insert_sequence(seq, list(toks))
                batch = wrapper.finalize()
                packed = batch.pack()
            with _TRACER.span("engine/put_dispatch"):
                # ONE metadata transfer per forward: ~15 small H2D copies
                # per decode step cost more than the step itself
                dev = jnp.asarray(packed)
                # (a routed family's step also returns its pairs per
                # expert; a prefill leaves them on the device)
                logits, new_cache, *_ = self._step_for(bucket)(
                    self.params, self._cache(), dev)
                self._cache_update(new_cache)
                for uid in batch.uids:
                    self.state_manager.get_sequence(uid).post_forward()
                self._touch_heat(batch.uids)
                return logits[:batch.n_seqs]

    def flush(self, uids: Sequence[int]) -> None:
        self._decode_state = None
        for uid in uids:
            self.state_manager.flush_sequence(uid)
            self._uid_tenants.pop(uid, None)

    # ------------------------------------------------------------------ #
    # Memory observability (telemetry/memory.py MemoryLedger plumbing)
    # ------------------------------------------------------------------ #
    def set_tenant(self, uid: int, tenant: Optional[str]) -> None:
        """Label ``uid``'s KV footprint with its tenant (lifecycle
        admission threads this through); cleared on flush."""
        if tenant:
            self._uid_tenants[int(uid)] = str(tenant)

    def _touch_heat(self, uids: Sequence[int]) -> None:
        """One heat-clock tick + whole-table touch for every sequence a
        dispatched forward covers (a decode/verify window reads ALL of a
        sequence's context pages; prefill writes its fresh ones)."""
        if self.heat is None:
            return
        self.heat.tick()
        blocks: List[int] = []
        for uid in uids:
            seq = self.state_manager.get_sequence(uid)
            if seq is not None:
                blocks.extend(seq.blocks)
        self.heat.touch(blocks)

    def memory_snapshot(self):
        """Heat-tracker snapshot with live holder/tenant attribution, or
        None when tracking is off."""
        if self.heat is None:
            return None
        holders = {uid: list(seq.blocks)
                   for uid, seq in self.state_manager._seqs.items()}
        return self.heat.snapshot(holders=holders,
                                  tenants=dict(self._uid_tenants))

    def _workspace_bytes(self) -> int:
        """Device bytes of decode-resume metadata + the sampling key — the
        ``decode_workspace`` ledger bucket."""
        n = int(getattr(self._rng, "nbytes", 0) or 0)
        st = self._decode_state
        if st is not None:
            n += int(getattr(st.get("meta"), "nbytes", 0) or 0)
        return n

    def register_memory_sources(self, ledger) -> None:
        """Wire this engine's known state trees into a
        :class:`~....telemetry.memory.MemoryLedger`: params, the KV page
        pool (the WHOLE preallocated pool — ``jax.live_arrays`` sees it
        regardless of allocation; used/free/cold lives in the heat
        section), decode workspace, and the heat snapshot."""
        ledger.register_source("params", lambda: self._param_bytes)
        ledger.register_source("kv_pages", lambda: self.kv.mem_bytes())
        if self.state_pool is not None:
            ledger.register_source("state_pool", self.state_pool.mem_bytes)
        ledger.register_source("decode_workspace", self._workspace_bytes)
        ledger.register_source(
            "host_kv",
            lambda: self.host_tier.used_bytes if self.host_tier else 0)
        ledger.attach_kv(self.memory_snapshot)
        if self.kv_swap is not None:
            ledger.attach_swap(self.kv_swap.stats)

    def kv_used_fraction(self) -> float:
        """Fraction of the KV block pool currently allocated — the
        scheduler's KV-pressure signal (preemption fires above its high
        watermark)."""
        total = self.state_manager.allocator.total_blocks
        return 1.0 - self.state_manager.free_blocks / total

    def lifetime_reservation(self, prompt_len: int,
                             max_new: int) -> Tuple[int, int]:
        """Whole-lifetime KV reservation for a request: (tokens, blocks).
        Capped at max_ctx — with an eos an early stop can keep
        prompt+max_new under the cap, so the cap, not the sum, is the
        reservation bound.  THE one definition of the admission formula;
        both ContinuousBatcher and LifecycleScheduler reserve through it
        so their admission behavior cannot desynchronize."""
        need = min(prompt_len + max_new, self.config.max_ctx)
        return need, -(-need // self.config.block_size)

    # ------------------------------------------------------------------ #
    # Radix prefix KV reuse (config.prefix_cache)
    # ------------------------------------------------------------------ #
    @property
    def prefix_cache(self):
        return self.state_manager.prefix_cache

    def _copy_pages(self, src_block: int, dst_block: int) -> None:
        """Copy one logical page across every layer's physical slot — the
        copy-on-write materialization for a shared partial page."""
        src = jnp.asarray([src_block + layer * self._num_blocks
                           for layer in range(self.family.page_layers)])
        dst = src + (dst_block - src_block)
        # (every array of the pool: a row's index key travels with it)
        self.kv.update(jax.tree.map(lambda a: a.at[dst].set(a[src]),
                                    self.kv.pages))
        if self.heat is not None:
            # the private copy inherits the shared page's heat — same
            # rows, same access history
            self.heat.transfer(src_block, dst_block)

    def _write_page_rows(self, block: int, rows) -> None:
        """H2D-write one logical page's canonical rows ``[L, block_size,
        2*KV, HD]`` into every layer's physical slot — the restore leg of
        a host-tier prefix spill."""
        phys = jnp.asarray([block + layer * self._num_blocks
                            for layer in range(self.family.page_layers)])
        self.kv.update(self.kv.pages.at[phys].set(
            jnp.asarray(rows, self.kv.pages.dtype)))

    def graft_prefix(self, uid: int, tokens: Sequence[int]) -> int:
        """Admission-side prefix reuse: graft the longest cached prefix of
        ``tokens`` into a fresh sequence and return how many tokens it
        covers (0 = miss / cache disabled); the caller prefills only the
        remainder.  Full matched pages are SHARED (one extra allocator ref
        each); a trailing partial page is copied into a private block
        before the graft returns — the sequence's very next forward
        appends into that page mid-row, and writing a shared page would
        corrupt every other holder (the copy-on-write invariant
        test_prefix_cache.py pins by checksumming the original page).
        When no block is free for the copy the partial page is simply
        dropped from the match — correctness never depends on the copy."""
        cache = self.prefix_cache
        if cache is None or len(tokens) < 2:
            return 0
        seq = self.state_manager.get_sequence(uid)
        assert seq is None or (not seq.blocks and seq.seen_tokens == 0), \
            f"prefix graft into a non-fresh sequence uid={uid}"
        matched, blocks, partial = cache.match(list(tokens))
        if self.kv_swap is not None and not partial:
            # extend the device-trie match through host-spilled full pages:
            # each one is re-materialized into a fresh block, re-committed
            # to the trie (which takes the owning ref), and then shared
            # with the sequence like any other matched page
            alloc = self.state_manager.allocator
            bs = self.config.block_size
            while matched + bs <= len(tokens) - 1:
                path = tuple(int(t) for t in tokens[:matched + bs])
                rows = self.kv_swap.peek_prefix(path)
                if rows is None:
                    break
                if alloc.free_blocks < 1:
                    cache.evict(1)
                if alloc.free_blocks < 1:
                    break
                blk = int(alloc.allocate(1)[0])
                self._write_page_rows(blk, rows)
                cache.commit(list(tokens), blocks + [blk],
                             upto=matched + bs)
                alloc.free([blk])       # the trie's ref now owns the page
                self.kv_swap.confirm_prefix(path)
                blocks.append(blk)
                matched += bs
        if not matched:
            return 0
        # create the descriptor FIRST: get_or_create can raise on the
        # tracked-sequence cap, and nothing may be allocated before it
        seq = self.state_manager.get_or_create_sequence(uid)
        if partial:
            # CoW the tail page: private copy, or shrink the match
            alloc = self.state_manager.allocator
            if alloc.free_blocks < 1:
                cache.evict(1)
            if alloc.free_blocks < 1:
                matched -= partial
                blocks = blocks[:-1]
                if not matched:
                    return 0
            else:
                private = int(alloc.allocate(1)[0])
                self._copy_pages(blocks[-1], private)
                # the sequence owns `private`; share only the full pages
                self.state_manager.share_blocks(seq, blocks[:-1],
                                                matched - partial)
                seq.blocks.append(private)
                seq.seen_tokens = matched
                return matched
        self.state_manager.share_blocks(seq, blocks, matched)
        return matched

    def commit_prefix(self, uid: int, tokens: Sequence[int],
                      allow_partial: bool = False) -> int:
        """Commit ``uid``'s prompt pages to the radix cache (no-op when
        disabled).  Called at prefill completion (full pages only — the
        sequence keeps appending into its partial tail) and again at
        retirement with ``allow_partial=True``, when the tail page goes
        quiet forever."""
        cache = self.prefix_cache
        seq = self.state_manager.get_sequence(uid)
        if cache is None or seq is None:
            return 0
        upto = min(len(tokens), seq.seen_tokens)
        return cache.commit(list(tokens), seq.blocks, upto=upto,
                            allow_partial=allow_partial)

    # ------------------------------------------------------------------ #
    # Speculative decoding: verify-window mode over the paged decode path
    # ------------------------------------------------------------------ #
    def rollback_kv(self, uid: int, new_seen: int) -> None:
        """Truncate ``uid``'s KV length to ``new_seen`` tokens — the
        spec-dec rejection path (and the draft engine's resync path).

        Cheap by construction: pages are NEVER copied or freed — the block
        allocator's truncation-keeps-mid-block-state property means the
        rows past the new length are simply dead, and the next append for
        this sequence overwrites them (positions re-derive from
        ``seen_tokens``).  Blocks stay allocated so a whole-lifetime
        reservation (LifecycleScheduler admission invariant: live requests
        never allocate mid-flight) survives any number of rollbacks; the
        over-hold is bounded by one speculative window.  Device-resident
        decode-resume metadata is invalidated — it was advanced past the
        rollback point."""
        seq = self.state_manager.get_sequence(uid)
        assert seq is not None, f"rollback of unknown uid {uid}"
        assert 0 <= new_seen <= seq.seen_tokens, \
            f"rollback can only truncate: {new_seen} > {seq.seen_tokens}"
        seq.seen_tokens = int(new_seen)
        seq.in_flight_tokens = 0
        self._decode_state = None

    def verify_decode(self, uids: Sequence[int],
                      seed_tokens: Sequence[int],
                      drafts: Sequence[Sequence[int]],
                      draft_wall_s: float = 0.0) -> "VerifyResult":
        """One speculative verify window: score every sequence's
        ``[seed] + draft`` candidate row in ONE ragged multi-token pass,
        accept the longest prefix matching the target's greedy argmax, and
        roll the KV length back past the first rejection.

        Greedy bit-exactness by construction: position 0's argmax is
        computed over exactly the context vanilla decode would see for the
        seed token, and draft position j only stays in the chain when every
        earlier candidate matched — so the emitted tokens are the vanilla
        greedy stream, just discovered up to ``K+1`` at a time.  Every
        window emits at least one token (the seed position's argmax), so
        rejection can never stall a stream; acceptance only changes speed.

        KV accounting: the full speculative extent (``1 + len(draft)``
        tokens per row) is appended — and allocated — up front, so
        KV-pressure signals (``kv_used_fraction``) count speculative pages
        while the window is in flight; rejection truncates the length
        (``rollback_kv``) without touching pages.

        ``draft_wall_s`` (host time the caller spent drafting) folds into
        the published ``serving/draft_overhead_frac`` / effective-tok/s
        gauges.  Shares the ``decode_window`` fault-injection site and the
        per-sequence non-finite isolation contract with fused decode
        windows."""
        n = len(uids)
        assert n == len(seed_tokens) == len(drafts)
        if self.latent_kv:
            raise NotImplementedError(
                "verify_decode (speculative decoding) is not supported with "
                "latent (MLA) pages: serve this model without a drafter")
        if self.index_kv is not None:
            raise NotImplementedError(
                "verify_decode (speculative decoding) is not supported with "
                "index keys beside the K/V rows: a verify window has no "
                "sparse read (every candidate would need its own set)")
        if self.state_pool is not None:
            raise NotImplementedError(
                "verify_decode (speculative decoding) is not supported with "
                "recurrent state: a rejected candidate cannot be taken back "
                "out of a state without a snapshot (ROADMAP R5)")
        lens = [1 + len(d) for d in drafts]
        if sum(lens) > self.config.max_tokens:
            # fail BEFORE touching allocator/descriptor state: the ragged
            # pack would raise mid-insert otherwise.  Callers must deal
            # draft lengths out of the flat token budget (the lifecycle
            # scheduler does; see _run_verify_window).
            raise RuntimeError(
                f"verify window needs {sum(lens)} flat tokens "
                f"({n} seqs + drafts) > max_tokens "
                f"{self.config.max_tokens} — cap the draft lengths")
        verdict = self.can_schedule(uids, lens)
        if verdict != SchedulingResult.Success:
            raise RuntimeError(f"cannot schedule verify window: {verdict}")
        self._decode_state = None      # host forward invalidates device meta
        bucket = self.bucket_for(sum(lens), n)
        with _TRACER.span("engine/decode_dispatch", n_seqs=n, steps=1,
                          key=f"verify{bucket[0]}", resumed=False) as sp:
            with _TRACER.span("engine/decode_pack"):
                wrapper = self._wrapper_for(bucket)
                wrapper.clear()
                ctx_before = []
                for uid, seed, draft in zip(uids, seed_tokens, drafts):
                    seq = self.state_manager.get_or_create_sequence(uid)
                    ctx_before.append(seq.seen_tokens)
                    ok = self.state_manager.maybe_allocate_kv(
                        seq, 1 + len(draft))
                    assert ok, "allocator raced"  # can_schedule checked
                    wrapper.insert_sequence(
                        seq, [int(seed)] + [int(t) for t in draft])
                batch = wrapper.finalize()
                dev = jnp.asarray(batch.pack())
            with _TRACER.span("engine/decode_launch"):
                step, first_compile = self._verify_step_for(bucket)
                sp.set(compiled=first_compile)

                t0 = time.perf_counter()
                self.decode_windows_dispatched += 1
                poisoned = False
                try:
                    inject("decode_window",
                           step=self.decode_windows_dispatched)
                except InjectedNaN:
                    poisoned = True
                    self._poison_kv(uids[0])
                greedy_dev, bad_dev, new_pages = step(self.params,
                                                      self.kv.pages, dev)
                self.kv.update(new_pages)
        with _TRACER.span("engine/window_wait", steps=1):
            jax.block_until_ready(greedy_dev)
        with _TRACER.span("engine/window_fetch"):
            greedy = np.asarray(greedy_dev)
            bad = np.asarray(bad_dev)
        duration_s = time.perf_counter() - t0

        accepted: List[List[int]] = []
        nonfinite_uids: List[int] = []
        drafted = accepted_draft = 0
        for row, (uid, draft) in enumerate(zip(uids, drafts)):
            seq = self.state_manager.get_sequence(uid)
            seq.post_forward()         # seen += 1 + len(draft)
            if bool(bad[row]):
                # poisoned row: emit nothing and leave NO speculative KV —
                # the caller flushes the request (NaN isolation, as in
                # fused decode windows); batchmates stay clean
                self.rollback_kv(uid, ctx_before[row])
                nonfinite_uids.append(uid)
                accepted.append([])
                continue
            off = int(batch.q_offset[row])
            g = [int(t) for t in greedy[off:off + 1 + len(draft)]]
            a = 0
            while a < len(draft) and int(draft[a]) == g[a]:
                a += 1
            drafted += len(draft)
            accepted_draft += a
            accepted.append(g[:a + 1])
            # truncate to the accepted length: seed + a matched drafts are
            # real context; rows past them are dead until overwritten
            self.rollback_kv(uid, ctx_before[row] + 1 + a)
        self._touch_heat(uids)
        emitted = sum(len(t) for t in accepted)
        self.spec_windows += 1
        self.spec_drafted += drafted
        self.spec_draft_accepted += accepted_draft
        self.spec_emitted += emitted
        result = VerifyResult(
            uids=list(uids), accepted=accepted,
            nonfinite_uids=nonfinite_uids, drafted=drafted,
            accepted_draft=accepted_draft, emitted=emitted,
            duration_s=duration_s, draft_s=float(draft_wall_s),
            compiled=first_compile, poisoned=poisoned)
        with _TRACER.span("engine/window_account"):
            self._record_verify_window(result)
        return result

    def _record_verify_window(self, result: "VerifyResult") -> None:
        """Publish the spec-dec gauges (``serving/acceptance_rate``,
        ``serving/effective_tok_per_s``, ``serving/draft_overhead_frac``)
        and a ``verify_window`` event.  Compile-polluted windows (first use
        of a verify bucket) stay off the telemetry plane — their wall time
        measures XLA compilation, exactly like decode-window rooflines."""
        if result.compiled:
            return
        from ...telemetry import get_telemetry

        tel = get_telemetry()
        if tel is None:
            return
        m = tel.metrics
        if self.spec_drafted:
            m.gauge("serving/acceptance_rate").set(
                round(self.spec_draft_accepted / self.spec_drafted, 4))
        wall = result.duration_s + result.draft_s
        if wall > 0:
            m.gauge("serving/effective_tok_per_s").set(
                round(result.emitted / wall, 2))
            m.gauge("serving/draft_overhead_frac").set(
                round(result.draft_s / wall, 4))
        tel.event("verify_window", n_seqs=len(result.uids),
                  drafted=result.drafted,
                  accepted_draft=result.accepted_draft,
                  emitted=result.emitted,
                  acceptance=round(result.accepted_draft /
                                   result.drafted, 4)
                  if result.drafted else None,
                  duration_s=round(result.duration_s, 6),
                  draft_s=round(result.draft_s, 6))

    # ------------------------------------------------------------------ #
    # Fused multi-step decode (device-resident loop; the CUDA-graph-decode
    # analogue — kills the host round trip per generated token)
    # ------------------------------------------------------------------ #
    def decode_batch(self, uids: Sequence[int],
                     seed_tokens: Sequence[int], steps: int,
                     temperature: float = 0.0,
                     rng: Optional[jax.Array] = None,
                     top_k: Optional[int] = None) -> np.ndarray:
        """Run ``steps`` decode iterations for ``uids`` entirely on device
        and block for the tokens [steps, n_seqs] (host numpy); the last
        generated token is NOT appended to the cache (matching put()
        semantics — it is the next call's seed).  See
        :meth:`decode_batch_async` for the non-blocking form."""
        return self.decode_batch_async(uids, seed_tokens, steps,
                                       temperature=temperature, rng=rng,
                                       top_k=top_k).tokens()

    def decode_batch_async(self, uids: Sequence[int],
                           seed_tokens: Sequence[int], steps: int,
                           temperature: float = 0.0,
                           rng: Optional[jax.Array] = None,
                           top_k: Optional[int] = None) -> "DecodeWindow":
        """Dispatch a fused decode window WITHOUT waiting for its tokens.

        Each sequence starts from its ``seed_tokens[i]`` (the next input
        token, e.g. the argmax of its prefill logits) and decodes ``steps``
        tokens with NO host synchronisation between steps: sampling
        (argmax / temperature / top-k) runs on device, KV blocks for the
        whole window are allocated up front so the block table is static,
        and the packed metadata advances on device.

        Device-resident continuation: the loop returns its ADVANCED
        metadata (next seed token, positions, ctx lengths) and the engine
        caches it; when the next window targets the same uid set with
        unchanged KV block tables, the cached device array is reused —
        no host repack, no H2D upload (``decode_resume_hits``).  If the
        previous window was already drained its last tokens are known on
        the host, and ``seed_tokens`` are honored: seeds matching the
        cached stream resume device-side, different seeds (stop-token
        rewrites, guided decoding) force a repack.

        One window ahead: a window may be dispatched over the uid set of
        the previous window BEFORE that one is drained — dispatch the next
        window first, THEN drain the previous handle's ``tokens()``, so the
        host's fetch, bookkeeping, packing and launch run while the device
        decodes.  The seeds are then unknowable on the host and
        ``seed_tokens`` are advisory: the undrained window's advanced
        metadata holds them ON THE DEVICE.  Where no block table grew (a
        scheduler that reserved the riders' whole lifetime at admission:
        always) the window resumes as above and nothing syncs.  Where one
        did (a caller that allocates a window at a time) the metadata must
        be packed anew and the true seeds are read back from the device: a
        sync with the undrained window, i.e. its drain — correct, and no
        window ahead.  At most two windows are so committed to the device:
        the one being drained and the one dispatched ahead of that drain.
        """
        with _TRACER.span("engine/decode_dispatch", n_seqs=len(uids),
                          steps=steps) as sp:
            return self._dispatch_decode_window(
                sp, uids, seed_tokens, steps, temperature, rng, top_k)

    def decode_chains(self, uids: Sequence[int]) -> bool:
        """Does the last window's advanced metadata still describe exactly
        ``uids``, in this order, with nothing cached since?  Then a window
        over them may be dispatched before that one is drained (see
        :meth:`decode_batch_async`); a host forward, a flush or a poisoned
        window in between says no, and the caller drains first."""
        st = self._decode_state
        if st is None or st["uids"] != tuple(uids):
            return False
        seqs = map(self.state_manager.get_sequence, uids)
        return all(seq is not None and st["seen"][seq.uid] == seq.seen_tokens
                   for seq in seqs)

    def _dispatch_decode_window(self, sp, uids, seed_tokens, steps,
                                temperature, rng, top_k) -> "DecodeWindow":
        c = self.config
        n = len(uids)
        with _TRACER.span("engine/decode_alloc"):
            verdict = self.can_schedule(uids, [steps] * n)
            if verdict != SchedulingResult.Success:
                raise RuntimeError(
                    f"cannot schedule decode window: {verdict}")
            # decode bucket: one flat token per sequence — the compiled
            # program carries n-ish tokens of work, not the full max_tokens
            # budget
            s_b = self._seq_bucket(n)
            bucket = (s_b, s_b)
            ctx_before = []
            grew = False
            for uid in uids:
                seq = self.state_manager.get_or_create_sequence(uid)
                ctx_before.append(seq.seen_tokens)
                prev = seq.cur_allocated_blocks
                ok = self.state_manager.maybe_allocate_kv(seq, steps)
                assert ok, "allocator raced"
                grew |= seq.cur_allocated_blocks != prev

            st = self._decode_state
            chained = self.decode_chains(uids)
            # off an UNDRAINED window: its last tokens are not on the host
            # and the caller's seeds are advisory
            undrained = chained and "last_tokens" not in st
            resume = chained and not grew and st["bucket"] == bucket
            if resume and not undrained:
                # the previous window was drained, so the caller KNOWS the
                # stream — a seed differing from the cached on-device token
                # (stop-token rewrite, guided decoding) must win over resume
                resume = tuple(int(t) for t in seed_tokens) \
                    == st["last_tokens"]
        if resume:
            self.decode_resume_hits += 1
            meta_dev = st["meta"]
        else:
            if undrained:
                # chaining off an UNDRAINED window that cannot resume
                # (block growth crossed a page boundary): the caller's
                # seeds are advisory and unknowable, so packing them would
                # silently corrupt the stream — the true next tokens are
                # the advanced meta's tokens field.  Reading it syncs with
                # the previous window, the price of a growth-boundary
                # repack; a scheduler that reserves its riders' whole
                # lifetime never pays it.  (Same uids ⟹ same n ⟹ same
                # bucket, so the slice below is the previous window's seq
                # rows.)
                seed_tokens = [int(t) for t in np.asarray(st["meta"][:n])]
            with _TRACER.span("engine/decode_pack"):
                wrapper = self._wrapper_for(bucket)
                wrapper.clear()
                for uid, tok in zip(uids, seed_tokens):
                    wrapper.insert_sequence(
                        self.state_manager.get_sequence(uid), [int(tok)])
                meta_dev = jnp.asarray(wrapper.finalize().pack())

        with _TRACER.span("engine/decode_launch"):
            return self._launch_decode_window(
                sp, uids, seed_tokens, steps, temperature, rng, top_k,
                bucket, meta_dev, resume, ctx_before)

    def _launch_decode_window(self, sp, uids, seed_tokens, steps,
                              temperature, rng, top_k, bucket, meta_dev,
                              resume, ctx_before) -> "DecodeWindow":
        c = self.config
        n = len(uids)
        uids_t = tuple(uids)
        top_k = c.top_k if top_k is None else int(top_k)
        key = (bucket, steps, float(temperature), top_k)
        first_compile = key not in self._decode_loops
        sp.set(key=f"{bucket[0]}x{steps}", resumed=resume,
               compiled=first_compile)
        if first_compile:
            from .model_runner import build_decode_loop

            loop = build_decode_loop(
                self.family, block_size=c.block_size, steps=steps,
                temperature=temperature, top_k=top_k,
                **self._program_kw(bucket))
            self._graph_lint_bucket("decode_loop", bucket, loop,
                                    with_rng=True)
            name = f"serve_decode_s{bucket[0]}x{steps}"
            self._decode_loops[key] = jax.jit(
                self._counted(("decode",) + key, loop, name),
                donate_argnums=(1,))
            self._register_program(name, "_decode_loops", key, bucket,
                                   with_rng=True)
        if rng is None:
            # persistent engine key: re-seeding each window with a constant
            # would repeat the identical sample stream every call
            self._rng, rng = jax.random.split(self._rng)
        # fault-injection site (DSTPU_FAULT_INJECT site=decode_window):
        # `slow` sleeps here (hung window), `kill` dies here (worker loss
        # mid-decode), `nan` poisons the FIRST scheduled sequence's cached
        # context so the compiled loop genuinely produces non-finite logits
        # for that row — exercising the same isolation path a hardware
        # NaN would.  t0 starts BEFORE the site so an injected stall lands
        # inside the window's wall time, where the decode watchdog looks.
        t0 = time.perf_counter()
        self.decode_windows_dispatched += 1
        poisoned = False
        try:
            inject("decode_window", step=self.decode_windows_dispatched)
        except InjectedNaN:
            poisoned = True
            if resume:
                # the resume path skipped the repack; recover the true next
                # tokens from the advanced device meta and rebuild host-side
                # so the window still dispatches against valid metadata
                seed_tokens = [int(t) for t in np.asarray(meta_dev[:n])]
                wrapper = self._wrapper_for(bucket)
                wrapper.clear()
                for uid, tok in zip(uids, seed_tokens):
                    wrapper.insert_sequence(
                        self.state_manager.get_sequence(uid), [int(tok)])
                meta_dev = jnp.asarray(wrapper.finalize().pack())
                resume = False
            self._poison_kv(uids[0])
        toks, new_cache, meta_out, nonfinite, *extra = \
            self._decode_loops[key](self.params, self._cache(), meta_dev, rng)
        self._cache_update(new_cache)
        seen = {}
        for uid in uids:
            seq = self.state_manager.get_sequence(uid)
            seq.in_flight_tokens = steps
            seq.post_forward()
            seen[uid] = seq.seen_tokens
        self._touch_heat(uids)
        # a NaN-poisoned window must NOT leave resumable device state: the
        # advanced meta was computed over poisoned pages, and a follow-up
        # window resuming it would silently keep decoding garbage even if
        # the caller never drains/flushes the victim
        self._decode_state = None if poisoned else {
            "uids": uids_t, "bucket": bucket, "meta": meta_out,
            "seen": seen}
        mean_ctx = float(np.mean(ctx_before)) + steps / 2.0 if n else 0.0
        window = DecodeWindow(self, toks, n, steps, mean_ctx, t0,
                              resumed=resume, compiled=first_compile,
                              uids=list(uids), nonfinite_dev=nonfinite,
                              moe_pairs_dev=extra[0] if extra else None)
        window._state = self._decode_state
        window._ctx_before = list(ctx_before)
        last, self._last_window = self._last_window, window
        if last is not None and last._drained_t is None:
            window._after = last        # queued behind an undrained window
        return window

    def _poison_kv(self, uid: int) -> None:
        """Write NaN over every cached page of ``uid`` across all layers
        (the ``decode_window``/``nan`` injection payload).  Rows past the
        sequence's context length are masked out by attention, and — with
        prefix reuse — pages holding more than one reference (shared via
        the radix cache) are SKIPPED: poisoning a shared system-prompt
        page would leak NaN into every co-tenant, breaking exactly the
        isolation property this injection exists to exercise.  The
        sequence's privately-owned decode pages (there is always at least
        one: decode windows allocate before the injection site fires) are
        enough to drive its logits non-finite."""
        seq = self.state_manager.get_sequence(uid)
        if seq is None or not seq.blocks:
            return
        alloc = self.state_manager.allocator
        own = [b for b in seq.blocks if alloc.refcount(b) == 1]
        if not own:
            # cannot happen for a decoding sequence (its tail page is
            # always private: fresh alloc or CoW copy) — but never poison
            # a shared page, whatever state got us here
            logger.warning(f"nan injection skipped: uid {uid} owns no "
                           f"private page")
            return
        phys = [b + layer * self._num_blocks
                for layer in range(self.family.page_layers) for b in own]
        phys = jnp.asarray(phys)
        self.kv.update(jax.tree.map(lambda a: a.at[phys].set(jnp.nan),
                                    self.kv.pages))

    @property
    def last_decode_roofline(self) -> Optional[Dict]:
        """The analytic HBM roofline of the last drained decode window
        (decode is bandwidth-bound, so %-of-peak HBM — not MFU — is its
        utilization number), computed when asked for.  A window that
        compiled its loop is flagged ``compile_polluted``: its wall time
        measures XLA, not decode."""
        facts = self._last_window_facts
        if facts is None:
            return None
        if self._last_roofline is None or self._last_roofline[0] is not facts:
            from ...profiling.serving_roofline import (
                decode_roofline_report, decode_window_bytes)

            n_seqs, steps, mean_ctx, duration_s, resumed, compiled = facts
            # decode_window_bytes counts 2*KV*hd values a token a layer:
            # the family's row says how many that is
            report = decode_roofline_report(decode_window_bytes(
                num_layers=self.family.page_layers, num_kv_heads=1,
                head_dim=self.family.row.read_values // 2,
                kv_dtype_bytes=jnp.dtype(self.kv.config.dtype).itemsize,
                param_bytes=self._param_bytes, n_seqs=n_seqs, steps=steps,
                mean_ctx=mean_ctx), duration_s, n_seqs, steps)
            report["resumed"] = resumed
            report["compile_polluted"] = compiled
            self._last_roofline = (facts, report)
        return self._last_roofline[1]

    def _account_decode_window(self, window: "DecodeWindow") -> None:
        """Keep a drained window's facts for ``last_decode_roofline`` and,
        when the process-global telemetry hub is installed, mirror the
        report into ``serving/*`` gauges so ``dstpu-telemetry`` renders the
        serving section.  With no hub this is one tuple."""
        if not window.n_seqs or not window.duration_s:
            return
        self._last_window_facts = (
            window.n_seqs, window.steps, window.mean_ctx, window.duration_s,
            window.resumed, window.compiled)
        if window.compiled:
            # first window per loop key times trace+XLA-compile inside its
            # wall clock; publishing that as tok/s or HBM %-of-peak would
            # put a ~100x-low sample on the telemetry plane.  The flagged
            # report stays on last_decode_roofline for inspection.
            return
        from ...telemetry import get_telemetry

        tel = get_telemetry()
        if tel is None:
            return
        from ...profiling.roofline import (kernel_roofline_report,
                                           publish_kernel_gauges)
        from ...profiling.serving_roofline import publish_decode_gauges

        report = self.last_decode_roofline
        publish_decode_gauges(tel.metrics, report)
        # per-kernel %-of-peak roofline (kernels/* gauges, the
        # dstpu-telemetry "kernels" section) for the decode attention
        # kernel: its analytic page-walk bytes over the window wall, plus
        # the QK+PV flops (decode is memory-bound — pct_peak_hbm is the
        # number that matters; flops ride along for the AI)
        fam = self.family
        attn_bytes = report["kernels"]["decode_attention"]["bytes"]
        attn_flops = (fam.row.attn_flops * fam.num_heads
                      * window.mean_ctx * window.n_seqs * window.steps
                      * fam.page_layers)
        kname = "decode_paged" if self.config.attn_impl == "paged" \
            else "decode_dense"
        publish_kernel_gauges(tel.metrics, kernel_roofline_report(
            kname, attn_flops, attn_bytes, window.duration_s))
        tel.event("decode_window", tok_per_s=report["decode_tok_per_s"],
                  hbm_pct_peak=report["hbm_pct_peak"],
                  n_seqs=window.n_seqs, steps=window.steps,
                  resumed=window.resumed)

    # ------------------------------------------------------------------ #
    # Dynamic SplitFuse scheduling (MII-layer policy, host-only logic)
    # ------------------------------------------------------------------ #
    def schedule(self, pending: Dict[int, List[int]]) -> List[Tuple[int, List[int]]]:
        """One-shot scheduling over a pending dict: decodes first (1 token
        each), then prompt chunks split to fill the token budget — the
        SplitFuse recipe.  O(pending) per call; the stateful
        :class:`ContinuousBatcher` is the O(batch)-per-step path."""
        budget = self.config.max_tokens
        picked: List[Tuple[int, List[int]]] = []
        # decodes (single token) first
        for uid, toks in list(pending.items()):
            if len(toks) == 1 and budget >= 1 and len(picked) < self.config.max_seqs:
                picked.append((uid, toks))
                budget -= 1
        for uid, toks in list(pending.items()):
            if len(toks) > 1 and budget > 0 and len(picked) < self.config.max_seqs:
                chunk = toks[:budget]
                picked.append((uid, chunk))
                budget -= len(chunk)
        return picked

    # ------------------------------------------------------------------ #
    # Convenience generation loop (greedy/temperature)
    # ------------------------------------------------------------------ #
    def generate(self, prompts: Sequence[Sequence[int]], max_new_tokens: int = 32,
                 temperature: float = 0.0, rng: Optional[jax.Array] = None,
                 eos_token_id: Optional[int] = None) -> List[List[int]]:
        """Batched generation through the stateful continuous batcher:
        SplitFuse prefill chunks + fused on-device decode windows, with KV
        backpressure (prompts queue instead of raising when the cache is
        full) and O(batch) scheduling cost per step."""
        pool = self.kv.config.num_blocks
        for p in prompts:
            # preserve the hard-error contract for impossible requests (the
            # batcher API rejects gracefully; generate() callers expect the
            # old put()-style RuntimeError).  With eos an early stop can
            # keep prompt+max_new under the cap, so only the eos-less case
            # is deterministically impossible.
            if len(p) > self.config.max_ctx or (
                    eos_token_id is None and
                    len(p) + max_new_tokens > self.config.max_ctx):
                raise RuntimeError(
                    f"cannot schedule batch: {SchedulingResult.SequenceTooLong}"
                    f" (prompt {len(p)} + {max_new_tokens} new > max_ctx "
                    f"{self.config.max_ctx})")
            need = min(len(p) + max_new_tokens, self.config.max_ctx)
            if -(-need // self.config.block_size) > pool:
                raise RuntimeError(
                    f"cannot schedule batch: "
                    f"{SchedulingResult.KVCacheLimitExceeded} (request needs "
                    f"{need} tokens; pool holds "
                    f"{pool * self.config.block_size})")
        batcher = ContinuousBatcher(self, max_new_tokens=max_new_tokens,
                                    temperature=temperature,
                                    eos_token_id=eos_token_id, rng=rng)
        for u, p in enumerate(prompts):
            batcher.add_request(u, list(p))
        done = batcher.run()
        return [done[u] for u in range(len(prompts))]

    def serialize(self, path: str) -> None:
        """Persist params (reference :251)."""
        from ...runtime.checkpoint_engine.orbax_checkpoint_engine import (
            OrbaxCheckpointEngine,
        )

        OrbaxCheckpointEngine(path).save(self.params, "model")


@dataclasses.dataclass
class VerifyResult:
    """Outcome of one speculative verify window
    (:meth:`InferenceEngineV2.verify_decode`).

    ``accepted[i]`` is the greedy token chain emitted for ``uids[i]`` —
    ``1 + a_i`` tokens where ``a_i`` is the matched-draft prefix length;
    its LAST element is the next decode seed (not yet in the KV cache,
    matching put()/decode semantics).  A uid listed in ``nonfinite_uids``
    emitted nothing and its KV was rolled back to the pre-window length.
    """

    uids: List[int]
    accepted: List[List[int]]
    nonfinite_uids: List[int]
    drafted: int                 # draft candidate tokens scored
    accepted_draft: int          # of those, matched the greedy chain
    emitted: int                 # tokens produced (>= len(uids) - poisoned)
    duration_s: float            # verify forward wall time
    draft_s: float               # caller-reported drafting wall time
    compiled: bool               # first use of this verify bucket
    poisoned: bool               # decode_window nan injection fired

    @property
    def acceptance_rate(self) -> float:
        return self.accepted_draft / self.drafted if self.drafted else 0.0


class DecodeWindow:
    """Handle for an in-flight fused decode window (JAX async dispatch).

    Created by :meth:`InferenceEngineV2.decode_batch_async`; the device is
    already executing the window.  :meth:`tokens` blocks for the result and
    (once) feeds the window's wall time into the decode HBM roofline.

    ``duration_s`` is dispatch→drain WALL time (JAX exposes no per-dispatch
    device time): host work done between dispatch and :meth:`tokens`
    inflates it and understates the published tok/s / HBM %-of-peak gauges.
    Drain promptly when the roofline numbers matter — the benches do.  A
    window dispatched while its predecessor was undrained started on the
    device when that one ended, not when it was dispatched: its time runs
    from the predecessor's drain, or a chained window would read as two.

    :meth:`tokens` launches NO device program: it waits for the window's own
    outputs, copies them whole and cuts them to ``n_seqs`` on the host.  A
    slice taken on the device would queue behind whatever was dispatched
    since — behind the NEXT window, when the caller runs one ahead — and the
    drain of window i would wait for window i+1.
    """

    def __init__(self, engine: "InferenceEngineV2", toks_dev, n_seqs: int,
                 steps: int, mean_ctx: float, t0: float,
                 resumed: bool = False, compiled: bool = False,
                 uids: Optional[List[int]] = None, nonfinite_dev=None,
                 moe_pairs_dev=None):
        self.engine = engine
        self.n_seqs = n_seqs
        self.steps = steps
        self.mean_ctx = mean_ctx
        self.resumed = resumed
        #: True when this window traced+compiled its decode loop — its wall
        #: time measures XLA compilation, not decode throughput
        self.compiled = compiled
        #: uids in seq-row order, so nonfinite flags map back to requests
        self.uids = list(uids) if uids is not None else []
        self._toks_dev = toks_dev
        self._nonfinite_dev = nonfinite_dev
        #: routed families: (token, choice) pairs per expert, summed over
        #: the window's steps and expert layers; host numpy after the drain
        self._moe_pairs_dev = moe_pairs_dev
        self.moe_pairs: Optional[np.ndarray] = None
        self._t0 = t0
        self._toks: Optional[np.ndarray] = None
        #: per-sequence poison flags [n_seqs], populated at drain: True
        #: when that sequence's logits went non-finite during the window
        self.nonfinite: Optional[np.ndarray] = None
        self.duration_s: Optional[float] = None
        self._state: Optional[dict] = None
        #: each row's cached tokens before the window (the launch sets it)
        self._ctx_before: List[int] = []
        #: the window this one was queued behind, while that one is undrained
        self._after: Optional["DecodeWindow"] = None
        self._drained_t: Optional[float] = None

    def tokens(self) -> np.ndarray:
        """Block for the generated tokens [steps, n_seqs]."""
        if self._toks is None:
            # the sync the copies below would make anyway, timed apart: the
            # wait is the device's, the fetch is the host's
            with _TRACER.span("engine/window_wait", steps=self.steps):
                jax.block_until_ready((self._toks_dev, self._nonfinite_dev,
                                       self._moe_pairs_dev))
            with _TRACER.span("engine/window_fetch"):
                n = self.n_seqs     # (the bucket's pad rows are cut HERE)
                self._toks = np.asarray(self._toks_dev)[:, :n]
                self.nonfinite = np.zeros(n, bool) \
                    if self._nonfinite_dev is None \
                    else np.asarray(self._nonfinite_dev)[:n]
                if self._moe_pairs_dev is not None:
                    self.moe_pairs = np.asarray(self._moe_pairs_dev)
            self._drained_t = time.perf_counter()
            after, self._after = self._after, None
            began = self._t0 if after is None or after._drained_t is None \
                else max(self._t0, after._drained_t)
            self.duration_s = self._drained_t - began
            self._toks_dev = None
            self._nonfinite_dev = None
            self._moe_pairs_dev = None
            with _TRACER.span("engine/window_account") as asp:
                if self.moe_pairs is not None:
                    self._account_moe(asp)
                if self.engine.index_kv is not None:
                    self._account_sparse(asp)
                pool = self.engine.state_pool
                if pool is not None:
                    used = pool.slots - self.engine.state_manager.free_slots
                    asp.set(state_slots=used, state_bytes=pool.mem_bytes(),
                            state_fill=used / pool.slots,
                            state_pad_share=pool.pad_share)
                if self.engine.family.window is not None:
                    self._account_window(asp)
                if self.engine.family.page_readers is not None:
                    # rows ONE reader of a page layer reads in the window:
                    # every rider's context at every step
                    asp.set(shared_read_layers=max(
                        self.engine.family.page_readers),
                        page_rows_read=int(sum(
                            c * self.steps + self.steps * (self.steps + 1) // 2
                            for c in self._ctx_before)))
                if self._state is not None and \
                        self.engine._decode_state is self._state:
                    # the last sampled token is the next window's seed: once
                    # it is host-known, resume can honor caller-supplied
                    # seeds
                    self._state["last_tokens"] = tuple(
                        int(t) for t in self._toks[-1])
                self.engine._account_decode_window(self)
        return self._toks

    def _account_window(self, sp) -> None:
        """What the window layers hold and read: a sequence's ring has as
        many live rows as the sequence has tokens, up to the window
        (``window_rows_held``: mean over the riders at the window's end),
        and a step reads them once a window layer (``window_rows_read``:
        summed over riders and steps, ONE layer's)."""
        ring = self.engine.family.window
        ctx = np.asarray(self._ctx_before, np.int64)[:, None] \
            + np.arange(1, self.steps + 1)[None, :]     # [riders, steps]
        held = np.minimum(ctx, ring.window)
        sp.set(window_layers=ring.num_layers, window_rows=ring.window,
               window_rows_held=float(held[:, -1].mean()) if ctx.size else 0.0,
               window_rows_read=int(held.sum()))

    def _account_moe(self, sp) -> None:
        """Dropless by construction: every (token, choice) pair of the
        window's live rows reached an expert.  ``moe_pairs_dropped`` is
        what the routing says it should have computed less what the
        experts' groups held: 0, asserted."""
        counts = self.engine.family.counts
        E = counts.num_experts
        held = self.moe_pairs[:E]
        pairs = int(held.sum())
        # a chip's share: the pairs of experts held elsewhere are counted
        # apart, neither computed nor dropped here; so are the pairs of
        # experts without weights (identity), which no chip owes
        elsewhere = int(self.moe_pairs[E]) if counts.elsewhere else 0
        identity = int(self.moe_pairs[-1]) if counts.identity else 0
        expected = self.n_seqs * self.steps * counts.per_token
        dropped = expected - pairs - elsewhere - identity
        assert dropped == 0, \
            f"dropless expert layer lost pairs: {expected} routed, " \
            f"{pairs} computed, {elsewhere} held elsewhere, " \
            f"{identity} identity"
        sp.set(moe_pairs=pairs, moe_pairs_dropped=dropped,
               moe_load_max_share=float(held.max()) / max(pairs, 1))
        if counts.elsewhere:
            sp.set(moe_pairs_elsewhere=elsewhere)
        if counts.identity:
            sp.set(moe_pairs_identity=identity,
                   moe_identity_pair_share=identity / max(expected, 1))

    def _account_sparse(self, sp) -> None:
        """What the window's queries scored and read, from the contexts the
        host already knows (no device read): step ``i``'s query of a row
        sees ``ctx_before + i + 1`` cached tokens, scores every one in each
        page layer, and reads ``min(ctx, topk)`` rows of them."""
        topk = self.engine.index_kv.topk
        ctx = np.asarray(self._ctx_before, np.int64)[:, None] \
            + np.arange(1, self.steps + 1)[None, :]
        layers = self.engine.family.page_layers
        scored = int(ctx.sum()) * layers
        selected = int(np.minimum(ctx, topk).sum()) * layers
        sp.set(sparse_tokens_scored=scored, sparse_tokens_selected=selected,
               sparse_select_share=selected / max(scored, 1),
               sparse_dense_queries=int((ctx <= topk).sum()),
               sparse_queries=int(ctx.size))

    def nonfinite_uids(self) -> List[int]:
        """uids whose logits went non-finite during this window (drains
        the window if needed)."""
        self.tokens()
        return [u for u, bad in zip(self.uids, self.nonfinite) if bad]


class ContinuousBatcher:
    """Stateful continuous-batching front end — admission, SplitFuse
    scheduling, KV backpressure, and eviction at O(batch) host cost per
    step, independent of the queued-request count.

    The one-shot :meth:`InferenceEngineV2.schedule` rebuilds its view of the
    world from a pending dict every step (O(pending)); at FastGen operating
    points (hundreds of queued requests, 64 live sequences) that rescan is
    pure scheduler overhead.  Here the state is incremental:

      * ``_decodes`` — uids with a next-token ready (each costs 1 budget
        token); rotated round-robin so no stream starves when
        len(decodes) > max_seqs.
      * ``_waiting`` / ``_prefilling`` — FIFO admission queue and the
        currently-chunking prompts; only the queue HEAD is examined when
        there is budget to admit (head-of-line, KV-backpressure aware).
      * finished sequences are flushed immediately (blocks return to the
        allocator) so long-running serving reaches a steady state instead
        of leaking cache.

    ``touched`` counts uids examined by the last ``next_batch`` — the
    sublinearity instrumentation the churn test pins (scheduling work is
    bounded by the batch budget, never by queue depth).

    Reference analogue: the MII scheduling layer over engine_v2.put
    (deepspeed/inference/v2/engine_v2.py:158-242 budget primitives).
    """

    def __init__(self, engine: InferenceEngineV2, max_new_tokens: int = 32,
                 temperature: float = 0.0,
                 eos_token_id: Optional[int] = None,
                 rng: Optional[jax.Array] = None):
        from collections import OrderedDict, deque

        self.eng = engine
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.eos_token_id = eos_token_id
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        self._waiting = deque()                    # uids not yet admitted
        self._prompts: Dict[int, List[int]] = {}   # uid -> full prompt
        self._prefill_pos: Dict[int, int] = {}     # uid -> tokens consumed
        self._prefilling: "OrderedDict[int, None]" = OrderedDict()
        self._decodes: "OrderedDict[int, int]" = OrderedDict()  # uid -> next tok
        self.produced: Dict[int, List[int]] = {}
        self.finished: Dict[int, List[int]] = {}
        self.rejected: List[int] = []          # impossible under any load
        self.touched = 0

    # -------------------------- admission ----------------------------- #
    def add_request(self, uid: int, tokens: List[int]) -> None:
        if uid in self._prompts or uid in self.finished:
            raise ValueError(f"uid {uid} already submitted")
        self.produced[uid] = []
        if not tokens:                 # nothing to condition on
            self.finished[uid] = []
            return
        self._prompts[uid] = list(tokens)
        self._prefill_pos[uid] = 0
        self._waiting.append(uid)

    @property
    def pending(self) -> int:
        return len(self._waiting) + len(self._prefilling) + len(self._decodes)

    # -------------------------- scheduling ---------------------------- #
    def next_batch(self) -> List[Tuple[int, List[int]]]:
        """Pick (uid, chunk) pairs for one forward.  Examines at most
        max_seqs decode uids + the prefilling set + the queue head —
        NEVER the whole waiting queue."""
        c = self.eng.config
        budget = c.max_tokens
        picked: List[Tuple[int, List[int]]] = []
        self.touched = 0

        # 1. ready decodes, round-robin (rotate so overflow isn't starved)
        n_dec = min(len(self._decodes), c.max_seqs, budget)
        for _ in range(n_dec):
            uid, tok = self._decodes.popitem(last=False)
            picked.append((uid, [tok]))
            budget -= 1
            self.touched += 1
        # 2. in-flight prefills continue (they hold KV blocks — finishing
        #    them frees capacity fastest)
        for uid in list(self._prefilling):
            if budget <= 0 or len(picked) >= c.max_seqs:
                break
            pos = self._prefill_pos[uid]
            chunk = self._prompts[uid][pos:pos + budget]
            picked.append((uid, chunk))
            budget -= len(chunk)
            self.touched += 1
        # 3. admit from the queue HEAD while budget and KV blocks allow.
        #    Admission RESERVES blocks for the request's whole lifetime
        #    (prompt + decode budget) so later chunks/decodes can never hit
        #    an out-of-blocks mid-flight; flush returns them at retirement.
        while (self._waiting and budget > 0 and len(picked) < c.max_seqs):
            uid = self._waiting[0]
            self.touched += 1
            # whole-lifetime reservation (engine.lifetime_reservation);
            # a capless eos-less overrun still raises at the put/decode
            # boundary, matching put()'s own contract
            need, need_blocks = self.eng.lifetime_reservation(
                len(self._prompts[uid]), self.max_new_tokens)
            if (len(self._prompts[uid]) > c.max_ctx
                    or need_blocks > self.eng.kv.config.num_blocks):
                # impossible under any load: reject, don't stall the queue
                logger.warning(
                    f"rejecting uid {uid}: prompt+decode needs {need} tokens "
                    f"({need_blocks} blocks) — exceeds max_ctx {c.max_ctx} / "
                    f"pool {self.eng.kv.config.num_blocks} blocks")
                self._waiting.popleft()
                self.rejected.append(uid)
                self.finished[uid] = []
                self._prompts.pop(uid, None)
                self._prefill_pos.pop(uid, None)
                continue
            seq = self.eng.state_manager.get_or_create_sequence(uid)
            if not self.eng.state_manager.maybe_allocate_kv(seq, need):
                break          # KV backpressure: head waits, queue intact
            self._waiting.popleft()
            self._prefilling[uid] = None
            picked.append((uid, self._prompts[uid][:budget]))
            budget -= len(picked[-1][1])
        return picked

    # ------------------------------ step ------------------------------ #
    def step(self) -> List[int]:
        """Run one engine forward (or a fused decode window when every live
        sequence is decoding); returns uids finished this step."""
        just_finished: List[int] = []
        pure_decode = (not self._prefilling and not self._waiting
                       and self._decodes and self.eos_token_id is None
                       and len(self._decodes) <= min(
                           self.eng.config.max_seqs,
                           self.eng.config.max_tokens))
        if pure_decode:
            uids = list(self._decodes)
            steps = min(self.max_new_tokens - len(self.produced[u])
                        for u in uids)
            if steps > 2:      # quantize: one compiled loop per pow2 window
                steps = 1 << (steps.bit_length() - 1)
            if steps > 1:
                if self.temperature > 0:
                    self._rng, sub = jax.random.split(self._rng)
                else:
                    sub = None
                toks = self.eng.decode_batch(
                    uids, [self._decodes[u] for u in uids], steps,
                    self.temperature, sub)
                for col, uid in enumerate(uids):
                    self.produced[uid].extend(int(t) for t in toks[:, col])
                    del self._decodes[uid]
                    if len(self.produced[uid]) >= self.max_new_tokens:
                        self._retire(uid, just_finished)
                    else:
                        self._decodes[uid] = self.produced[uid][-1]
                return just_finished

        batch = self.next_batch()
        if not batch:
            return just_finished
        logits = self.eng.put([u for u, _ in batch], [t for _, t in batch])
        if self.temperature > 0:
            self._rng, sub = jax.random.split(self._rng)
            toks = np.asarray(jax.random.categorical(
                sub, logits[:len(batch)] / self.temperature, axis=-1))
        else:
            toks = np.asarray(jnp.argmax(logits[:len(batch)], axis=-1))
        for row, (uid, chunk) in enumerate(batch):
            if uid in self._prefilling:
                self._prefill_pos[uid] += len(chunk)
                if self._prefill_pos[uid] < len(self._prompts[uid]):
                    continue                       # mid-prompt; logits unused
                del self._prefilling[uid]
            tok = int(toks[row])
            self.produced[uid].append(tok)
            if ((self.eos_token_id is not None and tok == self.eos_token_id)
                    or len(self.produced[uid]) >= self.max_new_tokens):
                self._retire(uid, just_finished)
            else:
                self._decodes[uid] = tok
        return just_finished

    def _retire(self, uid: int, finished_acc: List[int]) -> None:
        self.eng.flush([uid])                      # blocks back to the pool
        self.finished[uid] = self.produced[uid]
        self._prompts.pop(uid, None)
        self._prefill_pos.pop(uid, None)
        finished_acc.append(uid)

    def run(self) -> Dict[int, List[int]]:
        """Drive until every submitted request completes."""
        guard = 0

        def total_tokens():
            return sum(len(v) for v in self.produced.values()) + \
                sum(self._prefill_pos.get(u, 0) for u in self._prefilling)

        while self.pending:
            before = total_tokens()
            self.step()
            # progress = tokens moved (prefill consumed or decode produced);
            # pending COUNT is the wrong signal — long generations keep the
            # same live set for thousands of legitimate steps
            guard = guard + 1 if total_tokens() == before else 0
            if guard > 3:
                raise RuntimeError("scheduler made no progress "
                                   f"({self.pending} pending)")
        return self.finished
