"""T3-style fused compute+collective matmul kernels
(``kernels/fused_collective_matmul.py`` + ``runtime/comm/fused_gemm.py``):

  * fp edges BITWISE-equal to the unfused matmul→collective composition
    on the 8-device CPU sim, under BOTH the interpret-mode Pallas and the
    XLA dense seams, on the pure-DP (ZeRO-2-shaped) and dp4×tp2 meshes;
  * int8 edges bitwise-equal to unfused-matmul→PR-9-fused-wire and inside
    the PR-9 half-step error bound vs the fp oracle;
  * fused RMSNorm+matmul bitwise vs the ``models/transformer.py rms_norm``
    composition under jit, and the model-level knob (CPU default
    unchanged);
  * ``CollectiveAlgoSelector`` fused_gemm determinism + admission rules,
    the ``exchange_leaves`` leaf seam, engine-level ``overlap:"auto"``
    resolution, and a no-retrace probe mirroring PR-6's ``trace_counts``
    pattern.

Heavy parametrizations (the dp×tp mesh duplicates and the ZeRO-3 engine
build) are marked ``slow``; each (edge × wire) cell keeps an in-budget
dp8 representative — the tier-1 budget note in ISSUE/ROADMAP.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.kernels.fused_collective_matmul import (
    all_gather_matmul,
    matmul_reduce_scatter,
    matmul_reference,
    rmsnorm_matmul,
    rmsnorm_matmul_reference,
    shard_major_matmul,
)
from deepspeed_tpu.ops.quantizer.quantizer import quant_pack_wire
from deepspeed_tpu.runtime.comm import fused_gemm as fg
from deepspeed_tpu.runtime.comm import hierarchical as h
from deepspeed_tpu.runtime.comm.fused_wire import (
    fused_quantized_reduce_scatter,
)
from deepspeed_tpu.runtime.topology import (DATA, TopologyConfig,
                                            compat_shard_map,
                                            initialize_mesh)

pytestmark = pytest.mark.kernels

N_DEV = 8
M, K, N = 64, 32, 64          # M % n == 0 on both meshes; (M/n)·N % 256 == 0


@pytest.fixture
def mesh8():
    """Pure-DP 8-device mesh — the ZeRO-2-shaped exchange group."""
    return initialize_mesh(TopologyConfig(), force=True)


@pytest.fixture
def mesh_dp_tp():
    """dp4×tp2 — manual data axes with tensor staying Auto (the partial-
    manual composition the explicit wire runs under)."""
    return initialize_mesh(TopologyConfig(tensor=2), force=True)


def _data_axes(topo):
    from deepspeed_tpu.runtime.comm_path import dp_axes_info

    return dp_axes_info(topo)[0]


def _inputs(n, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    return x, w


def _run_epilogue(topo, impl, wire_bits):
    axes = _data_axes(topo)
    n = 1
    for a in axes:
        n *= topo.dims[a]
    x, w = _inputs(n)

    def fused(xl, wl):
        return matmul_reduce_scatter(xl[0], wl, axes, wire_bits=wire_bits,
                                     impl=impl)[None]

    def unfused(xl, wl):
        y = matmul_reference(xl[0], wl)
        if wire_bits:
            return fused_quantized_reduce_scatter(
                y, axes, bits=wire_bits)[None].reshape(1, M // n, N)
        part = jax.lax.psum_scatter(y, axes, scatter_dimension=0,
                                    tiled=True)
        return (part / n)[None]

    sm = lambda f: jax.jit(compat_shard_map(
        f, topo.mesh, (P(axes[0]), P()), P(axes[0]), manual_axes=set(axes)))
    return sm(fused)(x, w), sm(unfused)(x, w), x, w, n, axes


class TestEpilogue:
    """Reduce-scatter epilogue matmul: the trailing collective on ZeRO
    grad buckets / TP row-parallel projections, fused into the kernel."""

    @pytest.mark.parametrize("impl", ["pallas", "dense"])
    def test_fp_bitwise_dp8(self, mesh8, impl):
        out, base, *_ = _run_epilogue(mesh8, impl, 0)
        assert out.shape == base.shape
        assert jnp.all(out == base), "fp epilogue must be BITWISE"

    @pytest.mark.slow
    @pytest.mark.parametrize("impl", ["pallas", "dense"])
    def test_fp_bitwise_dp_tp(self, mesh_dp_tp, impl):
        out, base, *_ = _run_epilogue(mesh_dp_tp, impl, 0)
        assert jnp.all(out == base)

    def test_int8_bitwise_vs_unfused_matmul_then_wire_dp8(self, mesh8):
        out, base, *_ = _run_epilogue(mesh8, "pallas", 8)
        assert jnp.all(out == base), \
            "int8 epilogue must be bitwise vs unfused-matmul→fused-wire"

    @pytest.mark.slow
    def test_int8_bitwise_dp_tp(self, mesh_dp_tp):
        out, base, *_ = _run_epilogue(mesh_dp_tp, "pallas", 8)
        assert jnp.all(out == base)

    def test_int8_half_step_bound_vs_fp_oracle(self, mesh8):
        outq, _, x, w, n, axes = _run_epilogue(mesh8, "pallas", 8)
        outf, _, *_ = _run_epilogue(mesh8, "pallas", 0)
        # per-element quantization error ≤ half a quantization step of
        # its group (scale = max|y_group|/127) on every rank's
        # contribution; the mean over n contributions keeps the bound
        ys = [matmul_reference(x[i], w) for i in range(n)]
        max_scale = 0.0
        for y in ys:
            _, s = quant_pack_wire(y.reshape(-1), 8, 256)
            max_scale = max(max_scale, float(jnp.max(s)))
        err = float(jnp.abs(outq - outf).max())
        assert err <= 0.5 * max_scale * 1.001 + 1e-6, \
            f"err {err} exceeds half-step {0.5 * max_scale}"

    def test_rejects_misaligned_rows(self, mesh8):
        axes = _data_axes(mesh8)
        x = jnp.zeros((N_DEV, 12, K), jnp.float32)   # 12 % 8 != 0
        w = jnp.zeros((K, N), jnp.float32)

        def bad(xl, wl):
            return matmul_reduce_scatter(xl[0], wl, axes)[None]

        with pytest.raises(ValueError, match="not divisible"):
            jax.jit(compat_shard_map(
                bad, mesh8.mesh, (P(DATA), P()), P(DATA),
                manual_axes=set(axes)))(x, w)


def _run_prologue(topo, impl, wire_bits, Kp=64):
    axes = _data_axes(topo)
    n = 1
    for a in axes:
        n *= topo.dims[a]
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(M, Kp)), jnp.float32)
    ws = jnp.asarray(rng.normal(size=(n, Kp // n, N)), jnp.float32)

    def fused(wl):
        return all_gather_matmul(x, wl[0], axes, wire_bits=wire_bits,
                                 impl=impl)[None]

    def unfused(wl):
        wf = jax.lax.all_gather(wl[0], axes, axis=0, tiled=True)
        return matmul_reference(x, wf)[None]

    sm = lambda f: jax.jit(compat_shard_map(
        f, topo.mesh, (P(axes[0]),), P(axes[0]), manual_axes=set(axes)))
    return sm(fused)(ws), sm(unfused)(ws), x, ws, n


class TestPrologue:
    """All-gather prologue matmul: the ZeRO-3 / column-parallel weight
    gather fused in front of the consuming kernel's k-loop."""

    @pytest.mark.parametrize("impl", ["pallas", "dense"])
    def test_fp_bitwise_dp8(self, mesh8, impl):
        out, base, *_ = _run_prologue(mesh8, impl, 0)
        assert jnp.all(out == base), "fp prologue must be BITWISE"

    @pytest.mark.slow
    @pytest.mark.parametrize("impl", ["pallas", "dense"])
    def test_fp_bitwise_dp_tp(self, mesh_dp_tp, impl):
        out, base, *_ = _run_prologue(mesh_dp_tp, impl, 0)
        assert jnp.all(out == base)

    @pytest.mark.parametrize("impl", ["pallas", "dense"])
    def test_int8_half_step_bound_dp8(self, mesh8, impl):
        outq, base, x, ws, n = _run_prologue(mesh8, impl, 8)
        # |Δy| ≤ |x| @ (0.5·per-element scale): each gathered weight
        # element's dequant error is half its group's quantization step
        half = []
        for i in range(n):
            flat = ws[i].reshape(-1)
            _, s = quant_pack_wire(flat, 8, 256)
            per = jnp.repeat(s.reshape(-1), 256)[:flat.shape[0]]
            half.append(0.5 * per.reshape(ws[i].shape[0], N))
        bound = jnp.abs(x) @ jnp.concatenate(half, axis=0)
        err = jnp.abs(outq[0] - base[0])
        assert bool(jnp.all(err <= bound * 1.001 + 1e-5)), \
            f"max overshoot {float((err - bound).max())}"

    def test_pallas_and_dense_int8_agree(self, mesh8):
        """The two seams dequantize the same wire — results must be close
        (accumulation order differs per shard k-block by design)."""
        outp, *_ = _run_prologue(mesh8, "pallas", 8)
        outd, *_ = _run_prologue(mesh8, "dense", 8)
        assert jnp.allclose(outp, outd, atol=1e-4, rtol=1e-5)


class TestGatherWindowCacheRide:
    def test_prologue_rides_window_cache(self, mesh8):
        """Warm window: the cached full weight is consumed with NO gather
        in the program; cold after invalidate() — the PR-4 invariant."""
        from deepspeed_tpu.runtime.overlap.prefetch import GatherWindowCache

        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
        cache = GatherWindowCache()
        calls = {"n": 0}

        def gather_fn(_shard):
            calls["n"] += 1
            return w

        # GatherWindowCache.get(params, gather) calls gather(params)
        out1 = fg.gemm_all_gather_matmul(x, w, (), window_cache=cache,
                                         gather_fn=gather_fn, impl="dense")
        out2 = fg.gemm_all_gather_matmul(x, w, (), window_cache=cache,
                                         gather_fn=gather_fn, impl="dense")
        assert calls["n"] == 1 and cache.hits == 1
        assert jnp.all(out1 == out2)
        cache.invalidate()
        fg.gemm_all_gather_matmul(x, w, (), window_cache=cache,
                                  gather_fn=gather_fn, impl="dense")
        assert calls["n"] == 2
        with pytest.raises(ValueError, match="gather_fn"):
            fg.gemm_all_gather_matmul(x, w, (), window_cache=cache)


class TestRmsnormMatmul:
    def test_bitwise_vs_unfused_composition(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(2, 16, 32)), jnp.float32)
        sc = jnp.asarray(rng.normal(size=(32,)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
        fused = jax.jit(lambda x, s, w: rmsnorm_matmul(x, s, w, 1e-5,
                                                       impl="pallas"))
        ref = jax.jit(lambda x, s, w: rmsnorm_matmul_reference(x, s, w,
                                                               1e-5))
        assert jnp.all(fused(x, sc, w) == ref(x, sc, w)), \
            "fused RMSNorm+matmul must be bitwise under jit"

    def test_differentiable_through_pallas(self):
        """jax.grad must flow through the fused kernel (custom VJP whose
        backward is the reference composition's) — without it the
        fused_rmsnorm="auto" default would break TPU TRAINING at the
        first step."""
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(8, 32)), jnp.float32)
        sc = jnp.asarray(rng.normal(size=(32,)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)

        def loss_fused(x, s, w):
            return jnp.sum(rmsnorm_matmul(x, s, w, 1e-5, impl="pallas")**2)

        def loss_ref(x, s, w):
            return jnp.sum(rmsnorm_matmul_reference(x, s, w, 1e-5)**2)

        gf = jax.jit(jax.grad(loss_fused, argnums=(0, 1, 2)))(x, sc, w)
        gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(x, sc, w)
        for a, b in zip(gf, gr):
            assert a.shape == b.shape
            assert jnp.allclose(a, b, atol=1e-4, rtol=1e-5)

    def test_model_trains_with_fused_on(self):
        """End to end: jax.grad of the LM loss through a fused_rmsnorm=on
        model runs and matches the unfused model's grads."""
        from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                      init_params, lm_loss)

        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, 256, size=(2, 16)), jnp.int32)
        on = TransformerConfig.tiny(use_flash=False, fused_rmsnorm="on")
        off = TransformerConfig.tiny(use_flash=False, fused_rmsnorm="off")
        p = init_params(off, jax.random.PRNGKey(0))
        g_on = jax.jit(jax.grad(lambda p: lm_loss(p, toks, on)))(p)
        g_off = jax.jit(jax.grad(lambda p: lm_loss(p, toks, off)))(p)
        flat_on = jax.tree.leaves(g_on)
        flat_off = jax.tree.leaves(g_off)
        assert all(jnp.allclose(a, b, atol=2e-4, rtol=1e-4)
                   for a, b in zip(flat_on, flat_off))

    def test_model_knob_cpu_default_unchanged(self):
        """fused_rmsnorm="auto" stays OFF on the CPU sim — the default
        jaxpr (and every tier-1 numeric) is untouched."""
        from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                      forward, init_params)

        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, 256, size=(2, 32)), jnp.int32)
        off = TransformerConfig.tiny(use_flash=False, fused_rmsnorm="off")
        auto = TransformerConfig.tiny(use_flash=False)
        on = TransformerConfig.tiny(use_flash=False, fused_rmsnorm="on")
        p = init_params(off, jax.random.PRNGKey(0))
        lo = jax.jit(lambda p, t: forward(p, t, off))(p, toks)
        la = jax.jit(lambda p, t: forward(p, t, auto))(p, toks)
        lon = jax.jit(lambda p, t: forward(p, t, on))(p, toks)
        assert jnp.all(lo == la), "auto must equal off on CPU"
        assert jnp.allclose(lo, lon, atol=2e-5), \
            "fused-on forward must match the unfused model"


FIXED = dict(n_intra=4, n_inter=2, ici_bw=400e9, dcn_bw=25e9,
             hbm_bw=1600e9)


class TestSelectorFusedGemm:
    def test_not_offered_by_default(self):
        sel = h.CollectiveAlgoSelector(**FIXED)
        assert all(a != "fused_gemm" for a, _ in sel.candidates())

    def test_offered_when_allowed_and_deterministic(self):
        sel = h.CollectiveAlgoSelector(**FIXED, allow_fused_gemm=True,
                                       fused_compute_ms=50.0)
        assert ("fused_gemm", "fp") in sel.candidates()
        picks = {(c.algo, c.wire) for c in
                 (sel.select(64 << 20) for _ in range(8))}
        assert len(picks) == 1, f"nondeterministic: {picks}"

    def test_picked_with_compute_budget_not_without(self):
        """fused_gemm wins exactly when there is producing-GEMM compute to
        hide the exchange behind; with no evidence (0 ms) it ties flat
        and loses the stable-order tie-break."""
        with_budget = h.CollectiveAlgoSelector(
            **FIXED, allow_fused_gemm=True, fused_compute_ms=50.0
            ).select(64 << 20)
        assert with_budget.algo == "fused_gemm"
        without = h.CollectiveAlgoSelector(
            n_intra=8, n_inter=1, ici_bw=400e9, dcn_bw=25e9,
            hbm_bw=1600e9, allow_fused_gemm=True, fused_compute_ms=0.0
            ).select(64 << 20)
        assert without.algo == "flat"

    def test_exposed_floor_last_shard_stays_exposed(self):
        """An infinite compute budget cannot hide more than (n-1)/n of
        the wire: the last shard's block has nothing left to overlap."""
        sel = h.CollectiveAlgoSelector(**FIXED, allow_fused_gemm=True,
                                       fused_compute_ms=1e9)
        flat_ms = sel.predict_ms(64 << 20, "flat", "fp")
        fused_ms = sel.predict_ms(64 << 20, "fused_gemm", "fp")
        _ici, dcn, hbm = sel._domain_bytes(64 << 20, "flat", "fp")
        floor = 1e3 * (dcn / sel.dcn_bw) / 8 + 1e3 * hbm / sel.hbm_bw
        assert fused_ms == pytest.approx(floor)
        assert fused_ms < flat_ms

    def test_measured_retune_can_pick_fused_gemm(self):
        sel = h.CollectiveAlgoSelector(**FIXED, allow_fused_gemm=True)
        c = sel.select(8 << 20, measured_ms={"flat/fp": 5.0,
                                             "2hop/fp": 4.0,
                                             "fused_gemm/fp": 2.0})
        assert c.algo == "fused_gemm" and c.measured

    def test_predict_operand_bytes_fused_gemm(self):
        fp = h.predict_operand_bytes(1 << 20, "fused_gemm", "fp", 8, 1)
        assert fp["psum_scatter"] == float(1 << 20)
        assert fp["all_gather"] == float(1 << 20) / 8
        q = h.predict_operand_bytes(1 << 20, "fused_gemm", "int8", 8, 1)
        assert 0 < q["total"] < fp["total"], "int8 wire must shrink bytes"


class TestLeafSeam:
    """exchange_leaves with algo="fused_gemm" — the degenerate
    (no-producer) edge comm_path routes the plain-grad buckets through
    when the selector picks fused_gemm."""

    def _exchange(self, topo, algo, bits):
        axes = _data_axes(topo)
        n = 1
        for a in axes:
            n *= topo.dims[a]
        rng = np.random.default_rng(3)
        leaves = [jnp.asarray(rng.normal(size=(s,)), jnp.float32)
                  for s in (1000, 300, 17)]

        def body(ls):
            outs, stats = h.exchange_leaves(ls, axes, axes, (), algo, bits,
                                            n=n)
            return outs

        return jax.jit(compat_shard_map(
            body, topo.mesh, (P(),), P(), manual_axes=set(axes)))(leaves)

    def test_fp_matches_flat_mean(self, mesh8):
        flat = self._exchange(mesh8, "flat", 0)
        fused = self._exchange(mesh8, "fused_gemm", 0)
        for a, b in zip(flat, fused):
            assert jnp.allclose(a, b, atol=1e-5), \
                "fused_gemm leaf exchange is the exact mean (reordered)"

    def test_int8_is_the_fused_wire(self, mesh8):
        flat_q = self._exchange(mesh8, "flat", 8)
        fused_q = self._exchange(mesh8, "fused_gemm", 8)
        for a, b in zip(flat_q, fused_q):
            assert jnp.all(a == b), \
                "quantized fused_gemm leaf wire IS the PR-9 fused wire"


class TestEngineResolution:
    """overlap:"auto" end to end: the manager's selector resolves
    fused_gemm on the explicit wire and training stays correct."""

    def _build(self, zero_stage, hint_ms, seed=0):
        import deepspeed_tpu
        from deepspeed_tpu.models.transformer import (CausalLM,
                                                      TransformerConfig)

        topo = initialize_mesh(TopologyConfig(), force=True)
        model = CausalLM(TransformerConfig.tiny(use_flash=False))
        params = model.init_params(jax.random.PRNGKey(seed))
        conf = {"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": zero_stage},
                "overlap": {"enabled": True, "mode": "auto",
                            "explicit_wire": True, "bucket_bytes": 0,
                            "fused_gemm_compute_ms": hint_ms}}
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config=conf,
            topology=topo)
        return eng

    def _batch(self, model_vocab=256):
        rng = np.random.default_rng(0)
        return {"input_ids": jnp.asarray(
            rng.integers(0, model_vocab, size=(N_DEV, 32)), jnp.int32)}

    def test_auto_resolves_fused_gemm_and_trains(self):
        eng = self._build(zero_stage=2, hint_ms=1e3)
        eng.overlap.resolve_comm(eng)
        assert eng.overlap.comm_algo == "fused_gemm", \
            eng.overlap.comm_choice
        loss = eng.train_batch(self._batch())
        assert bool(jnp.isfinite(loss))

    def test_fused_gemm_update_matches_flat(self):
        """Same seed, fused_gemm vs flat wire: the exchange is the exact
        mean (fp-reordered), so the SECOND step's loss — which sees the
        first step's exchanged-gradient update — must agree to fp
        tolerance.  (The first step's loss predates any exchange and
        would compare trivially.)"""
        batch = self._batch()
        e1 = self._build(zero_stage=2, hint_ms=1e3)
        e1.train_batch(batch)
        l1 = e1.train_batch(batch)
        e2 = self._build(zero_stage=2, hint_ms=0.0)
        e2.overlap.hierarchical = "off"      # force flat
        e2.train_batch(batch)
        l2 = e2.train_batch(batch)
        assert jnp.allclose(l1, l2, rtol=1e-4, atol=1e-5), (l1, l2)

    @pytest.mark.slow
    def test_zero3_trains_under_fused_gemm(self):
        eng = self._build(zero_stage=3, hint_ms=1e3)
        eng.overlap.resolve_comm(eng)
        assert eng.overlap.comm_algo == "fused_gemm"
        loss = eng.train_batch(self._batch())
        assert bool(jnp.isfinite(loss))

    def test_manager_publishes_fused_gemm_gauge(self):
        from deepspeed_tpu.runtime.overlap.manager import OverlapManager
        from deepspeed_tpu.telemetry.metrics import MetricsRegistry

        class _Tel:
            def __init__(self):
                self.metrics = MetricsRegistry()

            def event(self, *a, **k):
                pass

        class _Cfg:
            enabled = True
            mode = "manual"
            deferred_grad_reduce = True
            bucket_bytes = 1 << 20
            prefetch_params = False
            explicit_wire = True
            wire_bits = 0
            hierarchical = "auto"

        tel = _Tel()
        mgr = OverlapManager(_Cfg(), telemetry=tel)
        mgr.comm_algo = "fused_gemm"
        mgr.publish()
        assert tel.metrics.gauge("comm/algo_fused_gemm").value() == 1.0
        assert tel.metrics.gauge("comm/algo_2hop").value() == 0.0


class TestNoRetrace:
    def test_one_trace_per_shape(self, mesh8):
        """PR-6 trace_counts pattern: the jitted fused epilogue traces
        once per shape — repeated steps hit the compile cache."""
        axes = _data_axes(mesh8)
        counts = {"n": 0}

        def body(xl, wl):
            counts["n"] += 1
            return matmul_reduce_scatter(xl[0], wl, axes,
                                         impl="pallas")[None]

        fn = jax.jit(compat_shard_map(body, mesh8.mesh, (P(DATA), P()),
                                      P(DATA), manual_axes=set(axes)))
        x, w = _inputs(N_DEV)
        jax.block_until_ready(fn(x, w))
        jax.block_until_ready(fn(x, w))
        assert counts["n"] == 1, "same shape must not retrace"
        x2 = jnp.concatenate([x, x], axis=1)         # new M
        jax.block_until_ready(fn(x2, w))
        assert counts["n"] == 2, "a new shape traces exactly once more"


class TestKernelRooflineTelemetry:
    """Satellite: per-kernel %-of-peak rooflines surfaced in
    dstpu-telemetry — publish_kernel_gauges → kernels/* series →
    kernels_summary → rendered section."""

    def test_gauges_roundtrip_into_summary_section(self):
        from deepspeed_tpu.profiling.roofline import (
            CPU_FALLBACK, kernel_roofline_report, publish_kernel_gauges)
        from deepspeed_tpu.telemetry.metrics import MetricsRegistry
        from deepspeed_tpu.telemetry.summary import kernels_summary

        reg = MetricsRegistry()
        rep = kernel_roofline_report("fused_gemm", flops=2e9, bytes_accessed=1e8,
                                     seconds=1e-2, spec=CPU_FALLBACK)
        publish_kernel_gauges(reg, rep)
        rows = kernels_summary(reg.snapshot())
        assert "fused_gemm" in rows
        row = rows["fused_gemm"]
        assert row["pct_peak_flops"] == pytest.approx(
            100.0 * (2e9 / 1e-2) / CPU_FALLBACK.peak_flops)
        # the stand-in peaks are labelled as such wherever they surface
        assert row["device_kind"] == CPU_FALLBACK.kind
        assert "cpu fallback peaks" in row["device_kind"]

    def test_summary_renders_kernels_section(self):
        from deepspeed_tpu.telemetry.summary import (format_summary,
                                                     summarize_run)

        s = summarize_run(None)
        assert "kernels (%-of-peak rooflines)" not in format_summary(s), \
            "no kernels gauges → no section"
        s["kernels"] = {"flash": {"tflops": 0.5, "pct_peak_flops": 25.0,
                                  "hbm_gbps": 10.0, "pct_peak_hbm": 1.0,
                                  "device_kind": "cpu"}}
        text = format_summary(s)
        assert "kernels (%-of-peak rooflines)" in text
        assert "flash" in text and "25.00%" in text

    def test_decode_roofline_publishes_kernels_gauge(self):
        """The engine path: a drained decode window lands a kernels/*
        row (the 'published from the engine like serving/*' contract) —
        exercised via the report+publish helpers the engine calls with
        its analytic page-walk bytes."""
        from deepspeed_tpu.profiling.roofline import (
            kernel_roofline_report, publish_kernel_gauges)
        from deepspeed_tpu.telemetry.metrics import MetricsRegistry

        reg = MetricsRegistry()
        rep = kernel_roofline_report("decode_paged", 1e6, 1e8, 1e-3)
        publish_kernel_gauges(reg, rep)
        v = reg.gauge("kernels/pct_peak_hbm").value(
            kernel="decode_paged", device=rep["device_kind"])
        assert v is not None and v > 0


class TestKernelOnly:
    def test_shard_major_matmul_bitwise(self):
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
        for n_shards in (1, 4, 8):
            out = shard_major_matmul(x, w, n_shards)
            assert jnp.all(out == matmul_reference(x, w)), n_shards
