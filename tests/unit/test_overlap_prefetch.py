"""ZeRO-3 weight all-gather prefetch: the scanned-layer double-buffered
gather combinator (numerics must match the plain scan exactly) and the
per-accumulation-window gathered-param cache on the imperative
explicit-comm path (no all-gather in the per-micro-step program; grads
bit-exact vs the uncached path).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.runtime.overlap.prefetch import (GatherWindowCache,
                                                    prefetched_layer_scan)
from deepspeed_tpu.runtime.topology import (DATA, TopologyConfig,
                                            compat_shard_map,
                                            initialize_mesh)

pytestmark = pytest.mark.overlap


class TestPrefetchedLayerScan:
    def test_matches_plain_scan(self, mesh8):
        """Double-buffered weights carry: every layer computes with the
        same gathered weights as the eager gather-in-body scan (fp
        tolerance — the restructured program may fuse differently)."""
        L, D = 4, 16
        rng = np.random.default_rng(0)
        stacked = {"w": jnp.asarray(rng.normal(size=(L, 8, D // 8, D)),
                                    jnp.float32)}
        x0 = jnp.asarray(rng.normal(size=(D,)), jnp.float32)

        def gather_layer(shard_tree):
            # [8, D/8, D] shards → full [D, D] weight
            return {"w": jax.lax.all_gather(
                shard_tree["w"], DATA, axis=0,
                tiled=True).reshape(D, D)}

        def body(x, w):
            y = jnp.tanh(w["w"] @ x)
            return y, jnp.sum(y)

        def prefetched(stacked, x0):
            return prefetched_layer_scan(body, gather_layer, stacked, x0, L)

        def plain(stacked, x0):
            def step(x, i):
                w = gather_layer(jax.tree.map(
                    lambda s: jax.lax.dynamic_index_in_dim(
                        s, i, 0, keepdims=False), stacked))
                return body(x, w)

            return jax.lax.scan(step, x0, jnp.arange(L))

        specs = ({"w": P(None, DATA)}, P())
        out_specs = (P(), P())
        got = jax.jit(compat_shard_map(prefetched, mesh8.mesh, specs,
                                       out_specs, manual_axes={DATA})
                      )(stacked, x0)
        want = jax.jit(compat_shard_map(plain, mesh8.mesh, specs,
                                        out_specs, manual_axes={DATA})
                       )(stacked, x0)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                                   rtol=1e-5, atol=1e-6)


class TestGatherWindowCache:
    def test_hit_and_invalidate(self):
        cache = GatherWindowCache()
        params = {"w": jnp.ones(4)}
        calls = []

        def gather(p):
            calls.append(1)
            return jax.tree.map(lambda x: x * 2, p)

        a = cache.get(params, gather)
        b = cache.get(params, gather)
        assert a is b and len(calls) == 1
        assert cache.hits == 1 and cache.misses == 1
        cache.invalidate()
        cache.get(params, gather)
        assert len(calls) == 2

    def test_donated_params_still_hit(self):
        """Donation hands the unchanged params new array objects every
        micro-step — the cache must not identity-key them (freshness is
        the engine's invalidate() discipline instead)."""
        cache = GatherWindowCache()
        gather = lambda p: p
        cache.get({"w": jnp.ones(4)}, gather)
        cache.get({"w": jnp.ones(4) * 1}, gather)   # new object, warm cache
        assert cache.misses == 1 and cache.hits == 1


class TestImperativeWindowPrefetch:
    def _engine(self, prefetch, gas=2):
        topo = initialize_mesh(TopologyConfig(), force=True)
        cfg = TransformerConfig.tiny(use_flash=False)
        model = CausalLM(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=params,
            config={"train_micro_batch_size_per_gpu": 2,
                    "gradient_accumulation_steps": gas,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "zero_optimization": {
                        "stage": 3, "zero_quantized_weights": True,
                        "stage3_param_persistence_threshold": 0},
                    "bf16": {"enabled": True},
                    "overlap": {"enabled": True,
                                "prefetch_params": prefetch}},
            topology=topo)
        return eng

    def _micro_batches(self, gas=2):
        rng = np.random.default_rng(3)
        return [{"input_ids": jnp.asarray(
            rng.integers(0, 64, size=(16, 32)), jnp.int32)}
            for _ in range(gas)]

    @pytest.mark.slow  # 12s: HLO text inspection; test_grads_bit_exact_vs_uncached remains in tier-1
    def test_window_cache_mechanics_and_hlo(self):
        """One stage-3 qwZ engine covers the whole mechanism: (1) the
        pregathered micro-step program carries NO all-gather (the qwZ int8
        wire moved to the once-per-window gather fn); (2) the cache serves
        every later micro-step of the window and re-gathers after the
        optimizer step invalidates it."""
        from deepspeed_tpu.runtime.comm_path import (build_explicit_micro_fn,
                                                     build_param_gather_fn)

        eng = self._engine(prefetch=True)
        mbs = self._micro_batches()
        for mb in mbs:
            eng.backward(mb)
        assert eng._gather_cache.misses == 1
        assert eng._gather_cache.hits == len(mbs) - 1
        # HLO: pregathered micro fn vs the standard gather-in-body one
        batch = mbs[0]
        gathered = build_param_gather_fn(eng)(eng.state.params)
        pre_txt = build_explicit_micro_fn(eng, pregathered=True).lower(
            eng.state, batch, gathered).as_text()
        std_txt = build_explicit_micro_fn(eng).lower(
            eng.state, batch).as_text()
        assert "all_gather" in std_txt     # the qwZ wire, per micro-step
        assert "all_gather" not in pre_txt  # prefetched once per window
        eng.step()
        for mb in mbs:
            eng.backward(mb)
        assert eng._gather_cache.misses == 2   # re-gathered post-update

    @pytest.mark.slow
    def test_grads_bit_exact_vs_uncached(self):
        """Gather is a pure function of unchanged params: caching must not
        move a single bit of the update.  (slow: two stage-3 qwZ engines;
        the fast tests above pin the mechanism — no all-gather in the
        pregathered HLO, cache reuse/invalidations.)"""
        mbs = self._micro_batches()
        e_pre = self._engine(prefetch=True)
        e_std = self._engine(prefetch=False)
        for eng in (e_pre, e_std):
            for mb in mbs:
                eng.backward(mb)
            eng.step()
        for a, b in zip(jax.tree.leaves(e_pre.state.params),
                        jax.tree.leaves(e_std.state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
