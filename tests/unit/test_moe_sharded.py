"""MoE routing/dispatch invariants + expert resharding (moe/sharded_moe.py).

The ROADMAP flags the MoE layer as needing hardening; these tests pin the
gating contracts the elastic-resharding work relies on: capacity-factor
edge cases, zero-token experts, deterministic tie-breaks, and the uneven
expert÷ep padding path (bit-identical routing through a padded stack)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.moe.sharded_moe import (
    _capacity, combine_sparse, dispatch_sparse, expert_shard_ranges,
    init_moe_params, moe_layer, moe_mlp_block, pad_experts_for_ep,
    padded_expert_count, placed_expert_ranges, reshard_expert_params,
    top1gating, top1gating_sparse, topkgating, topkgating_sparse)
from deepspeed_tpu.runtime import topology as topo_mod
from deepspeed_tpu.runtime.topology import (DATA, DATA_OUTER, EXPERT,
                                            TopologyConfig, initialize_mesh)
from deepspeed_tpu.telemetry import get_tracer

pytestmark = pytest.mark.moe

HID = 8


def skewed_logits(S=16, E=4, to_expert=0, seed=0):
    """Logits that route every token to one expert (zero-token experts
    everywhere else)."""
    rng = np.random.default_rng(seed)
    logits = rng.normal(size=(S, E)).astype(np.float32) * 0.01
    logits[:, to_expert] += 10.0
    return jnp.asarray(logits)


class TestCapacityEdgeCases:
    def test_min_capacity_clamps_tiny_factors(self):
        # ceil(16/4 * 0.01) = 1, clamped up to min_capacity
        assert _capacity(16, 4, 0.01, 4) == 4
        assert _capacity(16, 4, 0.01, 1) == 1

    def test_capacity_rounds_up(self):
        assert _capacity(10, 4, 1.0, 1) == 3      # ceil(2.5)

    @pytest.mark.parametrize("gating,kw", [
        (top1gating, {}), (topkgating, {"k": 2})])
    def test_overflow_tokens_are_dropped_not_misrouted(self, gating, kw):
        """All tokens want expert 0; beyond capacity they are dropped —
        never silently routed into another expert's rows."""
        S, E = 16, 4
        out = gating(skewed_logits(S, E), capacity_factor=0.25,
                     min_capacity=1, **kw)
        C = out.dispatch.shape[2]
        # dispatch is one-hot per (token, expert): each expert receives at
        # most C tokens, and only expert 0 receives the top-1 routes
        per_expert = np.asarray(out.dispatch.sum(axis=(0, 2)))
        assert per_expert[0] <= C
        got = np.asarray(out.dispatch.sum(axis=(1, 2)))
        assert got.max() <= kw.get("k", 1)        # a token rides ≤ k slots

    def test_sparse_overflow_goes_to_trash_slot(self):
        S, E = 16, 4
        out = top1gating_sparse(skewed_logits(S, E), capacity_factor=0.25,
                                min_capacity=1)
        C = out.capacity
        dropped = np.asarray(out.slot[:, 0]) == E * C
        assert dropped.sum() == S - C             # overflow beyond capacity
        # dropped tokens carry zero combine weight
        assert np.all(np.asarray(out.gate_val)[dropped] == 0.0)


class TestZeroTokenExperts:
    @pytest.mark.parametrize("impl", ["dense", "sparse"])
    def test_starved_experts_contribute_nothing_and_nothing_breaks(self, impl):
        params = init_moe_params(jax.random.PRNGKey(0), HID, 2 * HID, 4)
        # force router: every token to expert 1
        gate = np.zeros((HID, 4), np.float32)
        gate[:, 1] = 0.0
        params["gate"]["kernel"] = jnp.asarray(gate)
        x = jnp.ones((8, HID), jnp.float32)       # identical tokens, tied logits
        out, l_aux, counts = moe_layer(params, x, k=1, capacity_factor=8.0,
                                       dispatch_impl=impl)
        assert np.isfinite(np.asarray(out)).all()
        assert np.isfinite(float(l_aux))
        counts = np.asarray(counts)
        assert counts.sum() == 8 and (counts > 0).sum() == 1  # one hot expert

    def test_zero_token_expert_counts_are_zero(self):
        out = top1gating(skewed_logits(16, 4, to_expert=2))
        counts = np.asarray(out.exp_counts)
        assert counts[2] == 16
        assert counts[[0, 1, 3]].sum() == 0


class TestDeterministicTieBreaks:
    def test_top1_tie_picks_lowest_index_stably(self):
        logits = jnp.zeros((8, 4), jnp.float32)   # full tie
        a = top1gating(logits)
        b = top1gating(logits)
        idx = np.asarray(a.dispatch).sum(axis=2).argmax(axis=1)
        assert (idx == 0).all()                   # argmax: first index wins
        np.testing.assert_array_equal(np.asarray(a.dispatch),
                                      np.asarray(b.dispatch))

    def test_topk_tie_order_matches_lax_top_k_and_is_repeatable(self):
        logits = jnp.asarray(np.tile([1.0, 1.0, 1.0, 0.0], (6, 1)),
                             jnp.float32)
        runs = [topkgating(logits, k=2, capacity_factor=4.0)
                for _ in range(2)]
        np.testing.assert_array_equal(np.asarray(runs[0].dispatch),
                                      np.asarray(runs[1].dispatch))
        chosen = np.asarray(runs[0].dispatch).sum(axis=2)
        # lax.top_k breaks ties by lowest index: experts 0 and 1
        assert (chosen[:, :2] == 1).all() and (chosen[:, 2:] == 0).all()

    def test_sparse_and_dense_route_identically_under_ties(self):
        logits = jnp.asarray(np.tile([0.5, 0.5, 0.5, 0.5], (8, 1)),
                             jnp.float32)
        dense = topkgating(logits, k=2)
        sparse = topkgating_sparse(logits, k=2)
        dense_assign = np.asarray(dense.dispatch)          # [S, E, C]
        E, C = dense_assign.shape[1], dense_assign.shape[2]
        sparse_assign = np.zeros_like(dense_assign)
        slots = np.asarray(sparse.slot)
        for s in range(slots.shape[0]):
            for c in range(slots.shape[1]):
                sl = slots[s, c]
                if sl < E * C:
                    sparse_assign[s, sl // C, sl % C] = 1
        np.testing.assert_array_equal(dense_assign, sparse_assign)


class TestExpertResharding:
    def test_shard_ranges_balanced_with_remainder(self):
        assert expert_shard_ranges(6, 4) == [(0, 2), (2, 4), (4, 5), (5, 6)]
        assert expert_shard_ranges(8, 4) == [(0, 2), (2, 4), (4, 6), (6, 8)]
        assert expert_shard_ranges(3, 1) == [(0, 3)]
        sizes = [b - a for a, b in expert_shard_ranges(13, 5)]
        assert sum(sizes) == 13 and max(sizes) - min(sizes) <= 1

    def test_placed_ranges_match_even_padded_chunks(self):
        assert placed_expert_ranges(8, 4) == expert_shard_ranges(8, 4)
        assert placed_expert_ranges(6, 4) == [(0, 2), (2, 4), (4, 6), (6, 6)]
        assert placed_expert_ranges(5, 3) == [(0, 2), (2, 4), (4, 5)]

    def test_padded_expert_count(self):
        assert padded_expert_count(6, 4) == 8
        assert padded_expert_count(8, 4) == 8
        assert padded_expert_count(5, 3) == 6
        assert padded_expert_count(4, 1) == 4

    @pytest.mark.parametrize("impl", ["dense", "sparse"])
    def test_padded_stack_routes_bit_identically(self, impl):
        """6 experts padded onto an ep=4-friendly stack of 8: outputs match
        the unpadded layer exactly — padding columns route -inf logits and
        capacity/l_aux use the logical count."""
        E = 6
        params = init_moe_params(jax.random.PRNGKey(1), HID, 2 * HID, E)
        x = jax.random.normal(jax.random.PRNGKey(2), (16, HID), jnp.float32)
        ref_out, ref_aux, ref_counts = moe_layer(params, x, k=2,
                                                 capacity_factor=2.0,
                                                 dispatch_impl=impl)
        padded, e_logical = pad_experts_for_ep(params, 4)
        assert e_logical == E
        assert padded["gate"]["kernel"].shape == (HID, 8)
        assert padded["experts"]["w1"].shape[0] == 8
        out, aux, counts = moe_layer(padded, x, k=2, capacity_factor=2.0,
                                     dispatch_impl=impl,
                                     num_experts_logical=e_logical)
        if impl == "sparse":
            np.testing.assert_array_equal(np.asarray(ref_out), np.asarray(out))
        else:
            # the routing is the same bit for bit (next test); the dense
            # combine einsum sums over the expert axis, and two more zero
            # terms regroup XLA's float32 sum by an ulp (ROADMAP D1)
            np.testing.assert_allclose(np.asarray(ref_out), np.asarray(out),
                                       rtol=0, atol=2e-7)
        assert float(ref_aux) == float(aux)
        np.testing.assert_array_equal(np.asarray(ref_counts),
                                      np.asarray(counts)[:E])
        assert np.asarray(counts)[E:].sum() == 0   # padding never routed

    def test_padded_stack_gates_bit_identically(self):
        """The dense gate of a padded stack: the same (token, expert, slot)
        assignments and combine weights, zero in every padding column."""
        E = 6
        logits = jax.random.normal(jax.random.PRNGKey(5), (16, E), jnp.float32)
        ref = topkgating(logits, k=2, capacity_factor=2.0)
        got = topkgating(jnp.pad(logits, ((0, 0), (0, 2))), k=2,
                         capacity_factor=2.0, num_experts_logical=E)
        np.testing.assert_array_equal(np.asarray(ref.combine),
                                      np.asarray(got.combine)[:, :E])
        np.testing.assert_array_equal(np.asarray(ref.dispatch),
                                      np.asarray(got.dispatch)[:, :E])
        assert not np.asarray(got.dispatch)[:, E:].any()
        assert float(ref.l_aux) == float(got.l_aux)

    def test_reshard_divisible_places_on_expert_axis(self):
        topo = initialize_mesh(TopologyConfig(expert=4), force=True)
        params = init_moe_params(jax.random.PRNGKey(0), HID, 2 * HID, 8)
        placed, info = reshard_expert_params(params, topo)
        assert not info["padded"]
        assert info["num_experts_logical"] == 8
        w1 = placed["experts"]["w1"]
        assert EXPERT in (w1.sharding.spec[0] if isinstance(
            w1.sharding.spec[0], tuple) else (w1.sharding.spec[0],))
        assert w1.sharding.shard_shape(w1.shape)[0] == 2   # 8 experts / ep 4

    def test_reshard_uneven_pads_and_preserves_outputs(self):
        topo = initialize_mesh(TopologyConfig(expert=4), force=True)
        E = 6
        params = init_moe_params(jax.random.PRNGKey(3), HID, 2 * HID, E)
        x = jax.random.normal(jax.random.PRNGKey(4), (16, HID), jnp.float32)
        ref = moe_layer(params, x, k=1, capacity_factor=2.0)[0]
        placed, info = reshard_expert_params(params, topo)
        assert info["padded"] and info["num_experts_padded"] == 8
        # actual placement: even chunks of the PADDED stack clipped to the
        # logical count — rank 3 holds only padding
        assert info["shard_ranges"] == [(0, 2), (2, 4), (4, 6), (6, 6)]
        assert info["shard_ranges"] == placed_expert_ranges(6, 4)
        out = moe_layer(placed, x, k=1, capacity_factor=2.0,
                        num_experts_logical=info["num_experts_logical"])[0]
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=1e-6, atol=1e-6)


class TestSparseDispatchCombine:
    def test_dispatch_combine_roundtrip_with_trash_slot(self):
        S, E, C, D = 6, 2, 3, 4
        tokens = jnp.asarray(np.arange(S * D, dtype=np.float32).reshape(S, D))
        slot = jnp.asarray([[0], [1], [3], [E * C], [4], [2]], jnp.int32)
        gate_val = jnp.ones((S, 1), jnp.float32)
        ecd = dispatch_sparse(slot, tokens, E, C, jnp.float32)
        assert ecd.shape == (E, C, D)
        back = combine_sparse(slot, gate_val, ecd, jnp.float32)
        kept = np.asarray(slot[:, 0]) < E * C
        np.testing.assert_array_equal(np.asarray(back)[kept],
                                      np.asarray(tokens)[kept])
        assert np.all(np.asarray(back)[~kept] == 0.0)      # dropped → zeros


# --------------------------------------------------------------------- #
# The block on a data mesh: global routing, expert slots spread (ISSUE 26)
# --------------------------------------------------------------------- #
#: sizes chosen so that no two dimensions coincide: a shape in the compiled
#: text names its tensor (at capacity factor 2.0, C = 128 and C/G = 32)
N_EXP, D_MODEL, D_FFN, N_TOK = 8, 16, 48, 256
QUANTITIES = ("out", "l_aux", "d_tokens", "d_gate_proj", "d_up_proj",
              "d_down_proj")
COLLECTIVE = re.compile(
    r"= .*\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start)?\(")
#: (dispatch, mesh, capacity factor): at 0.5 most pairs are over capacity,
#: and which ones are dropped must not depend on the mesh either
CASES = [("sparse", "data4", 2.0), ("dense", "data4", 2.0),
         ("sparse", "data4", 0.5), ("sparse", "mics2x2", 2.0)]


def _block_params():
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    lp = {"router": {"kernel": jax.random.normal(ks[0], (D_MODEL, N_EXP))},
          "gate_proj": {"kernel": 0.2 * jax.random.normal(
              ks[1], (N_EXP, D_MODEL, D_FFN))},
          "up_proj": {"kernel": 0.2 * jax.random.normal(
              ks[2], (N_EXP, D_MODEL, D_FFN))},
          "down_proj": {"kernel": 0.2 * jax.random.normal(
              ks[3], (N_EXP, D_FFN, D_MODEL))}}
    return lp, jax.random.normal(ks[4], (N_TOK, D_MODEL))


def _value_and_grads(impl, capacity_factor):
    def objective(lp, x):
        out, l_aux = moe_mlp_block(lp, x, k=2, dispatch_impl=impl,
                                   capacity_factor=capacity_factor)
        return jnp.sum(out ** 2) + l_aux, (out, l_aux)

    return jax.jit(jax.value_and_grad(objective, argnums=(0, 1),
                                      has_aux=True))


def _named(result):
    (_, (out, l_aux)), (d_lp, d_x) = result
    named = {"out": out, "l_aux": l_aux, "d_tokens": d_x}
    named.update({f"d_{k}": d_lp[k]["kernel"]
                  for k in ("gate_proj", "up_proj", "down_proj")})
    return {k: np.asarray(v) for k, v in named.items()}


def _on_data_mesh(lp, x, mesh="data4"):
    """Four data shards (``mics2x2``: as data_outer 2 × data 2), the tokens
    batch-sharded and the expert weights stored as ZeRO-3 stores them (their
    last dimension over ``data``)."""
    topo = initialize_mesh(
        TopologyConfig(data=4, zero_shard_size=2 if mesh == "mics2x2" else -1),
        devices=jax.devices()[:4], force=True)
    x = jax.device_put(x, NamedSharding(topo.mesh, P((DATA_OUTER, DATA))))
    lp = jax.tree.map(
        lambda w: jax.device_put(w, NamedSharding(
            topo.mesh, P(None, None, DATA) if w.ndim == 3 else P())), lp)
    return topo, lp, x


def _layouts():
    return [r.attrs for r in get_tracer().records() if r.name == "moe/layout"]


@pytest.fixture(scope="module", params=CASES,
                ids=lambda case: "-".join(map(str, case)))
def both_programs(request):
    """One block on one device and on four data shards, with the compiled
    text of the second."""
    impl, mesh, capacity_factor = request.param
    lp, x = _block_params()
    topo_mod.reset_topology()
    get_tracer().clear()
    one = _named(_value_and_grads(impl, capacity_factor)(lp, x))
    one_layout = _layouts()
    _, lp4, x4 = _on_data_mesh(lp, x, mesh)
    fn = _value_and_grads(impl, capacity_factor)
    get_tracer().clear()
    four = _named(fn(lp4, x4))
    four_layout = _layouts()
    text = fn.lower(lp4, x4).compile().as_text()
    topo_mod.reset_topology()
    return dict(one=one, four=four, text=text, one_layout=one_layout,
                four_layout=four_layout,
                capacity=int(np.ceil(N_TOK * 2 / N_EXP * capacity_factor)))


class TestBlockOnDataShards:
    """Routing stays one function of the whole batch (capacity, slots in
    token order, drops, balance term); only where the slots are computed
    changes, so a data mesh gives the one-device result."""

    @pytest.mark.parametrize("quantity", QUANTITIES)
    def test_equals_one_device(self, both_programs, quantity):
        want = both_programs["one"][quantity]
        got = both_programs["four"][quantity]
        assert got.shape == want.shape
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()

    def test_each_shard_computes_its_part_of_every_experts_slots(
            self, both_programs):
        (one,), (four,) = (both_programs["one_layout"],
                           both_programs["four_layout"])
        cap = both_programs["capacity"]
        assert one == dict(groups=1, tokens_per_group=N_TOK, capacity=cap,
                           slots_per_group=cap, experts=N_EXP, local=False)
        assert four == dict(groups=4, tokens_per_group=N_TOK // 4,
                            capacity=cap, slots_per_group=cap // 4,
                            experts=N_EXP, local=True)

    def test_collectives_move_no_expert_activation(self, both_programs):
        """What has the F dimension and crosses chips is a WEIGHT (ZeRO-3's
        gather, the gradient's reduction): no [E, C, F] product, and nothing
        at all under ``moe/experts``; dispatch and combine exchange the
        [E, C, D] buffer."""
        cap = both_programs["capacity"]
        lines = [ln for ln in both_programs["text"].splitlines()
                 if COLLECTIVE.search(ln)]
        assert any("moe/dispatch" in ln for ln in lines)
        assert any("moe/combine" in ln for ln in lines)
        for line in lines:
            assert "moe/experts" not in line, line
            for shape in re.findall(r"\[([0-9,]+)\]", line.split("(")[0]):
                dims = [int(d) for d in shape.split(",")]
                assert dims not in ([N_EXP, cap, D_FFN],
                                    [N_EXP, cap // 4, D_FFN]), line


def _block_jaxpr(lp, x, manual_mesh=None):
    """The jaxpr of one fresh trace of the block (a jitted function would
    hand back its cached one); inside a region manual over ``data`` when a
    mesh is given."""
    fn = lambda lp, x: moe_mlp_block(lp, x)  # noqa: E731
    if manual_mesh is not None:
        fn = jax.jit(topo_mod.compat_shard_map(
            fn, manual_mesh, in_specs=(P(), P(DATA)),
            out_specs=(P(DATA), P()), manual_axes={DATA}))
    return str(jax.make_jaxpr(fn)(lp, x))


def _global_jaxpr(monkeypatch, *args):
    """The block's program with the grouped path switched off: the one
    program it hands to GSPMD (or runs locally)."""
    with monkeypatch.context() as m:
        m.setattr(sharded_moe, "_routing_groups", lambda *sizes: None)
        return _block_jaxpr(*args)


class TestBlockFallBacks:
    """Where the block cannot observe a mesh of data shards alone it does
    not engage: its jaxpr is the one with the grouped path switched off,
    with no ``shard_map`` of its own."""

    @pytest.mark.parametrize("case", ["one_device", "indivisible_tokens",
                                      "indivisible_capacity",
                                      "expert_axis_2", "tensor_axis_2",
                                      "already_manual"])
    def test_falls_back_to_the_global_program(self, case, monkeypatch):
        lp, x = _block_params()
        mesh = None
        if case == "one_device":
            initialize_mesh(TopologyConfig(), devices=jax.devices()[:1],
                            force=True)
        elif case == "expert_axis_2":
            initialize_mesh(TopologyConfig(data=4, expert=2), force=True)
        elif case == "tensor_axis_2":
            initialize_mesh(TopologyConfig(data=2, tensor=2),
                            devices=jax.devices()[:4], force=True)
        else:
            topo = initialize_mesh(TopologyConfig(data=4),
                                   devices=jax.devices()[:4], force=True)
            if case == "indivisible_tokens":
                x = x[:N_TOK - 2]
            elif case == "indivisible_capacity":
                x = x[:N_TOK - 4]       # 252 tokens: C = 126
            else:
                mesh = topo.mesh
        try:
            get_tracer().clear()
            text = _block_jaxpr(lp, x, mesh)
            (layout,) = _layouts()
            assert layout["local"] is False and layout["groups"] == 1
            assert text == _global_jaxpr(monkeypatch, lp, x, mesh)
            assert text.count("shard_map") == (case == "already_manual")
        finally:
            topo_mod.reset_topology()

    @pytest.mark.parametrize("dims", [dict(data=2, tensor=2),
                                      dict(data=2, seq=2),
                                      dict(data=2, expert=2)],
                             ids=lambda d: "x".join(f"{k}{v}"
                                                    for k, v in d.items()))
    def test_other_parallel_axes_compile_and_run_in_bf16(self, dims):
        """A mesh with another axis of more than one device keeps the GSPMD
        program: compiled and run in bf16 (a region manual over ``data``
        alone would abort jax 0.9's CPU compiler there), it gives the
        one-device result."""
        lp, x = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                             _block_params())
        fn = jax.jit(lambda lp, x: moe_mlp_block(lp, x))  # noqa: E731
        topo_mod.reset_topology()
        want, want_aux = fn(lp, x)
        topo = initialize_mesh(TopologyConfig(**dims),
                               devices=jax.devices()[:4], force=True)
        try:
            get_tracer().clear()
            got, got_aux = jax.jit(lambda lp, x: moe_mlp_block(lp, x))(
                lp, jax.device_put(x, NamedSharding(topo.mesh, P(DATA))))
            (layout,) = _layouts()
            assert layout["local"] is False
            want, got = (np.asarray(a, np.float32) for a in (want, got))
            assert np.abs(got - want).max() <= 2e-2 * np.abs(want).max()
            assert abs(float(got_aux) - float(want_aux)) <= 1e-5
        finally:
            topo_mod.reset_topology()

    def test_engages_on_the_data_mesh(self, monkeypatch):
        """The control of the cases above: same comparison, other verdict."""
        lp, x = _block_params()
        _on_data_mesh(lp, x)
        try:
            text = _block_jaxpr(lp, x)
            assert "shard_map" in text
            assert text != _global_jaxpr(monkeypatch, lp, x)
        finally:
            topo_mod.reset_topology()
