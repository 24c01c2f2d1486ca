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
# The block on a data mesh: global routing, each shard computes its own
# kept pairs with a grouped matmul (ISSUE 26, ISSUE 31)
# --------------------------------------------------------------------- #
#: sizes chosen so that no two dimensions coincide: a shape in the compiled
#: text names its tensor
N_EXP, D_MODEL, D_FFN, N_TOK = 8, 16, 48, 256
SHARDS = 4
QUANTITIES = ("out", "l_aux", "d_tokens", "d_gate_proj", "d_up_proj",
              "d_down_proj")
COLLECTIVE = re.compile(
    r"= .*\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start)?\(")


def _case(name, impl="sparse", mesh="data4", cf=2.0, k=2, tokens=N_TOK,
          rows=128):
    """``rows``: what a shard hands its grouped matmul, the shard's
    ``tokens / SHARDS * k`` pairs to a whole row tile."""
    return dict(name=name, impl=impl, mesh=mesh, cf=cf, k=k, tokens=tokens,
                rows=rows)


#: at capacity factor 0.5 most pairs are over capacity, and which ones are
#: dropped must not depend on the mesh either; the named cases steer the
#: router (:func:`_steer`)
CASES = [_case("cf2"), _case("cf0.5-drops", cf=0.5),
         _case("mics2x2", mesh="mics2x2"),
         _case("expert_absent_on_a_shard"),
         _case("one_expert_takes_a_shard", cf=4.0, k=1, rows=64),
         _case("valid_cuts_a_shards_tail"),
         _case("pairs_no_multiple_of_the_tile", tokens=264, rows=256)]
DENSE = _case("dense", impl="dense")


def _block_params(tokens=N_TOK):
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    lp = {"router": {"kernel": jax.random.normal(ks[0], (D_MODEL, N_EXP))},
          "gate_proj": {"kernel": 0.2 * jax.random.normal(
              ks[1], (N_EXP, D_MODEL, D_FFN))},
          "up_proj": {"kernel": 0.2 * jax.random.normal(
              ks[2], (N_EXP, D_MODEL, D_FFN))},
          "down_proj": {"kernel": 0.2 * jax.random.normal(
              ks[3], (N_EXP, D_FFN, D_MODEL))}}
    return lp, jax.random.normal(ks[4], (tokens, D_MODEL))


def _steer(case, lp, x):
    """The named cases: tokens moved along a router column so that one
    shard's routing is what the name says, or a validity mask; returns
    ``(x, valid)``.  What the grouped matmul of that shard then sees is
    asserted here, from the router's own logits."""
    name, per = case["name"], case["tokens"] // SHARDS
    router = np.asarray(lp["router"]["kernel"])
    x, valid = np.array(x), None

    def loads(shard):
        logits = x[shard * per:(shard + 1) * per] @ router
        top = np.argsort(-logits, axis=1)[:, :case["k"]]
        return np.bincount(top.reshape(-1), minlength=N_EXP)

    if name == "expert_absent_on_a_shard":
        x[per:2 * per] -= 3.0 * router[:, 3]
        assert loads(1)[3] == 0 and (loads(0) > 0).all()
    elif name == "one_expert_takes_a_shard":
        x[2 * per:3 * per] += 3.0 * router[:, 5]
        assert loads(2)[5] == per * case["k"]
    elif name == "valid_cuts_a_shards_tail":
        valid = np.ones(case["tokens"], bool)
        valid[per + 40:2 * per] = False
        valid[-24:] = False
    return jnp.asarray(x), None if valid is None else jnp.asarray(valid)


def _value_and_grads(case):
    def objective(lp, x, valid):
        out, l_aux = moe_mlp_block(lp, x, k=case["k"], valid=valid,
                                   dispatch_impl=case["impl"],
                                   capacity_factor=case["cf"])
        return jnp.sum(out ** 2) + l_aux, (out, l_aux)

    return jax.jit(jax.value_and_grad(objective, argnums=(0, 1),
                                      has_aux=True))


def _named(result):
    (_, (out, l_aux)), (d_lp, d_x) = result
    named = {"out": out, "l_aux": l_aux, "d_tokens": d_x}
    named.update({f"d_{k}": d_lp[k]["kernel"]
                  for k in ("gate_proj", "up_proj", "down_proj")})
    return {k: np.asarray(v) for k, v in named.items()}


def _on_data_mesh(lp, x, mesh="data4", valid=None):
    """Four data shards (``mics2x2``: as data_outer 2 × data 2), the tokens
    batch-sharded and the expert weights stored as ZeRO-3 stores them (their
    last dimension over ``data``)."""
    topo = initialize_mesh(
        TopologyConfig(data=4, zero_shard_size=2 if mesh == "mics2x2" else -1),
        devices=jax.devices()[:4], force=True)
    rows = NamedSharding(topo.mesh, P((DATA_OUTER, DATA)))
    x = jax.device_put(x, rows)
    if valid is not None:
        valid = jax.device_put(valid, rows)
    lp = jax.tree.map(
        lambda w: jax.device_put(w, NamedSharding(
            topo.mesh, P(None, None, DATA) if w.ndim == 3 else P())), lp)
    return topo, lp, x, valid


def _layouts():
    return [r.attrs for r in get_tracer().records() if r.name == "moe/layout"]


def _both_programs(case):
    """One block on one device and on four data shards, with the compiled
    text of the second."""
    lp, x = _block_params(case["tokens"])
    x, valid = _steer(case, lp, x)
    topo_mod.reset_topology()
    get_tracer().clear()
    one = _named(_value_and_grads(case)(lp, x, valid))
    one_layout = _layouts()
    _, lp4, x4, valid4 = _on_data_mesh(lp, x, case["mesh"], valid)
    fn = _value_and_grads(case)
    get_tracer().clear()
    four = _named(fn(lp4, x4, valid4))
    four_layout = _layouts()
    text = fn.lower(lp4, x4, valid4).compile().as_text()
    topo_mod.reset_topology()
    capacity = max(int(np.ceil(
        case["tokens"] * case["k"] / N_EXP * case["cf"])), 4)
    return dict(one=one, four=four, text=text, one_layout=one_layout,
                four_layout=four_layout, capacity=capacity, case=case,
                valid=valid)


@pytest.fixture(scope="module", params=CASES, ids=lambda case: case["name"])
def both_programs(request):
    return _both_programs(request.param)


@pytest.fixture(scope="module")
def dense_programs():
    return _both_programs(DENSE)


def _assert_equal(programs, quantity):
    want = programs["one"][quantity]
    got = programs["four"][quantity]
    assert got.shape == want.shape
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


class TestBlockOnDataShards:
    """Routing stays one function of the whole batch (capacity, slots in
    token order, drops, balance term); only where a kept pair is computed
    changes, so a data mesh gives the one-device result."""

    @pytest.mark.parametrize("quantity", QUANTITIES)
    def test_equals_one_device(self, both_programs, quantity):
        _assert_equal(both_programs, quantity)
        if both_programs["valid"] is not None and quantity == "out":
            masked = ~np.asarray(both_programs["valid"])
            assert masked.any()
            assert not both_programs["four"]["out"][masked].any()

    def test_each_shard_computes_its_own_pairs(self, both_programs):
        (one,), (four,) = (both_programs["one_layout"],
                           both_programs["four_layout"])
        cap, case = both_programs["capacity"], both_programs["case"]
        tokens = case["tokens"]
        assert one == dict(groups=1, tokens_per_group=tokens, capacity=cap,
                           experts=N_EXP, local=False, compute="padded",
                           rows_per_group=N_EXP * cap,
                           padded_rows_per_group=N_EXP * cap)
        assert four == dict(groups=SHARDS, tokens_per_group=tokens // SHARDS,
                            capacity=cap, experts=N_EXP, local=True,
                            compute="grouped", rows_per_group=case["rows"],
                            padded_rows_per_group=N_EXP * cap // SHARDS)

    def test_collectives_move_no_expert_activation(self, both_programs):
        """Under ``moe/*`` only the router's bookkeeping crosses chips: the
        gather of the shards' G × E loads, the sum of the kept counts and
        the balance term's mean (and its transpose).  Nothing there has a
        model or an expert dimension: a kept pair is computed where its
        token lives."""
        lines = [ln for ln in both_programs["text"].splitlines()
                 if COLLECTIVE.search(ln) and "moe/" in ln]
        assert any("moe/route" in ln for ln in lines)
        for line in lines:
            for scope in ("moe/dispatch", "moe/experts", "moe/combine"):
                assert scope not in line, line
            for shape in re.findall(r"\[([0-9,]+)\]", line.split("(")[0]):
                dims = [int(d) for d in shape.split(",")]
                assert D_MODEL not in dims and D_FFN not in dims, line
                assert np.prod(dims) <= SHARDS * N_EXP, line


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

    @pytest.mark.parametrize("quantity", QUANTITIES)
    def test_dense_oracle_equals_one_device(self, dense_programs, quantity):
        """The [S, E, C] one-hots cannot feed a grouped matmul: on the data
        mesh the dense dispatch is the one GSPMD program, same result."""
        _assert_equal(dense_programs, quantity)

    def test_dense_oracle_keeps_the_padded_program(self, dense_programs):
        (one,), (four,) = (dense_programs["one_layout"],
                           dense_programs["four_layout"])
        assert four == one and one["compute"] == "padded"
        assert one["local"] is False and one["groups"] == 1

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


# --------------------------------------------------------------------- #
# The grouped matmul both expert layers share (moe/dropless.py), trained
# --------------------------------------------------------------------- #
class TestGroupedMatmul:
    """megablox with the repo's VJP (interpret mode here) against
    ``jax.lax.ragged_dot``: the product and both gradients, in the rows
    some group covers."""

    #: rows in 2 groups of K 256 -> N 384: 512 rows make a 256-row tile
    SIZES = {"even": [256, 256], "straddles_a_tile": [200, 312],
             "an_empty_group": [0, 512], "a_tail_in_no_group": [130, 250],
             "nothing_kept": [0, 0]}

    @pytest.mark.parametrize("sizes", list(SIZES))
    def test_megablox_vjp_equals_ragged_dot(self, sizes):
        from deepspeed_tpu.moe.dropless import grouped_matmul, row_tile

        group_sizes = jnp.asarray(self.SIZES[sizes], jnp.int32)
        live = int(group_sizes.sum())
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        x = jax.random.normal(ks[0], (512, 256))
        w = jax.random.normal(ks[1], (2, 256, 384))
        dy = jax.random.normal(ks[2], (512, 384))
        assert row_tile(512, 2) == 256

        def run(impl):
            out, vjp = jax.vjp(lambda x, w: grouped_matmul(
                x, w, group_sizes, impl), x, w)
            dx, dw = vjp(dy)
            # what rows in no group hold is the caller's to select out
            return [np.asarray(a) for a in (out[:live], dx[:live], dw)]

        with jax.default_matmul_precision("highest"):
            for got, want in zip(run("megablox"), run("ragged_dot")):
                assert got.shape == want.shape
                np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("rows,groups,tile", [
        (4, 64, 16), (64, 64, 64), (256, 64, 128), (2048, 64, 128),
        (2048, 256, 128), (512, 2, 256), (4096, 8, 256), (8192, 8, 256),
        (128, 8, 128), (1 << 16, 8, 256)])
    def test_row_tile(self, rows, groups, tile):
        from deepspeed_tpu.moe.dropless import row_tile

        assert row_tile(rows, groups) == tile


@pytest.fixture(scope="module")
def through_megablox():
    """The drops case with the region's grouped matmul on megablox (in
    interpret mode) and not on ``ragged_dot``, the CPU's choice."""
    import functools

    from deepspeed_tpu.moe import dropless

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(sharded_moe, "grouped_matmul", functools.partial(
            dropless.grouped_matmul, impl="megablox"))
        return _both_programs(_case("cf0.5-drops-megablox", cf=0.5))


@pytest.mark.parametrize("quantity", QUANTITIES)
def test_block_on_megablox_equals_one_device(through_megablox, quantity):
    assert through_megablox["four_layout"][0]["compute"] == "grouped"
    _assert_equal(through_megablox, quantity)
