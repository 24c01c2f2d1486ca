"""What a checkpointed layer saves (PR 48): the selection from a byte
budget, the names the model and the flash kernel's VJP rule place, and that
saving them changes no gradient and really takes the second forward pass out
of the backward program; and (PR 63) the experts' rows and the stacks ZeRO-3
gathered, kept so that a step gathers them once.

The budget comes from the device's ``bytes_limit`` through the engine
(``checkpointing.engine_memory``); the CPU reports no memory, so a test that
wants names saved opens that scope itself with a large limit.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import transformer
from deepspeed_tpu.models.transformer import (CausalLM, TransformerConfig,
                                              lm_loss)
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as ac
from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import \
    Saveable
from deepspeed_tpu.moe.sharded_moe import ROW_NAMES, STACK_NAMES
from deepspeed_tpu.profiling.roofline import spec_for_kind
from deepspeed_tpu.runtime.topology import (DATA, TopologyConfig,
                                            initialize_mesh)
from deepspeed_tpu.runtime.zero.sharding import ZeroShardingPlan
from deepspeed_tpu.telemetry import get_tracer

pytestmark = pytest.mark.core

#: by FLOPs a byte at the tiny widths in float32: a matmul's output is worth
#: half its contraction width, 128 / 2 = 64 (six of them tie: the order
#: given); the flash kernel's pair half its 128 tokens less the row
#: statistics' bytes, 2 * 128 * 128 / (128 * 4 + 4 * 4) = 62
ALL = ("gate_proj", "up_proj", "q_proj", "k_proj", "v_proj", "attn_residual",
       "flash_out", "flash_lse")
PLENTY = (1 << 40, 0)       # (bytes_limit, engine state): everything fits

#: three entries, dearest to make again a byte first when sorted: b (8), a
#: and c (4, the tie keeps the order given)
TENSORS = [Saveable(("a",), 100, 400.0), Saveable(("b", "b2"), 50, 400.0),
           Saveable(("c",), 10, 40.0)]
#: and a gathered weight behind them (1 a byte), which kept gives back the 80
#: bytes the reserve held for gathering it again
GATHERED = TENSORS + [Saveable(("w",), 80, 80.0, freed=80)]


@pytest.fixture(autouse=True)
def _plain_policy():
    yield
    ac.reset()


@pytest.mark.parametrize("budget, want", [
    (10 ** 9, ("b", "b2", "a", "c")),       # all fit: all
    (2 * 160, ("b", "b2", "a", "c")),       # exactly
    (2 * 160 - 1, ("b", "b2", "a")),        # the prefix by FLOPs a byte
    (2 * 150 - 1, ("b", "b2")),             # a does not fit: c is not tried
    (2 * 50, ("b", "b2")),
    (2 * 50 - 1, ()),                       # the best does not fit: none
    (0, ()),
])
def test_selection_is_the_prefix_that_fits(budget, want):
    assert ac.select_saved(TENSORS, layers=2, budget_bytes=budget) == want


@pytest.mark.parametrize("budget, want", [
    (2 * 160 + 80, ("b", "b2", "a", "c", "w")),     # 2 x 80 less the 80 freed
    (2 * 160 + 79, ("b", "b2", "a", "c")),
    (2 * 160 - 1, ("b", "b2", "a")),        # c does not fit: w is not tried
])
def test_a_kept_gather_gives_its_reserve_back(budget, want):
    assert ac.select_saved(GATHERED, layers=2, budget_bytes=budget) == want


def _layout_records():
    return [r for r in get_tracer().records()
            if r.name == "train/remat_layout"]


def _tiny(**kw):
    kw.setdefault("remat", True)
    return TransformerConfig(vocab_size=256, hidden_size=128,
                             intermediate_size=256, num_layers=2, num_heads=4,
                             num_kv_heads=2, max_seq_len=128, **kw)


def _tokens(batch=2, seq=128):
    return jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0, 256)


def _grad_fn(cfg, tokens, memory=None):
    def grad(params):
        def loss(p):
            if memory is None:
                return lm_loss(p, {"input_ids": tokens}, cfg)
            with ac.engine_memory(*memory):
                return lm_loss(p, {"input_ids": tokens}, cfg)
        return jax.grad(loss)(params)
    return grad


@pytest.fixture
def kernels_on(monkeypatch):
    """The flash kernel and the fused RMSNorm-matmul in interpret mode, as
    the chip's default path has them."""
    from deepspeed_tpu.kernels import fused_collective_matmul as fcm

    monkeypatch.setattr(fcm, "resolve_impl", lambda impl="auto":
                        "pallas" if impl == "auto" else impl)
    return dict(attn_impl="flash", fused_rmsnorm="on")


@pytest.mark.parametrize("path", ["kernels", "xla"])
def test_saving_every_name_changes_no_gradient(path, request):
    opts = request.getfixturevalue("kernels_on") if path == "kernels" \
        else dict(attn_impl="xla", fused_rmsnorm="off")
    cfg = _tiny(**opts)
    params = CausalLM(cfg).init_params(jax.random.PRNGKey(0))   # float32
    tokens = _tokens()
    before = len(_layout_records())
    saved = jax.jit(_grad_fn(cfg, tokens, PLENTY))(params)
    record = _layout_records()[before:]
    assert len(record) == 1
    want = ALL if path == "kernels" else ALL[:-2]   # XLA attention names none
    assert record[0].attrs["saved"] == want
    plain = jax.jit(_grad_fn(dataclasses.replace(
        cfg, remat_policy="nothing_saveable"), tokens))(params)
    for a, b in zip(jax.tree.leaves(saved), jax.tree.leaves(plain)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_saved_names_take_the_second_forward_out_of_the_backward(kernels_on):
    """One forward call of ``flash_fwd`` and of each of a layer's five
    ``rmsnorm_matmul`` in the differentiated step (the scan bodies are in
    the text once each); two under ``nothing_saveable``: the forward scan's
    and the backward scan's own."""
    cfg = _tiny(**kernels_on)
    params = CausalLM(cfg).init_params(jax.random.PRNGKey(0))
    tokens = _tokens()

    def calls(cfg, memory):
        text = str(jax.make_jaxpr(_grad_fn(cfg, tokens, memory))(params))
        return {k: text.count(f"name={k}") for k in
                ("flash_fwd", "rmsnorm_matmul", "flash_bwd_dq",
                 "flash_bwd_dkv")}

    assert calls(cfg, PLENTY) == {"flash_fwd": 1, "rmsnorm_matmul": 5,
                                  "flash_bwd_dq": 1, "flash_bwd_dkv": 1}
    plain = {"flash_fwd": 2, "rmsnorm_matmul": 10,
             "flash_bwd_dq": 1, "flash_bwd_dkv": 1}
    assert calls(dataclasses.replace(
        cfg, remat_policy="nothing_saveable"), None) == plain
    # the best entry alone (gate_proj: [256, 256] float32 a layer, and the
    # backward's copy of one layer's, with a byte to spare): the flash
    # kernel and the four other projections are still made again
    reserve = transformer._remat_layout(cfg, 2, 128, 4)[1]
    gate = (2 + 1) * 256 * 256 * 4 + 1
    assert calls(cfg, (gate + reserve, 0)) == {
        "flash_fwd": 2, "rmsnorm_matmul": 9,
        "flash_bwd_dq": 1, "flash_bwd_dkv": 1}
    assert calls(cfg, (gate - 2 + reserve, 0)) == plain
    # every matmul's output and not the flash kernel's pair ([256, 128] and
    # [256, 4] float32 a layer), which now ranks last: one byte short of
    # all, the forward kernel alone runs twice
    everything = (2 + 1) * sum(
        t.bytes for t in transformer._remat_layout(cfg, 2, 128, 4)[0])
    assert calls(cfg, (everything - 1 + reserve, 0)) == {
        "flash_fwd": 2, "rmsnorm_matmul": 5,
        "flash_bwd_dq": 1, "flash_bwd_dkv": 1}
    assert calls(cfg, (everything + reserve, 0)) == calls(cfg, PLENTY)


def _engine(cfg, act_ckpt=None):
    topo = initialize_mesh(TopologyConfig(), force=True)
    model = CausalLM(cfg)
    config = {"train_micro_batch_size_per_gpu": 1,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
              "bf16": {"enabled": True}}
    if act_ckpt:
        config["activation_checkpointing"] = act_ckpt
    eng, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config=config, topology=topo)
    return eng


def _step_text(eng):
    batch = {"input_ids": jnp.zeros((8, 128), jnp.int32)}
    return str(jax.make_jaxpr(eng._build_train_batch_fn())(eng.state, batch))


def test_no_memory_report_is_the_program_of_nothing_saveable():
    """The CPU reports no ``bytes_limit``: the engine tells the layer 0, the
    rule saves nothing, and the train step's text is the one an explicit
    ``nothing_saveable`` gives — the program before PR 48."""
    eng = _engine(_tiny(use_flash=False))
    limit, state = eng._device_memory
    n = sum(x.size for x in jax.tree.leaves(eng.state.params))
    # fp32 master and two moments (+ Adam's count), the bf16 copy, bf16 grads
    assert limit == 0 and 16 * n <= state <= 16 * n + 64
    before = len(_layout_records())
    text = _step_text(eng)
    record = _layout_records()[before:]
    assert len(record) == 1             # once a traced step
    attrs = dict(record[0].attrs)
    assert attrs.pop("reserve_bytes") > 0
    assert attrs == dict(saved=(), bytes_per_layer=0, layers=2,
                         budget_bytes=0, state_bytes=state)
    plain = _engine(_tiny(use_flash=False, remat_policy="nothing_saveable"))
    assert _step_text(plain) == text
    assert len(_layout_records()) == before + 1     # an explicit name: no rule


def test_engine_tells_the_layer_what_the_device_holds(monkeypatch):
    """With a memory report the engine's scope reaches the model's trace:
    every name is saved when the limit leaves room, and the record carries
    the engine's state bytes."""
    eng = _engine(_tiny(use_flash=False))
    state = eng._device_memory[1]
    monkeypatch.setattr(eng, "_device_memory", (state + (1 << 30), state))
    before = len(_layout_records())
    _step_text(eng)
    attrs = _layout_records()[before].attrs
    assert attrs["saved"] == ALL[:-2] and attrs["state_bytes"] == state
    assert attrs["budget_bytes"] == (1 << 30) - attrs["reserve_bytes"]
    # a device's share: 8 rows over 8 data shards, one row of 128 tokens
    assert attrs["bytes_per_layer"] == 128 * 2 * (2 * 256 + 128 + 2 * 64 + 128)


@pytest.mark.parametrize("override", ["policy_name", "ds_config"])
def test_explicit_policy_and_ds_config_still_override(override):
    """Neither consults the rule, whatever the device holds."""
    cfg = _tiny(use_flash=False)
    if override == "policy_name":
        cfg = dataclasses.replace(
            cfg, remat_policy="dots_with_no_batch_dims_saveable")
    else:
        ac.configure(partition_activations=True)
    params = CausalLM(cfg).init_params(jax.random.PRNGKey(0))
    before = len(_layout_records())
    def text(memory):       # a policy prints as a function at an address
        return re.sub(r"0x[0-9a-f]+", "", str(jax.make_jaxpr(
            _grad_fn(cfg, _tokens(), memory))(params)))

    assert text(PLENTY) == text(None)
    assert len(_layout_records()) == before
    if override == "policy_name":
        with pytest.raises(ValueError, match="remat_policy"):
            lm_loss(params, {"input_ids": _tokens()},
                    dataclasses.replace(cfg, remat_policy="no_such_policy"))


def test_names_inside_a_data_parallel_shard_map(kernels_on):
    """On a data=4 mesh the kernels sit in ``shard_map`` regions and the
    flash kernel's names inside one: the policy still finds them."""
    topo = initialize_mesh(TopologyConfig(data=4),
                           devices=jax.devices()[:4], force=True)
    cfg = _tiny(**kernels_on)
    params = CausalLM(cfg).init_params(jax.random.PRNGKey(0))
    tokens = _tokens(batch=4)
    with topo.mesh:
        text = str(jax.make_jaxpr(_grad_fn(cfg, tokens, PLENTY))(params))
        assert text.count("name=flash_fwd") == 1
        assert text.count("name=rmsnorm_matmul") == 5
        saved = jax.jit(_grad_fn(cfg, tokens, PLENTY))(params)
        plain = jax.jit(_grad_fn(dataclasses.replace(
            cfg, remat_policy="nothing_saveable"), tokens))(params)
    for a, b in zip(jax.tree.leaves(saved), jax.tree.leaves(plain)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_layout_of_the_mistral_cell():
    """ISSUE 48's arithmetic at the one-chip cell's shapes (4 x 2048 rows of
    Mistral-7B's widths in bf16): ~0.70 GB a layer; by FLOPs a byte the six
    matmul outputs first (a contraction of 4,096: gate and up lead the tie),
    the flash kernel's pair last (2,048 tokens: its dots are single bf16
    passes like theirs, PR 54); the experts' cell names no gate or up."""
    cfg = TransformerConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=2, num_heads=32, num_kv_heads=8, max_seq_len=2048,
        remat=True, attn_impl="flash")
    tensors, reserve = transformer._remat_layout(cfg, 4, 2048, 2)
    rows = 4 * 2048
    by_name = {t.names: t.bytes for t in tensors}
    assert by_name == {
        ("gate_proj",): rows * 14336 * 2, ("up_proj",): rows * 14336 * 2,
        ("flash_out", "flash_lse"): rows * 4096 * 2 + rows * 32 * 4,
        ("q_proj",): rows * 4096 * 2, ("k_proj",): rows * 1024 * 2,
        ("v_proj",): rows * 1024 * 2, ("attn_residual",): rows * 4096 * 2}
    assert 0.70e9 < sum(by_name.values()) < 0.71e9
    assert reserve == 3 * rows * 32000 * 4          # the head's, 3.1 GB
    assert ac.select_saved(tensors, 2, 2 * sum(by_name.values())) == ALL
    assert ac.select_saved(tensors, 2, 10 ** 9) == ("gate_proj", "up_proj")
    assert ac.select_saved(
        tensors, 2, 2 * sum(by_name.values()) - 1) == ALL[:-2]
    moe = dataclasses.replace(cfg, num_experts=8)
    names = [n for t in transformer._remat_layout(moe, 4, 2048, 2)[0]
             for n in t.names]
    assert names == ["flash_out", "flash_lse", "q_proj", "k_proj", "v_proj",
                     "attn_residual"]


# --------------------------------------------------------------------- #
# PR 63: the experts' rows and the stacks ZeRO-3 gathered
# --------------------------------------------------------------------- #
ATTENTION = ("q_proj", "k_proj", "v_proj", "attn_residual", "flash_out",
             "flash_lse")


def _mixtral_cell(monkeypatch, devices=4, **kw):
    """The four-chip cell's shapes (2 x 2048 rows a chip of Mixtral-8x7B's
    widths in bf16, depth 1) over ``devices`` data shards with the v5e's
    peaks: ``(tensors, reserve)``."""
    from deepspeed_tpu.profiling import roofline

    monkeypatch.setattr(roofline, "device_spec",
                        lambda device=None: spec_for_kind("TPU v5 lite"))
    initialize_mesh(TopologyConfig(data=devices),
                    devices=jax.devices()[:devices], force=True)
    cfg = TransformerConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=1, num_heads=32, num_kv_heads=8, max_seq_len=2048,
        remat=True, attn_impl="flash", **{"num_experts": 8, **kw})
    return transformer._remat_layout(cfg, 2 * devices, 2048, 2)


def test_layout_of_the_mixtral_cell(monkeypatch):
    """PR 63's arithmetic at the four-chip cell: a chip's 8,192 pair rows
    out of the three grouped matmuls (0.54 GB), attention's six names (0.12
    GB), the three gathered stacks (0.94 GB each) — taken in that order,
    the down rows first: a matmul's output is worth its contraction's FLOPs
    at the MXU's peak (72.8 and 20.8 ms a GB), a stack three quarters of its
    bytes over the links (3.75 ms a GB).  Activations are held twice at
    depth 1 (``layers + 1``), a stack once: the copy its backward slices out
    is the gathered layer the reserve holds already."""
    tensors, reserve = _mixtral_cell(monkeypatch)
    stack, pair_rows = 8 * 4096 * 14336 * 2, 2 * 2048 * 2
    by_name = {t.names: t.bytes for t in tensors}
    assert [t.names for t in tensors[:3]] == [(n,) for n in ROW_NAMES]
    assert [t.names for t in tensors[-3:]] == [(n,) for n in STACK_NAMES]
    gate, up, down = ((n,) for n in ROW_NAMES)
    assert by_name[gate] == by_name[up] == pair_rows * 14336 * 2
    assert by_name[down] == pair_rows * 4096 * 2
    assert all(by_name[(n,)] == stack for n in STACK_NAMES)
    rows = by_name[gate] + by_name[up] + by_name[down]
    attention = sum(by_name.values()) - rows - 3 * stack
    assert (rows, attention) == (536_870_912, 117_964_800)
    # the gathered layer, the largest weight's gradient, every activation
    # made again and a cotangent for each: above the head's 1.57 GB
    weights = 2 * 4096 * (4096 + 2 * 1024 + 4096) + 3 * stack
    assert reserve == weights + stack + 2 * (attention + rows) \
        == 5_151_653_888
    ms_a_gb = {t.names[0]: 1e12 * t.seconds / t.bytes for t in tensors}
    assert ms_a_gb[ROW_NAMES[0]] == pytest.approx(4096 / 197)
    assert ms_a_gb[ROW_NAMES[2]] == pytest.approx(14336 / 197)
    assert ms_a_gb[STACK_NAMES[0]] == pytest.approx(0.75 / 0.2)
    need = 2 * (attention + rows) + 3 * stack
    assert need == 4_128_243_712        # PERF.md section 6, PR 63
    everything = ROW_NAMES[2:] + ROW_NAMES[:2] + ATTENTION + STACK_NAMES
    assert ac.select_saved(tensors, 2, need) == everything
    assert ac.select_saved(tensors, 2, need - 1) == everything[:-1]


def test_a_budget_without_room_for_the_stacks_keeps_the_activations(
        monkeypatch):
    """Where only a prefix fits, activations win: the rows, then
    attention's names, then the stacks one by one."""
    tensors, _ = _mixtral_cell(monkeypatch)
    rows = ROW_NAMES[2:] + ROW_NAMES[:2]
    activations = 2 * sum(t.bytes for t in tensors[:-3])
    assert ac.select_saved(tensors, 2, activations) == rows + ATTENTION
    assert ac.select_saved(tensors, 2, 10 ** 9) == rows[:2]
    assert ac.select_saved(tensors, 2, activations + 8 * 4096 * 14336 * 2) \
        == rows + ATTENTION + STACK_NAMES[:1]


@pytest.mark.parametrize("case, kw, devices", [
    ("one_device", {}, 1),
    ("one_expert", dict(num_experts=1), 4),
    ("dense_oracle", dict(moe_dispatch="dense"), 4),
])
def test_nothing_of_the_experts_is_listed_off_the_grouped_path(
        monkeypatch, case, kw, devices):
    """No gather on one device, no expert block with one expert, and the
    padded einsums (one device; the dense oracle) name nothing."""
    names = [n for t in _mixtral_cell(monkeypatch, devices, **kw)[0]
             for n in t.names]
    assert not set(names) & set(ROW_NAMES + STACK_NAMES)
    assert ("gate_proj" in names) == (case == "one_expert")


def _zero3_experts():
    """A tiny Mixtral on four data shards, its parameters laid out as ZeRO-3
    stores them (the expert stacks over ``data``): ``(mesh, cfg, params,
    shardings, tokens)``."""
    topo = initialize_mesh(TopologyConfig(data=4), devices=jax.devices()[:4],
                           force=True)
    cfg = _tiny(num_experts=4, moe_top_k=2, attn_impl="xla",
                fused_rmsnorm="off")
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))       # float32
    shardings = ZeroShardingPlan(
        topo, 3, base_specs=model.partition_specs).param_shardings(params)
    assert DATA in shardings["layers"]["gate_proj"]["kernel"].spec
    return (topo.mesh, cfg, jax.device_put(params, shardings), shardings,
            _tokens(batch=4))


def _stack_gathers(hlo: str) -> int:
    """All-gathers of a whole ``[4, 128, 256]`` / ``[4, 256, 128]`` expert
    stack in a compiled program's text (a scan body is in it once)."""
    return len(re.findall(
        r"= f32\[(?:1,)?4,(?:128,256|256,128)\]\S* all-gather(?:-start)?\(",
        hlo))


def test_kept_stacks_are_gathered_once_a_step():
    """Forward and backward scan each gather the three stacks under
    ``nothing_saveable``; with the names saved the backward's are gone, and
    so are its second grouped matmuls — and no gradient moves."""
    mesh, cfg, params, shardings, tokens = _zero3_experts()
    plain_cfg = dataclasses.replace(cfg, remat_policy="nothing_saveable")
    with mesh:
        before = len(_layout_records())
        saved = jax.jit(_grad_fn(cfg, tokens, PLENTY), out_shardings=shardings)
        plain = jax.jit(_grad_fn(plain_cfg, tokens), out_shardings=shardings)
        assert _stack_gathers(saved.lower(params).compile().as_text()) == 3
        assert _stack_gathers(plain.lower(params).compile().as_text()) == 6
        record = _layout_records()[before].attrs
        assert set(record["saved"]) == set(
            ROW_NAMES + STACK_NAMES + ATTENTION[:-2])
        matmuls = [str(jax.make_jaxpr(_grad_fn(c, tokens, m))(params)).count(
            "ragged_dot") for c, m in ((cfg, PLENTY), (plain_cfg, None))]
        assert matmuls[0] < matmuls[1]
        for a, b in zip(jax.tree.leaves(saved(params)),
                        jax.tree.leaves(plain(params))):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_no_budget_is_the_experts_program_of_nothing_saveable():
    """A limit that the reserve alone uses up: the names are identities and
    the text is ``nothing_saveable``'s."""
    mesh, cfg, params, _, tokens = _zero3_experts()
    reserve = transformer._remat_layout(cfg, 4, 128, 4)[1]

    def text(cfg, memory):
        return re.sub(r"0x[0-9a-f]+", "", str(jax.make_jaxpr(
            _grad_fn(cfg, tokens, memory))(params)))

    with mesh:
        before = len(_layout_records())
        assert text(cfg, (reserve, 0)) == text(dataclasses.replace(
            cfg, remat_policy="nothing_saveable"), None)
        assert _layout_records()[before].attrs["saved"] == ()
