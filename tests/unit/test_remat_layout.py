"""What a checkpointed layer saves (PR 48): the selection from a byte
budget, the names the model and the flash kernel's VJP rule place, and that
saving them changes no gradient and really takes the second forward pass out
of the backward program.

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
from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh
from deepspeed_tpu.telemetry import get_tracer

pytestmark = pytest.mark.core

#: by FLOPs a byte at the tiny widths in float32: a matmul's output is worth
#: half its contraction width, 128 / 2 = 64 (six of them tie: the order
#: given); the flash kernel's pair half its 128 tokens less the row
#: statistics' bytes, 2 * 128 * 128 / (128 * 4 + 4 * 4) = 62
ALL = ("gate_proj", "up_proj", "q_proj", "k_proj", "v_proj", "attn_residual",
       "flash_out", "flash_lse")
PLENTY = (1 << 40, 0)       # (bytes_limit, engine state): everything fits

#: three entries, best FLOPs a byte first when sorted: b (8), a and c (4, the
#: tie keeps the order given)
TENSORS = [Saveable(("a",), 100, 400.0), Saveable(("b", "b2"), 50, 400.0),
           Saveable(("c",), 10, 40.0)]


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
