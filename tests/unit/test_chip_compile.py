"""What the CPU sim cannot see: does the main path COMPILE for the chip?

The TPU's compiler is installed in the sandbox and compiles for a chip that
is described, not attached (``jax.experimental.topologies``, ``v5e:2x2``).
Every kernel of ``chip_smoke.py``'s two phases is compiled here at the
smoke's widths (Mistral-7B: hidden 4096, intermediate 14336, 32/8 heads of
128, vocab 32000), plus the default training step of a hidden-4096 model on
one chip and ZeRO-3 across the four.  Interpret-mode tests pass kernels the
chip refuses — more VMEM than a kernel may use, a block not aligned to the
tiling, a Mosaic call GSPMD cannot partition: each of those was live at the
parent of this file.  A compile that passes is not a chip run; nothing here
is timed.

Plus three quick tests of the bring-up plumbing: ``chip_smoke.py`` itself at
its CPU-rehearsal size, and the compile-cache placement.
"""
import importlib.util
import json
import os
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

pytestmark = pytest.mark.kernels

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the smoke's widths
D, F, H, KV, HD, V = 4096, 14336, 32, 8, 128, 32000
PAGE, CTX = 64, 8192
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no libtpu, or it cannot
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {exc!r}")


@pytest.fixture
def for_the_chip(monkeypatch):
    """Steer the seams that read ``jax.default_backend()`` (cpu here) onto
    their device branch — in the test, not through an option of the
    program — and keep these compiles out of the persistent cache (a
    described-device entry cannot be read back without the chip)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from deepspeed_tpu.accelerator import real_accelerator
    from deepspeed_tpu.accelerator.tpu_accelerator import TPUAccelerator
    from deepspeed_tpu.inference.v2.kernels import (gdn_ops, mla_ops,
                                                    ragged_ops, sparse_ops,
                                                    ssd_ops, ssm_ops)
    from deepspeed_tpu.kernels import fused_collective_matmul as fcm
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops.adam import fused_adam
    from deepspeed_tpu.ops.transformer import flash_attention as fa

    for mod in (fa, fcm, ragged_ops, mla_ops, gdn_ops, sparse_ops, ssm_ops,
                ssd_ops, fused_adam):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    monkeypatch.setattr(dropless, "_on_tpu", lambda: True)
    monkeypatch.setattr(fcm, "resolve_impl",
                        lambda impl="auto": "pallas" if impl == "auto"
                        else impl)
    monkeypatch.setattr(real_accelerator, "_ACCELERATOR", TPUAccelerator())
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _on(sharding, shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# ---- one builder per case: (fn, abstract args) ---------------------------
def _flash(grad, model_blocks=False):
    """bf16 inputs, so bf16 operands of every dot (PR 54), at the kernel's
    own default blocks or at the model's ``flash_block_q`` x
    ``flash_block_k``, the tiles both train cells run."""
    def build(dev):
        from deepspeed_tpu.models.transformer import TransformerConfig
        from deepspeed_tpu.ops.transformer.flash_attention import \
            flash_attention

        blocks = {}
        if model_blocks:
            cfg = TransformerConfig.tiny()
            blocks = dict(block_q=cfg.flash_block_q, block_k=cfg.flash_block_k)
        q = _on(dev, (2, 2048, H, HD))
        kv = _on(dev, (2, 2048, KV, HD))
        if not grad:
            return partial(flash_attention, **blocks), (q, kv, kv)
        loss = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, **blocks).astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2)), (q, kv, kv)
    return build


def _flash_latent(grad):
    """Latent attention's training shape: q/k 192 wide, v 128, 32 heads,
    2 x 4096 tokens, at the model's blocks (the JoyAI-LLM-Flash cell)."""
    def build(dev):
        from deepspeed_tpu.models.joyai_flash import JoyAIFlashConfig
        from deepspeed_tpu.ops.transformer.flash_attention import \
            flash_attention

        cfg = JoyAIFlashConfig()
        blocks = dict(block_q=cfg.flash_block_q, block_k=cfg.flash_block_k)
        qk = _on(dev, (2, 4096, cfg.num_heads, cfg.qk_head_dim))
        v = _on(dev, (2, 4096, cfg.num_heads, cfg.v_head_dim))
        if not grad:
            return partial(flash_attention, **blocks), (qk, qk, v)
        loss = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, **blocks).astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2)), (qk, qk, v)
    return build


def _paged(decode):
    def build(dev):
        from deepspeed_tpu.inference.v2.kernels.ragged_ops import (
            decode_paged_attention, ragged_paged_attention)

        seqs, blocks = 16, CTX // PAGE
        pool = _on(dev, (2 * seqs * blocks + 1, PAGE, 2 * KV, HD))
        lens = _on(dev, (seqs,), jnp.int32)
        table = _on(dev, (seqs, blocks), jnp.int32)
        if decode:
            return (lambda q, p, n, t: decode_paged_attention(
                q, p, n, t, num_kv_heads=KV)), \
                (_on(dev, (seqs, H, HD)), pool, lens, table)
        return (lambda q, p, n, t, cu: ragged_paged_attention(
            q, p, n, t, cu, num_kv_heads=KV)), \
            (_on(dev, (512, H, HD)), pool, lens, table,
             _on(dev, (seqs + 1,), jnp.int32))
    return build


def _paged_decode_pool(kv, hd, load, pages, heads=16, rows=64, blocks=64,
                       dtype=BF16, pairs=1):
    """The K/V decode kernel on a pool ``_decode_head_load`` answers
    ``load`` for: the rule must promise only what Mosaic lowers (the
    strided pair load must lower for the chip, not only interpret), so the
    answer is asserted and then BOTH kinds are compiled.  (A bf16 pool of
    6 or 12 combined rows, or of 64-wide heads, is refused at the page DMA
    whatever the load, at PR 32 too: PERF.md section 7.)  ``pairs``: the
    head pairs a pass scores (``_pairs_per_pass``, PR 37), asserted too —
    the widened pass must fit the scoped VMEM limit on the chip."""
    def build(dev):
        from deepspeed_tpu.inference.v2.kernels.ragged_ops import (
            _decode_head_load, _pairs_per_pass, decode_paged_attention)

        assert _decode_head_load(dtype, kv, hd, PAGE) == load
        assert load == "general" or _pairs_per_pass(kv, heads // kv) == pairs
        return (lambda q, p, n, t: decode_paged_attention(
            q, p, n, t, num_kv_heads=kv)), \
            (_on(dev, (rows, heads, hd), dtype),
             _on(dev, (pages, PAGE, 2 * kv, hd), dtype),
             _on(dev, (rows,), jnp.int32),
             _on(dev, (rows, blocks), jnp.int32))
    return build


def _paged_lane_heads(op, rows=64, ring=False):
    """The page operations on a pool that stores a token with heads ALONG
    THE LANES (``KVRow.packed(10, 128)``: 2 K rows and 2 V rows of 640, 5
    heads each; 20 combined rows of 128 are refused at the page DMA and
    padded by the chip's layout all the same): the Phi-4-mini-flash cell's
    page layer of 4,800 pages with 75-page tables, or its eight rings of
    512 rows seen as 8 pages of 64 (``ring``), 40 query heads in groups of
    4, 64 rows / a ``rows``-token chunk."""
    def build(dev):
        from deepspeed_tpu.inference.v2.kernels.ragged_ops import (
            _decode_head_load, _lane_heads, _pairs_per_pass,
            decode_paged_attention, paged_kv_append, ragged_paged_attention)
        from deepspeed_tpu.models.serving import KVRow

        row = KVRow.packed(10, HD)
        assert row.token_shape == (4, 5 * HD) and row.lane_heads == 5
        seqs, blocks, pages = (64, 8, 8 * 65 * 8 + 8) if ring \
            else (64, 75, 4801)
        pool = _on(dev, (pages, PAGE) + row.token_shape)
        assert _lane_heads(HD, pool) == 5
        assert _decode_head_load(BF16, 2, HD, PAGE) == "strided"
        assert _pairs_per_pass(2, 4) == 1              # 5 passes a chunk
        lens = _on(dev, (seqs,), jnp.int32)
        table = _on(dev, (seqs, blocks), jnp.int32)
        if op == "decode":
            return (lambda q, p, n, t: decode_paged_attention(
                q, p, n, t, num_kv_heads=10)), \
                (_on(dev, (seqs, 40, HD)), pool, lens, table)
        if op == "ragged":
            return (lambda q, p, n, t, cu: ragged_paged_attention(
                q, p, n, t, cu, num_kv_heads=10, pages_per_chunk=2)), \
                (_on(dev, (rows, 40, HD)), pool, lens, table,
                 _on(dev, (seqs + 1,), jnp.int32))
        new = _on(dev, (512, 10, HD))
        where = _on(dev, (512,), jnp.int32)
        return paged_kv_append, (pool, new, new, where, where)
    build.xla_only = op == "append"
    return build


def _paged_decode_cell(rows):
    """The K/V decode kernel as the Mistral serving cells run it: a
    16-layer pool of 1,730 pages a layer, 64-page tables, bf16, 4 / 32 / 64
    rows (prefill cell's side windows, long-context, closed-loop decode)."""
    return _paged_decode_pool(KV, HD, "strided", 16 * 1730 + 1, heads=H,
                              rows=rows)


def _rmsnorm(d, f):
    def build(dev):
        from deepspeed_tpu.kernels.fused_collective_matmul import \
            rmsnorm_matmul

        return (lambda x, s, w: rmsnorm_matmul(x, s, w, 1e-5,
                                               impl="pallas")), \
            (_on(dev, (4, 2048, d)), _on(dev, (d,)), _on(dev, (d, f)))
    return build


def _adam(dev):
    from deepspeed_tpu.ops.adam.fused_adam import fused_adam_update

    t = _on(dev, (2, D, F), jnp.float32)
    return (lambda p, g, m, v, step: fused_adam_update(
        p, g, m, v, step, lr=3e-4, weight_decay=0.1)), \
        (t, t, t, t, _on(dev, (), jnp.int32))


def _train_step(zero_stage, layers=1, device_bytes=0):
    """The default model config (flash + fused RMSNorm both "auto" = on for
    a TPU, remat) at hidden 4096 under the engine's step recipe: bf16 cast
    of fp32 masters, ``value_and_grad`` of the LM loss, AdamW.  Depth 1 and
    a short batch keep the compile quick; the kernels' tiles are the real
    ones (rows >= 256, full widths).  ``zero_stage`` 3 lays the state out
    with the engine's own ZeRO-3 plan over all four chips.  ``device_bytes``
    is the memory the checkpointed layer is told the device has, as the
    engine tells it: 0 saves nothing, a terabyte every named value (the
    flash kernel's, named inside its VJP rule, must lower)."""
    def build(topology):
        import optax

        from deepspeed_tpu.models.transformer import (CausalLM,
                                                      TransformerConfig)
        from deepspeed_tpu.runtime.activation_checkpointing import \
            checkpointing as ac
        from deepspeed_tpu.runtime.topology import (TopologyConfig,
                                                    initialize_mesh)
        from deepspeed_tpu.runtime.zero.sharding import ZeroShardingPlan
        from deepspeed_tpu.telemetry import get_tracer

        n = 4 if zero_stage else 1
        topo = initialize_mesh(TopologyConfig(),
                               devices=list(topology.devices[:n]),
                               force=True)
        cfg = TransformerConfig(
            vocab_size=V, hidden_size=D, intermediate_size=F,
            num_layers=layers, num_heads=H, num_kv_heads=KV, max_seq_len=512,
            remat=True)
        assert cfg.use_flash and cfg.fused_rmsnorm == "auto"   # defaults
        assert cfg.remat_policy == "auto"
        model = CausalLM(cfg)
        tx = optax.adamw(3e-4, weight_decay=0.1)
        p_abs = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
        plan = ZeroShardingPlan(topo, zero_stage,
                                base_specs=model.partition_specs)
        p_sh = plan.param_shardings(p_abs)
        o_sh = plan.opt_state_shardings(jax.eval_shape(tx.init, p_abs),
                                        p_abs)
        place = lambda t, sh: jax.tree.map(  # noqa: E731
            lambda x, s: _on(s, x.shape, x.dtype), t, sh)

        def step(params, opt, tokens):
            def loss_fn(p32):
                p = jax.tree.map(lambda x: x.astype(BF16), p32)
                with ac.engine_memory(device_bytes, 0):
                    return model.loss_fn(p, {"input_ids": tokens}, None)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            saved = [r for r in get_tracer().records()    # the last record
                     if r.name != "engine/host_gc"][-1]   # but for a pause
            assert saved.name == "train/remat_layout" and \
                len(saved.attrs["saved"]) == (8 if device_bytes else 0)
            updates, opt = tx.update(grads, opt, params)
            return optax.apply_updates(params, updates), opt, loss

        tokens = _on(NamedSharding(topo.mesh, topo.batch_spec()),
                     (n, 512), jnp.int32)
        return step, (place(p_abs, p_sh),
                      place(jax.eval_shape(tx.init, p_abs), o_sh), tokens)
    build.whole_topology = True
    return build


def _mla(decode):
    """Xing4.0-29B-A4B's latent attention at its published widths: 32
    heads on one 640-lane latent row a token, contexts to 25,088."""
    def build(dev):
        from deepspeed_tpu.inference.v2.kernels import mla_ops

        seqs, blocks, row = 64, 25088 // PAGE, 640
        pool = _on(dev, (5 * 3000 + 1, PAGE, row))
        lens = _on(dev, (seqs,), jnp.int32)
        table = _on(dev, (seqs, blocks), jnp.int32)
        kw = dict(rank=512, scale=0.1447)
        if decode:
            return (lambda q, p, n, t: mla_ops.mla_paged_decode(
                q, p, n, t, **kw)), (_on(dev, (seqs, H, row)), pool, lens,
                                     table)
        return (lambda q, p, n, t, cu: mla_ops.mla_ragged_prefill(
            q, p, n, t, cu, **kw)), \
            (_on(dev, (512, H, row)), pool, lens, table,
             _on(dev, (seqs + 1,), jnp.int32))
    return build


def _mla64(rows, lens=None):
    """LongCat-Flash's latent attention: the SAME 640-lane latent row as
    Xing's, read by 64 heads (twice the query block and the float32
    accumulators in VMEM), the cell's 8 page layers of ~6,400 blocks,
    contexts to 4,224.  ``rows``: None the decode kernel on 128 sequences,
    else the ragged kernel on a prefill bucket of that many tokens.
    ``lens``: the decode kernel on a batch of these contexts, constants of
    the program (a 0 between live rows: the row behind the empty one
    starts its own first chunk, with the SMEM hand-over scratch)."""
    def build(dev):
        from deepspeed_tpu.inference.v2.kernels import mla_ops

        seqs = len(lens) if lens else 128
        blocks, row, heads = 4224 // PAGE, 640, 64
        pool = _on(dev, (8 * 6400 + 1, PAGE, row))
        kv_lens = _on(dev, (seqs,), jnp.int32)
        table = _on(dev, (seqs, blocks), jnp.int32)
        kw = dict(rank=512, scale=192 ** -0.5)
        if rows is None:
            return (lambda q, p, n, t: mla_ops.mla_paged_decode(
                q, p, n if lens is None else jnp.asarray(lens, jnp.int32),
                t, **kw)), (_on(dev, (seqs, heads, row)), pool, kv_lens,
                            table)
        return (lambda q, p, n, t, cu: mla_ops.mla_ragged_prefill(
            q, p, n, t, cu, **kw)), \
            (_on(dev, (rows, heads, row)), pool, kv_lens, table,
             _on(dev, (seqs + 1,), jnp.int32))
    return build


def _longcat(decode):
    """The benchmark's LongCat-Flash configuration (4 double layers = 8
    latent page layers, published widths, 16 experts held of 512 + 256
    identity outputs, an eighth of the vocabulary): a fused decode window of
    128 sequences x 2 steps (two latent walks a scan step on one pool, the
    768-way router, megablox over 1,536 pair rows of which 11 tiles are in
    no group), or a 512-token SplitFuse step."""
    def build(dev):
        from deepspeed_tpu.inference.v2.model_runner import (
            build_decode_loop, build_ragged_step)
        from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import \
            pack_layout
        from deepspeed_tpu.models.longcat_flash import (LongCatFlashConfig,
                                                        LongCatFlashLM)

        cfg = LongCatFlashConfig(num_layers=4, vocab_size=16384,
                                 experts_held=16)
        model = LongCatFlashLM(cfg)
        family = model.serving_family()
        shapes = jax.eval_shape(lambda k: model.init_params(k, BF16),
                                jax.random.PRNGKey(0))
        params = jax.tree.map(
            lambda x: _on(dev, x.shape, jnp.float32 if x.dtype == jnp.float32
                          else BF16), shapes)
        seqs, blocks, nb = 128, 4224 // PAGE, 6400
        pool = _on(dev, (family.page_layers * nb + 1, PAGE, cfg.latent_row))
        kw = dict(max_seqs=seqs, max_blocks=blocks, num_blocks=nb,
                  attn_impl="paged", jit=False)
        if decode:
            loop = build_decode_loop(family, max_q=seqs, block_size=PAGE,
                                     steps=2, **kw)
            meta = pack_layout(seqs, seqs, blocks)["_total"][0]
            return loop, (params, pool, _on(dev, (meta,), jnp.int32),
                          _on(dev, (2,), jnp.uint32))
        step = build_ragged_step(family, max_q=512, **kw)
        meta = pack_layout(512, seqs, blocks)["_total"][0]
        return step, (params, pool, _on(dev, (meta,), jnp.int32))
    return build


def _keye(decode, bucket=512):
    """The benchmark's Keye-VL configuration (6 layers, published widths, 16
    experts held of 128, the whole vocabulary) on a pool of the cell's size
    (K/V pages and index-key pages, ~850k tokens): a fused decode window of
    20 sequences x 2 steps — every layer scores up to 66,688 index keys a
    sequence, selects 2,048 and reads their rows, or takes the K/V decode
    kernel (both are in the program: which runs follows the contexts) — or
    a SplitFuse step of ``bucket`` tokens (the masked walk, or the ragged
    K/V kernel)."""
    def build(dev):
        from deepspeed_tpu.inference.v2.model_runner import (
            build_decode_loop, build_ragged_step)
        from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import \
            pack_layout
        from deepspeed_tpu.models.keye_vl import KeyeVLConfig, KeyeVLLM

        cfg = KeyeVLConfig(num_layers=6, experts_held=16)
        model = KeyeVLLM(cfg)
        family = model.serving_family()
        shapes = jax.eval_shape(lambda k: model.init_params(k, BF16),
                                jax.random.PRNGKey(0))
        params = jax.tree.map(
            lambda x: _on(dev, x.shape, jnp.float32 if x.dtype == jnp.float32
                          else BF16), shapes)
        seqs, blocks, nb = 20, 66688 // PAGE, 13300
        pages = family.page_layers * nb + 1
        pool = (_on(dev, (pages, PAGE, 2 * cfg.num_kv_heads, cfg.head_dim)),
                _on(dev, (pages, PAGE // 2, 2 * cfg.indexer_head_dim)))
        kw = dict(max_seqs=seqs, max_blocks=blocks, num_blocks=nb,
                  attn_impl="paged", jit=False)
        if decode:
            loop = build_decode_loop(family, max_q=seqs, block_size=PAGE,
                                     steps=2, **kw)
            meta = pack_layout(seqs, seqs, blocks)["_total"][0]
            return loop, (params, pool, _on(dev, (meta,), jnp.int32),
                          _on(dev, (2,), jnp.uint32))
        step = build_ragged_step(family, max_q=bucket, **kw)
        meta = pack_layout(bucket, seqs, blocks)["_total"][0]
        return step, (params, pool, _on(dev, (meta,), jnp.int32))
    return build


def _index_score(seqs=20, blocks=66688 // PAGE, nb=13300):
    """The indexer's decode score alone at the Keye cell's shape: 16 index
    heads of 64 against a sequence's own pages of index keys (two 64-value
    keys a 128-lane row), the pool of the cell's six page layers."""
    def build(dev):
        from deepspeed_tpu.inference.v2.kernels import sparse_ops

        assert sparse_ops._walk_serves(
            jax.ShapeDtypeStruct((1, PAGE // 2, 128), BF16), 16)
        return sparse_ops.index_score_paged, (
            _on(dev, (seqs, 16, 64)), _on(dev, (seqs, 16)),
            _on(dev, (6 * nb + 1, PAGE // 2, 128)),
            _on(dev, (seqs,), jnp.int32), _on(dev, (seqs, blocks), jnp.int32))
    return build


def _grouped_matmul(rows):
    """64 experts of width 1024 on hidden 3584: ``rows`` (token, choice)
    pairs sorted by expert (256 = a 64-wide decode step, 2048 = a 512-token
    prefill chunk), up and down."""
    def build(dev):
        from deepspeed_tpu.moe.dropless import grouped_matmul

        def both(x, up, down, sizes):
            return grouped_matmul(grouped_matmul(x, up, sizes), down, sizes)

        return both, (_on(dev, (rows, 3584)), _on(dev, (64, 3584, 1024)),
                      _on(dev, (64, 1024, 3584)), _on(dev, (64,), jnp.int32))
    return build


def _grouped_matmul_train(dev):
    """The Mixtral ZeRO-3 cell's expert block on one data shard: 8,192
    (token, choice) pairs sorted by expert through 8 experts of 4096 ->
    14336 -> 4096, forward and both gradients (megablox's ``gmm``, ``gmm``
    on the transposed weights, ``tgmm``), each at its own tiling."""
    from deepspeed_tpu.moe.dropless import grouped_matmul

    def grads(x, gate, up, down, sizes):
        def ffn(x, gate, up, down):
            act = jax.nn.silu(grouped_matmul(x, gate, sizes))
            y = grouped_matmul(act * grouped_matmul(x, up, sizes), down,
                               sizes)
            return y.astype(jnp.float32).sum()

        return jax.grad(ffn, argnums=(0, 1, 2, 3))(x, gate, up, down)

    return grads, (_on(dev, (8192, D)), _on(dev, (8, D, F)),
                   _on(dev, (8, D, F)), _on(dev, (8, F, D)),
                   _on(dev, (8,), jnp.int32))


def _joyai_expert_layer(dev):
    """The JoyAI-LLM-Flash cell's expert layer as a chip's share, forward
    and backward: 8,192 tokens, the top 8 of 256 router outputs, 16 experts
    of 2048 -> 768 -> 2048 held — 65,536 pair rows of which a sixteenth lie
    in a group, the rest behind the last one (``moe/dropless.py``,
    ``trained``) — beside the shared expert."""
    from deepspeed_tpu.models.joyai_flash import JoyAIFlashConfig, moe_block

    cfg = JoyAIFlashConfig(experts_held=16)
    D_, F_, E_ = cfg.hidden_size, cfg.moe_intermediate_size, cfg.held

    def grads(h, router, experts, shared, bias):
        def loss(h, router, experts, shared):
            lp = {"router": {"kernel": router}, "experts": experts,
                  "shared": shared}
            return moe_block(h, lp, bias, cfg)[0].astype(jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2, 3))(h, router, experts,
                                                    shared)

    ffn = lambda *lead: {"gate": _on(dev, lead + (D_, F_)),  # noqa: E731
                         "up": _on(dev, lead + (D_, F_)),
                         "down": _on(dev, lead + (F_, D_))}
    return grads, (_on(dev, (8192, D_)),
                   _on(dev, (D_, cfg.n_routed_experts), jnp.float32),
                   ffn(E_), ffn(), _on(dev, (cfg.n_routed_experts,),
                                       jnp.float32))


def _xing4_decode_window(dev):
    """A whole fused decode window of the benchmark's Xing4 configuration
    (1 dense + 4 expert layers, published widths, 64 sequences x 2 steps):
    five Mosaic calls in one program, two scans, the [T, 4, D] carry."""
    from deepspeed_tpu.inference.v2.model_runner import build_decode_loop
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import pack_layout
    from deepspeed_tpu.models.xing4 import Xing4Config, Xing4LM

    cfg = Xing4Config(num_layers=5, first_k_dense=1)
    shapes = jax.eval_shape(lambda k: Xing4LM(cfg).init_params(k, BF16),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda x: _on(dev, x.shape, x.dtype), shapes)
    seqs, blocks, nb = 64, 25088 // PAGE, 15000
    loop = build_decode_loop(
        Xing4LM(cfg).serving_family(), max_q=seqs, max_seqs=seqs, max_blocks=blocks, block_size=PAGE,
        num_blocks=nb, attn_impl="paged", steps=2, jit=False)
    meta = pack_layout(seqs, seqs, blocks)["_total"][0]
    return loop, (params, _on(dev, (5 * nb + 1, PAGE, cfg.latent_row)),
                  _on(dev, (meta,), jnp.int32), _on(dev, (2,), jnp.uint32))


def _gdn_decode(dev):
    """A layer's Gated DeltaNet decode call at the Qwen3-Next cell's shape:
    64 rows x 32 heads x a [128, 128] float32 state, the pool of 6 x 64 + 1
    slots aliased in place."""
    from deepspeed_tpu.inference.v2.kernels.gdn_ops import gdn_decode

    f32 = jnp.float32
    rows, heads = 64, 32
    vec = _on(dev, (rows, heads, 128), f32)
    gate = _on(dev, (rows, heads), f32)
    return gdn_decode, (vec, vec, vec, gate, gate,
                        _on(dev, (6 * rows + 1, heads, 128, 128), f32),
                        _on(dev, (rows,), jnp.int32))


def _qwen3next(decode):
    """The benchmark's Qwen3-Next configuration (two periods of 3 DeltaNet +
    1 attention layer, published widths, 128 experts held of 512, a quarter
    of the vocabulary): a fused decode window of 64 sequences x 2 steps
    (gdn_decode, the K/V page kernel on 2 x 256 rows, megablox, one scan of
    periods with both pools in the carry), or a 512-token SplitFuse step."""
    def build(dev):
        from deepspeed_tpu.inference.v2.model_runner import (
            build_decode_loop, build_ragged_step)
        from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import \
            pack_layout
        from deepspeed_tpu.models.qwen3_next import (Qwen3NextConfig,
                                                     Qwen3NextLM)

        cfg = Qwen3NextConfig(num_layers=8, vocab_size=37984,
                              experts_held=128)
        model = Qwen3NextLM(cfg)
        shapes = jax.eval_shape(lambda k: model.init_params(k, BF16),
                                jax.random.PRNGKey(0))
        params = jax.tree.map(lambda x: _on(dev, x.shape, x.dtype), shapes)
        seqs, blocks, nb = 64, 3200 // PAGE, 3200
        cache = (_on(dev, (2 * nb + 1, PAGE, 4, 256)),
                 tuple(_on(dev, (6 * seqs + 1,) + shape, dtype)
                       for shape, dtype in cfg.state.arrays(BF16)))
        kw = dict(max_seqs=seqs, max_blocks=blocks, num_blocks=nb,
                  attn_impl="paged", jit=False)
        if decode:
            loop = build_decode_loop(model.serving_family(), max_q=seqs,
                                     block_size=PAGE, steps=2, **kw)
            meta = pack_layout(seqs, seqs, blocks, True)["_total"][0]
            return loop, (params, cache, _on(dev, (meta,), jnp.int32),
                          _on(dev, (2,), jnp.uint32))
        step = build_ragged_step(model.serving_family(), max_q=512, **kw)
        meta = pack_layout(512, seqs, blocks, True)["_total"][0]
        return step, (params, cache, _on(dev, (meta,), jnp.int32))
    return build


def _gdn_decode_olmo(dev):
    """A layer's Gated DeltaNet decode call at the Olmo-Hybrid cell's shape:
    128 rows x 30 heads x a [96, 192] float32 state, stored as the state
    kind says (15 head pairs of [96, 384]: whole lane tiles, one grid step a
    sequence), the pool of 6 x 128 + 1 slots aliased in place."""
    from deepspeed_tpu.inference.v2.kernels.gdn_ops import gdn_decode
    from deepspeed_tpu.models.serving import GatedDeltaState

    f32 = jnp.float32
    rows, heads = 128, 30
    kind = GatedDeltaState(6, heads, heads, 96, 192, 4)
    assert kind.state_layout == "pairs"
    shape = kind.arrays(BF16)[0][0]
    assert shape == (15, 96, 384)
    key = _on(dev, (rows, heads, 96), f32)
    gate = _on(dev, (rows, heads), f32)
    return gdn_decode, (key, key, _on(dev, (rows, heads, 192), f32), gate,
                        gate, _on(dev, (6 * rows + 1,) + shape, f32),
                        _on(dev, (rows,), jnp.int32))


def _gdn_conv_step(rows, channels, bias=False):
    """A layer's decode-form convolution alone at a recurrent cell's shape:
    ``rows`` sequences x ``channels``, the bf16 carry pool of 6 x rows + 1
    slots of [3, channels] aliased in place, a row's block at its slot;
    ``bias``: the selective scan's, added before the SiLU."""
    def build(dev):
        from deepspeed_tpu.inference.v2.kernels.gdn_ops import \
            causal_conv_step

        return causal_conv_step, (
            _on(dev, (rows, channels)), _on(dev, (4, channels)),
            _on(dev, (6 * rows + 1, 3, channels)),
            _on(dev, (rows,), jnp.int32), _on(dev, (rows,), jnp.bool_)) \
            + ((_on(dev, (channels,)),) if bias else ())
    return build


def _ssm_decode(dev):
    """A scan layer's one-token update alone at the Phi-4-mini-flash cell's
    shape: 64 rows x a [16, 5120] float32 state, the pool of 9 x 64 + 1
    slots aliased in place."""
    from deepspeed_tpu.inference.v2.kernels.ssm_ops import ssm_decode

    f32 = jnp.float32
    rows, N, C = 64, 16, 5120
    vec, col = _on(dev, (rows, C), f32), _on(dev, (rows, N), f32)
    return ssm_decode, (vec, vec, col, col, _on(dev, (N, C), f32),
                        _on(dev, (C,), f32),
                        _on(dev, (9 * rows + 1, N, C), f32),
                        _on(dev, (rows,), jnp.int32),
                        _on(dev, (rows,), jnp.bool_))


def _ssd_decode(dev):
    """A Mamba-2 layer's one-token update alone at the Nemotron-3-Super
    cell's shape: 128 rows x 128 heads of a [64, 128] float32 state stored
    two heads along the lanes, the pool of 5 x 128 + 1 slots aliased in
    place."""
    from deepspeed_tpu.inference.v2.kernels.ssd_ops import ssd_decode

    f32 = jnp.float32
    rows, heads, hd, groups, N = 128, 128, 64, 8, 128
    head = _on(dev, (rows, heads), f32)
    group = _on(dev, (rows, groups, N), f32)
    return ssd_decode, (_on(dev, (rows, heads, hd), f32), head, head, group,
                        group, _on(dev, (5 * rows + 1, heads // 2, N, 2 * hd),
                                   f32),
                        _on(dev, (rows,), jnp.int32))


def _nemotron_h(decode, bucket=512, wide=128, steps=2):
    """The benchmark's Nemotron-3-Super configuration (the first period
    ``MEMEMEM*EME`` at published widths, 128 of 512 experts held, a quarter
    of the vocabulary): a fused decode window of ``wide`` sequences x
    ``steps`` steps (``ssd_decode`` and the convolution step in place, the
    K/V decode kernel on the one page layer, the grouped matmul over 22
    picks a token in the latent), or a SplitFuse step of ``bucket`` tokens
    (the chunked state-space-dual form); both pools in the carry."""
    def build(dev):
        from deepspeed_tpu.inference.v2.model_runner import (
            build_decode_loop, build_ragged_step)
        from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import \
            pack_layout
        from deepspeed_tpu.models.nemotron_h import (NemotronHConfig,
                                                     NemotronHLM)

        model = NemotronHLM(NemotronHConfig(
            vocab_size=32768, experts_held=128, max_seq_len=2240))
        family = model.serving_family()
        shapes = jax.eval_shape(lambda k: model.init_params(k, BF16),
                                jax.random.PRNGKey(0))
        params = jax.tree.map(lambda x: _on(dev, x.shape, x.dtype), shapes)
        seqs, blocks, nb = 128, 2240 // PAGE, 4480
        cache = (_on(dev, (nb + 1, PAGE) + family.row.token_shape),
                 tuple(_on(dev, (5 * seqs + 1,) + shape, dtype)
                       for shape, dtype in family.state.arrays(BF16)))
        kw = dict(max_seqs=seqs, max_blocks=blocks, num_blocks=nb,
                  attn_impl="paged", jit=False)
        if decode:
            kw.update(max_seqs=wide)
            loop = build_decode_loop(family, max_q=wide, block_size=PAGE,
                                     steps=steps, **kw)
            meta = pack_layout(wide, wide, blocks, True)["_total"][0]
            return loop, (params, cache, _on(dev, (meta,), jnp.int32),
                          _on(dev, (2,), jnp.uint32))
        step = build_ragged_step(family, max_q=bucket, **kw)
        meta = pack_layout(bucket, seqs, blocks, True)["_total"][0]
        return step, (params, cache, _on(dev, (meta,), jnp.int32))

    def mamba_layers_run_two_kernels_in_place(compiled):
        """A Mamba-2 layer's decode form is two Mosaic calls on the pools
        where they lie: no gathered ``[rows, 64, 128, 128]`` states."""
        text = compiled.as_text()
        for kernel in ("ssd_decode", "gdn_conv_step"):
            assert f"/{kernel}/pallas_call" in text, kernel
        assert "f32[128,64,128,128]" not in text

    def the_chunked_form_leaves_the_pool_where_it_lies(compiled):
        """The chunk loop carries the state pool in the layout it is stored
        in: unpinned, the compiler laid the carried pool out state-values-
        minor for the chunk's products and copied all 2.5 GiB of it at the
        step's entry and back at its end (temporaries 2.84 GiB, PR 60)."""
        assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * 2 ** 30

    if decode and wide == 128:
        build.check = mamba_layers_run_two_kernels_in_place
    if not decode:
        build.check = the_chunked_form_leaves_the_pool_where_it_lies
    return build


def _paged_stored_heads(op, rows=512):
    """The page operations with 30 query and K/V heads of 128 on a pool that
    STORES a token in 32 (``KVRow.tiled(30, 128)``: 64 combined rows; 60 are
    refused at the page DMA): the Olmo-Hybrid cell's two page layers of
    3,900 pages, 36-page tables, 128 rows / a ``rows``-token chunk (the SMALL
    prefill buckets too: at 16 and 32 rows the ragged kernel ran out of
    scoped VMEM on the chip while 512 compiled, PR 34)."""
    def build(dev):
        from deepspeed_tpu.inference.v2.kernels.ragged_ops import (
            _decode_head_load, _pairs_per_pass, decode_paged_attention,
            paged_kv_append, ragged_paged_attention)
        from deepspeed_tpu.models.serving import KVRow

        row = KVRow.tiled(30, HD)
        assert row.token_shape == (64, HD)
        assert _decode_head_load(BF16, 30, HD, PAGE) == "general"
        assert _decode_head_load(BF16, row.stored, HD, PAGE) == "strided"
        assert _pairs_per_pass(row.stored, 1) == 4     # 4 passes a chunk
        seqs, blocks = 128, 36
        pool = _on(dev, (2 * 3900 + 1, PAGE) + row.token_shape)
        lens = _on(dev, (seqs,), jnp.int32)
        table = _on(dev, (seqs, blocks), jnp.int32)
        if op == "decode":
            return (lambda q, p, n, t: decode_paged_attention(
                q, p, n, t, num_kv_heads=30)), \
                (_on(dev, (seqs, 30, HD)), pool, lens, table)
        if op == "ragged":
            return (lambda q, p, n, t, cu: ragged_paged_attention(
                q, p, n, t, cu, num_kv_heads=30)), \
                (_on(dev, (rows, 30, HD)), pool, lens, table,
                 _on(dev, (seqs + 1,), jnp.int32))
        new = _on(dev, (512, 30, HD))
        where = _on(dev, (512,), jnp.int32)
        return paged_kv_append, (pool, new, new, where, where)
    build.xla_only = op == "append"
    return build


def _olmo_hybrid(decode):
    """The benchmark's Olmo-Hybrid configuration (two periods of 3 DeltaNet +
    1 full-attention layer, published widths, whole vocabulary): a fused
    decode window of 128 sequences x 2 steps (gdn_decode on head pairs, the
    K/V page kernel on 32 stored heads, one scan of periods with both pools
    in the carry), or a 512-token SplitFuse step."""
    def build(dev):
        from deepspeed_tpu.inference.v2.model_runner import (
            build_decode_loop, build_ragged_step)
        from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import \
            pack_layout
        from deepspeed_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                                      OlmoHybridLM)

        cfg = OlmoHybridConfig(num_layers=8)
        model = OlmoHybridLM(cfg)
        family = model.serving_family()
        shapes = jax.eval_shape(lambda k: model.init_params(k, BF16),
                                jax.random.PRNGKey(0))
        params = jax.tree.map(lambda x: _on(dev, x.shape, x.dtype), shapes)
        seqs, blocks, nb = 128, 2304 // PAGE, 3900
        cache = (_on(dev, (2 * nb + 1, PAGE) + family.row.token_shape),
                 tuple(_on(dev, (6 * seqs + 1,) + shape, dtype)
                       for shape, dtype in cfg.state.arrays(BF16)))
        kw = dict(max_seqs=seqs, max_blocks=blocks, num_blocks=nb,
                  attn_impl="paged", jit=False)
        if decode:
            loop = build_decode_loop(family, max_q=seqs, block_size=PAGE,
                                     steps=2, **kw)
            meta = pack_layout(seqs, seqs, blocks, True)["_total"][0]
            return loop, (params, cache, _on(dev, (meta,), jnp.int32),
                          _on(dev, (2,), jnp.uint32))
        step = build_ragged_step(family, max_q=512, **kw)
        meta = pack_layout(512, seqs, blocks, True)["_total"][0]
        return step, (params, cache, _on(dev, (meta,), jnp.int32))
    return build


def _phi4_flash(decode, bucket=512, wide=64, steps=2):
    """The benchmark's Phi-4-mini-flash configuration, whole (32 layers,
    published widths, the whole vocabulary): a fused decode window of
    ``wide`` sequences x ``steps`` steps (the selective scan's one-token
    form, the K/V decode kernel on 10 row pairs stored five along the lanes
    of a row, over the window layers' rings AND over the one page layer,
    eight readers; 1 x 1: the reference check's windows), or a SplitFuse
    step of ``bucket`` tokens (the blocked scan, the ragged window form, the
    ragged page kernel); page pool, state pool and rings in the carry."""
    def build(dev):
        from deepspeed_tpu.inference.v2.model_runner import (
            build_decode_loop, build_ragged_step)
        from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import \
            pack_layout
        from deepspeed_tpu.models.phi4_flash import (Phi4FlashConfig,
                                                     Phi4FlashLM)

        model = Phi4FlashLM(Phi4FlashConfig())
        family = model.serving_family()
        assert family.row.token_shape == (4, 640)      # 5,120 B a token
        shapes = jax.eval_shape(lambda k: model.init_params(k, BF16),
                                jax.random.PRNGKey(0))
        params = jax.tree.map(lambda x: _on(dev, x.shape, x.dtype), shapes)
        seqs, blocks, nb = 64, 4800 // PAGE, 4800
        cache = (_on(dev, (nb + 1, PAGE) + family.row.token_shape),
                 tuple(_on(dev, (kind.num_layers * seqs + 1,) + shape, dtype)
                       for kind in family.slot_kinds
                       for shape, dtype in kind.arrays(BF16)))
        kw = dict(max_seqs=seqs, max_blocks=blocks, num_blocks=nb,
                  attn_impl="paged", jit=False)
        if decode:
            kw.update(max_seqs=wide)
            loop = build_decode_loop(family, max_q=wide, block_size=PAGE,
                                     steps=steps, **kw)
            meta = pack_layout(wide, wide, blocks, True)["_total"][0]
            return loop, (params, cache, _on(dev, (meta,), jnp.int32),
                          _on(dev, (2,), jnp.uint32))
        step = build_ragged_step(family, max_q=bucket, **kw)
        meta = pack_layout(bucket, seqs, blocks, True)["_total"][0]
        return step, (params, cache, _on(dev, (meta,), jnp.int32))

    def scan_layers_run_two_kernels_in_place(compiled):
        """A scan layer's decode form is two Mosaic calls on the pools
        where they lie: no gathered ``[rows, 16, 5120]`` states, and no
        more temporaries than the XLA form's window had (0.09 GiB, PR 55).
        And the pools are held at 5,120 B a token AS THE CHIP LAYS THEM
        OUT: the arguments (7.17 GiB of weights, 4,801 pages, 8 x 65 + 1
        rings of 512 rows, the scan states) are 10.09 GiB where the padded
        form's were 11.72 (PR 55) — 0.89 GiB of pages and 0.74 of rings."""
        text = compiled.as_text()
        for kernel in ("ssm_decode", "gdn_conv_step"):
            assert f"/{kernel}/pallas_call" in text, kernel
        assert "f32[64,16,5120]" not in text
        memory = compiled.memory_analysis()
        assert memory.temp_size_in_bytes < 0.09 * 2 ** 30
        assert 10.0 < memory.argument_size_in_bytes / 2 ** 30 < 10.15

    if decode and wide == 64:
        build.check = scan_layers_run_two_kernels_in_place
    return build


CASES = {
    "phi4flash_decode_window": _phi4_flash(decode=True),
    # PR 59: the other window lengths and the reference check's one-wide
    # windows, on the pool that stores a token in its own bytes
    "phi4flash_decode_window[8 steps]": _phi4_flash(decode=True, steps=8),
    "phi4flash_decode_window[1 wide, 1 step]":
        _phi4_flash(decode=True, wide=1, steps=1),
    "phi4flash_prefill_step": _phi4_flash(decode=False),
    # EVERY prefill bucket (PR 34's lesson, learnt again in PR 55: of these
    # only the 32-token bucket ran out of scoped VMEM on the chip)
    **{f"phi4flash_prefill_step[{rows} rows]":
       _phi4_flash(decode=False, bucket=rows)
       for rows in (16, 32, 64, 128, 256)},
    "gdn_decode": _gdn_decode,
    "qwen3next_decode_window": _qwen3next(decode=True),
    "qwen3next_prefill_step": _qwen3next(decode=False),
    "mla_paged_decode": _mla(decode=True),
    "mla_ragged_prefill": _mla(decode=False),
    "grouped_matmul[256 pairs]": _grouped_matmul(256),
    "grouped_matmul[2048 pairs]": _grouped_matmul(2048),
    "grouped_matmul[8192 pairs, trained]": _grouped_matmul_train,
    "expert_layer[16 of 256 held, 65536 pairs, trained]": _joyai_expert_layer,
    "xing4_decode_window": _xing4_decode_window,
    "flash_fwd": _flash(grad=False),
    "flash_bwd": _flash(grad=True),
    "flash_fwd[the model's blocks]": _flash(grad=False, model_blocks=True),
    "flash_bwd[the model's blocks]": _flash(grad=True, model_blocks=True),
    "flash_fwd[q/k 192, v 128]": _flash_latent(grad=False),
    "flash_bwd[q/k 192, v 128]": _flash_latent(grad=True),
    "decode_paged_attention": _paged(decode=True),
    "ragged_paged_attention": _paged(decode=False),
    "decode_paged_attention[4 rows]": _paged_decode_cell(4),
    "decode_paged_attention[32 rows]": _paged_decode_cell(32),
    "decode_paged_attention[64 rows]": _paged_decode_cell(64),
    # Qwen3-Next's cell: 16 / 2 heads of 256, its two page-owning layers'
    # 3,200 pages each, 50-page tables — two lane tiles a page
    "decode_paged_attention[qwen3next, 64 rows]": _paged_decode_pool(
        2, 256, "strided", 2 * 3200 + 1, blocks=50),
    "decode_paged_attention[KV 8, hd 256]": _paged_decode_pool(
        8, 256, "strided", 16 * 400 + 1, pairs=2),     # Gemma-2-9B's heads
    "decode_paged_attention[KV 4, hd 128]": _paged_decode_pool(
        4, 128, "strided", 16 * 1730 + 1),
    # PR 37, several head pairs a pass: the Olmo-Hybrid cell's pool with
    # every stored head a model head (multi-head attention, 4 pairs) and a
    # group of 2 on 16 kv heads (2 pairs), 128 rows, 36-page tables
    "decode_paged_attention[KV 32, group 1, 128 rows]": _paged_decode_pool(
        32, 128, "strided", 2 * 3900 + 1, heads=32, rows=128, blocks=36,
        pairs=4),
    "decode_paged_attention[KV 16, group 2, 128 rows]": _paged_decode_pool(
        16, 128, "strided", 2 * 3900 + 1, heads=32, rows=128, blocks=36,
        pairs=2),
    "decode_paged_attention[KV 1, general]": _paged_decode_pool(
        1, 128, "general", 16 * 400 + 1),              # a K/V word row
    "decode_paged_attention[float32 KV 6, general]": _paged_decode_pool(
        6, 128, "general", 16 * 400 + 1, heads=12, dtype=jnp.float32),
    # PR 59, heads along the lanes: Phi-4-mini-flash's 10 row pairs of 128
    "decode_paged_attention[10 heads, 5 a row]": _paged_lane_heads("decode"),
    "decode_paged_attention[10 heads, 5 a row, a ring]":
        _paged_lane_heads("decode", ring=True),
    "ragged_paged_attention[10 heads, 5 a row]":
        _paged_lane_heads("ragged", rows=512),
    "ragged_paged_attention[10 heads, 5 a row, 16 rows]":
        _paged_lane_heads("ragged", rows=16),
    "ragged_paged_attention[10 heads, 5 a row, 32 rows]":
        _paged_lane_heads("ragged", rows=32),
    "paged_kv_append[10 heads, 5 a row]": _paged_lane_heads("append"),
    "rmsnorm_matmul[4096x14336]": _rmsnorm(D, F),      # gate / up
    "rmsnorm_matmul[4096x6144]": _rmsnorm(D, 6144),    # fused qkv width
    "rmsnorm_matmul[4096x1024]": _rmsnorm(D, 1024),    # k / v
    "rmsnorm_matmul[4096x32000]": _rmsnorm(D, V),      # lm head
    "fused_adam_update": _adam,
    "train_step[1 chip]": _train_step(zero_stage=0),
    "train_step[zero3 x 4 chips]": _train_step(zero_stage=3),
    "train_step[1 chip, 2 layers, every name saved]":
        _train_step(zero_stage=0, layers=2, device_bytes=1 << 40),
    # Olmo-Hybrid: head counts and widths that tile neither sublanes nor lanes
    "gdn_decode[30 heads of 96 x 192, pairs]": _gdn_decode_olmo,
    "decode_paged_attention[30 heads stored in 32]":
        _paged_stored_heads("decode"),
    "ragged_paged_attention[30 heads stored in 32]":
        _paged_stored_heads("ragged"),
    "ragged_paged_attention[30 heads stored in 32, 16 rows]":
        _paged_stored_heads("ragged", rows=16),
    "ragged_paged_attention[30 heads stored in 32, 32 rows]":
        _paged_stored_heads("ragged", rows=32),
    "paged_kv_append[30 heads stored in 32]": _paged_stored_heads("append"),
    "olmo_hybrid_decode_window": _olmo_hybrid(decode=True),
    "olmo_hybrid_prefill_step": _olmo_hybrid(decode=False),
    # the decode-form convolution alone, at the two recurrent cells' shapes
    "gdn_conv_step[128 rows x 11520]": _gdn_conv_step(128, 11520),
    "gdn_conv_step[64 rows x 8192]": _gdn_conv_step(64, 8192),
    # the selective scan's decode form alone, at the Phi-4-mini-flash cell's
    "gdn_conv_step[64 rows x 5120, bias]": _gdn_conv_step(64, 5120, True),
    "ssm_decode[64 rows x 16 x 5120]": _ssm_decode,
    # Nemotron-3-Super: the state-space-dual kind and the latent experts
    "ssd_decode[128 rows x 128 heads x 64 x 128]": _ssd_decode,
    "nemotronh_decode_window": _nemotron_h(decode=True),
    # the width the cell runs at (ISSUE 60's fall-back, taken)
    "nemotronh_decode_window[64 wide, 8 steps]":
        _nemotron_h(decode=True, wide=64, steps=8),
    "nemotronh_decode_window[1 wide, 1 step]":
        _nemotron_h(decode=True, wide=1, steps=1),
    "nemotronh_prefill_step": _nemotron_h(decode=False),
    "nemotronh_prefill_step[16 rows]": _nemotron_h(decode=False, bucket=16),
    # LongCat-Flash: the shared latent kernels at 64 heads (every prefill
    # bucket's query tile, PR 34's lesson), and the double layer's programs
    "mla_paged_decode[64 heads]": _mla64(None),
    "mla_paged_decode[64 heads, middle row empty]":
        _mla64(None, lens=[2130, 0, 577]),
    "mla_ragged_prefill[64 heads, 16 rows]": _mla64(16),
    "mla_ragged_prefill[64 heads, 128 rows]": _mla64(128),
    "mla_ragged_prefill[64 heads, 512 rows]": _mla64(512),
    "longcat_decode_window": _longcat(decode=True),
    "longcat_prefill_step": _longcat(decode=False),
    "index_score_paged[20 rows, 1042-page tables]": _index_score(),
    "keye_decode_window": _keye(decode=True),
    "keye_prefill_step[512]": _keye(decode=False),
    "keye_prefill_step[16]": _keye(decode=False, bucket=16),
}


@pytest.mark.parametrize("case", list(CASES))
def test_compiles_for_v5e(v5e, for_the_chip, case):
    build = CASES[case]
    if getattr(build, "whole_topology", False):
        fn, args = build(v5e)
    else:
        fn, args = build(SingleDeviceSharding(v5e.devices[0]))
    lowered = jax.jit(fn).lower(*args)
    # the device kernel is IN the program (an interpret-mode or XLA
    # fall-back would compile too, and prove nothing); the append is an XLA
    # scatter by design
    assert getattr(build, "xla_only", False) \
        or "tpu_custom_call" in lowered.as_text(), \
        f"{case}: no Mosaic kernel in the lowered program"
    compiled = lowered.compile()        # raises what the chip would raise
    getattr(build, "check", lambda compiled: None)(compiled)


#: ``causal_conv_step``'s kernel as the DeltaNet cells' programs held it
#: before the convolution could take a bias (PR 35's, at [20, 256] bf16)
CONV_STEP_OPERANDS = (
    "j:Ref<smem>{i32[20]} k:Ref<smem>{i32[20]} l:Ref{bf16[16,256]} "
    "m:Ref{f32[4,256]} n:Ref{bf16[1,3,256]} o:Ref{f32[16,256]} "
    "p:Ref{bf16[1,3,256]} q:Ref<vmem>{f32[16,256]}")


def test_the_deltanet_convolution_traces_as_before_the_bias():
    """With no bias the shared kernel is the parent's program: the same
    operands, the pool aliased at the same place, and not one equation
    more (the bias is one ``get`` and one ``add``)."""
    from deepspeed_tpu.inference.v2.kernels.gdn_ops import causal_conv_step

    args = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in (
        ((20, 256), BF16), ((4, 256), BF16), ((41, 3, 256), BF16),
        ((20,), jnp.int32), ((20,), jnp.bool_), ((256,), BF16))]
    text = " ".join(str(jax.make_jaxpr(causal_conv_step)(*args[:5])).split())
    assert f"jaxpr={{ lambda ; {CONV_STEP_OPERANDS}. let" in text
    assert "input_output_aliases=((4, 1),)" in text and len(text) == 3435
    biased = " ".join(str(jax.make_jaxpr(causal_conv_step)(*args)).split())
    assert "input_output_aliases=((5, 1),)" in biased
    assert biased.count(" add ") == text.count(" add ") + 1


@pytest.mark.parametrize("rows", [256, 512, 1024, 2048])
def test_grouped_matmul_tiles_of_the_serving_shapes_are_pr28s(rows):
    """Xing4's expert layer (64 experts, 3584 <-> 1024; a layer's, and the
    whole stack of 4 x 64 groups) keeps the tiles it was measured with."""
    from deepspeed_tpu.moe.dropless import _tilings

    for groups in (64, 256):
        assert _tilings(rows, 3584, 1024, groups)[0] == (128, 1792, 512)
        assert _tilings(rows, 1024, 3584, groups)[0] == (128, 1024, 512)


def test_grouped_matmul_tiles_of_the_training_shapes():
    """Mixtral's 8 experts at 8,192 pairs a shard (4,096 in the benchmark's
    forward check): 256-row tiles, a 4096 contraction whole (PERF.md PR 31);
    forward, rows' gradient, weights' gradient."""
    from deepspeed_tpu.moe.dropless import _tilings

    for rows in (4096, 8192):
        assert _tilings(rows, D, F, 8) == (
            (256, 4096, 512), (256, 1024, 2048), (256, 1024, 1024))
        assert _tilings(rows, F, D, 8) == (
            (256, 1024, 2048), (256, 4096, 512), (256, 1024, 1024))


def test_rmsnorm_blocks_follow_the_shapes():
    """The fused kernel's tiles come from D, the dtype and the VMEM limit:
    aligned to the chip's tiling, inside the limit, and ``None`` (the
    caller runs the unfused composition) where nothing fits."""
    from deepspeed_tpu.kernels.fused_collective_matmul import (
        _VMEM_LIMIT_BYTES, rmsnorm_blocks, rmsnorm_vmem_bytes)

    for d, f in ((4096, 14336), (4096, 32000), (8192, 28672),
                 (16384, 53248)):
        bm, bn = rmsnorm_blocks(8192, d, f, BF16, BF16)
        assert bn % 128 == 0 and f % bn == 0 and bm % 16 == 0
        assert rmsnorm_vmem_bytes(bm, bn, d, 2, 2) <= _VMEM_LIMIT_BYTES
    # at the parent: block_n = 500 for F = 32000 (not a lane multiple) and
    # 16.6 MiB of VMEM at D = 4096 with the default 256 x 512 tiles
    assert rmsnorm_blocks(8192, 4096, 32000, BF16, BF16) == (256, 256)
    assert rmsnorm_blocks(8192, 16384, 53248, jnp.float32,
                          jnp.float32) is None
    assert rmsnorm_blocks(7, 64, 96, jnp.float32, jnp.float32) == (7, 96)


# ---- chip_smoke.py and the compile cache ---------------------------------
def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_needs_a_chip_or_its_rehearsal_option(capsys):
    """No chip, no rehearsal option: non-zero exit and NO result on stdout.
    With ``--cpu-rehearsal`` the same phases run at toy widths and the last
    line is the contract's."""
    smoke = _load_chip_smoke()
    assert jax.devices()[0].platform == "cpu"
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""

    assert smoke.main(["--cpu-rehearsal"]) == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    phases = {ln["phase"]: ln for ln in lines[:-1] if "phase" in ln}
    assert phases["train"]["ok"] and phases["train"]["global_steps"] == 5
    assert phases["train"]["losses"][-1] < phases["train"]["losses"][0]
    assert phases["serve"]["ok"] and all(
        r["status"] == 200 and r["tokens"] == 4
        for r in phases["serve"]["requests"])
    assert "32->2" in phases["config"]["reduced"]


@pytest.mark.parametrize("case", ["from_env", "checkout", "held_to_cpu",
                                  "threshold_from_env"])
def test_compile_cache_is_placed_from_outside(monkeypatch, case):
    """``JAX_COMPILATION_CACHE_DIR`` set: no directory is set in code.
    Unset: the one fixed path inside the checkout — except in a process
    held to the CPU (this one), which gets no cache.  Wherever the cache
    lives, programs from a tenth of a second of compile time up are kept
    (the narrow decode programs compile in under JAX's own second), unless
    the threshold too is given from outside."""
    from deepspeed_tpu.utils import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    assert os.environ["JAX_PLATFORMS"] == "cpu"        # tests/conftest.py
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    keep = ("jax_persistent_cache_min_compile_time_secs",
            compile_cache.MIN_COMPILE_SECS)
    if case in ("from_env", "threshold_from_env"):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        if case == "threshold_from_env":
            monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                               "2")
        assert compile_cache.configure_compile_cache() == "/some/dir"
        assert calls == ([keep] if case == "from_env" else [])
        return
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    if case == "held_to_cpu":
        assert compile_cache.configure_compile_cache() is None
        assert calls == []
        return
    monkeypatch.delenv("JAX_PLATFORMS")                # as on the chip
    fixed = os.path.join(REPO_ROOT, ".jax_cache")
    assert compile_cache.configure_compile_cache() == fixed
    assert calls == [keep, ("jax_compilation_cache_dir", fixed)]
