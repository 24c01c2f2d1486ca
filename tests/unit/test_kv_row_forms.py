"""The STORED form of a cached K/V token (``models/serving.KVRow``).

Phi-4-mini-flash's 10 row pairs of 128 tile no sublane tile.  Its parent
stored them padded to 16 + 16 rows (``KVRow.tiled``: 8,192 B a token, three
eighths zeros that every read streamed); ``KVRow.packed`` lays five heads
along the lanes of a row, 2 K rows and 2 V rows of 640 (5,120 B, the
model's own).  Every page operation and every form of the window ring
reads either form off the pool's shape, so here both forms run every
operation on the same rows: each equals the dense oracle within the
tolerances of ``test_serving_decode.py``, and the packed form equals the
padded one TO THE BIT wherever the products are the same (the same chunk
size, the same order of a row's sum).

And a pool whose head count tiles keeps the parent's programs: the decode,
prefill and verify programs of Mistral-7B's, Qwen3-Next's and Olmo-Hybrid's
families are text-equal to the parent commit's (sha-256 of the jaxpr with
the kernels in it; ``python tests/unit/test_kv_row_forms.py`` prints them
for whatever tree is on ``PYTHONPATH``).
"""
import hashlib
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.kernels import page_ops, ragged_ops, window_ops
from deepspeed_tpu.models.serving import KVRow, WindowRing, tiling_kv_heads

pytestmark = pytest.mark.kernels

KV, G, HD = 10, 4, 128
H = KV * G
PS, NB = 16, 6
SCALE = HD ** -0.5
ROWS = {"padded": KVRow.tiled(KV, HD), "packed": KVRow.packed(KV, HD)}
#: contexts of one batch: a chunk boundary, an empty row, one row, a full table
LENS = [70, 0, 1, 33, PS * NB]


def test_the_row_kinds_say_what_a_token_is_stored_in():
    assert ROWS["padded"].token_shape == (32, 128)      # 8,192 B in bf16
    assert ROWS["packed"].token_shape == (4, 640)       # 5,120 B: its own
    assert ROWS["packed"] == KVRow(KV, HD, lane_heads=5)
    assert all(r.read_values == 2 * KV * HD for r in ROWS.values())
    assert np.prod(ROWS["packed"].token_shape) == ROWS["packed"].read_values
    # a head count that tiles is stored as it is, by either constructor
    for n in (1, 2, 4, 8, 16, 32):
        assert tiling_kv_heads(n) == n
        assert KVRow.packed(n, HD) == KVRow(n, HD)
        assert KVRow.packed(n, HD).token_shape \
            == KVRow.tiled(n, HD).token_shape == (2 * n, HD)
    # the rows left tile: 2, 4, 8 or a multiple of 16 combined
    for n in (3, 6, 10, 12, 20, 30):
        row = KVRow.packed(n, HD)
        assert row.token_shape[0] in (2, 4, 8) and row.lane_heads > 1
        assert np.prod(row.token_shape) == row.read_values
    with pytest.raises(ValueError, match="lane_heads"):
        KVRow(10, HD, lane_heads=4)
    with pytest.raises(ValueError, match="lane_heads"):
        KVRow(10, HD, stored_kv_heads=16, lane_heads=2)


def _rows(seed, dtype):
    rng = np.random.default_rng(seed)
    T = sum(LENS)
    k, v = (jnp.asarray(rng.standard_normal((T, KV, HD)), dtype)
            for _ in range(2))
    table = rng.permutation(len(LENS) * NB).reshape(len(LENS), NB) \
        .astype(np.int32)
    page = np.concatenate([table[s][np.arange(n) // PS]
                           for s, n in enumerate(LENS)]).astype(np.int32)
    off = np.concatenate([np.arange(n) % PS for n in LENS]).astype(np.int32)
    return rng, k, v, jnp.asarray(table), jnp.asarray(page), jnp.asarray(off)


def _pool(row, dtype, k, v, page, off):
    """NaN wherever nothing was appended: no operation may read there."""
    pool = jnp.full((len(LENS) * NB + 1, PS) + row.token_shape, jnp.nan,
                    dtype)
    return ragged_ops.paged_kv_append(pool, k, v, page, off)


@pytest.fixture(scope="module", params=[jnp.bfloat16, jnp.float32],
                ids=["bf16", "f32"])
def paged(request):
    """bf16: the strided pair load; float32: the general load."""
    dtype = request.param
    rng, k, v, table, page, off = _rows(59, dtype)
    pools = {name: _pool(row, dtype, k, v, page, off)
             for name, row in ROWS.items()}
    T = sum(LENS)
    return dict(
        dtype=dtype, k=k, v=v, table=table, pools=pools,
        kvl=jnp.asarray(LENS, jnp.int32),
        cu=jnp.asarray(np.concatenate([[0], np.cumsum(LENS)]), jnp.int32),
        q1=jnp.asarray(rng.standard_normal((len(LENS), H, HD)), dtype),
        qT=jnp.asarray(rng.standard_normal((T, H, HD)), dtype))


def _dense_decode(c, pool):
    return ragged_ops.decode_attend_dense(
        c["q1"], pool, c["kvl"], c["table"], num_kv_heads=KV, scale=SCALE)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


def _op_append(c, form):
    """What was appended is read back head by head, and nothing else was
    written (the padded form's extra heads are zeros)."""
    pool = np.asarray(c["pools"][form].astype(jnp.float32))
    heads = np.asarray(ragged_ops._token_heads(
        c["pools"][form], HD).astype(jnp.float32))
    stored = ROWS[form].stored
    at = 0
    for s, n in enumerate(LENS):
        pages = np.asarray(c["table"])[s]
        got = heads[pages].reshape(NB * PS, 2 * stored, HD)[:n]
        np.testing.assert_array_equal(
            got[:, :KV], np.asarray(c["k"][at:at + n].astype(jnp.float32)))
        np.testing.assert_array_equal(
            got[:, stored:stored + KV],
            np.asarray(c["v"][at:at + n].astype(jnp.float32)))
        assert not got[:, KV:stored].any() and not got[:, stored + KV:].any()
        assert np.isnan(heads[pages].reshape(NB * PS, -1)[n:]).all()
        at += n
    assert np.isnan(pool[-1]).all()                 # the trash page
    return heads[:, :, :KV], heads[:, :, stored:stored + KV]


def _op_decode(c, form):
    out = ragged_ops.decode_paged_attention(
        c["q1"], c["pools"][form], c["kvl"], c["table"], num_kv_heads=KV,
        scale=SCALE, pages_per_chunk=4, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(_dense_decode(c, c["pools"][form]), np.float32),
        **_tol(c["dtype"]))
    assert not np.asarray(out, np.float32)[1].any()       # the empty row
    return out


def _op_decode_alibi(c, form):
    """Slopes a query head: they ride with the queries into pass order."""
    slopes = np.linspace(0.01, 0.2, H).astype(np.float32)
    kw = dict(num_kv_heads=KV, scale=SCALE, alibi=slopes)
    out = ragged_ops.decode_paged_attention(
        c["q1"], c["pools"][form], c["kvl"], c["table"], pages_per_chunk=2,
        interpret=True, **kw)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(ragged_ops.decode_attend_dense(
            c["q1"], c["pools"][form], c["kvl"], c["table"], **kw),
            np.float32), **_tol(c["dtype"]))
    return out


def _op_decode_dense(c, form):
    return _dense_decode(c, c["pools"][form])


def _gather(c, pool):
    mq = max(LENS)
    q_seq = jnp.zeros((len(LENS), mq, H, HD), c["dtype"])
    for s, n in enumerate(LENS):
        if n:
            q_seq = q_seq.at[s, :n].set(
                c["qT"][int(c["cu"][s]):int(c["cu"][s + 1])])
    return page_ops._attend_gather(q_seq, pool, c["table"], c["kvl"],
                                   c["kvl"], SCALE, num_kv_heads=KV)


def _op_ragged(c, form):
    out = ragged_ops.ragged_paged_attention(
        c["qT"], c["pools"][form], c["kvl"], c["table"], c["cu"],
        num_kv_heads=KV, scale=SCALE, block_q=64, pages_per_chunk=2,
        interpret=True)
    ref = _gather(c, c["pools"][form])
    for s, n in enumerate(LENS):
        np.testing.assert_allclose(
            np.asarray(out[int(c["cu"][s]):int(c["cu"][s + 1])], np.float32),
            np.asarray(ref[s, :n]), **_tol(c["dtype"]))
    return out


def _op_verify(c, form):
    """Windows of up to 4 tokens at each context's end."""
    q_len = np.minimum(LENS, 4)
    cu = jnp.asarray(np.concatenate([[0], np.cumsum(q_len)]), jnp.int32)
    return ragged_ops.verify_window_attention(
        c["qT"][:int(cu[-1])], c["pools"][form], c["kvl"], c["table"], cu,
        num_kv_heads=KV, scale=SCALE, interpret=True)


def _op_dense(c, form):
    return _gather(c, c["pools"][form])


PAGE_OPS = {"append": _op_append, "decode": _op_decode,
            "decode_alibi": _op_decode_alibi, "decode_dense": _op_decode_dense, "ragged": _op_ragged,
            "verify": _op_verify, "dense_oracle": _op_dense}


@pytest.mark.parametrize("op", list(PAGE_OPS))
@pytest.mark.parametrize("form", list(ROWS))
def test_page_operation_in_each_stored_form(paged, form, op):
    got = PAGE_OPS[op](paged, form)
    if form == "packed":                     # ... and the padded form's bits
        want = PAGE_OPS[op](paged, "padded")
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert np.array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32), equal_nan=True)


def test_page_ops_pick_the_operations_from_the_row_kind():
    """``page_ops(row)`` of either kind are the K/V operations, and the
    layout records say which form a traced call got."""
    from deepspeed_tpu.telemetry import get_tracer

    _, k, v, table, page, off = _rows(3, jnp.bfloat16)
    kvl = jnp.asarray(LENS, jnp.int32)
    q = jnp.ones((len(LENS), H, HD), jnp.bfloat16)
    for form, (row_bytes, passes, tiles) in {
            "padded": (8192, 8, 1), "packed": (5120, 5, 5)}.items():
        ops = page_ops.page_ops(ROWS[form])
        pool = ops.append(jnp.zeros((len(LENS) * NB + 1, PS)
                                    + ROWS[form].token_shape, jnp.bfloat16),
                          k, v, page, off)
        n = len(get_tracer().records())
        ragged_ops.decode_paged_attention(q, pool, kvl, table, num_kv_heads=KV,
                                          interpret=True)
        ops.ragged(jnp.ones((sum(LENS), H, HD), jnp.bfloat16), pool, kvl,
                   table, jnp.asarray(np.concatenate([[0], np.cumsum(LENS)]),
                                      jnp.int32),
                   block_q=64, pages_per_chunk=8, scale=SCALE)
        recs = {r.name: r.attrs for r in get_tracer().records()[n:]}
        rec = recs["attn/decode_layout"]
        assert (rec["row_bytes"], rec["read_bytes"]) == (row_bytes, 5120)
        assert (rec["load"], rec["passes_per_chunk"], rec["lane_tiles"],
                rec["lane_heads"], rec["kv_heads"]) \
            == ("strided", passes, tiles, tiles, KV)
        rec = recs["attn/ragged_layout"]
        assert (rec["row_bytes"], rec["read_bytes"], rec["lane_heads"],
                rec["stored_kv_heads"]) \
            == (row_bytes, 5120, tiles, ROWS[form].stored)
        # from 40 query heads up a chunk is 2 pages (PR 55's VMEM lesson)
        assert rec["P"] == 2


# --------------------------------------------------------------------- #
# The window ring: a 512-row ring across its first and second wrap
# --------------------------------------------------------------------- #
W, RING_PAGE, SLOTS = 512, 64, 3
RING = WindowRing(num_layers=1, window=W, page=RING_PAGE)


def _ring_batch(q_len, ctx_len):
    q_len, ctx_len = np.asarray(q_len), np.asarray(ctx_len)
    cu = np.concatenate([[0], np.cumsum(q_len)])
    seq = np.repeat(np.arange(len(q_len)), q_len)
    pos = np.concatenate([np.arange(c - n, c) for n, c in zip(q_len, ctx_len)])
    return {k: jnp.asarray(a, jnp.int32) for k, a in dict(
        q_len=q_len, ctx_len=ctx_len, cu_q_lens=cu, seq_of_token=seq,
        pos_of_token=pos).items()}


#: a sequence's life as (mode, chunks) runs: a prefill to just under the
#: window's edge and single tokens across the first wrap (position 512);
#: across the second (1,024); chunks over both; the oracle; a second
#: sequence in the slot the first has left
RING_CASES = {
    "decode across the first wrap": [("ragged", [W - 3]), ("decode", [1] * 6)],
    "decode across the second wrap": [("ragged", [W, W - 3]),
                                      ("decode", [1] * 6)],
    "ragged chunks across both wraps": [("ragged", [200, 300, 260, 270, 100])],
    "oracle across the first wrap": [("oracle", [W - 2, 4])],
    "a reused slot": [("ragged", [40]), ("decode", [1] * 3)],
}


@pytest.fixture(scope="module")
def ring_runs():
    cache = {}

    def run(form, case):
        if (form, case) not in cache:
            cache[form, case] = _ring_modes(ROWS[form], RING_CASES[case],
                                            reuse=case == "a reused slot")
        return cache[form, case]
    return run


def _ring_modes(row, runs, reuse):
    """One sequence in slot 1 (slot 0: a bystander whose ring must stay
    NaN) fed chunk after chunk, each through its run's mode; with ``reuse`` a
    second sequence then starts in the same slot → (every chunk's outputs,
    the banded oracle's)."""
    rng = np.random.default_rng(11)

    @partial(jax.jit, static_argnames="mode")    # one program a chunk length
    def op(q, k, v, ring, rows, *, mode, batch):
        return page_ops.window_op(row, RING)(
            q, k, v, ring, rows, mode=mode, batch=batch,
            valid=jnp.ones((q.shape[0],), bool), scale=SCALE,
            pages_per_chunk=8)

    ring = jnp.full((SLOTS + 1, W) + row.token_shape, jnp.nan, jnp.bfloat16)
    outs, oracle = [], []
    for life in range(2 if reuse else 1):
        ctx, ks, vs = 0, [], []
        for mode, chunks in runs:
            for n in chunks:
                q = jnp.asarray(rng.standard_normal((n, H, HD)), jnp.bfloat16)
                k, v = (jnp.asarray(rng.standard_normal((n, KV, HD)),
                                    jnp.bfloat16) for _ in range(2))
                ks.append(k), vs.append(v)
                ctx += n
                out, ring = op(q, k, v, ring, jnp.asarray([1], jnp.int32),
                               mode=mode, batch=_ring_batch([n], [ctx]))
                outs.append(np.asarray(out, np.float32))
                oracle.append(_banded(q, jnp.concatenate(ks),
                                      jnp.concatenate(vs)))
    assert np.isnan(np.asarray(ring[0], np.float32)).all()
    return outs, oracle


def _banded(q, k, v):
    """The last ``len(q)`` tokens of a sequence attend themselves and the
    ``W - 1`` before, in float32 on the rounded rows."""
    n, T = q.shape[0], k.shape[0]
    qf = np.asarray(q, np.float32).reshape(n, KV, G, HD)
    kf, vf = np.asarray(k, np.float32), np.asarray(v, np.float32)
    q_pos = np.arange(T - n, T)[:, None]
    ok = (np.arange(T)[None] <= q_pos) & (q_pos - np.arange(T)[None] < W)
    sc = np.einsum("qkgd,ckd->kgqc", qf, kf) * SCALE
    sc = np.where(ok[None, None], sc, -1e30)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    return np.einsum("kgqc,ckd->qkgd", pr, vf).reshape(n, H, HD)


@pytest.mark.parametrize("case", list(RING_CASES))
@pytest.mark.parametrize("form", list(ROWS))
def test_window_ring_in_each_stored_form(ring_runs, monkeypatch, form, case):
    # the ring's decode form through the KERNEL (interpret mode), not the
    # dense lowering the CPU would pick
    monkeypatch.setattr(
        window_ops, "decode_attention",
        partial(ragged_ops.decode_attention, impl="pallas"))
    outs, oracle = ring_runs(form, case)
    for got, want in zip(outs, oracle):
        np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)
    if form == "packed":
        padded, _ = ring_runs("padded", case)
        for a, b in zip(outs, padded):
            np.testing.assert_array_equal(a, b)


def test_window_layout_record_says_the_stored_form():
    from deepspeed_tpu.telemetry import get_tracer

    for form, row_bytes in (("padded", 8192), ("packed", 5120)):
        n = len(get_tracer().records())
        _ring_modes(ROWS[form], [("ragged", [5])], reuse=False)
        rec = [r.attrs for r in get_tracer().records()[n:]
               if r.name == "attn/window_layout"]
        assert rec == [dict(form="ragged", window=W, page=RING_PAGE,
                            kv_heads=KV, dtype="bfloat16",
                            row_bytes=row_bytes, read_bytes=5120)]


# --------------------------------------------------------------------- #
# Pools whose head count tiles: the parent's programs, text for text
# --------------------------------------------------------------------- #
def _program_texts():
    """name → the jaxpr text of a program with the device kernels in it."""
    from deepspeed_tpu.inference.v2.model_runner import (
        build_decode_loop, build_ragged_step, build_verify_step)
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import pack_layout

    bf16, page = jnp.bfloat16, 64

    def sds(shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype)

    def family(kind):
        if kind == "mistral":
            from deepspeed_tpu.models.transformer import (CausalLM,
                                                          TransformerConfig)
            model = CausalLM(TransformerConfig(
                vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                num_layers=2, num_heads=32, num_kv_heads=8, max_seq_len=8192))
            return model, model.serving_family(), 64, 128, 1730, ()
        if kind == "qwen3next":
            from deepspeed_tpu.models.qwen3_next import (Qwen3NextConfig,
                                                         Qwen3NextLM)
            cfg = Qwen3NextConfig(num_layers=4, vocab_size=37984,
                                  experts_held=128)
            model = Qwen3NextLM(cfg)
            return model, model.serving_family(), 64, 50, 3200, (cfg.state,)
        from deepspeed_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                                      OlmoHybridLM)
        cfg = OlmoHybridConfig(num_layers=4)
        model = OlmoHybridLM(cfg)
        return model, model.serving_family(), 128, 36, 3900, (cfg.state,)

    texts = {}
    for kind in ("mistral", "qwen3next", "olmohybrid"):
        model, fam, seqs, blocks, nb, states = family(kind)
        params = jax.eval_shape(lambda k: model.init_params(k, bf16),
                                jax.random.PRNGKey(0))
        cache = sds((fam.page_layers * nb + 1, page) + fam.row.token_shape)
        if states:
            cache = (cache, tuple(
                sds((s.num_layers * seqs + 1,) + shape, dtype)
                for s in states for shape, dtype in s.arrays(bf16)))
        kw = dict(max_seqs=seqs, max_blocks=blocks, num_blocks=nb,
                  attn_impl="paged", jit=False)

        def meta(tokens):
            return sds((pack_layout(tokens, seqs, blocks, bool(states))
                        ["_total"][0],), jnp.int32)

        programs = {
            "decode": (build_decode_loop(fam, max_q=seqs, block_size=page,
                                         steps=2, **kw),
                       (params, cache, meta(seqs), sds((2,), jnp.uint32))),
            "prefill": (build_ragged_step(fam, max_q=512, **kw),
                        (params, cache, meta(512))),
            "prefill16": (build_ragged_step(fam, max_q=16, **kw),
                          (params, cache, meta(16)))}
        if not states:
            programs["verify"] = (
                build_verify_step(fam, max_q=seqs * 4, **kw),
                (params, cache, meta(seqs * 4)))
        for name, (fn, args) in programs.items():
            texts[f"{kind}.{name}"] = " ".join(
                str(jax.make_jaxpr(fn)(*args)).split())
    return texts


def _digest(text):
    # a kernel's source line rides in its call's parameters
    text = re.sub(r" at [^ ]*\.py:\d+", "", text)
    return f"{len(text)}:{hashlib.sha256(text.encode()).hexdigest()[:16]}"


#: at the parent commit (03d7b32), from this file run as a script
PARENT_PROGRAMS = {
    "mistral.decode": "54224:b89364200b996ce7",
    "mistral.prefill": "52280:37b3ebf899a8db80",
    "mistral.prefill16": "70502:70c60eebc674dfcf",
    "mistral.verify": "52633:25a6278329844caa",
    "qwen3next.decode": "250237:1f3956fc0ecd4f6a",
    "qwen3next.prefill": "246113:53e6f437d1ac2f3e",
    "qwen3next.prefill16": "260158:fb5c5674992b3e82",
    "olmohybrid.decode": "144509:9623f2fe989a8d31",
    "olmohybrid.prefill": "277980:dcecd06d435cfdc0",
    "olmohybrid.prefill16": "277084:28ea1e4d4235656e",
}


@pytest.fixture
def kernels_in_the_text(monkeypatch):
    """Trace the device branch (the Pallas calls), not the CPU's lowering."""
    from deepspeed_tpu.inference.v2.kernels import gdn_ops
    from deepspeed_tpu.moe import dropless

    for mod in (ragged_ops, gdn_ops):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    monkeypatch.setattr(dropless, "_on_tpu", lambda: True)


def test_pools_that_tile_keep_the_parents_programs(kernels_in_the_text):
    texts = _program_texts()
    for name, text in texts.items():
        kernel = "paged_decode" if name.endswith("decode") else \
            "ragged_prefill"
        assert f"pallas_call[" in text and kernel in text, name
    assert {name: _digest(text) for name, text in texts.items()} \
        == PARENT_PROGRAMS


if __name__ == "__main__":
    import json

    mp = pytest.MonkeyPatch()
    kernels_in_the_text.__wrapped__(mp)
    print(json.dumps({name: _digest(text)
                      for name, text in _program_texts().items()}, indent=4))
