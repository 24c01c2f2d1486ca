"""The program's own spans and scopes (ISSUE 25): ``serve/*`` from the
scheduler, ``engine/*`` from the engines, on the process-global tracer with
no hub installed; a request that does not finish says why (``serve/retire``
and one warning line); the same names as host events in a profiler trace;
stable names for the device operations under them.  CPU, toy widths."""
import glob
import logging
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (
    InferenceEngineV2,
    RaggedInferenceEngineConfig,
)
from deepspeed_tpu.inference.v2.lifecycle import (LifecycleScheduler,
                                                  ServeRequest)
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.profiling import xprof_parse
from deepspeed_tpu.runtime.fault import injection
from deepspeed_tpu.telemetry import get_telemetry, get_tracer
from deepspeed_tpu.telemetry.trace import DEFAULT_MAX_SPANS, own_times

pytestmark = pytest.mark.serving


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = TransformerConfig.tiny(use_flash=False)
    model = CausalLM(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


@pytest.fixture()
def tracer():
    """The process-global tracer, emptied and at its default size."""
    tr = get_tracer()
    tr.configure(max_spans=DEFAULT_MAX_SPANS, jax_annotations=True,
                 drop_recorded=True)
    yield tr
    tr.configure(max_spans=DEFAULT_MAX_SPANS, jax_annotations=True)


def _engine(tiny_lm, **kw):
    model, params = tiny_lm
    defaults = dict(max_tokens=16, max_seqs=4, max_ctx=64, block_size=8,
                    dtype=jnp.float32, attn_impl="gather")
    defaults.update(kw)
    return InferenceEngineV2(model, params,
                             RaggedInferenceEngineConfig(**defaults))


def _serve(tiny_lm, clock=time.monotonic, n=3, new=6):
    sched = LifecycleScheduler(_engine(tiny_lm), window_steps=4, clock=clock)
    for uid in range(n):
        assert sched.submit(ServeRequest(
            uid=uid, prompt=[3 + uid, 5, 7, 11], max_new_tokens=new)).admitted
    sched.run_until_idle()
    return sched


def _children(records, parent):
    """Records directly under ``parent`` (one record): same thread, one
    level deeper, inside its interval."""
    return [r for r in records if r.tid == parent.tid
            and r.depth == parent.depth + 1
            and parent.start_s <= r.start_s
            and r.start_s + r.dur_s <= parent.start_s + parent.dur_s + 1e-9]


class TestSchedulerSpans:
    def test_no_hub_is_needed(self, tiny_lm, tracer):
        assert get_telemetry() is None
        _serve(tiny_lm)
        names = {r.name for r in tracer.records()}
        assert {"serve/step", "serve/admit", "serve/prefill", "serve/window",
                "engine/put", "engine/put_pack", "engine/put_dispatch",
                "serve/logits_fetch", "serve/prefill_apply",
                "engine/decode_dispatch", "engine/decode_pack",
                "engine/window_wait", "engine/window_fetch",
                "engine/window_account", "serve/window_apply",
                "serve/queue_wait", "serve/first_token"} <= names

    @pytest.mark.parametrize("child,parent", [
        ("serve/lifecycle", "serve/step"),
        ("serve/admit", "serve/step"), ("serve/prefill", "serve/step"),
        ("serve/window", "serve/step"), ("engine/put", "serve/prefill"),
        ("serve/logits_fetch", "serve/prefill"),
        ("serve/prefill_apply", "serve/prefill"),
        ("engine/put_pack", "engine/put"),
        ("engine/put_dispatch", "engine/put"),
        ("engine/decode_dispatch", "serve/window"),
        ("engine/decode_alloc", "engine/decode_dispatch"),
        ("engine/decode_pack", "engine/decode_dispatch"),
        ("engine/decode_launch", "engine/decode_dispatch"),
        ("engine/window_wait", "serve/window"),
        ("engine/window_fetch", "serve/window"),
        ("engine/window_account", "serve/window"),
        ("serve/window_apply", "serve/window"),
    ])
    def test_parents(self, tiny_lm, tracer, child, parent):
        _serve(tiny_lm)
        found = [r for r in tracer.records() if r.name == child]
        assert found and all(r.parent == parent for r in found)

    def test_step_kind_says_what_the_step_did(self, tiny_lm, tracer):
        _serve(tiny_lm)
        records = tracer.records()
        steps = [r for r in records if r.name == "serve/step"]
        assert {"prefill", "decode"} <= {r.attrs["kind"] for r in steps}
        for step in steps:
            under = {r.name for r in _children(records, step)}
            if step.attrs["kind"] == "prefill":
                assert "serve/prefill" in under and "serve/window" not in under
            elif step.attrs["kind"] == "decode":
                assert "serve/window" in under and "serve/prefill" not in under
            assert {"waiting", "prefilling", "decoding"} <= set(step.attrs)
        first = steps[0].attrs
        assert (first["waiting"], first["decoding"]) == (3, 0)

    def test_counters_ride_on_the_spans(self, tiny_lm, tracer):
        _serve(tiny_lm, n=3)
        records = tracer.records()
        admits = [r for r in records if r.name == "serve/admit"]
        assert sum(r.attrs["admitted"] for r in admits) == 3
        assert sum(r.attrs["preempted"] for r in admits) == 0
        puts = [r for r in records if r.name == "engine/put"]
        assert sum(r.attrs["tokens"] for r in puts) == 12
        assert all(r.attrs["bucket"] >= r.attrs["tokens"] for r in puts)
        windows = [r for r in records if r.name == "serve/window"]
        assert windows and all(r.attrs["n_seqs"] == 3 and r.attrs["steps"]
                               in (1, 2, 4) for r in windows)
        dispatch = [r for r in records if r.name == "engine/decode_dispatch"]
        assert {"key", "resumed", "compiled"} <= set(dispatch[0].attrs)

    def test_self_time_is_duration_less_children(self, tiny_lm, tracer):
        _serve(tiny_lm)
        records = tracer.records()
        own = own_times(records)
        steps = [r for r in records if r.name == "serve/step"]
        by_hand = sum(s.dur_s - sum(c.dur_s for c in _children(records, s))
                      for s in steps)
        assert own["serve/step"] == pytest.approx(by_hand, abs=1e-9)
        assert 0.0 <= own["serve/step"] <= sum(s.dur_s for s in steps)

    def test_injected_clock_queue_wait_and_first_token(self, tiny_lm, tracer):
        clock = FakeClock()
        sched = LifecycleScheduler(_engine(tiny_lm), window_steps=4,
                                   clock=clock)
        sched.submit(ServeRequest(uid=41, prompt=list(range(3, 27)),
                                  max_new_tokens=2))     # two 16-token chunks
        clock.t += 2.5
        sched.step()                # admitted, first chunk
        clock.t += 1.5
        sched.step()                # second chunk: first token
        by_name = {r.name: r for r in tracer.records()
                   if r.name in ("serve/queue_wait", "serve/first_token")}
        wait, first = by_name["serve/queue_wait"], by_name["serve/first_token"]
        assert wait.dur_s == pytest.approx(2.5)
        assert first.dur_s == pytest.approx(1.5)
        assert wait.attrs["uid"] == first.attrs["uid"] == 41
        assert wait.attrs["prompt_tokens"] == 24
        assert first.attrs["chunks"] == 2 and first.attrs["preempted"] == 0
        assert sched.request(41).first_token_t == pytest.approx(1004.0)

    def test_ring_is_bounded(self, tiny_lm, tracer):
        tracer.configure(max_spans=16)
        before = tracer.total_recorded
        _serve(tiny_lm)
        assert len(tracer.records()) == 16
        assert tracer.dropped > 0
        assert tracer.total_recorded - before == 16 + tracer.dropped

    def test_span_cost_with_nothing_listening(self, tracer):
        best = float("inf")         # the quietest batch: the suite's other
        for _ in range(10):         # workers share these cores
            t0 = time.perf_counter()
            for _ in range(2000):
                with tracer.span("cost/probe", tokens=1):
                    pass
            best = min(best, (time.perf_counter() - t0) / 2000 * 1e6)
        assert best < 20.0, f"{best:.1f} us per span"

    def test_roofline_is_computed_when_asked(self, tiny_lm, tracer):
        eng = _engine(tiny_lm)
        assert eng.last_decode_roofline is None
        logits = eng.put([0], [[3, 5, 7, 11]])
        eng.decode_batch([0], [int(jnp.argmax(logits[0]))], steps=4)
        report = eng.last_decode_roofline
        assert report["compile_polluted"] and report["steps"] == 4
        assert "hbm_pct_peak" in report
        assert eng.last_decode_roofline is report      # one report a window

    def test_spans_reach_a_profiler_trace(self, tiny_lm, tracer, tmp_path):
        _serve(tiny_lm)                 # compile outside the trace
        try:
            jax.profiler.start_trace(str(tmp_path))
        except Exception as exc:  # noqa: BLE001
            pytest.skip(f"no profiler session can start here: {exc}")
        try:
            _serve(tiny_lm)
        finally:
            jax.profiler.stop_trace()
        found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                          recursive=True)
        assert found
        data = jax.profiler.ProfileData.from_file(found[0])
        host = {ev.name.split("#")[0] for plane in data.planes
                if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events}
        assert {"serve/step", "serve/prefill", "serve/window", "engine/put",
                "engine/put_dispatch", "engine/decode_dispatch",
                "engine/window_wait", "engine/window_fetch",
                "serve/window_apply"} <= host


@pytest.fixture()
def warnings_logged():
    """WARNING lines of the package logger (it does not propagate)."""
    lines = []

    class Keep(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    handler = Keep(level=logging.WARNING)
    lg = logging.getLogger("deepspeed_tpu")
    lg.addHandler(handler)
    yield lines
    lg.removeHandler(handler)


def _shed_at_full_queue(sched, clock):
    sched.max_queue = 1
    sched.submit(ServeRequest(uid=0, prompt=[3, 5], max_new_tokens=2))
    assert not sched.submit(
        ServeRequest(uid=7, prompt=[3, 5], max_new_tokens=2)).admitted


def _shed_while_draining(sched, clock):
    sched.start_drain()
    assert sched.submit(ServeRequest(
        uid=7, prompt=[3, 5], max_new_tokens=2)).reason == "draining"


def _cannot_fit(sched, clock):
    sched.submit(ServeRequest(uid=7, prompt=list(range(3, 90)),
                              max_new_tokens=2))        # max_ctx is 64
    sched.step()


def _cancelled(sched, clock):
    sched.submit(ServeRequest(uid=7, prompt=[3, 5, 7], max_new_tokens=8))
    sched.step()
    sched.cancel(7)
    sched.step()


def _poisoned(sched, clock):
    sched.submit(ServeRequest(uid=7, prompt=[3, 5, 7, 11], max_new_tokens=8))
    injection.configure("site=decode_window,kind=nan,times=1")
    try:
        sched.run_until_idle()
    finally:
        injection.clear()


def _past_deadline(sched, clock):
    sched.submit(ServeRequest(uid=7, prompt=[3, 5, 7], max_new_tokens=8,
                              deadline_s=1.0))
    sched.step()
    clock.t += 2.0
    sched.step()


def _no_first_token_in_time(sched, clock):
    sched.submit(ServeRequest(uid=7, prompt=[3, 5, 7], max_new_tokens=8,
                              ttft_timeout_s=1.0))
    clock.t += 2.0
    sched.step()


def _drain_deadline(sched, clock):
    sched.submit(ServeRequest(uid=7, prompt=[3, 5, 7], max_new_tokens=8))
    sched.drain(deadline_s=0.0)


class TestRetire:
    """Every end but ``finished`` goes through the one ``_retire`` and says
    why: one ``serve/retire`` span, one warning line, no hub installed."""

    @pytest.mark.parametrize("how,state,reason,produced", [
        (_shed_at_full_queue, "shed", "queue_full", 0),
        (_shed_while_draining, "shed", "draining", 0),
        (_cannot_fit, "failed", "impossible", 0),
        (_cancelled, "cancelled", "cancelled", 1),
        (_poisoned, "failed", "nan", 1),
        (_past_deadline, "expired", "deadline", 1),
        (_no_first_token_in_time, "expired", "ttft_timeout", 0),
        (_drain_deadline, "expired", "drain_deadline", 0),
    ])
    def test_says_why(self, tiny_lm, tracer, warnings_logged, how, state,
                      reason, produced):
        assert get_telemetry() is None
        clock = FakeClock()
        sched = LifecycleScheduler(_engine(tiny_lm), window_steps=4,
                                   clock=clock)
        how(sched, clock)
        retired = [r for r in tracer.records() if r.name == "serve/retire"]
        assert len(retired) == 1
        attrs = retired[0].attrs
        assert (attrs["uid"], attrs["state"], attrs["reason"],
                attrs["produced"]) == (7, state, reason, produced)
        assert attrs["waiting"] >= 0 and 0.0 <= attrs["kv_used"] <= 1.0
        lines = [ln for ln in warnings_logged if "serve/retire" in ln]
        assert len(lines) == 1
        for word in ("uid=7", f"state={state}", f"reason={reason}",
                     "waiting=", "kv_used="):
            assert word in lines[0]
        req = sched.request(7)
        if req is not None:         # a shed request was never registered
            assert (req.state.value, req.finish_reason) == (state, reason)
        if state == "shed":
            assert sched.counters["serving/shed"] == 1
        # nothing of the request stays behind
        assert 7 not in sched._waiting and 7 not in sched._prefilling \
            and 7 not in sched._decodes

    def test_a_finished_request_leaves_none(self, tiny_lm, tracer,
                                            warnings_logged):
        sched = _serve(tiny_lm)
        assert all(sched.request(u).state.value == "finished"
                   for u in range(3))
        assert not [r for r in tracer.records() if r.name == "serve/retire"]
        assert not [ln for ln in warnings_logged if "serve/retire" in ln]

    def test_an_exception_out_of_the_step_is_said_first(
            self, tiny_lm, tracer, warnings_logged, monkeypatch):
        sched = LifecycleScheduler(_engine(tiny_lm), window_steps=4)
        sched.submit(ServeRequest(uid=7, prompt=[3, 5, 7], max_new_tokens=4))

        def put(*a, **k):
            raise MemoryError("RESOURCE_EXHAUSTED")

        monkeypatch.setattr(sched.eng, "put", put)
        with pytest.raises(MemoryError):
            sched.step()
        retired = [r for r in tracer.records() if r.name == "serve/retire"]
        assert len(retired) == 1
        assert retired[0].attrs["state"] == "error"
        assert retired[0].attrs["reason"] == "prefill"
        assert retired[0].attrs["error"] == "MemoryError"
        assert retired[0].parent == "serve/step"
        assert [ln for ln in warnings_logged if "error=MemoryError" in ln]
        step = [r for r in tracer.records() if r.name == "serve/step"][-1]
        assert step.error == "MemoryError"

    def test_the_benchmarks_reader_counts_them(self, tiny_lm, tracer,
                                               monkeypatch):
        """``requests_unfinished.*``: the count of ``serve/retire`` inside
        the window, 0 for a clean run, nothing for a program without spans."""
        bench = os.path.join(os.path.dirname(__file__), "..", "..",
                             "benchmark")
        monkeypatch.syspath_prepend(os.path.abspath(bench))
        for name in [m for m in sys.modules if m == "lib"
                     or m.startswith("lib.")]:
            monkeypatch.delitem(sys.modules, name)
        from lib import manifest, program_trace

        spec = manifest.metric_of("requests_unfinished.prefill")
        assert spec == manifest.metric_of("requests_unfinished.decode")
        reader = manifest.load_module("readers", spec["reader"])

        def read(lo, hi):
            monkeypatch.setitem(program_trace._LOADED, "ring", False)
            return reader.read({"window": (lo, hi)}, spec["args"])

        t0 = time.perf_counter()
        assert read(t0, t0 + 60) is None        # no serve/step: no value
        _serve(tiny_lm)
        t1 = time.perf_counter()
        assert read(t0, t1) == 0.0
        clock = FakeClock()
        sched = LifecycleScheduler(_engine(tiny_lm), window_steps=4,
                                   clock=clock)
        _shed_while_draining(sched, clock)                  # shed
        sched.draining = False
        _cannot_fit(sched, clock)                           # failed
        sched.submit(ServeRequest(uid=8, prompt=[3, 5], max_new_tokens=8))
        sched.step()
        sched.cancel(8)
        sched.step()                                        # cancelled
        t2 = time.perf_counter()
        assert read(t1, t2) == 3.0
        assert read(t0, t1) == 0.0              # none of them before t1
        monkeypatch.setitem(program_trace._LOADED, "ring", False)


def _lowered_text(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def _has_scope(text, scope):
    """``scope`` is a component of some operation's name stack (under a
    transformation it reads ``jvp(scope)`` or ``transpose(jvp(scope))``)."""
    return re.search(r"[/(\"]" + re.escape(scope) + r"[)/]", text) is not None


def _lowered_moe_block(shards):
    """``moe_mlp_block`` over 32 tokens lowered on a ``data=shards`` mesh."""
    from deepspeed_tpu.moe.sharded_moe import moe_mlp_block
    from deepspeed_tpu.runtime import topology

    lp = {"router": {"kernel": jnp.zeros((8, 4))},
          "gate_proj": {"kernel": jnp.zeros((4, 8, 16))},
          "up_proj": {"kernel": jnp.zeros((4, 8, 16))},
          "down_proj": {"kernel": jnp.zeros((4, 16, 8))}}
    topology.initialize_mesh(topology.TopologyConfig(data=shards),
                             devices=jax.devices()[:shards], force=True)
    try:
        return _lowered_text(lambda x: moe_mlp_block(lp, x)[0],
                             jnp.zeros((32, 8)))
    finally:
        topology.reset_topology()


# ---- the train engine ------------------------------------------------------
def _train_engine(zero_stage=0, num_experts=1):
    import deepspeed_tpu
    from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

    topo = initialize_mesh(TopologyConfig(), force=True)
    cfg = TransformerConfig.tiny(use_flash=False, num_experts=num_experts)
    model = CausalLM(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(0)), config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "gradient_clipping": 1.0, "bf16": {"enabled": True},
            "zero_optimization": {"stage": zero_stage}},
        topology=topo)
    tokens = np.arange(engine.train_batch_size() * 16, dtype=np.int32) \
        .reshape(engine.train_batch_size(), 16) % cfg.vocab_size
    return engine, {"input_ids": tokens}


@pytest.fixture(scope="module")
def dense_step():
    engine, batch = _train_engine(zero_stage=2)
    engine.train_batch(batch)
    return engine, batch, engine.compiled_step_text()


class TestTrainSpans:
    def test_three_spans_a_step_without_a_hub(self, tracer):
        engine, batch = _train_engine()
        assert engine.telemetry is None
        engine.train_batch(batch)
        engine.train_batch(batch)
        records = tracer.records()
        steps = [r for r in records if r.name == "engine/train_batch"]
        assert [r.attrs["step"] for r in steps] == [1, 2]
        dispatch = [r for r in records if r.name == "engine/dispatch"]
        assert len(dispatch) == 2 and \
            all(r.parent == "engine/train_batch" for r in dispatch)
        post = [r for r in records if r.name == "engine/post_step"]
        assert len(post) == 2 and all(r.parent is None for r in post)

    @pytest.mark.parametrize("scope", [
        "attention", "mlp", "lm_head", "loss", "optimizer", "optimizer/clip",
        "zero/gather_params", "zero/reduce_grads"])
    def test_dense_step_scopes(self, dense_step, scope):
        engine, batch, _ = dense_step
        state = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), engine.state)
        text = engine._compiled["train_batch"].lower(state, batch).as_text(
            debug_info=True)
        assert _has_scope(text, scope)

    @pytest.mark.parametrize("shards", [1, 4])
    @pytest.mark.parametrize("scope", ["moe/route", "moe/dispatch",
                                       "moe/experts", "moe/combine"])
    def test_moe_scopes(self, scope, shards):
        """The four phases keep their scopes where the block spreads the
        expert slots over the data shards, inside its ``shard_map``."""
        text = _lowered_moe_block(shards)
        assert _has_scope(text, scope)
        assert ("shard_map" in text) == (shards > 1)

    @pytest.mark.parametrize("shards", [1, 4])
    def test_moe_layout_record(self, shards, tracer):
        """Every traced call of the block says which program it became."""
        _lowered_moe_block(shards)
        (layout,) = [r.attrs for r in tracer.records()
                     if r.name == "moe/layout"]
        # 32 tokens, top-2 of 4 experts, capacity factor 2.0: 32 slots an
        # expert = 128 padded rows on one device; a data shard hands the
        # grouped matmul its own 8 x 2 pairs (one tile of 16 rows) where its
        # part of the slots was 32 rows
        assert layout == dict(
            groups=shards, tokens_per_group=32 // shards, capacity=32,
            experts=4, local=shards > 1,
            compute="grouped" if shards > 1 else "padded",
            rows_per_group=16 if shards > 1 else 128,
            padded_rows_per_group=128 // shards)

    def test_compiled_text_gives_scopes(self, dense_step):
        _, _, text = dense_step
        module, scopes = xprof_parse.parse_hlo_scopes(text)
        assert module.startswith("jit_")
        named = {s for s in scopes.values() if s}
        assert any(s.endswith("optimizer") or "/optimizer" in s or
                   s.startswith("optimizer") for s in named)
        assert any("attention" in s.split("/") for s in named)
        assert any("mlp" in s.split("/") for s in named)
        # the registry hands the same table out under the module's name
        assert xprof_parse.registered_scopes()[module] == scopes


class TestKernelNames:
    """Every Pallas call of the main path sits in a scope of its own."""

    @pytest.mark.parametrize("name", ["flash_fwd", "flash_bwd_dq",
                                      "flash_bwd_dkv"])
    def test_flash(self, name):
        from deepspeed_tpu.ops.transformer.flash_attention import \
            flash_attention

        q = jnp.zeros((1, 128, 2, 128), jnp.float32)
        text = _lowered_text(jax.grad(
            lambda q, k, v: flash_attention(q, k, v).sum(), argnums=(0, 1, 2)),
            q, q, q)
        assert _has_scope(text, name)

    def test_rmsnorm_matmul(self):
        from deepspeed_tpu.kernels.fused_collective_matmul import \
            rmsnorm_matmul

        text = _lowered_text(
            lambda x, s, w: rmsnorm_matmul(x, s, w, 1e-5, impl="pallas"),
            jnp.zeros((128, 128)), jnp.ones((128,)), jnp.zeros((128, 256)))
        assert _has_scope(text, "rmsnorm_matmul")

    @pytest.mark.parametrize("name", ["paged_decode", "ragged_prefill"])
    def test_serve_kernels(self, name):
        from deepspeed_tpu.inference.v2.kernels import ragged_ops

        pages = jnp.zeros((8, 8, 4, 128), jnp.float32)
        lens = jnp.array([5, 9], jnp.int32)
        table = jnp.zeros((2, 2), jnp.int32)
        if name == "paged_decode":
            text = _lowered_text(
                lambda q: ragged_ops.decode_paged_attention(
                    q, pages, lens, table, num_kv_heads=2),
                jnp.zeros((2, 4, 128), jnp.float32))
        else:
            text = _lowered_text(
                lambda q: ragged_ops.ragged_paged_attention(
                    q, pages, lens, table, jnp.array([0, 5, 14], jnp.int32),
                    num_kv_heads=2),
                jnp.zeros((14, 4, 128), jnp.float32))
        assert _has_scope(text, name)


class TestScopeOfOpName:
    @pytest.mark.parametrize("op_name,scope", [
        ("jit(step_fn)/jit(main)/optimizer/clip/mul", "optimizer/clip"),
        ("jit(step_fn)/transpose(jvp(layers))/while/body/closed_call/"
         "checkpoint/attention/dot_general",
         "layers/while/body/closed_call/checkpoint/attention"),
        ("jit(step_fn)/jvp(layers)/while/body/closed_call/mlp/moe/dispatch/"
         "scatter-add", "layers/while/body/closed_call/mlp/moe/dispatch"),
        ("jit(f)/attention/jvp()/reduce_sum", "attention"),
        ("jit(step_fn)/transpose(jvp(zero/gather_params))/"
         "convert_element_type", "zero/gather_params"),
        ("concatenate.26", ""),
    ])
    def test_scope(self, op_name, scope):
        assert xprof_parse.scope_of_op_name(op_name) == scope

    def test_fusion_takes_its_body_and_copy_its_operand(self):
        text = """HloModule jit_step_fn, entry_computation_layout={()->f32[]}

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %m = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(step_fn)/optimizer/mul"}
  ROOT %a = f32[4]{0} add(%m, %p), metadata={op_name="jit(step_fn)/optimizer/add"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %copy.2 = f32[4]{0} copy(%fusion.1)
  %ar = f32[4]{0} all-reduce(%copy.2), metadata={op_name="concatenate.26"}
  ROOT %n = f32[4]{0} negate(%x), metadata={op_name="jit(step_fn)/jvp(loss)/neg"}
}
"""
        module, scopes = xprof_parse.parse_hlo_scopes(text)
        assert module == "jit_step_fn"
        assert scopes["fusion.1"] == scopes["copy.2"] == scopes["ar"] \
            == "optimizer"
        assert scopes["n"] == "loss" and scopes["x"] == ""
        ops = [("fusion.1", 0.0, 10.0), ("copy.2", 10.0, 2.0),
               ("n", 12.0, 3.0), ("x", 20.0, 1.0)]
        assert xprof_parse.time_by_scope(ops, scopes) == {
            "optimizer": 12.0, "loss": 3.0, "": 1.0}
