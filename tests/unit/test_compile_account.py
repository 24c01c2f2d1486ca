"""The program's own account of its compiles (ISSUE 36): ``jax.monitoring``
listeners installed by ``configure_compile_cache()`` write one
``compile/trace``, ``compile/lower`` and ``compile/backend`` record a program
into the process-global tracer's ring; ``compile_account()`` reads them as
one row a program; ``engine/init`` spans each engine's construction.  CPU,
no cache on disk."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.telemetry import get_tracer
from deepspeed_tpu.telemetry.trace import DEFAULT_MAX_SPANS
from deepspeed_tpu.utils import compile_cache
from deepspeed_tpu.utils.compile_cache import (compile_account,
                                               configure_compile_cache)

pytestmark = pytest.mark.telemetry

PHASES = ("compile/trace", "compile/lower", "compile/backend")
TRACE, LOWER, BACKEND = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration")
CACHE = "/jax/compilation_cache/"
X = np.ones(8, np.float32)      # no eager program is built to make an input


@pytest.fixture()
def tracer():
    """The process-global tracer, emptied, with the listeners installed the
    way every entry point installs them."""
    tr = get_tracer()
    tr.configure(max_spans=DEFAULT_MAX_SPANS, drop_recorded=True)
    assert configure_compile_cache() is None        # held to the CPU
    yield tr
    tr.enabled = True
    compile_cache.install_compile_listeners()
    tr.configure(max_spans=DEFAULT_MAX_SPANS)


def named(name, fn=lambda x: (x * 2 + 1).sum()):
    def call(x):
        return fn(x)
    call.__name__ = call.__qualname__ = name
    return jax.jit(call)


def recorded(tracer):
    """Records but the collector's pauses (``engine/host_gc``), which come
    whenever Python collects."""
    return sum(1 for r in tracer.records() if r.name != "engine/host_gc")


def of(tracer, program):
    return [r for r in tracer.records() if r.name in PHASES
            and r.attrs["program"] == program]


def test_one_record_a_phase_and_none_on_the_fast_path(tracer):
    step = named("serve_decode_s64x8")
    step(X)
    records = of(tracer, "jit_serve_decode_s64x8")
    assert [r.name for r in records] == list(PHASES)
    assert all(r.parent is None and r.dur_s > 0 for r in records)
    # on the ring's clock, in the order they ran, inside the call
    starts = [r.start_s for r in records]
    assert starts == sorted(starts)
    assert records[2].attrs["cache"] in ("compiled", "off")
    before = recorded(tracer)
    step(X)
    assert recorded(tracer) == before


def test_parent_is_the_span_that_paid(tracer):
    step = named("step_of_two_shapes")
    step(X)
    with tracer.span("engine/x"):
        step(np.ones(16, np.float32))
    records = of(tracer, "jit_step_of_two_shapes")
    assert [r.parent for r in records] == [None] * 3 + ["engine/x"] * 3
    row, = [r for r in compile_account() if
            r["program"] == "jit_step_of_two_shapes"]
    assert row["times"] == 2 and row["parents"] == [None, "engine/x"]
    assert row["trace_s"] == pytest.approx(
        sum(r.dur_s for r in records if r.name == "compile/trace"))
    assert row["first_start_s"] == records[0].start_s
    assert sum(row["cache"].values()) == 2


def test_inner_traces_are_counted_not_written(tracer):
    inner = named("inner_program")
    outer = named("outer_program", lambda x: inner(x) + inner(x * 2))
    outer(X)
    assert not of(tracer, "jit_inner_program")
    trace, lower, backend = of(tracer, "jit_outer_program")
    assert trace.attrs["inner_traces"] >= 1


def test_a_thousand_numpy_calls_are_three_records(tracer):
    fired = []
    listener = lambda event, duration, **kw: fired.append(  # noqa: E731
        event == TRACE)

    def thousand(x):
        for i in range(1000):
            x = jnp.add(x[: 8 - i % 4], 1.0)[0] + jnp.zeros(8)
        return x

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        before = recorded(tracer)
        named("thousand_calls", thousand)(X)
        assert recorded(tracer) == before + 3
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    trace, lower, _ = of(tracer, "jit_thousand_calls")
    # every firing but the outermost trace is inside one of the two phases
    assert trace.attrs["inner_traces"] + lower.attrs["inner_traces"] \
        == sum(fired) - 1
    assert trace.attrs["inner_traces"] >= 8


def _backend_phase(*inside, name="jit(asked_program)"):
    jax.monitoring.record_scalar(BACKEND, time.time(), fun_name=name)
    for event, seconds in inside:
        if seconds is None:
            jax.monitoring.record_event(CACHE + event)
        else:
            jax.monitoring.record_event_duration_secs(CACHE + event, seconds)
    jax.monitoring.record_event_duration_secs(BACKEND, 0.5, fun_name=name)


@pytest.mark.parametrize("inside,want", [
    ((), {"cache": "off"}),
    ((("compile_requests_use_cache", None),), {"cache": "compiled"}),
    ((("compile_requests_use_cache", None), ("cache_misses", None)),
     {"cache": "written"}),
    ((("compile_requests_use_cache", None), ("cache_hits", None),
      ("compile_time_saved_sec", 3.0), ("cache_retrieval_time_sec", 0.25)),
     {"cache": "hit", "saved_s": 3.0, "retrieval_s": 0.25}),
])
def test_the_caches_answer(tracer, inside, want):
    now = time.perf_counter()
    _backend_phase(*inside)
    record, = of(tracer, "jit_asked_program")
    assert record.attrs == dict(want, program="jit_asked_program")
    assert record.dur_s == 0.5
    # the start is the exit less the duration, on the ring's clock
    assert tracer.epoch + record.start_s == pytest.approx(now - 0.5, abs=0.1)
    # an answer outside a backend phase belongs to no program
    jax.monitoring.record_event(CACHE + "cache_hits")
    _backend_phase()
    assert of(tracer, "jit_asked_program")[1].attrs["cache"] == "off"
    answers = compile_account()[0]["cache"]
    assert sum(answers.values()) == 2 and answers[want["cache"]] >= 1 \
        and answers["off"] >= 1


@pytest.mark.parametrize("fun_name,program", [
    ("serve_decode_s64x8", "jit_serve_decode_s64x8"),
    ("jit(serve_decode_s64x8)", "jit_serve_decode_s64x8"),
    ("<lambda>", "jit__lambda"), ("jit(<lambda>)", "jit__lambda"),
    ("pmap(step)", "pmap_step"), ("jit(_where)", "jit__where"),
])
def test_program_is_the_modules_name(tracer, fun_name, program):
    jax.monitoring.record_scalar(LOWER, time.time(), fun_name=fun_name)
    jax.monitoring.record_event_duration_secs(LOWER, 0.1, fun_name=fun_name)
    assert [r.name for r in of(tracer, program)] == ["compile/lower"]


def test_the_modules_name_is_what_xla_is_given(tracer):
    step = named("serve_prefill_t16")
    assert "module @jit_serve_prefill_t16 " in step.lower(X).as_text()
    assert of(tracer, "jit_serve_prefill_t16")


def test_install_twice_is_one_record_a_phase(tracer):
    compile_cache.install_compile_listeners()
    configure_compile_cache()
    named("installed_twice")(X)
    assert [r.name for r in of(tracer, "jit_installed_twice")] == list(PHASES)


def test_removed_listeners_and_a_disabled_tracer_record_nothing(tracer):
    compile_cache.remove_compile_listeners()
    compile_cache.remove_compile_listeners()        # twice is fine too
    named("nobody_listens")(X)
    compile_cache.install_compile_listeners()
    tracer.enabled = False
    named("tracer_is_off")(X)
    tracer.enabled = True
    assert not of(tracer, "jit_nobody_listens")
    assert not of(tracer, "jit_tracer_is_off")
    named("listening_again")(X)
    assert len(of(tracer, "jit_listening_again")) == 3


def test_a_trace_that_raises_leaves_the_depth_balanced(tracer):
    def bad(x):
        raise ValueError("while tracing")

    with pytest.raises(ValueError):
        named("raises_in_trace", bad)(X)
    assert [r.name for r in of(tracer, "jit_raises_in_trace")] \
        == ["compile/trace"]
    named("after_the_raise")(X)
    assert len(of(tracer, "jit_after_the_raise")) == 3


def test_a_phase_opened_before_the_listeners_is_dropped(tracer):
    jax.monitoring.record_event_duration_secs(TRACE, 0.1, fun_name="late")
    assert not of(tracer, "jit_late")
    named("after_the_late_one")(X)
    assert len(of(tracer, "jit_after_the_late_one")) == 3


def test_depth_is_per_thread(tracer):
    """A thread that compiles while another is inside a trace writes its
    own records: the other's open phase does not make it inner."""
    done = []

    def other():
        named("on_another_thread")(X)
        done.append(threading.get_ident())

    def outer(x):
        worker = threading.Thread(target=other)
        worker.start()
        worker.join(timeout=60)
        return x + 1

    named("holds_a_trace_open", outer)(X)
    assert done
    records = of(tracer, "jit_on_another_thread")
    assert [r.name for r in records] == list(PHASES)
    assert {r.tid for r in records} == set(done)
    assert of(tracer, "jit_holds_a_trace_open")[0].attrs["inner_traces"] >= 1


def test_account_of_given_records_keeps_no_state(tracer):
    named("first_program")(X)
    named("second_program")(X)
    rows = compile_account()
    assert [r["program"] for r in rows] == ["jit_first_program",
                                            "jit_second_program"]
    assert set(rows[0]) == {"program", "trace_s", "inner_traces", "lower_s",
                            "backend_s", "cache", "times", "first_start_s",
                            "parents"}
    only = compile_account(of(tracer, "jit_second_program"))
    assert [r["program"] for r in only] == ["jit_second_program"]
    tracer.clear()
    assert compile_account() == []
    assert compile_account([]) == []


# ---- the engines -----------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_lm():
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    model = CausalLM(TransformerConfig.tiny(use_flash=False))
    return model, model.init_params(jax.random.PRNGKey(0))


@pytest.mark.serving
def test_a_decode_width_not_yet_seen_names_the_call_that_paid(tiny_lm,
                                                              tracer):
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.lifecycle import (LifecycleScheduler,
                                                      ServeRequest)

    model, params = tiny_lm
    engine = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
        max_tokens=16, max_seqs=4, max_ctx=64, block_size=8,
        dtype=jnp.float32, attn_impl="gather"))
    init, = [r for r in tracer.records() if r.name == "engine/init"]
    assert init.parent is None
    sched = LifecycleScheduler(engine, window_steps=4)

    def serve(uids):
        for uid in uids:
            assert sched.submit(ServeRequest(
                uid=uid, prompt=[3 + uid, 5, 7, 11],
                max_new_tokens=6)).admitted
        sched.run_until_idle()

    serve(range(3))                 # 4-wide windows
    tracer.clear()
    serve([7])                      # 1 wide: a program the engine lacks
    records = tracer.records()
    paid = [r for r in records if r.name == "compile/backend"
            and r.attrs["program"].startswith("jit_serve_decode_s1x")]
    assert paid and all(r.parent.startswith("engine/") for r in paid)
    steps = [r for r in records if r.name == "serve/step"]
    for r in paid:
        assert any(s.tid == r.tid and s.start_s <= r.start_s and r.start_s
                   + r.dur_s <= s.start_s + s.dur_s for s in steps)
    rows = {r["program"]: r for r in compile_account()}
    for r in paid:                  # one program a window length
        row = rows[r.attrs["program"]]
        assert row["times"] == 1 and row["trace_s"] > 0
        assert row["parents"] == [r.parent]
    # and a width it has: nothing compiles
    tracer.clear()
    serve([8])
    assert not [r for r in tracer.records() if r.name == "compile/backend"
                and r.attrs["program"].startswith("jit_serve_")]


def test_engine_init_spans_the_train_engines_construction(tiny_lm, tracer):
    import deepspeed_tpu
    from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

    model, params = tiny_lm
    deepspeed_tpu.initialize(
        model=model, model_parameters=params, config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}},
        topology=initialize_mesh(TopologyConfig(), force=True))
    records = tracer.records()
    init, = [r for r in records if r.name == "engine/init"]
    under = [r for r in records if r.parent == "engine/init"]
    # the optimizer state's jitted init compiles inside it
    assert any(r.name == "compile/backend" for r in under)
    assert all(init.start_s <= r.start_s and r.start_s + r.dur_s
               <= init.start_s + init.dur_s + 1e-6 for r in under)
