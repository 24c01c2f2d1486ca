"""The step account (ISSUE 58): host facts on the two step spans, the
collector as a span, the train step's device wait, why a decode window found
nothing in flight (``held_by``), and the benchmark's reader that finds the
one stalled step of a run in the ring.  CPU, toy widths."""
import gc
import json
import os
import sys
import threading
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
from deepspeed_tpu.runtime.fault import injection
from deepspeed_tpu.telemetry import get_tracer
from deepspeed_tpu.telemetry import trace as trace_mod
from deepspeed_tpu.telemetry.trace import DEFAULT_MAX_SPANS

pytestmark = pytest.mark.serving

HOST_FACTS = {"cpu_s", "nvcsw", "nivcsw"}
REASONS = ["dropped", "drafter", "draining", "queued", "prefilling", "cancel",
           "free_row", "rotation", "chains", "finisher", "ctx_cap",
           "deadline"]


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


def _scheduler(tiny_lm, **kw):
    model, params = tiny_lm
    eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
        max_tokens=16, max_seqs=4, max_ctx=64, block_size=8,
        dtype=jnp.float32, attn_impl="gather"))
    return LifecycleScheduler(eng, window_steps=4, **kw)


def _submit(sched, uids, new=6, prompt=4):
    for uid in uids:
        assert sched.submit(ServeRequest(
            uid=uid, prompt=[3 + uid % 7, 5, 7, 11, 13, 17][:prompt],
            max_new_tokens=new)).admitted


def _windows(tracer):
    return [r.attrs for r in tracer.records() if r.name == "serve/window"]


def _tokens(sched, uids):
    return {u: list(sched.request(u).produced) for u in uids}


def _train_steps(n=2):
    import deepspeed_tpu
    from deepspeed_tpu.runtime.topology import TopologyConfig, initialize_mesh

    topo = initialize_mesh(TopologyConfig(), force=True)
    cfg = TransformerConfig.tiny(use_flash=False)
    model = CausalLM(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(0)), config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True}}, topology=topo)
    tokens = np.arange(engine.train_batch_size() * 16, dtype=np.int32) \
        .reshape(engine.train_batch_size(), 16) % cfg.vocab_size
    for _ in range(n):
        engine.train_batch({"input_ids": tokens})


# ---- the tracer ------------------------------------------------------------
class TestHostFacts:
    def test_only_the_two_step_spans_carry_them(self, tiny_lm, tracer):
        sched = _scheduler(tiny_lm)
        _submit(sched, range(3))
        sched.run_until_idle()
        _train_steps()
        records = tracer.records()
        assert {"serve/window", "engine/put", "engine/dispatch",
                "engine/post_step"} <= {r.name for r in records}
        for r in records:
            carried = HOST_FACTS & set(r.attrs or {})
            if r.name in ("serve/step", "engine/train_batch"):
                assert carried == HOST_FACTS, r.name
                assert 0.0 <= r.attrs["cpu_s"] <= r.dur_s + 1e-3
                assert r.attrs["nvcsw"] >= 0 and r.attrs["nivcsw"] >= 0
            else:
                assert not carried, r.name
        steps = [r for r in records if r.name == "engine/train_batch"]
        assert [r.attrs["step"] for r in steps] == [1, 2]

    def test_one_system_call_an_edge_and_none_for_any_other_span(
            self, monkeypatch):
        calls = []
        getrusage = trace_mod.resource.getrusage

        def counted(who):
            calls.append(who)
            return getrusage(who)

        def no_second_call():
            raise AssertionError("the CPU time comes from the same call")

        monkeypatch.setattr(trace_mod.resource, "getrusage", counted)
        monkeypatch.setattr(time, "thread_time", no_second_call)
        tr = trace_mod.Tracer()
        with tr.span("engine/dispatch"):
            tr.record("serve/queue_wait", time.perf_counter(), 0.0)
        assert calls == []
        with tr.step_span(name="serve/step"):
            assert calls == [trace_mod.resource.RUSAGE_THREAD]
            with tr.span("serve/window"):
                pass
        assert calls == [trace_mod.resource.RUSAGE_THREAD] * 2
        with tr.step_span(7, name="engine/train_batch"):
            pass
        assert len(calls) == 4
        assert HOST_FACTS <= set(tr.records()[-1].attrs)

    def test_cpu_time_tells_a_sleep_from_a_spin(self):
        tr = trace_mod.Tracer()
        with tr.step_span(name="asleep"):
            time.sleep(0.05)
        with tr.step_span(name="awake"):
            until = time.thread_time() + 0.05   # of CPU: other workers
            while time.thread_time() < until:   # share these cores
                pass
        asleep, awake = tr.records()
        assert asleep.attrs["cpu_s"] < 0.02 <= asleep.dur_s
        assert asleep.attrs["nvcsw"] >= 1          # it gave the core up
        assert 0.045 <= awake.attrs["cpu_s"] <= awake.dur_s + 1e-3

    def test_no_attribute_and_no_error_without_thread_usage(
            self, monkeypatch):
        monkeypatch.setattr(trace_mod, "_RUSAGE_THREAD", None)   # not Linux
        tr = trace_mod.Tracer()
        with tr.step_span(3, name="engine/train_batch"):
            pass
        (rec,) = tr.records()
        assert rec.attrs == {"step": 3}


class TestCollectorSpan:
    def test_a_forced_collection_inside_a_step(self, tiny_lm, tracer,
                                               monkeypatch):
        sched = _scheduler(tiny_lm)
        _submit(sched, [0])
        settle, fired = sched._settle, []

        def settle_and_collect():       # directly under ``serve/step``
            if not fired:
                fired.append(gc.collect())
            return settle()

        monkeypatch.setattr(sched, "_settle", settle_and_collect)
        gc.disable()                    # no collection but the forced one
        try:
            sched.step()
        finally:
            gc.enable()
        (pause,) = [r for r in tracer.records() if r.name == "engine/host_gc"]
        assert pause.attrs["generation"] == 2
        assert pause.attrs["collected"] == fired[0]
        assert pause.parent == "serve/step" and pause.depth == 1
        (step,) = [r for r in tracer.records() if r.name == "serve/step"]
        assert step.start_s <= pause.start_s and \
            pause.start_s + pause.dur_s <= step.start_s + step.dur_s

    def test_a_collection_under_the_tracers_own_lock(self, tracer):
        """Python 3.12 runs the collector between any two bytecodes,
        ``Tracer._record``'s included: the hook must get through the lock
        its own thread holds, and its record must be kept."""
        done = []

        def collect_under_the_lock():
            with tracer._lock:
                gc.collect()
            done.append(True)

        worker = threading.Thread(target=collect_under_the_lock, daemon=True)
        worker.start()
        worker.join(timeout=20)
        assert done and not worker.is_alive(), "the hook deadlocked"
        pauses = [r for r in tracer.records() if r.name == "engine/host_gc"
                  and r.tid == worker.ident]
        assert [r.attrs["generation"] for r in pauses] == [2]

    def test_collections_at_every_allocation_from_two_threads(self, tracer):
        """A threshold of 1 while two threads record spans: no deadlock, no
        record lost, every pause closed."""
        n, errs = 300, []

        def work(tag):
            try:
                for i in range(n):
                    with tracer.span(f"probe/{tag}", i=i):
                        [[] for _ in range(3)]      # tracked allocations
            except Exception as e:  # noqa: BLE001 — surfaced below
                errs.append(e)

        before = gc.get_threshold()
        switch = sys.getswitchinterval()
        threads = [threading.Thread(target=work, args=(t,), daemon=True)
                   for t in "ab"]
        gc.set_threshold(1, 1, 1)
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            gc.set_threshold(*before)
            sys.setswitchinterval(switch)
        assert not errs and not any(t.is_alive() for t in threads)
        records = tracer.records()
        for tag in "ab":
            assert sorted(r.attrs["i"] for r in records
                          if r.name == f"probe/{tag}") == list(range(n))
        pauses = [r for r in records if r.name == "engine/host_gc"]
        assert pauses and all("collected" in r.attrs for r in pauses)
        assert trace_mod._GC_SPAN is trace_mod.NULL_SPAN

    def test_a_disabled_tracer_records_no_pause(self, tracer):
        tracer.enabled = False
        try:
            gc.collect()
        finally:
            tracer.enabled = True
        assert not tracer.records()


class TestTrainStepWait:
    def test_one_a_step_inside_the_step_after_the_dispatch(self, tracer):
        _train_steps(3)
        records = tracer.records()
        steps = [r for r in records if r.name == "engine/train_batch"]
        waits = [r for r in records if r.name == "engine/step_wait"]
        dispatches = [r for r in records if r.name == "engine/dispatch"]
        assert len(steps) == len(waits) == len(dispatches) == 3
        for step, dispatch, wait in zip(steps, dispatches, waits):
            assert wait.parent == dispatch.parent == "engine/train_batch"
            assert wait.depth == dispatch.depth == step.depth + 1
            assert step.start_s <= dispatch.start_s
            assert dispatch.start_s + dispatch.dur_s <= wait.start_s
            assert wait.start_s + wait.dur_s <= step.start_s + step.dur_s


# ---- why a window found nothing in flight ----------------------------------
def _drained_schedule(tiny_lm, drive):
    """The tokens of the same traffic under a scheduler that drains every
    window (the parent's schedule before windows ran ahead)."""
    sched = _scheduler(tiny_lm)
    sched._may_run_ahead = lambda: False
    return drive(sched)


class TestHeldBy:
    def test_a_full_batch_runs_ahead_until_its_finishers(self, tiny_lm,
                                                         tracer):
        def drive(sched):
            _submit(sched, range(3), new=22)
            _submit(sched, [3], new=10)
            sched.run_until_idle()
            return _tokens(sched, range(4))

        tokens = drive(_scheduler(tiny_lm))
        held = [(w["ahead"], w["held_by"]) for w in _windows(tracer)]
        # the short answer has 9 tokens to go after the prefill's: windows
        # of 4, 4 and 1 steps, the last two ahead; then its row is free
        assert held[:4] == [(0, "first"), (1, ""), (1, ""), (0, "finisher")]
        assert held[4:] and set(held[4:]) == {(0, "free_row")}
        assert [w["steps"] for w in _windows(tracer)][:3] == [4, 4, 1]
        assert tokens == _drained_schedule(tiny_lm, drive)

    def test_a_free_row_holds_every_window(self, tiny_lm, tracer):
        def drive(sched):
            _submit(sched, range(3), new=10)
            sched.run_until_idle()
            return _tokens(sched, range(3))

        tokens = drive(_scheduler(tiny_lm))
        held = [(w["ahead"], w["held_by"]) for w in _windows(tracer)]
        assert held[0] == (0, "first")
        assert held[1:] and set(held[1:]) == {(0, "free_row")}
        assert tokens == _drained_schedule(tiny_lm, drive)

    def test_an_arrival_at_a_full_batch_is_the_root_of_its_boundary(
            self, tiny_lm, tracer):
        """The queued request drains the window in flight; its prefill step
        follows (nothing in flight: no second reason), and the next window
        says ``queued``.  A first window after an idle scheduler says
        ``first`` again."""
        def drive(sched):
            _submit(sched, range(4), new=30)
            for _ in range(3):
                sched.step()            # prefill, window, window ahead
            _submit(sched, [9], new=3)
            sched.run_until_idle()
            _submit(sched, [10], new=3)     # after the scheduler went idle
            sched.run_until_idle()
            return _tokens(sched, list(range(4)) + [9, 10])

        sched = _scheduler(tiny_lm)
        tokens = drive(sched)
        held = [w["held_by"] for w in _windows(tracer)]
        assert held[:3] == ["first", "", "queued"]
        assert held[-1] == "first"
        assert "first" not in held[1:-1]
        assert all((w["held_by"] == "") == (w["ahead"] == 1)
                   for w in _windows(tracer))
        assert tokens == _drained_schedule(tiny_lm, drive)

    @pytest.mark.parametrize("reason", REASONS)
    def test_the_first_condition_that_holds_is_named(self, tiny_lm, tracer,
                                                     reason):
        """Each condition of ``_may_run_ahead`` alone, then with every later
        one as well: the first in the order of the tests is the one said.
        (``prefilling`` cannot be driven into — a prefill in flight always
        fills the step's batch, so no window goes out beside it — it is
        reached here as the others are, by the state it tests.)"""
        def hold(sched, why):
            fl, reqs = sched._inflight, sched._reqs
            if why == "dropped":
                fl.dropped.add(fl.uids[0])
            elif why == "drafter":
                sched.drafter = object()
            elif why == "draining":
                sched.draining = True
            elif why == "queued":
                sched._waiting.append(99)
            elif why == "prefilling":
                sched._prefilling[98] = None
            elif why == "cancel":
                sched._cancel_requested.add(fl.uids[1])
            elif why == "free_row":
                sched._free_slots = lambda: 1
            elif why == "rotation":
                sched._decodes.move_to_end(fl.uids[0])
            elif why == "chains":
                sched.eng.decode_chains = lambda uids: False
            elif why == "finisher":
                reqs[fl.uids[0]].max_new_tokens = \
                    len(reqs[fl.uids[0]].produced) + fl.steps
            elif why == "ctx_cap":
                sched.eng.config.max_ctx = 8
            elif why == "deadline":
                reqs[fl.uids[0]].deadline_t = sched.clock() - 1.0

        def in_flight():
            sched = _scheduler(tiny_lm)
            _submit(sched, range(4), new=30)
            sched.step()
            sched.step()
            assert sched._inflight is not None and sched._may_run_ahead()
            assert sched._held == ""
            return sched

        sched = in_flight()
        hold(sched, reason)
        assert not sched._may_run_ahead() and sched._held == reason
        sched = in_flight()
        for later in reversed(REASONS[REASONS.index(reason):]):
            hold(sched, later)
        assert not sched._may_run_ahead() and sched._held == reason


# ---- the benchmark's reader -------------------------------------------------
@pytest.fixture()
def bench(monkeypatch, tmp_path):
    """The benchmark's ``lib`` on the path, its checkout's root in
    ``tmp_path`` and a command line that names a cell."""
    here = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark")
    monkeypatch.syspath_prepend(os.path.abspath(here))
    for name in [m for m in sys.modules if m == "lib"
                 or m.startswith("lib.")]:
        monkeypatch.delitem(sys.modules, name)
    from lib import manifest, program_trace

    monkeypatch.setattr(program_trace, "ROOT", str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "a-cell"])

    def read(metric, lo, hi):
        monkeypatch.setitem(program_trace._LOADED, "ring", False)
        spec = manifest.metric_of(metric)
        reader = manifest.load_module("readers", spec["reader"])
        return reader.read({"window": (lo, hi)}, spec["args"])

    yield read
    monkeypatch.setitem(program_trace._LOADED, "ring", False)


class TestTheReaderFindsTheStall:
    DELAY = 0.3

    def _round(self, sched, first_uid, slow_window=None):
        """Four requests to the end; ``slow_window``: the index (from this
        round's first) of the one window that sleeps at its launch."""
        if slow_window is not None:
            at = sched.eng.decode_windows_dispatched + slow_window
            injection.configure(f"site=decode_window,kind=slow,"
                                f"delay={self.DELAY},steps={at}")
        t0 = time.perf_counter()
        try:
            _submit(sched, range(first_uid, first_uid + 4), new=40)
            sched.run_until_idle()
        finally:
            injection.clear()
        return t0, time.perf_counter()

    def test_a_sleep_in_one_window(self, tiny_lm, tracer, bench, tmp_path):
        sched = _scheduler(tiny_lm)
        self._round(sched, 0)                       # every shape compiles
        for attempt in range(3):    # the quietest: other workers share
            lo, hi = self._round(sched, 10 * (attempt + 1),     # the cores
                                 slow_window=5)
            lost = bench("stall_s.decode", lo, hi)
            if abs(lost - self.DELAY) <= 0.10 * self.DELAY:
                break
        assert lost == pytest.approx(self.DELAY, rel=0.10)
        assert bench("stall_wait_s.decode", lo, hi) == 0.0
        assert bench("stall_client_s.decode", lo, hi) == 0.0
        with open(tmp_path / ".bench_trace" / "a-cell" /
                  "stall_account.json") as f:
            account = json.load(f)
        (entry,) = account["stalls"]
        assert entry["what"] == "step"
        assert entry["owner"] == "engine/decode_launch"
        assert entry["class"] == "decode steps=4 drained=4"
        assert entry["steps"] == 4 and entry["key"] == "4x4"
        assert entry["excess_s"] == pytest.approx(lost)
        assert entry["dur_s"] >= self.DELAY > entry["median_s"] * 3
        assert 0.0 <= entry["offset_s"] <= hi - lo
        # asleep, not running: the thread gave the core up and used none
        assert entry["cpu_s"] < self.DELAY / 2 and entry["nvcsw"] >= 1
        assert entry["host_gc_s"] >= 0.0 and entry["compiles"] == []
        assert account["classes"][entry["class"]]["n"] >= 3
        # the round's one prefill step has no class to be judged by
        (alone,) = [c for c in account["classes"].values()
                    if c["judged_by"] is None]
        assert account["unjudged"] == alone["n"] == 1

    def test_the_same_run_without_the_sleep_reads_zero(self, tiny_lm, tracer,
                                                       bench, tmp_path):
        sched = _scheduler(tiny_lm)
        self._round(sched, 0)
        lost = []
        for attempt in range(3):    # the quietest: other workers share
            lo, hi = self._round(sched, 10 * (attempt + 1))     # the cores
            lost.append(bench("stall_s.decode", lo, hi))
            if lost[-1] == 0.0:
                break
        assert lost[-1] == 0.0, lost
        with open(tmp_path / ".bench_trace" / "a-cell" /
                  "stall_account.json") as f:
            account = json.load(f)
        assert account["stalls"] == [] and account["steps"] > 10
        shares = [bench(f"window_held_share.{r}", lo, hi)
                  for r in ("finisher", "free_row", "queued")]
        # four equal answers: every window but the first goes out ahead
        ahead = bench("window_ahead_share.decode", lo, hi)
        assert shares == [0.0, 0.0, 0.0] and 0.5 < ahead < 1.0
        assert bench("gc_ms_in_window.decode", lo, hi) >= 0.0
