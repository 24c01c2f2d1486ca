"""Fleet-wide request tracing (marker: tracing): traceparent context
mint/parse/propagation, the span store's tail-based sampling and merge
dedupe, end-to-end merged waterfalls (disaggregated prefill ≥90% wall
coverage, kill-mid-run reroute showing BOTH replicas, preempt/resume,
speculative draft/verify), incident events naming the victim request's
trace id, the /traces live endpoint, the dstpu-trace CLI, the
dstpu-telemetry tracing section with TTFT exemplar links, and the
host-sync cleanliness of the trace bookkeeping in the decode hot path.
"""
import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (
    InferenceEngineV2,
    RaggedInferenceEngineConfig,
)
from deepspeed_tpu.inference.v2.lifecycle import (
    LifecycleScheduler,
    RequestState,
    ServeRequest,
)
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.telemetry.tracing import (
    RequestTraceStore,
    TraceContext,
    get_trace_store,
    install_trace_store,
    span_coverage,
)

pytestmark = pytest.mark.tracing


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = TransformerConfig.tiny(use_flash=False)
    model = CausalLM(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def _engine(tiny_lm, **kw):
    model, params = tiny_lm
    defaults = dict(max_tokens=32, max_seqs=4, max_ctx=64, block_size=8,
                    dtype=jnp.float32, attn_impl="gather")
    defaults.update(kw)
    return InferenceEngineV2(model, params,
                             RaggedInferenceEngineConfig(**defaults))


@pytest.fixture(scope="module")
def shared_eng(tiny_lm):
    """One engine shared by the scheduler-level tests — compiles once."""
    return _engine(tiny_lm)


@pytest.fixture(autouse=True)
def fresh_store():
    """Every test gets a clean process-global store (sample_every=1 so
    assertions never race the sampling counter); always uninstalled after
    so other suites see tracing disabled."""
    store = RequestTraceStore(sample_every=1)
    install_trace_store(store)
    yield store
    install_trace_store(None)


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# --------------------------------------------------------------------- #
# Context wire format
# --------------------------------------------------------------------- #
class TestTraceContext:
    def test_mint_parse_roundtrip(self):
        c = TraceContext.mint()
        h = c.header()
        assert h.startswith("00-") and len(h) == 55
        assert TraceContext.parse(h) == c

    def test_parse_rejects_malformed(self):
        for bad in (None, "", "garbage", "00-short-xy-01",
                    "99-" + "a" * 32 + "-" + "b" * 16 + "-01"):
            assert TraceContext.parse(bad) is None

    def test_child_keeps_trace_id_fresh_span_id(self):
        c = TraceContext.mint()
        k = c.child()
        assert k.trace_id == c.trace_id and k.span_id != c.span_id

    def test_from_request_header_wins_over_body(self):
        a, b = TraceContext.mint(), TraceContext.mint()
        got = TraceContext.from_request({"traceparent": a.header()},
                                        {"traceparent": b.header()})
        assert got.trace_id == a.trace_id
        got = TraceContext.from_request({}, {"traceparent": b.header()})
        assert got.trace_id == b.trace_id
        # nothing carried → fresh mint, sampled by default
        got = TraceContext.from_request({}, {})
        assert got.trace_id not in (a.trace_id, b.trace_id)
        assert got.sampled


# --------------------------------------------------------------------- #
# Store: tail sampling, merge dedupe, exemplars
# --------------------------------------------------------------------- #
class TestStoreSampling:
    def test_steady_state_sampled_one_in_n(self):
        s = RequestTraceStore(sample_every=5)
        kept = 0
        for i in range(20):
            tid = f"{i:032x}"
            s.add_span(tid, "decode_window", t0=time.time(), dur_s=0.001)
            kept += 1 if s.finish(tid, wall_s=0.01)["kept"] else 0
        assert kept == 4                      # 1-in-5 of 20
        assert s.counters["trace/dropped"] == 16

    def test_flagged_always_kept(self):
        s = RequestTraceStore(sample_every=1000)
        for i, flag in enumerate(("shed", "preempted", "rerouted",
                                  "nan_isolated", "deadline_expired")):
            tid = f"f{i:031x}"
            s.add_span(tid, "queue_wait", t0=0.0, dur_s=0.0)
            rec = s.finish(tid, flag=flag, wall_s=0.01)
            if i == 0:
                assert rec["kept"]            # seq 0 sampled anyway
            else:
                assert rec["kept"] and rec["flags"] == [flag]
        assert s.counters["trace/flagged"] == 5

    def test_exemplar_holder_kept_and_bounded(self):
        s = RequestTraceStore(sample_every=1000, exemplar_k=2)
        s.finish("0" * 32, wall_s=0.01)       # seq 0: burn the free keep
        for i in range(1, 4):
            tid = f"{i:032x}"
            assert s.note_exemplar("ttft_s", float(i), tid)
            # a current exemplar holder is always kept → the link resolves
            assert s.finish(tid, wall_s=0.01)["kept"]
        # set is [3, 2]: a smaller offer is rejected and its trace
        # follows normal sampling (here: dropped)
        tid = f"{9:032x}"
        assert not s.note_exemplar("ttft_s", 1.5, tid)
        assert not s.finish(tid, wall_s=0.01)["kept"]
        ex = s.exemplars()["ttft_s"]
        assert [e["value"] for e in ex] == [3.0, 2.0]

    def test_slow_cohort_kept(self):
        s = RequestTraceStore(sample_every=10**6, slow_min_samples=10,
                              slow_quantile=0.9)
        for i in range(1, 40):
            tid = f"{i:032x}"
            wall = 10.0 if i == 30 else 0.01  # one outlier past the p90
            rec = s.finish(tid, wall_s=wall)
            if i == 30:
                assert rec["kept"]

    def test_merge_dedupes_by_sid_and_carries_flags(self):
        a, b = RequestTraceStore(), RequestTraceStore()
        tid = "a" * 32
        a.add_span(tid, "prefill", t0=1.0, dur_s=0.5, component="serve:1")
        payload = a.finish(tid, flag="rerouted", wall_s=1.0)
        assert b.merge(tid, payload) == 1
        assert b.merge(tid, payload) == 0     # idempotent re-merge
        rec = b.finish(tid, wall_s=2.0)
        assert rec["kept"] and "rerouted" in rec["flags"]
        assert len(rec["spans"]) == 1

    def test_drop_then_keep_upgrade_restores_spans(self, tmp_path):
        # shared in-process store, sample_every > 1: the replica's finish
        # samples the trace OUT (spans cleared, sids tombstoned); the
        # router then merges the in-band copy and flags it.  The upgrade
        # must restore the spans (without re-counting aggregates), move
        # the kept/dropped counters, and re-emit the newest jsonl line
        # with the full end-to-end record.
        s = RequestTraceStore(sample_every=1000,
                              jsonl_path=str(tmp_path / "traces.jsonl"))
        s.finish("0" * 32)                    # burn the 1-in-N keep slot
        tid = "a" * 32
        s.add_span(tid, "prefill", t0=1.0, dur_s=1.0, component="serve:1")
        rep = s.finish(tid, wall_s=1.0)       # replica hop: sampled out
        assert s.get(tid) is None
        assert s.merge(tid, {"spans": rep["spans"],
                             "flags": rep["flags"]}) == 1
        s.add_span(tid, "route", t0=0.5, dur_s=2.0, component="router")
        s.flag(tid, "rerouted")
        s.finish(tid, wall_s=2.0)             # router hop: keep-upgrade
        assert sorted(sp["kind"] for sp in s.get(tid)["spans"]) \
            == ["prefill", "route"]
        assert s.counters["trace/dropped"] == 0
        assert s.counters["trace/kept"] == 2
        assert s.segment_summary()["prefill"]["count"] == 1
        s.flush()
        from deepspeed_tpu.telemetry.tracing.cli import load_traces

        (rec,) = [r for r in load_traces(str(tmp_path))
                  if r["trace"] == tid]
        assert sorted(sp["kind"] for sp in rec["spans"]) \
            == ["prefill", "route"]
        assert rec["wall_s"] == 2.0

    def test_ring_bounded(self):
        s = RequestTraceStore(sample_every=1, max_traces=8)
        for i in range(50):
            tid = f"{i:032x}"
            s.add_span(tid, "route", t0=0.0, dur_s=0.0)
            s.finish(tid, wall_s=0.01)
        assert len(s.traces()) <= 8
        assert s.counters["trace/evicted"] >= 42


# --------------------------------------------------------------------- #
# Scheduler span production (one shared engine)
# --------------------------------------------------------------------- #
class TestSchedulerSpans:
    def test_full_lifecycle_span_kinds(self, shared_eng, fresh_store):
        s = LifecycleScheduler(shared_eng, window_steps=4)
        ctx = TraceContext.mint()
        t0 = time.time()
        s.submit(ServeRequest(uid=1, prompt=[4, 6, 8], max_new_tokens=12,
                              trace=ctx))
        s.run_until_idle()
        t1 = time.time()
        rec = s.request(1).trace_result
        assert rec is not None and rec["kept"]
        kinds = {sp["kind"] for sp in rec["spans"]}
        assert {"queue_wait", "admission", "prefill"} <= kinds
        assert "decode_window" in kinds or "compile" in kinds
        # every span names this scheduler's component and the uid
        assert {sp["component"] for sp in rec["spans"]} == {"serve"}
        assert {sp["uid"] for sp in rec["spans"]} == {1}
        # the typed segments account for (nearly all of) the request wall
        assert span_coverage(rec["spans"], t0, t1) >= 0.8
        assert fresh_store.segment_summary()["prefill"]["count"] >= 1

    def test_untraced_request_records_nothing(self, shared_eng,
                                              fresh_store):
        s = LifecycleScheduler(shared_eng, window_steps=4)
        s.submit(ServeRequest(uid=2, prompt=[4, 6], max_new_tokens=4))
        s.run_until_idle()
        assert s.request(2).trace_result is None
        assert fresh_store.counters.get("trace/started", 0) == 0

    def test_expiry_incident_names_trace_and_flags(self, shared_eng,
                                                   tmp_path):
        from deepspeed_tpu.telemetry import Telemetry, set_telemetry

        tel = Telemetry(output_dir=str(tmp_path / "tel"))
        set_telemetry(tel)
        try:
            clock = FakeClock()
            s = LifecycleScheduler(shared_eng, clock=clock)
            ctx = TraceContext.mint()
            s.submit(ServeRequest(uid=3, prompt=[3, 5], max_new_tokens=4,
                                  deadline_s=2.0, trace=ctx))
            clock.advance(5.0)
            s.step()
            assert s.request(3).state == RequestState.EXPIRED
            events = tel.events.recent(kind="serving_expired")
            assert events and events[-1]["trace"] == ctx.trace_id
            rec = s.request(3).trace_result
            assert rec["kept"] and "deadline_expired" in rec["flags"]
        finally:
            set_telemetry(None)
            tel.close()

    def test_speculative_stream_has_draft_and_verify_spans(
            self, shared_eng):
        from deepspeed_tpu.inference.v2.speculative import (
            NGramDrafter,
            SpeculativeConfig,
        )

        s = LifecycleScheduler(
            shared_eng, window_steps=4,
            speculative=SpeculativeConfig(mode="ngram", k=4),
            drafter=NGramDrafter())
        ctx = TraceContext.mint()
        s.submit(ServeRequest(uid=4, prompt=[142] * 6, max_new_tokens=10,
                              trace=ctx))
        s.run_until_idle()
        rec = s.request(4).trace_result
        kinds = {sp["kind"] for sp in rec["spans"]}
        assert "draft" in kinds
        assert "verify" in kinds or "compile" in kinds


class TestPreemptResumeTrace:
    def test_preempted_stream_trace_shows_both_lives(self, tiny_lm):
        """Propagation through preemption/resume: the victim's ONE trace
        carries its first admission, the preempt marker, a SECOND
        queue_wait + resume, and lands flagged (always-kept)."""
        eng = _engine(tiny_lm, max_tokens=16, num_blocks=10)
        s = LifecycleScheduler(eng, window_steps=4, kv_high_watermark=0.2)
        ctx = TraceContext.mint()
        s.submit(ServeRequest(uid=0, prompt=[3, 5, 7, 11, 13],
                              max_new_tokens=16, trace=ctx))
        s.step()
        s.step()                    # uid 0 decoding, holds 3 of 10 blocks
        s.submit(ServeRequest(uid=1, prompt=[2] * 40, max_new_tokens=24))
        s.run_until_idle()
        assert s.counters["serving/preempted"] == 1
        rec = s.request(0).trace_result
        kinds = [sp["kind"] for sp in rec["spans"]]
        assert "preempt" in kinds and "resume" in kinds
        assert kinds.count("queue_wait") == 2   # admitted twice
        assert "preempted" in rec["flags"] and rec["kept"]


# --------------------------------------------------------------------- #
# Fleet: merged disagg trace + reroute across replica death
# --------------------------------------------------------------------- #
def _mk_replica(tiny_lm, block_size=8):
    from deepspeed_tpu.inference.v2.server import ServingServer

    eng = _engine(tiny_lm, block_size=block_size, max_ctx=96)
    sched = LifecycleScheduler(eng, window_steps=4, max_queue=16)
    return eng, sched, ServingServer(sched, port=0,
                                     bind="127.0.0.1").start()


def _post(port, body, timeout=300, path="/v1/generate"):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


class TestFleetMergedTrace:
    def test_disagg_request_one_merged_trace_covers_wall(self, tiny_lm,
                                                         fresh_store):
        """THE acceptance property: router → prefill replica → KV ship →
        decode replica produces ONE merged trace whose typed work
        segments cover ≥90% of the externally measured request wall."""
        from deepspeed_tpu.serving.fleet import FleetRouter, RouterServer

        _, _, rd = _mk_replica(tiny_lm, block_size=8)
        _, _, rp = _mk_replica(tiny_lm, block_size=16)
        router = FleetRouter(poll_s=0.2, disagg_threshold=8)
        router.add_replica(f"127.0.0.1:{rd.port}", role="decode")
        router.add_replica(f"127.0.0.1:{rp.port}", role="prefill")
        rs = RouterServer(router, port=0, bind="127.0.0.1").start()
        try:
            prompt = [3, 5, 7, 11, 13, 17, 19, 23, 29, 31]
            t0 = time.time()
            code, out = _post(rs.port, {"prompt": prompt,
                                        "max_new_tokens": 12})
            t1 = time.time()
            assert code == 200 and out["state"] == "finished"
            tid = out["trace_id"]
            rec = fresh_store.get(tid)
            assert rec is not None
            kinds = {sp["kind"] for sp in rec["spans"]}
            comps = {sp["component"] for sp in rec["spans"]}
            # the disaggregated path end to end, in one trace
            assert {"queue_wait", "admission", "prefill",
                    "kv_ship_encode", "kv_ship_wire",
                    "kv_ship_import", "route"} <= kinds
            assert comps == {"router", f"serve:{rd.port}",
                             f"serve:{rp.port}"}
            assert router.counters["fleet/prefill_disagg"] == 1
            # ≥90% of the measured wall is attributed to WORK segments
            # (the route envelope is excluded from the union)
            assert span_coverage(rec["spans"], t0, t1) >= 0.9
            # live endpoints resolve the id
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{rs.port}/traces?request={tid}",
                    timeout=30) as r:
                assert json.loads(r.read())["trace"] == tid
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{rs.port}/traces", timeout=30) as r:
                summary = json.loads(r.read())
            assert "prefill" in summary["segments"]
            assert summary["counters"].get("trace/kept", 0) >= 1
        finally:
            rs.stop()
            rd.stop()
            rp.stop()

    def test_rerouted_stream_merges_spans_from_both_replicas(self,
                                                             tiny_lm,
                                                             fresh_store):
        """Kill-mid-run chaos path: a replica dies after ADMITTING a
        stream but before its first token — the router reroutes, and the
        merged trace shows spans from BOTH replicas plus the reroute
        marker, flagged rerouted (always kept)."""
        from deepspeed_tpu.serving.fleet import FleetRouter, RouterServer

        _, _, r_dead = _mk_replica(tiny_lm)
        _, _, r_alive = _mk_replica(tiny_lm)
        router = FleetRouter(poll_s=30.0)      # no scrape rescue
        dead = router.add_replica(f"127.0.0.1:{r_dead.port}", name="dead")
        alive = router.add_replica(f"127.0.0.1:{r_alive.port}",
                                   name="alive")
        rs = RouterServer(router, port=0, bind="127.0.0.1").start()
        try:
            alive.queue_depth = 10             # bias the pick to 'dead'
            ctx = TraceContext.mint()
            done = {}

            def client():
                req = urllib.request.Request(
                    f"http://127.0.0.1:{rs.port}/v1/generate",
                    data=json.dumps({
                        "prompt": [5, 6, 7], "max_new_tokens": 6,
                        "stream": True,
                        "traceparent": ctx.header()}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=300) as r:
                    done["body"] = r.read().decode()

            t = threading.Thread(target=client, daemon=True)
            t.start()
            # deterministic kill point: wait until the dead replica has
            # ADMITTED the stream (its queue_wait span is in the shared
            # store) — it is then mid-prefill-compile, zero tokens out
            deadline = time.time() + 60
            dead_comp = f"serve:{r_dead.port}"
            while time.time() < deadline:
                rec = fresh_store.get(ctx.trace_id)
                if rec and any(sp["component"] == dead_comp
                               for sp in rec["spans"]):
                    break
                time.sleep(0.01)
            r_dead.hard_kill()
            t.join(timeout=300)
            assert "finished" in done.get("body", "")
            assert router.counters["fleet/rerouted"] >= 1
            rec = fresh_store.get(ctx.trace_id)
            comps = {sp["component"] for sp in rec["spans"]}
            kinds = {sp["kind"] for sp in rec["spans"]}
            assert {dead_comp, f"serve:{r_alive.port}"} <= comps
            assert "reroute" in kinds
            assert "rerouted" in rec["flags"] and rec["kept"]
        finally:
            rs.stop()
            r_alive.stop()


# --------------------------------------------------------------------- #
# CLI + summary section (synthetic traces; no engines)
# --------------------------------------------------------------------- #
def _synthetic_store(tmp_path, n=3):
    store = RequestTraceStore(
        jsonl_path=str(tmp_path / "traces.jsonl"), sample_every=1)
    now = time.time()
    for i in range(n):
        tid = f"{i:032x}"
        store.add_span(tid, "queue_wait", t0=now, dur_s=0.01,
                       component="router", uid=i)
        store.add_span(tid, "prefill", t0=now + 0.01, dur_s=0.2 + i,
                       component="serve:1", uid=i, tokens=8)
        store.add_span(tid, "decode_window", t0=now + 0.3, dur_s=0.05,
                       component="serve:1", uid=i)
        store.finish(tid, wall_s=0.3 + i)
    return store


class TestTraceCLI:
    def test_overview_slowest_and_request_views(self, tmp_path, capsys):
        from deepspeed_tpu.telemetry.tracing.cli import main

        _synthetic_store(tmp_path)
        assert main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "per-segment decomposition" in out and "prefill" in out
        assert main([str(tmp_path), "--slowest", "2"]) == 0
        out = capsys.readouterr().out
        assert f"{2:032x}" in out            # the slowest (wall 2.3s)
        assert main([str(tmp_path), "--request", f"{1:032x}"]) == 0
        out = capsys.readouterr().out
        assert "queue_wait" in out and "decode_window" in out
        assert "coverage" in out

    def test_unknown_request_and_empty_dir(self, tmp_path, capsys):
        from deepspeed_tpu.telemetry.tracing.cli import main

        assert main([str(tmp_path)]) == 2    # no traces.jsonl yet
        capsys.readouterr()
        _synthetic_store(tmp_path)
        assert main([str(tmp_path), "--request", "ffff"]) == 1

    def test_chrome_export_reuses_span_exporter(self, tmp_path):
        from deepspeed_tpu.telemetry.tracing.cli import main

        _synthetic_store(tmp_path)
        out_json = str(tmp_path / "chrome.json")
        assert main([str(tmp_path), "--chrome", out_json]) == 0
        with open(out_json) as f:
            doc = json.load(f)
        evs = doc["traceEvents"]
        assert evs and all(e["ph"] == "X" for e in evs)
        # components map to stable tids; every event names its trace
        assert {e["args"]["component"] for e in evs} == \
            {"router", "serve:1"}
        assert all("trace" in e["args"] for e in evs)


class TestTelemetrySection:
    def test_summary_renders_segments_and_exemplars(self, tmp_path):
        from deepspeed_tpu.telemetry import Telemetry, set_telemetry
        from deepspeed_tpu.telemetry.summary import (
            format_summary,
            summarize_run,
        )

        tel = Telemetry(output_dir=str(tmp_path / "tel"))
        set_telemetry(tel)
        try:
            store = RequestTraceStore(sample_every=1)
            install_trace_store(store)
            tid = "e" * 32
            store.add_span(tid, "prefill", t0=time.time(), dur_s=0.25)
            store.add_span(tid, "decode_window", t0=time.time(),
                           dur_s=0.03)
            store.note_exemplar("ttft_s", 0.8, tid)
            store.finish(tid, wall_s=0.3)
            tel.flush()
        finally:
            set_telemetry(None)
            tel.close()
        summary = summarize_run(str(tmp_path / "tel" / "events.jsonl"))
        tr = summary["tracing"]
        assert tr["segments"]["prefill"]["count"] == 1
        assert tr["counters"]["kept"] == 1
        assert tr["exemplars"]["ttft_s"][0]["trace"] == tid
        text = format_summary(summary)
        assert "request tracing" in text
        assert "TTFT tail exemplars" in text and tid[:12] in text


class TestHotPathCleanliness:
    def test_trace_bookkeeping_passes_host_sync_lint(self):
        """The dstpu-check source passes stay clean over the tracing
        plane and the instrumented decode hot path — span recording must
        never add a per-iteration device→host sync."""
        import os

        from deepspeed_tpu.analysis.source_passes import run_source_passes

        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        findings = run_source_passes([
            os.path.join(root, "deepspeed_tpu/telemetry/tracing"),
            os.path.join(root, "deepspeed_tpu/inference/v2/lifecycle.py"),
            os.path.join(root, "deepspeed_tpu/serving/fleet"),
        ])
        assert not findings, [f.render() for f in findings]
