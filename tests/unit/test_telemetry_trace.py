"""Tracer tests: span nesting, exception safety, Chrome-trace export, and
the disabled-mode zero-overhead guarantee."""
import json
import threading
import time

import jax.numpy as jnp
import pytest

from deepspeed_tpu.telemetry.trace import NULL_SPAN, Tracer

pytestmark = pytest.mark.telemetry


class TestSpanNesting:
    def test_nesting_records_depth_and_parent(self):
        tr = Tracer()
        with tr.span("outer"):
            with tr.span("mid"):
                with tr.span("inner"):
                    pass
        by_name = {r.name: r for r in tr.records()}
        assert by_name["outer"].depth == 0 and by_name["outer"].parent is None
        assert by_name["mid"].depth == 1 and by_name["mid"].parent == "outer"
        assert by_name["inner"].depth == 2 and by_name["inner"].parent == "mid"

    def test_sibling_spans_share_parent(self):
        tr = Tracer()
        with tr.span("outer"):
            with tr.span("a"):
                pass
            with tr.span("b"):
                pass
        by_name = {r.name: r for r in tr.records()}
        assert by_name["a"].parent == "outer"
        assert by_name["b"].parent == "outer"
        with tr.span("next"):   # stack fully unwound: a top-level span again
            pass
        assert (tr.records()[-1].depth, tr.records()[-1].parent) == (0, None)

    def test_duration_measured(self):
        tr = Tracer()
        with tr.span("sleepy"):
            time.sleep(0.02)
        (rec,) = tr.records()
        assert rec.dur_s >= 0.015

    def test_attrs_and_set(self):
        tr = Tracer()
        with tr.span("s", tag="ckpt-1") as sp:
            sp.set(extra=7)
        (rec,) = tr.records()
        assert rec.attrs == {"tag": "ckpt-1", "extra": 7}

    def test_sync_fences_jax_value(self):
        tr = Tracer()
        x = jnp.ones((16,)) * 2
        with tr.span("fenced", sync=x):
            pass
        (rec,) = tr.records()
        assert rec.dur_s >= 0

    def test_threads_have_independent_stacks(self):
        tr = Tracer()
        errs = []

        def work(i):
            try:
                with tr.span(f"t{i}"):
                    time.sleep(0.01)
                    tr.record(f"in_t{i}", time.perf_counter(), 0.0)
            except Exception as e:  # surfaced below
                errs.append(e)

        threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        # what each thread had open was its own span, whatever the others did
        inner = [r for r in tr.records() if r.name.startswith("in_")]
        assert sorted((r.name, r.parent, r.depth) for r in inner) == [
            (f"in_t{i}", f"t{i}", 1) for i in range(4)]
        outer = [r for r in tr.records() if r not in inner]
        assert len(outer) == 4
        assert all(r.depth == 0 for r in outer)


class TestExceptionSafety:
    def test_exception_recorded_and_propagates(self):
        tr = Tracer()
        with pytest.raises(ValueError):
            with tr.span("boom"):
                raise ValueError("x")
        (rec,) = tr.records()
        assert rec.error == "ValueError"
        assert (rec.depth, rec.parent) == (0, None)
        with tr.span("after"):
            pass
        assert tr.records()[-1].depth == 0

    def test_exception_in_nested_span_unwinds_stack(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with tr.span("outer"):
                with tr.span("inner"):
                    raise RuntimeError("deep")
        by_name = {r.name: r for r in tr.records()}
        assert by_name["inner"].error == "RuntimeError"
        assert by_name["outer"].error == "RuntimeError"
        assert (by_name["inner"].depth, by_name["inner"].parent) == (1, "outer")
        assert (by_name["outer"].depth, by_name["outer"].parent) == (0, None)
        # a fresh span after the exception nests at top level again
        with tr.span("after"):
            pass
        assert {r.name: r.depth for r in tr.records()}["after"] == 0


class TestChromeTrace:
    def test_export_round_trip(self, tmp_path):
        tr = Tracer()
        with tr.span("step", step=3):
            with tr.span("fwd"):
                pass
        path = tr.export_chrome_trace(str(tmp_path / "trace.json"))
        data = json.loads(open(path).read())
        assert data["displayTimeUnit"] == "ms"
        events = data["traceEvents"]
        assert {e["name"] for e in events} == {"step", "fwd"}
        for e in events:
            assert e["ph"] == "X"
            assert e["dur"] >= 0 and "ts" in e and "tid" in e
        fwd = next(e for e in events if e["name"] == "fwd")
        assert fwd["args"]["parent"] == "step"

    def test_max_spans_ring_counts_drops(self):
        tr = Tracer(max_spans=3)
        for i in range(5):
            with tr.span(f"s{i}"):
                pass
        assert len(tr.records()) == 3
        assert tr.dropped == 2
        assert tr.total_recorded == 5
        assert tr.to_chrome_trace()["metadata"]["dropped_spans"] == 2

    def test_flush_export_survives_ring_eviction(self, tmp_path):
        """Incremental JSONL export tracks the monotonic recorded total, so
        ring eviction neither re-exports old spans nor silently drops new
        ones once the buffer has filled."""
        from deepspeed_tpu.telemetry import Telemetry, read_jsonl

        tel = Telemetry(output_dir=str(tmp_path / "tel"), memory_interval=0,
                        max_spans=4)
        for i in range(4):
            with tel.span(f"a{i}"):
                pass
        tel.flush()                      # exports a0..a3, ring now full
        for i in range(6):               # a0..a3 evicted, b0..b1 evicted too
            with tel.span(f"b{i}"):
                pass
        tel.flush()                      # must export b2..b5 + drop marker
        tel.close()
        recs = list(read_jsonl(str(tmp_path / "tel" / "events.jsonl")))
        spans = [r["name"] for r in recs if r["kind"] == "span"]
        assert spans == ["a0", "a1", "a2", "a3", "b2", "b3", "b4", "b5"]
        (drop,) = [r for r in recs if r["kind"] == "spans_dropped"]
        assert drop["count"] == 2


class TestDisabledOverhead:
    def test_disabled_returns_shared_null_span(self):
        tr = Tracer(enabled=False)
        assert tr.span("x") is NULL_SPAN
        assert tr.span("y", sync=object(), attr=1) is NULL_SPAN
        assert tr.step_span(7) is NULL_SPAN
        with tr.span("x"):
            pass
        assert tr.records() == []

    def test_disabled_span_cost_is_negligible(self):
        """Acceptance guard: with telemetry disabled the hot path adds no
        measurable per-step overhead.  200k disabled spans in well under a
        second means the per-step cost (a handful of spans) is sub-µs."""
        tr = Tracer(enabled=False)
        n = 200_000
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.span("hot"):
                pass
        elapsed = time.perf_counter() - t0
        assert elapsed < 2.0, f"{n} disabled spans took {elapsed:.2f}s"

    def test_engine_without_telemetry_has_none_hub(self):
        """The engine wires telemetry only when the config block enables it;
        its _span helper must degrade to the shared null span."""
        import jax

        import deepspeed_tpu
        from deepspeed_tpu.runtime.topology import (TopologyConfig,
                                                    initialize_mesh)

        from .simple_model import init_mlp_params, mlp_loss_fn

        topo = initialize_mesh(TopologyConfig(), force=True)
        params = init_mlp_params(jax.random.PRNGKey(0))
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=mlp_loss_fn, model_parameters=params,
            config={"train_micro_batch_size_per_gpu": 1}, topology=topo)
        assert engine.telemetry is None
        assert engine._span("anything") is NULL_SPAN
