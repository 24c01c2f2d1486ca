"""The set-up metrics of ISSUE 36: ``program_span_before`` on a ring given by
``program_trace.preload`` and, once, on the live tracer with the program's
own listeners; each new metric's entry, file and cells."""
import json
import os

import pytest

from lib import manifest, program_trace

MAN = manifest.manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
TRAIN = ["mistral7b-train-1chip", "mixtral8x7b-train-zero3-4chip"]
DECODE = ["mistral7b-serve-decode", "mistral7b-serve-decode-longctx",
          "xing4-29b-serve-sessions", "qwen3next-80b-serve-sessions",
          "olmohybrid7b-serve-rollouts"]
SETUP = {"setup_trace_s": "s", "setup_lower_s": "s", "setup_compile_s": "s",
         "setup_cache_load_s": "s", "setup_programs_compiled": "programs",
         "setup_engine_init_s": "s"}
IN_WINDOW = {"compile_ms_in_window.train": ("train engine",
                                            "train_tokens_per_s", TRAIN),
             "compile_ms_in_window.decode": ("serve engine", "tpot_p50_ms",
                                             DECODE),
             "compile_ms_in_window.prefill": ("serve engine", "ttft_mean_ms",
                                              ["mistral7b-serve-prefill"])}


def reader(name):
    return manifest.load_module("readers", name)


@pytest.fixture(autouse=True)
def fresh():
    program_trace.preload()
    yield
    program_trace.preload()


def backend(t0, dur, cache, program="jit_serve_decode_s64x8"):
    return ("compile/backend", t0, dur, {"program": program, "cache": cache},
            1)


RING = [     # name, t0, dur, attrs, tid; the window starts at 100
    ("engine/init", 10.0, 5.0, {}, 1),
    ("compile/trace", 11.0, 1.0, {"program": "jit_init", "inner_traces": 7},
     1),
    ("compile/lower", 12.0, 0.5, {"program": "jit_init"}, 1),
    backend(12.5, 2.0, "written", "jit_init"),
    backend(20.0, 0.25, "hit"),
    backend(21.0, 0.5, "compiled", "jit_convert_element_type"),
    backend(22.0, 0.125, "off", "jit_convert_element_type"),
    ("compile/trace", 30.0, 4.0, {"program": "jit_serve_decode_s64x8"}, 1),
    backend(98.0, 4.0, "written"),         # across the start: the window's
    ("compile/trace", 120.0, 3.0, {"program": "jit_serve_decode_s2x8"}, 1),
    backend(123.0, 1.5, "hit", "jit_serve_decode_s2x8"),       # after it
    ("serve/step", 100.5, 30.0, {"kind": "decode"}, 1),
]
RUN = {"window": (100.0, 151.0), "trace": None}
NOT_HIT = {"key": "cache", "not_in": ["hit"]}


@pytest.mark.parametrize("args,want", [
    ({"spans": ["compile/trace"], "stat": "sum"}, 5.0),
    ({"spans": ["compile/lower"], "stat": "sum"}, 0.5),
    ({"spans": ["compile/backend"], "stat": "sum", "where": NOT_HIT}, 2.625),
    ({"spans": ["compile/backend"], "stat": "count", "where": NOT_HIT}, 3.0),
    ({"spans": ["compile/backend"], "stat": "sum",
      "where": {"key": "cache", "in": ["hit"]}}, 0.25),
    ({"spans": ["compile/backend"], "stat": "sum"}, 2.875),
    ({"spans": ["engine/init"], "stat": "sum"}, 5.0),
    ({"spans": ["compile/trace", "compile/lower"], "stat": "count"}, 3.0),
    ({"spans": ["compile/no_such_phase"], "stat": "sum"}, None),
    ({"spans": ["serve/step"], "stat": "sum"}, 0.0),    # recorded, none before
])
def test_records_that_end_before_the_window(args, want):
    program_trace.preload(ring=RING)
    got = reader("program_span_before").read(RUN, args)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("name", sorted(SETUP))
def test_a_program_without_the_listeners_gives_no_value(name):
    spec = manifest.metric_of(name)
    read = reader(spec["reader"]).read
    program_trace.preload(ring=[sp for sp in RING
                                if sp[0] == "serve/step"])
    assert read(RUN, spec["args"]) is None
    program_trace.preload(ring=None)        # no tracer at all
    assert read(RUN, spec["args"]) is None


def test_a_tracer_that_dropped_gives_no_value_not_a_short_sum(monkeypatch):
    from deepspeed_tpu.telemetry import get_tracer

    args = manifest.metric_of("setup_trace_s")["args"]
    read = reader("program_span_before").read
    program_trace.preload(ring=RING)
    assert read(RUN, args) == pytest.approx(5.0)
    monkeypatch.setattr(get_tracer(), "dropped", 3)
    assert read(RUN, args) is None


@pytest.mark.parametrize("name,want", [
    ("setup_trace_s", 5.0), ("setup_lower_s", 0.5),
    ("setup_compile_s", 2.625), ("setup_cache_load_s", 0.25),
    ("setup_programs_compiled", 3.0), ("setup_engine_init_s", 5.0)])
def test_each_set_up_metric_reads_its_records(name, want):
    spec = manifest.metric_of(name)
    assert spec["reader"] == "program_span_before"
    program_trace.preload(ring=RING)
    assert reader(spec["reader"]).read(RUN, spec["args"]) \
        == pytest.approx(want)


def test_the_four_phases_add_up_to_less_than_the_set_up():
    program_trace.preload(ring=RING)
    total = sum(
        reader("program_span_before").read(RUN, manifest.metric_of(n)["args"])
        for n in ("setup_trace_s", "setup_lower_s", "setup_compile_s",
                  "setup_cache_load_s"))
    assert total == pytest.approx(5.0 + 0.5 + 2.625 + 0.25) and total < 90.0


@pytest.mark.parametrize("name", sorted(IN_WINDOW))
def test_compiles_in_the_window_use_the_reader_that_exists(name):
    spec = manifest.metric_of(name)
    assert spec == {"reader": "program_span_stat",
                    "args": {"span": "compile/backend", "stat": "sum"}}
    read = reader("program_span_stat").read
    program_trace.preload(ring=RING)
    # the phase across the start and the one after it, in milliseconds
    assert read(RUN, spec["args"]) == pytest.approx(5500.0)
    program_trace.preload(ring=[sp for sp in RING if sp[1] < 90.0])
    assert read(RUN, spec["args"]) == 0.0       # a correct run: 0, not None
    program_trace.preload(ring=[sp for sp in RING
                                if not sp[0].startswith("compile/")])
    assert read(RUN, spec["args"]) is None      # the parent commit


@pytest.mark.parametrize("name", sorted(SETUP) + sorted(IN_WINDOW))
def test_entry_file_and_cells(name):
    entry, = [m for m in MAN["per_layer"] if m["name"] == name]
    layer, moves, cells = IN_WINDOW.get(
        name, ("entry / config", "setup_s", CELLS))
    assert entry == {
        "name": name, "unit": SETUP.get(name, "ms"), "better": "lower",
        "source": "program_span", "layer": layer, "moves": moves,
        "workloads": cells}
    assert os.path.isfile(os.path.join(manifest.BENCH, "metrics",
                                       name + ".json"))
    assert set(manifest.metric_of(name)) == {"reader", "args"}


def test_the_new_entries_are_the_last_nine():
    assert [m["name"] for m in MAN["per_layer"][-9:]] \
        == list(SETUP) + list(IN_WINDOW)


def test_the_live_ring_and_the_account_beside_the_profile(tmp_path,
                                                          monkeypatch):
    """The program's listeners, a compile before the window and one inside
    it, through both readers; ``setup_account.json`` lands beside the
    profile with the program's rows."""
    import time

    import jax
    import numpy as np
    from deepspeed_tpu.telemetry import get_tracer
    from deepspeed_tpu.utils.compile_cache import configure_compile_cache

    from lib.spans import Spans

    tracer = get_tracer()
    tracer.clear()
    configure_compile_cache()

    def warm_program(x):
        return x * 2 + 1

    def late_program(x):
        return x - 1

    with tracer.span("engine/init"):
        jax.jit(warm_program)(np.ones(4, np.float32))
    time.sleep(0.01)
    lo = time.perf_counter()
    jax.jit(late_program)(np.ones(4, np.float32))
    run = {"window": (lo, time.perf_counter() + 1.0), "trace": {"device": {}},
           "facts": {"setup_seconds": 1.0, "cache_entries_new": 0},
           "spans": Spans()}
    program_trace._LOADED.update(ring=False, scopes=True, xplane=True)
    monkeypatch.setattr(program_trace, "_XPLANE", {"dir": str(tmp_path)})
    before = reader("program_span_before")
    value = lambda name: manifest.load_module(  # noqa: E731
        "readers", manifest.metric_of(name)["reader"]).read(
            run, manifest.metric_of(name)["args"])
    assert value("setup_programs_compiled") == 1.0
    assert value("setup_cache_load_s") == 0.0
    assert value("setup_engine_init_s") >= value("setup_trace_s") \
        + value("setup_lower_s") + value("setup_compile_s") > 0.0
    assert value("compile_ms_in_window.decode") > 0.0
    assert before.read(run, {"spans": ["compile/backend"], "stat": "count"}) \
        == 1.0
    with open(tmp_path / "setup_account.json") as f:
        account = json.load(f)
    programs = account["programs"]
    assert [row["program"] for row in programs["before_the_window"]] \
        == ["jit_warm_program"]
    assert programs["before_the_window"][0]["parents"] == ["engine/init"]
    assert [row["program"] for row in programs["in_the_window"]] \
        == ["jit_late_program"]
    assert programs["after_the_window"] == []
    assert list(account["top_level_spans_before_the_window_s"]) \
        == ["engine/init"]
    assert sum(n for rows in programs.values() for row in rows
               for n in row["cache"].values()) == 2
