"""The traffic generators are pure functions of their arguments, every seed
offers the same work in another order, and requests are timed from when
they were due."""
import json
import os

import pytest

from lib import manifest
from lib import serve_system as ss

open_loop = manifest.load_module("generators", "open_loop")
closed_loop = manifest.load_module("generators", "closed_loop")
BIG_SEED = 2 ** 31 + 12345


def traffic(name):
    with open(os.path.join(manifest.BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_open_loop_schedule_is_a_pure_function_of_the_seed():
    job = traffic("poisson-prefill")
    a = open_loop.schedule(job, 51.0, BIG_SEED)
    assert a == open_loop.schedule(job, 51.0, BIG_SEED)
    assert a != open_loop.schedule(job, 51.0, BIG_SEED + 1)
    assert all(0.0 <= due < 51.0 for due, _, _ in a)
    assert [due for due, _, _ in a] == sorted(due for due, _, _ in a)


def test_every_seed_offers_the_same_sizes_and_gaps_in_another_order():
    job = traffic("poisson-prefill")
    a = open_loop.schedule(job, 51.0, 1)
    b = open_loop.schedule(job, 51.0, BIG_SEED)
    assert sorted((p, o) for _, p, o in a) == sorted((p, o) for _, p, o in b)
    assert len(a) == pytest.approx(job["rate_per_s"] * 51.0, rel=0.2)
    lo, hi = job["prompt_tokens"]["min"], job["prompt_tokens"]["max"]
    assert all(lo <= p <= hi for _, p, _ in a)
    short, long = job["output_tokens"]["min"], job["output_tokens"]["max"]
    outs = {o for _, _, o in a}
    assert outs <= set(range(short, long + 1)) and len(outs) >= 20
    # answers end anywhere, so the scheduler's shorter windows (fewer steps
    # than window_steps 8, when an answer is about to end) all occur
    assert {(o - 1) % 8 for o in outs} == set(range(8))
    # whole blocks move: offsets inside a block are the same set
    block = job["block_s"]
    assert sorted(round(d % block, 6) for d, _, _ in a) == \
        sorted(round(d % block, 6) for d, _, _ in b)


def test_closed_loop_rounds_hold_one_multiset():
    job = traffic("closed-64-decode")
    a = closed_loop.round_of(job, 0, 1)
    b = closed_loop.round_of(job, 0, BIG_SEED)
    assert a == closed_loop.round_of(job, 0, 1)
    assert a != b and sorted(a) == sorted(b) and len(a) == job["clients"]
    assert all(128 <= p <= 512 and 256 <= o <= 768 for p, o in a)


def test_requests_are_timed_from_when_they_were_due():
    req = ss.Served(uid=1, due=10.0, prompt_len=100, want=3)
    req.submitted = 10.25            # the generator ran a quarter second late
    req.admitted = 10.5
    req.times, req.counts = [11.0, 11.5], [1, 3]
    req.state = "finished"
    out = ss.request_metrics([req])
    assert out["ttft_ms"] == [pytest.approx(1000.0)]       # not 750
    assert out["gen_lag_ms"] == [pytest.approx(250.0)]
    assert out["queue_wait_ms"] == [pytest.approx(250.0)]
    # (last token - first token) / (tokens - 1), however a window groups them
    assert out["tpot_ms"] == [pytest.approx(250.0)]


def test_length_distributions_are_fixed_multisets():
    import numpy as np

    spec = {"dist": "lognormal", "median": 1024, "sigma": 0.8,
            "min": 128, "max": 4032}
    a = ss.lengths(spec, 200, np.random.default_rng(1))
    b = ss.lengths(spec, 200, np.random.default_rng(2))
    assert sorted(a) == sorted(b) and a != b
    assert abs(sorted(a)[100] - 1024) < 16
