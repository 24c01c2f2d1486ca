"""One decode window ahead of the host (marker: serving).

The scheduler dispatches window i+1 before it drains window i where the
riders stay and every row of the batch is taken; the seed tokens stay on the
device (the window ahead resumes from the advanced metadata); the drain
launches no device program.  Tiny models on the CPU: a K/V family, the
latent (MLA) family and a recurrent family.  The run-ahead schedule's greedy
streams must be, request for request, those of the same scheduler forced to
drain every window.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.lifecycle import (
    LifecycleScheduler, RequestState, ServeRequest)
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.runtime.fault import injection
from deepspeed_tpu.telemetry import get_tracer
from deepspeed_tpu.utils import compile_cache

pytestmark = pytest.mark.serving

#: published keys at a tiny size (tests/unit/test_xing4_serving.py's, with
#: one expert layer less)
XING = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=2, first_k_dense_replace=1,
    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
    routed_scaling_factor=2, norm_topk_prob=True, scoring_func="sigmoid",
    topk_method="noaux_tc", n_group=1, topk_group=1, hc_mult=4,
    hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling={"type": "yarn", "factor": 8, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 16},
    max_position_embeddings=256, tie_word_embeddings=False)
#: (tests/unit/test_qwen3_next_serving.py's, one period of 3 DeltaNet + 1
#: attention layer)
QWEN = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=4,
    full_attention_interval=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=32, partial_rotary_factor=0.25, rope_theta=10000000,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4, num_experts=4,
    num_experts_per_tok=3, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[], rms_norm_eps=1e-6,
    rope_scaling=None, max_position_embeddings=256,
    tie_word_embeddings=False, ep_size=4, ep_rank=1)

SLOTS = 4
WINDOW = 8          # = the block size: a rider crosses a page every window


@pytest.fixture(scope="module")
def models():
    made = {}

    def get(family):
        if family not in made:
            if family == "kv":
                m = CausalLM(TransformerConfig.tiny(use_flash=False))
                made[family] = m, m.init_params(jax.random.PRNGKey(0))
            elif family == "latent":
                from deepspeed_tpu.models import xing4

                m = xing4.Xing4LM.from_hf_config(XING)
                made[family] = m, m.init_params(jax.random.PRNGKey(0),
                                                jnp.float32)
            else:
                from deepspeed_tpu.models import qwen3_next

                m = qwen3_next.Qwen3NextLM.from_hf_config(QWEN)
                made[family] = m, m.init_params(jax.random.PRNGKey(0),
                                                jnp.float32)
        return made[family]

    return get


@pytest.fixture(autouse=True)
def _clean():
    injection.clear()
    get_tracer().clear()
    yield
    injection.clear()


@pytest.fixture(scope="module")
def engines(models):
    """One engine a family for the whole file (its programs compile once):
    every test gives all its blocks back, and reads counters as differences."""
    made = {}

    def get(family="kv"):
        if family not in made:
            model, params = models(family)
            cfg = dict(max_tokens=16, max_seqs=SLOTS, max_ctx=128,
                       block_size=8, dtype=jnp.float32)
            if family == "kv":
                cfg["attn_impl"] = "gather"
            made[family] = InferenceEngineV2(
                model, params, RaggedInferenceEngineConfig(**cfg))
        eng = made[family]
        assert eng.state_manager.free_blocks == \
            eng.state_manager.allocator.total_blocks, "an earlier test leaked"
        return eng

    return get


def scheduler_for(eng, ahead=True, **kw):
    """``ahead=False``: the same scheduler forced to drain every window."""
    sched = LifecycleScheduler(eng, window_steps=WINDOW, **kw)
    if not ahead:
        sched._may_run_ahead = lambda: False
    return sched


def prompts_for(n, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 250, size=5 + 3 * i).tolist() for i in range(n)]


def submit_all(sched, wants, prompts=None, **kw):
    prompts = prompts or prompts_for(len(wants))
    for uid, (prompt, want) in enumerate(zip(prompts, wants)):
        assert sched.submit(ServeRequest(uid=uid, prompt=prompt,
                                         max_new_tokens=want, **kw)).admitted


def windows():
    """(ahead, steps) of every ``serve/window`` recorded, oldest first."""
    return [(r.attrs["ahead"], r.attrs["steps"])
            for r in get_tracer().records()
            if r.name == "serve/window" and "ahead" in r.attrs]


def streams(sched, uids):
    return {u: list(sched.request(u).produced) for u in uids}


def step_until_in_flight(sched, windows_done=1):
    """Step until a window is in flight with ``windows_done`` drained."""
    before = sched.eng.decode_windows_dispatched
    for _ in range(200):
        sched.step()
        if sched._inflight is not None and \
                sched.eng.decode_windows_dispatched - before > windows_done:
            return
    raise AssertionError("no window was ever left in flight")


def resumed_dispatches():
    """``resumed`` of every ``engine/decode_dispatch``, oldest first."""
    return [r.attrs["resumed"] for r in get_tracer().records()
            if r.name == "engine/decode_dispatch" and "resumed" in r.attrs]


# --------------------------------------------------------------------- #
# (1) the same streams as the drained schedule
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("family", ["kv", "latent", "recurrent"])
def test_run_ahead_streams_equal_the_drained_schedule(engines, family):
    """8-step windows over 8-token pages: every rider crosses a page boundary
    in every window (its pages were reserved at admission, so no table grows
    and a window ahead resumes: nothing is packed, nothing read back)."""
    wants = [44, 44, 37, 30]            # a full batch; 30 ends first
    eng = engines(family)

    def run(ahead):
        get_tracer().clear()
        hits = eng.decode_resume_hits
        sched = scheduler_for(eng, ahead)
        submit_all(sched, wants)
        sched.run_until_idle()
        assert all(sched.request(u).state == RequestState.FINISHED
                   for u in range(SLOTS))
        assert eng.state_manager.free_blocks == \
            eng.state_manager.allocator.total_blocks
        return (streams(sched, range(SLOTS)), windows(),
                resumed_dispatches(), eng.decode_resume_hits - hits)

    drained, drained_windows, _, _ = run(False)
    assert not any(a for a, _ in drained_windows)
    ahead, ahead_windows, resumed, hits = run(True)
    assert ahead == drained
    assert [len(ahead[u]) for u in range(SLOTS)] == wants
    # 29 tokens until uid 3 must end: windows of 8, 8, 8, 4 are each run
    # ahead of; the last (1 step, the finisher inside) is dispatched ahead
    # too and drained in its own step; then a slot is free
    assert ahead_windows[:5] == [(0, 8), (1, 8), (1, 8), (1, 4), (1, 1)]
    assert sum(a for a, _ in ahead_windows) == 4
    assert resumed[1:5] == [True] * 4
    assert sum(resumed) == hits


# --------------------------------------------------------------------- #
# (2) when it engages
# --------------------------------------------------------------------- #
def _drafter():
    from deepspeed_tpu.inference.v2.speculative import SpeculativeConfig

    return dict(speculative=SpeculativeConfig(mode="ngram", k=2))


@pytest.mark.parametrize("case,family,wants,kw,expect", [
    ("full_batch", "kv", [20] * SLOTS, {}, True),
    ("full_state_pool", "recurrent", [20] * SLOTS, {}, True),
    ("a_free_slot", "kv", [20] * (SLOTS - 1), {}, False),
    ("a_free_state_slot", "recurrent", [20] * (SLOTS - 1), {}, False),
    # 1 token from prefill + 8: everybody ends inside the first window
    ("finishers_in_flight", "kv", [1 + WINDOW] * SLOTS, {}, False),
    ("a_drafter", "kv", [20] * SLOTS, "drafter", False),
    # a fifth request: the K/V family admits it (no slot binds it) and the
    # decode set rotates, so a window's riders are never the next one's
    ("more_riders_than_rows", "kv", [20] * (SLOTS + 1), {}, False),
])
def test_ahead_engages_exactly_under_its_rules(engines, case, family, wants,
                                               kw, expect):
    eng = engines(family)
    sched = scheduler_for(eng, **(_drafter() if kw == "drafter" else kw))
    submit_all(sched, wants)
    sched.run_until_idle()
    assert [len(sched.request(u).produced) for u in range(len(wants))] \
        == wants
    seen = windows()
    n_ahead = sum(a for a, _ in seen)
    assert (n_ahead > 0) == expect, seen
    # every window ahead resumed from the undrained one's advanced metadata
    # (a dispatch's record closes inside its window's)
    resumed = resumed_dispatches()
    assert len(resumed) == len(seen) or kw == "drafter"  # (verify windows)
    assert all(r for r, (a, _) in zip(resumed, seen) if a)
    if expect:
        # 19 tokens owed after the prefill's one: 8, 8, 2 are run ahead of;
        # the last step holds the finishers and is drained in its own step
        assert seen == [(0, 8), (1, 8), (1, 2), (1, 1)]


def test_an_arrival_at_a_full_batch_waits_one_window_more(engines):
    """The K/V family admits on free blocks, not on ``max_seqs``: with every
    row of the batch taken and blocks free, an arrival while a window is in
    flight is started once that window has drained.  The drained schedule
    would have started it a window sooner (the window in flight was
    dispatched ahead); the cost is that one window and never a second: the
    next step dispatches nothing before the arrival's prefill."""
    eng = engines()
    sched = scheduler_for(eng)
    submit_all(sched, [40] * SLOTS)
    step_until_in_flight(sched)
    assert len(sched._decodes) == eng.config.max_seqs
    assert eng.state_manager.free_blocks > 0
    dispatched = eng.decode_windows_dispatched
    assert sched.submit(ServeRequest(uid=9, prompt=[5, 6, 7],
                                     max_new_tokens=4)).admitted
    sched.step()
    assert sched._inflight is None                  # drained first
    assert eng.decode_windows_dispatched == dispatched
    assert sched.request(9).first_token_t is not None   # prefilled at once
    # with five riders over four rows the decode set rotates: nothing more
    # runs ahead until the batch is the riders' own again
    get_tracer().clear()
    sched.step()
    assert windows() == [(0, WINDOW)]
    sched.run_until_idle()
    assert len(sched.request(9).produced) == 4


# --------------------------------------------------------------------- #
# (3) a rider that ends where the host could not foresee it
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("family", ["kv", "recurrent"])
def test_eos_inside_a_window_that_was_run_ahead_of(engines, family):
    wants = [44] * SLOTS
    eng = engines(family)
    free = scheduler_for(eng)
    submit_all(free, wants)
    free.run_until_idle()
    plain = streams(free, range(SLOTS))
    # an EOS that one rider first produces inside its SECOND window (tokens
    # 9..16 of its stream), which the third is dispatched ahead of, and that
    # nobody produces before then
    eos = None
    for victim in range(SLOTS):
        others = [t for u in range(SLOTS) if u != victim
                  for t in plain[u][:25]]
        for cut in range(9, 17):
            tok = plain[victim][cut]
            if tok not in plain[victim][:cut] and tok not in others:
                eos = tok
                break
        if eos is not None:
            break
    assert eos is not None, plain

    def run(ahead):
        get_tracer().clear()
        sched = scheduler_for(eng, ahead, eos_token_id=eos)
        held = {}

        def on_event(event, req):
            if event == "finished" and req.uid == victim:
                held["in_flight"] = sched._inflight
                held["blocks"] = eng.state_manager.get_sequence(victim)
        submit_all(sched, wants, on_event=on_event)
        sched.run_until_idle()
        return sched, held

    drained, _ = run(False)
    sched, held = run(True)
    got = streams(sched, range(SLOTS))
    assert got == streams(drained, range(SLOTS))    # batch-mates bit-equal
    assert got[victim] == plain[victim][:cut + 1]   # ends at its EOS
    assert sched.request(victim).finish_reason == "eos"
    # when it ended the next window already carried it: its row was dropped
    # and its blocks were still its own, until that window had drained
    assert held["in_flight"] is not None
    assert victim in held["in_flight"].dropped
    assert held["blocks"] is not None and held["blocks"].blocks
    assert eng.state_manager.free_blocks == \
        eng.state_manager.allocator.total_blocks
    assert eng.state_manager.free_slots in (None, SLOTS)


# --------------------------------------------------------------------- #
# (4) whatever touches a rider drains first
# --------------------------------------------------------------------- #
class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("event", ["cancel", "expiry", "nan", "drain",
                                   "error"])
def test_with_a_window_in_flight(engines, monkeypatch, event):
    wants = [44] * SLOTS
    eng = engines()
    base = scheduler_for(eng, ahead=False)
    submit_all(base, wants)
    base.run_until_idle()
    plain = streams(base, range(SLOTS))

    clock = FakeClock()
    sched = scheduler_for(eng, clock=clock)
    submit_all(sched, wants[:-1])
    assert sched.submit(ServeRequest(
        uid=SLOTS - 1, prompt=prompts_for(SLOTS)[-1], max_new_tokens=44,
        deadline_s=50.0)).admitted
    step_until_in_flight(sched)
    owed = sched._inflight.steps
    had = {u: len(sched.request(u).produced) for u in range(SLOTS)}
    victim = None
    if event == "cancel":
        victim = 0
        assert sched.cancel(victim)
        sched.step()
        assert sched.request(victim).state == RequestState.CANCELLED
    elif event == "expiry":
        victim = SLOTS - 1
        clock.t += 100.0
        sched.step()
        assert sched.request(victim).state == RequestState.EXPIRED
    elif event == "nan":
        # the NEXT window dispatched (ahead of the one in flight) poisons
        # its first rider; its batch-mates' rows are untouched
        victim = 0
        injection.configure("site=decode_window,kind=nan,times=1")
        sched.step()
        assert sched.request(victim).state == RequestState.FAILED
        assert sched.request(victim).finish_reason == "nan"
        assert sched.counters["serving/nan_isolated"] == 1
        injection.clear()
    elif event == "drain":
        sched.start_drain()
        sched.step()
        assert sched._inflight is None
    elif event == "error":
        def boom(*a, **k):
            raise RuntimeError("dispatch failed")
        with monkeypatch.context() as patched:
            patched.setattr(eng, "decode_batch_async", boom, raising=False)
            with pytest.raises(RuntimeError, match="dispatch failed"):
                sched.step()
        assert sched._inflight is None      # nothing stays in flight
    if victim is not None:
        # the window in flight was drained first: the rider got its tokens
        got = sched.request(victim).produced
        assert len(got) == had[victim] + owed
        assert got == plain[victim][:len(got)]
        assert eng.state_manager.get_sequence(victim) is None
    else:
        for u in range(SLOTS):
            assert len(sched.request(u).produced) >= had[u] + owed
    if event == "drain":
        sched.drain(deadline_s=60.0)
    else:
        sched.run_until_idle()
    for u in range(SLOTS):
        if u != victim:
            assert sched.request(u).state == RequestState.FINISHED
            assert sched.request(u).produced == plain[u]
    assert eng.state_manager.free_blocks == \
        eng.state_manager.allocator.total_blocks


def test_drain_deadline_with_a_window_in_flight_leaks_nothing(engines):
    eng = engines()
    sched = scheduler_for(eng)
    submit_all(sched, [44] * SLOTS)
    step_until_in_flight(sched)
    out = sched.drain(deadline_s=0.0)
    assert out["expired"] == SLOTS and sched._inflight is None
    assert eng.state_manager.free_blocks == \
        eng.state_manager.allocator.total_blocks


# --------------------------------------------------------------------- #
# (5) the engine's half
# --------------------------------------------------------------------- #
@pytest.fixture()
def compile_records():
    """The process-global tracer with the compile listeners installed, as
    every entry point installs them."""
    compile_cache.install_compile_listeners()
    yield lambda: [r for r in get_tracer().records()
                   if r.name.startswith("compile/")]


@pytest.mark.parametrize("family", ["kv", "latent", "recurrent"])
def test_the_drain_launches_no_device_program(engines, family,
                                              compile_records):
    eng = engines(family)
    uids = [0, 1, 2]                    # a 4-wide bucket: a pad row to cut
    logits = eng.put(uids, [[3, 5, 7], [2, 4], [9]])
    seeds = [int(t) for t in np.asarray(jnp.argmax(logits, -1))]
    eng.decode_batch(uids, seeds, 4)    # compiles the loop, warms the rest
    seeds = [int(t) for t in eng.decode_batch(uids, seeds, 4)[-1]]
    first = eng.decode_batch_async(uids, seeds, 4)
    second = eng.decode_batch_async(uids, seeds, 4)      # one ahead
    traces = sum(eng.trace_counts.values())
    compiled = len(compile_records())
    toks = first.tokens()
    bad = first.nonfinite_uids()
    assert toks.shape == (4, 3) and bad == []
    assert first.nonfinite.shape == (3,)
    assert sum(eng.trace_counts.values()) == traces
    assert len(compile_records()) == compiled, compile_records()[compiled:]
    assert second.tokens().shape == (4, 3)
    assert len(compile_records()) == compiled
    # a chained window's time runs from its predecessor's drain
    assert second.duration_s <= second._drained_t - first._drained_t + 1e-9
    eng.flush(uids)
