#!/usr/bin/env python
"""Smoke-check the serving stack end to end on the CPU sim.

Chip runs are rare and budgeted, so the serving stack could rot between
them without anything noticing: an import error in the decode
loop, a broken bucket key, a kernel-dispatch regression, or a lifecycle/
drain regression only surfaces when someone finally gets a chip.  Three
scenarios, all enforced from ``tests/unit/test_serving_decode_smoke.py``
the same way the no-bare-print lint is:

  * ``decode``    — prefill through ``put()`` then a fused device-resident
    4-token ``decode_batch`` window under BOTH attention impls (``paged``
    fast path and the ``gather`` numerics oracle), asserting the greedy
    streams agree and the decode HBM roofline was recorded.
  * ``lifecycle`` — two requests through the LifecycleScheduler; one
    deadline-expires mid-window (fake clock) and is flushed with its KV
    blocks reclaimed; the survivor drains the exact token stream an
    unperturbed run produces; the pool's free count returns to initial.
  * ``drain``     — the real ``bin/dstpu-serve`` process: SIGTERM during
    an active decode returns the in-flight request's completed response,
    rejects new requests with 503 (Retry-After), reports ``draining`` on
    ``/healthz``, and exits 0 within the drain deadline.
  * ``specdec``   — speculative decoding: prefill a planted-repetition
    prompt, run an 8-token spec-dec decode with the n-gram drafter under
    BOTH attention impls; the drafter must accept at least one
    multi-token window, the greedy stream must be bit-identical to
    vanilla decode, and every KV block must be reclaimed.
  * ``fleet``     — the fleet tier with REAL processes: ``bin/dstpu-router``
    over two ``bin/dstpu-serve --prefix-cache`` replicas; a prefix-cached
    request pair on one replica must land a cache hit AND answer
    bit-identically to the cold replica; requests through the router
    succeed; SIGTERM-draining one replica mid-stream loses ZERO streams
    (in-flight finishes, new work routes to the survivor, drained
    replica exits 0).
  * ``trace``     — fleet-wide request tracing with REAL processes: a
    ``dstpu-router --disagg-threshold`` over a prefill replica and a
    decode replica; ONE disaggregated request must produce ONE merged
    trace on the router whose waterfall carries queue / prefill /
    kv_ship (encode+wire+import) / decode segments from BOTH replicas,
    ``GET /traces?request=`` resolves it, and ``bin/dstpu-trace
    --request`` renders the waterfall from the router's traces.jsonl.

Usage: ``python tools/check_serving_smoke.py
[--scenario all|decode|lifecycle|drain|specdec|fleet|trace]``
Exit status 1 lists what broke.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

DECODE_STEPS = 4


def scenario_decode(check):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2,
        RaggedInferenceEngineConfig,
    )
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    cfg = TransformerConfig.tiny(use_flash=False)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = [3, 5, 7, 11, 13]

    streams = {}
    for impl in ("paged", "gather"):
        try:
            eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
                max_tokens=16, max_seqs=4, max_ctx=64, block_size=8,
                dtype=jnp.float32, attn_impl=impl, block_q=16,
                pages_per_chunk=2))
            logits = eng.put([0], [prompt])
            check(f"{impl}: prefill logits finite",
                  bool(np.isfinite(np.asarray(logits)).all()))
            seed = int(jnp.argmax(logits[0]))
            window = eng.decode_batch_async([0], [seed], steps=DECODE_STEPS)
            toks = window.tokens()
            check(f"{impl}: decode window shape",
                  toks.shape == (DECODE_STEPS, 1), f"got {toks.shape}")
            check(f"{impl}: decode roofline recorded",
                  eng.last_decode_roofline is not None
                  and "hbm_pct_peak" in (eng.last_decode_roofline or {}),
                  f"got {eng.last_decode_roofline!r}")
            eng.flush([0])
            streams[impl] = [int(t) for t in toks[:, 0]]
        except Exception as exc:  # noqa: BLE001
            check(f"{impl}: prefill→decode", False, repr(exc)[-300:])

    if "paged" in streams and "gather" in streams:
        check("paged and gather decode the same greedy stream",
              streams["paged"] == streams["gather"],
              f"paged={streams.get('paged')} gather={streams.get('gather')}")


def scenario_lifecycle(check):
    """Admit two → deadline-expire one mid-window → survivor drains the
    unperturbed token stream → every block reclaimed."""
    import jax
    import jax.numpy as jnp

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

    cfg = TransformerConfig.tiny(use_flash=False)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))

    def mk():
        return InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
            max_tokens=16, max_seqs=4, max_ctx=64, block_size=8,
            dtype=jnp.float32, attn_impl="gather"))

    clock = {"t": 1000.0}

    try:
        # unperturbed survivor stream
        eng = mk()
        s = LifecycleScheduler(eng, window_steps=2,
                               clock=lambda: clock["t"])
        s.submit(ServeRequest(uid=1, prompt=[4, 6, 8], max_new_tokens=8))
        s.run_until_idle()
        ref = list(s.request(1).produced)

        eng = mk()
        pool = eng.state_manager.free_blocks
        s = LifecycleScheduler(eng, window_steps=2,
                               clock=lambda: clock["t"])
        s.submit(ServeRequest(uid=0, prompt=[3, 5, 7, 11],
                              max_new_tokens=32, deadline_s=5.0))
        s.submit(ServeRequest(uid=1, prompt=[4, 6, 8], max_new_tokens=8))
        s.step()                                  # both prefill → decode
        s.step()                                  # one shared window
        check("lifecycle: victim decoding before expiry",
              s.request(0).state == RequestState.DECODE,
              f"state={s.request(0).state}")
        clock["t"] += 10.0                        # blow the deadline
        s.run_until_idle()
        check("lifecycle: victim expired mid-stream",
              s.request(0).state == RequestState.EXPIRED
              and len(s.request(0).produced) < 32,
              f"state={s.request(0).state} "
              f"produced={len(s.request(0).produced)}")
        check("lifecycle: deadline counter",
              s.counters.get("serving/deadline_expired") == 1,
              f"counters={dict(s.counters)}")
        check("lifecycle: survivor stream matches unperturbed run",
              s.request(1).state == RequestState.FINISHED
              and list(s.request(1).produced) == ref,
              f"got={s.request(1).produced} want={ref}")
        check("lifecycle: all blocks reclaimed",
              eng.state_manager.free_blocks == pool,
              f"free={eng.state_manager.free_blocks} want={pool}")
    except Exception as exc:  # noqa: BLE001
        check("lifecycle scenario", False, repr(exc)[-300:])


def scenario_specdec(check):
    """Planted-repetition prompt → 8-token spec-dec decode (n-gram
    drafter) → >=1 multi-token acceptance, stream bit-identical to
    vanilla, blocks reclaimed — both attention impls.

    The prompt [142]*6 is the planted repetition: this seed/params
    combination greedily continues with a constant stream (verified
    deterministic on the CPU sim), so the suffix-match drafter MUST land
    full-length accepted windows — an acceptance regression here is a
    spec-dec bug, not workload noise."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2,
        RaggedInferenceEngineConfig,
    )
    from deepspeed_tpu.inference.v2.speculative import (
        NGramDrafter,
        speculative_decode,
    )
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    cfg = TransformerConfig.tiny(use_flash=False)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = [142] * 6
    steps = 8

    def mk(impl):
        return InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
            max_tokens=16, max_seqs=4, max_ctx=64, block_size=8,
            dtype=jnp.float32, attn_impl=impl, block_q=16,
            pages_per_chunk=2))

    for impl in ("paged", "gather"):
        try:
            eng = mk(impl)
            logits = eng.put([0], [prompt])
            seed = int(jnp.argmax(logits[0]))
            vanilla = [int(t) for t in
                       eng.decode_batch([0], [seed], steps)[:, 0]]
            eng.flush([0])

            eng = mk(impl)
            pool0 = eng.state_manager.free_blocks
            logits = eng.put([0], [prompt])
            seed2 = int(jnp.argmax(logits[0]))
            check(f"{impl}: specdec prefill argmax matches vanilla",
                  seed2 == seed, f"{seed2} != {seed}")
            out, stats = speculative_decode(
                eng, NGramDrafter(), [0], [seed2], [prompt + [seed2]],
                steps=steps, k=4)
            check(f"{impl}: specdec stream bit-identical to vanilla",
                  out[0][:steps] == vanilla,
                  f"spec={out[0][:steps]} vanilla={vanilla}")
            check(f"{impl}: n-gram drafter accepted a multi-token window",
                  stats["accepted_draft"] >= 1 and
                  stats["windows"] < steps,
                  f"stats={stats}")
            eng.flush([0])
            check(f"{impl}: specdec blocks reclaimed",
                  eng.state_manager.free_blocks == pool0,
                  f"free={eng.state_manager.free_blocks} want={pool0}")
        except Exception as exc:  # noqa: BLE001
            check(f"{impl}: specdec scenario", False, repr(exc)[-300:])


#: every generate-path shed (429/503) body seen by ANY scenario, audited
#: in main(): since the per-tenant QoS work, EVERY shed anywhere in the
#: fleet must name the tenant it hit — an unattributed shed means a shed
#: path escaped the accounting and per-tenant isolation can't be trusted
SHED_BODIES = []


def _http(method, url, body=None, timeout=30):
    req = urllib.request.Request(url, method=method,
                                 data=json.dumps(body).encode()
                                 if body is not None else None)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        resp = json.loads(e.read())
        if e.code in (429, 503) and "/v1/generate" in url:
            SHED_BODIES.append((url, e.code, resp))
        return e.code, resp


#: the drain scenario's in-flight answer (see its docstring)
DRAIN_TOKENS = 900


def scenario_drain(check):
    """SIGTERM the real dstpu-serve during an active decode.

    Deflaked (flagged in PR 9: passed standalone, failed in-suite): the
    drain deadline was 60s, but in-suite this machine can spend most of
    that compiling decode buckets for the 64-token in-flight request —
    blowing the deadline expires the request instead of completing it.
    The deadline is sized for a loaded CI box now (the drain still exits
    the moment the request finishes; the budget is a ceiling, not a
    sleep), and every wait below synchronizes on an observable state
    transition (healthz pending / draining, process exit) rather than a
    fixed wall-time margin.

    Deflaked again (PR 28): with the compile cache warm (``.jax_cache`` in
    the checkout, PR 21) the toy model's 64-token answer was over inside
    one 0.1 s poll of ``/healthz``, so ``pending >= 1`` was never seen, the
    SIGTERM met an idle server and nothing reported ``draining``.  The
    in-flight answer is 900 tokens now (225 windows): long enough to be
    observed and to be drained, whatever the cache holds."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO_ROOT, "bin", "dstpu-serve"),
         "--port", "0", "--bind", "127.0.0.1", "--max-tokens", "16",
         "--max-seqs", "4", "--max-ctx", "1024", "--block-size", "8",
         "--window-steps", "4", "--drain-deadline", "300",
         "--telemetry-dir", "/tmp/dstpu_serve_smoke_tel"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    port = None
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                break
            if "dstpu-serve listening on" in line:
                port = int(line.rsplit(":", 1)[1])
                break
        check("drain: server came up", port is not None)
        if port is None:
            return
        # keep draining the child's stdout: a full pipe buffer blocks the
        # child's next log write — including the drain handler's own log
        # line — wedging the very shutdown path under test
        tail = []

        def _pump():
            for line in proc.stdout:
                tail.append(line)
                del tail[:-50]

        threading.Thread(target=_pump, daemon=True).start()
        base = f"http://127.0.0.1:{port}"
        code, body = _http("GET", f"{base}/healthz")
        check("drain: healthz healthy before", code == 200
              and body.get("status") == "healthy", f"{code} {body}")

        result = {}

        def long_request():
            result["resp"] = _http(
                "POST", f"{base}/v1/generate",
                {"prompt": [5, 6, 7], "max_new_tokens": DRAIN_TOKENS},
                timeout=400)

        t = threading.Thread(target=long_request, daemon=True)
        t.start()
        # wait until the request is genuinely in flight (admitted counter)
        deadline = time.monotonic() + 120
        inflight = False
        while time.monotonic() < deadline and not inflight:
            code, body = _http("GET", f"{base}/healthz")
            inflight = (body.get("pending") or 0) >= 1
            time.sleep(0.1)
        check("drain: request in flight before SIGTERM", inflight)

        proc.send_signal(signal.SIGTERM)
        # /healthz flips to draining (503) while the decode finishes —
        # poll the STATE TRANSITION, bounded only by the widened drain
        # budget (the 64-token decode keeps the server alive far longer
        # than the flip takes; exit-before-observation means drain broke)
        saw_draining = False
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline and not saw_draining \
                and proc.poll() is None:
            try:
                code, body = _http("GET", f"{base}/healthz", timeout=5)
            except Exception:  # noqa: BLE001 — server may already be gone
                break
            saw_draining = code == 503 and body.get("status") == "draining"
            if not saw_draining:
                time.sleep(0.05)   # throttle: don't hammer the draining box
        check("drain: healthz reported draining", saw_draining)
        # new requests are shed with 503 + Retry-After while draining
        try:
            code, body = _http("POST", f"{base}/v1/generate",
                               {"prompt": [1, 2], "max_new_tokens": 4},
                               timeout=10)
            check("drain: new request shed with 503",
                  code == 503 and body.get("reason") == "draining",
                  f"{code} {body}")
        except Exception as exc:  # noqa: BLE001
            # On a slow box the in-flight decode can finish — and the
            # server exit cleanly — between observing `draining` and this
            # probe landing.  ONLY that race is excused: the server must
            # already be gone (or in its final sub-second teardown) when
            # the probe failed, hence the short grace.  A server that is
            # still draining its 64-token decode but refuses connections
            # (e.g. a listener closed at SIGTERM) outlives the grace by
            # tens of seconds and still fails.  The shed-while-draining
            # response itself stays unit-tested (test_serving_lifecycle,
            # test_serving_server).
            exited_clean = False
            try:
                exited_clean = proc.wait(timeout=5) == 0
            except subprocess.TimeoutExpired:
                pass
            check("drain: new request shed with 503", exited_clean,
                  f"server unreachable during drain and not exited "
                  f"5s later: {exc!r}")

        rc = proc.wait(timeout=330)
        check("drain: exit 0 within the drain deadline", rc == 0,
              f"rc={rc}")
        t.join(timeout=60)
        code, resp = result.get("resp", (None, None))
        check("drain: in-flight request completed",
              code == 200 and resp and resp.get("state") == "finished"
              and len(resp.get("tokens") or []) == DRAIN_TOKENS,
              f"code={code} resp={str(resp)[:200]}")
    except Exception as exc:  # noqa: BLE001
        check("drain scenario", False, repr(exc)[-300:])
    finally:
        if proc.poll() is None:
            proc.kill()


def _spawn(argv_tail, marker, telemetry_dir, timeout=120):
    """Start a bin/ server subprocess and read its bound port off the
    '<marker> listening on' stdout line; returns (proc, port, tail).

    The banner wait runs on a reader thread: a child that wedges before
    printing (stdout open, nothing coming) must fail THIS deadline, not
    sit in a blocked readline() until some outer test timeout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable] + argv_tail +
        ["--telemetry-dir", telemetry_dir],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    found = threading.Event()
    state = {"port": None}
    tail = []

    def _pump():
        for line in proc.stdout:
            if not found.is_set() and f"{marker} listening on" in line:
                state["port"] = int(line.rsplit(":", 1)[1])
                found.set()
            tail.append(line)
            del tail[:-50]
        found.set()                     # EOF: child died before the banner

    threading.Thread(target=_pump, daemon=True).start()
    found.wait(timeout)
    return proc, state["port"], tail


def scenario_fleet(check):
    """Real processes: dstpu-router over two --prefix-cache dstpu-serve
    replicas.  Prefix pair lands a cache hit bit-identical to the cold
    replica; SIGTERM-draining one replica loses zero streams."""
    procs = []
    try:
        ports = []
        for i in range(2):
            proc, port, _tail = _spawn(
                [os.path.join(REPO_ROOT, "bin", "dstpu-serve"),
                 "--port", "0", "--bind", "127.0.0.1",
                 "--max-tokens", "32", "--max-seqs", "4",
                 "--max-ctx", "96", "--block-size", "8",
                 "--window-steps", "4", "--prefix-cache",
                 "--drain-deadline", "300"],
                "dstpu-serve", f"/tmp/dstpu_fleet_smoke_tel{i}")
            procs.append(proc)
            ports.append(port)
        check("fleet: both replicas came up", all(ports), f"{ports}")
        if not all(ports):
            return
        rproc, rport, _rtail = _spawn(
            [os.path.join(REPO_ROOT, "bin", "dstpu-router"),
             "--port", "0", "--bind", "127.0.0.1",
             "--replica", f"127.0.0.1:{ports[0]}",
             "--replica", f"127.0.0.1:{ports[1]}",
             "--poll", "0.3", "--drain-deadline", "60"],
            "dstpu-router", "/tmp/dstpu_fleet_smoke_rtel")
        procs.append(rproc)
        check("fleet: router came up", rport is not None)
        if rport is None:
            return
        base = f"http://127.0.0.1:{rport}"
        rep = [f"http://127.0.0.1:{p}" for p in ports]

        code, body = _http("GET", f"{base}/healthz", timeout=30)
        check("fleet: router healthz healthy with 2 routable",
              code == 200 and body.get("routable") == 2, f"{code} {body}")

        # -- prefix-cached pair on replica 0, cold oracle on replica 1 --
        sys_prefix = [7, 3, 9, 4, 11, 6, 2, 8, 13, 5]
        pair = [sys_prefix + [21], sys_prefix + [33, 34]]
        for prompt in pair:
            code, warm = _http("POST", f"{rep[0]}/v1/generate",
                               {"prompt": prompt, "max_new_tokens": 6},
                               timeout=300)
            check(f"fleet: warm replica answered ({prompt[-1]})",
                  code == 200, f"{code} {warm}")
        code, cold = _http("POST", f"{rep[1]}/v1/generate",
                           {"prompt": pair[1], "max_new_tokens": 6},
                           timeout=300)
        check("fleet: prefix hit bit-exact vs cold replica",
              code == 200 and warm.get("tokens") == cold.get("tokens"),
              f"warm={warm.get('tokens')} cold={cold.get('tokens')}")
        code, health = _http("GET", f"{rep[0]}/healthz", timeout=30)
        hits = (health.get("counters") or {}).get("serving/prefix_hits", 0)
        check("fleet: replica 0 counted a prefix-cache hit", hits >= 1,
              f"counters={health.get('counters')}")

        # -- SIGTERM drain of replica 0 with zero failed streams -------
        results = {}

        def via_router(key, n_new):
            results[key] = _http(
                "POST", f"{base}/v1/generate",
                {"prompt": [5, 6, 7, key], "max_new_tokens": n_new},
                timeout=400)

        tin = threading.Thread(target=via_router, args=(1, 48),
                               daemon=True)
        tin.start()
        time.sleep(1.0)                 # let it land somewhere
        procs[0].send_signal(signal.SIGTERM)
        # new work keeps flowing while replica 0 drains
        t2 = threading.Thread(target=via_router, args=(2, 8), daemon=True)
        t2.start()
        rc = procs[0].wait(timeout=330)
        check("fleet: drained replica exited 0", rc == 0, f"rc={rc}")
        tin.join(timeout=120)
        t2.join(timeout=120)
        for key in (1, 2):
            code, body = results.get(key, (None, None))
            check(f"fleet: stream {key} survived the drain",
                  code == 200 and body.get("state") == "finished",
                  f"code={code} body={str(body)[:200]}")
        code, body = _http("GET", f"{base}/healthz", timeout=30)
        check("fleet: router still routable after drain",
              code == 200 and body.get("routable", 0) >= 1,
              f"{code} {body}")
    except Exception as exc:  # noqa: BLE001
        check("fleet scenario", False, repr(exc)[-300:])
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()


def scenario_trace(check):
    """Real processes: router with --disagg-threshold over a prefill
    replica (block 16) and a decode replica (block 8).  One long-prompt
    request disaggregates; the merged trace on the router must carry the
    full set of segment kinds across both replicas, resolve via
    /traces?request=, and render via bin/dstpu-trace --request."""
    import shutil

    rtel = "/tmp/dstpu_trace_smoke_rtel"
    shutil.rmtree(rtel, ignore_errors=True)
    procs = []
    try:
        specs = [("decode", "8", "/tmp/dstpu_trace_smoke_tel0"),
                 ("prefill", "16", "/tmp/dstpu_trace_smoke_tel1")]
        ports = {}
        for role, block, tel in specs:
            proc, port, _tail = _spawn(
                [os.path.join(REPO_ROOT, "bin", "dstpu-serve"),
                 "--port", "0", "--bind", "127.0.0.1",
                 "--max-tokens", "32", "--max-seqs", "4",
                 "--max-ctx", "96", "--block-size", block,
                 "--window-steps", "4", "--trace-sample", "1"],
                "dstpu-serve", tel)
            procs.append(proc)
            ports[role] = port
        check("trace: both replicas came up", all(ports.values()),
              f"{ports}")
        if not all(ports.values()):
            return
        rproc, rport, _rtail = _spawn(
            [os.path.join(REPO_ROOT, "bin", "dstpu-router"),
             "--port", "0", "--bind", "127.0.0.1",
             "--replica", f"127.0.0.1:{ports['decode']}",
             "--prefill-replica", f"127.0.0.1:{ports['prefill']}",
             "--disagg-threshold", "8", "--poll", "0.3",
             "--trace-sample", "1"],
            "dstpu-router", rtel)
        procs.append(rproc)
        check("trace: router came up", rport is not None)
        if rport is None:
            return
        base = f"http://127.0.0.1:{rport}"
        prompt = [3, 5, 7, 11, 13, 17, 19, 23, 29, 31]
        code, out = _http("POST", f"{base}/v1/generate",
                          {"prompt": prompt, "max_new_tokens": 24},
                          timeout=300)
        tid = (out or {}).get("trace_id")
        check("trace: disagg request finished with a trace id",
              code == 200 and out.get("state") == "finished" and tid,
              f"{code} {str(out)[:200]}")
        if not tid:
            return
        code, rec = _http("GET", f"{base}/traces?request={tid}",
                          timeout=30)
        kinds = {s.get("kind") for s in (rec or {}).get("spans") or []}
        comps = {s.get("component") for s in (rec or {}).get("spans") or []}
        check("trace: merged waterfall has queue/prefill/kv_ship/decode "
              "segments",
              code == 200
              and {"queue_wait", "prefill", "kv_ship_encode",
                   "kv_ship_wire", "kv_ship_import"} <= kinds
              and ("decode_window" in kinds or "compile" in kinds),
              f"code={code} kinds={sorted(k for k in kinds if k)}")
        check("trace: spans from router AND both replicas",
              len(comps) >= 3 and "router" in comps,
              f"components={sorted(c for c in comps if c)}")
        # the router wrote the merged trace through to traces.jsonl —
        # the offline CLI must render the same request
        cli = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "bin", "dstpu-trace"),
             rtel, "--request", tid],
            capture_output=True, text=True, timeout=120)
        check("trace: dstpu-trace --request renders the waterfall",
              cli.returncode == 0 and tid in cli.stdout
              and "kv_ship_wire" in cli.stdout
              and "queue_wait" in cli.stdout,
              f"rc={cli.returncode} out={cli.stdout[-300:]}"
              f"{cli.stderr[-200:]}")
    except Exception as exc:  # noqa: BLE001
        check("trace scenario", False, repr(exc)[-300:])
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--scenario", default="all",
                   choices=["all", "decode", "lifecycle", "drain",
                            "specdec", "fleet", "trace"])
    args = p.parse_args(argv)

    failures = []

    def check(name: str, ok: bool, detail: str = ""):
        if not ok:
            failures.append(f"{name}: {detail}")

    try:
        import jax  # noqa: F401 — fail fast with a clear import error

        import deepspeed_tpu.inference.v2.engine_v2  # noqa: F401
    except Exception as exc:  # noqa: BLE001
        print(f"serving stack import failed: {exc!r}")
        return 1

    if args.scenario in ("all", "decode"):
        scenario_decode(check)
    if args.scenario in ("all", "lifecycle"):
        scenario_lifecycle(check)
    if args.scenario in ("all", "specdec"):
        scenario_specdec(check)
    if args.scenario in ("all", "drain"):
        scenario_drain(check)
    if args.scenario in ("all", "fleet"):
        scenario_fleet(check)
    if args.scenario in ("all", "trace"):
        scenario_trace(check)

    for url, code, body in SHED_BODIES:
        check("shed response attributed to a tenant",
              bool(body.get("tenant")),
              f"{code} from {url} carried no tenant: {str(body)[:150]}")

    if failures:
        print("\n".join(failures))
        print(f"\n{len(failures)} serving smoke check(s) failed "
              f"(tools/check_serving_smoke.py)")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
