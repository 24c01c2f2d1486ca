#!/usr/bin/env python
"""Gate the memory observability plane end to end, real processes.

The memory-tiering work this PR stages (spill cold KV pages to a host
tier) is only plannable if the whole observability chain holds together:
a real ``bin/dstpu-serve`` publishes a CONSERVED ``/memory`` ledger while
decoding → the router rolls replica ledgers into one fleet view → the
serve loop records ``kv_heat`` events → ``bin/dstpu-mem`` turns a
recorded heat trace into the what-if-spill table that names the cold
set.  Any link rotting (a bucket source unregistered, the heat tracker
drifting from the allocator, the event schema renamed) breaks silently
without silicon — so this is enforced from
``tests/unit/test_mem_obs_smoke.py`` the same way the serving smoke
checks are.

Checks:
  * serve: a real dstpu-serve answers ``/memory`` mid-decode with a
    conserved snapshot (params + kv_pages attributed, live KV pages
    visible) and drains clean on SIGTERM.
  * cli: ``bin/dstpu-mem --url`` renders the live occupancy ledger.
  * fleet: an in-process FleetRouter scraping two real replicas serves a
    ``/memory`` rollup whose totals are exactly the sum of the replica
    ledgers it scraped.
  * trace: the drained serve telemetry dir contains kv_heat events.
  * what-if: an in-process 32k-context prefix-cache scenario (common
    prefix goes cold in the trie, later requests re-graft it) recorded
    as a heat trace; ``bin/dstpu-mem`` names a concrete non-empty
    spillable cold set and a positive avoided-recompute estimate.

Usage: ``python tools/check_mem_obs.py``.  Exit status 1 lists what
broke.
"""
from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


def _spawn_serve(tel_dir, timeout=120):
    """One dstpu-serve on a kernel-assigned port, banner-parsed (same
    pattern as tools/check_goodput.py)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO_ROOT, "bin", "dstpu-serve"),
         "--port", "0", "--bind", "127.0.0.1", "--max-tokens", "32",
         "--max-seqs", "4", "--max-ctx", "96", "--block-size", "8",
         "--window-steps", "4", "--drain-deadline", "300",
         "--telemetry-dir", tel_dir],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    found = threading.Event()
    state = {"port": None}
    tail = []

    def _pump():
        for line in proc.stdout:
            if not found.is_set() and "dstpu-serve listening on" in line:
                state["port"] = int(line.rsplit(":", 1)[1])
                found.set()
            tail.append(line)
            del tail[:-50]
        found.set()

    threading.Thread(target=_pump, daemon=True).start()
    found.wait(timeout)
    return proc, state["port"], tail


def _get(port, path, timeout=30):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
        return json.loads(r.read())


def _post(port, body, timeout=300):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _stop(proc):
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
    try:
        return proc.wait(timeout=330)
    except subprocess.TimeoutExpired:
        proc.kill()
        return -9


def _record_32k_trace(tel_dir):
    """The staging scenario for the host-offload tier: a 32k-context
    engine with the radix prefix cache on.  Wave A shares a long system
    prefix and retires (the trie keeps the pages — they go COLD); wave B
    decodes unrelated prompts (windows advance past the cold
    thresholds); wave C re-grafts the prefix (each graft is a would-be
    host-tier hit).  Every settle point emits a ``kv_heat`` event, so
    the recorded trace is exactly what dstpu-mem's what-if table eats.
    """
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2,
        RaggedInferenceEngineConfig,
    )
    from deepspeed_tpu.inference.v2.lifecycle import (
        LifecycleScheduler,
        ServeRequest,
    )
    from deepspeed_tpu.models.transformer import CausalLM, \
        TransformerConfig
    from deepspeed_tpu.telemetry.hub import Telemetry

    cfg = TransformerConfig.tiny(use_flash=False)
    model = CausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = InferenceEngineV2(model, params, RaggedInferenceEngineConfig(
        max_tokens=64, max_seqs=4, max_ctx=32768, block_size=64,
        num_blocks=96, dtype=jnp.float32, attn_impl="gather",
        prefix_cache=True))
    tel = Telemetry(output_dir=tel_dir, chrome_trace=False,
                    prometheus=False)

    def snap_event():
        snap = eng.memory_snapshot()
        if snap:
            tel.event("kv_heat", component="gate32k", **snap)

    prefix = [(7 + 13 * i) % 97 + 2 for i in range(1024)]  # 16 pages
    sched = LifecycleScheduler(eng, window_steps=4, max_queue=64)
    uid = iter(range(1, 1000))

    def wave(prompts, max_new=8, tenant=None):
        uids = []
        for p in prompts:
            u = next(uid)
            uids.append(u)
            sched.submit(ServeRequest(uid=u, prompt=p,
                                      max_new_tokens=max_new,
                                      tenant=tenant))
        sched.run_until_idle()
        snap_event()
        return uids

    # wave A: three tenants share the system prefix, then retire —
    # the trie keeps the prefix pages alive with no sequence holder
    wave([prefix + [200 + i, 201, 202] for i in range(3)],
         tenant="bulk")
    # wave B: unrelated short prompts; enough decode windows pass for
    # the trie-held prefix pages to age well past the cold thresholds
    for r in range(4):
        wave([[5 + r, 9 + i, 13, 17] for i in range(2)], max_new=24,
             tenant="interactive")
    # wave C: the prefix comes back — admission grafts the cold pages
    # (each graft touch is the retouch the what-if estimator counts)
    wave([prefix + [300 + i, 301] for i in range(2)], tenant="bulk")
    snap_event()
    tel.close()
    return eng


def main(argv=None) -> int:
    failures = []

    def check(name, ok, detail=""):
        if not ok:
            failures.append(f"{name}: {detail}")

    tel_a = "/tmp/dstpu_mem_gate_a"
    tel_b = "/tmp/dstpu_mem_gate_b"
    tel_32k = "/tmp/dstpu_mem_gate_32k"
    report_path = "/tmp/dstpu_mem_gate_report.json"
    for d in (tel_a, tel_b, tel_32k):
        shutil.rmtree(d, ignore_errors=True)

    # ---- serve phase: conserved /memory mid-decode ------------------- #
    proc_a, port_a, tail_a = _spawn_serve(tel_a)
    proc_b, port_b, tail_b = _spawn_serve(tel_b)
    try:
        check("serve: replica A came up", port_a is not None,
              "".join(tail_a[-10:]))
        check("serve: replica B came up", port_b is not None,
              "".join(tail_b[-10:]))
        if port_a is None or port_b is None:
            return _finish(failures)

        results = {}

        def bg_post(key, port, max_new):
            try:
                results[key] = _post(port, {"prompt": [3, 5, 7, 11],
                                            "max_new_tokens": max_new,
                                            "tenant": "gate"})
            except Exception as e:  # noqa: BLE001 — checked below
                results[key] = {"error": repr(e)}

        t_a = threading.Thread(target=bg_post, args=("a", port_a, 48),
                               daemon=True)
        t_a.start()
        mid = None
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                snap = _get(port_a, "/memory", timeout=10)
            except Exception:  # noqa: BLE001 — server still warming
                time.sleep(0.1)
                continue
            kv = snap.get("kv") or {}
            if snap.get("conserved") and kv.get("live_pages"):
                mid = snap
                break
            time.sleep(0.1)
        t_a.join(timeout=300)
        check("serve: request finished",
              results.get("a", {}).get("state") == "finished",
              str(results.get("a"))[:200])
        check("serve: conserved /memory observed mid-decode",
              mid is not None, "never saw conserved snapshot with live "
              "KV pages within 60s")
        if mid:
            buckets = mid.get("buckets") or {}
            check("serve: params bucket attributed",
                  buckets.get("params", 0) > 0, str(buckets)[:200])
            check("serve: kv_pages bucket attributed",
                  buckets.get("kv_pages", 0) > 0, str(buckets)[:200])
            frac = mid.get("unattributed_frac")     # 0.0 is a reading
            check("serve: unattributed within bound",
                  frac is not None and abs(frac) <= 0.02,
                  f"unattributed_frac={frac}")

        # ---- cli phase: live ledger render --------------------------- #
        cli = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "bin", "dstpu-mem"),
             "--url", f"http://127.0.0.1:{port_a}"],
            capture_output=True, text=True, timeout=120)
        check("cli: dstpu-mem --url exit 0", cli.returncode == 0,
              f"rc={cli.returncode} err={cli.stderr[-200:]}")
        check("cli: occupancy ledger rendered",
              "HBM occupancy ledger" in cli.stdout
              and "kv_pages" in cli.stdout, cli.stdout[-300:])

        # ---- fleet phase: router rollup sums the replica ledgers ----- #
        _post(port_b, {"prompt": [2, 4, 6], "max_new_tokens": 8,
                       "tenant": "gate"})
        from deepspeed_tpu.serving.fleet import FleetRouter, RouterServer

        router = FleetRouter(poll_s=60.0)          # scrape on demand
        router.add_replica(f"127.0.0.1:{port_a}", name="ra")
        router.add_replica(f"127.0.0.1:{port_b}", name="rb")
        router.scrape_all()
        _, body = router.health()
        roll = body.get("memory") or {}
        scraped = [r.get("memory") for r in router.snapshot()
                   if r.get("memory")]
        check("fleet: rollup covers both replicas",
              roll.get("processes") == 2 and len(scraped) == 2,
              f"processes={roll.get('processes')} "
              f"scraped={len(scraped)}")
        want_live = sum(float(s.get("live_bytes") or 0) for s in scraped)
        check("fleet: rollup live_bytes is the sum of replica ledgers",
              abs(float(roll.get("live_bytes") or 0) - want_live) < 1.0,
              f"rollup={roll.get('live_bytes')} sum={want_live}")
        want_kv = sum(float((s.get("buckets") or {}).get("kv_pages") or 0)
                      for s in scraped)
        check("fleet: rollup kv_pages bucket sums",
              abs(float((roll.get("buckets") or {}).get("kv_pages") or 0)
                  - want_kv) < 1.0,
              f"rollup={roll.get('buckets')} sum={want_kv}")
        rs = RouterServer(router, port=0, bind="127.0.0.1").start()
        try:
            http_roll = _get(rs.port, "/memory")
            check("fleet: router /memory serves the rollup",
                  set((http_roll.get("replicas") or {})) == {"ra", "rb"},
                  str(http_roll)[:200])
        finally:
            rs.stop()
    finally:
        rc_a = _stop(proc_a)
        rc_b = _stop(proc_b)
    check("serve: replica A drained clean", rc_a == 0, f"rc={rc_a}")
    check("serve: replica B drained clean", rc_b == 0, f"rc={rc_b}")

    # ---- trace phase: serve recorded kv_heat events ------------------ #
    from deepspeed_tpu.telemetry.memreport import read_heat_trace

    evs = read_heat_trace(tel_a)
    check("trace: serve recorded kv_heat events", len(evs) >= 1,
          f"{len(evs)} events under {tel_a}")

    # ---- what-if phase: 32k prefix scenario → dstpu-mem report ------- #
    eng = _record_32k_trace(tel_32k)
    check("what-if: engine saw prefix sharing",
          (eng.memory_snapshot() or {}).get("allocs_total", 0) > 0
          and eng.heat is not None and eng.heat.transfers >= 0,
          str(eng.memory_snapshot())[:200])
    cli = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bin", "dstpu-mem"),
         tel_32k, "--thresholds", "2,4", "--host-mb", "0.25,1,4",
         "--json", report_path],
        capture_output=True, text=True, timeout=120)
    check("what-if: dstpu-mem exit 0", cli.returncode == 0,
          f"rc={cli.returncode} err={cli.stderr[-300:]}")
    check("what-if: report names the spillable cold set",
          "spillable cold set:" in cli.stdout
          and "what-if host-offload spill" in cli.stdout,
          cli.stdout[-300:])
    rows = []
    if os.path.exists(report_path):
        with open(report_path) as f:
            rows = json.load(f).get("what_if") or []
    check("what-if: candidate table non-empty", len(rows) >= 4,
          f"{len(rows)} rows")
    cold = [r for r in rows if r["peak_cold_pages"] > 0]
    check("what-if: a concrete cold set exists (MB > 0)",
          any(r["peak_cold_mb"] > 0 for r in cold),
          json.dumps(rows[:4]))
    check("what-if: re-grafts count as avoided recompute",
          any(r["avoided_recompute_tokens"] > 0 for r in rows),
          json.dumps(rows[:4]))
    return _finish(failures)


def _finish(failures) -> int:
    if failures:
        print("\n".join(failures))
        print(f"\n{len(failures)} memory observability gate check(s) "
              f"failed (tools/check_mem_obs.py)")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
