#!/usr/bin/env python
"""Real-process smoke for the elastic fleet: dstpu-fleet must scale a
live router in BOTH directions under load, with zero non-shed failures
and every shed attributed to a tenant.

One operator-registered ``dstpu-serve`` replica sits behind a
``dstpu-router`` carrying a rate-limited ``bulk`` tenant class; a
``dstpu-fleet`` controller (min=1, max=2, hair-trigger drain SLO, short
cooldown) watches the router.  A mixed-tenant burst (flooding ``bulk``
+ steady ``interactive``) must push the controller to spawn a second
replica (scale-up observed on ``/replicas``); going idle must make it
SIGTERM-drain its own spawn back down (scale-down observed).  Along the
way:

  * every client response is a 200 ``finished`` or a tenant-attributed
    429/503 shed — anything else is a dropped request and fails;
  * the flooded ``bulk`` tenant actually sheds (the QoS quota bit), and
    those sheds show up in the router's per-tenant accounting;
  * the controller exits 0 on SIGTERM and (``--on-exit drain``) takes
    its spawned replica down with it.

Enforced tier-1 from ``tests/unit/test_fleet_autoscale.py`` the same
way check_serving_smoke.py is, so the autoscaling path can't rot between
chip runs.

Usage: ``python tools/check_fleet_scale.py``; exit 1 lists what broke.
"""
from __future__ import annotations

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
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from check_serving_smoke import _http, _spawn  # noqa: E402

SERVE_FLAGS = ["--max-tokens", "32", "--max-seqs", "4", "--max-ctx", "96",
               "--block-size", "8", "--window-steps", "4",
               "--drain-deadline", "120"]


def run(check) -> None:
    procs = []
    fleet_proc = None
    try:
        # -- operator replica + QoS router ----------------------------- #
        sproc, sport, _ = _spawn(
            [os.path.join(REPO_ROOT, "bin", "dstpu-serve"),
             "--port", "0", "--bind", "127.0.0.1"] + SERVE_FLAGS,
            "dstpu-serve", "/tmp/dstpu_fleet_scale_tel0")
        procs.append(sproc)
        check("scale: seed replica came up", sport is not None)
        if sport is None:
            return
        rproc, rport, rtail = _spawn(
            [os.path.join(REPO_ROOT, "bin", "dstpu-router"),
             "--port", "0", "--bind", "127.0.0.1",
             "--replica", f"127.0.0.1:{sport}", "--poll", "0.3",
             "--tenant-class", "bulk:priority=-1,rate=8,burst=12"],
            "dstpu-router", "/tmp/dstpu_fleet_scale_rtel")
        procs.append(rproc)
        check("scale: router came up", rport is not None)
        if rport is None:
            return
        base = f"http://127.0.0.1:{rport}"

        # -- the controller under test --------------------------------- #
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        fleet_proc = subprocess.Popen(
            [sys.executable, os.path.join(REPO_ROOT, "bin", "dstpu-fleet"),
             "--router", base, "--poll", "0.5",
             "--min-replicas", "1", "--max-replicas", "2",
             "--drain-high", "0.001", "--drain-low", "5.0",
             "--hysteresis-up", "1", "--hysteresis-down", "3",
             "--cooldown", "2.0", "--spawn-timeout", "240",
             "--telemetry-dir", "/tmp/dstpu_fleet_scale_ctel"]
            + [f"--replica-flag={SERVE_FLAGS[i]}={SERVE_FLAGS[i + 1]}"
               for i in range(0, len(SERVE_FLAGS), 2)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env)
        ftail = []

        def _pump():
            for line in fleet_proc.stdout:
                ftail.append(line)
                del ftail[:-60]

        threading.Thread(target=_pump, daemon=True).start()

        # -- mixed-tenant load until scale-up is observed -------------- #
        stop_load = threading.Event()
        outcomes = []          # (tenant, code, body) per completed request
        olock = threading.Lock()

        def client(tenant, max_new):
            i = 0
            while not stop_load.is_set():
                i += 1
                try:
                    code, body = _http(
                        "POST", f"{base}/v1/generate",
                        {"prompt": [3 + i % 7, 5, 7, 11],
                         "max_new_tokens": max_new, "tenant": tenant},
                        timeout=300)
                except Exception as exc:  # noqa: BLE001
                    code, body = None, {"error": repr(exc)}
                with olock:
                    outcomes.append((tenant, code, body))
                time.sleep(0.1)     # don't spin on instant 429s

        loaders = ([threading.Thread(target=client, args=("interactive", 8),
                                     daemon=True) for _ in range(4)]
                   + [threading.Thread(target=client, args=("bulk", 4),
                                       daemon=True) for _ in range(4)])
        for t in loaders:
            t.start()

        # Keep the load on until the controller has scaled up AND the
        # flooded bulk tenant has actually been rate-shed at least once
        # (with a hair-trigger drain SLO, scale-up can land within a
        # couple of requests — too soon for the quota bucket to drain).
        scaled_up = False
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            try:
                code, body = _http("GET", f"{base}/healthz", timeout=15)
                scaled_up = scaled_up or int(body.get("registered") or 0) >= 2
            except Exception:  # noqa: BLE001
                pass
            with olock:
                n_done = len(outcomes)
                bulk_shed_seen = any(t == "bulk" and c == 429
                                     for t, c, _ in outcomes)
            if scaled_up and n_done >= 24 and bulk_shed_seen:
                break
            time.sleep(1.0)
        check("scale: controller scaled UP to 2 replicas", scaled_up,
              f"controller tail: {''.join(ftail[-12:])[-600:]}")

        stop_load.set()
        for t in loaders:
            t.join(timeout=330)

        # -- idle: the controller must scale its own spawn back down --- #
        scaled_down = False
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline and not scaled_down:
            try:
                code, body = _http("GET", f"{base}/healthz", timeout=15)
                live = [r for r in body.get("replicas") or []
                        if not r.get("lost")]
                scaled_down = scaled_up and len(live) <= 1
            except Exception:  # noqa: BLE001
                pass
            time.sleep(1.0)
        check("scale: controller scaled DOWN back to 1 replica",
              scaled_down,
              f"controller tail: {''.join(ftail[-12:])[-600:]}")

        # -- zero non-shed failures, every shed tenant-attributed ------ #
        bad = [(t, c, str(b)[:120]) for t, c, b in outcomes
               if not (c == 200 and b.get("state") == "finished")
               and not (c in (429, 503) and b.get("tenant"))]
        check("scale: zero non-shed failures across the run", not bad,
              f"{len(bad)} of {len(outcomes)}: {bad[:4]}")
        check("scale: enough traffic to mean anything",
              len(outcomes) >= 20, f"only {len(outcomes)} requests")
        bulk_sheds = sum(1 for t, c, b in outcomes
                         if t == "bulk" and c == 429)
        check("scale: flooded bulk tenant was rate-shed", bulk_sheds >= 1,
              f"outcomes={len(outcomes)}")
        code, body = _http("GET", f"{base}/healthz", timeout=15)
        tens = body.get("tenants") or {}
        check("scale: router accounts the bulk sheds per tenant",
              (tens.get("bulk") or {}).get("shed", 0) >= 1,
              f"tenants={json.dumps(tens)[:300]}")

        # -- controller teardown: exit 0, spawned replica drained ------ #
        fleet_proc.send_signal(signal.SIGTERM)
        rc = fleet_proc.wait(timeout=240)
        check("scale: controller exited 0 on SIGTERM", rc == 0,
              f"rc={rc} tail: {''.join(ftail[-8:])[-400:]}")
    except Exception as exc:  # noqa: BLE001
        check("fleet scale scenario", False, repr(exc)[-300:])
    finally:
        if fleet_proc is not None and fleet_proc.poll() is None:
            fleet_proc.kill()
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()


def main(argv=None) -> int:
    failures = []

    def check(name: str, ok: bool, detail: str = ""):
        if not ok:
            failures.append(f"{name}: {detail}")

    run(check)
    if failures:
        print("\n".join(failures))
        print(f"\n{len(failures)} fleet scale check(s) failed "
              f"(tools/check_fleet_scale.py)")
        return 1
    print("fleet scale smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
