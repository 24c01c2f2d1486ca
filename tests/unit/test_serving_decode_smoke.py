"""CI gate for the serving smoke check (tools/check_serving_smoke.py):
`InferenceEngineV2` prefill → fused 4-token decode under both attention
impls, the request-lifecycle scenario (deadline expiry mid-window with
block reclaim + unperturbed survivor stream), the speculative-decoding
scenario (planted-repetition prompt → n-gram drafter accepts >=1
multi-token verify window → stream bit-identical to vanilla → blocks
reclaimed, both impls), the real `dstpu-serve` graceful-drain scenario
(SIGTERM during active decode → draining healthz → 503 for new work →
completed in-flight response → exit 0), and the FLEET scenario (real
`dstpu-router` over two `--prefix-cache` replicas: prefix-cached request
pair answers bit-identically to the cold replica with a counted cache
hit; SIGTERM-draining one replica loses zero streams and exits 0), and
the TRACE scenario (real disaggregated router: one request produces ONE
merged trace with queue/prefill/kv_ship/decode segments from both
replicas, resolvable via /traces and rendered by dstpu-trace) — all
on the CPU sim, same enforcement pattern as the no-bare-print lint, so
the serving stack cannot rot silently between chip runs."""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.serving

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHECK = os.path.join(REPO_ROOT, "tools", "check_serving_smoke.py")


class TestServingSmoke:
    def test_smoke_check_passes(self):
        """This IS the CI gate: every scenario (decode parity + roofline,
        lifecycle expiry/reclaim, spec-dec bit-exactness + acceptance,
        dstpu-serve drain, fleet router + prefix-cache + replica drain,
        disaggregated request tracing) must hold."""
        proc = subprocess.run([sys.executable, CHECK],
                              capture_output=True, text=True, timeout=900)
        assert proc.returncode == 0, \
            f"serving smoke checks failed:\n{proc.stdout}" \
            f"{proc.stderr[-1000:]}"
