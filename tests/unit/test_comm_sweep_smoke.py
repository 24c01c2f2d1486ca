"""CI gate for the comm_sweep bench + selector smoke check
(tools/check_comm_sweep.py): the flat-vs-2hop × wire grid runs end to end
on the CPU sim, predicted collective bytes track the jaxpr-measured bytes,
the CollectiveAlgoSelector's measured re-tune picks the measured-fastest
config, and the comm/* gauges are published — same enforcement pattern as
check_serving_smoke.py, so the hierarchical/quantized collective stack
cannot rot silently between chip runs."""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.comm

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHECK = os.path.join(REPO_ROOT, "tools", "check_comm_sweep.py")


class TestCommSweepSmoke:
    def test_comm_sweep_check_passes(self):
        """This IS the CI gate: sweep → selector → gauges on the CPU sim."""
        proc = subprocess.run([sys.executable, CHECK],
                              capture_output=True, text=True, timeout=840)
        assert proc.returncode == 0, \
            f"comm_sweep checks failed:\n{proc.stdout}{proc.stderr[-1500:]}"
