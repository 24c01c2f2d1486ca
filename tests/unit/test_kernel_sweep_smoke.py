"""CI gate for the kernel_sweep bench (tools/check_kernel_sweep.py): all
four kernel families (flash, decode_paged, fused_wire, fused_gemm) run end
to end on the CPU sim, every roofline row is finite and physically
plausible (0 < %-of-peak < 100 — the flash_sweep >peak artifact class is
rejected), bound classification matches the analytic AI model, and the
kernels/* gauges are published — same enforcement pattern as
check_comm_sweep.py, so the kernel roofline table cannot rot silently
between chip runs."""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.kernels

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHECK = os.path.join(REPO_ROOT, "tools", "check_kernel_sweep.py")


class TestKernelSweepSmoke:
    def test_kernel_sweep_check_passes(self):
        """This IS the CI gate: sweep → roofline table → gauges on the
        CPU sim, inside the ~60 s subprocess budget."""
        proc = subprocess.run([sys.executable, CHECK],
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, \
            f"kernel_sweep checks failed:\n{proc.stdout}{proc.stderr[-1500:]}"
