"""Localhost pod-launch rehearsal: the real ``bin/dstpu``
CLI fans out N distinct processes with the per-rank env contract, each
process runs ``deepspeed_tpu.init_distributed`` against a real
``jax.distributed`` coordinator, and a cross-process collective agrees —
so a physical pod slice becomes a hostfile change, not new code.

Reference semantics: deepspeed/launcher/runner.py:529 (single-node spawn)
+ launcher/launch.py per-rank env contract.
"""
import os
import pytest
import subprocess
import sys
import textwrap

pytestmark = pytest.mark.core

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    import deepspeed_tpu
    from deepspeed_tpu import comm

    comm.init_distributed()
    rank = jax.process_index()
    world = jax.process_count()
    assert world == 2, f"expected 2 processes, got {world}"
    assert len(jax.devices()) == 2, jax.devices()

    # a real cross-process collective must agree on every rank
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    total = multihost_utils.process_allgather(jnp.asarray([rank + 1]))
    assert float(total.sum()) == 3.0, total

    out = os.environ["DSTPU_TEST_OUT"]
    with open(f"{out}.rank{rank}", "w") as f:
        f.write(f"ok {rank}/{world}")
    print(f"[rank {rank}] pod rehearsal OK", flush=True)
""")


class TestPodLaunchRehearsal:
    @staticmethod
    def _free_port() -> int:
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]


    def test_dstpu_popen_two_process_coordinator(self, tmp_path):
        script = tmp_path / "worker.py"
        script.write_text(WORKER)
        out = tmp_path / "sentinel"
        env = dict(os.environ, DSTPU_TEST_OUT=str(out),
                   PYTHONPATH=REPO + os.pathsep + os.environ.get(
                       "PYTHONPATH", ""))
        # jax.distributed needs each process to see ONE local cpu device
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "dstpu"),
             "--launcher", "popen", "--num_procs", "2",
             "--master_port", str(self._free_port()), str(script)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, timeout=240)
        assert proc.returncode == 0, proc.stdout[-3000:]
        for r in range(2):
            p = f"{out}.rank{r}"
            assert os.path.exists(p), (r, proc.stdout[-2000:])
            assert open(p).read() == f"ok {r}/2"
