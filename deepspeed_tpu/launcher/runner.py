"""Job launcher CLI (reference: deepspeed/launcher/runner.py:419 main(),
hostfile parsing :213, include/exclude filters :293; per-node launch.py:133).

TPU pods run ONE process per host (JAX owns all local chips), so the launcher
is simpler than the reference's one-proc-per-GPU model: parse a hostfile,
compute the coordinator address, and start the user script on every host with
``COORDINATOR_ADDRESS``/``DSTPU_RANK``/``DSTPU_WORLD_SIZE`` env — the env that
``comm.init_distributed`` consumes.  Single-host runs exec in-place.

Usage:  dstpu [--hostfile HF] [--include ...] [--master_port P] script.py args…
"""
from __future__ import annotations

import argparse
import os
import shlex
import subprocess
import sys
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

from ..utils.logging import logger

DLTS_HOSTFILE = "/job/hostfile"
EXPORT_ENVS = ["PYTHONPATH", "PATH", "LD_LIBRARY_PATH", "JAX_PLATFORMS", "XLA_FLAGS"]


def parse_args(args=None):
    parser = argparse.ArgumentParser(
        description="deepspeed_tpu launcher",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("-H", "--hostfile", type=str, default=DLTS_HOSTFILE,
                        help="hostfile: lines of '<host> slots=<n>'")
    parser.add_argument("-i", "--include", type=str, default="",
                        help="host filter, e.g. 'worker-0@worker-1'")
    parser.add_argument("-e", "--exclude", type=str, default="",
                        help="host exclusion filter")
    parser.add_argument("--num_nodes", type=int, default=-1)
    parser.add_argument("--master_port", type=int, default=29500)
    parser.add_argument("--master_addr", type=str, default="")
    parser.add_argument("--launcher", type=str, default="ssh",
                        choices=["ssh", "pdsh", "openmpi", "mpich", "slurm",
                                 "local", "popen"])
    parser.add_argument("--num_procs", type=int, default=2,
                        help="popen launcher: local process count (pod "
                             "rehearsal — one process per simulated host)")
    parser.add_argument("--force_multi", action="store_true")
    parser.add_argument("user_script", type=str)
    parser.add_argument("user_args", nargs=argparse.REMAINDER)
    return parser.parse_args(args)


def fetch_hostfile(hostfile_path: str) -> Optional[Dict[str, int]]:
    """Reference :213 — '<hostname> slots=<n>' per line, '#' comments."""
    if not os.path.isfile(hostfile_path):
        return None
    resource_pool: "OrderedDict[str, int]" = OrderedDict()
    with open(hostfile_path) as f:
        for line in f:
            line = line.split("#")[0].strip()
            if not line:
                continue
            try:
                host, slots = line.split()
                count = int(slots.split("=")[1])
            except (ValueError, IndexError):
                raise ValueError(f"malformed hostfile line: {line!r}")
            if host in resource_pool:
                raise ValueError(f"duplicate host {host!r} in hostfile")
            resource_pool[host] = count
    return resource_pool or None


def parse_inclusion_exclusion(resource_pool: Dict[str, int], inclusion: str,
                              exclusion: str) -> Dict[str, int]:
    """Reference :293 — 'host1@host2' selects hosts; 'host:0,1' selects slots
    (slot selection is not meaningful on TPU hosts — host-granular only)."""
    active = OrderedDict(resource_pool)
    if inclusion:
        wanted = set(h.split(":")[0] for h in inclusion.split("@"))
        unknown = wanted - set(active)
        if unknown:
            raise ValueError(f"included hosts not in hostfile: {sorted(unknown)}")
        active = OrderedDict((h, n) for h, n in active.items() if h in wanted)
    if exclusion:
        dropped = set(h.split(":")[0] for h in exclusion.split("@"))
        active = OrderedDict((h, n) for h, n in active.items() if h not in dropped)
    if not active:
        raise ValueError("no hosts remain after include/exclude filters")
    return active


def encode_world_info(resource_pool: Dict[str, int]) -> str:
    import base64
    import json

    return base64.urlsafe_b64encode(
        json.dumps(resource_pool).encode()).decode()


def build_launch_env(rank: int, world_size: int, master_addr: str,
                     master_port: int) -> Dict[str, str]:
    env = {k: os.environ[k] for k in EXPORT_ENVS if k in os.environ}
    env.update({
        "DSTPU_RANK": str(rank),
        "DSTPU_WORLD_SIZE": str(world_size),
        "COORDINATOR_ADDRESS": f"{master_addr}:{master_port}",
        "RANK": str(rank),
        "WORLD_SIZE": str(world_size),
        "MASTER_ADDR": master_addr,
        "MASTER_PORT": str(master_port),
    })
    return env


def main(args=None):
    args = parse_args(args)
    resource_pool = fetch_hostfile(args.hostfile)

    if args.launcher == "popen":
        # Localhost pod rehearsal: N distinct processes +
        # a real jax.distributed coordinator on 127.0.0.1 — the same
        # per-rank env contract a physical pod launch uses, so a real
        # slice becomes a hostfile change, not new code.  One process per
        # simulated host (the TPU one-proc-per-host model).
        world_size = args.num_procs
        master_addr = args.master_addr or "127.0.0.1"
        procs: List[subprocess.Popen] = []
        for rank in range(world_size):
            # local children inherit the full env (same-host semantics);
            # build_launch_env supplies the per-rank rendezvous contract
            env = dict(os.environ)
            env.update(build_launch_env(rank, world_size, master_addr,
                                        args.master_port))
            cmd = [sys.executable, args.user_script] + args.user_args
            logger.info(f"rank {rank}: {' '.join(map(shlex.quote, cmd))}")
            procs.append(subprocess.Popen(cmd, env=env))
        # fail fast: one dead rank would leave the others blocked in a
        # collective until the distributed timeout — terminate peers on the
        # first nonzero exit (reference runner.py sigkill_handler semantics)
        rc = 0
        live = list(procs)
        while live:
            time.sleep(0.2)
            for p in list(live):
                ret = p.poll()
                if ret is None:
                    continue
                live.remove(p)
                if ret != 0 and rc == 0:
                    rc = ret
                    logger.error(f"a rank exited rc={ret}; "
                                 f"terminating {len(live)} peer(s)")
                    for q in live:
                        q.terminate()
        sys.exit(rc)

    if not resource_pool or args.launcher == "local":
        # single host: exec in place (reference single-node path :529)
        cmd = [sys.executable, args.user_script] + args.user_args
        logger.info(f"launching local: {' '.join(map(shlex.quote, cmd))}")
        result = subprocess.run(cmd)
        sys.exit(result.returncode)

    active = parse_inclusion_exclusion(resource_pool, args.include, args.exclude)
    if args.num_nodes > 0:
        active = OrderedDict(list(active.items())[:args.num_nodes])
    hosts = list(active)
    master_addr = args.master_addr or hosts[0]
    world_size = len(hosts)

    # rank-free shared env: workers derive rank from the backend's native
    # env (or DSTPU_NODE_LIST for pdsh/ssh) — see multinode_runner.py
    shared_env = build_launch_env(0, world_size, master_addr, args.master_port)
    for key in ("RANK", "DSTPU_RANK", "LOCAL_RANK"):
        shared_env.pop(key, None)

    from .multinode_runner import RUNNERS, MultiNodeRunner

    if args.launcher in RUNNERS:
        # single fan-out command (reference multinode_runner.py backends)
        runner = RUNNERS[args.launcher](args.user_script, args.user_args,
                                        shared_env)
        if not runner.backend_installed():
            logger.error(f"launcher backend {args.launcher!r} not installed")
            sys.exit(1)
        cmd = runner.get_cmd(hosts, master_addr, args.master_port)
        logger.info(f"launching via {args.launcher}: "
                    f"{' '.join(map(shlex.quote, cmd))}")
        env = dict(os.environ)
        env.update(runner.exports)      # slurm --export=ALL inherits these
        for key in ("RANK", "DSTPU_RANK", "LOCAL_RANK"):
            env.pop(key, None)          # stale launcher-env ranks would be
        sys.exit(subprocess.run(cmd, env=env).returncode)  # fanned to all tasks

    # ssh: one remote command per host, with the true per-rank env
    base = MultiNodeRunner(args.user_script, args.user_args, shared_env)
    base._set_rendezvous(master_addr, args.master_port)
    procs: List[subprocess.Popen] = []
    for rank, host in enumerate(hosts):
        remote_cmd = base.worker_cmdline(
            {"RANK": str(rank), "DSTPU_RANK": str(rank),
             "WORLD_SIZE": str(world_size),
             "DSTPU_WORLD_SIZE": str(world_size)})
        logger.info(f"rank {rank} @ {host}")
        procs.append(subprocess.Popen(["ssh", host, remote_cmd]))

    rc = 0
    for p in procs:
        p.wait()
        rc = rc or p.returncode
    sys.exit(rc)


if __name__ == "__main__":
    main()
