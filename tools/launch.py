#!/usr/bin/env python
"""Distributed job launcher — `tools/launch.py` parity.

TPU-native rendition of the reference `tools/launch.py` + dmlc tracker
(SURVEY.md §2.8, §3.5): instead of spawning scheduler + parameter
servers + workers over ssh/mpi/yarn, SPMD training needs exactly N
identical worker processes rendezvousing at a coordinator
(`jax.distributed.initialize`).

Launch modes (`--launcher`):
  local  — spawn N worker processes on THIS machine (the reference's
           `--launcher local` CI pattern: "an N-worker cluster on one
           machine", how the dist kvstore tests run without a cluster).
           Workers are pinned to the CPU backend so they don't fight
           over an accelerator.
  env    — emit the environment for externally-orchestrated workers
           (GKE/GCE/slurm): print per-worker env assignments and exit.

Worker-side contract (read by `parallel.collectives` /
`kvstore.create('dist_sync')`):
  MXTPU_COORDINATOR   host:port of process 0
  MXTPU_NUM_PROCESSES N
  MXTPU_PROCESS_ID    0..N-1
(the dmlc DMLC_PS_ROOT_URI / DMLC_NUM_WORKER / DMLC_WORKER_ID
equivalents; those names are also exported for script compat.)

Usage:
  python tools/launch.py -n 3 --launcher local python train.py --kv-store dist_sync
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys


def build_parser():
    p = argparse.ArgumentParser(
        description="launch a distributed training job",
        usage="launch.py [-h] -n NUM_WORKERS [--launcher {local,env}] command ...")
    p.add_argument("-n", "--num-workers", type=int, required=True)
    p.add_argument("--launcher", type=str, default="local",
                   choices=["local", "env"])
    p.add_argument("--coordinator-port", type=int, default=0,
                   help="port for process 0 (0 = pick a free port)")
    p.add_argument("--coordinator-host", type=str, default=None,
                   help="routable host of process 0 (env mode; default: "
                        "this machine's hostname)")
    p.add_argument("--env-keys", type=str, default="",
                   help="comma-separated extra env vars to forward")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="worker command, e.g. python train.py ...")
    return p


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def worker_env(rank: int, n: int, coordinator: str, base=None) -> dict:
    env = dict(base if base is not None else os.environ)
    env["MXTPU_COORDINATOR"] = coordinator
    env["MXTPU_NUM_PROCESSES"] = str(n)
    env["MXTPU_PROCESS_ID"] = str(rank)
    # dmlc-compatible names for scripts that read the reference's vars
    env["DMLC_PS_ROOT_URI"] = coordinator.split(":")[0]
    env["DMLC_PS_ROOT_PORT"] = coordinator.split(":")[1]
    env["DMLC_NUM_WORKER"] = str(n)
    env["DMLC_WORKER_ID"] = str(rank)
    env["DMLC_ROLE"] = "worker"
    return env


def launch_local(n: int, command, coordinator_port: int = 0) -> int:
    port = coordinator_port or _free_port()
    coordinator = f"127.0.0.1:{port}"
    procs = []
    for rank in range(n):
        env = worker_env(rank, n, coordinator)
        # local mode = CI pattern: CPU backend, keep off the accelerator
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("JAX_PLATFORM_NAME", None)
        procs.append(subprocess.Popen(command, env=env))

    rc = 0
    try:
        for p in procs:
            p.wait()
            rc = rc or p.returncode
    except KeyboardInterrupt:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        rc = 1
    return rc


def main(argv=None):
    args = build_parser().parse_args(argv)
    command = args.command
    if command and command[0] == "--":
        command = command[1:]
    if not command:
        print("launch.py: no worker command given", file=sys.stderr)
        return 2
    if args.launcher == "env":
        port = args.coordinator_port or _free_port()
        # externally-orchestrated workers live on OTHER machines: the
        # coordinator address must be routable, not loopback
        host = args.coordinator_host or socket.getfqdn()
        coordinator = f"{host}:{port}"
        for rank in range(args.num_workers):
            env = worker_env(rank, args.num_workers, coordinator, base={})
            assigns = " ".join(f"{k}={v}" for k, v in sorted(env.items()))
            print(f"# worker {rank}\n{assigns} {' '.join(command)}")
        return 0
    return launch_local(args.num_workers, command, args.coordinator_port)


if __name__ == "__main__":
    sys.exit(main())
