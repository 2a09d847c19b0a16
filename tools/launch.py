#!/usr/bin/env python
"""launch — start a distributed training job (reference ``tools/launch.py``:
dmlc-core tracker spawning workers/servers/scheduler over local/ssh/mpi).

TPU-native launcher: the parameter-server role split collapses into SPMD
(SURVEY.md §5.8) — every process is a worker; coordination happens through
``jax.distributed`` (coordinator address + process ids over DCN) instead of
a ZeroMQ scheduler. This tool sets the same env contract our kvstore reads
(``DMLC_NUM_WORKER``/``DMLC_WORKER_ID`` kept for script parity, plus the
jax.distributed variables) and spawns N copies of the training command.

  python tools/launch.py -n 4 python train_imagenet.py --kv-store dist_sync
  python tools/launch.py -n 2 -H hostfile ...   # ssh multi-host

One process per host drives ALL of that host's chips (a chip belongs to one
process at a time, and ``DataParallelTrainer`` / ``Module(context=[...])``
span the local devices from inside one process). The local launcher assigns
no devices to its workers, so ``-n N`` on a single host is the CPU test path
(``JAX_PLATFORMS=cpu``, what ``tests/test_dist.py`` runs): on a chip host
every local worker would try to open every chip. Across hosts, launch one
worker per host (``-H hostfile``).
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys

__all__ = ["launch_local", "launch_ssh", "worker_env"]


def worker_env(rank, num_workers, coordinator, base=None):
    """Env for one worker (reference tracker sets DMLC_*; we add the
    jax.distributed trio consumed by parallel/collectives.py)."""
    env = dict(base if base is not None else os.environ)
    env.update({
        "DMLC_ROLE": "worker",
        "DMLC_NUM_WORKER": str(num_workers),
        "DMLC_NUM_SERVER": "0",
        "DMLC_WORKER_ID": str(rank),
        "MXNET_COORDINATOR_ADDRESS": coordinator,
        "JAX_COORDINATOR_ADDRESS": coordinator,
        "JAX_NUM_PROCESSES": str(num_workers),
        "JAX_PROCESS_ID": str(rank),
    })
    return env


def launch_local(num_workers, command, coordinator="127.0.0.1:9870"):
    """Spawn N worker copies locally (reference local launcher :57-121)."""
    procs = []
    for rank in range(num_workers):
        p = subprocess.Popen(command,
                             env=worker_env(rank, num_workers, coordinator))
        procs.append(p)

    def _kill(sig, frame):
        for p in procs:
            p.terminate()
        sys.exit(1)

    prev_int = signal.signal(signal.SIGINT, _kill)
    prev_term = signal.signal(signal.SIGTERM, _kill)
    try:
        codes = [p.wait() for p in procs]
    finally:
        # restore the caller's handlers: leaking _kill process-wide
        # turns any later KeyboardInterrupt delivery (e.g. the step
        # watchdog's interrupt_main) into a silent SystemExit
        signal.signal(signal.SIGINT, prev_int)
        signal.signal(signal.SIGTERM, prev_term)
    return max(codes) if codes else 0


def launch_ssh(hosts, num_workers, command, coordinator=None):
    """One worker per host via ssh (reference ssh launcher). Host 0 runs the
    jax.distributed coordinator."""
    if coordinator is None:
        coordinator = f"{hosts[0]}:9870"
    procs = []
    for rank in range(num_workers):
        host = hosts[rank % len(hosts)]
        env = worker_env(rank, num_workers, coordinator, base={})
        env_str = " ".join(f"{k}={v}" for k, v in env.items()
                           if k.startswith(("DMLC_", "JAX_", "MXNET_")))
        remote_cmd = f"cd {os.getcwd()} && env {env_str} " + \
            " ".join(command)
        p = subprocess.Popen(["ssh", "-o", "StrictHostKeyChecking=no",
                              host, remote_cmd])
        procs.append(p)
    codes = [p.wait() for p in procs]
    return max(codes) if codes else 0


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="Launch a distributed training job",
        usage="launch.py [-h] [-n N] [-H HOSTFILE] command ...")
    p.add_argument("-n", "--num-workers", type=int, required=True)
    p.add_argument("-H", "--hostfile", default=None,
                   help="one host per line -> ssh launch; absent -> local")
    p.add_argument("--coordinator", default=None,
                   help="host:port of the jax.distributed coordinator")
    p.add_argument("command", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if not args.command:
        print("no command given", file=sys.stderr)
        sys.exit(1)
    if args.hostfile:
        with open(args.hostfile) as f:
            hosts = [h.strip() for h in f if h.strip()]
        rc = launch_ssh(hosts, args.num_workers, args.command,
                        args.coordinator)
    else:
        rc = launch_local(args.num_workers, args.command,
                          args.coordinator or "127.0.0.1:9870")
    sys.exit(rc)


if __name__ == "__main__":
    main()
