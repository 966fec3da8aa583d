#!/usr/bin/env python
"""Multi-process job launcher.

Reference: tools/launch.py (dmlc_tracker — spawns scheduler/servers/workers
with DMLC_ROLE env vars, :72-110). TPU-native redesign: there is no parameter
server; the launcher spawns N identical WORKER processes wired together by
jax.distributed (coordinator = worker 0). This is the local recipe the
distributed tests use (SURVEY §4: multi-node-without-cluster), and the same
env contract a real multi-host TPU job uses (one process per host).

It is for several hosts, or for CPU workers on one. A chip belongs to one
process at a time, and on a host with four chips ONE process must own all
four: N workers started here would each reach for the same chips and fail
or hang. There, build a mesh over ``jax.devices()`` in a single process
(``chip_smoke.py --chips 4`` does) and do not use this launcher.

Env contract consumed by mxnet_tpu.kvstore:
    MXTPU_DIST_COORD  - coordinator address host:port
    MXTPU_DIST_NPROC  - number of processes
    MXTPU_DIST_RANK   - this process's rank

Usage:
    python tools/launch.py -n 3 [--launcher local] python my_script.py args...
"""
from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch_local(n, command, env_extra=None):
    import time

    port = _free_port()
    procs = []
    for rank in range(n):
        env = dict(os.environ)
        env.update(env_extra or {})
        env["MXTPU_DIST_COORD"] = f"127.0.0.1:{port}"
        env["MXTPU_DIST_NPROC"] = str(n)
        env["MXTPU_DIST_RANK"] = str(rank)
        procs.append(subprocess.Popen(command, env=env))
    # poll all workers: one crashing must kill the siblings immediately,
    # or survivors block inside jax.distributed.initialize for minutes
    rc = 0
    alive = list(procs)
    while alive:
        time.sleep(0.2)
        for p in list(alive):
            ret = p.poll()
            if ret is None:
                continue
            alive.remove(p)
            if ret != 0 and rc == 0:
                rc = ret
                for q in alive:
                    q.terminate()
    for p in procs:
        p.wait()
    return rc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--launcher", default="local", choices=["local"])
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")
    sys.exit(launch_local(args.num_workers, args.command))


if __name__ == "__main__":
    main()
