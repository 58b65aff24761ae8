#!/usr/bin/env python
"""Cluster launcher for distributed training.

Parity: reference `tools/launch.py` + dmlc-tracker local launcher
(spawns scheduler/servers/workers with DMLC_* envs; see
tests/nightly/test_distributed_training-gpu.sh for the multi-process-on-
one-host pattern).

Usage:
  python tools/launch.py -n 2 -s 1 python train.py --kv-store dist_sync

Spawns -s server processes and -n worker processes on this host (the
`local` launcher; ssh/mpi cluster modes hand the same env contract to a
remote shell).  Env contract (same names as the reference):
  DMLC_ROLE          worker | server | scheduler
  DMLC_PS_ROOT_URI   server host (this host for local mode)
  DMLC_PS_ROOT_PORT  base port; server shard i listens on port+i
  DMLC_NUM_WORKER / DMLC_NUM_SERVER
  DMLC_WORKER_ID / DMLC_SERVER_ID
  MXNET_KVSTORE_SYNC 1 for dist_sync semantics (default), 0 for async
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from mxnet_tpu.context import (chip_visibility_env,  # noqa: E402
                               must_place_children)


def _reserve_ports(n):
    """Base port with n CONSECUTIVE bindable ports (server shard i listens
    on base+i, so probing only the base — the old behavior — left shards
    1..n-1 to collide with whatever else is on the host; that was the
    consecutive-test-run flake)."""
    for _ in range(64):
        s0 = socket.socket()
        s0.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s0.bind(("", 0))
        base = s0.getsockname()[1]
        socks = [s0]
        ok = base + n < 65536
        for i in range(1, n):
            s = socket.socket()
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            try:
                s.bind(("", base + i))
            except OSError:
                s.close()
                ok = False
                break
            socks.append(s)
        for s in socks:
            s.close()
        if ok:
            return base
    raise RuntimeError("no contiguous free port range of %d found" % n)


def _kill_all(procs):
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    for p in procs:
        try:
            p.wait(timeout=5)
        except subprocess.TimeoutExpired:
            p.kill()


def _wait_servers_ready(procs, port, n, deadline_s=60.0):
    """Block until every server shard accepts a TCP connection (the server
    treats an immediately-closed probe as a normal client EOF).  Returns
    False if any server process died first (e.g. lost a bind race)."""
    import time
    deadline = time.monotonic() + deadline_s
    ready = [False] * n
    while time.monotonic() < deadline and not all(ready):
        for i in range(n):
            if ready[i]:
                continue
            if procs[i].poll() is not None:
                return False
            try:
                c = socket.create_connection(("127.0.0.1", port + i),
                                             timeout=0.5)
                c.close()
                ready[i] = True
            except OSError:
                pass
        if not all(ready):
            time.sleep(0.1)
    return all(ready)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("-s", "--num-servers", type=int, default=1)
    ap.add_argument("--launcher", default="local", choices=["local"])
    ap.add_argument("--sync-dst-dir", default=None, help="unused (parity)")
    ap.add_argument("--port", type=int, default=None)
    ap.add_argument("--async", dest="async_mode", action="store_true")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")

    # one process per chip: worker i sees chip i, or the first worker
    # would take every chip of the host; more workers than chips is
    # refused here, before anything starts
    chip_env = [{}] * args.num_workers
    if must_place_children(os.environ):
        base = _reserve_ports(args.num_workers)
        chip_env = [chip_visibility_env(wid, base + wid)
                    for wid in range(args.num_workers)]

    # a lost bind race (another process grabbed a probed port between the
    # probe and the server's bind) is detectable — the server dies before
    # accepting — and retryable with a fresh range
    for attempt in range(3):
        port = args.port or _reserve_ports(args.num_servers)
        base_env = dict(os.environ)
        base_env.update({
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_WORKER": str(args.num_workers),
            "DMLC_NUM_SERVER": str(args.num_servers),
            "MXNET_KVSTORE_SYNC": "0" if args.async_mode else "1",
        })

        procs = []
        try:
            # servers first (workers block connecting until they're up)
            for sid in range(args.num_servers):
                env = dict(base_env)
                # a server aggregates on the host: held off the chips,
                # which belong to the workers
                env.update({"DMLC_ROLE": "server",
                            "DMLC_SERVER_ID": str(sid),
                            "DMLC_SERVER_PORT": str(port + sid),
                            "JAX_PLATFORMS": "cpu"})
                procs.append(subprocess.Popen(
                    [sys.executable, "-c",
                     "import mxnet_tpu as mx;"
                     "mx.kvstore._init_kvstore_server_module()"], env=env))
            if not _wait_servers_ready(procs, port, args.num_servers):
                if args.port is not None or attempt == 2:
                    print("launch.py: servers failed to start on ports "
                          "%d..%d" % (port, port + args.num_servers - 1),
                          file=sys.stderr)
                    return 1
                _kill_all(procs)
                procs = []
                continue  # retry on a fresh port range
            workers = []
            for wid in range(args.num_workers):
                env = dict(base_env)
                env.update({"DMLC_ROLE": "worker",
                            "DMLC_WORKER_ID": str(wid)})
                env.update(chip_env[wid])
                workers.append(subprocess.Popen(args.command, env=env))
            rc = 0
            for w in workers:
                rc |= w.wait()
            return rc
        finally:
            _kill_all(procs)
    return 1


if __name__ == "__main__":
    sys.exit(main())
