"""Replica supervisor: launch, monitor, and auto-restart serving
replicas.

The process-management half of the fleet (the router is the traffic
half): N replica processes (``serving/replica.py``) are spawned on
pre-reserved ports, health-gated on ``/readyz`` at startup, and watched
by a monitor thread.  A replica that exits — crash, OOM, SIGKILL chaos —
is restarted **on the same port** (the router's replica identity is
``host:port``, so a restart needs no router reconfiguration: the probe
loop re-admits the ejected address as soon as ``/readyz`` answers).

Restart discipline (the crash-loop brake):

- **budget** — at most ``MXNET_FLEET_RESTART_BUDGET`` restarts per
  replica within a sliding ``MXNET_FLEET_RESTART_WINDOW_SEC`` window;
  past it the replica is declared ``failed`` and left down (a broken
  model spec would otherwise burn CPU forever while the router keeps
  ejecting it).
- **backoff** — consecutive crashes back off exponentially from
  ``MXNET_FLEET_RESTART_BACKOFF_MS``; a replica that stays healthy for
  a while resets its streak.

Cold-start is bounded by the persistent XLA compile cache the replica
entry point turns on (``runtime.enable_compile_cache``): the first
replica's per-bucket warmup
pays the compiles, every later boot (including restarts and rollout
re-warms) reads them back in seconds.
"""
from __future__ import annotations

import collections
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

from .. import config as _config
from .. import profiler
from ..context import chip_visibility_env, must_place_children

__all__ = ["ReplicaProcess", "ReplicaSupervisor"]


def _reserve_ports(n, host="127.0.0.1"):
    """Grab n distinct free ports (best-effort: bound-then-closed)."""
    socks, ports = [], []
    try:
        for _ in range(n):
            s = socket.socket()
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind((host, 0))
            socks.append(s)
            ports.append(s.getsockname()[1])
    finally:
        for s in socks:
            s.close()
    return ports


class ReplicaProcess:
    """One supervised replica slot: fixed (rid, port), restartable
    process behind it."""

    def __init__(self, rid, host, port):
        self.rid = rid
        self.host = host
        self.port = port
        self.proc = None
        self.state = "stopped"   # stopped | running | failed
        self.restarts = 0
        self.restart_times = collections.deque()  # window accounting
        self.consecutive_crashes = 0
        self.started_at = 0.0
        self.next_restart = 0.0
        self.log_path = None

    @property
    def addr(self):
        return "%s:%d" % (self.host, self.port)

    def alive(self):
        return self.proc is not None and self.proc.poll() is None

    def describe(self):
        return {"addr": self.addr, "state": self.state,
                "pid": self.proc.pid if self.alive() else None,
                "restarts": self.restarts,
                "consecutive_crashes": self.consecutive_crashes}


class ReplicaSupervisor:
    """Launch and babysit N replica processes serving one model spec.

    ``spec`` is the replica spec dict (see ``serving/replica.py``); it
    is written to a temp JSON file all replicas read.  ``env`` overrides
    are merged over the parent environment per replica (the supervisor
    always stamps ``MXNET_SERVING_REPLICA_ID``)."""

    def __init__(self, spec, *, replicas=None, host="127.0.0.1",
                 ports=None, restart_budget=None, restart_window_s=None,
                 restart_backoff_ms=None, env=None,
                 startup_timeout_s=120.0, command_builder=None,
                 ready_probe=None):
        self.spec = dict(spec)
        # the supervision machinery (ports, budget/backoff, monitor) is
        # process-kind agnostic: command_builder(r, spec_path) -> argv
        # and ready_probe(r, timeout) -> bool let non-HTTP processes
        # (e.g. PageStore members) ride the same restart discipline
        self.command_builder = command_builder
        self.ready_probe = ready_probe
        self.n = int(replicas if replicas is not None
                     else _config.get("MXNET_FLEET_REPLICAS"))
        self.host = host
        self.restart_budget = int(
            restart_budget if restart_budget is not None
            else _config.get("MXNET_FLEET_RESTART_BUDGET"))
        self.restart_window_s = float(
            restart_window_s if restart_window_s is not None
            else _config.get("MXNET_FLEET_RESTART_WINDOW_SEC"))
        self.restart_backoff_s = max(1e-3, float(
            restart_backoff_ms if restart_backoff_ms is not None
            else _config.get("MXNET_FLEET_RESTART_BACKOFF_MS")) / 1e3)
        self.env = dict(env or {})
        self.env_by_rid = {}  # rid -> extra env (e.g. MXNET_GEN_ROLE)
        self.startup_timeout_s = float(startup_timeout_s)
        ports = list(ports) if ports else _reserve_ports(self.n, host)
        if len(ports) != self.n:
            raise ValueError("need %d ports, got %d" % (self.n, len(ports)))
        self.replicas = [ReplicaProcess("r%d" % i, host, p)
                         for i, p in enumerate(ports)]
        self._next_idx = self.n   # rid counter for autoscale add_replica
        self._chips = {}          # rid -> (chip, its env) (see _chip_env)
        self._spec_path = None
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._monitor = None

    # -- lifecycle --------------------------------------------------------
    def addresses(self):
        return [r.addr for r in self.replicas]

    def start(self, wait_ready=True):
        for r in self.replicas:
            self._env(r)    # every replica has a chip, or nothing starts
        fd, self._spec_path = tempfile.mkstemp(prefix="mxtpu-fleet-",
                                               suffix=".json")
        with os.fdopen(fd, "w") as f:
            json.dump(self.spec, f)
        for r in self.replicas:
            self._spawn(r)
        if wait_ready:
            self.wait_ready()
        self._monitor = threading.Thread(target=self._monitor_loop,
                                         name="mxtpu-fleet-supervisor",
                                         daemon=True)
        self._monitor.start()
        return self.addresses()

    def _chip_env(self, r, env):
        """One process per chip: a model replica is shown the lowest chip
        no sibling holds (``context.chip_visibility_env``), or its first
        child would take every chip of the host and the second would
        fail or hang.  A replica the host has no chip left for is
        refused here (``start``/``add_replica`` raise before a process
        exists).  Nothing is stamped where
        ``context.must_place_children`` says so (CPU lane, no TPU, the
        operator's own ``TPU_VISIBLE_CHIPS``), or for the only replica
        of a multi-chip mesh (the host's chips are its mesh).
        Several replicas of which one wants more than one chip is
        refused: that partition of a host has not run on a chip yet."""
        if not must_place_children(env):
            return {}
        need = 1
        for s in env.get("MXNET_MESH_SHAPE", "").split(","):
            need *= int(s) if s.strip() else 1
        if need > 1:
            if len(self.replicas) > 1:
                raise RuntimeError(
                    "replica %s wants a %d-chip mesh beside %d other "
                    "replica(s): sharing one host between multi-chip "
                    "replicas is not supported (one replica per host, or "
                    "one chip per replica)"
                    % (r.rid, need, len(self.replicas) - 1))
            return {}
        with self._lock:
            if r.rid not in self._chips:
                used = {chip for chip, _ in self._chips.values()}
                chip = next(i for i in range(len(used) + 1)
                            if i not in used)
                # a restart keeps its chip and its runtime port
                self._chips[r.rid] = (chip, chip_visibility_env(
                    chip, _reserve_ports(1, self.host)[0]))
            return dict(self._chips[r.rid][1])

    def _env(self, r):
        env = dict(os.environ)
        env.update(self.env)
        env.update(self.env_by_rid.get(r.rid, {}))
        if self.command_builder is None:
            env.update(self._chip_env(r, env))
        return env

    def _spawn(self, r):
        env = self._env(r)
        env["MXNET_SERVING_REPLICA_ID"] = r.rid
        # the package must be importable from a bare `python -m`
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH",
                                                            "")
        if r.log_path is None:
            r.log_path = os.path.join(
                tempfile.gettempdir(),
                "mxtpu-replica-%s-%d.log" % (r.rid, os.getpid()))
        if self.command_builder is not None:
            argv = list(self.command_builder(r, self._spec_path))
        else:
            argv = [sys.executable, "-m", "mxnet_tpu.serving.replica",
                    "--spec", self._spec_path, "--port", str(r.port),
                    "--host", r.host, "--id", r.rid]
        log = open(r.log_path, "ab")
        try:
            r.proc = subprocess.Popen(
                argv, stdout=log, stderr=subprocess.STDOUT, env=env)
        finally:
            log.close()
        r.state = "running"
        r.started_at = time.monotonic()
        return r

    def _ready(self, r, timeout=1.0):
        if self.ready_probe is not None:
            try:
                return bool(self.ready_probe(r, timeout))
            except (OSError, RuntimeError):
                return False
        import http.client
        try:
            conn = http.client.HTTPConnection(r.host, r.port,
                                              timeout=timeout)
            try:
                conn.request("GET", "/readyz")
                return conn.getresponse().status == 200
            finally:
                conn.close()
        except OSError:
            return False

    def wait_ready(self, timeout=None):
        """Block until every running replica answers /readyz (startup
        warmup included); raises with the laggard's log tail on timeout."""
        deadline = time.monotonic() + (timeout if timeout is not None
                                       else self.startup_timeout_s)
        for r in list(self.replicas):
            while not self._ready(r):
                if not r.alive():
                    raise RuntimeError(
                        "replica %s exited during startup (rc=%s)\n%s"
                        % (r.rid, r.proc.poll(), self._log_tail(r)))
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        "replica %s not ready within %.0fs\n%s"
                        % (r.rid, self.startup_timeout_s,
                           self._log_tail(r)))
                time.sleep(0.05)
        return True

    def _log_tail(self, r, nbytes=2000):
        try:
            with open(r.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - nbytes))
                return f.read().decode(errors="replace")
        except OSError:
            return "<no log>"

    # -- elastic membership (autoscaler hooks) ----------------------------
    def add_replica(self, env=None, spawn=True):
        """Scale-up: reserve a fresh port, register a new replica slot,
        and (by default) spawn its process immediately.  The monitor
        loop adopts it — same restart budget and backoff as the boot
        cohort.  Returns the new :class:`ReplicaProcess` (the caller
        waits on readiness through the router's probe loop, not here)."""
        port = _reserve_ports(1, self.host)[0]
        with self._lock:
            rid = "r%d" % self._next_idx
            self._next_idx += 1
            r = ReplicaProcess(rid, self.host, port)
            if env:
                self.env_by_rid[rid] = dict(env)
            self.replicas.append(r)
        if spawn and self._spec_path is not None:
            try:
                self._spawn(r)
            except RuntimeError:    # no chip left: the fleet is unchanged
                with self._lock:
                    self.replicas.remove(r)
                    self.env_by_rid.pop(rid, None)
                raise
        profiler.record_event_stat("fleet.replica_spawn")
        return r

    def stop_replica(self, rid, timeout=15.0):
        """Scale-down: remove one replica from supervision (no restart)
        and terminate its process.  The caller is responsible for
        draining/migrating its sessions FIRST — this is the mechanical
        tail of the autoscaler's drain-by-migration path."""
        with self._lock:
            r = next((x for x in self.replicas if x.rid == rid), None)
            if r is None:
                return None
            self.replicas.remove(r)
            self.env_by_rid.pop(rid, None)
            self._chips.pop(rid, None)
        r.state = "stopped"
        if r.alive():
            r.proc.send_signal(signal.SIGTERM)
            try:
                r.proc.wait(timeout)
            except subprocess.TimeoutExpired:
                r.proc.kill()
                r.proc.wait(5.0)
        profiler.record_event_stat("fleet.replica_drained")
        return r

    # -- monitor / restart ------------------------------------------------
    def _monitor_loop(self):
        while not self._stop.wait(0.1):
            now = time.monotonic()
            for r in list(self.replicas):
                if self._stop.is_set():
                    return
                if r.state == "failed" or r.alive():
                    # a healthy stretch forgives the crash streak
                    if (r.alive() and r.consecutive_crashes
                            and now - r.started_at
                            > self.restart_window_s / 4):
                        r.consecutive_crashes = 0
                    continue
                if r.state == "stopped":
                    continue
                # replica exited: crash-loop brake, then respawn
                if r.next_restart == 0.0:
                    rc = r.proc.poll() if r.proc is not None else None
                    profiler.record_event_stat("fleet.replica_exit")
                    while (r.restart_times and now - r.restart_times[0]
                           > self.restart_window_s):
                        r.restart_times.popleft()
                    if len(r.restart_times) >= self.restart_budget:
                        r.state = "failed"
                        profiler.record_event_stat("fleet.crash_loop")
                        print("supervisor: replica %s exceeded restart "
                              "budget (%d in %.0fs; last rc=%s) — giving "
                              "up" % (r.rid, len(r.restart_times),
                                      self.restart_window_s, rc),
                              file=sys.stderr, flush=True)
                        continue
                    backoff = (self.restart_backoff_s
                               * (2 ** r.consecutive_crashes))
                    r.next_restart = now + backoff
                if now >= r.next_restart:
                    r.next_restart = 0.0
                    r.restarts += 1
                    r.restart_times.append(now)
                    r.consecutive_crashes += 1
                    self._spawn(r)
                    profiler.record_event_stat("fleet.replica_restart")

    def alive_count(self):
        return sum(1 for r in list(self.replicas) if r.alive())

    def ready_count(self):
        return sum(1 for r in list(self.replicas)
                   if r.alive() and self._ready(r))

    def states(self):
        """Per-replica process + crash-loop state: on top of
        ``describe()``, each entry carries the restart-discipline
        internals (budget remaining in the sliding window, backoff
        stage, pending-restart countdown) so the crash-loop brake is
        observable BEFORE a replica hits ``failed``."""
        now = time.monotonic()
        out = {}
        for r in list(self.replicas):
            d = r.describe()
            in_window = sum(1 for t in r.restart_times
                            if now - t <= self.restart_window_s)
            d["restart_budget"] = self.restart_budget
            d["restarts_in_window"] = in_window
            d["restart_budget_remaining"] = max(
                0, self.restart_budget - in_window)
            d["backoff_stage"] = r.consecutive_crashes
            d["restart_window_s"] = self.restart_window_s
            d["next_restart_in_s"] = (
                round(max(0.0, r.next_restart - now), 3)
                if r.next_restart else 0.0)
            out[r.rid] = d
        return out

    # -- chaos hooks ------------------------------------------------------
    def kill(self, index, sig=signal.SIGKILL):
        """Chaos hook: signal one replica process (default SIGKILL — the
        no-drain, no-goodbye failure the fleet is tested against)."""
        r = self.replicas[index]
        if r.alive():
            r.proc.send_signal(sig)
        return r

    def stop(self, timeout=15.0):
        self._stop.set()
        if self._monitor is not None:
            self._monitor.join(5.0)
            self._monitor = None
        for r in list(self.replicas):
            r.state = "stopped"
            if r.alive():
                r.proc.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + timeout
        for r in list(self.replicas):
            if r.proc is None:
                continue
            try:
                r.proc.wait(max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                r.proc.kill()
                r.proc.wait(5.0)
        if self._spec_path and os.path.exists(self._spec_path):
            os.unlink(self._spec_path)
            self._spec_path = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False
