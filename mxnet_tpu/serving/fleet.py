"""Serving fleet: supervisor + router + zero-downtime rolling rollout.

:class:`ServingFleet` is the one-object production story: N supervised
replica processes (``supervisor.py``) behind a health-routing frontend
(``router.py``), with model-version rollout that never drops a request.

Rollout protocol (``fleet.rollout`` / module-level :func:`rollout`):

1. Pin ONE fleet-wide version number (current latest + 1) so every
   replica publishes the same version — admin loads are per-replica,
   and letting each pick its own "latest + 1" could diverge.
2. **Canary baseline**: probe the first replica's CURRENT latest with a
   handful of requests; their p99 is the regression yardstick (measured
   the same way, on the same replica, as the post-flip probes —
   apples to apples).
3. One replica at a time: **drain** it at the router (no new traffic;
   in-flight requests finish; the warmup compiles compete with
   nothing), admin-**load** the new version — the registry warms every
   batch bucket BEFORE flipping the latest pointer, reading the
   replica's persistent compile cache — then **undrain**.  Traffic on the replica never sees a gap: old
   version until the flip, new version after, both fully compiled.
4. The first replica is the **canary**: after its flip it is probed on
   the new version; if the probe error rate exceeds
   ``canary_error_rate`` or probe p99 exceeds ``canary_p99_factor`` x
   the baseline p99, the rollout **aborts and rolls back** — the new
   version is unloaded everywhere it landed (the registry's latest
   falls back to the old version) and :class:`RolloutAbortedError`
   is raised.  Replicas 2..N only ever see a version the canary
   survived.

A fleet-wide rollout is therefore: at most one replica warming at any
moment, N-1 (or N, via the last-resort drain route) replicas serving
the whole time, and an abort path that converges back to the old
version without restarting anything.

Session migration (serving PR 11): the fleet boots a page store and
hands its address(es) to every replica (``MXNET_GEN_PAGESTORE``), so
decode sessions outlive any single replica — a drained/rolled/killed
replica's parked sessions are pushed (or, after SIGKILL, recovered from
their replayed transcripts) and pulled by whichever survivor the router
picks next.  The store itself is survivable too: with
``MXNET_PAGESTORE_REPLICAS`` (or ``pagestore={"replicas": N}``) the
fleet runs a :class:`~mxnet_tpu.kvstore.pagestore.PageStoreFleet` — N
supervised, WAL-durable store processes with synchronous replication
and epoch-fenced failover — instead of the single in-process
:class:`~mxnet_tpu.kvstore.pagestore.PageStoreServer`.
``rollout`` migrates each replica's parked sessions out before the
admin load instead of resetting them, and ``roles=`` specializes
replicas into prefill/decode pools (``router.Router`` routes fresh long
prompts to prefill, everything else to decode).
"""
from __future__ import annotations

import http.client
import json
import os
import time

import numpy as onp

from .. import config as _config
from .. import faults
from .. import profiler
from ..kvstore.pagestore import PageStoreFleet, PageStoreServer
from .autoscale import Autoscaler
from .errors import RolloutAbortedError, ServingError
from .metrics import LatencyHistogram
from .router import Router, RouterServer
from .supervisor import ReplicaSupervisor

__all__ = ["ServingFleet", "rollout"]


def _replica_request(host, port, method, path, body=None, timeout=30.0):
    """One fresh-connection round trip to a replica (admin + probes —
    kept off the router's pooled dispatch connections)."""
    payload = json.dumps(body).encode() if body is not None else None
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request(method, path, body=payload,
                     headers=({"Content-Type": "application/json"}
                              if payload else {}))
        resp = conn.getresponse()
        data = resp.read()
    finally:
        conn.close()
    try:
        doc = json.loads(data.decode() or "{}")
    except ValueError:
        doc = {"error": data.decode(errors="replace"), "code": "internal"}
    return resp.status, doc


def _probe(host, port, name, version, item, n, deadline_ms=2000.0,
           timeout=30.0):
    """n single-item :predict probes pinned to one version on one
    replica; returns (errors, p99_ms)."""
    path = ("/v1/models/%s:predict" % name if version is None
            else "/v1/models/%s/versions/%d:predict" % (name, version))
    hist = LatencyHistogram()
    errors = 0
    for _ in range(n):
        t0 = time.monotonic()
        try:
            status, doc = _replica_request(
                host, port, "POST", path,
                {"instances": [item], "deadline_ms": deadline_ms},
                timeout=timeout)
            if status != 200:
                errors += 1
        except OSError:
            errors += 1
        hist.observe(time.monotonic() - t0)
    snap = hist.snapshot()
    return errors, snap.get("p99_ms")


def _migrate_sessions(host, port, timeout=30.0):
    """Push every generate engine's parked sessions on one replica out
    to the fleet page store (best-effort: a replica without generators,
    without a store, or already dead migrates nothing)."""
    migrated = 0
    try:
        status, doc = _replica_request(host, port, "GET", "/v1/stats",
                                       timeout=timeout)
        if status != 200:
            return 0
        for gname in (doc.get("generators") or {}):
            status, out = _replica_request(
                host, port, "POST", "/v1/admin/migrate_out",
                {"name": gname}, timeout=timeout)
            if status == 200:
                migrated += int(out.get("migrated", 0))
    except OSError:
        return migrated
    return migrated


def rollout(router, model_spec, *, canary_probes=8,
            canary_error_rate=0.25, canary_p99_factor=5.0,
            admin_timeout_s=600.0, order=None):
    """Roll ``model_spec`` (see ``registry.load_model_spec``) across
    every replica of ``router``, canary-first.  Returns a report dict;
    raises :class:`RolloutAbortedError` (after rolling back) when the
    canary regresses.  Works against any admin-enabled replicas — the
    in-process test fleet and the supervised process fleet alike."""
    spec = dict(model_spec)
    name = spec.get("name")
    if not name or not spec.get("builder"):
        raise ServingError("rollout spec needs 'name' and 'builder'")
    rids = list(order) if order else router.replica_ids()
    if not rids:
        raise ServingError("rollout: router has no replicas")
    replicas = {rid: router._replicas[rid] for rid in rids}

    # one fleet-wide version: current latest (across replicas) + 1
    latest = 0
    for r in replicas.values():
        try:
            status, doc = _replica_request(r.host, r.port, "GET",
                                           "/v1/models/%s" % name)
            if status == 200:
                latest = max(latest, int(doc.get("latest", 0)))
        except OSError:
            continue  # ejected/dead replica: the probe loop owns it
    version = int(spec.get("version") or latest + 1)
    spec["version"] = version

    probe_item = None
    if spec.get("item_shape") is not None:
        probe_item = onp.zeros(tuple(spec["item_shape"]),
                               dtype=spec.get("dtype",
                                              "float32")).tolist()

    report = {"model": name, "version": version, "replicas": [],
              "canary": None, "aborted": False}
    profiler.record_event_stat("fleet.rollout_start")
    applied = []

    def _rollback(why):
        for rid in applied:
            r = replicas[rid]
            try:
                _replica_request(r.host, r.port, "POST",
                                 "/v1/admin/unload",
                                 {"name": name, "version": version},
                                 timeout=admin_timeout_s)
            except OSError:
                pass  # dead replica reboots into the OLD spec anyway
            router.set_drain(rid, False)
        profiler.record_event_stat("fleet.rollout_abort")
        report["aborted"] = True
        report["abort_reason"] = why
        raise RolloutAbortedError(
            "rollout of %s v%d aborted and rolled back: %s"
            % (name, version, why))

    baseline_p99 = None
    for i, rid in enumerate(rids):
        r = replicas[rid]
        if i == 0 and probe_item is not None and latest > 0:
            # canary baseline on the OLD version, same replica, same
            # measurement as the post-flip probes
            _, baseline_p99 = _probe(r.host, r.port, name, None,
                                     probe_item, canary_probes)
        router.set_drain(rid, True)
        # migrate parked decode sessions out BEFORE the load: a rollout
        # that swaps a generate engine must not reset anyone's chat —
        # the sessions sit in the page store until their next turn
        # pulls them (usually right back onto this replica, re-warmed)
        migrated = _migrate_sessions(r.host, r.port,
                                     timeout=admin_timeout_s)
        try:
            status, doc = _replica_request(
                r.host, r.port, "POST", "/v1/admin/load", spec,
                timeout=admin_timeout_s)
        except OSError as e:
            _rollback("replica %s unreachable during load: %r" % (rid, e))
        if status != 200:
            _rollback("replica %s load failed: %s"
                      % (rid, doc.get("error", "HTTP %d" % status)))
        applied.append(rid)
        router.set_drain(rid, False)
        report["replicas"].append({"rid": rid,
                                   "warmed": doc["model"]["warmed"],
                                   "migrated_sessions": migrated})
        if i == 0 and probe_item is not None:
            errors, p99 = _probe(r.host, r.port, name, version,
                                 probe_item, canary_probes)
            rate = errors / float(canary_probes)
            report["canary"] = {"rid": rid, "probes": canary_probes,
                                "errors": errors, "error_rate": rate,
                                "p99_ms": p99,
                                "baseline_p99_ms": baseline_p99}
            if rate > canary_error_rate:
                _rollback("canary error rate %.2f > %.2f"
                          % (rate, canary_error_rate))
            if (baseline_p99 and p99
                    and p99 > canary_p99_factor * baseline_p99):
                _rollback("canary p99 %.1fms > %gx baseline %.1fms"
                          % (p99, canary_p99_factor, baseline_p99))
    profiler.record_event_stat("fleet.rollout_done")
    return report


class ServingFleet:
    """N supervised replicas + router + rollout, as one object::

        fleet = ServingFleet(
            {"models": [{"name": "m",
                         "builder": "mxnet_tpu.serving.replica:demo_dense",
                         "kwargs": {"seed": 0}, "item_shape": [16],
                         "max_batch_size": 8}]},
            replicas=3)
        fleet.start()
        cli = ServingClient(*fleet.address)   # fleet looks like 1 server
        ...
        fleet.rollout({"name": "m", "builder": ..., "kwargs": {...},
                       "item_shape": [16], "max_batch_size": 8})
        fleet.stop()
    """

    def __init__(self, spec, *, replicas=None, policy="least_loaded",
                 host="127.0.0.1", port=0, env=None, roles=None,
                 sharding=None, router_kwargs=None,
                 supervisor_kwargs=None, autoscale=None, pagestore=None):
        self.supervisor = ReplicaSupervisor(
            spec, replicas=replicas, host=host, env=env,
            **(supervisor_kwargs or {}))
        # roles: per-replica "prefill" | "decode" | "mixed", by index
        # (spec may also carry a "roles" list); short lists pad "mixed"
        roles = list(roles if roles is not None
                     else (spec.get("roles") or []))
        self._roles = [str(roles[i]) if i < len(roles) else "mixed"
                       for i in range(len(self.supervisor.replicas))]
        for r, role in zip(self.supervisor.replicas, self._roles):
            if role != "mixed":
                self.supervisor.env_by_rid.setdefault(
                    r.rid, {})["MXNET_GEN_ROLE"] = role
        # sharding: per-replica mesh stamping ("sharding" kwarg or spec
        # key) — a dict applies to every replica, a list assigns by
        # index (None/missing entries serve replicated).  The stamped
        # MXNET_MESH_SHAPE / MXNET_MESH_AXES are what a generate spec's
        # {"sharding": {"from_env": true}} block resolves against in
        # the replica process (ShardingConfig.from_env); "host_devices"
        # forces fake host devices so a CPU replica can build the mesh.
        shd = sharding if sharding is not None else spec.get("sharding")
        if shd is None or isinstance(shd, dict):
            shd = [shd] * len(self.supervisor.replicas)
        for r, blk in zip(self.supervisor.replicas, shd):
            if not blk:
                continue
            renv = self.supervisor.env_by_rid.setdefault(r.rid, {})
            shape = blk.get("mesh_shape")
            if shape:
                renv["MXNET_MESH_SHAPE"] = ",".join(
                    str(int(s)) for s in shape)
            axes = blk.get("axis_names")
            if axes:
                renv["MXNET_MESH_AXES"] = ",".join(axes)
            if blk.get("host_devices"):
                renv["XLA_FLAGS"] = (
                    os.environ.get("XLA_FLAGS", "")
                    + " --xla_force_host_platform_device_count=%d"
                    % int(blk["host_devices"])).strip()
        self._policy = policy
        self._router_kwargs = dict(router_kwargs or {})
        self._host = host
        self._port = int(port)
        # autoscale=True enables the control loop with config-knob
        # defaults; a dict supplies Autoscaler(**kwargs) overrides
        self._autoscale_cfg = autoscale
        # pagestore={"replicas": N, "dir": ..., "processes": bool, ...}
        # opts into the durable, replicated store (PageStoreFleet);
        # None defers to MXNET_PAGESTORE_REPLICAS / _DIR config knobs
        self._pagestore_cfg = dict(pagestore or {})
        self.router = None
        self.server = None
        self.pagestore = None
        self.autoscaler = None

    @property
    def address(self):
        return self.server.address

    def start(self):
        # the fleet page store is the session-migration rendezvous; every
        # replica learns its address through the environment (an env=
        # override of MXNET_GEN_PAGESTORE wins — e.g. an external store)
        if (int(_config.get("MXNET_GEN_MIGRATE"))
                and "MXNET_GEN_PAGESTORE" not in self.supervisor.env):
            n_store = int(self._pagestore_cfg.get(
                "replicas", _config.get("MXNET_PAGESTORE_REPLICAS")))
            if n_store >= 1:
                # durable, replicated store: N supervised members,
                # epoch-fenced failover; replicas get the full address
                # list (primary first) and fail over client-side
                cfg = dict(self._pagestore_cfg)
                cfg.pop("replicas", None)
                cfg.setdefault("host", self._host)
                self.pagestore = PageStoreFleet(replicas=n_store, **cfg)
                self.supervisor.env["MXNET_GEN_PAGESTORE"] = (
                    self.pagestore.start())
            else:
                # single in-process store (durable when
                # MXNET_PAGESTORE_DIR is set — the dir is read by the
                # PageStoreServer constructor)
                self.pagestore = PageStoreServer(host=self._host)
                self.supervisor.env["MXNET_GEN_PAGESTORE"] = (
                    self.pagestore.start())
        self.supervisor.start()
        self.router = Router(self.supervisor.addresses(),
                             policy=self._policy, roles=self._roles,
                             **self._router_kwargs)
        self.server = RouterServer(self.router, host=self._host,
                                   port=self._port,
                                   supervisor=self.supervisor,
                                   pagestore=self.pagestore)
        self.server.start()
        if self._autoscale_cfg:
            kwargs = (dict(self._autoscale_cfg)
                      if isinstance(self._autoscale_cfg, dict) else {})
            self.autoscaler = Autoscaler(
                collect=self._autoscale_collect,
                scale_up=self._autoscale_up,
                scale_down=self._autoscale_down,
                flip_role=self._autoscale_flip, **kwargs)
            self.server.autoscaler = self.autoscaler
            self.autoscaler.start()
        return self.address

    def rollout(self, model_spec, **kwargs):
        return rollout(self.router, model_spec, **kwargs)

    # -- autoscaler hooks -------------------------------------------------
    # The Autoscaler is deliberately fleet-agnostic: it sees a stats
    # dict and calls back into these four hooks, so tier-1 tests can
    # drive the same control loop on fake stats with no processes.

    def _autoscale_collect(self):
        """Fleet-wide load signals: router membership + each routable
        replica's own /v1/stats (queue depth, busy slots, KV occupancy)."""
        out = {}
        for rid, st in self.router.states().items():
            routable = (st.get("state") == "healthy" and st.get("ready")
                        and not st.get("draining"))
            row = {"role": st.get("role", "mixed"), "routable": routable,
                   "queued": 0, "active": 0, "slots": 0, "kv_frac": 0.0}
            if routable:
                host, _, port = rid.rpartition(":")
                try:
                    status, doc = _replica_request(host, int(port), "GET",
                                                   "/v1/stats", timeout=5.0)
                except (OSError, ValueError):
                    status, doc = 0, {}
                if status == 200:
                    for g in (doc.get("generators") or {}).values():
                        row["queued"] += int(g.get("queued", 0))
                        row["active"] += int(g.get("active", 0))
                        row["slots"] += int(g.get("slots", 0))
                        kv = g.get("kv") or {}
                        row["kv_frac"] = max(row["kv_frac"],
                                             float(kv.get("occupancy",
                                                          0.0)))
                    for depth in (doc.get("queue_depths") or {}).values():
                        row["queued"] += int(depth)
            out[rid] = row
        return {"replicas": out}

    def _autoscale_up(self, role="mixed"):
        """Spawn one replica under the chip budget and register it with
        the router unroutable; the probe loop admits it on /readyz."""
        faults.check("replica.spawn")
        env = {"MXNET_GEN_ROLE": role} if role != "mixed" else None
        r = self.supervisor.add_replica(env=env)
        self.router.add_replica(r.addr, role=role, ready=False)
        return r.addr

    def _autoscale_down(self, rid):
        """Drain one replica without resetting anyone: stop new traffic,
        park every decode session in the page store, then retire the
        process.  Returns the number of sessions migrated out."""
        self.router.set_drain(rid, True)
        host, _, port = rid.rpartition(":")
        migrated = _migrate_sessions(host, int(port))
        self.router.remove_replica(rid)
        for r in list(self.supervisor.replicas):
            if r.addr == rid:
                self.supervisor.stop_replica(r.rid)
                break
        return migrated

    def _autoscale_flip(self, rid, role):
        """Repurpose one replica prefill<->decode at runtime: flip the
        engine's own role gate, then the router's pool assignment, then
        the supervisor env so a crash-restart keeps the new role."""
        host, _, port = rid.rpartition(":")
        _replica_request(host, int(port), "POST", "/v1/admin/set_role",
                         {"role": role}, timeout=10.0)
        self.router.set_role(rid, role)
        for r in self.supervisor.replicas:
            if r.addr == rid:
                renv = self.supervisor.env_by_rid.setdefault(r.rid, {})
                if role == "mixed":
                    renv.pop("MXNET_GEN_ROLE", None)
                else:
                    renv["MXNET_GEN_ROLE"] = role
                break
        return role

    def status(self):
        return {"router": self.router.snapshot() if self.router else None,
                "supervisor": self.supervisor.states(),
                "autoscale": (self.autoscaler.snapshot()
                              if self.autoscaler else None),
                "pagestore": (self.pagestore.stats_summary()
                              if self.pagestore else None)}

    def stop(self):
        if self.autoscaler is not None:
            self.autoscaler.stop()
            self.autoscaler = None
        if self.server is not None:
            self.server.stop()  # stops the router's probe loop too
            self.server = None
        self.supervisor.stop()
        if self.pagestore is not None:
            self.pagestore.stop()
            self.pagestore = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False
