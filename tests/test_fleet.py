"""Serving-fleet tier-1 matrix (in-process replicas unless a real
process is the point) plus the slow chaos acceptance.

Covers: least-loaded and consistent-hash dispatch, strike/eject/
re-admit passive+active failure detection, shed-retry then router-level
shed (backpressure propagation with Retry-After), deterministic
router.dispatch fault injection, idempotency-aware failover, rolling
rollout with canary abort + rollback (zero-downtime under concurrent
traffic), persistent-compile-cache warm restart, and the supervisor's
auto-restart + crash-loop budget.  The SIGKILL-a-replica-under-
sustained-load acceptance runs tools/chaos.py --scenario fleet in the
slow lane.
"""
import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as onp
import pytest

from mxnet_tpu import faults, profiler, serving
from mxnet_tpu.serving.fleet import rollout
from mxnet_tpu.serving.replica import demo_affine

pytestmark = [pytest.mark.serving, pytest.mark.fleet]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ITEM = (4,)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _server(fn=None, *, admin=True, max_queue_depth=256, flush_ms=2,
            **load_kwargs):
    """One in-process 'replica': registry + batcher + HTTP server."""
    reg = serving.ModelRegistry()
    reg.load("m", fn if fn is not None else demo_affine(scale=2.0),
             item_shape=ITEM, max_batch_size=4, warmup=False,
             **load_kwargs)
    srv = serving.ModelServer(reg, flush_ms=flush_ms, admin=admin,
                              max_queue_depth=max_queue_depth)
    srv.start()
    return srv


def _addrs(servers):
    return ["127.0.0.1:%d" % s.port for s in servers]


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


X = onp.arange(8, dtype="float32").reshape(2, 4)


# ---------------------------------------------------------------------------
# dispatch policies
# ---------------------------------------------------------------------------
def test_least_loaded_dispatch_spreads_and_is_correct():
    servers = [_server() for _ in range(3)]
    router = serving.Router(_addrs(servers), probe_ms=0)
    rs = serving.RouterServer(router)
    rs.start()
    try:
        cli = serving.ServingClient(*rs.address, timeout=10)
        for _ in range(12):
            onp.testing.assert_allclose(cli.predict("m", X), X * 2.0)
        st = router.states()
        # every replica took traffic (round-robin tie-break on idle)
        assert all(s["counters"]["responses"] > 0 for s in st.values()), st
        snap = router.snapshot()
        assert snap["counters"]["responses_total"] == 12
        assert "p99_ms" in snap["latency"]
        # the fleet profiler table recorded the dispatches
        assert profiler.aggregate_stats()["fleet"][
            "router.dispatch"]["count"] >= 12
        cli.close()
    finally:
        rs.stop()
        for s in servers:
            s.stop()


def test_consistent_hash_affinity_and_remap_on_ejection():
    servers = [_server() for _ in range(3)]
    router = serving.Router(_addrs(servers), policy="hash", probe_ms=0)
    try:
        per_key_owner = {}
        for key in range(40):
            before = {rid: s["counters"]["dispatched"]
                      for rid, s in router.states().items()}
            status, _ = router.dispatch(
                "/v1/models/m:predict", {"instances": [X[0].tolist()]},
                affinity_key="k%d" % key)
            assert status == 200
            after = {rid: s["counters"]["dispatched"]
                     for rid, s in router.states().items()}
            owner = [rid for rid in after if after[rid] > before[rid]]
            assert len(owner) == 1
            per_key_owner["k%d" % key] = owner[0]
        # 40 keys spread over >1 replica (vnode ring, not mod-hash)
        assert len(set(per_key_owner.values())) > 1
        # repeating any key hits the same owner
        for key, owner in list(per_key_owner.items())[:5]:
            before = router.states()[owner]["counters"]["dispatched"]
            router.dispatch("/v1/models/m:predict",
                            {"instances": [X[0].tolist()]},
                            affinity_key=key)
            assert router.states()[owner]["counters"]["dispatched"] \
                == before + 1
        # eject an owner: only ITS keys remap, and deterministically
        victim = per_key_owner["k0"]
        with router._lock:
            router._replicas[victim].state = "ejected"
        status, _ = router.dispatch("/v1/models/m:predict",
                                    {"instances": [X[0].tolist()]},
                                    affinity_key="k0")
        assert status == 200  # served by the next ring owner
        # re-admit: the key returns home (ring is stable, not rebuilt)
        with router._lock:
            router._replicas[victim].state = "healthy"
        before = router.states()[victim]["counters"]["dispatched"]
        router.dispatch("/v1/models/m:predict",
                        {"instances": [X[0].tolist()]},
                        affinity_key="k0")
        assert router.states()[victim]["counters"]["dispatched"] \
            == before + 1
    finally:
        router.stop()
        for s in servers:
            s.stop()


# ---------------------------------------------------------------------------
# failure detection: strikes, ejection, re-admission
# ---------------------------------------------------------------------------
def test_strike_eject_readmit_cycle():
    live = _server()
    dead_port = _free_port()  # nothing listening: connect refused
    router = serving.Router(
        ["127.0.0.1:%d" % dead_port, "127.0.0.1:%d" % live.port],
        strikes=2, probe_ms=50, eject_backoff_ms=50)
    dead_rid = "127.0.0.1:%d" % dead_port
    try:
        # every request succeeds (failover), while the dead replica
        # accumulates strikes and gets ejected
        for _ in range(6):
            status, doc = router.dispatch("/v1/models/m:predict",
                                          {"instances": [X[0].tolist()]})
            assert status == 200
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and \
                router.states()[dead_rid]["state"] != "ejected":
            time.sleep(0.02)
        st = router.states()[dead_rid]
        assert st["state"] == "ejected"
        assert st["counters"]["ejections"] >= 1
        assert router.metrics.counters["retries_total"] >= 1
        # traffic now bypasses the ejected replica entirely
        before = router.states()[dead_rid]["counters"]["dispatched"]
        for _ in range(4):
            assert router.dispatch("/v1/models/m:predict",
                                   {"instances": [X[0].tolist()]}
                                   )[0] == 200
        assert router.states()[dead_rid]["counters"]["dispatched"] \
            == before
        # a server appears on the dead port: probe loop re-admits it
        reg = serving.ModelRegistry()
        reg.load("m", demo_affine(scale=2.0), item_shape=ITEM,
                 max_batch_size=4, warmup=False)
        revived = serving.ModelServer(reg, flush_ms=2, port=dead_port)
        revived.start()
        try:
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and \
                    router.states()[dead_rid]["state"] != "healthy":
                time.sleep(0.05)
            st = router.states()[dead_rid]
            assert st["state"] == "healthy", st
            assert st["counters"]["readmissions"] >= 1
            ev = profiler.aggregate_stats()["events"]
            assert ev.get("fleet.eject", 0) >= 1
            assert ev.get("fleet.readmit", 0) >= 1
        finally:
            revived.stop()
    finally:
        router.stop()
        live.stop()


def test_router_dispatch_fault_injection_fails_over():
    """Deterministic chaos at the router.dispatch site: injected resets
    read as replica transport failures (strike + failover) yet every
    client request still succeeds."""
    servers = [_server() for _ in range(2)]
    router = serving.Router(_addrs(servers), strikes=5, probe_ms=0)
    try:
        with faults.inject("router.dispatch", "reset", n=3):
            for _ in range(9):
                status, _ = router.dispatch(
                    "/v1/models/m:predict", {"instances": [X[0].tolist()]})
                assert status == 200
        # >= 3: the failover retries re-enter the injection site, so a
        # retry can itself trip the every-3rd-call rule
        assert faults.stats()["tripped"]["router.dispatch"] >= 3
        assert router.metrics.counters["retries_total"] >= 3
        assert router.metrics.counters["responses_total"] == 9
    finally:
        router.stop()
        for s in servers:
            s.stop()


def test_non_idempotent_inflight_failure_not_replayed():
    """A connection the replica kills AFTER reading the request fails
    over only for idempotent requests; ``idempotent=False`` surfaces the
    failure instead of double-running the predict."""
    # slammer replica: accepts, reads, slams — reply-phase loss
    lsock = socket.socket()
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(8)
    hits = []
    stop = threading.Event()

    def slammer():
        lsock.settimeout(0.2)
        while not stop.is_set():
            try:
                conn, _ = lsock.accept()
            except socket.timeout:
                continue
            hits.append(1)
            try:
                conn.recv(65536)
            finally:
                conn.close()

    t = threading.Thread(target=slammer, daemon=True)
    t.start()
    good = _server()
    # slammer first: least-loaded tie-break picks insertion order on idle
    router = serving.Router(
        ["127.0.0.1:%d" % lsock.getsockname()[1],
         "127.0.0.1:%d" % good.port], strikes=10, probe_ms=0)
    try:
        n0 = len(hits)
        with pytest.raises(serving.ServingError, match="non-idempotent"):
            router.dispatch("/v1/models/m:predict",
                            {"instances": [X[0].tolist()]},
                            idempotent=False)
        assert len(hits) - n0 == 1  # sent once, reply lost, NOT replayed
        # same failure with the default (stateless models are pure):
        # fails over to the good replica and succeeds
        status, doc = router.dispatch("/v1/models/m:predict",
                                      {"instances": [X[0].tolist()]})
        assert status == 200
    finally:
        router.stop()
        good.stop()
        stop.set()
        t.join(5)
        lsock.close()


def test_poisoned_request_error_propagates_not_shed():
    """A request that fails the MODEL on every replica (poisoned input)
    must come back as the replica's own 500, not disguise itself as a
    503 fleet-overload shed — it would fail everywhere forever."""
    def fussy(batch):
        if onp.isnan(onp.asarray(batch)).any():
            raise ValueError("poisoned input")
        return onp.asarray(batch) * 2.0

    servers = [_server(fussy) for _ in range(2)]
    router = serving.Router(_addrs(servers), strikes=10, probe_ms=0)
    try:
        poison = [1.0, float("nan"), 1.0, 1.0]
        status, doc = router.dispatch("/v1/models/m:predict",
                                      {"instances": [poison]})
        assert status == 500 and "poisoned" in doc["error"]
        # both replicas were tried (the retry), then the error surfaced
        assert sum(s["counters"]["errors"]
                   for s in router.states().values()) == 2
        # the fleet still serves good requests
        status, _ = router.dispatch("/v1/models/m:predict",
                                    {"instances": [X[0].tolist()]})
        assert status == 200
    finally:
        router.stop()
        for s in servers:
            s.stop()


# ---------------------------------------------------------------------------
# backpressure propagation
# ---------------------------------------------------------------------------
def test_shed_retry_then_router_shed_with_retry_after():
    """Replica 503 load-shed retries once on the least-loaded
    alternative; when EVERY replica sheds, the router sheds at its own
    socket with Retry-After instead of queueing."""
    gates = [threading.Event(), threading.Event()]

    def blocked(gate):
        def fn(batch):
            gate.wait(20)
            return onp.asarray(batch) * 2.0
        return fn

    servers = [_server(blocked(g), max_queue_depth=1, flush_ms=1)
               for g in gates]
    router = serving.Router(_addrs(servers), probe_ms=0)
    rs = serving.RouterServer(router)
    rs.start()
    try:
        cli = serving.ServingClient(*rs.address, timeout=20, retries=0)
        # occupy both replicas' workers + fill both queues directly
        futs = []
        for srv in servers:
            futs.append(srv.batcher.submit("m", X[0]))  # worker grabs it
            for _ in range(200):
                if srv.batcher.queue_depth("m") == 0:
                    break
                time.sleep(0.005)
            futs.append(srv.batcher.submit("m", X[0]))  # queue now full
        # through the router: replica A sheds -> retried on B -> B sheds
        # -> the ROUTER sheds with Retry-After (backpressure propagated)
        with pytest.raises(serving.QueueFullError) as ei:
            cli.predict("m", X[:1], deadline_ms=5000)
        assert getattr(ei.value, "retry_after", None) is not None
        st = router.states()
        assert sum(s["counters"]["sheds"] for s in st.values()) == 2
        assert router.metrics.counters["shed_total"] >= 3  # 2 + router's
        # relief: open the gates, the fleet serves again (single item:
        # a 2-instance batch could legitimately re-shed a depth-1 queue)
        for g in gates:
            g.set()
        for f in futs:
            f.result(timeout=20)
        onp.testing.assert_allclose(cli.predict("m", X[:1]), X[:1] * 2.0)
        cli.close()
    finally:
        for g in gates:
            g.set()
        rs.stop()
        for s in servers:
            s.stop()


# ---------------------------------------------------------------------------
# rolling rollout
# ---------------------------------------------------------------------------
def test_rolling_rollout_zero_downtime_under_traffic():
    """Rollout drains one replica at a time and hot-swaps via the
    registry: concurrent traffic sees zero failures, old results until
    the flip, new ones after, and BOTH replicas finish on the new
    version."""
    servers = [_server() for _ in range(2)]
    router = serving.Router(_addrs(servers), probe_ms=0)
    errors, stop = [], threading.Event()

    def traffic():
        while not stop.is_set():
            try:
                status, doc = router.dispatch(
                    "/v1/models/m:predict", {"instances": [X[0].tolist()]})
                assert status == 200, doc
                v = float(doc["predictions"][0][0])
                if v not in (0.0,):  # X[0][0] == 0 -> 0 under any scale
                    errors.append(("value", v))
            except Exception as e:  # pragma: no cover
                errors.append(("exc", repr(e)))

    th = threading.Thread(target=traffic, daemon=True)
    th.start()
    try:
        report = rollout(
            router,
            {"name": "m",
             "builder": "mxnet_tpu.serving.replica:demo_affine",
             "kwargs": {"scale": 3.0}, "item_shape": list(ITEM),
             "max_batch_size": 4, "warmup": False}, canary_probes=4)
        stop.set()
        th.join(10)
        assert not errors, errors[:3]
        assert report["version"] == 2 and not report["aborted"]
        assert report["canary"]["errors"] == 0
        for srv in servers:
            assert srv.registry.latest_version("m") == 2
        status, doc = router.dispatch("/v1/models/m:predict",
                                      {"instances": [X[1].tolist()]})
        onp.testing.assert_allclose(onp.asarray(doc["predictions"][0]),
                                    X[1] * 3.0)
        # nobody is left drained
        assert not any(s["draining"] for s in router.states().values())
    finally:
        stop.set()
        router.stop()
        for s in servers:
            s.stop()


def test_rollout_canary_abort_rolls_back():
    """A new version whose canary error rate regresses is unloaded
    everywhere it landed; the fleet converges back to the old version
    and replicas 2..N never see the bad version at all."""
    servers = [_server() for _ in range(3)]
    router = serving.Router(_addrs(servers), probe_ms=0)
    try:
        with pytest.raises(serving.RolloutAbortedError, match="error rate"):
            rollout(router,
                    {"name": "m",
                     "builder": "mxnet_tpu.serving.replica:demo_faulty",
                     "kwargs": {"p": 1.0}, "item_shape": list(ITEM),
                     "max_batch_size": 4, "warmup": False},
                    canary_probes=4)
        ev = profiler.aggregate_stats()["events"]
        assert ev.get("fleet.rollout_abort", 0) >= 1
        for srv in servers:
            assert srv.registry.latest_version("m") == 1  # rolled back
        assert not any(s["draining"] for s in router.states().values())
        status, doc = router.dispatch("/v1/models/m:predict",
                                      {"instances": [X[0].tolist()]})
        assert status == 200  # old version still serving
    finally:
        router.stop()
        for s in servers:
            s.stop()


def test_rollout_canary_p99_regression_aborts():
    """The canary gate also trips on tail latency: a new version 50x
    slower than baseline rolls back even though it answers correctly."""
    servers = [_server() for _ in range(2)]
    router = serving.Router(_addrs(servers), probe_ms=0)
    try:
        with pytest.raises(serving.RolloutAbortedError, match="p99"):
            rollout(router,
                    {"name": "m",
                     "builder": "mxnet_tpu.serving.replica:demo_affine",
                     "kwargs": {"scale": 3.0, "slow_ms": 300.0},
                     "item_shape": list(ITEM), "max_batch_size": 4,
                     "warmup": False},
                    canary_probes=3, canary_p99_factor=5.0)
        for srv in servers:
            assert srv.registry.latest_version("m") == 1
    finally:
        router.stop()
        for s in servers:
            s.stop()


# ---------------------------------------------------------------------------
# persistent compile cache (warm restart)
# ---------------------------------------------------------------------------
_CACHE_SCRIPT = r"""
import json, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_COMPILATION_CACHE_DIR"] = sys.argv[1]
# a CPU compile of the demo model is faster than JAX's 1 s floor
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
from mxnet_tpu import runtime, serving
from mxnet_tpu.serving.replica import demo_dense
assert runtime.enable_compile_cache() == sys.argv[1]   # as replica main does
reg = serving.ModelRegistry()
t0 = time.monotonic()
served = reg.load("m", demo_dense(seed=0), item_shape=(16,),
                  max_batch_size=4)  # warmup=True: compile every bucket
print(json.dumps({"warm_s": time.monotonic() - t0,
                  "warmed": served.warmed,
                  "entries": sorted(f for f in os.listdir(sys.argv[1])
                                    if f.endswith("-cache"))}))
"""


def test_compile_cache_warm_restart(tmp_path):
    """Two replica boots against one JAX_COMPILATION_CACHE_DIR: the first
    writes per-bucket executables, the second's warmup is pure cache
    reads — zero NEW cache entries (every compile was a hit)."""
    cache = str(tmp_path / "xla-cache")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    def boot():
        out = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT, cache],
                             capture_output=True, text=True, timeout=300,
                             env=env, cwd=REPO)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    first = boot()
    assert first["warmed"] and first["entries"], first
    second = boot()
    assert second["warmed"]
    # warm restart compiled NOTHING new: same cache entries, all hits
    assert second["entries"] == first["entries"]


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------
class _FakeProc:
    def __init__(self, rc=1):
        self.pid = 4242
        self._rc = rc

    def poll(self):
        return self._rc

    def wait(self, timeout=None):
        return self._rc

    def send_signal(self, sig):
        pass


def test_supervisor_crash_loop_budget_and_backoff(monkeypatch):
    """A replica that dies instantly is restarted with exponential
    backoff at most restart_budget times per window, then declared
    failed — the crash-loop brake (unit-level: fake processes)."""
    sup = serving.ReplicaSupervisor(
        {"models": []}, replicas=1, restart_budget=3,
        restart_window_s=60.0, restart_backoff_ms=300)
    spawns = []

    def fake_spawn(r):
        spawns.append(time.monotonic())
        r.proc = _FakeProc(rc=1)  # dies immediately
        r.state = "running"
        r.started_at = time.monotonic()
        return r

    monkeypatch.setattr(sup, "_spawn", fake_spawn)
    sup._spec_path = None
    fake_spawn(sup.replicas[0])
    sup._monitor = threading.Thread(target=sup._monitor_loop, daemon=True)
    sup._monitor.start()
    try:
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and \
                sup.replicas[0].state != "failed":
            time.sleep(0.02)
        r = sup.replicas[0]
        assert r.state == "failed"
        assert r.restarts == 3  # the budget, not one more
        assert len(spawns) == 4  # initial + 3 restarts
        # consecutive crashes backed off: 0.3/0.6/1.2 s (the monitor's
        # 0.1 s poll quantizes, hence the coarse base + margin)
        gaps = [b - a for a, b in zip(spawns, spawns[1:])]
        assert gaps[-1] > gaps[0] + 0.4
        ev = profiler.aggregate_stats()["events"]
        assert ev.get("fleet.crash_loop", 0) >= 1
    finally:
        sup._stop.set()
        sup._monitor.join(5)


def test_supervisor_restarts_sigkilled_replica_real_process():
    """One REAL replica process: SIGKILL it, the supervisor respawns it
    on the same port and it answers /readyz again (the router re-admits
    by address, so no reconfiguration is ever needed)."""
    spec = {"models": [{"name": "m",
                        "builder": "mxnet_tpu.serving.replica:demo_affine",
                        "kwargs": {"scale": 2.0}, "item_shape": [4],
                        "max_batch_size": 4, "warmup": False}],
            "flush_ms": 2}
    sup = serving.ReplicaSupervisor(
        spec, replicas=1, restart_backoff_ms=50,
        env={"JAX_PLATFORMS": "cpu"})
    try:
        sup.start()
        assert sup.ready_count() == 1
        port = sup.replicas[0].port
        pid0 = sup.replicas[0].proc.pid
        sup.kill(0, signal.SIGKILL)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and sup.ready_count() < 1:
            time.sleep(0.1)
        r = sup.replicas[0]
        assert r.alive() and r.proc.pid != pid0
        assert r.port == port and r.restarts == 1
        # the restarted replica actually serves
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("POST", "/v1/models/m:predict",
                     body=json.dumps({"instances": [[1, 1, 1, 1]]}),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        doc = json.loads(resp.read())
        conn.close()
        assert resp.status == 200
        onp.testing.assert_allclose(doc["predictions"][0],
                                    [2.0, 2.0, 2.0, 2.0])
    finally:
        sup.stop()


def test_replica_crash_fault_site_parses():
    rules = faults.parse_spec(
        "replica.crash:kill@n=5;router.dispatch:reset@p=0.1")
    assert [r.site for r in rules] == ["replica.crash", "router.dispatch"]
    with faults.inject("replica.crash", "kill", n=1):
        assert faults.check("replica.crash") == "kill"  # soft kind


# ---------------------------------------------------------------------------
# chaos acceptance (slow lane)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_chaos_fleet_sigkill_under_load_and_rollout():
    """The ISSUE acceptance: SIGKILL one of 3 replicas mid-traffic —
    zero failed requests, p99 < 5x steady state, supervisor restores
    the fleet, and a rolling rollout completes during traffic."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos.py"),
         "--scenario", "fleet", "-n", "3"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    sys.stdout.write(out.stdout[-3000:])
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "chaos: PASS" in out.stdout
