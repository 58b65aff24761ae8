#!/usr/bin/env python
"""Chaos runner: a short dist_sync training job under a standard fault
spec, asserting the resilience invariants hold end to end.

Runs tests/dist_worker.py in "trainer" mode through tools/launch.py
twice — once clean, once with MXNET_FAULT_SPEC injected into every
worker — and checks that (1) faults actually tripped, (2) replicas
stayed identical within each run, and (3) the faulty run's final
weights are bit-identical to the clean run's (bounded retry + reconnect
+ server-side (key, rank, seq) dedup must never drop or double-apply a
gradient).

Scenarios (--scenario):
  faults   (default) transport-fault chaos: faulty vs clean dist_sync
           run, PASS when bit-identical (the PR-3 acceptance).
  preempt  elastic preemption: SIGTERM worker 1 mid-epoch (it must exit
           0 after a graceful checkpoint + membership leave), relaunch
           it, and PASS when the job completes without manual
           intervention — step count conserved (every global step
           applied exactly once), replicas identical.
  mesh     elastic mesh resharding: SIGKILL one worker of a dp=4xtp=2
           mesh run mid-epoch (its chips hold irreplaceable tp shards).
           The server evicts it, survivors shrink the mesh dp-first,
           recover every shard from the newest sharded boundary
           checkpoint, and finish.  PASS when zero shards are
           unrecovered, the checkpoint dir leaks no orphan shard files,
           and the survivor's final params are bit-identical to a fresh
           run at the surviving world size from the same checkpoint.
  fleet    serving-fleet failover: N supervised replicas behind the
           router under sustained closed-loop load; SIGKILL one replica
           mid-traffic.  PASS when (1) ZERO requests fail (the router
           fails in-flight idempotent predicts over to a survivor),
           (2) the kill-window p99 stays < 5x the steady-state p99,
           (3) the supervisor restores the full replica count, and
           (4) a subsequent rolling model rollout (canary + drain one
           at a time) completes during traffic with zero dropped
           requests and the new version serving everywhere.
  llm      LLM decode failover + session migration: N replicas serving
           a causal LM through the continuous-batching decode engine
           (consistent-hash session affinity, fleet page store);
           SIGKILL one mid-generation under sustained decode traffic,
           then roll the generate engine with sessions parked.  PASS
           when sessionless generations never fail, every session
           failure is TYPED (explicit non-idempotent error — no silent
           misroute), ZERO sessions reset (SIGKILL and rollout both
           recover through the page store: pages when pushed, replayed
           transcripts otherwise), the supervisor restores the fleet,
           fresh sessions work, and router-level failures are zero.
  ramp     fleet autoscaling + SLO admission: a 10x diurnal traffic
           ramp (two tiers, three tenants) against one replica under a
           chip budget of 3.  PASS when the autoscaler scales out on
           the ramp and back in after the drop (never exceeding the
           budget), drains migrate every parked session (ZERO resets —
           dawn's sessions resume after the full cycle), bulk is shed
           at least as often as latency with honest Retry-After on
           every shed, latency-tier p99 during the scaled-up hold
           stays <= 5x steady-state, and /v1/stats carries the full
           auditable decision ring.
  store    durable, replicated page store: (A) SIGKILL -9 a single
           store process and restart it on the same WAL dir — every
           record AND every generation fence must come back (a stale
           put from a pre-crash holder still bounces); (B) SIGKILL the
           store PRIMARY of a 3-member replicated store under session
           traffic, mid-autoscale-drain and again mid-rollout.  PASS
           when zero sessions reset, warm transcripts stay
           bit-identical to the greedy oracle, the store fails over
           both times (epoch-fenced), and killed members heal back in.

Usage:
  python tools/chaos.py                       # default spec, 2 workers
  python tools/chaos.py -n 4 -s 2 \\
      --spec 'kvstore.send:reset@p=0.1;kvstore.recv:reset@p=0.05'
  python tools/chaos.py --no-compare-clean    # skip the baseline run
  python tools/chaos.py --scenario preempt    # SIGTERM + rejoin drill
  python tools/chaos.py --scenario fleet -n 3 # kill-a-replica drill

Exit code 0 = all invariants held.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAUNCH = os.path.join(REPO, "tools", "launch.py")
WORKER = os.path.join(REPO, "tests", "dist_worker.py")

DEFAULT_SPEC = "kvstore.send:reset@p=0.05;kvstore.recv:reset@p=0.03"


def _run(out_dir, n, s, spec=None):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("MXNET_FAULT_SPEC", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("MXNET_KV_BACKOFF_MS", "5")
    if spec:
        env["MXNET_FAULT_SPEC"] = spec
    r = subprocess.run(
        [sys.executable, LAUNCH, "-n", str(n), "-s", str(s),
         sys.executable, WORKER, out_dir, "trainer"],
        cwd=REPO, env=env, timeout=600)
    if r.returncode != 0:
        raise SystemExit("chaos: launch failed (rc=%d)" % r.returncode)
    results = []
    for w in range(n):
        with open(os.path.join(out_dir, "worker%d.json" % w)) as f:
            results.append(json.load(f))
    return results


def _params_equal(a, b, label):
    import numpy as onp
    if a.keys() != b.keys():
        print("FAIL [%s]: parameter sets differ" % label)
        return False
    ok = True
    for k in a:
        if not onp.array_equal(onp.asarray(a[k]), onp.asarray(b[k])):
            print("FAIL [%s]: divergence in %s" % (label, k))
            ok = False
    return ok


def _spawn_cluster(out_dir, n, s, env, worker_mode="elastic"):
    """launch.py's local env contract, but with direct Popen handles so
    the scenario can SIGTERM / relaunch individual workers."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from launch import _reserve_ports, _wait_servers_ready
    port = _reserve_ports(s)
    env = dict(env)
    env.update({
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": str(n),
        "DMLC_NUM_SERVER": str(s),
        "MXNET_KVSTORE_SYNC": "1",
    })
    servers = []
    for sid in range(s):
        senv = dict(env)
        senv.update({"DMLC_ROLE": "server", "DMLC_SERVER_ID": str(sid),
                     "DMLC_SERVER_PORT": str(port + sid)})
        servers.append(subprocess.Popen(
            [sys.executable, "-c",
             "import mxnet_tpu as mx;"
             "mx.kvstore._init_kvstore_server_module()"], env=senv))
    if not _wait_servers_ready(servers, port, s):
        raise SystemExit("chaos: servers failed to start")

    def spawn_worker(wid):
        wenv = dict(env)
        wenv.update({"DMLC_ROLE": "worker", "DMLC_WORKER_ID": str(wid)})
        return subprocess.Popen(
            [sys.executable, WORKER, out_dir, worker_mode],
            cwd=REPO, env=wenv)

    return servers, spawn_worker


def scenario_preempt(args):
    """SIGTERM worker 1 mid-epoch; it must exit 0 (graceful checkpoint +
    membership leave); relaunch it; the job must complete without manual
    intervention with the step count conserved and replicas identical."""
    n, s = args.num_workers, args.num_servers
    total = 12
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("MXNET_FAULT_SPEC", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("MXNET_KV_BACKOFF_MS", "5")
    env["ELASTIC_TOTAL_STEPS"] = str(total)
    # pace the steps so the SIGTERM reliably lands mid-epoch (after the
    # first steps, well before the last)
    env["ELASTIC_STEP_DELAY"] = "0.4"
    env.setdefault("MXNET_PREEMPT_GRACE_SEC", "30")

    ok = True
    with tempfile.TemporaryDirectory(prefix="chaos-preempt-") as out_dir:
        servers, spawn_worker = _spawn_cluster(out_dir, n, s, env)
        workers = {wid: spawn_worker(wid) for wid in range(n)}
        try:
            # preempt only after real progress (the workers' per-step
            # heartbeat), never during startup compiles — and well before
            # the end of the epoch
            hb = os.path.join(out_dir, "progress_rank1")
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                try:
                    with open(hb) as f:
                        if int(f.read() or 0) >= 3:
                            break
                except (OSError, ValueError):
                    pass
                if workers[1].poll() is not None:
                    break
                time.sleep(0.1)
            victim = workers[1]
            if victim.poll() is not None:
                print("FAIL: worker 1 finished before the preemption — "
                      "scenario did not test anything")
                return 1
            print("chaos: SIGTERM worker 1 (pid %d) mid-epoch"
                  % victim.pid)
            victim.send_signal(signal.SIGTERM)
            rc = victim.wait(timeout=120)
            if rc != 0:
                print("FAIL: preempted worker exited %d (graceful "
                      "preemption must exit 0)" % rc)
                ok = False
            ckpt = os.path.join(out_dir, "ckpt_rank1")
            if not os.path.isdir(ckpt) or not os.listdir(ckpt):
                print("FAIL: no graceful checkpoint written at %s" % ckpt)
                ok = False
            print("chaos: relaunching worker 1")
            workers[1] = spawn_worker(1)
            for wid, w in workers.items():
                rc = w.wait(timeout=300)
                if rc != 0:
                    print("FAIL: worker %d exited %d" % (wid, rc))
                    ok = False
            if not ok:
                return 1
            results = []
            for wid in range(n):
                with open(os.path.join(out_dir,
                                       "worker%d.json" % wid)) as f:
                    results.append(json.load(f))
        finally:
            for w in workers.values():
                if w.poll() is None:
                    w.kill()
            for p in servers:
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
            for p in servers:
                try:
                    p.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    p.kill()

        # relaunched worker actually resumed (not restarted from 0)
        if results[1]["start_step"] <= 0:
            print("FAIL: relaunched worker started from step %d — it "
                  "never resumed" % results[1]["start_step"])
            ok = False
        # step count conserved: every global step applied exactly once
        if results[0]["status"]["round"] != total:
            print("FAIL: server completed %s rounds, expected %d"
                  % (results[0]["status"]["round"], total))
            ok = False
        if not _params_equal(results[0]["params"], results[1]["params"],
                             "rank0 vs relaunched rank1"):
            ok = False
        ev = {}
        for r in results:
            for k, v in (r.get("events") or {}).items():
                ev[k] = ev.get(k, 0) + v
        print("chaos: membership events across workers: %s" % (ev or {}))
        if not results[1].get("rejoined"):
            print("FAIL: the relaunched worker never re-entered the "
                  "membership as a rejoin")
            ok = False
        if not ev.get("elastic.membership_change"):
            print("FAIL: no worker ever observed a membership change")
            ok = False
    print("chaos: %s" % ("PASS" if ok else "FAIL"))
    return 0 if ok else 1


def scenario_mesh(args):
    """SIGKILL one worker of a dp×tp elastic-mesh run mid-epoch: the
    server evicts it (MXNET_KV_EVICT_SEC), the survivor's barrier raises
    MembershipChanged, and the survivor must shrink the mesh to the
    surviving device budget, recover EVERY shard from the newest sharded
    boundary checkpoint, and finish.  PASS when (1) the survivor
    resharded (dp=4xtp=2 → dp=2xtp=2 here) with zero unrecovered
    shards, (2) the checkpoint dir leaks no orphan shard files, and (3)
    the survivor's final params are bit-identical to a FRESH reference
    run started at the surviving world size from the same checkpoint
    boundary (the mesh_ref oracle)."""
    n, s = args.num_workers, args.num_servers
    total = 10
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the fake-device lane: 8 CPU "chips" per worker process stand in
    # for the dp=4 x tp=2 mesh
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.pop("MXNET_FAULT_SPEC", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("MXNET_KV_BACKOFF_MS", "5")
    # a SIGKILLed worker never leaves gracefully: the server must EVICT
    # it from a stalled barrier, well before the stall watchdog trips
    env["MXNET_KV_EVICT_SEC"] = "3"
    env["MXNET_KV_STALL_SEC"] = "60"
    env["MESH_TOTAL_STEPS"] = str(total)
    env["MESH_STEP_DELAY"] = "0.4"  # SIGKILL lands mid-epoch
    env["MESH_SHAPE"] = "4,2"
    env["DMLC_NDEV"] = "4"  # each worker reports 4 of the 8 chips

    ok = True
    with tempfile.TemporaryDirectory(prefix="chaos-mesh-") as out_dir:
        servers, spawn_worker = _spawn_cluster(out_dir, n, s, env,
                                               worker_mode="mesh")
        workers = {wid: spawn_worker(wid) for wid in range(n)}
        try:
            # kill only after real progress (per-step heartbeat), never
            # during startup compiles
            hb = os.path.join(out_dir, "progress_rank1")
            deadline = time.monotonic() + 180
            while time.monotonic() < deadline:
                try:
                    with open(hb) as f:
                        if int(f.read() or 0) >= 2:
                            break
                except (OSError, ValueError):
                    pass
                if workers[1].poll() is not None:
                    break
                time.sleep(0.1)
            victim = workers[1]
            if victim.poll() is not None:
                print("FAIL: worker 1 finished before the kill — "
                      "scenario did not test anything")
                return 1
            print("chaos-mesh: SIGKILL worker 1 (pid %d) mid-epoch — "
                  "its 4 chips hold irreplaceable tp shards"
                  % victim.pid)
            victim.kill()
            victim.wait(timeout=30)
            rc = workers[0].wait(timeout=300)
            if rc != 0:
                print("FAIL: surviving worker exited %d" % rc)
                return 1
            with open(os.path.join(out_dir, "worker0.json")) as f:
                survivor = json.load(f)
        finally:
            for w in workers.values():
                if w.poll() is None:
                    w.kill()
            for p in servers:
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
            for p in servers:
                try:
                    p.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    p.kill()

        print("chaos-mesh: survivor %s -> %s, resumed at step %s, "
              "devices live %s" % (survivor.get("mesh_before"),
                                   survivor.get("mesh_after"),
                                   survivor.get("resume_step"),
                                   survivor.get("devices_live")))
        if not survivor.get("resharded"):
            print("FAIL: the survivor never resharded — the eviction "
                  "was not observed")
            ok = False
        if survivor.get("unrecovered_shards", -1) != 0:
            print("FAIL: %s unrecovered shard(s) after resharding"
                  % survivor.get("unrecovered_shards"))
            ok = False
        if survivor.get("mesh_after") == survivor.get("mesh_before"):
            print("FAIL: mesh did not shrink (%s)"
                  % survivor.get("mesh_after"))
            ok = False

        # zero leaked shards: every shard file in the survivor's
        # checkpoint dir belongs to a manifest-complete step, and no
        # half-written temp files remain
        import re as _re
        ckpt = os.path.join(out_dir, "ckpt_rank0")
        shard_re = _re.compile(r"^step_(\d+)\.shard_\d+\.npz$")
        leaked = []
        for fn in sorted(os.listdir(ckpt)):
            if ".tmp" in fn:
                leaked.append(fn)
                continue
            m = shard_re.match(fn)
            if m and not os.path.exists(os.path.join(
                    ckpt, "step_%s.manifest.json" % m.group(1))):
                leaked.append(fn)
        if leaked:
            print("FAIL: %d leaked shard file(s): %s"
                  % (len(leaked), leaked[:6]))
            ok = False
        else:
            print("chaos-mesh: zero leaked shards in %d checkpoint "
                  "file(s)" % len(os.listdir(ckpt)))

        if not ok:
            print("chaos: FAIL")
            return 1

        # bit-identity oracle: a FRESH run at the surviving world size,
        # from the same checkpoint boundary, must land bit-identical
        print("chaos-mesh: reference run at %s from step %s"
              % (survivor["mesh_after"], survivor["resume_step"]))
        ref_env = dict(env)
        ref_env["MESH_REF_CKPT"] = ckpt
        ref_env["MESH_REF_START"] = str(survivor["resume_step"])
        ref_env["MESH_SHAPE"] = ",".join(
            str(x) for x in survivor["mesh_shape_after"])
        r = subprocess.run(
            [sys.executable, WORKER, out_dir, "mesh_ref"],
            cwd=REPO, env=ref_env, timeout=300)
        if r.returncode != 0:
            print("FAIL: reference run exited %d" % r.returncode)
            ok = False
        else:
            with open(os.path.join(out_dir, "mesh_ref.json")) as f:
                ref = json.load(f)
            if _params_equal(survivor["params"], ref["params"],
                             "survivor vs fresh-start reference"):
                print("chaos-mesh: survivor is bit-identical to a "
                      "fresh run at the surviving world size")
            else:
                ok = False
    print("chaos: %s" % ("PASS" if ok else "FAIL"))
    return 0 if ok else 1


def scenario_fleet(args):
    """SIGKILL one of N serving replicas at sustained load, then roll a
    new model version out — the full production-failover drill (see the
    module docstring for the PASS conditions)."""
    import threading

    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as onp

    from mxnet_tpu import profiler, serving

    n = max(2, args.num_workers)  # replicas (reuses the -n flag)
    clients = 4
    steady_s, kill_s, rollout_min_s = 4.0, 8.0, 2.0
    item = onp.ones((1, 8), dtype="float32")

    spec = {"models": [{"name": "m",
                        "builder": "mxnet_tpu.serving.replica:demo_affine",
                        "kwargs": {"scale": 2.0, "slow_ms": 2.0},
                        "item_shape": [8], "max_batch_size": 8,
                        "warmup": False}],
            "flush_ms": 2.0, "max_queue_depth": 512}
    fleet = serving.ServingFleet(
        spec, replicas=n,
        router_kwargs={"probe_ms": 50},
        supervisor_kwargs={"restart_backoff_ms": 100})
    print("chaos-fleet: starting %d replicas" % n)
    fleet.start()
    ok = True
    samples = []          # (t_done, latency_s, ok, expected_scale_ok)
    samples_lock = threading.Lock()
    stop = threading.Event()
    expect_scale = [2.0]  # flips to {2,3} during rollout, 3 after

    def load_client(cid):
        cli = serving.ServingClient(*fleet.address, timeout=30, retries=0)
        while not stop.is_set():
            # judge against the expectation at request START: a request
            # in flight while the rollout completes may legally serve
            # either version
            exp = expect_scale[0]
            t0 = time.monotonic()
            good = True
            try:
                out = cli.predict("m", item)
                ratio = float(out[0][0])  # input is ones: out == scale
                if ratio not in (2.0, 3.0) or \
                        (exp == 3.0 and ratio != 3.0):
                    good = False
                    print("chaos-fleet: WRONG result %r (expected %r)"
                          % (ratio, exp))
            except Exception as e:
                good = False
                print("chaos-fleet: request FAILED: %r" % (e,))
            with samples_lock:
                samples.append((time.monotonic(), time.monotonic() - t0,
                                good))
        cli.close()

    threads = [threading.Thread(target=load_client, args=(c,),
                                daemon=True) for c in range(clients)]
    try:
        for t in threads:
            t.start()
        time.sleep(steady_s)
        t_kill = time.monotonic()
        victim = fleet.supervisor.kill(1, signal.SIGKILL)
        print("chaos-fleet: SIGKILL replica %s (pid was on port %d) "
              "mid-traffic" % (victim.rid, victim.port))
        # sustained load while the router ejects + fails over and the
        # supervisor restarts the replica
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and \
                fleet.supervisor.ready_count() < n:
            time.sleep(0.2)
        restored = fleet.supervisor.ready_count()
        time.sleep(max(0.0, kill_s - (time.monotonic() - t_kill)))
        t_recovered = time.monotonic()

        # rolling rollout DURING traffic: drain-one-at-a-time + canary
        expect_scale[0] = 0.0  # mixed versions are legal mid-rollout
        report = fleet.rollout(
            {"name": "m",
             "builder": "mxnet_tpu.serving.replica:demo_affine",
             "kwargs": {"scale": 3.0, "slow_ms": 2.0},
             "item_shape": [8], "max_batch_size": 8, "warmup": False},
            canary_probes=6)
        expect_scale[0] = 3.0
        time.sleep(rollout_min_s)  # post-rollout traffic on the new v
        stop.set()
        for t in threads:
            t.join(30)

        with samples_lock:
            all_s = list(samples)
        failed = [s for s in all_s if not s[2]]
        steady = [s[1] for s in all_s if s[0] < t_kill]
        killwin = [s[1] for s in all_s if t_kill <= s[0] < t_recovered]
        p99_steady = float(onp.percentile(steady, 99)) if steady else 0.0
        p99_kill = float(onp.percentile(killwin, 99)) if killwin else 0.0
        print("chaos-fleet: %d requests total, %d failed; steady p99 "
              "%.1f ms, kill-window p99 %.1f ms (%.1fx); replicas "
              "restored: %d/%d; rollout: v%d, canary %s"
              % (len(all_s), len(failed), p99_steady * 1e3,
                 p99_kill * 1e3,
                 (p99_kill / p99_steady) if p99_steady else 0.0,
                 restored, n, report["version"], report["canary"]))
        ev = profiler.aggregate_stats()["events"]
        print("chaos-fleet: events: %s" % {
            k: v for k, v in sorted(ev.items()) if k.startswith("fleet.")})

        if failed:
            print("FAIL: %d request(s) failed — the kill must not cost "
                  "a single idempotent request" % len(failed))
            ok = False
        if not steady or not killwin:
            print("FAIL: load generator produced no samples in a phase "
                  "(steady=%d kill=%d)" % (len(steady), len(killwin)))
            ok = False
        elif p99_kill > 5.0 * max(p99_steady, 0.01):
            print("FAIL: kill-window p99 %.1f ms exceeds 5x steady "
                  "%.1f ms" % (p99_kill * 1e3, p99_steady * 1e3))
            ok = False
        if restored < n:
            print("FAIL: supervisor restored %d/%d replicas" %
                  (restored, n))
            ok = False
        if report["aborted"]:
            print("FAIL: rollout aborted: %s" % report.get("abort_reason"))
            ok = False
        if not ev.get("fleet.replica_restart"):
            print("FAIL: no supervisor restart was recorded — the kill "
                  "tested nothing")
            ok = False
    finally:
        stop.set()
        fleet.stop()
    print("chaos: %s" % ("PASS" if ok else "FAIL"))
    return 0 if ok else 1


def scenario_llm(args):
    """SIGKILL a replica mid-generation under sustained continuous-
    batching decode traffic (sessions pinned by consistent hash), then
    a rolling generate-engine swap with sessions parked.

    PASS conditions (session-migration bar — the fleet page store makes
    sessions survive their replica): (1) sessionless generations NEVER
    fail — they are idempotent and the router fails them over; (2) every
    session-traffic failure is TYPED (the router's explicit
    non-idempotent mid-request error) — never a silent misroute; (3)
    ZERO SessionResetErrors, SIGKILL included — every parked turn's
    transcript was couriered to the page store before the client saw
    its result, so survivors replay instead of resetting; (4) the
    supervisor restores the full replica count and fresh sessions work
    everywhere; (5) a rollout with parked sessions migrates them —
    every one resumes afterwards, zero resets; (6) zero router-level
    failures (FleetUnavailableError) — the fleet always had someone to
    answer."""
    import threading

    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # the drill runs the shipped engine config: async step pipelining
    # ON (ISSUE 17) — the zero-reset bar must hold with launches in
    # flight at every SIGKILL, drain, and rollout point
    os.environ["MXNET_GEN_ASYNC"] = "1"

    from mxnet_tpu import serving
    from mxnet_tpu.serving.errors import (FleetUnavailableError,
                                          SessionResetError)

    n = max(2, args.num_workers)
    clients = 4
    steady_s = 3.0

    spec = {"models": [{"name": "llm",
                        "builder":
                            "mxnet_tpu.models.decoder:decoder_tiny_lm",
                        "kwargs": {"seed": 0},
                        # pool sized so parked sessions never hit the
                        # LRU reclaim during the run: the drill tests
                        # failover resets, not cache-pressure resets
                        # speculation on (n-gram drafter, k=2): the
                        # zero-reset bar must hold with draft/verify/
                        # rollback in the loop — spec output is
                        # bit-identical, so the oracle checks unchanged
                        "generate": {"slots": 4, "page_size": 8,
                                     "prefill_chunk": 8, "max_ctx": 64,
                                     "total_pages": 513,
                                     "speculate": True, "spec_k": 2,
                                     # resolved per replica from the
                                     # supervisor-stamped mesh env:
                                     # replica 0 serves dp=1xtp=2, the
                                     # rest (no env) serve replicated
                                     "sharding": {"from_env": True}}}],
            "max_queue_depth": 512}
    fleet = serving.ServingFleet(
        spec, replicas=n, policy="hash",
        sharding=[{"mesh_shape": [1, 2], "axis_names": ["dp", "tp"],
                   "host_devices": 2}],
        router_kwargs={"probe_ms": 50},
        supervisor_kwargs={"restart_backoff_ms": 100,
                           "startup_timeout_s": 300})
    print("chaos-llm: starting %d LLM replicas (replica 0 "
          "tensor-parallel tp=2; compiling decode programs)" % n)
    fleet.start()
    ok = True
    stop = threading.Event()
    counters = {"ok": 0, "reset": 0, "typed_midflight": 0, "ctx_full": 0,
                "router": 0, "other": 0}
    lock = threading.Lock()

    def bump(key):
        with lock:
            counters[key] += 1

    def load_client(cid):
        """Sustained decode traffic: sessionless generations (idempotent
        — must never fail) interleaved with create+resume session
        pairs (typed failures allowed only while the owner is dead)."""
        cli = serving.ServingClient(*fleet.address, timeout=60, retries=0)
        i = 0
        epoch = [0, 0, 0, 0]
        while not stop.is_set():
            i += 1
            # a bounded rotating session set: real clients re-use
            # conversations, and start a fresh one when the context
            # window fills (the typed BadRequest is that signal)
            slot = i % 4
            sid = "c%d-%d-e%d" % (cid, slot, epoch[slot])
            try:
                if i % 3:  # sessionless: failover makes these lossless
                    cli.generate("llm", [cid + 1, 2, 3], max_tokens=6)
                else:
                    cli.generate("llm", [cid + 1, 2, 3], max_tokens=4,
                                 session=sid)
                    cli.generate("llm", [5], max_tokens=4, session=sid,
                                 resume=True)
                bump("ok")
            except serving.BadRequestError as e:
                if "max_ctx" in str(e):  # conversation full: rotate
                    epoch[slot] += 1
                    bump("ctx_full")
                else:
                    bump("other")
                    print("chaos-llm: UNTYPED failure: %r" % (e,))
            except SessionResetError:
                bump("reset")
            except FleetUnavailableError:
                bump("router")
                print("chaos-llm: ROUTER-LEVEL failure (must be zero)")
            except serving.ServingError as e:
                if "non-idempotent" in str(e):
                    bump("typed_midflight")
                else:
                    bump("other")
                    print("chaos-llm: UNTYPED failure: %r" % (e,))
            except Exception as e:
                bump("other")
                print("chaos-llm: UNTYPED failure: %r" % (e,))
        cli.close()

    threads = [threading.Thread(target=load_client, args=(c,),
                                daemon=True) for c in range(clients)]

    def _gen_stats(port):
        import http.client as _http
        import json as _json
        try:
            c = _http.HTTPConnection("127.0.0.1", port, timeout=10)
            c.request("GET", "/v1/stats")
            doc = _json.loads(c.getresponse().read())
            c.close()
            return doc.get("generators", {}).get("llm", {})
        except Exception:
            return {}

    tp_ok = True
    try:
        # the TP replica must actually BE tensor-parallel (a silent
        # fallback to replicated would pass every traffic check below
        # without exercising the sharded path at all)
        r0 = fleet.supervisor.replicas[0]
        shd = _gen_stats(r0.port).get("sharding") or {}
        if shd.get("tp") != 2:
            print("chaos-llm: FAIL replica 0 not tensor-parallel: %r"
                  % (shd,))
            tp_ok = False
        else:
            print("chaos-llm: replica 0 serving %s, decode collectives "
                  "%r" % (shd.get("mesh"), shd.get("collectives")))
        # park a known set of sessions BEFORE the kill: the victim's
        # share must come back as typed SessionResetError on resume
        warm_cli = serving.ServingClient(*fleet.address, timeout=60)
        warm = ["warm-%d" % i for i in range(3 * n)]
        for sid in warm:
            warm_cli.generate("llm", [1, 2, 3], max_tokens=3, session=sid)

        for t in threads:
            t.start()
        time.sleep(steady_s)
        # kill a replica that actually HOLDS warm sessions, so the
        # typed-reset path is provably exercised
        import http.client as _http
        import json as _json

        def _session_count(port):
            try:
                c = _http.HTTPConnection("127.0.0.1", port, timeout=10)
                c.request("GET", "/v1/stats")
                doc = _json.loads(c.getresponse().read())
                c.close()
                return (doc.get("generators", {}).get("llm", {})
                        .get("sessions", 0))
            except Exception:
                return 0

        counts = [_session_count(r.port)
                  for r in fleet.supervisor.replicas]
        victim_idx = max(range(n), key=lambda i: counts[i])
        victim = fleet.supervisor.kill(victim_idx, signal.SIGKILL)
        print("chaos-llm: SIGKILL replica %s (held %d sessions) "
              "mid-generation" % (victim.rid, counts[victim_idx]))
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and \
                fleet.supervisor.ready_count() < n:
            time.sleep(0.2)
        restored = fleet.supervisor.ready_count()
        # let the router's probe loop re-admit the restarted replica so
        # the consistent-hash ring is stable again before session checks
        settle = time.monotonic() + 30
        while time.monotonic() < settle:
            states = fleet.router.states()
            if all(s["state"] == "healthy" and s["ready"]
                   for s in states.values()):
                break
            time.sleep(0.2)
        time.sleep(1.0)
        stop.set()
        for t in threads:
            t.join(60)

        # resume every pre-kill session: survivors continue, the
        # victim's sessions fail typed — and ONLY typed
        resumed, resets, untyped = 0, 0, 0
        for sid in warm:
            for attempt in (0, 1):
                try:
                    warm_cli.generate("llm", [7], max_tokens=3,
                                      session=sid, resume=True)
                    resumed += 1
                except SessionResetError:
                    resets += 1
                except serving.ServingError as e:
                    # a typed mid-flight loss is the protocol answer for
                    # an ambiguous non-idempotent failure; one re-resume
                    # resolves it (reset or continue)
                    if "non-idempotent" in str(e) and attempt == 0:
                        continue
                    untyped += 1
                    print("chaos-llm: UNTYPED warm-resume failure: %r"
                          % (e,))
                except Exception as e:
                    untyped += 1
                    print("chaos-llm: UNTYPED warm-resume failure: %r"
                          % (e,))
                break
        # fresh sessions after recovery must work everywhere
        fresh_fail = 0
        for i in range(2 * n):
            for attempt in (0, 1):
                try:
                    sid = "fresh-%d-%d" % (i, attempt)
                    warm_cli.generate("llm", [1, 2], max_tokens=3,
                                      session=sid)
                    warm_cli.generate("llm", [4], max_tokens=3,
                                      session=sid, resume=True)
                except SessionResetError:
                    # ring-remap race while a replica's readiness
                    # settles: the protocol answer is restart — one
                    # retry must succeed on a stable ring
                    if attempt == 0:
                        continue
                    fresh_fail += 1
                    print("chaos-llm: fresh session FAILED after retry")
                except Exception as e:
                    fresh_fail += 1
                    print("chaos-llm: fresh session FAILED: %r" % (e,))
                break

        # rollout-during-sessions drill: park sessions, roll the
        # generate engine across every replica, resume them all — the
        # rollout must MIGRATE parked sessions, never reset them
        roll = ["roll-%d" % i for i in range(2 * n)]
        for sid in roll:
            warm_cli.generate("llm", [2, 4, 6], max_tokens=3,
                              session=sid)
        rollout_fail, roll_resets, roll_ok = 0, 0, 0
        try:
            rep = fleet.rollout(dict(spec["models"][0]))
            migrated = sum(r.get("migrated_sessions", 0)
                           for r in rep["replicas"])
            print("chaos-llm: rollout migrated %d parked session(s)"
                  % migrated)
        except Exception as e:
            rollout_fail = 1
            print("chaos-llm: rollout FAILED: %r" % (e,))
        for sid in roll:
            for attempt in (0, 1):
                try:
                    warm_cli.generate("llm", [8], max_tokens=3,
                                      session=sid, resume=True)
                    roll_ok += 1
                except SessionResetError:
                    roll_resets += 1
                    print("chaos-llm: session %s RESET by rollout" % sid)
                except serving.ServingError as e:
                    if attempt == 0:  # readiness settle: one retry
                        continue
                    roll_resets += 1
                    print("chaos-llm: post-rollout resume failed: %r"
                          % (e,))
                except Exception as e:
                    roll_resets += 1
                    print("chaos-llm: post-rollout resume failed: %r"
                          % (e,))
                break
        warm_cli.close()

        print("chaos-llm: load %s; warm resumes: %d ok, %d reset, %d "
              "untyped; fresh failures: %d; replicas restored %d/%d; "
              "rollout resumes: %d ok, %d reset"
              % (counters, resumed, resets, untyped, fresh_fail,
                 restored, n, roll_ok, roll_resets))
        if counters["router"]:
            print("FAIL: %d router-level failure(s)" % counters["router"])
            ok = False
        if counters["other"] or untyped:
            print("FAIL: untyped failures under session traffic")
            ok = False
        if restored < n:
            print("FAIL: supervisor restored %d/%d replicas"
                  % (restored, n))
            ok = False
        if fresh_fail:
            print("FAIL: %d fresh session(s) failed after recovery"
                  % fresh_fail)
            ok = False
        if resets or counters["reset"]:
            print("FAIL: %d session reset(s) — with the page store, "
                  "SIGKILL must lose ZERO sessions (transcripts are "
                  "couriered at every park)"
                  % (resets + counters["reset"]))
            ok = False
        if resumed < len(warm):
            print("FAIL: only %d/%d warm sessions resumed after the "
                  "kill" % (resumed, len(warm)))
            ok = False
        if rollout_fail:
            print("FAIL: rollout raised")
            ok = False
        if roll_resets:
            print("FAIL: %d session(s) reset by the rollout — it must "
                  "migrate parked sessions, not reset them"
                  % roll_resets)
            ok = False
        if not counters["ok"]:
            print("FAIL: load generator completed no requests")
            ok = False
        if not tp_ok:
            print("FAIL: the fleet's TP replica did not serve "
                  "tensor-parallel")
            ok = False
    finally:
        stop.set()
        fleet.stop()
    print("chaos: %s" % ("PASS" if ok else "FAIL"))
    return 0 if ok else 1


def scenario_store(args):
    """SIGKILL the page store ITSELF — the process every migration,
    drain, and rollout routes through.

    Phase A (durability): a single store process with a WAL dir takes
    records at several generations (including a take, which advances a
    fence), dies by SIGKILL -9, and restarts on the same dir.  PASS:
    every record is served byte-identical, and a stale-generation put
    from a pre-crash holder still bounces — the fences were recovered,
    not just the payloads.

    Phase B (replication): a ServingFleet with a 3-member replicated
    store (subprocesses under the supervisor) serves sustained session
    traffic; the store PRIMARY is SIGKILLed mid-autoscale-drain and
    again mid-rollout.  PASS: zero ``SessionResetError``s anywhere,
    every warm session resumes with a transcript bit-identical to the
    greedy full-forward oracle, the store fails over both times
    (epoch-fenced promotion), and the killed member is healed back in.
    """
    import socket as _socket
    import threading

    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from mxnet_tpu.kvstore.pagestore import PageStoreClient, _ask

    ok = True

    def _wait_store(addr, timeout=60.0):
        deadline = time.monotonic() + timeout
        while True:
            try:
                return _ask(addr, {"op": "stats"}, timeout=1.0)
            except (OSError, RuntimeError):
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.1)

    # -- phase A: kill -9 + restart of one durable, unreplicated store --
    print("chaos-store: phase A — WAL durability across SIGKILL")
    s = _socket.socket()
    s.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    with tempfile.TemporaryDirectory(prefix="chaos-store-") as wal_dir:
        argv = [sys.executable, "-m", "mxnet_tpu.kvstore.pagestore",
                "--host", "127.0.0.1", "--port", str(port),
                "--dir", wal_dir, "--role", "primary"]
        proc = subprocess.Popen(argv, env=env)
        addr = "127.0.0.1:%d" % port
        _wait_store(addr)
        cli = PageStoreClient.from_addr(addr)
        blob = bytes(range(256)) * 17
        assert cli.put("llm/pages", {"kind": "pages", "blob": blob},
                       gen=3)
        assert cli.put("llm/tr", {"kind": "transcript",
                                  "history": [5, 9, 2], "pending": 7},
                       gen=1)
        assert cli.put("llm/fence", {"kind": "transcript",
                                     "history": [1]}, gen=4)
        rec, claimed = cli.take("llm/fence")  # fence advances to 5
        assert claimed == 5, claimed
        cli.close()
        proc.send_signal(signal.SIGKILL)
        proc.wait(30)
        print("chaos-store: store SIGKILLed (rc=%s); restarting on the "
              "same WAL dir" % proc.returncode)
        proc = subprocess.Popen(argv, env=env)
        try:
            _wait_store(addr)
            cli = PageStoreClient.from_addr(addr)
            rec, gen = cli.take("llm/pages")
            if rec is None or bytes(rec["blob"]) != blob or gen != 4:
                print("FAIL: pages record not recovered byte-identical "
                      "(gen=%s)" % gen)
                ok = False
            rec, gen = cli.take("llm/tr")
            if (rec is None or list(rec["history"]) != [5, 9, 2]
                    or rec["pending"] != 7):
                print("FAIL: transcript record not recovered: %r" % (rec,))
                ok = False
            # the correctness subtlety: the PRE-CRASH holder's late put
            # (stale generation) must still bounce after recovery
            if cli.put("llm/fence", {"kind": "transcript",
                                     "history": [1]}, gen=5):
                print("FAIL: stale-gen put accepted after restart — the "
                      "WAL lost the generation fences")
                ok = False
            elif cli.last_refusal != "stale":
                print("FAIL: expected 'stale' refusal, got %r"
                      % cli.last_refusal)
                ok = False
            if not cli.put("llm/fence", {"kind": "transcript",
                                         "history": [1, 2]}, gen=6):
                print("FAIL: next-gen put refused after restart (%r)"
                      % cli.last_refusal)
                ok = False
            cli.close()
        finally:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(15)
            except subprocess.TimeoutExpired:
                proc.kill()
    print("chaos-store: phase A %s" % ("ok" if ok else "FAILED"))

    # -- phase B: kill the replicated store primary under traffic -------
    import jax.numpy as jnp

    from mxnet_tpu import serving
    from mxnet_tpu.models import decoder
    from mxnet_tpu.serving.errors import (FleetUnavailableError,
                                          SessionResetError)

    n = max(2, args.num_workers)
    spec = {"models": [{"name": "llm",
                        "builder":
                            "mxnet_tpu.models.decoder:decoder_tiny_lm",
                        "kwargs": {"seed": 0},
                        "generate": {"slots": 4, "page_size": 8,
                                     "prefill_chunk": 8, "max_ctx": 64,
                                     "total_pages": 513}}],
            "max_queue_depth": 512}
    fleet = serving.ServingFleet(
        spec, replicas=n, policy="hash",
        router_kwargs={"probe_ms": 50},
        supervisor_kwargs={"restart_backoff_ms": 100,
                           "startup_timeout_s": 300},
        pagestore={"replicas": 3, "processes": True,
                   "probe_interval_s": 0.2, "strikes": 2})
    print("chaos-store: phase B — %d LLM replicas + 3-member "
          "replicated store (compiling decode programs)" % n)
    fleet.start()
    store_addrs = fleet.supervisor.env["MXNET_GEN_PAGESTORE"]
    print("chaos-store: store members %s (primary %s)"
          % (store_addrs, fleet.pagestore.primary))

    stop = threading.Event()
    counters = {"ok": 0, "reset": 0, "typed_midflight": 0, "ctx_full": 0,
                "router": 0, "other": 0}
    lock = threading.Lock()

    def bump(key):
        with lock:
            counters[key] += 1

    def load_client(cid):
        cli = serving.ServingClient(*fleet.address, timeout=60, retries=0)
        i = 0
        epoch = [0, 0, 0, 0]
        while not stop.is_set():
            i += 1
            slot = i % 4
            sid = "c%d-%d-e%d" % (cid, slot, epoch[slot])
            try:
                if i % 3:
                    cli.generate("llm", [cid + 1, 2, 3], max_tokens=6)
                else:
                    cli.generate("llm", [cid + 1, 2, 3], max_tokens=4,
                                 session=sid)
                    cli.generate("llm", [5], max_tokens=4, session=sid,
                                 resume=True)
                bump("ok")
            except serving.BadRequestError as e:
                if "max_ctx" in str(e):
                    epoch[slot] += 1
                    bump("ctx_full")
                else:
                    bump("other")
                    print("chaos-store: UNTYPED failure: %r" % (e,))
            except SessionResetError:
                bump("reset")
                print("chaos-store: session RESET under load "
                      "(must be zero)")
            except FleetUnavailableError:
                bump("router")
                print("chaos-store: ROUTER-LEVEL failure (must be zero)")
            except serving.ServingError as e:
                if "non-idempotent" in str(e):
                    bump("typed_midflight")
                else:
                    bump("other")
                    print("chaos-store: UNTYPED failure: %r" % (e,))
            except Exception as e:
                bump("other")
                print("chaos-store: UNTYPED failure: %r" % (e,))
        cli.close()

    threads = [threading.Thread(target=load_client, args=(c,),
                                daemon=True) for c in range(3)]

    # warm sessions with client-side transcript tracking: hist[sid] is
    # the exact (prompt, output) sequence the greedy oracle must replay
    hist = {}
    tainted = set()

    def warm_turn(cli, sid, prompt, max_tokens):
        for attempt in (0, 1):
            try:
                out = cli.generate("llm", prompt, max_tokens=max_tokens,
                                   session=sid, resume=sid in hist)
                hist.setdefault(sid, []).append(
                    (list(prompt), [int(t) for t in out["tokens"]]))
                return True
            except SessionResetError:
                raise
            except serving.ServingError as e:
                # ambiguous non-idempotent loss: one re-resume resolves
                # it, but the session may have advanced server-side, so
                # exclude it from the bit-identity oracle
                if "non-idempotent" in str(e) and attempt == 0:
                    tainted.add(sid)
                    continue
                print("chaos-store: warm turn on %s FAILED: %r"
                      % (sid, e))
                return False
        return False

    resets, warm_fail = 0, 0
    try:
        warm_cli = serving.ServingClient(*fleet.address, timeout=60)
        warm = ["warm-%d" % i for i in range(3 * n)]
        for sid in warm:
            if not warm_turn(warm_cli, sid, [1, 2, 3], 3):
                warm_fail += 1
        for t in threads:
            t.start()
        time.sleep(2.0)

        # -- kill 1: mid-autoscale-drain ----------------------------
        # drain a session-holding replica (parked sessions push to the
        # store) and SIGKILL the store primary while the drain runs
        import http.client as _http
        import json as _json

        def _session_count(port_):
            try:
                c = _http.HTTPConnection("127.0.0.1", port_, timeout=10)
                c.request("GET", "/v1/stats")
                doc = _json.loads(c.getresponse().read())
                c.close()
                return (doc.get("generators", {}).get("llm", {})
                        .get("sessions", 0))
            except Exception:
                return 0

        counts = [_session_count(r.port)
                  for r in fleet.supervisor.replicas]
        victim = fleet.supervisor.replicas[
            max(range(n), key=lambda i: counts[i])]
        drained = []

        def _drain():
            drained.append(fleet._autoscale_down(victim.addr))

        dr = threading.Thread(target=_drain, daemon=True)
        dr.start()
        time.sleep(0.05)
        killed = fleet.pagestore.kill_primary()
        print("chaos-store: SIGKILL store primary %s mid-drain of "
              "replica %s (%d sessions held)"
              % (killed, victim.rid, counts[
                  fleet.supervisor.replicas.index(victim)]))
        dr.join(120)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and \
                fleet.pagestore.failovers_total < 1:
            time.sleep(0.2)
        print("chaos-store: drain migrated %s session(s); store "
              "failovers=%d, new primary %s"
              % (drained, fleet.pagestore.failovers_total,
                 fleet.pagestore.primary))
        if fleet.pagestore.failovers_total < 1:
            print("FAIL: store never failed over after the kill")
            ok = False
        # every warm session must resume — the drained replica's were
        # parked in the store ACROSS the primary kill
        for sid in warm:
            try:
                if not warm_turn(warm_cli, sid, [7], 3):
                    warm_fail += 1
            except SessionResetError:
                resets += 1

        # -- kill 2: mid-rollout ------------------------------------
        # wait for the restarted member to heal back in first, so the
        # second failover has a follower to promote
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and \
                fleet.pagestore.rejoins < 1:
            time.sleep(0.2)
        if fleet.pagestore.rejoins < 1:
            print("FAIL: killed store member never healed back in")
            ok = False
        roll_err = []

        def _roll():
            try:
                fleet.rollout(dict(spec["models"][0]))
            except Exception as e:
                roll_err.append(e)

        rt = threading.Thread(target=_roll, daemon=True)
        rt.start()
        time.sleep(0.5)
        killed = fleet.pagestore.kill_primary()
        print("chaos-store: SIGKILL store primary %s mid-rollout"
              % killed)
        rt.join(300)
        if roll_err:
            print("FAIL: rollout raised across the store kill: %r"
                  % (roll_err[0],))
            ok = False
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and \
                fleet.pagestore.failovers_total < 2:
            time.sleep(0.2)
        if fleet.pagestore.failovers_total < 2:
            print("FAIL: store did not fail over a second time")
            ok = False
        stop.set()
        for t in threads:
            t.join(60)
        for sid in warm:
            try:
                if not warm_turn(warm_cli, sid, [9], 3):
                    warm_fail += 1
            except SessionResetError:
                resets += 1
        warm_cli.close()

        # -- greedy-oracle bit-identity over the whole run ----------
        lm = decoder.decoder_tiny_lm(seed=0)
        params, cfg = lm.jax_params(), lm.config
        mismatches = 0
        for sid in warm:
            if sid in tainted:
                continue
            toks = []
            for prompt, out in hist.get(sid, []):
                toks += prompt
                for want in out:
                    logits = decoder.full_forward(
                        params, cfg, jnp.asarray([toks], jnp.int32))
                    got = int(jnp.argmax(logits[0, -1]))
                    if got != want:
                        mismatches += 1
                        print("chaos-store: session %s DIVERGED from "
                              "the greedy oracle (%d != %d)"
                              % (sid, want, got))
                        break
                    toks.append(got)
                else:
                    continue
                break
        summary = fleet.pagestore.stats_summary()
        print("chaos-store: load %s; warm failures: %d; resets: %d; "
              "oracle: %d/%d sessions bit-identical (%d ambiguous "
              "excluded); store %s"
              % (counters, warm_fail, resets,
                 len(warm) - len(tainted) - mismatches,
                 len(warm) - len(tainted), len(tainted), summary))
        if counters["reset"] or resets:
            print("FAIL: %d session reset(s) — killing the store must "
                  "lose ZERO sessions (WAL + replication + failover)"
                  % (counters["reset"] + resets))
            ok = False
        if counters["router"] or counters["other"]:
            print("FAIL: router-level or untyped failures under load")
            ok = False
        if warm_fail:
            print("FAIL: %d warm turn(s) failed outright" % warm_fail)
            ok = False
        if mismatches:
            print("FAIL: warm sessions diverged from the greedy oracle")
            ok = False
        if not counters["ok"]:
            print("FAIL: load generator completed no requests")
            ok = False
    finally:
        stop.set()
        fleet.stop()
    print("chaos: %s" % ("PASS" if ok else "FAIL"))
    return 0 if ok else 1


def scenario_ramp(args):
    """10x diurnal traffic ramp against an autoscaling fleet: two tiers
    (latency | bulk), three tenants (pro=4, free=1, batch), one replica
    at dawn, a chip budget of 3.

    PASS conditions (the fleet-autoscaling + SLO-admission bar):
    (1) the autoscaler spawns replicas as the ramp crosses the up band
        (>= 1 scale_up, peak live replicas > 1) and NEVER exceeds the
        chip budget; after the drop it drains back down (>= 1
        scale_down, final live < peak) — and a drain MIGRATES parked
        sessions, so (2) ZERO SessionResetErrors anywhere: every
        session parked at dawn resumes after the full ramp/drop cycle;
    (3) the degradation ladder holds: bulk requests are shed at least
        as often as latency requests, every shed is TYPED (503
        queue_full / deadline_infeasible) and carries a Retry-After;
    (4) latency-tier p99 during the scaled-up hold stays <= 5x the
        steady-state p99;
    (5) every decision is auditable after the fact: /v1/stats carries
        the autoscale counters + decision ring."""
    import threading

    import numpy as onp

    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["MXNET_GEN_ASYNC"] = "1"
    os.environ["MXNET_SLO_TENANT_WEIGHTS"] = "free=1,pro=4"
    # the replica cold-start cut needs nothing here: every replica's
    # entry point turns on the persistent compile cache, so a scaled-up
    # replica re-serves from cache instead of cold XLA compiles

    from mxnet_tpu import serving
    from mxnet_tpu.serving.errors import (DeadlineInfeasibleError,
                                          QueueFullError,
                                          SessionResetError)

    budget = 3
    spec = {"models": [{
        "name": "llm",
        "builder": "mxnet_tpu.models.decoder:decoder_tiny_lm",
        "kwargs": {"seed": 0},
        # a small engine queue so the 10x peak actually exercises the
        # shed ladder while the fleet is still scaling up
        "generate": {"slots": 4, "page_size": 8, "prefill_chunk": 8,
                     "max_ctx": 64, "total_pages": 513,
                     "max_queue_depth": 8}}]}
    fleet = serving.ServingFleet(
        spec, replicas=1, policy="hash",
        router_kwargs={"probe_ms": 50},
        supervisor_kwargs={"restart_backoff_ms": 100,
                           "startup_timeout_s": 300},
        autoscale={"chip_budget": budget, "min_replicas": 1,
                   "up_queue": 1.5, "down_queue": 0.25,
                   "up_kv": 0.85, "down_kv": 0.5,
                   "cooldown_s": 2.0, "interval_ms": 250.0,
                   "ema_alpha": 0.5})
    print("chaos-ramp: starting 1 replica under a chip budget of %d "
          "(compiling decode programs)" % (budget,))
    fleet.start()
    ok = True
    stop = threading.Event()
    peak_on = threading.Event()  # gates the 9 extra ramp clients
    phase = {"name": "warmup"}
    lock = threading.Lock()
    counters = {"ok": 0, "reset": 0, "shed_latency": 0, "shed_bulk": 0,
                "infeasible": 0, "shed_untagged": 0, "other": 0}
    samples = {"steady": [], "hold": []}

    def bump(key):
        with lock:
            counters[key] += 1

    def load_client(cid, tier, tenant, ramp_only):
        cli = serving.ServingClient(*fleet.address, timeout=120,
                                    retries=0)
        i = 0
        epoch = [0, 0, 0]  # rotating session slots (llm-drill idiom)
        while not stop.is_set():
            if ramp_only and not peak_on.is_set():
                peak_on.wait(0.2)
                continue
            i += 1
            sid = None
            if tier == "latency" and i % 5 == 0:
                slot = (i // 5) % 3
                sid = "s%d-%d-e%d" % (cid, slot, epoch[slot])
            t0 = time.monotonic()
            try:
                cli.generate("llm", [cid % 96 + 1, 2, 3], max_tokens=4,
                             tier=tier, tenant=tenant, session=sid,
                             resume=False,
                             deadline_ms=60000 if tier == "bulk"
                             else None)
                dt = time.monotonic() - t0
                bump("ok")
                if tier == "latency":
                    with lock:
                        ph = phase["name"]
                        if ph in samples:
                            samples[ph].append(dt)
            except serving.BadRequestError as e:
                if sid is not None and "max_ctx" in str(e):
                    epoch[(i // 5) % 3] += 1  # conversation full: rotate
                else:
                    bump("other")
                    print("chaos-ramp: UNTYPED failure: %r" % (e,))
            except QueueFullError as e:
                bump("shed_%s" % tier)
                ra = getattr(e, "retry_after", None)
                if ra is None:
                    bump("shed_untagged")
                stop.wait(min(float(ra or 0.2), 2.0))  # honor it
            except DeadlineInfeasibleError as e:
                bump("infeasible")
                stop.wait(min(float(
                    getattr(e, "retry_after", None) or 0.2), 2.0))
            except SessionResetError:
                bump("reset")
                print("chaos-ramp: session RESET (must be zero)")
            except Exception as e:
                bump("other")
                print("chaos-ramp: UNTYPED failure: %r" % (e,))
        cli.close()

    # dawn traffic (~1x): 1 latency client + 1 bulk client.  Peak
    # (~10x): +9 latency (pro/free mix) and +3 bulk (batch tenant).
    plan = [(0, "latency", "pro", False), (1, "bulk", "batch", False)]
    plan += [(10 + i, "latency", "pro" if i % 2 else "free", True)
             for i in range(9)]
    plan += [(30 + i, "bulk", "batch", True) for i in range(3)]
    threads = [threading.Thread(target=load_client, args=p, daemon=True)
               for p in plan]

    live_seen = {"max": 0}

    def monitor():
        while not stop.is_set():
            snap = fleet.autoscaler.snapshot()
            live = (snap["signals"]["live"] or 0)
            if live > live_seen["max"]:
                live_seen["max"] = live
            stop.wait(0.25)

    mon = threading.Thread(target=monitor, daemon=True)

    def _router_stats():
        import http.client as _http
        c = _http.HTTPConnection(*fleet.address, timeout=10)
        c.request("GET", "/v1/stats")
        doc = json.loads(c.getresponse().read())
        c.close()
        return doc

    try:
        # park sessions at dawn: they must survive the whole cycle
        warm_cli = serving.ServingClient(*fleet.address, timeout=120)
        warm = ["warm-%d" % i for i in range(6)]
        for sid in warm:
            warm_cli.generate("llm", [1, 2, 3], max_tokens=3,
                              session=sid)
        for t in threads:
            t.start()
        mon.start()
        time.sleep(3.0)  # warmup: everything compiled and flowing
        with lock:
            phase["name"] = "steady"
        steady_s = 8.0
        time.sleep(steady_s)
        with lock:
            phase["name"] = "ramp"
        print("chaos-ramp: steady done (%d latency samples); ramping "
              "traffic 10x" % len(samples["steady"]))
        peak_on.set()
        # the fleet must scale OUT under the ramp; wait for it, then
        # measure the scaled-up hold window
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if fleet.autoscaler.counters["scale_up"] >= 1 \
                    and fleet.autoscaler.snapshot()["signals"]["live"] > 1:
                break
            time.sleep(0.25)
        with lock:
            phase["name"] = "hold"
        hold_s = 12.0
        time.sleep(hold_s)
        snap = fleet.autoscaler.snapshot()
        print("chaos-ramp: hold done at live=%s (%d hold samples); "
              "dropping traffic" % (snap["signals"]["live"],
                                    len(samples["hold"])))
        with lock:
            phase["name"] = "drop"
        peak_on.clear()  # ramp clients idle again; dawn traffic stays
        stop_extra = time.monotonic() + 90
        while time.monotonic() < stop_extra:
            if fleet.autoscaler.counters["scale_down"] >= 1:
                break
            time.sleep(0.25)
        time.sleep(1.0)
        stop.set()
        peak_on.set()  # release ramp clients parked on the gate
        for t in threads:
            t.join(120)
        mon.join(5)

        # dawn's parked sessions resume after the full cycle — the
        # drains MIGRATED them, nothing was reset
        resumed, resets = 0, 0
        for sid in warm:
            try:
                warm_cli.generate("llm", [7], max_tokens=3, session=sid,
                                  resume=True)
                resumed += 1
            except SessionResetError:
                resets += 1
                print("chaos-ramp: warm session %s RESET" % sid)
            except Exception as e:
                print("chaos-ramp: warm resume failed: %r" % (e,))
        warm_cli.close()

        doc = _router_stats()
        audit = doc.get("autoscale") or {}
        acts = audit.get("counters") or {}
        final_live = (audit.get("signals") or {}).get("live") or 0
        p99s = (onp.percentile(samples["steady"], 99)
                if samples["steady"] else 0.0)
        p99h = (onp.percentile(samples["hold"], 99)
                if samples["hold"] else 0.0)
        print("chaos-ramp: load %s; autoscale %s; live peak=%d "
              "final=%d; latency p99 steady=%.3fs hold=%.3fs"
              % (counters, acts, live_seen["max"], final_live,
                 p99s, p99h))
        for d in (audit.get("decisions") or [])[-8:]:
            print("chaos-ramp: decision %s" % d)

        if acts.get("scale_up", 0) < 1 or live_seen["max"] < 2:
            print("FAIL: the ramp never scaled out (scale_up=%s, "
                  "peak live=%d)" % (acts.get("scale_up"),
                                     live_seen["max"]))
            ok = False
        if live_seen["max"] > budget:
            print("FAIL: %d live replicas exceeded the chip budget %d"
                  % (live_seen["max"], budget))
            ok = False
        if acts.get("scale_down", 0) < 1 or final_live >= live_seen["max"]:
            print("FAIL: the drop never scaled in (scale_down=%s, "
                  "final live=%d, peak=%d)"
                  % (acts.get("scale_down"), final_live,
                     live_seen["max"]))
            ok = False
        if counters["reset"] or resets:
            print("FAIL: %d session reset(s) — drains must migrate, "
                  "never reset" % (counters["reset"] + resets))
            ok = False
        if resumed < len(warm):
            print("FAIL: only %d/%d dawn sessions resumed after the "
                  "cycle" % (resumed, len(warm)))
            ok = False
        if counters["shed_latency"] > counters["shed_bulk"]:
            print("FAIL: latency tier shed more than bulk (%d > %d) — "
                  "the ladder sheds bulk first"
                  % (counters["shed_latency"], counters["shed_bulk"]))
            ok = False
        if counters["shed_untagged"]:
            print("FAIL: %d shed(s) carried no Retry-After"
                  % counters["shed_untagged"])
            ok = False
        if counters["other"]:
            print("FAIL: %d untyped failure(s)" % counters["other"])
            ok = False
        if not (audit.get("decisions") or []):
            print("FAIL: no auditable decisions at /v1/stats")
            ok = False
        if samples["steady"] and samples["hold"] \
                and p99h > 5.0 * max(p99s, 0.5):
            # the 0.5s floor absorbs scheduler noise when the steady
            # p99 is a few milliseconds on an idle CPU host
            print("FAIL: hold p99 %.3fs > 5x steady p99 %.3fs"
                  % (p99h, p99s))
            ok = False
        if not counters["ok"]:
            print("FAIL: load generator completed no requests")
            ok = False
    finally:
        stop.set()
        peak_on.set()
        fleet.stop()
    print("chaos: %s" % ("PASS" if ok else "FAIL"))
    return 0 if ok else 1


def main():
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("-n", "--num-workers", type=int, default=2)
    ap.add_argument("-s", "--num-servers", type=int, default=1)
    ap.add_argument("--scenario", default="faults",
                    choices=["faults", "preempt", "mesh", "fleet", "llm",
                             "ramp", "store"],
                    help="faults = transport chaos (bit-identical check);"
                         " preempt = SIGTERM + relaunch + rejoin drill;"
                         " mesh = SIGKILL a worker holding irreplaceable"
                         " dp×tp shards; survivors shrink the mesh and"
                         " recover from the sharded boundary checkpoint;"
                         " fleet = SIGKILL a serving replica under load"
                         " + rolling rollout (-n = replica count);"
                         " llm = SIGKILL a replica under sustained"
                         " continuous-batching decode traffic (typed"
                         " session resets, lossless sessionless traffic);"
                         " ramp = 10x diurnal traffic ramp against the"
                         " autoscaler (scale out/in under a chip budget,"
                         " bulk shed first, zero session resets);"
                         " store = SIGKILL the page store itself (WAL"
                         " recovery, then replicated failover mid-drain"
                         " and mid-rollout, zero session resets)")
    ap.add_argument("--spec", default=DEFAULT_SPEC,
                    help="MXNET_FAULT_SPEC for the chaos run "
                         "(default: %(default)s)")
    ap.add_argument("--no-compare-clean", action="store_true",
                    help="skip the fault-free baseline run")
    args = ap.parse_args()
    if args.scenario == "preempt":
        return scenario_preempt(args)
    if args.scenario == "mesh":
        return scenario_mesh(args)
    if args.scenario == "fleet":
        return scenario_fleet(args)
    if args.scenario == "llm":
        return scenario_llm(args)
    if args.scenario == "ramp":
        return scenario_ramp(args)
    if args.scenario == "store":
        return scenario_store(args)

    ok = True
    with tempfile.TemporaryDirectory(prefix="chaos-") as tmp:
        fault_dir = os.path.join(tmp, "faulty")
        os.makedirs(fault_dir)
        print("chaos: faulty run (spec=%r, %d workers, %d servers)"
              % (args.spec, args.num_workers, args.num_servers))
        faulty = _run(fault_dir, args.num_workers, args.num_servers,
                      spec=args.spec)

        trips = {}
        for r in faulty:
            for site, n in (r.get("fault_trips") or {}).items():
                trips[site] = trips.get(site, 0) + n
        print("chaos: fault trips across workers: %s" % (trips or "NONE"))
        if not trips:
            print("FAIL: the fault spec never tripped — nothing was "
                  "actually tested")
            ok = False

        for r in faulty[1:]:
            if not _params_equal(faulty[0]["params"], r["params"],
                                 "replica rank0 vs rank%d" % r["rank"]):
                ok = False

        if not args.no_compare_clean:
            clean_dir = os.path.join(tmp, "clean")
            os.makedirs(clean_dir)
            print("chaos: clean baseline run")
            clean = _run(clean_dir, args.num_workers, args.num_servers)
            if _params_equal(clean[0]["params"], faulty[0]["params"],
                             "clean vs faulty"):
                print("chaos: faulty run is bit-identical to the clean "
                      "run")
            else:
                ok = False

    print("chaos: %s" % ("PASS" if ok else "FAIL"))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
