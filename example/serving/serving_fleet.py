"""Serve a replicated fleet with mxnet_tpu.serving.fleet: supervised
replica processes + health-routing frontend + zero-downtime rollout.

What this demonstrates (the fleet half of tests/test_fleet.py, as a
runnable deployment shape):

1. launch N supervised replica processes from one model spec (models
   named by importable builder path; the supervisor health-gates them on
   /readyz, auto-restarts crashes, and the persistent compile cache
   makes every boot after the first warm);
2. put the ``Router`` in front — clients talk to ONE address and can't
   tell the fleet from a single server;
3. SIGKILL a replica mid-traffic: requests keep succeeding (router
   failover), the supervisor restores the replica, the router re-admits
   it;
4. roll out model v2 with ``fleet.rollout`` — drain one replica at a
   time, warm-before-flip, canary gate — while traffic keeps flowing;
5. scrape the fleet stats: per-replica dispatch/eject/retry counters +
   fleet p50/p95/p99.

Run::

    python example/serving/serving_fleet.py            # 3 replicas
    python example/serving/serving_fleet.py --smoke    # CI: 2 replicas
"""
import argparse
import signal
import threading
import time

import numpy as onp

from mxnet_tpu import serving


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fewer replicas / requests (CI lane)")
    ap.add_argument("--replicas", type=int, default=None)
    ap.add_argument("--clients", type=int, default=None)
    ap.add_argument("--seconds", type=float, default=None,
                    help="sustained-load duration per phase")
    args = ap.parse_args()

    replicas = args.replicas or (2 if args.smoke else 3)
    clients = args.clients or (2 if args.smoke else 6)
    phase_s = args.seconds or (1.5 if args.smoke else 6.0)
    in_units = 16

    spec = {"models": [{"name": "dense",
                        "builder": "mxnet_tpu.serving.replica:demo_dense",
                        "kwargs": {"units": 4, "in_units": in_units,
                                   "seed": 0},
                        "item_shape": [in_units], "max_batch_size": 8}],
            "flush_ms": 5.0, "max_queue_depth": 256}

    fleet = serving.ServingFleet(
        spec, replicas=replicas,
        router_kwargs={"probe_ms": 100},
        supervisor_kwargs={"restart_backoff_ms": 100})
    t0 = time.perf_counter()
    fleet.start()
    host, port = fleet.address
    print("fleet of %d replicas up in %.1fs, router on http://%s:%d "
          "(replicas: %s)" % (replicas, time.perf_counter() - t0, host,
                              port, fleet.supervisor.addresses()))

    stop = threading.Event()
    counts = {"ok": 0, "fail": 0}
    lock = threading.Lock()

    def client_loop(cid):
        rng = onp.random.RandomState(cid)
        cli = serving.ServingClient(host, port, timeout=60, retries=0)
        while not stop.is_set():
            try:
                x = rng.rand(1, in_units).astype("float32")
                preds = cli.predict("dense", x)
                assert preds.shape == (1, 4)
                with lock:
                    counts["ok"] += 1
            except Exception as e:
                with lock:
                    counts["fail"] += 1
                print("request failed: %r" % (e,))
        cli.close()

    threads = [threading.Thread(target=client_loop, args=(c,),
                                daemon=True) for c in range(clients)]
    for t in threads:
        t.start()
    try:
        time.sleep(phase_s)
        victim = fleet.supervisor.kill(1, signal.SIGKILL)
        print("SIGKILL replica %s mid-traffic..." % victim.rid)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and \
                fleet.supervisor.ready_count() < replicas:
            time.sleep(0.2)
        print("supervisor restored %d/%d replicas"
              % (fleet.supervisor.ready_count(), replicas))

        print("rolling out v2 (scale changes) during traffic...")
        report = fleet.rollout(
            {"name": "dense",
             "builder": "mxnet_tpu.serving.replica:demo_dense",
             "kwargs": {"units": 4, "in_units": in_units, "seed": 1},
             "item_shape": [in_units], "max_batch_size": 8},
            canary_probes=4)
        print("rollout: v%d on %d replicas, canary error rate %s"
              % (report["version"], len(report["replicas"]),
                 report["canary"]["error_rate"]))
        time.sleep(phase_s)
    finally:
        stop.set()
        for t in threads:
            t.join(30)

    snap = fleet.router.snapshot()
    print("traffic: %d ok, %d failed; fleet p50/p95/p99 ms: %s / %s / %s"
          % (counts["ok"], counts["fail"],
             snap["latency"].get("p50_ms"), snap["latency"].get("p95_ms"),
             snap["latency"].get("p99_ms")))
    for rid, st in sorted(snap["replicas"].items()):
        c = st["counters"]
        print("  replica %s: %s, dispatched %d, retries %d, "
              "ejections %d, readmissions %d"
              % (rid, st["state"], c["dispatched"], c["retries"],
                 c["ejections"], c["readmissions"]))
    fleet.stop()
    if counts["fail"]:
        raise SystemExit("%d request(s) failed" % counts["fail"])
    print("fleet done")


if __name__ == "__main__":
    main()
