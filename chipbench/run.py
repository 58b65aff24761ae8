#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, in one process, on the chip.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration and a traffic mix; both are data files found
by name (``configs/<config>.json``, ``traffic/<mix>.json``), and so is every
per-layer metric (``metrics/<metric>.json``).  The configuration's ``kind``
names the module of this directory that builds the system, warms it up,
checks it against the plain reference and runs the measured window
(``serve``, ``train``).  The last line of standard output is the result;
everything before it is for the reader.  Without a TPU, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result.

``--override '<json>'`` merges values into the configuration (``"config"``),
the traffic mix (``"traffic"``), allows the CPU (``"allow_cpu"``) for a
rehearsal, or has the kind read controls beside the program (``"controls"``:
a list of their names).  Such a run is not a run of the cell: it says so
first and its result carries ``"correct": false``.
"""
import time

T_START = time.perf_counter()     # set-up is counted from here

import argparse
import importlib
import json
import math
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if __name__ == "__main__":
    # the checkout's root in place of this directory: the program is imported
    # from there and this directory as the package ``chipbench`` (its
    # trace.py must not shadow the standard library's)
    sys.path[0:1] = [ROOT]


def log(msg=""):
    print(msg, flush=True)


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def merge(base, over):
    """``over``'s values into ``base``, nested groups key by key."""
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            merge(base[k], v)
        else:
            base[k] = v
    return base


def resolve(spec, default_module=None):
    """``"module:function"`` -> the function.  A bare module name is a module
    of this directory; a dotted one is the program's."""
    mod, _, fn = spec.rpartition(":")
    mod = mod or default_module
    return getattr(importlib.import_module(
        mod if "." in mod else "chipbench." + mod), fn)


class Clock:
    """The run's bookkeeping around the measured window: the split of set-up
    time, what JAX's persistent cache answered before the window, and what
    JAX traced inside it (every program is traced to a jaxpr first, whether
    the executable then comes from the cache or the compiler), which must be
    nothing."""

    def __init__(self, t_start):
        import jax
        self.t_start = self.t_lap = t_start
        self.split, self.traced = [], []
        self.hits = self.misses = 0
        self.setup_s = self.cache_at_open = self.mark = None
        self.in_window = []
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, _secs, fun_name=None, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.traced.append(fun_name)

    def lap(self, name):
        """Set-up time since the last lap goes under ``name``."""
        now = time.perf_counter()
        self.split.append((name, now - self.t_lap))
        self.t_lap = now

    def open_window(self):
        """Set-up ends here; returns its seconds since process start."""
        self.cache_at_open = (self.hits, self.misses)
        self.mark = len(self.traced)
        self.setup_s = time.perf_counter() - self.t_start
        return self.setup_s

    def close_window(self):
        self.in_window = self.traced[self.mark:]


def device_gate(chips, allow_cpu):
    import jax
    devs = jax.devices()
    d = devs[0]
    log("device: platform=%s kind=%r count=%d jax=%s"
        % (d.platform, d.device_kind, len(devs), jax.__version__))
    if d.platform != "tpu" and not allow_cpu:
        sys.exit("no TPU: jax.devices()[0].platform is %r" % d.platform)
    if len(devs) < chips:
        sys.exit("the cell asks for %d chip(s), JAX finds %d"
                 % (chips, len(devs)))
    return devs


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--override", default="")
    args = ap.parse_args()

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        sys.exit("no workload %r in BENCHMARK.json (have: %s)"
                 % (args.workload, ", ".join(sorted(cells))))
    cell = cells[args.workload]
    config = load("configs", cell["config"] + ".json")
    traffic = load("traffic", cell["traffic"] + ".json")
    override = json.loads(args.override) if args.override else {}
    if override:
        log("OVERRIDE %s: this is not a run of the cell"
            % json.dumps(override, sort_keys=True))
        merge(config, override.get("config", {}))
        merge(traffic, override.get("traffic", {}))

    # the compile cache: the directory the environment names, else a fixed
    # one inside the checkout; every program is kept, however small (PR 21:
    # otherwise the small ones compile again on every run).  The program's
    # own runtime.enable_compile_cache() defers to the same variable.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    log("compile cache: %s" % os.environ["JAX_COMPILATION_CACHE_DIR"])

    import jax
    clock = Clock(T_START)
    devs = device_gate(cell["chips"], bool(override.get("allow_cpu")))
    kind = devs[0].device_kind
    peaks = load("peaks.json")["chips"].get(kind)
    if peaks is None and not override.get("allow_cpu"):
        sys.exit("device_kind %r is not in chipbench/peaks.json" % kind)
    log("cell %s: config %s x traffic %s on %d chip(s), seed %d, %.0f s, "
        "trace %d" % (cell["name"], cell["config"], cell["traffic"],
                      cell["chips"], args.seed, args.seconds, args.trace))

    ctx = {
        "seed": args.seed, "seconds": args.seconds,
        "trace": bool(args.trace), "chips": cell["chips"],
        "devices": devs[:cell["chips"]], "config": config,
        "traffic": traffic, "clock": clock, "log": log,
        "resolve": resolve, "trace_dir": None,
        "controls": tuple(override.get("controls", ())),
    }
    with tempfile.TemporaryDirectory(prefix="chipbench_trace_") as tdir:
        if args.trace:
            ctx["trace_dir"] = tdir
        facts = resolve(config["kind"] + ":run")(ctx)
        if args.trace:
            from chipbench import trace as reduction
            t0 = time.perf_counter()
            facts["trace"] = reduction.reduce(tdir, cell["chips"])
            log("trace: window %.3f s busy %.3f s (idle %.2f%%), read in "
                "%.1f s" % (facts["trace"]["window_s"],
                            facts["trace"]["busy_s"],
                            100 * (1 - facts["trace"]["busy_s"]
                                   / facts["trace"]["window_s"]),
                            time.perf_counter() - t0))

    facts.update(config=config, traffic=traffic, chips=cell["chips"],
                 peaks=peaks)
    stats = [d.memory_stats() or {} for d in ctx["devices"]]
    fullest = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))
    # the allocator's peak leaves out what a running program takes beside
    # its arguments; a kind that knows it gives the bytes held in the window
    facts["memory"] = dict(fullest, peak_bytes=max(
        fullest.get("peak_bytes_in_use", 0), facts.get("window_bytes", 0)))
    log("memory of the fullest chip: %s" % json.dumps(facts["memory"],
                                                       sort_keys=True))
    # each number compared beside its limit; what a kind compares once the
    # window has closed runs here, after the memory peak was read, and
    # outside both set-up and window
    checks = list(facts.get("checks", []))
    if "after_window" in facts:
        t0 = time.perf_counter()
        checks += facts.pop("after_window")()
        log("the reference over the window's answers took %.1f s"
            % (time.perf_counter() - t0))
    facts["end_to_end"]["setup_s"] = clock.setup_s
    log("set-up %.3f s: %s" % (clock.setup_s, ", ".join(
        "%s %.2f" % kv for kv in clock.split)))
    log("compile cache at window open: hits=%d misses=%d"
        % clock.cache_at_open)
    in_window = clock.in_window
    log("compilations inside the window: %d (must be 0)%s"
        % (len(in_window), " " + ", ".join(map(str, in_window))
           if in_window else ""))

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in bench[group]:
        if args.workload not in m.get("workloads", [args.workload]):
            continue
        if args.trace:
            spec = load("metrics", m["name"] + ".json")
            value = resolve(spec["reader"], "readers")(
                facts, **spec.get("args", {}))
        else:
            value = facts["end_to_end"].get(m["name"])
        if value is not None:       # nothing to read: left out of the line
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": int(facts["memory"]["peak_bytes"])}
    checks.append({"name": "compilations_in_window", "value": len(in_window),
                   "limit": 0})
    # a number that is not finite is no JSON: it goes out as 1e30
    checks = {c["name"]: {"value": float(c["value"])
                          if math.isfinite(c["value"]) else 1e30,
                          "limit": c["limit"]} for c in checks}
    held = all(c["value"] <= c["limit"] for c in checks.values())
    result = {
        "correct": bool(facts.get("correct", True) and held
                        and facts["attempted"] > 0 and not override),
        "attempted": int(facts["attempted"]), "failed": int(facts["failed"]),
        "metrics": metrics, "device": device,
    }
    if args.trace:
        device["busy_s"] = facts["trace"]["busy_s"]
        device["window_s"] = facts["trace"]["window_s"]
        result["breakdown"] = {"device_ops": facts["trace"]["device_ops"],
                               "idle_gaps": facts["trace"]["idle_gaps"]}
    if "readings" in facts:         # what a kind printed beside its checks
        result["readings"] = facts["readings"]
    result["checks"] = checks       # last in the line, and last on stderr
    log(json.dumps(result))
    for name, c in checks.items():
        print("check %s: %.6g, limit %.6g -> %s"
              % (name, c["value"], c["limit"],
                 "held" if c["value"] <= c["limit"] else "NOT HELD"),
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
