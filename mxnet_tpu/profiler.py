"""Profiler (parity: python/mxnet/profiler.py + src/profiler/ chrome-trace).

TPU-native: host-side scoped events (Task/Frame/Marker) are recorded to a
chrome://tracing JSON like the reference's Profiler; device-side profiling
delegates to the XLA/PJRT profiler (jax.profiler xplane traces), the moral
equivalent of the reference's NVTX/VTune bridges.

:func:`span` is the one way the program itself writes a host span: it lands
in the xplane's ``/host:CPU`` plane, on the same clock as the device's
operations, whenever a ``jax.profiler`` session records (``set_config(
xplane_dir=...)`` + ``start()``, or anyone's ``jax.profiler.start_trace``),
and costs about half a microsecond when none does.  Task / Frame / Event
open one too.
"""
from __future__ import annotations

import json
import os
import threading
import time

from jax.profiler import TraceAnnotation as _TraceAnnotation

_STATE = {
    "config": {"filename": "profile.json", "profile_all": False},
    "running": False,
    "events": [],
    "lock": threading.Lock(),
    "device_dir": None,
}

# Per-op aggregate statistics (reference src/profiler/aggregate_stats.cc +
# MXAggregateProfileStatsPrint, src/c_api/c_api_profile.cc:284).  Enabled
# by set_config(aggregate_stats=True); ndarray.apply_op feeds it.
_AGG = {
    "enabled": False,
    "ops": {},      # name -> [count, total_s, min_s, max_s]
    "memory": {},   # counter name -> [samples, last, peak]
    "events": {},   # name -> count (always on: fault trips, kv retries)
    "comm": {},     # name -> [buckets, bytes, total_queue_s, max_queue_s]
    "fleet": {},    # name -> [count, total_s, max_s] (router dispatches)
    "lock": threading.Lock(),
}


def span(name, **args):
    """A host span named ``name`` in the JAX profiler's trace, as a context
    manager; ``args`` become the event's statistics (``rid=7``).  What is
    known only at the end goes in through ``set_metadata`` of the object
    the ``with`` binds::

        with profiler.span("engine.admit") as sp:
            sp.set_metadata(admitted=self._admit())
    """
    return _TraceAnnotation(name, **args)


def record_op_stat(name, dur_s):
    """Accumulate one op dispatch into the aggregate table (hot path:
    callers check _AGG['enabled'] first)."""
    with _AGG["lock"]:
        st = _AGG["ops"].get(name)
        if st is None:
            _AGG["ops"][name] = [1, dur_s, dur_s, dur_s]
        else:
            st[0] += 1
            st[1] += dur_s
            if dur_s < st[2]:
                st[2] = dur_s
            if dur_s > st[3]:
                st[3] = dur_s


def record_counter(name, **values):
    """Public counter hook for subsystems (membership generation, mesh
    size, fault trips): emits one chrome-trace counter sample when a
    trace is recording, else is a no-op."""
    if _STATE["running"]:
        _emit(name, "counter", "C", time.time(), dict(values))


def record_event_stat(name, n=1):
    """Count a discrete event (fault-injection trip, kvstore retry,
    checkpoint fallback).  Unlike op stats these are not gated on
    aggregate_stats=True — they are rare and operators need them after
    the fact; read back via aggregate_stats()['events']."""
    with _AGG["lock"]:
        _AGG["events"][name] = _AGG["events"].get(name, 0) + n


def record_comm_stat(name, nbytes=0, queue_s=0.0, n=1):
    """Accumulate one gradient-communication launch (a fused bucket
    pushpull, kvstore/bucketing.py).  Always on, like event stats — the
    per-step bucket count / bytes / queue→launch latency are the
    observables the overlap design is validated against
    (tests/test_bucketing.py asserts on them).  Read back via
    aggregate_stats()['comm']."""
    with _AGG["lock"]:
        st = _AGG["comm"].get(name)
        if st is None:
            _AGG["comm"][name] = [n, nbytes, queue_s, queue_s]
        else:
            st[0] += n
            st[1] += nbytes
            st[2] += queue_s
            if queue_s > st[3]:
                st[3] = queue_s


def record_fleet_stat(name, dur_s=0.0, n=1):
    """Accumulate one serving-fleet router event (a dispatch, a failover
    retry, a shed) with its router-side latency.  Always on, like comm
    stats — the per-replica dispatch/retry/eject counters are the
    observables the failover design is validated against (tools/chaos.py
    --scenario fleet asserts on them).  Read back via
    aggregate_stats()['fleet']."""
    with _AGG["lock"]:
        st = _AGG["fleet"].get(name)
        if st is None:
            _AGG["fleet"][name] = [n, dur_s, dur_s]
        else:
            st[0] += n
            st[1] += dur_s
            if dur_s > st[2]:
                st[2] = dur_s


def record_memory_stat(name, value):
    with _AGG["lock"]:
        st = _AGG["memory"].get(name)
        if st is None:
            _AGG["memory"][name] = [1, value, value]
        else:
            st[0] += 1
            st[1] = value
            if value > st[2]:
                st[2] = value


def aggregate_stats():
    """Snapshot: {'ops': {name: {count,total_ms,min_ms,max_ms,avg_ms}},
    'memory': {name: {samples,last_bytes,peak_bytes}}}."""
    with _AGG["lock"]:
        ops = {n: {"count": c, "total_ms": t * 1e3, "min_ms": lo * 1e3,
                   "max_ms": hi * 1e3, "avg_ms": t / c * 1e3}
               for n, (c, t, lo, hi) in _AGG["ops"].items()}
        mem = {n: {"samples": s, "last_bytes": last, "peak_bytes": peak}
               for n, (s, last, peak) in _AGG["memory"].items()}
        events = dict(_AGG["events"])
        comm = {n: {"count": c, "bytes": b,
                    "queue_total_ms": tq * 1e3, "queue_max_ms": mq * 1e3,
                    "queue_avg_ms": tq / c * 1e3 if c else 0.0}
                for n, (c, b, tq, mq) in _AGG["comm"].items()}
        fleet = {n: {"count": c, "total_ms": t * 1e3, "max_ms": mx * 1e3,
                     "avg_ms": t / c * 1e3 if c else 0.0}
                 for n, (c, t, mx) in _AGG["fleet"].items()}
    return {"ops": ops, "memory": mem, "events": events, "comm": comm,
            "fleet": fleet}


def reset_stats():
    with _AGG["lock"]:
        _AGG["ops"].clear()
        _AGG["memory"].clear()
        _AGG["events"].clear()
        _AGG["comm"].clear()
        _AGG["fleet"].clear()


def get_summary(sort_by="total", ascending=False):
    """Printable per-op-name summary table (the
    MXAggregateProfileStatsPrint analog)."""
    key = {"total": "total_ms", "count": "count", "avg": "avg_ms",
           "min": "min_ms", "max": "max_ms"}.get(sort_by, "total_ms")
    snap = aggregate_stats()
    lines = ["Profile Statistics:",
             "  Operator summary (host dispatch)",
             "  %-28s %10s %12s %12s %12s %12s" % (
                 "Name", "Calls", "Total(ms)", "Min(ms)", "Max(ms)",
                 "Avg(ms)")]
    rows = sorted(snap["ops"].items(), key=lambda kv: kv[1][key],
                  reverse=not ascending)
    for name, st in rows:
        lines.append("  %-28s %10d %12.4f %12.4f %12.4f %12.4f" % (
            name[:28], st["count"], st["total_ms"], st["min_ms"],
            st["max_ms"], st["avg_ms"]))
    if snap["memory"]:
        lines.append("  Memory counters")
        lines.append("  %-28s %10s %14s %14s" % (
            "Name", "Samples", "Last(bytes)", "Peak(bytes)"))
        for name, st in sorted(snap["memory"].items()):
            lines.append("  %-28s %10d %14d %14d" % (
                name[:28], st["samples"], st["last_bytes"],
                st["peak_bytes"]))
    if snap["events"]:
        lines.append("  Event counters")
        lines.append("  %-28s %10s" % ("Name", "Count"))
        for name, count in sorted(snap["events"].items()):
            lines.append("  %-28s %10d" % (name[:28], count))
    if snap["comm"]:
        lines.append("  Gradient communication (fused buckets)")
        lines.append("  %-28s %10s %14s %12s %12s" % (
            "Name", "Buckets", "Bytes", "QAvg(ms)", "QMax(ms)"))
        for name, st in sorted(snap["comm"].items()):
            lines.append("  %-28s %10d %14d %12.4f %12.4f" % (
                name[:28], st["count"], st["bytes"], st["queue_avg_ms"],
                st["queue_max_ms"]))
    if snap["fleet"]:
        lines.append("  Serving fleet (router)")
        lines.append("  %-28s %10s %12s %12s %12s" % (
            "Name", "Count", "Total(ms)", "Avg(ms)", "Max(ms)"))
        for name, st in sorted(snap["fleet"].items()):
            lines.append("  %-28s %10d %12.4f %12.4f %12.4f" % (
                name[:28], st["count"], st["total_ms"], st["avg_ms"],
                st["max_ms"]))
    return "\n".join(lines)


def set_config(**kwargs):
    """profiler.set_config(filename=..., profile_all=..., ...)"""
    _STATE["config"].update(kwargs)


def set_state(state="stop", profile_process="worker"):
    if state == "run":
        start()
    else:
        stop()


def start(profile_process="worker"):
    _STATE["running"] = True
    _STATE["start_ts"] = time.time()
    _AGG["enabled"] = bool(_STATE["config"].get("aggregate_stats", False))
    dev_dir = _STATE["config"].get("xplane_dir")
    if dev_dir:
        import jax
        jax.profiler.start_trace(dev_dir)
        _STATE["device_dir"] = dev_dir


def stop(profile_process="worker"):
    _STATE["running"] = False
    _AGG["enabled"] = False  # stats stay readable until reset_stats()
    if _STATE["device_dir"]:
        import jax
        jax.profiler.stop_trace()
        _STATE["device_dir"] = None


def _emit(name, cat, ph, ts, args=None):
    with _STATE["lock"]:
        _STATE["events"].append({
            "name": name, "cat": cat, "ph": ph, "pid": os.getpid(),
            "tid": threading.get_ident(), "ts": ts * 1e6,
            "args": args or {},
        })


def dump(finished=True, profile_process="worker"):
    fname = _STATE["config"].get("filename", "profile.json")
    with _STATE["lock"]:
        events = list(_STATE["events"])
    with open(fname, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return fname


def dumps(reset=False, format="json"):
    """format='json' → chrome-trace events; format='table' → the per-op
    aggregate summary (reference profiler.dumps(format='table') →
    MXAggregateProfileStatsPrint)."""
    if format == "table":
        s = get_summary()
        if reset:
            reset_stats()
        return s
    with _STATE["lock"]:
        s = json.dumps({"traceEvents": _STATE["events"]})
        if reset:
            _STATE["events"].clear()
    return s


def pause(profile_process="worker"):
    _STATE["running"] = False


def resume(profile_process="worker"):
    _STATE["running"] = True


class _Scoped:
    _cat = "event"

    def __init__(self, name):
        self.name = name
        self._t0 = None
        self._span = None

    def start(self):
        self._t0 = time.time()
        self._span = span(self.name)
        self._span.__enter__()
        if _STATE["running"]:
            _emit(self.name, self._cat, "B", self._t0)

    def stop(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        if _STATE["running"]:
            _emit(self.name, self._cat, "E", time.time())

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


class Task(_Scoped):
    _cat = "task"

    def __init__(self, name, domain=None):
        super().__init__(name)


class Frame(_Scoped):
    _cat = "frame"

    def __init__(self, name, domain=None):
        super().__init__(name)


class Event(_Scoped):
    _cat = "event"


class Counter:
    def __init__(self, name, domain=None, value=0):
        self.name = name
        self.value = value

    def set_value(self, value):
        self.value = value
        if _STATE["running"]:
            _emit(self.name, "counter", "C", time.time(),
                  {"value": self.value})

    def increment(self, delta=1):
        self.set_value(self.value + delta)

    def decrement(self, delta=1):
        self.set_value(self.value - delta)


class Marker:
    def __init__(self, name, domain=None):
        self.name = name

    def mark(self, scope="process"):
        if _STATE["running"]:
            _emit(self.name, "marker", "i", time.time())


def scope(name="<unk>:"):
    return Task(name)


# ---------------------------------------------------------------------------
# device-memory (HBM) observability
#
# Parity: reference `src/profiler/storage_profiler.h:131` (per-device
# memory aggregates surfaced through `c_api_profile.cc:197`).  Re-based on
# PJRT: the plugin's allocator stats when it exposes them, else a
# client-side census of live jax.Arrays (the CPU backend returns None
# from memory_stats(), so the census is the path there).
# ---------------------------------------------------------------------------
_PEAKS = {}  # device -> peak bytes observed by the census

# device_kind prefix -> (HBM bytes, bf16 matmul peak FLOP/s).  Public chip
# specs; override with MXNET_TPU_HBM_BYTES / MXNET_TPU_PEAK_FLOPS when the
# platform reports an unknown kind.
_CHIP_SPECS = (
    ("TPU v5 lite", 16 << 30, 197e12),   # v5e
    ("TPU v5e", 16 << 30, 197e12),
    ("TPU v5p", 95 << 30, 459e12),
    ("TPU v5", 95 << 30, 459e12),
    ("TPU v6", 32 << 30, 918e12),        # Trillium
    ("TPU v4", 32 << 30, 275e12),
    ("TPU v3", 32 << 30, 123e12),
    ("TPU v2", 16 << 30, 46e12),
)


def chip_spec(device=None):
    """{'device_kind', 'hbm_bytes', 'peak_flops_bf16', 'in_table'} for a
    device (None = default device); unknown kinds yield None fields unless
    the MXNET_TPU_* env overrides are set.  ``in_table`` says whether the
    kind is one the table knows, whatever the overrides say: a measurement
    on a device that is not in the table is an error, not a default."""
    import jax
    d = device if device is not None else jax.devices()[0]
    kind = getattr(d, "device_kind", "") or ""
    hbm = peak = None
    in_table = False
    for prefix, h, p in _CHIP_SPECS:
        if kind.startswith(prefix):
            hbm, peak, in_table = h, p, True
            break
    env_hbm = os.environ.get("MXNET_TPU_HBM_BYTES")
    env_peak = os.environ.get("MXNET_TPU_PEAK_FLOPS")
    if env_hbm:
        hbm = int(float(env_hbm))
    if env_peak:
        peak = float(env_peak)
    return {"device_kind": kind, "hbm_bytes": hbm,
            "peak_flops_bf16": peak, "in_table": in_table}


def device_memory_stats(device=None):
    """Per-device memory usage: bytes_in_use / peak_bytes_in_use /
    bytes_limit.

    source='pjrt' when the plugin's allocator stats are available
    (authoritative, includes XLA temp buffers); source='live_arrays' is a
    client-side census of live jax.Array shards on the device — it misses
    in-flight executable temps but tracks the working set and its peak."""
    import jax
    d = device if device is not None else jax.devices()[0]
    stats = None
    try:
        stats = d.memory_stats()
    except Exception:
        stats = None
    spec = chip_spec(d)
    if stats:
        return {"bytes_in_use": int(stats.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
                "bytes_limit": int(stats.get("bytes_limit")
                                   or spec["hbm_bytes"] or 0) or None,
                "num_allocs": stats.get("num_allocs"),
                "source": "pjrt"}
    total = 0
    count = 0
    for a in jax.live_arrays():
        try:
            for sh in a.addressable_shards:
                if sh.device == d:
                    total += sh.data.nbytes
                    count += 1
        except Exception:
            continue  # deleted/donated arrays mid-iteration
    peak = max(_PEAKS.get(d, 0), total)
    _PEAKS[d] = peak
    return {"bytes_in_use": total, "peak_bytes_in_use": peak,
            "bytes_limit": spec["hbm_bytes"], "num_live_buffers": count,
            "source": "live_arrays"}


def sample_device_memory(device=None, name="device_memory"):
    """Record the current device-memory census as a chrome-trace counter
    sample (reference: the storage profiler's per-device counter series)
    and return it."""
    st = device_memory_stats(device)
    if _STATE["running"]:
        _emit(name, "counter", "C", time.time(),
              {"bytes_in_use": st["bytes_in_use"],
               "peak_bytes_in_use": st["peak_bytes_in_use"]})
    if _AGG["enabled"]:
        record_memory_stat(name, st["bytes_in_use"])
    return st
